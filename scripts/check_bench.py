#!/usr/bin/env python
"""Benchmark-artifact gate: schema-validate every BENCH_*.json at the repo
root (the per-PR artifacts CI uploads — BENCH_ef.json from the EF
frontier, BENCH_faults.json from the fault frontier, BENCH_lm.json from
the LM frontier, BENCH_serve.json from the serving frontier).  The REQUIRED set makes a
*missing* artifact fail too: a benchmark that silently stopped writing its
file must not read as green.

Every artifact must be a JSON object with

* ``rows``   — a non-empty list of flat row objects (scalar/str/None
  values only: the artifacts diff cleanly and plot without unpickling);
* ``checks`` — a dict of check-name -> true / false / null (null = the
  check was skipped in this variant, e.g. a --tiny run).

A ``false`` check is also a failure here: a committed artifact recording a
failing claim must fail the gate, not ride along silently.

Exit code 0 iff every artifact validates.

    python scripts/check_bench.py
"""
from __future__ import annotations

import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCALARS = (int, float, str, bool, type(None))
REQUIRED = ("BENCH_ef.json", "BENCH_faults.json", "BENCH_lm.json",
            "BENCH_serve.json")


def validate(path: pathlib.Path) -> list[str]:
    errors = []
    try:
        doc = json.loads(path.read_text())
    except ValueError as e:
        return [f"{path.name}: not valid JSON ({e})"]
    if not isinstance(doc, dict):
        return [f"{path.name}: top level must be an object"]

    rows = doc.get("rows")
    if not isinstance(rows, list) or not rows:
        errors.append(f"{path.name}: 'rows' must be a non-empty list")
    else:
        for i, row in enumerate(rows):
            if not isinstance(row, dict):
                errors.append(f"{path.name}: rows[{i}] is not an object")
            elif bad := [k for k, v in row.items()
                         if not isinstance(v, SCALARS)]:
                errors.append(f"{path.name}: rows[{i}] has non-scalar "
                              f"fields {bad}")

    checks = doc.get("checks")
    if not isinstance(checks, dict) or not checks:
        errors.append(f"{path.name}: 'checks' must be a non-empty object")
    else:
        for name, v in checks.items():
            if not (v is None or isinstance(v, bool)):
                errors.append(f"{path.name}: checks[{name!r}] must be "
                              f"true/false/null, got {v!r}")
            elif v is False:
                errors.append(f"{path.name}: checks[{name!r}] is false — "
                              f"artifact records a failing claim")
    return errors


def main() -> int:
    paths = sorted(ROOT.glob("BENCH_*.json"))
    if not paths:
        print("check_bench: no BENCH_*.json artifacts at repo root",
              file=sys.stderr)
        return 1
    errors = [e for p in paths for e in validate(p)]
    names = {p.name for p in paths}
    errors += [f"required artifact {r} is missing"
               for r in REQUIRED if r not in names]
    for e in errors:
        print(f"check_bench: {e}", file=sys.stderr)
    print(f"check_bench: {len(paths)} artifact(s), "
          f"{'FAIL' if errors else 'OK'}")
    return 1 if errors else 0


if __name__ == "__main__":
    raise SystemExit(main())
