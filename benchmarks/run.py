"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (for table rows ``us_per_call`` holds
the headline numeric, usually total wire bits) and writes the full structured
results + claim checks to benchmarks/results/paper_repro.json.

Flags:

* ``--claims-only`` — run only the modules that gate paper claims; every
  module does, so this is the default (the flag stays for CI's command).
* ``--tiny`` — forward ``tiny=True`` to every module whose ``run`` accepts
  it (shorter horizons / looser targets for CI smoke).

Any module that *raises* fails the harness exactly like a failed claim: the
exception is recorded as a synthetic failing check and the exit code is
nonzero — a crashed benchmark must never read as green.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time
import traceback


def _modules():
    from . import (adaptive_sweep, bits_sweep, convergence, ef_frontier,
                   fault_frontier, lasg_frontier, lm_frontier,
                   participation_frontier, serve_frontier, table2_gradient,
                   table3_stochastic)
    return [("table2", table2_gradient), ("table3", table3_stochastic),
            ("convergence", convergence), ("bits_sweep", bits_sweep),
            ("adaptive_sweep", adaptive_sweep),
            ("lasg_frontier", lasg_frontier),
            ("participation_frontier", participation_frontier),
            ("ef_frontier", ef_frontier),
            ("fault_frontier", fault_frontier),
            ("lm_frontier", lm_frontier),
            ("serve_frontier", serve_frontier)]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--claims-only", action="store_true",
                    help="only modules that gate paper claims (all do)")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: forward tiny=True where supported")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    t0 = time.time()
    out_rows, results = [], {}
    all_checks = {}

    for name, mod in _modules():
        t = time.time()
        kwargs = {}
        if args.tiny and "tiny" in inspect.signature(mod.run).parameters:
            kwargs["tiny"] = True
        try:
            checks = mod.run(out_rows, results, **kwargs)
        except Exception:
            traceback.print_exc()
            checks = {"raised no exception": False}
        all_checks.update({f"{name}: {k}": v for k, v in checks.items()})
        print(f"# {name} done in {time.time()-t:.1f}s", file=sys.stderr)

    print("name,us_per_call,derived")
    for name, val, derived in out_rows:
        print(f"{name},{val},{derived}")

    os.makedirs(os.path.join(os.path.dirname(__file__), "results"), exist_ok=True)
    path = os.path.join(os.path.dirname(__file__), "results", "paper_repro.json")
    with open(path, "w") as f:
        json.dump(results, f, indent=1)

    print("\n# paper-claim validation", file=sys.stderr)
    failed = skipped = 0
    for k, v in all_checks.items():
        tag = "SKIP" if v is None else "PASS" if v else "FAIL"
        print(f"#  [{tag}] {k}", file=sys.stderr)
        failed += v is not None and not v
        skipped += v is None
    print(f"# {len(all_checks)-failed-skipped}/{len(all_checks)} claims hold "
          f"({skipped} skipped, {time.time()-t0:.1f}s total) -> {path}",
          file=sys.stderr)
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
