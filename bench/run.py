"""Run one cell of the benchmark once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, and prints no result, where JAX finds no TPU or fewer chips
than the cell asks for.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones), ``device``,
with a trace ``breakdown``, and last ``checks``: each number the check
compared beside its limit.  The same numbers end standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

# JAX's persistent compilation cache, inside the checkout at a fixed path,
# given to the program before JAX is imported (the program takes the
# directory this variable names)
CACHE = pathlib.Path(__file__).resolve().parents[1] / ".jax_cache"
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    import harness
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START,
                             log=lambda m: print(m, flush=True))
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        sys.exit(3)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
