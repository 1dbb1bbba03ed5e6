"""Readings that set a cell's limits, on the chip.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 [--control-seeds 1,2,3]

For each seed: the program's readings (the timed path's compiled step from
the seed, through the rounds the check follows) against the reference; and
for each control seed, the control (the reference in float8) and the fault
"half of the batch left out" (planted in the reference put in the program's
place), each against the reference.  One JSON line per reading, with the
verdict of ``correct.judge`` against the cell's committed limits (a sound
run reads ``"correct": true``, the control and the fault ``false``);
nothing is timed.  Limits go between the largest sound reading and the
smallest control or fault reading, as ``PERF.md`` sets out.
"""
import argparse
import copy
import gc
import json

import jax

import correct
import harness
import reference
import traffic


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = [int(s) for s in args.control_seeds.split(",") if s]

    c = harness.cell(args.workload)
    harness.check_chips(c["chips"])
    config, W = c["config"], c["chips"]
    mix = copy.deepcopy(c["mix"])
    rounds = harness.checked_rounds(mix)
    mix["pool_rounds"] = rounds
    prog = harness._program()
    prog["use_compile_cache"]()
    built = harness.build(prog, config, mix, W)
    compiled = None

    def emit(kind, seed, got, ref):
        r = correct.readings(got, ref)
        ok, _ = correct.judge(r, c["limits"])
        print(json.dumps({"kind": kind, "seed": seed, "correct": ok, **r,
                          "losses": got["losses"],
                          "reference_losses": ref["losses"]}), flush=True)

    for seed in seeds:
        key = traffic.seed_key(seed)
        state = harness.initial_state(prog, built, config, key, W)
        pool = harness.make_pool(built, mix, config["vocab"], key, W)
        if compiled is None:
            compiled = jax.jit(built["step"], donate_argnums=0).lower(
                state, pool[0]).compile()
        state, got = harness.program_readings(compiled, state, pool, config,
                                              key, mix, rounds)
        del state, pool
        gc.collect()
        ref = reference.run(seed, config, mix, W)
        emit("program", seed, got, ref)
        if seed in controls:
            emit("control_fp8", seed,
                 reference.run(seed, config, mix, W, precision="fp8"), ref)
            emit("fault_half_batch", seed,
                 reference.run(seed, config, mix, W, half_batch=True), ref)
    for seed in controls:
        if seed not in seeds:
            ref = reference.run(seed, config, mix, W)
            emit("control_fp8", seed,
                 reference.run(seed, config, mix, W, precision="fp8"), ref)
            emit("fault_half_batch", seed,
                 reference.run(seed, config, mix, W, half_batch=True), ref)


if __name__ == "__main__":
    main()
