"""End-to-end LM training driver with LAQ gradient exchange.

One LAQ worker per device: the mesh is ``(n_devices, 1)`` over
``("data", "model")``, built from ``jax.devices()``.

    # on one TPU chip: Mamba2-130M at its published config, packed 4-bit wire
    PYTHONPATH=src python examples/train_lm.py --arch mamba2-130m \\
        --batch 2 --seq 2048 --steps 20 --wire packed

    # on CPU, as the tests run it: 8 forced host devices = 8 LAQ workers
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \\
        PYTHONPATH=src python examples/train_lm.py --steps 50

    # stochastic lazy rule + gradient accumulation (the AccumulatingSource
    # fold shared with core/engine.py; 2 sequential microbatches per worker)
    PYTHONPATH=src python examples/train_lm.py --steps 20 --strategy slaq --accum 2

    # error-feedback top-k compression (float wire)
    PYTHONPATH=src python examples/train_lm.py --steps 20 --strategy ef

    # a profiler trace of rounds 10 to 15, for TensorBoard / XProf
    PYTHONPATH=src python examples/train_lm.py --steps 20 --profile-dir /tmp/laq

``--preset`` picks a small dense LM (``smoke``, ``100m``); ``--arch`` any
published architecture of ``repro.configs`` at its full config instead.

Demonstrates the full production path: sharded data pipeline -> partial-auto
shard_map LAQ train step (per-worker quantize + skip + explicit aggregation
collective) -> optimizer -> checkpoint, with bits/rounds telemetry.

Each round runs in a ``laq.round`` step annotation and its host read of the
metrics in ``laq.readback``; on the profiler's one clock they sit beside the
step's device operations, which carry the step's ``laq.*`` spans
(``repro/core/spans.py``).
"""
import argparse
import os
import sys
import time
from typing import NamedTuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import save_checkpoint
from repro.configs import ARCH_IDS, get_config
from repro.core.strategy import StrategyConfig
from repro.data import lm_batches
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import device_mesh, n_workers_of
from repro.launch.train import (init_train_state, make_train_step,
                                train_state_specs)
from repro.models.config import ModelConfig
from repro.optim import adamw

PRESETS = {
    "smoke": ModelConfig(name="lm-smoke", arch_type="dense", n_layers=4,
                         d_model=256, vocab=4096, n_heads=4, n_kv_heads=2,
                         head_dim=64, d_ff=1024, q_chunk=128, kv_chunk=64),
    "100m": ModelConfig(name="lm-100m", arch_type="dense", n_layers=12,
                        d_model=768, vocab=32768, n_heads=12, n_kv_heads=4,
                        head_dim=64, d_ff=2048, q_chunk=256, kv_chunk=128),
}

# CLI strategy -> StrategyConfig.  The first four are the paper's
# deterministic kinds; the rest exercise the stochastic levers on the LM
# step: slaq = variance-aware LASG-WK rule, wk2 = same-sample noise-free
# rule (second backprop), svrg = variance-reduced local gradients, ef =
# error-feedback top-k compression (float wire, data-parallel mesh).
STRATEGIES = ("gd", "qgd", "lag", "laq", "slaq", "wk2", "svrg", "ef")

# the first and last round that --profile-dir traces (past the first
# rounds, whose dispatch still warms up)
PROFILE_ROUNDS = (10, 15)


def build_strategy(name: str, bits: int,
                   wire_backend: str = "fused") -> StrategyConfig:
    base = dict(bits=bits, per_leaf_radius=True, wire_backend=wire_backend)
    if name in ("gd", "qgd", "lag", "laq"):
        return StrategyConfig(kind=name, **base)
    if name == "slaq":
        return StrategyConfig(kind="laq", lazy_rule="lasg_wk", **base)
    if name == "wk2":
        return StrategyConfig(kind="laq", lazy_rule="lasg_wk2", **base)
    if name == "svrg":
        return StrategyConfig(kind="laq", grad_mode="svrg", **base)
    if name == "ef":
        return StrategyConfig(kind="laq", compressor="topk",
                              compressor_k=0.05, error_feedback=True, **base)
    raise ValueError(name)


def model_config(name: str) -> ModelConfig:
    """A preset of this script, or a published architecture by id."""
    return PRESETS[name] if name in PRESETS else get_config(name)


class TrainRun(NamedTuple):
    state: object          # final TrainState
    step: object           # the un-jitted step fn (``step.wire_backend``)
    compiled: object       # the compiled, state-donating step
    compile_s: float       # lower + compile, seconds
    history: list          # per step: loss, uploads, bits
    mesh: object
    n_workers: int
    n_params: int


def train(cfg: ModelConfig, strategy: StrategyConfig, *, steps: int,
          batch: int, seq: int, lr: float, wire: str, accum: int = 1,
          seed: int = 0, n_devices: int | None = None,
          profile_dir: str | None = None, log=print) -> TrainRun:
    """Initialize from ``seed`` on a data-parallel mesh of the first
    ``n_devices`` devices (all by default) and run ``steps`` LAQ rounds of
    the sharded train step on synthetic token batches.  With
    ``profile_dir``, rounds ``PROFILE_ROUNDS`` are traced there."""
    mesh = device_mesh(n_devices=n_devices)
    wa = ("data",)
    W = n_workers_of(mesh, wa)
    assert batch % W == 0, f"batch must be divisible by {W} workers"
    assert (batch // W) % accum == 0, \
        "accum must divide the per-worker batch"
    opt = adamw(weight_decay=0.01)

    specs = train_state_specs(cfg, mesh, strategy, opt, wa)
    state = jax.jit(
        lambda k: init_train_state(k, cfg, mesh, strategy, opt, wa),
        out_shardings=jax.tree.map(lambda s: s.sharding, specs),
    )(jax.random.PRNGKey(seed))
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    log(f"model={cfg.name} params={n_params / 1e6:.1f}M "
        f"strategy={strategy.kind}/{wire}/{strategy.wire_backend} "
        f"accum={accum} mesh={dict(mesh.shape)}")

    step = make_train_step(cfg, mesh, strategy, opt, lr=lr, worker_axes=wa,
                           wire=wire, microbatch=accum)
    batches = lm_batches(seed, batch, seq, cfg.vocab,
                         sharding=NamedSharding(mesh, P("data", None)))
    b = next(batches)
    t0 = time.perf_counter()
    compiled = jax.jit(step, donate_argnums=0).lower(state, b).compile()
    compile_s = time.perf_counter() - t0
    log(f"compiled the train step in {compile_s:.1f}s")

    history = []
    tracing = False
    try:
        for i in range(steps):
            if profile_dir and i == PROFILE_ROUNDS[0]:
                jax.profiler.start_trace(profile_dir)
                tracing = True
            with jax.profiler.StepTraceAnnotation("laq.round", step_num=i):
                state, m = compiled(state, b)
                with jax.profiler.TraceAnnotation("laq.readback"):
                    history.append({"loss": float(m.loss),
                                    "uploads": int(m.uploads),
                                    "bits": float(m.bits)})
            if tracing and i == PROFILE_ROUNDS[1]:
                jax.profiler.stop_trace()
                tracing = False
                log(f"traced rounds {PROFILE_ROUNDS[0]}-{i} -> {profile_dir}")
            if i % 10 == 0 or i == steps - 1:
                log(f"step {i:4d} loss={history[-1]['loss']:7.4f} "
                    f"uploads={history[-1]['uploads']} "
                    f"cum_bits={float(state.comm.total_bits):.3e}")
            b = next(batches)
    finally:
        if tracing:
            jax.profiler.stop_trace()
            log(f"traced rounds {PROFILE_ROUNDS[0]}-{steps - 1} -> "
                f"{profile_dir}")
    return TrainRun(state, step, compiled, compile_s, history, mesh, W,
                    n_params)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="smoke", choices=list(PRESETS))
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS),
                    help="a published architecture at its full config "
                         "(overrides --preset)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--strategy", default="laq", choices=list(STRATEGIES))
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--accum", type=int, default=1,
                    help="sequential microbatches per worker (gradient "
                         "accumulation; activation memory / accum)")
    ap.add_argument("--wire", default="float", choices=["float", "packed"])
    ap.add_argument("--wire-backend", default="fused",
                    choices=["fused", "reference"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--profile-dir", default=None,
                    help="write a profiler trace of rounds %d to %d here"
                         % PROFILE_ROUNDS)
    args = ap.parse_args()

    use_compile_cache()
    cfg = model_config(args.arch or args.preset)
    strategy = build_strategy(args.strategy, args.bits, args.wire_backend)
    if strategy.compressed or strategy.error_feedback:
        assert args.wire == "float", "--strategy ef requires --wire float"
    run = train(cfg, strategy, steps=args.steps, batch=args.batch,
                seq=args.seq, lr=args.lr, wire=args.wire, accum=args.accum,
                seed=args.seed, profile_dir=args.profile_dir)
    if args.ckpt:
        save_checkpoint(args.ckpt, jax.device_get(run.state.params),
                        args.steps)
        print(f"checkpoint -> {args.ckpt}")
    comm = run.state.comm
    skip_rate = 1 - float(comm.total_uploads) / (run.n_workers * args.steps)
    dense = 32 * run.n_params * run.n_workers * args.steps
    print(f"done: final loss {run.history[-1]['loss']:.4f}; worker-upload "
          f"skip rate {skip_rate:.1%}; total wire bits "
          f"{float(comm.total_bits):.3e} (dense GD would be {dense:.3e})")


if __name__ == "__main__":
    main()
