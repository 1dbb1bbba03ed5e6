import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()
# ^ MUST run before any jax import/init: jax locks the device count on first use.

# Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).
#
# For each combination this builds the sharded step function (train / prefill /
# decode per the shape's kind), lowers it with ShapeDtypeStruct stand-ins (no
# allocation), compiles it for the production mesh, and records
# memory_analysis / cost_analysis / collective-bytes roofline terms to JSON.
#
#     PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all
#     PYTHONPATH=src python -m repro.launch.dryrun --multi-pod ...

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import for_shape, get_config
from repro.core.strategy import StrategyConfig
from repro.models import n_active_params, n_params
from repro.models.config import INPUT_SHAPES
from repro.optim import adamw, sgd

from .mesh import make_production_mesh, worker_axes_of
from .roofline import Roofline, analyze, memory_analysis_dict, peaks_for
from .serve import make_decode_step, make_prefill_step, serve_specs
from .train import batch_specs, make_train_step, train_state_specs

# the chip of the production mesh the dry-run compiles for; its roofline
# terms are estimates for that chip
TARGET_KIND = "TPU v5 lite"


def _build_lowered(cfg, shape, mesh, strategy, opt, wire, hierarchical,
                   multi_pod, microbatch=1):
    """Lower the shape-appropriate step for ``cfg`` on ``mesh``."""
    if shape.kind == "train":
        wa = worker_axes_of(mesh, hierarchical=hierarchical)
        step = make_train_step(cfg, mesh, strategy, opt, lr=1e-3,
                               worker_axes=wa, wire=wire,
                               microbatch=microbatch)
        state_s = train_state_specs(cfg, mesh, strategy, opt, wa)
        batch_s = batch_specs(cfg, mesh, shape.global_batch, shape.seq_len)
        return jax.jit(step).lower(state_s, batch_s)
    if shape.kind == "prefill":
        params_s, _, _ = serve_specs(cfg, mesh, shape.global_batch, shape.seq_len)
        pf = make_prefill_step(cfg, max_len=shape.seq_len)
        dp = ("pod", "data") if multi_pod else ("data",)
        from jax.sharding import NamedSharding, PartitionSpec as P
        tokens_s = jax.ShapeDtypeStruct(
            (shape.global_batch, shape.seq_len), jnp.int32,
            sharding=NamedSharding(mesh, P(dp, None)))
        return jax.jit(pf).lower(params_s, tokens_s)
    params_s, cache_s, tokens_s = serve_specs(
        cfg, mesh, shape.global_batch, shape.seq_len)
    return jax.jit(make_decode_step(cfg)).lower(params_s, cache_s, tokens_s)


def _probe_costs(compiled):
    from .roofline import collective_bytes
    ca = compiled.cost_analysis()
    cb = collective_bytes(compiled.as_text())
    return (float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0)),
            cb)


def roofline_probe(cfg, shape, mesh, strategy, opt, wire, hierarchical,
                   multi_pod, microbatch=1):
    """Exact roofline terms via reduced-depth UNROLLED lowerings.

    XLA cost_analysis counts a while/scan body once regardless of trip count,
    so the scanned full-depth compile under-reports per-layer work.  We lower
    unrolled variants at L = 0 (isolates the fixed embed/head/LAQ part
    exactly, nearly-free compile), at L = 1 and, for hybrids, at L = 1 with
    that layer hybrid, and extrapolate: total = fixed + n_layers*per_layer
    + n_hybrid*per_shared_block.  Exact since per-layer cost is
    index-independent.
    """
    def probe(**kw):
        cfg_L = dataclasses.replace(cfg, scan_layers=False, **kw)
        lowered = _build_lowered(cfg_L, shape, mesh, strategy, opt, wire,
                                 hierarchical, multi_pod, microbatch)
        return _probe_costs(lowered.compile())

    n_hybrid = len(cfg.hybrid_layers)
    fixed = probe(n_layers=0, hybrid_layers=())
    one = probe(n_layers=1, hybrid_layers=())
    hyb = probe(n_layers=1, hybrid_layers=(0,)) if n_hybrid else one

    def extrap(f, v1, vh):
        return (f + cfg.n_layers * max(v1 - f, 0.0)
                + n_hybrid * max(vh - v1, 0.0))
    flops = extrap(fixed[0], one[0], hyb[0])
    hbm = extrap(fixed[1], one[1], hyb[1])
    coll = {k: extrap(fixed[2][k], one[2][k], hyb[2][k]) for k in fixed[2]}
    return flops, hbm, coll


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            strategy_kind: str = "laq", bits: int = 4, wire: str = "float",
            hierarchical: bool = False, optimizer_name: str = "sgd",
            mesh=None, probe: bool = True, cfg_overrides: dict | None = None,
            strategy_overrides: dict | None = None, microbatch: int = 1,
            tag: str = "") -> dict:
    shape = INPUT_SHAPES[shape_name]
    cfg = for_shape(get_config(arch), shape)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    strategy = StrategyConfig(kind=strategy_kind, bits=bits,
                              per_leaf_radius=True,
                              **(strategy_overrides or {}))
    opt = {"sgd": sgd, "adamw": adamw}[optimizer_name]()

    if shape.kind == "train":
        model_flops = 6.0 * n_active_params(cfg) * shape.global_batch * shape.seq_len
    elif shape.kind == "prefill":
        model_flops = 2.0 * n_active_params(cfg) * shape.global_batch * shape.seq_len
    else:
        model_flops = 2.0 * n_active_params(cfg) * shape.global_batch

    # 1) full-depth scanned lowering: THE compile proof + memory analysis
    t0 = time.time()
    lowered = _build_lowered(cfg, shape, mesh, strategy, opt, wire,
                             hierarchical, multi_pod, microbatch)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = memory_analysis_dict(compiled)

    # 2) roofline terms from unrolled reduced-depth probes (exact counts)
    rf = analyze(compiled, n_devices=n_dev, model_flops_global=model_flops,
                 device_kind=TARGET_KIND)
    if probe:
        flops, hbm, coll = roofline_probe(cfg, shape, mesh, strategy, opt,
                                          wire, hierarchical, multi_pod,
                                          microbatch)
        rf = Roofline(peaks=peaks_for(TARGET_KIND), flops=flops, hbm_bytes=hbm,
                      coll_bytes=float(sum(coll.values())),
                      coll_breakdown={k: int(v) for k, v in coll.items()},
                      model_flops=model_flops / n_dev)
    rec = {
        "tag": tag,
        "arch": arch, "shape": shape_name, "kind": shape.kind,
        "mesh": dict(zip(mesh.axis_names, [mesh.shape[a] for a in mesh.axis_names])),
        "multi_pod": multi_pod,
        "strategy": strategy_kind, "bits": bits, "wire": wire,
        "hierarchical": hierarchical,
        "n_params": n_params(cfg), "n_active_params": n_active_params(cfg),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": mem,
        "roofline": rf.to_dict(),
        "ok": True,
    }
    print(f"[dryrun] {tag or 'baseline'} {arch} x {shape_name} mesh={rec['mesh']} "
          f"strategy={strategy_kind}/{wire} OK "
          f"(lower {t_lower:.1f}s compile {t_compile:.1f}s) "
          f"bottleneck={rf.bottleneck} "
          f"t=({rf.t_compute*1e3:.1f}, {rf.t_memory*1e3:.1f}, "
          f"{rf.t_collective*1e3:.1f}) ms  useful={rf.useful_flops_ratio:.2f}", flush=True)
    if mem:
        print(f"         memory_analysis: {mem}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true",
                    help="run single-pod AND multi-pod for each pair")
    ap.add_argument("--strategy", default="laq", choices=["gd", "qgd", "lag", "laq"])
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--wire", default="float", choices=["float", "packed"])
    ap.add_argument("--hierarchical", action="store_true")
    ap.add_argument("--optimizer", default="sgd", choices=["sgd", "adamw"])
    ap.add_argument("--no-probe", action="store_true",
                    help="skip the unrolled roofline probes (compile proof only)")
    ap.add_argument("--out", default="benchmarks/results/dryrun.json")
    args = ap.parse_args()

    fast_order = ["mamba2-130m", "stablelm-1.6b", "musicgen-medium",
                  "qwen3-moe-30b-a3b", "yi-6b", "zamba2-7b", "qwen3-8b",
                  "yi-9b", "phi3.5-moe-42b-a6.6b", "chameleon-34b"]
    archs = fast_order if args.arch == "all" else args.arch.split(",")
    shapes = list(INPUT_SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    results.append(run_one(
                        arch, shape, multi_pod=mp,
                        strategy_kind=args.strategy, bits=args.bits,
                        wire=args.wire, hierarchical=args.hierarchical,
                        optimizer_name=args.optimizer,
                        probe=not args.no_probe))
                except Exception as e:  # noqa: BLE001 — record and continue
                    traceback.print_exc()
                    results.append({"arch": arch, "shape": shape,
                                    "multi_pod": mp, "ok": False,
                                    "error": f"{type(e).__name__}: {e}"})
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    n_ok = sum(r.get("ok") for r in results)
    print(f"[dryrun] {n_ok}/{len(results)} combinations lowered+compiled -> {args.out}")
    if n_ok < len(results):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
