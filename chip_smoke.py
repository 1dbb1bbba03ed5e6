"""Proof that LAQ training runs on a TPU chip.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-worker path, on four chips

With one chip, three phases, each of which must pass:

  (a) every wire kernel of the fused path, compiled for the chip, on random
      leaves of Mamba2-130M's real widths, against kernels/ref.py: codes,
      deltas and partial sums bit for bit, moments to float32 accuracy;
  (b) Mamba2-130M at its published config, whole, trained as LAQ (4-bit,
      packed wire, fused backend, AdamW) through examples/train_lm.py's
      path: the loss is finite and falls, the step runs the Pallas kernels;
  (c) the same steps on the reference backend: params bitwise equal to (b);
  (d) one LAQ round each of a small dense model and a small Zamba2 hybrid
      (2,048 tokens over two query chunks, the second scanning four kv
      chunks): every attention leaf of the dense layers and of the shared
      blocks, and the embedding, gets a finite, non-zero aggregate.

With ``--chips 4``, only this: Mamba2-130M on a (4, 1) mesh, four LAQ
workers, packed wire against float wire, params exactly equal.

The last line of the output is one JSON object naming the device.  Without
a TPU the script exits non-zero before any phase runs.
"""

import argparse
import gc
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "mamba2-130m"


def log(msg):
    print(msg, flush=True)


def check(ok, what):
    if not ok:
        raise SystemExit(f"FAILED: {what}")
    log(f"  ok: {what}")


def same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.tobytes() == b.tobytes()


def bitwise(name, got, want):
    got, want = np.asarray(got), np.asarray(want)
    if not same_bits(got, want):
        bad = np.flatnonzero(got.reshape(-1) != want.reshape(-1))
        log(
            f"  {name}: {bad.size} of {want.size} elements differ, first at "
            f"{bad[:5].tolist()}: {got.reshape(-1)[bad[:5]].tolist()} vs "
            f"{want.reshape(-1)[bad[:5]].tolist()}"
        )
    check(same_bits(got, want), f"{name} bitwise equal to the reference")


def close(name, got, want, rtol=1e-4):
    got, want = float(got), float(want)
    check(
        abs(got - want) <= rtol * abs(want) + 1e-6,
        f"{name} {got!r} within rtol {rtol} of the reference {want!r}",
    )


def leaf_shapes():
    """Mamba2-130M's largest leaf (the embedding) and a ragged one."""
    from repro.configs import get_config
    from repro.models import init_params

    cfg = get_config(ARCH)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return {k: params[k].shape for k in ("embed", "final_norm")}


def kernels_phase(seed, shapes):
    """(a) the compiled wire kernels against kernels/ref.py."""
    from repro.kernels import (
        absmax,
        dequant_acc,
        quantize_codes_adaptive,
        quantize_pack,
        quantize_pack_adaptive,
        quantize_pack_fused,
    )
    from repro.kernels import ref
    from repro.kernels.quant_pack import BLOCK

    grid = (2, 4, 8)
    key = jax.random.PRNGKey(seed)
    for name, shape in shapes.items():
        n = int(np.prod(shape))
        log(f"[kernels] leaf {name} {shape} ({n} elements)")
        k1, k2, key = jax.random.split(key, 3)
        g = (jax.random.normal(k1, (n,)) * 3).astype(jnp.float32)
        qh = jax.random.normal(k2, (n,)).astype(jnp.float32)
        pad = (-n) % BLOCK
        diff = jax.jit(lambda g, q: jnp.pad(g - q, (0, pad)))(g, qh)

        R = absmax(g, qh)
        bitwise("absmax", R, jax.jit(ref.absmax_ref)(g, qh))

        for bits in (2, 4, 8):
            cpb = 8 // bits
            pk, dl, qn, esq, isq = quantize_pack_fused(g, qh, R, bits)
            rpk, rdl, rqn, resq, risq = jax.jit(
                ref.quantize_pack_fused_ref, static_argnums=3
            )(g, qh, R, bits)
            bitwise(
                f"quantize_pack_fused b={bits} codes",
                pk.reshape(-1)[: n // cpb],
                rpk[: n // cpb],
            )
            bitwise(f"quantize_pack_fused b={bits} delta", dl, rdl)
            bitwise(f"quantize_pack_fused b={bits} q_new", qn, rqn)
            close(f"quantize_pack_fused b={bits} err_sq", esq, resq)
            close(f"quantize_pack_fused b={bits} innovation_sq", isq, risq)

            pk, dl = quantize_pack(g, qh, R, bits)
            rpk, rdl = jax.jit(ref.quantize_pack_ref, static_argnums=2)(diff, R, bits)
            bitwise(f"payload b={bits} codes", pk.reshape(-1), rpk)
            bitwise(f"payload b={bits} delta", dl, rdl[:n])

            W = 2
            pks = jnp.stack([pk, jnp.roll(pk, 1)])
            Rw = jnp.stack([R, 0.5 * R])
            keep = jnp.ones((W,), jnp.float32)
            acc = dequant_acc(pks, Rw, keep, bits, n)
            racc = jax.jit(ref.dequant_acc_ref, static_argnums=(3, 4))(
                pks, Rw, keep, bits, n
            )
            bitwise(f"dequant_acc b={bits} W={W}", acc, racc)
            acc = dequant_acc(pks[:1], Rw[:1], keep[:1], bits, n, qh)
            racc = jax.jit(ref.dequant_acc_ref, static_argnums=(3, 4))(
                pks[:1], Rw[:1], keep[:1], bits, n, qh
            )
            bitwise(f"dequant_acc b={bits} W=1 into the aggregate", acc, racc)

        for sel, bits in enumerate(grid):
            onehot = jnp.eye(len(grid), dtype=jnp.float32)[sel]
            pk, dl, qn, esq, isq = quantize_pack_adaptive(g, qh, R, onehot, grid)
            rpk, rdl, rqn, resq, risq = jax.jit(
                ref.quantize_pack_adaptive_ref, static_argnums=(3, 4)
            )(g, qh, R, grid, sel)
            cpb = 8 // max(grid)
            bitwise(f"adaptive b={bits} codes", pk[: n // cpb], rpk[: n // cpb])
            bitwise(f"adaptive b={bits} delta", dl, rdl)
            bitwise(f"adaptive b={bits} q_new", qn, rqn)
            close(f"adaptive b={bits} err_sq", esq, resq)
            close(f"adaptive b={bits} innovation_sq", isq, risq)
            codes, dl = quantize_codes_adaptive(g, qh, R, onehot, grid)
            rpk, rdl = jax.jit(ref.quantize_pack_ref, static_argnums=2)(
                diff, R, bits
            )
            bitwise(
                f"adaptive codes sweep b={bits} codes", codes, _codes(rpk, bits)[:n]
            )
            bitwise(f"adaptive codes sweep b={bits} delta", dl, rdl[:n])
            fpk, fdl = quantize_pack(g, qh, R, bits)
            bitwise(
                f"adaptive codes sweep b={bits} codes vs payload kernel",
                codes,
                _codes(fpk.reshape(-1), bits)[:n],
            )
            bitwise(f"adaptive codes sweep b={bits} delta vs payload kernel", dl, fdl)
        del g, qh, diff


def _codes(packed, bits):
    """Unpack little-end-first codes (the byte order of kernels/ref.py)."""
    p = np.asarray(packed)
    if bits == 8:
        return p
    mask = (1 << bits) - 1
    parts = [(p >> (bits * j)) & mask for j in range(8 // bits)]
    return np.stack(parts, axis=-1).reshape(-1)


def train_phase(backend, wire, steps, batch, seq, lr, seed, n_devices):
    """(b)/(c)/four-chip: examples/train_lm.py's train() on Mamba2-130M."""
    sys.path.insert(0, os.path.join(ROOT, "examples"))
    import train_lm

    cfg = train_lm.model_config(ARCH)
    strategy = train_lm.build_strategy("laq", 4, backend)
    run = train_lm.train(
        cfg,
        strategy,
        steps=steps,
        batch=batch,
        seq=seq,
        lr=lr,
        wire=wire,
        seed=seed,
        n_devices=n_devices,
        log=lambda m: log(f"  {m}"),
    )
    losses = [h["loss"] for h in run.history]
    for i, h in enumerate(run.history):
        log(
            f"  step {i} loss {h['loss']!r} uploads {h['uploads']}/"
            f"{run.n_workers} skips {run.n_workers - h['uploads']} "
            f"wire bits {h['bits']!r}"
        )
    comm = run.state.comm
    ups = int(comm.total_uploads)
    log(
        f"  params {run.n_params} ({run.n_params / 1e6:.1f} M) "
        f"mesh {dict(run.mesh.shape)} workers {run.n_workers} "
        f"uploads {ups} skips {run.n_workers * steps - ups} "
        f"total wire bits {float(comm.total_bits)!r} "
        f"wire backend {run.step.wire_backend}"
    )
    check(all(np.isfinite(losses)), "every loss is finite")
    check(losses[-1] < losses[0], f"the loss falls: {losses[0]!r} -> {losses[-1]!r}")
    hlo = run.compiled.as_text()
    params = jax.device_get(run.state.params)
    return run, params, hlo


def attention_phase(seed):
    """(d): attention's gradients survive the chip, in a dense model's
    layers and in Zamba2's shared block."""
    import dataclasses

    from repro.configs import get_config, smoke_config
    from repro.core.strategy import StrategyConfig
    from repro.launch.mesh import device_mesh
    from repro.launch.train import init_train_state, make_train_step
    from repro.optim import adamw

    for arch, attn in (("stablelm-1.6b", "blocks"), ("zamba2-7b", "shared")):
        cfg = dataclasses.replace(smoke_config(get_config(arch)),
                                  q_chunk=1024, kv_chunk=512)
        mesh = device_mesh(n_devices=1)
        strategy = StrategyConfig(kind="laq", bits=4, per_leaf_radius=True,
                                  wire_backend="fused")
        opt = adamw()
        step = jax.jit(make_train_step(cfg, mesh, strategy, opt, lr=1e-3,
                                       worker_axes=("data",), wire="packed"))
        state = init_train_state(jax.random.PRNGKey(seed), cfg, mesh,
                                 strategy, opt, ("data",))
        s = jax.random.randint(jax.random.PRNGKey(seed + 1), (1, 2049), 0,
                               cfg.vocab)
        state, m = step(state, {"tokens": s[:, :-1], "targets": s[:, 1:]})
        log(f"  {arch}: loss {float(m.loss)!r} uploads {int(m.uploads)}")
        mu = state.opt_state.mu
        leaves = dict(jax.tree_util.tree_leaves_with_path(mu[attn]["attn"]))
        leaves["embed"] = mu["embed"]
        for path, leaf in leaves.items():
            name = path if isinstance(path, str) else jax.tree_util.keystr(path)
            n = float(jnp.linalg.norm(leaf.astype(jnp.float32)))
            log(f"  {arch} aggregate norm {name}: {n!r}")
            check(np.isfinite(n) and n > 0,
                  f"{arch} {name}'s aggregate is finite and non-zero")


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def params_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(same_bits(x, y) for x, y in zip(la, lb))


def report_diff(a, b):
    """Per leaf, how many elements of two param trees differ."""
    for path, x in jax.tree_util.tree_leaves_with_path(a):
        y = np.asarray(_leaf(b, path))
        x = np.asarray(x)
        bad = int(np.sum(x.astype(np.float32) != y.astype(np.float32)))
        if bad:
            log(f"  {jax.tree_util.keystr(path)}: {bad} of {x.size} differ")


def _leaf(tree, path):
    for k in path:
        tree = tree[k.key]
    return tree


def max_param_diff(a, b):
    return max(
        float(np.max(np.abs(np.asarray(x, np.float32) - np.asarray(y, np.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4, help="sequences per worker")
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX sees {dev.platform}; nothing was run", file=sys.stderr)
        sys.exit(1)
    if len(jax.devices()) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices", file=sys.stderr)
        sys.exit(1)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import use_compile_cache

    cache = use_compile_cache()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"device {dev.device_kind} x{len(jax.devices())}; jax {jax.__version__}")
    log(f"compile cache {cache}: {entries} entries at start")
    t0 = time.perf_counter()
    train = dict(steps=args.steps, seq=args.seq, lr=args.lr, seed=args.seed)

    if args.chips == 1:
        log("[a] wire kernels, compiled, against kernels/ref.py")
        kernels_phase(args.seed, leaf_shapes())
        gc.collect()

        log("[b] Mamba2-130M, LAQ 4-bit, packed wire, fused backend")
        run, fused, hlo = train_phase(
            "fused", "packed", batch=args.batch, n_devices=1, **train
        )
        check(run.step.wire_backend == "fused", "the step ran the fused backend")
        check("tpu_custom_call" in hlo, "the compiled step holds tpu_custom_call")
        log(
            f"  tpu_custom_call in the step: {hlo.count('tpu_custom_call')}; "
            f"quantize passes a leaf a round: "
            f"{run.step.wire_layout['quantize_passes']}"
        )
        log(f"  peak device memory {peak_bytes()} bytes")
        del run, hlo
        gc.collect()

        log("[c] the same steps on the reference backend")
        run, reference, _ = train_phase(
            "reference", "packed", batch=args.batch, n_devices=1, **train
        )
        log(f"  peak device memory {peak_bytes()} bytes")
        report_diff(fused, reference)
        same = params_equal(fused, reference)
        check(same, "fused and reference params bitwise equal")
        del run
        gc.collect()

        log("[d] small dense model and Zamba2 hybrid, one LAQ round each, "
            "attention gradients")
        attention_phase(args.seed)
    else:
        log("[4 chips] Mamba2-130M, 4 LAQ workers, packed wire vs float wire")
        batch = 4 * args.batch
        _, packed, _ = train_phase("fused", "packed", batch=batch, n_devices=4, **train)
        gc.collect()
        _, floatw, _ = train_phase("fused", "float", batch=batch, n_devices=4, **train)
        report_diff(packed, floatw)
        diff = max_param_diff(packed, floatw)
        log(f"  max |packed - float| param diff {diff!r}")
        check(diff == 0.0, "packed and float wire params exactly equal")

    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"compile cache {cache}: {entries} entries at end")
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
