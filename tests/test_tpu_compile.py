"""Compile every wire kernel for a described TPU v5e, at Mamba2-130M widths.

Nothing runs: the TPU compiler installed with jax compiles for a chip that
is described, not attached, and refuses what the chip's Pallas lowering
would refuse (block shapes off the tiling, scalar operands outside SMEM,
too much VMEM).  Interpret-mode tests cannot see any of that.  The kernels
are compiled as the wrappers in ``kernels/ops.py`` call them, on a leaf
padded to whole blocks.  One test compiles the one-chip sharded step on the
whole-leaf wire at a small width and counts its kernels.  Every test skips
where no v5e topology can be described.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import quant_pack as qp

EMBED = 50288 * 768                      # Mamba2-130M's largest leaf
N = -(-EMBED // qp.BLOCK) * qp.BLOCK     # padded to whole blocks, as ops.py does
assert (EMBED, N // qp.BLOCK) == (38_621_184, 1_179)
SPARSE_K = 1 << 23                       # ~5% of the model: an EF top-k payload
GRID = (2, 4, 8)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here, or its library held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _kernel_cases():
    f32, u8, i32 = jnp.float32, jnp.uint8, jnp.int32
    leaf = [((N,), f32), ((N,), f32)]
    cases = {
        "absmax": (lambda g, q: qp.absmax_pallas(g, q, interpret=False),
                   leaf),
        "quantize_codes_adaptive": (
            lambda g, q, R, s: qp.quantize_codes_adaptive_pallas(
                g, q, R, s, GRID, interpret=False),
            leaf + [((1,), f32), ((1,), i32)]),
        "quantize_pack_adaptive": (
            lambda g, q, R, s: qp.quantize_pack_adaptive_pallas(
                g, q, R, s, GRID, EMBED, interpret=False),
            leaf + [((1,), f32), ((1,), i32)]),
        "payload": (
            lambda g, q, R: qp.quantize_pack_payload_pallas(
                g, q, R, 4, interpret=False),
            leaf + [((1,), f32)]),
        "dequant_acc": (
            lambda p, R, k, a: qp.dequant_acc_pallas(
                p, R, k, 4, N, a, interpret=False),
            [((4, N // 2), u8), ((4,), f32), ((4,), f32), ((N,), f32)]),
        # the whole-leaf wire's pair, as the sharded step runs it on one
        # chip: the send kernel's rows, stacked, into the decode kernel
        "payload_rows_to_dequant_acc": (
            lambda g, q, R, k: qp.dequant_acc_pallas(
                qp.quantize_pack_payload_pallas(
                    g, q, R, 4, interpret=False)[0][None],
                R, k, 4, N, interpret=False),
            leaf + [((1,), f32), ((1,), f32)]),
        "sparse_quant_pack": (
            lambda v, lo, hi: qp.sparse_quant_pack_pallas(
                v, lo, hi, 1, interpret=False),
            [((SPARSE_K,), f32), ((1,), f32), ((1,), f32)]),
    }
    for bits in (1, 2, 4, 8):
        cases[f"quantize_pack_b{bits}"] = (
            lambda g, q, R, b=bits: qp.quantize_pack_pallas(
                g, q, R, b, EMBED, interpret=False),
            leaf + [((1,), f32)])
    return cases


CASES = _kernel_cases()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture
def kernels_compiled(monkeypatch):
    """The kernels' wrappers take interpret mode on a CPU default backend,
    which this process has: steer them to the chip's lowering, with no
    trace cached on either side of the switch."""
    from repro.kernels import ops
    jax.clear_caches()
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)
    yield
    jax.clear_caches()


def test_flat_wire_step_quantizes_once(topo, no_persistent_cache,
                                       kernels_compiled):
    """The one-chip step on the whole-leaf wire: per non-empty leaf three
    kernels, the radius (``absmax``), the fused pass 2 whose payload ships,
    and the decode (``dequant_acc``).  Quantizing again for the exchange
    made four: XLA merges the two identical radius kernels, not the two
    pass-2 kernels."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh

    from repro.configs import get_config, smoke_config
    from repro.core.strategy import StrategyConfig
    from repro.core.wire import FusedWire
    from repro.launch.train import (batch_specs, make_train_step,
                                    train_state_specs)
    from repro.models import init_params
    from repro.optim import adamw

    cfg = dataclasses.replace(smoke_config(get_config("mamba2-130m")),
                              n_layers=1, d_model=64, vocab=512)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    strategy = StrategyConfig(kind="laq", bits=4, per_leaf_radius=True,
                              wire_backend=FusedWire(lowering="pallas"))
    opt = adamw()
    step = make_train_step(cfg, mesh, strategy, opt, lr=1e-3,
                           worker_axes=("data",), wire="packed")
    assert step.wire_layout["quantize_passes"] == 1
    hlo = jax.jit(step).lower(
        train_state_specs(cfg, mesh, strategy, opt, ("data",)),
        batch_specs(cfg, mesh, 2, 64)).compile().as_text()
    leaves = [leaf for leaf in jax.tree.leaves(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))) if leaf.size]
    assert hlo.count('custom_call_target="tpu_custom_call"') \
        == 3 * len(leaves)
