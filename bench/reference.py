"""The plain reference of a training cell's first rounds: LAQ (Sun et al.
2019, eqs. 4-7) over a model's plain reference, with AdamW as the server's
optimizer, imported from nothing of the program.

Per round, each worker ``m`` takes the gradient of its rows' mean cross
entropy over ``W``, quantizes the innovation ``g - qhat`` onto the b-bit grid
of radius ``R = ||g - qhat||_inf`` (one radius per leaf where the job says
so), and uploads unless eq. 7a and 7b both allow a skip.  The server adds the
uploaded innovations to its aggregate, and AdamW (float32 master weights,
parameters stored in the configured dtype) steps on the aggregate.

The weights come from :func:`weights`, which the harness also uses to give
the program its initial state: the yardstick makes them, not the program.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np

import traffic
from models.common import make_weights


def family(config: dict):
    """The plain reference module of a configuration's family."""
    return importlib.import_module(f"models.{config['family']}")


def weights(key, config: dict):
    """The initial weights of ``config`` from ``key`` (call under jit).

    The barrier keeps each weight rounded to its stored dtype wherever the
    caller goes on: without it XLA on the TPU folds a later cast back to
    float32 into the draw (excess precision), and a float32 master copy
    made in the same call starts from the unrounded draw."""
    return jax.lax.optimization_barrier(make_weights(
        jax.random.fold_in(key, 0), family(config).layout(config)))


def leaf_names(tree):
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_leaves_with_path(tree)]


def leaf_norms(tree):
    """Float32 L2 norm of every leaf, in ``jax.tree.leaves`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def _quantize(d, bits, per_leaf):
    """The dequantized b-bit innovation of every leaf of ``d`` (eqs. 5-6):
    code ``floor((d + R) / (2 tau R) + 1/2)`` with ``tau = 1 / (2^b - 1)``,
    written as ``(d + R) / (2R) * (2^b - 1)`` so that a zero innovation
    lands exactly on its tie (code ``2^(b-1)``), as the equation has it."""
    levels = 2 ** bits - 1
    if per_leaf:
        radii = [jnp.max(jnp.abs(x)) for x in jax.tree.leaves(d)]
    else:
        top = jnp.max(jnp.stack([jnp.max(jnp.abs(x))
                                 for x in jax.tree.leaves(d)]))
        radii = [top] * len(jax.tree.leaves(d))
    out = []
    for x, R in zip(jax.tree.leaves(d), radii):
        safe = jnp.where(R > 0, R, 1.0)
        code = jnp.clip(jnp.floor((x + R) / (2 * safe) * levels + 0.5),
                        0, levels)
        out.append(jnp.where(R > 0, (2 * code / levels - 1) * R, 0.0))
    return jax.tree.unflatten(jax.tree.structure(d), out)


def codes(tree, bits):
    """The b-bit codes of every leaf of a dequantized tree ``tree`` (a
    ``qhat``): with its radius ``R = max |q|`` (codes 0 and ``2^b - 1`` sit
    at ``-R`` and ``R``), ``q = (2 code / (2^b - 1) - 1) R``.  A leaf of
    radius 0 gives codes 0.  Returns uint8 arrays in leaf order."""
    half = (2 ** bits - 1) / 2
    out = []
    for q in jax.tree.leaves(tree):
        q = q.astype(jnp.float32)
        R = jnp.max(jnp.abs(q))
        c = jnp.rint((q / jnp.where(R > 0, R, 1.0) + 1.0) * half)
        out.append(jnp.where(R > 0, c, 0.0).astype(jnp.uint8))
    return out


def upload_bits(tree, bits, per_leaf):
    """Bits of one upload (eq. 6's accounting): ``b`` per coordinate and 32
    per radius."""
    leaves = jax.tree.leaves(tree)
    radii = len(leaves) if per_leaf else 1
    return 32 * radii + bits * sum(x.size for x in leaves)


def _sq(tree):
    return sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree))


def _round(params, opt, laq, grads, mix):
    """One LAQ round and one AdamW step, given each worker's gradient."""
    s = mix["strategy"]
    crit = mix["criterion"]
    o = mix["optimizer"]
    lr, W = mix["lr"], len(grads)
    hist_term = jnp.sum(crit["xi"] * laq["hist"]) / (lr ** 2 * W ** 2)
    agg, qhat, eps_hat, clock = laq["agg"], [], [], []
    uploads = 0
    for m, g in enumerate(grads):
        qh = laq["qhat"][m]
        delta = _quantize(jax.tree.map(jnp.subtract, g, qh), s["bits"],
                          s["per_leaf_radius"])
        q_new = jax.tree.map(jnp.add, qh, delta)
        err_sq = _sq(jax.tree.map(jnp.subtract, g, q_new))
        rhs = hist_term + 3.0 * (err_sq + laq["eps_hat"][m])
        skip = (_sq(delta) <= rhs) & (laq["clock"][m] < crit["t_bar"])
        up = ~skip
        qhat.append(jax.tree.map(lambda n, o_: jnp.where(up, n, o_), q_new, qh))
        eps_hat.append(jnp.where(up, err_sq, laq["eps_hat"][m]))
        clock.append(jnp.where(up, 0, laq["clock"][m] + 1))
        agg = jax.tree.map(lambda a, d: a + jnp.where(up, d, 0.0), agg, delta)
        uploads = uploads + up.astype(jnp.int32)

    b1, b2 = o["b1"], o["b2"]
    count = opt["count"] + 1
    bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count
    mu = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, opt["mu"], agg)
    nu = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, opt["nu"], agg)
    master = jax.tree.map(
        lambda w, m_, v: w - lr * ((m_ / bc1) / (jnp.sqrt(v / bc2) + o["eps"])
                                   + o["weight_decay"] * w),
        opt["master"], mu, nu)
    new_params = jax.tree.map(lambda w, p: w.astype(p.dtype), master, params)
    moved = _sq(jax.tree.map(lambda a, b: a.astype(jnp.float32)
                             - b.astype(jnp.float32), new_params, params))
    laq = dict(agg=agg, qhat=qhat, eps_hat=jnp.stack(eps_hat),
               clock=jnp.stack(clock),
               hist=jnp.concatenate([moved[None], laq["hist"][:-1]]))
    opt = dict(mu=mu, nu=nu, master=master, count=count)
    return new_params, opt, laq, uploads


def _keep_half(rows):
    """The fault "half of the batch left out": the first half of the rows,
    or of the positions where a worker holds one row."""
    if rows["tokens"].shape[0] > 1:
        return jax.tree.map(lambda x: x[:x.shape[0] // 2], rows)
    return jax.tree.map(lambda x: x[:, :x.shape[1] // 2], rows)


def run(seed: int, config: dict, mix: dict, n_workers: int, *,
        precision: str = "f32", half_batch: bool = False):
    """The reference's readings over the mix's checked rounds from ``seed``:
    ``losses``, ``uploads`` and wire ``bits`` per round, per-leaf norms of
    the aggregate the optimizer gets in round 1 (``agg_norms``) and of the
    raw gradient sum there (``grad_norms``), worker 0's codes after round 1
    (``codes``) and where its innovation was exactly 0 (``ties``), and
    per-leaf norms of the master weights' change after the
    last round (``change_norms``)."""
    fam, model = family(config), config
    steps = mix.get("checked_rounds", 3)
    W = n_workers
    rpw = mix["rows_per_worker"]
    block = mix.get("reference_rows_per_block", 1)
    with jax.default_matmul_precision("highest"):
        key = traffic.seed_key(seed)
        make = jax.jit(functools.partial(weights, config=config))
        params = make(key)
        t_bar = mix["criterion"]["t_bar"]
        clock0 = t_bar if mix["strategy"].get("first_round_upload", True) else 0

        @jax.jit
        def fresh(params):
            def zeros():
                return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                    params)
            opt = dict(mu=zeros(), nu=zeros(),
                       master=jax.tree.map(lambda p: p.astype(jnp.float32),
                                           params),
                       count=jnp.zeros((), jnp.float32))
            laq = dict(agg=zeros(), qhat=[zeros() for _ in range(W)],
                       eps_hat=jnp.zeros((W,), jnp.float32),
                       clock=jnp.full((W,), clock0, jnp.int32),
                       hist=jnp.zeros((mix["criterion"]["D"],), jnp.float32))
            return opt, laq

        opt, laq = fresh(params)

        @jax.jit
        def block_grad(params, rows):
            pf = jax.tree.map(lambda p: p.astype(jnp.float32), params)
            return jax.value_and_grad(
                lambda q: fam.loss_sum(q, rows, model, precision))(pf)

        round_fn = jax.jit(functools.partial(_round, mix=mix),
                           donate_argnums=(0, 1, 2))
        vocab = model["vocab"]
        streams = traffic.round_streams(key, range(steps), mix, vocab, W)
        out = dict(losses=[], uploads=[], bits=[])
        for k in range(steps):
            batch = traffic.split(streams[k])
            grads = []
            loss = 0.0
            for m in range(W):
                rows = jax.tree.map(lambda x: x[m * rpw:(m + 1) * rpw], batch)
                if half_batch:
                    rows = _keep_half(rows)
                n_tok = rows["tokens"].size
                g_sum, l_sum = None, 0.0
                for r in range(0, rows["tokens"].shape[0], block):
                    part = jax.tree.map(lambda x: x[r:r + block], rows)
                    l, g = block_grad(params, part)
                    l_sum = l_sum + l
                    g_sum = g if g_sum is None else jax.tree.map(jnp.add, g_sum, g)
                scale = 1.0 / (n_tok * W)
                grads.append(jax.tree.map(lambda g: g * scale, g_sum))
                loss = loss + l_sum * scale
            if k == 0:
                out["grad_norms"] = np.asarray(leaf_norms(
                    functools.reduce(lambda a, b: jax.tree.map(jnp.add, a, b),
                                     grads)))
            if k == 0:
                # eq. 5 puts a zero innovation exactly on the tie between
                # two codes, which rounding may break either way
                out["ties"] = jax.device_get([x == 0 for x in jax.tree.leaves(
                    jax.tree.map(jnp.subtract, grads[0], laq["qhat"][0]))])
            params, opt, laq, up = round_fn(params, opt, laq, grads)
            del grads
            out["losses"].append(float(loss))
            out["uploads"].append(int(up))
            out["bits"].append(int(up) * upload_bits(
                params, mix["strategy"]["bits"],
                mix["strategy"]["per_leaf_radius"]))
            if k == 0:
                out["agg_norms"] = np.asarray(leaf_norms(laq["agg"]))
                out["codes"] = jax.device_get(
                    codes(laq["qhat"][0], mix["strategy"]["bits"]))
        del params
        p0 = make(key)
        out["change_norms"] = np.asarray(leaf_norms(jax.tree.map(
            lambda w, p: w - p.astype(jnp.float32), opt["master"], p0)))
        out["names"] = leaf_names(p0)
    return out
