"""Top-level model API: loss, init, serving entry points."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import stack
from .config import ModelConfig

AUX_LOSS_WEIGHT = 0.01


def lm_loss(params, batch, cfg: ModelConfig):
    """Next-token cross entropy.

    The target-logit extraction uses a one-hot contraction over the vocab dim
    (instead of take_along_axis) so the reduction over the *model-sharded*
    vocab lowers to a partial-sum all-reduce rather than an all-gather of the
    full logits.
    """
    logits, aux = stack.forward(params, batch["tokens"], cfg)
    targets = batch["targets"]
    V = logits.shape[-1]
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(targets, V, dtype=logits.dtype)
    tgt = jnp.einsum("bsv,bsv->bs", logits, onehot)
    ce = jnp.mean(lse - tgt)
    return ce + AUX_LOSS_WEIGHT * aux


def lm_worker_loss(cfg: ModelConfig, n_workers: int):
    """One federated worker's local LM objective: ``lm_loss / W``.

    ``lm_loss`` is a token **mean**, so dividing by the worker count makes
    the engine's global objective ``sum_m f_m`` equal the global mean token
    cross-entropy — ``exp(global_loss)`` is perplexity.  Mean convention
    also makes the loss mean-decomposable over equal microbatches, the
    contract ``AccumulatingSource`` / ``accumulate_loss_grads``
    (core/engine.py) need; pass ``scale=1.0`` to the source, the ``1/W``
    normalization already lives here.
    """
    def loss_fn(params, batch):
        return lm_loss(params, batch, cfg) / n_workers

    return loss_fn


init_params = stack.init_params
forward = stack.forward
prefill = stack.prefill
decode_step = stack.decode_step
init_cache = stack.init_cache
