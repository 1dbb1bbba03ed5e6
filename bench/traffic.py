"""Token batches for a training job, made on the device from the seed.

A traffic mix is a JSON file under ``bench/traffic/``: the LAQ job (strategy,
wire, optimizer, learning rate) and the shape of each round's input (rows per
worker, sequence length, microbatches) and of the token stream.  This module
is the one generator that reads it.

The stream is a copy of ``synthetic_lm_batch`` in ``src/repro/data/
synthetic.py``: a Zipf marginal with a Markov mix, so the loss has signal.
Round ``r`` of seed ``s`` draws from ``fold_in(key(s), r)``: every round's
rows differ, and the reference regenerates any round without the program.
"""
from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp

HERE = pathlib.Path(__file__).resolve().parent


def load(name: str) -> dict:
    """The traffic mix ``bench/traffic/<name>.json``."""
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def seed_key(seed: int):
    """A PRNG key from a seed of any size, 32 bits or more."""
    if seed < 0:
        raise ValueError(f"seed must be a whole number >= 0, got {seed}")
    key = jax.random.PRNGKey(seed % (1 << 31))
    return jax.random.fold_in(key, seed >> 31)


def stream(key, rows: int, seq: int, vocab: int, zipf_exponent: float,
           markov_mix: float):
    """``[rows, seq + 1]`` int32 token ids below ``vocab``."""
    k1, k2 = jax.random.split(key)
    u = jax.random.uniform(k1, (rows, seq + 1))
    zipf = jnp.minimum((1.0 / jnp.maximum(u, 1e-6)) ** zipf_exponent,
                       float(vocab)) - 1
    base = zipf.astype(jnp.int32) % vocab
    mix = jax.random.bernoulli(k2, markov_mix, (rows, seq + 1))
    rolled = (base * 31 + 7) % vocab
    return jnp.where(mix, rolled, base)


def rows_per_round(mix: dict, n_workers: int) -> int:
    return mix["rows_per_worker"] * n_workers


def tokens_per_round(mix: dict, n_workers: int) -> int:
    return rows_per_round(mix, n_workers) * mix["seq"]


def round_streams(key, rounds, mix: dict, vocab: int, n_workers: int):
    """``[len(rounds), rows, seq + 1]``: the streams of the given rounds,
    from ``key = seed_key(seed)``."""
    data_key = jax.random.fold_in(key, 1)
    rows = rows_per_round(mix, n_workers)

    def one(r):
        return stream(jax.random.fold_in(data_key, r), rows, mix["seq"], vocab,
                      mix["zipf_exponent"], mix["markov_mix"])

    return jax.vmap(one)(jnp.asarray(rounds, jnp.int32))


def split(s):
    """A ``[..., seq + 1]`` stream as the step's ``{"tokens", "targets"}``."""
    return {"tokens": s[..., :-1], "targets": s[..., 1:]}
