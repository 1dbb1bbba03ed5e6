"""Shared benchmark substrate: the paper's two models (regularized logistic
regression; 1-hidden-layer ReLU network) on the synthetic MNIST-like mixture
(the container is offline), M = 10 workers, paper hyperparameters."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import CriterionConfig
from repro.data import classification_dataset, split_workers

M_WORKERS = 10
LAMBDA = 0.01
PAPER_CRITERION = CriterionConfig(D=10, xi=0.8 / 10, t_bar=100)


def make_dataset(n_per_class=60, seed=0, heterogeneity=0.0):
    X, Y = classification_dataset(jax.random.PRNGKey(seed), n_per_class=n_per_class)
    Xw, Yw = split_workers(X, Y, M_WORKERS, heterogeneity=heterogeneity)
    return (Xw, Yw), (X, Y)


def logreg_loss(n_total):
    def loss_fn(params, data):
        x, y = data
        logits = x @ params["w"].T
        ce = -jnp.sum(y * jax.nn.log_softmax(logits, -1))
        return (ce + 0.5 * LAMBDA * jnp.sum(params["w"] ** 2)) / n_total
    return loss_fn


def logreg_init():
    return {"w": jnp.zeros((10, 784))}


def nn_loss(n_total):
    """784 -> 200 ReLU -> 10, regularized (paper Sec. G)."""
    def loss_fn(params, data):
        x, y = data
        h = jax.nn.relu(x @ params["w1"] + params["b1"])
        logits = h @ params["w2"] + params["b2"]
        ce = -jnp.sum(y * jax.nn.log_softmax(logits, -1))
        reg = 0.5 * LAMBDA * (jnp.sum(params["w1"] ** 2) + jnp.sum(params["w2"] ** 2))
        return (ce + reg) / n_total
    return loss_fn


def nn_init(seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {
        "w1": jax.random.normal(k1, (784, 200)) * (784 ** -0.5),
        "b1": jnp.zeros((200,)),
        "w2": jax.random.normal(k2, (200, 10)) * (200 ** -0.5),
        "b2": jnp.zeros((10,)),
    }


def accuracy_logreg(params, X, Y):
    pred = jnp.argmax(X @ params["w"].T, -1)
    return float(jnp.mean((pred == jnp.argmax(Y, -1)).astype(jnp.float32)))


def accuracy_nn(params, X, Y):
    h = jax.nn.relu(X @ params["w1"] + params["b1"])
    pred = jnp.argmax(h @ params["w2"] + params["b2"], -1)
    return float(jnp.mean((pred == jnp.argmax(Y, -1)).astype(jnp.float32)))
