"""The launch layer's meshes.

Functions, not module-level constants: importing this module must never
touch jax device state (a caller may still set XLA_FLAGS, e.g. forced host
devices, before jax initialises).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def auto_axes(mesh):
    """``mesh`` with every axis Auto.

    The launch layer shards by PartitionSpec and leaves the rest to GSPMD
    (params over ``model``, batches over the worker axes).  ``jax.make_mesh``
    builds Explicit axes by default, under which sharding is part of every
    array's type and the model's embedding gather and einsums over
    ``model``-sharded weights are rejected as ambiguous.
    """
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def device_mesh(model: int = 1, n_devices: int | None = None):
    """A ``("data", "model")`` mesh over the first ``n_devices`` devices
    (all by default), ``model`` devices per model-parallel group, with Auto
    axes."""
    devices = jax.devices()[:n_devices]
    n = len(devices)
    return auto_axes(jax.make_mesh((n // model, model), ("data", "model"),
                                   devices=devices))


def worker_axes_of(mesh, *, hierarchical: bool = False):
    """LAQ worker granularity: flat = every data shard is a worker;
    hierarchical = pods are workers (intra-pod full-precision psum)."""
    names = mesh.axis_names
    if "pod" in names:
        return ("pod",) if hierarchical else ("pod", "data")
    return ("data",)


def n_workers_of(mesh, worker_axes) -> int:
    n = 1
    for a in worker_axes:
        n *= mesh.shape[a]
    return n
