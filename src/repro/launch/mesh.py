"""Production meshes.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  512 chips as (pod=2, data=16, model=16) — the "pod" axis is the
federation boundary: client data-parallelism extends across pods while
weights stay replicated over "pod", and QAFeL's quantized hidden-state
broadcast is what crosses it.

Defined as functions (never module-level constants) so importing this
module touches no jax device state — required for the dry-run's
host-device-count trick to work.

Every axis is Auto: sharding propagates through GSPMD and ``shard_map``
bodies, which the flat substrate's reshapes rely on (``jax.make_mesh``
defaults to Explicit axes, under which those reshapes are type errors).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """A 1x1 mesh on whatever single device is present (CPU smoke tests)."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_sim_mesh(n_dev: int | None = None):
    """A 1-D ("data",) mesh for the sharded flat substrate.

    This is the mesh the host-level protocol (``QAFeL(..., mesh=)``, the
    cohort engine, the fused flush) shards over: cohort members and flat
    state segments both live on "data". ``n_dev=None`` uses every local
    device — 8 under the CI job's
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` trick, 1 on a
    plain CPU (where the sharded path still runs, as a one-segment
    shard_map, and stays bit-identical to the unsharded one).
    """
    if n_dev is None:
        n_dev = jax.device_count()
    if n_dev > jax.device_count():
        raise ValueError(
            f"make_sim_mesh({n_dev}) but only {jax.device_count()} device(s) "
            "are visible; set XLA_FLAGS=--xla_force_host_platform_device_count"
            f"={n_dev} before importing jax to fake them on CPU")
    return _auto_mesh((n_dev,), ("data",))


def make_sim_mesh2d(shape: tuple[int, int] | None = None):
    """A 2-D ("data","model") mesh for the LLM-scale flat substrate.

    The flat server state shards over BOTH axes (bucket-row segments,
    data-major — ``sharding.rules.flat_axes``); cohort members shard over
    "data" only while each member's packed codes shard their row dim over
    "model". ``shape=None`` puts every local device on "data" (the 1-D
    layout, as a 2-D mesh). Same visibility rule / XLA_FLAGS hint as
    ``make_sim_mesh``.
    """
    if shape is None:
        shape = (jax.device_count(), 1)
    n_data, n_model = shape
    if n_data * n_model > jax.device_count():
        raise ValueError(
            f"make_sim_mesh2d({shape}) needs {n_data * n_model} devices but "
            f"only {jax.device_count()} are visible; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n_data * n_model} before importing jax to fake them on CPU")
    return _auto_mesh((n_data, n_model), ("data", "model"))
