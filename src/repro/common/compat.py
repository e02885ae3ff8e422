"""JAX API shims.

``shard_map`` takes the manual axes as ``axis_names`` (a set); callers pass
any iterable.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None, check_vma=None):
    """``jax.shard_map`` with optional keywords left at jax's defaults.

    axis_names: axes that are Manual inside ``f`` (others stay auto/GSPMD).
    check_vma:  replication checking.
    """
    kwargs = {}
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)
