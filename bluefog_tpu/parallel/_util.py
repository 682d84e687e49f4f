"""Shared helpers for the parallel strategies."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def vma_full(ref, shape, dtype, fill=0.0):
    """A constant array carrying ``ref``'s varying-manual-axes type.

    The safe way to build sentinels/inits inside ``shard_map``: fresh
    ``jnp.full`` constants are unvarying-typed and fail vma checks against
    compute branches, while operand arithmetic (``ref * 0.0``) propagates
    NaN whenever ``ref`` contains inf.  Outside a trace this is just
    ``jnp.full``.
    """
    z = jnp.full(shape, fill, dtype)
    vma = tuple(jax.typeof(ref).vma)
    return lax.pcast(z, vma, to="varying") if vma else z


def pvary(x, axis_name):
    """Re-type a value as varying over ``axis_name`` under shard_map's
    varying-manual-axes checking.  ``pcast`` refuses an axis the value
    already varies over, so only the missing axes are cast."""
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    have = jax.typeof(x).vma
    missing = tuple(a for a in axes if a not in have)
    return lax.pcast(x, missing, to="varying") if missing else x


def resolve_axis_size(axis_name: str, axis_size) -> int:
    """Validate ``axis_size`` against the mesh axis it names.

    Inside a shard_map/pmap trace the bound axis size is authoritative: a
    stale ``axis_size`` argument would otherwise produce silently wrong
    causal masks (ring) or an opaque XLA dimension error (ulysses
    all_to_all).  Outside a trace the axis is unbound and the passed value
    is all we have.  ``axis_size=None`` means "no caller claim": allowed
    inside a trace, an error outside one.
    """
    try:
        n = lax.axis_size(axis_name)
    except NameError:
        if axis_size is None:
            raise
        return axis_size
    if axis_size is not None and axis_size != n:
        raise ValueError(
            f"axis_size={axis_size} does not match the actual size of mesh "
            f"axis {axis_name!r} ({n})"
        )
    return n
