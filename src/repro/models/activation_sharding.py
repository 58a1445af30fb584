"""Activation sharding constraints (MaxText-style logical-axis annotations).

XLA SPMD propagation, left alone, may legally replicate activations (it
optimizes its own cost model) — at 512 devices that turns per-device temps
into global-batch temps.  The model code annotates activations with LOGICAL
axes via ``shard_act``; the launcher activates a (mesh, rules) context inside
the traced step function so annotations lower to
``jax.lax.with_sharding_constraint`` pins.  Without an active context (unit
tests, single device) annotations are no-ops.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax

_CTX = threading.local()


@contextlib.contextmanager
def activation_sharding(mesh, rules):
    prev = getattr(_CTX, "val", None)
    _CTX.val = (mesh, rules)
    try:
        yield
    finally:
        _CTX.val = prev


def shard_act(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Constrain ``x`` to the mesh axes the logical ``axes`` map to."""
    ctx = getattr(_CTX, "val", None)
    if ctx is None or x is None:
        return x
    mesh, rules = ctx
    if len(axes) != x.ndim:
        raise ValueError(f"axes {axes} rank != array rank {x.ndim}")
    return jax.lax.with_sharding_constraint(x, rules.sharding(mesh, axes))


def mesh_axes_of(axis: str):
    """(mesh, mesh axes) the logical ``axis`` maps to in the active context;
    None without a context or where the axis is not sharded."""
    ctx = getattr(_CTX, "val", None)
    if ctx is None:
        return None
    mesh, rules = ctx
    entry = rules.sharding(mesh, (axis,)).spec[0]
    if entry is None:
        return None
    return mesh, tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)
