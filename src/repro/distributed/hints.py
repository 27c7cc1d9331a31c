"""Sharding hints usable from model code without carrying a mesh around.

``shard_hint(x, "batch", None, ...)`` applies a with_sharding_constraint
when tracing under a mesh whose axis names are known; outside any mesh
(CPU smoke tests) it is a no-op.

Dim tokens:
  None     — replicated on this dim
  "keep"   — UNCONSTRAINED (GSPMD chooses)
  "batch"  — the activation batch axes of the current lowering; set by
             the launcher via ``batch_axes_ctx`` (e.g. ("data","model")
             for fully-sharded train batches, ("data",) for MoE / decode);
             defaults to whichever of ("pod","data") exist in the mesh.
  "model" / "data" / "pod" / tuples — those axes if present.

Every resolved axis set is divisibility-checked against the dim size and
dropped (-> replicated) when it does not divide — so the same model code
lowers for every (arch x shape x mesh) combination.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

_STATE = threading.local()


@contextlib.contextmanager
def batch_axes_ctx(axes: Optional[Tuple[str, ...]]):
    """Set the activation batch axes for hints inside this lowering."""
    prev = getattr(_STATE, "batch_axes", None)
    _STATE.batch_axes = axes
    try:
        yield
    finally:
        _STATE.batch_axes = prev


def _current_mesh():
    """The mesh of the enclosing ``with mesh:`` block, or None."""
    from jax._src import mesh as mesh_src
    pm = mesh_src.thread_resources.env.physical_mesh
    return None if pm.empty else pm


def _resolve(dim, names):
    if dim == "keep":
        return P.UNCONSTRAINED
    if dim is None:
        return None
    if dim == "batch":
        ctx = getattr(_STATE, "batch_axes", None)
        if ctx is not None:
            present = tuple(a for a in ctx if a in names)
            return present if present else None
        ba = tuple(a for a in ("pod", "data") if a in names)
        return ba if ba else None
    if isinstance(dim, str):
        return dim if dim in names else None
    if isinstance(dim, tuple):
        present = tuple(a for a in dim if a in names)
        return present if present else None
    return None


def shard_hint(x: jax.Array, *dims) -> jax.Array:
    """Constrain x's sharding; no-op outside a named mesh."""
    mesh = _current_mesh()
    if mesh is None:
        return x
    names = tuple(mesh.axis_names)
    sizes = dict(zip(names, (mesh.shape[a] for a in names)))
    if len(dims) != x.ndim:
        dims = tuple(dims) + (None,) * (x.ndim - len(dims))
    spec = []
    used: set = set()
    for i, d in enumerate(dims):
        r = _resolve(d, names)
        if r is not None and r is not P.UNCONSTRAINED:
            axes = tuple(a for a in ((r,) if isinstance(r, str) else r)
                         if a not in used)   # each axis at most once
            if not axes:
                r = None
            else:
                total = int(np.prod([sizes[a] for a in axes]))
                if x.shape[i] % total != 0:
                    r = None  # indivisible -> replicate
                else:
                    used.update(axes)
                    r = axes if len(axes) > 1 else axes[0]
        spec.append(r)
    return jax.lax.with_sharding_constraint(x, P(*spec))
