"""jit'd public wrappers for the Pallas kernels.

``interpret=None`` resolves per process (:func:`default_interpret`): on
a CPU backend the kernels execute in interpret mode — the kernel body
runs as XLA ops, validating the TPU program logic against the pure-jnp
oracles in ref.py; on a TPU backend they compile through Mosaic; any
other backend raises.  The batched wrappers are thin jit shells over the
kernels' native batch grid axes (NOT vmaps of the unbatched forms).

Donation: ``scatter_update`` aliases the cache input to its output
INSIDE the kernel (in-place on TPU when XLA proves the buffer dead), but
the jit wrapper itself does NOT donate — callers routinely keep using
the pre-scatter array (oracle comparisons, retries), and a donated
buffer is deleted on dispatch (reading it afterwards raises).  Use
``scatter_update_donated`` on the serving path when the caller truly
hands the buffer over; tests/test_kernels.py pins both behaviours.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import (proxy_score as _ps, rglru_scan as _rg,
                           scatter_update as _sc, sparse_attention as _sa)


def default_interpret() -> bool:
    """Interpret mode on CPU, compiled kernels on TPU.  Any other
    platform raises: a process that meant to reach a TPU and fell back
    to another backend must not run the kernels as something else."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default backend is {platform!r}")


@functools.partial(jax.jit, static_argnames=("interpret",))
def proxy_score(x, proxy_mat, p_cached, interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    return _ps.proxy_score(x, proxy_mat, p_cached, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cosine_drift(x, p_cached, interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    return _ps.cosine_drift(x, p_cached, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def gather_norm(h, idx, weight, eps=1e-6, interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    return _ps.gather_norm(h, idx, weight, eps, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "soft_cap",
                                             "banded", "q_span",
                                             "interpret"))
def sparse_attention(q, k, v, q_pos, k_scale=None, v_scale=None,
                     window=0, soft_cap=0.0, banded=False, q_span=0,
                     interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    return _sa.sparse_attention(q, k, v, q_pos, k_scale=k_scale,
                                v_scale=v_scale, window=window,
                                soft_cap=soft_cap, banded=banded,
                                q_span=q_span, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def scatter_update(cache, idx, rows, interpret=None):
    """Non-donating form: ``cache`` stays readable after the call."""
    interpret = default_interpret() if interpret is None else interpret
    return _sc.scatter_update(cache, idx, rows, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def scatter_update_donated(cache, idx, rows, interpret=None):
    """Donating form: in-place on TPU; ``cache`` is DELETED on dispatch
    and must not be read afterwards."""
    interpret = default_interpret() if interpret is None else interpret
    return _sc.scatter_update(cache, idx, rows, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def scatter_update_multi(caches, idx, rows, interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    return _sc.scatter_update_multi(caches, idx, rows,
                                    interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def rglru_scan(a, b, interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    return _rg.rglru_scan(a, b, interpret=interpret)


# Batched forms: same kernels — the batch dimension is a real grid axis,
# and interpret resolves per process like every other wrapper (the old
# shims vmapped the unbatched kernels with interpret hard-coded True,
# silently running the kernel body in Python on TPU).
batched_proxy_score = proxy_score
batched_sparse_attention = sparse_attention
