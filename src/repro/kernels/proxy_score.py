"""Pallas kernels for SPA-Cache Phase 1 (identification) hot spots.

``proxy_score``: the paper's identification kernel (Fig. 4): p = x @ W_r
followed by a rowwise cosine similarity against the cached identifiers.
On GPU these are two kernels with an HBM round-trip for p; on TPU we fuse
them — x streams HBM -> VMEM once per block, the projection runs on the
MXU, and the similarity reduction runs on the VPU while the block is
still resident.  The batch dimension is a real grid axis.

``cosine_drift``: the projection-free variant (attn_in identifier, the
incremental-identifier full-N rescore): same single pass over the rows,
no matmul.

``gather_norm``: Phase-1 epilogue — the k SELECTED rows are gathered
from the full residual stream and rms-normed in one pass, emitting both
the raw rows (for the residual add) and the normed rows (for QKV).

Numerics follow the XLA serve path: the projection accumulates in f32,
rounds through the storage dtype, and the cosine is computed on the
ROUNDED p.  In interpret mode on CPU ``PallasBackend`` decodes
byte-identically to ``XlaBackend`` (tests/test_backend_parity.py); on a
TPU the two backends reduce in different orders and agree to rounding.

TPU layout rules these kernels are written to (Mosaic refuses anything
else): the last two dims of every VMEM block are multiples of (8, 128)
or equal the array's dims, so scores leave the kernel as a [B, N, 1]
column (the lane reduction's natural layout) and are squeezed by XLA;
index arrays ride in SMEM through scalar prefetch; and a dynamic row of
a [.., N, d] array cannot be sliced out of HBM by a DMA (a DMA moves
whole (8|16, 128) tiles), so ``gather_norm`` DMAs the aligned tile that
holds each row and picks the row out of it in VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def sublane_tile(dtype) -> int:
    """Rows per (sublane, 128) tile of ``dtype`` in TPU memory: 8 for
    32-bit types, 16 for 16-bit, 32 for 8-bit."""
    return 32 // jnp.dtype(dtype).itemsize


def _cosine(p: jax.Array, pc: jax.Array, eps: float) -> jax.Array:
    num = jnp.sum(p * pc, axis=-1, keepdims=True)
    den = jnp.sqrt(jnp.sum(p * p, axis=-1, keepdims=True)
                   * jnp.sum(pc * pc, axis=-1, keepdims=True))
    return num / jnp.maximum(den, eps)


def _project_and_score(x_ref, w_ref, pc, scores_ref, pnow_ref, eps):
    x = x_ref[0].astype(jnp.float32)             # [bn, d]
    w = w_ref[...].astype(jnp.float32)           # [d, r]
    p = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # round p through the storage dtype BEFORE scoring — the XLA path
    # scores the projection it commits, and byte-parity of the
    # selections requires scoring the same values.
    p_store = p.astype(pnow_ref.dtype)
    pnow_ref[0] = p_store
    scores_ref[0] = _cosine(p_store.astype(jnp.float32),
                            pc.astype(jnp.float32), eps)


def _proxy_score_kernel(x_ref, w_ref, pc_ref, scores_ref, pnow_ref, *,
                        eps: float):
    _project_and_score(x_ref, w_ref, pc_ref[0], scores_ref, pnow_ref, eps)


def _cosine_drift_kernel(x_ref, pc_ref, scores_ref, *, eps: float):
    scores_ref[0] = _cosine(x_ref[0].astype(jnp.float32),
                            pc_ref[0].astype(jnp.float32), eps)


def proxy_score_block_n(d: int, r: int, n: int = 0,
                        vmem_budget: int = 8 * 2 ** 20) -> int:
    """Rows per proxy_score block: what fits ``vmem_budget`` (x double-
    buffered plus its f32 copy, W_r, the f32 projection), rounded DOWN to
    a multiple of 128 (the lane width: scores leave as a 128-aligned
    column block).  With ``n`` the block prefers a divisor of n (no pad
    rows) and is n itself when n fits whole."""
    per_row = (3 * d + 3 * r) * 4
    fit = (vmem_budget - d * r * 4) // max(per_row, 1)
    bn = max(128, (fit // 128) * 128)
    if not n:
        return bn
    if n <= bn:
        return n
    for cand in range(bn, 127, -128):
        if n % cand == 0:
            return cand
    return bn


def _batched(*arrays):
    """Add a size-1 batch axis to 2D inputs (legacy unbatched callers)."""
    return tuple(a if a is None or a.ndim == 3 else a[None]
                 for a in arrays)


def _pad_rows(n: int, bn: int, *arrays):
    pad = (-n) % bn
    if not pad:
        return arrays
    return tuple(jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in arrays)


def proxy_score(x: jax.Array, proxy_mat: jax.Array, p_cached: jax.Array,
                *, eps: float = 1e-8, block_n: int = 0,
                interpret: bool = False):
    """x: [B, N, d] (or [N, d]); proxy_mat: [d, r]; p_cached: [B, N, r].
    Returns (scores [B, N] f32, p_now [B, N, r] in x.dtype)."""
    unbatched = x.ndim == 2
    x, p_cached = _batched(x, p_cached)
    b, n, d = x.shape
    r = proxy_mat.shape[1]
    bn = min(block_n or proxy_score_block_n(d, r, n), n)
    x, p_cached = _pad_rows(n, bn, x, p_cached)
    n_p = x.shape[1]

    scores, p_now = pl.pallas_call(
        functools.partial(_proxy_score_kernel, eps=eps),
        grid=(b, n_p // bn),
        in_specs=[
            pl.BlockSpec((1, bn, d), lambda bb, i: (bb, i, 0)),
            pl.BlockSpec((d, r), lambda bb, i: (0, 0)),
            pl.BlockSpec((1, bn, r), lambda bb, i: (bb, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn, 1), lambda bb, i: (bb, i, 0)),
            pl.BlockSpec((1, bn, r), lambda bb, i: (bb, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n_p, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, n_p, r), x.dtype),
        ],
        interpret=interpret,
        name="proxy_score",
    )(x, proxy_mat, p_cached)
    scores, p_now = scores[:, :n, 0], p_now[:, :n]
    return (scores[0], p_now[0]) if unbatched else (scores, p_now)


def cosine_drift(x: jax.Array, p_cached: jax.Array, *, eps: float = 1e-8,
                 block_n: int = 0, interpret: bool = False) -> jax.Array:
    """Projection-free drift: cosine(x, p_cached) per row.
    x, p_cached: [B, N, r] (or [N, r]).  Returns [B, N] f32."""
    unbatched = x.ndim == 2
    x, p_cached = _batched(x, p_cached)
    b, n, r = x.shape
    bn = min(block_n or proxy_score_block_n(r, r, n), n)
    x, p_cached = _pad_rows(n, bn, x, p_cached)
    n_p = x.shape[1]

    scores = pl.pallas_call(
        functools.partial(_cosine_drift_kernel, eps=eps),
        grid=(b, n_p // bn),
        in_specs=[
            pl.BlockSpec((1, bn, r), lambda bb, i: (bb, i, 0)),
            pl.BlockSpec((1, bn, r), lambda bb, i: (bb, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn, 1), lambda bb, i: (bb, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n_p, 1), jnp.float32),
        interpret=interpret,
        name="cosine_drift",
    )(x, p_cached)
    scores = scores[:, :n, 0]
    return scores[0] if unbatched else scores


# ---------------------------------------------------------------------------
# Paged variants (DESIGN.md §5): the cached identifier vectors live in a
# pooled page arena [P, page, r] addressed through a per-row page table
# rather than a dense [B, N, r] buffer.  The page table is scalar-
# prefetched into SMEM; each grid step starts one whole-page DMA per
# logical page of its block into a contiguous VMEM buffer, runs the
# projection while they land, then scores the block exactly as the dense
# kernel does — so paging adds indirection but no extra HBM round-trip,
# and the scores equal the dense kernel's at the same block size.
# ---------------------------------------------------------------------------


def _fetch_pages(pt_ref, a_ref, buf, sem, *, base, ppb: int, page: int):
    """Copy arena pages pt[base : base+ppb] into buf [ppb*page, r];
    returns the copy descriptors (callers wait on them)."""
    copies = []
    for t in range(ppb):
        cp = pltpu.make_async_copy(
            a_ref.at[pt_ref[base + t]],
            buf.at[pl.ds(t * page, page)], sem)
        cp.start()
        copies.append(cp)
    return copies


def _proxy_score_paged_kernel(pt_ref, x_ref, w_ref, a_ref, scores_ref,
                              pnow_ref, buf, sem, *, eps: float, ppb: int,
                              page: int, n_log: int):
    base = pl.program_id(0) * n_log + pl.program_id(1) * ppb
    copies = _fetch_pages(pt_ref, a_ref, buf, sem, base=base, ppb=ppb,
                          page=page)
    for cp in copies:
        cp.wait()
    _project_and_score(x_ref, w_ref, buf[...], scores_ref, pnow_ref, eps)


def _pages_per_block(n_log: int, page: int, d: int, r: int) -> int:
    ppb = max(1, proxy_score_block_n(d, r) // page)
    ppb = min(ppb, n_log)
    while n_log % ppb:
        ppb -= 1
    return ppb


def proxy_score_paged(x: jax.Array, proxy_mat: jax.Array,
                      arena: jax.Array, pt: jax.Array, *,
                      eps: float = 1e-8, interpret: bool = False):
    """Fused Phase-1 identification against a PAGED identifier cache.

    x: [B, N, d]; proxy_mat: [d, r]; arena: [P, page, r] pooled pages;
    pt: [B, n_log] page table (N == n_log * page).  Returns
    (scores [B, N] f32, p_now [B, N, r] in x.dtype) — the values of
    gathering the pages dense and calling :func:`proxy_score` with
    ``block_n = pages_per_block * page``."""
    b, n, d = x.shape
    page, r = arena.shape[1], arena.shape[2]
    n_log = pt.shape[1]
    assert n == n_log * page, (n, n_log, page)
    ppb = _pages_per_block(n_log, page, d, r)
    bn = ppb * page

    scores, p_now = pl.pallas_call(
        functools.partial(_proxy_score_paged_kernel, eps=eps, ppb=ppb,
                          page=page, n_log=n_log),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_log // ppb),
            in_specs=[
                pl.BlockSpec((1, bn, d), lambda bb, i, pt_: (bb, i, 0)),
                pl.BlockSpec((d, r), lambda bb, i, pt_: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, bn, 1), lambda bb, i, pt_: (bb, i, 0)),
                pl.BlockSpec((1, bn, r), lambda bb, i, pt_: (bb, i, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((bn, r), arena.dtype),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, n, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, n, r), x.dtype),
        ],
        interpret=interpret,
        name="proxy_score_paged",
    )(pt.astype(jnp.int32).reshape(-1), x, proxy_mat, arena)
    return scores[..., 0], p_now


def _cosine_drift_paged_kernel(pt_ref, x_ref, a_ref, scores_ref, buf, sem,
                               *, eps: float, ppb: int, page: int,
                               n_log: int):
    base = pl.program_id(0) * n_log + pl.program_id(1) * ppb
    copies = _fetch_pages(pt_ref, a_ref, buf, sem, base=base, ppb=ppb,
                          page=page)
    for cp in copies:
        cp.wait()
    scores_ref[0] = _cosine(x_ref[0].astype(jnp.float32),
                            buf[...].astype(jnp.float32), eps)


def cosine_drift_paged(x: jax.Array, arena: jax.Array, pt: jax.Array, *,
                       eps: float = 1e-8,
                       interpret: bool = False) -> jax.Array:
    """Projection-free paged drift: cosine(x[b, n], page(n)) per row.
    x: [B, N, r]; arena: [P, page, r]; pt: [B, n_log].  Returns [B, N]
    f32 — the dense gather + :func:`cosine_drift` at the same block."""
    b, n, r = x.shape
    page = arena.shape[1]
    n_log = pt.shape[1]
    assert n == n_log * page, (n, n_log, page)
    ppb = _pages_per_block(n_log, page, r, r)
    bn = ppb * page

    scores = pl.pallas_call(
        functools.partial(_cosine_drift_paged_kernel, eps=eps, ppb=ppb,
                          page=page, n_log=n_log),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_log // ppb),
            in_specs=[
                pl.BlockSpec((1, bn, r), lambda bb, i, pt_: (bb, i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, bn, 1),
                                   lambda bb, i, pt_: (bb, i, 0)),
            scratch_shapes=[pltpu.VMEM((bn, r), arena.dtype),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n, 1), jnp.float32),
        interpret=interpret,
        name="cosine_drift_paged",
    )(pt.astype(jnp.int32).reshape(-1), x, arena)
    return scores[..., 0]


def _pick_row(t32: jax.Array, off) -> jax.Array:
    """Row ``off`` (dynamic) of an f32 [tile, d] value as [1, d]: rotate
    it to row 0 (a sublane roll, exact) and slice statically."""
    tile = t32.shape[0]
    if tile == 1:
        return t32
    return pltpu.roll(t32, (tile - off) % tile, 0)[0:1]


def _gather_norm_kernel(idx_ref, w_ref, h_ref, rows_ref, normed_ref,
                        tiles, rows32, sem, *, eps: float, gb: int,
                        tile: int, kp: int):
    """Row j of the block lives in the aligned ``tile``-row slab of h
    that holds it: DMA that slab (double-buffered: slab j+1 is in flight
    while row j is picked out), then select the row in f32 VMEM (a
    dynamic one-row read is only addressable on 32-bit data)."""
    bb = pl.program_id(0)
    base = bb * kp + pl.program_id(1) * gb

    def slab(j, slot):
        ri = idx_ref[base + j]
        start = pl.multiple_of((ri // tile) * tile, tile)
        return ri - start, pltpu.make_async_copy(
            h_ref.at[bb, pl.ds(start, tile)], tiles.at[slot],
            sem.at[slot])

    slab(0, 0)[1].start()

    def body(j, carry):
        slot = j % 2

        @pl.when(j + 1 < gb)
        def _prefetch():
            slab(j + 1, 1 - slot)[1].start()

        off, cp = slab(j, slot)
        cp.wait()
        rows32[pl.ds(j, 1), :] = _pick_row(
            tiles[slot].astype(jnp.float32), off)
        return carry

    jax.lax.fori_loop(0, gb, body, 0)
    rf = rows32[...]                                        # [gb, d] f32
    rows_ref[0] = rf.astype(rows_ref.dtype)
    var = jnp.mean(jnp.square(rf), axis=-1, keepdims=True)
    normed = (rf * jax.lax.rsqrt(var + eps)) * (1.0 + w_ref[...])
    normed_ref[0] = normed.astype(normed_ref.dtype)


def gather_norm(h: jax.Array, idx: jax.Array, weight: jax.Array,
                eps: float = 1e-6, *, block_g: int = 128,
                interpret: bool = False):
    """Fused gathered-row rms_norm (Phase-1 epilogue).

    h: [B, N, d]; idx: [B, k] (out-of-range clamps like a "clip"-mode
    gather); weight: [d] rms_norm scale.  Returns (rows [B, k, d] raw,
    normed [B, k, d]) — one pass over the k selected rows.
    """
    b, n, d = h.shape
    k = idx.shape[1]
    idx = jnp.clip(idx.astype(jnp.int32), 0, n - 1)
    gb = min(block_g, k)
    pad = (-k) % gb
    if pad:
        idx = jnp.pad(idx, ((0, 0), (0, pad)))   # clamped dupes, sliced off
    kp = idx.shape[1]
    tile = math.gcd(sublane_tile(h.dtype), n)

    rows, normed = pl.pallas_call(
        functools.partial(_gather_norm_kernel, eps=eps, gb=gb, tile=tile,
                          kp=kp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kp // gb),
            in_specs=[
                pl.BlockSpec((1, d), lambda bb, i, ix: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, gb, d), lambda bb, i, ix: (bb, i, 0)),
                pl.BlockSpec((1, gb, d), lambda bb, i, ix: (bb, i, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((2, tile, d), h.dtype),
                            pltpu.VMEM((gb, d), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, kp, d), h.dtype),
            jax.ShapeDtypeStruct((b, kp, d), h.dtype),
        ],
        interpret=interpret,
        name="gather_norm",
    )(idx.reshape(-1), weight.astype(jnp.float32).reshape(1, d), h)
    return rows[:, :k], normed[:, :k]
