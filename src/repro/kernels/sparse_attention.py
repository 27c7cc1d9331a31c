"""Pallas kernel: gathered-query flash attention vs a full KV cache.

SPA-Cache Phase 2 on TPU: k selected query rows attend to the whole
(partially refreshed) KV cache. Flash-style online softmax with the
running (m, l, acc) state held in VMEM scratch across the sequential
kv-block grid dimension. Supports GQA (kv head = q head // G),
bidirectional sliding windows (query positions are arbitrary gathered
indices), gemma2 attention-logit softcap, int8 KV with per-row dequant
scales, a real batch grid axis, and the stratified long-context banded
path: with ``banded=True`` and a static ``q_span`` bound (guaranteed by
stratified selection — DESIGN.md §4) each q block visits only the
``band_width`` kv blocks covering its window, starting at a per-q-block
offset delivered through TPU scalar prefetch (the same
``banded_starts`` the XLA path uses, so the two paths select identical
kv blocks and stay byte-identical).

Numerics mirror ``models.attention.flash_attention`` op-for-op (scale
applied after the QK dot, masking before the running-max update, f32
state) so the backends decode byte-identically.

Grid: (B, H, nq, nk_or_band) — the kv axis minor (sequential on TPU), so
VMEM scratch carries the softmax state per (batch, head, q-block).
Layout: q, k, v and the output keep their [B, S, heads*hd] row layout
(no transposes in XLA); a block is one head's (rows, hd) column slab, so
hd must be a multiple of 128 on a TPU.  Query positions arrive as a
[B, kq, 1] column and int8 dequant scales as their natural [B, N, KVH]
rows (the head's column is selected in VMEM); the per-row ``kv_len``
and the banded kv starts are scalar-prefetched.  VMEM per step: bq*hd
(q) + 2*bk*hd (kv) + bq*bk (scores) + scratch, double-buffered — (512,
512) blocks with hd = 128 stay under ~4 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _head_scale(s_ref, kv_head):
    """[bk, 1] f32 dequant scale of one kv head from a [bk, KVH] block
    (an exact masked select: a dynamic lane slice does not lower)."""
    blk = s_ref[0].astype(jnp.float32)
    sel = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1) == kv_head
    return jnp.max(jnp.where(sel, blk, -jnp.inf), axis=1, keepdims=True)


def _attn_step(qpos, q, k, v, ks, vs, o_ref, m_scr, l_scr, acc_scr, *,
               kv_base, j, nj, window: int, soft_cap: float,
               n_valid: int, scale: float, kv_limit):
    """One kv-block online-softmax update (shared by both grid flavors).

    ``kv_limit`` (scalar int32) is the batch row's valid canvas length
    (paged serving): kv positions >= kv_limit mask out exactly like the
    global ``n_valid`` pad bound, mirroring the XLA path's per-row
    ``kv_len`` mask op-for-op.  ``qpos`` is [bq, 1]; ``ks``/``vs`` are
    [bk, 1] scales or None."""

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qf = q.astype(jnp.float32)                        # [bq, hd]
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if ks is not None:
        kf = kf * ks
        vf = vf * vs

    s = jax.lax.dot_general(qf, kf, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if soft_cap > 0.0:
        s = soft_cap * jnp.tanh(s / soft_cap)

    kv_pos = kv_base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = jnp.logical_and(kv_pos < n_valid, kv_pos < kv_limit)
    if window > 0:
        valid = jnp.logical_and(valid, jnp.abs(qpos - kv_pos) <= window)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                               # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(valid, p, 0.0)
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_new))
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc_scr[...] * alpha + jax.lax.dot_general(
        p, vf, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(j == nj - 1)
    def _finalize():
        l_safe = jnp.where(l_scr[...] == 0.0, 1.0, l_scr[...])
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def _kernel(*refs, banded: bool, scaled: bool, nj: int, bk: int, g: int,
            window: int, soft_cap: float, n_valid: int, scale: float):
    if banded:
        starts_ref, kvl_ref, *refs = refs
    else:
        kvl_ref, *refs = refs
    if scaled:
        qpos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, *scr = refs
    else:
        qpos_ref, q_ref, k_ref, v_ref, o_ref, *scr = refs
    bb, hh, i, j = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                    pl.program_id(3))
    kvb = starts_ref[i] + j if banded else j
    ks = vs = None
    if scaled:
        ks, vs = _head_scale(ks_ref, hh // g), _head_scale(vs_ref, hh // g)
    _attn_step(qpos_ref[0], q_ref[0], k_ref[0], v_ref[0], ks, vs, o_ref,
               *scr, kv_base=kvb * bk, j=j, nj=nj, window=window,
               soft_cap=soft_cap, n_valid=n_valid, scale=scale,
               kv_limit=kvl_ref[bb])


def sparse_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     q_pos: jax.Array, *, k_scale=None, v_scale=None,
                     window: int = 0, soft_cap: float = 0.0,
                     banded: bool = False, q_span: int = 0,
                     block_q: int = 512, block_k: int = 512,
                     kv_len=None, interpret: bool = False) -> jax.Array:
    """q: [B, kq, H, hd]; k/v: [B, N, KVH, hd]; q_pos: [B, kq]
    (2D/3D unbatched forms also accepted).  k_scale/v_scale: [B, N, KVH]
    or None.  ``banded`` + ``q_span`` enable the stratified banded path
    (requires window > 0).  ``kv_len``: [B] per-row valid canvas length
    (None = N).  Returns [B, kq, H, hd] in q.dtype."""
    unbatched = q.ndim == 3
    if unbatched:
        q, k, v, q_pos = q[None], k[None], v[None], q_pos[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
        if kv_len is not None:
            kv_len = kv_len[None]
    b, kq, h, hd = q.shape
    n, kvh = k.shape[1], k.shape[2]
    assert h % kvh == 0
    g = h // kvh
    scale = 1.0 / (hd ** 0.5)
    scaled = k_scale is not None

    bq = min(block_q, kq)
    bk = min(block_k, n)
    pad_q = (-kq) % bq
    pad_k = (-n) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)),
                        constant_values=2 ** 30)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        if scaled:
            k_scale = jnp.pad(k_scale, ((0, 0), (0, pad_k), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, pad_k), (0, 0)))
    kq_p, skv_p = q.shape[1], k.shape[1]
    nq, nk = kq_p // bq, skv_p // bk
    q_pos = q_pos.astype(jnp.int32)
    kv_len = (jnp.full((b,), n, jnp.int32) if kv_len is None
              else kv_len.astype(jnp.int32))

    # [B, S, heads, hd] -> [B, S, heads*hd]: free reshapes, one head per
    # (rows, hd) block
    operands = [q_pos[..., None], q.reshape(b, kq_p, h * hd),
                k.reshape(b, skv_p, kvh * hd), v.reshape(b, skv_p, kvh * hd)]
    if scaled:
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32)]

    use_band = (banded and window > 0 and q_span > 0
                and n > (q_span + 2 * window + 2 * bk))
    if use_band:
        from repro.models.attention import band_width, banded_starts
        nj = band_width(q_span, window, bk, nk)
        prefetch = [banded_starts(q_pos.reshape(b, nq, bq), window, skv_p,
                                  nj, bk), kv_len]

        def kv_block(i, j, st):
            return st[i] + j
    else:
        nj = nk
        prefetch = [kv_len]

        def kv_block(i, j, st):
            return j

    def q_map(bb, hh, i, j, *pre):
        return (bb, i, hh)

    def kv_map(bb, hh, i, j, *pre):
        return (bb, kv_block(i, j, pre[0]), hh // g)

    def scale_map(bb, hh, i, j, *pre):
        return (bb, kv_block(i, j, pre[0]), 0)

    in_specs = [
        pl.BlockSpec((1, bq, 1), lambda bb, hh, i, j, *pre: (bb, i, 0)),
        pl.BlockSpec((1, bq, hd), q_map),
        pl.BlockSpec((1, bk, hd), kv_map),
        pl.BlockSpec((1, bk, hd), kv_map),
    ]
    if scaled:
        in_specs += [pl.BlockSpec((1, bk, kvh), scale_map)] * 2
    out = pl.pallas_call(
        functools.partial(_kernel, banded=use_band, scaled=scaled, nj=nj,
                          bk=bk, g=g, window=window, soft_cap=soft_cap,
                          n_valid=n, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, h, nq, nj),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bq, hd), q_map),
            scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, hd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kq_p, h * hd), q.dtype),
        interpret=interpret,
        name="sparse_attention",
    )(*prefetch, *operands)

    out = out.reshape(b, kq_p, h, hd)[:, :kq]        # [B, kq, H, hd]
    return out[0] if unbatched else out
