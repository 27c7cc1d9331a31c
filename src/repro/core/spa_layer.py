"""SPA-Cache transformer block (paper Algorithm 1) + layer orchestration.

Phase 1 — update identification & selection: project current (normed)
inputs to identifier vectors, score cosine drift against the cached
identifiers, select the top-k most-drifted rows (k = rho(l) * N from the
adaptive budget).

Phase 2 — attention with partially cached KV: recompute Q/K/V only for
selected rows, scatter K/V into the cache, attend sparse queries against
the full (partially refreshed) KV cache.

Phase 3 — FFN & output update: run FFN/MoE on the selected rows, scatter
into the output cache H^c; the layer output is the refreshed H^c.

Execution modes:
  * unrolled  — exact per-layer k (small models, hybrids)
  * bucketed  — contiguous layer buckets with shared k compiled as
                ``lax.scan`` segments (full-size models; DESIGN.md §4.4)

The kernel-shaped stages of every phase (identification, gather+norm,
attention, commits) dispatch through ``strategy.backend`` — a
``KernelBackend`` (DESIGN.md §4.5): XLA ops by default, the Pallas TPU
kernel suite with ``PallasBackend`` (selection/top-k always stays XLA).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ATTENTION_KINDS, ModelConfig
from repro.core import budget, cache as cache_lib, identifiers, selection
from repro.core.cache import CachePolicy
from repro.core.strategy import CacheStrategy, resolve_strategy
from repro.models import common
from repro.models.transformer import (apply_block_dense, apply_ffn_or_moe,
                                      layer_window, qkv_project)

Params = Dict[str, Any]


def _hint_cache_slice(cache_sl: Dict[str, jax.Array], b: int,
                      skip: Tuple[str, ...] = ()) -> Dict[str, jax.Array]:
    """Keep cache buffers sequence-sharded over "model" after scatters
    (GSPMD otherwise materializes replicated copies per layer). For
    batch=1 long-context the sequence spans all axes.  ``skip`` names
    buffers left untouched (paged arenas have no batch/sequence axes)."""
    from repro.distributed.hints import shard_hint
    n_spec = ("pod", "data", "model") if b == 1 else "model"
    b_spec = None if b == 1 else "batch"
    out = {}
    for key, arr in cache_sl.items():
        if key in skip:
            out[key] = arr
            continue
        dims = (b_spec, n_spec) + (None,) * (arr.ndim - 2)
        out[key] = shard_hint(arr, *dims)
    return out


def stratify_blocks_for(n: int, k: int) -> int:
    """Number of strata so that every q block's position span is bounded.

    With per-block top-(k/nb) selection over nb equal blocks, any
    ``block_q`` consecutive selected rows span at most
    ``ceil(block_q / (k/nb)) + 1`` strata, i.e. <= span_bound positions.
    We pick nb so each stratum is ~4096 positions.
    """
    if n <= 8192:
        return 0
    nb = max(1, n // 4096)
    while n % nb:
        nb -= 1
    return nb


def q_span_bound(n: int, k: int, nb: int, block_q: int = 512) -> int:
    if nb <= 1:
        return 0
    per = max(1, k // nb)
    stratum = n // nb
    n_strata_per_block = (block_q + per - 1) // per + 1
    return n_strata_per_block * stratum


def _mask_tail_scores(scores: jax.Array, n: int,
                      kv_len: Optional[jax.Array]) -> jax.Array:
    """Rows past a request's valid canvas length never select: their
    similarity is forced to +inf (LOW = drifted = update, so +inf is
    'never update') — shared by both identifier paths so the paged
    selection semantics cannot drift between them.

    Caveat: ``select_stratified`` (long-context windowed path,
    n > 8192) takes a fixed per-block quota regardless of score, so
    strata wholly past ``kv_len`` still select dead rows — state stays
    correct (zero-page commits drop, attention masks them) but a short
    row's refresh budget dilutes.  Per-row dynamic stratification needs
    dynamic shapes; until then keep paged canvases <= the stratify
    threshold or window-free (DESIGN.md §5)."""
    if kv_len is None:
        return scores
    return jnp.where(jnp.arange(n)[None, :] < kv_len[:, None],
                     scores, jnp.inf)


def _identifier_scores(strategy: CacheStrategy, bp: Params, proxy_mat, x,
                       cache_sl, scores_override, prev_idx=None,
                       page_table=None):
    """Returns (scores, p_now_full_or_None, proxy_now_cache_or_None).

    Projection + drift scoring run on ``strategy.backend`` — the fused
    Pallas identification kernel on ``PallasBackend``, jnp ops on
    ``XlaBackend`` (DESIGN.md §4.5).  With ``page_table`` the cached
    identifiers are a pooled page arena (DESIGN.md §5) and scoring reads
    them through page-table indirection.

    Incremental mode (beyond-paper, DESIGN.md §6): only rows whose
    INPUTS changed (= rows refreshed by the previous layer, or newly
    committed tokens at layer 0) can have drifted proxies, so the rank-r
    projection runs on those k rows instead of all N — identification HBM
    traffic drops from N*d to k*d per layer.  The full-N rescore against
    the cached identifiers is the backend's score-only pass."""
    backend = strategy.backend
    if scores_override is not None:
        return scores_override, None, None
    if (strategy.incremental and prev_idx is not None
            and "proxy_now" in cache_sl):
        rows = selection.gather_rows(x, prev_idx)   # x = scaled h
        p_rows = strategy.project(rows, bp, proxy_mat)
        proxy_now = selection.scatter_rows(cache_sl["proxy_now"],
                                           prev_idx, p_rows)
        scores = backend.score_drift(
            strategy, proxy_now.astype(jnp.float32), cache_sl["proxy"],
            page_table=page_table)
        return scores, None, proxy_now
    scores, p_now = backend.identifier_scores(strategy, bp, proxy_mat, x,
                                              cache_sl["proxy"],
                                              page_table=page_table)
    return scores, p_now, None


def spa_attn_block(cfg: ModelConfig, kind: str, bp: Params,
                   proxy_mat: Optional[jax.Array],
                   cache_sl: Dict[str, jax.Array], h: jax.Array,
                   k_upd: int, policy: CachePolicy,
                   strategy: Optional[CacheStrategy] = None,
                   scores_override: Optional[jax.Array] = None,
                   prev_idx: Optional[jax.Array] = None,
                   page_table: Optional[jax.Array] = None,
                   kv_len: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, Dict[str, jax.Array], jax.Array,
                              jax.Array]:
    """One SPA-Cache attention block step. h: [B,N,d] current inputs.
    Returns (h_out, new_cache, aux, selected_idx).

    Paged serving (DESIGN.md §5): with ``page_table`` the ``proxy``
    buffer in ``cache_sl`` is a pooled page arena (identification and
    proxy commits go through page-table indirection); ``kv_len`` [B]
    marks each row's valid canvas length — rows past it never select
    (scores forced to +inf) and never attend (masked K/V)."""
    strategy = resolve_strategy(cfg, strategy)
    b, n, d = h.shape
    w = layer_window(cfg, kind)

    if strategy.full_attn_ident:
        return _attn_out_identifier_block(
            cfg, kind, bp, cache_sl, h, k_upd, policy, strategy,
            page_table=page_table, kv_len=kv_len)

    # ---- Phase 1: identification & selection ----
    # Cosine drift is invariant to per-row scale, so the rms division of
    # the pre-attention norm is mathematically irrelevant for the
    # identifier: score on h * (1 + norm_weight) directly and rms-norm
    # only the k SELECTED rows afterwards. This keeps the full-sequence
    # tensor in bf16 (the gather's cross-shard all-reduce halves) and
    # skips an N*d norm per layer.
    with jax.named_scope("spa_identify"):
        ident_in = h * (1.0 + bp["norm1"]).astype(h.dtype)
        scores, p_now, proxy_now = _identifier_scores(
            strategy, bp, proxy_mat, ident_in, cache_sl, scores_override,
            prev_idx, page_table=page_table)
        scores = _mask_tail_scores(scores, n, kv_len)
        nb = stratify_blocks_for(n, k_upd) if w > 0 else 0
        if nb > 1:
            idx = selection.select_stratified(scores, k_upd, nb)
            span = q_span_bound(n, k_upd, nb)
        else:
            idx = selection.select_topk_drift(scores, k_upd)
            span = 0
    k_eff = idx.shape[1]

    # ---- Phase 2: attention with partially cached KV ----
    # NOTE §Perf: sharding the selected rows over "model" here was
    # MEASURED WORSE (7x compute): GSPMD lowers a cross-shard gather with
    # sharded output to a one-hot matmul (B*k*N*d FLOPs). Rows stay
    # replicated over "model"; the gather costs one all-reduce per layer.
    # The backend's gather_norm emits BOTH the raw rows (residual) and
    # the rms-normed rows (QKV input) in one pass over the k rows.
    with jax.named_scope("spa_attend"):
        h_rows, x_rows = strategy.backend.gather_norm(h, idx, bp["norm1"],
                                                      cfg.norm_eps)
        q, k_new, v_new = qkv_project(bp, x_rows, cfg, idx)
        cache_sl = strategy.commit_kv(cache_sl, idx, k_new, v_new, policy)
        kf, vf, ks, vs = cache_lib.read_kv_for_attention(cache_sl, policy)
        attn = strategy.backend.attention(
            q, kf, vf, k_scale=ks, v_scale=vs, q_positions=idx, window=w,
            soft_cap=cfg.attn_softcap, banded=(w > 0 and span > 0),
            q_span=span, kv_len=kv_len)
        from repro.distributed.hints import shard_hint
        attn_out = shard_hint(attn.reshape(b, k_eff, cfg.q_dim) @ bp["wo"],
                              "batch", "keep", None)
        if cfg.post_norms:
            attn_out = common.rms_norm(attn_out, bp["norm_post_attn"],
                                       cfg.norm_eps)
        h_mid = h_rows + attn_out

    # ---- Phase 3: FFN & output update ----
    with jax.named_scope("spa_ffn"):
        y = common.rms_norm(h_mid, bp["norm2"], cfg.norm_eps)
        ffn_out, aux = apply_ffn_or_moe(bp, y, cfg)
        if cfg.post_norms:
            ffn_out = common.rms_norm(ffn_out, bp["norm_post_ffn"],
                                      cfg.norm_eps)
        y_rows = h_mid + ffn_out
        cache_sl = strategy.commit(cache_sl, idx, y_rows, policy,
                                   p_now=p_now, proxy_now=proxy_now,
                                   page_table=page_table)

    cache_sl = _hint_cache_slice(
        cache_sl, b, skip=(("proxy",) if page_table is not None else ()))
    h_out = cache_lib.read_h_full(cache_sl, policy, h.dtype)
    # sequence-parallel residual stream between layers (decode): the
    # identification / gathers / FFN are row-local; only attention and
    # top-k cross shards.
    from repro.distributed.hints import shard_hint
    n_spec = ("pod", "data", "model") if b == 1 else "model"
    h_out = shard_hint(h_out, None if b == 1 else "batch", n_spec, None)
    return h_out, cache_sl, aux, idx


def _attn_out_identifier_block(cfg, kind, bp, cache_sl, h, k_upd, policy,
                               strategy, page_table=None, kv_len=None):
    """Table-1 'attn output' identifier: full attention is computed for ALL
    rows against the (stale) cached KV purely for identification; only the
    FFN runs sparsely. Matches the paper's cost profile (slower than the
    value proxy, still much faster than vanilla)."""
    b, n, d = h.shape
    w = layer_window(cfg, kind)
    with jax.named_scope("spa_identify"):
        x = common.rms_norm(h, bp["norm1"], cfg.norm_eps)
        positions = jnp.broadcast_to(jnp.arange(n)[None], (b, n))
        q_all, k_all, v_all = qkv_project(bp, x, cfg, positions)
        kf, vf, ks, vs = cache_lib.read_kv_for_attention(cache_sl, policy)
        attn_all = strategy.backend.attention(
            q_all, kf, vf, k_scale=ks, v_scale=vs, window=w,
            soft_cap=cfg.attn_softcap, banded=(w > 0), kv_len=kv_len)
        attn_all = attn_all.reshape(b, n, cfg.q_dim) @ bp["wo"]
        if cfg.post_norms:
            attn_all = common.rms_norm(attn_all, bp["norm_post_attn"],
                                       cfg.norm_eps)
        scores = strategy.backend.score_drift(strategy, attn_all,
                                              cache_sl["proxy"],
                                              page_table=page_table)
        scores = _mask_tail_scores(scores, n, kv_len)
        idx = selection.select_topk_drift(scores, k_upd)

    with jax.named_scope("spa_attend"):
        cache_sl = strategy.commit_kv(
            cache_sl, idx, selection.gather_rows(k_all, idx),
            selection.gather_rows(v_all, idx), policy)
        h_mid = selection.gather_rows(h, idx) + selection.gather_rows(
            attn_all, idx)
    with jax.named_scope("spa_ffn"):
        y = common.rms_norm(h_mid, bp["norm2"], cfg.norm_eps)
        ffn_out, aux = apply_ffn_or_moe(bp, y, cfg)
        if cfg.post_norms:
            ffn_out = common.rms_norm(ffn_out, bp["norm_post_ffn"],
                                      cfg.norm_eps)
        y_rows = h_mid + ffn_out
        cache_sl = strategy.commit(cache_sl, idx, y_rows, policy,
                                   attn_all=attn_all, page_table=page_table)
    cache_sl = _hint_cache_slice(
        cache_sl, b, skip=(("proxy",) if page_table is not None else ()))
    h_out = cache_lib.read_h_full(cache_sl, policy, h.dtype)
    return h_out, cache_sl, aux, idx


# ---------------------------------------------------------------------------
# Whole-model serve forward
# ---------------------------------------------------------------------------

def _homogeneous_attention(cfg: ModelConfig) -> bool:
    kinds = set(cfg.layer_pattern)
    return len(kinds) == 1 and next(iter(kinds)) in ATTENTION_KINDS


def spa_forward(params: Params, cfg: ModelConfig,
                cache: Dict[str, Dict[str, jax.Array]], h: jax.Array,
                spa_proxies: Optional[Dict[str, jax.Array]] = None,
                scores_override: Optional[jax.Array] = None,
                changed_idx: Optional[jax.Array] = None,
                strategy: Optional[CacheStrategy] = None,
                backend=None,
                page_table: Optional[jax.Array] = None,
                kv_len: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Dict, jax.Array]:
    """Run all blocks with the given CacheStrategy on attention layers.

    cache: {kind: {name: [Lk, B, N, ...]}} (from ``init_model_cache`` or
    prefill). changed_idx [B, c]: positions whose INPUT rows changed since
    the previous step (newly committed tokens) — used by the incremental
    identifier. strategy defaults to ``cfg.spa`` resolved through the
    registry; ``backend`` (a KernelBackend or "xla"/"pallas") overrides
    the strategy's kernel backend for this call. Returns (h_final,
    new_cache, aux).

    Paged serving (DESIGN.md §5): ``page_table`` [B, n_log] marks the
    ``proxy`` buffers in ``cache`` as pooled page arenas
    ([Lk, P, page, r]); ``kv_len`` [B] is each row's valid canvas length
    (selection + attention mask the tail).
    """
    strategy = resolve_strategy(cfg, strategy)
    if backend is not None:
        strategy = strategy.with_backend(backend)
    policy = CachePolicy.from_config(cfg)
    b, n = h.shape[0], h.shape[1]
    ks = strategy.k_schedule(cfg, n)
    k_max = max(ks)
    uses_proxy_mat = strategy.uses_proxy_mat
    aux_total = jnp.zeros((), jnp.float32)

    incremental = strategy.incremental and scores_override is None

    def pad_idx(idx):
        """Pad/clip an index set to [B, k_max] with sentinel n."""
        if idx is None:
            return jnp.full((b, k_max), n, jnp.int32)
        idx = idx.astype(jnp.int32)
        idx = jnp.where(idx < 0, n, idx)       # -1 ring slots -> sentinel
        if idx.shape[1] >= k_max:
            return idx[:, :k_max]
        return jnp.pad(idx, ((0, 0), (0, k_max - idx.shape[1])),
                       constant_values=n)

    prev = pad_idx(changed_idx) if incremental else None

    if (_homogeneous_attention(cfg) and cfg.scan_layers
            and cfg.n_layers >= 8 and scores_override is None):
        # Each segment scans over absolute layer ids: the body reads layer
        # ``l_idx`` of the full weight and proxy stacks and of the WHOLE
        # cache stack it carries, and updates that layer in place
        # (dynamic_update_slice).  A segment sliced out of a stack before
        # the scan is materialised every step; a dynamic_slice inside the
        # body fuses into the dot that reads it.  While-loop carries
        # update in place under XLA buffer donation, so the multi-GB cache
        # stacks exist ONCE.
        kind = cfg.layer_pattern[0]
        blocks = params["blocks"][kind]
        prox_stack = (spa_proxies[kind]
                      if uses_proxy_mat and spa_proxies else None)
        cache_kind = cache[kind]

        def layer_of(t, l_idx):
            return jax.lax.dynamic_index_in_dim(t, l_idx, 0, keepdims=False)

        for (a, b_end, kseg) in budget.bucketize(ks, strategy.n_buckets):

            def body(carry, l_idx, _kseg=kseg):
                if incremental:
                    h_c, aux_c, cache_c, prev_c = carry
                else:
                    h_c, aux_c, cache_c = carry
                    prev_c = None
                bp_l = jax.tree.map(lambda t: layer_of(t, l_idx), blocks)
                pm = (layer_of(prox_stack, l_idx)
                      if prox_stack is not None else None)
                csl = jax.tree.map(lambda t: layer_of(t, l_idx), cache_c)
                h_c, csl_new, aux, idx = spa_attn_block(
                    cfg, kind, bp_l, pm, csl, h_c, _kseg, policy,
                    strategy, prev_idx=prev_c, page_table=page_table,
                    kv_len=kv_len)
                cache_c = jax.tree.map(
                    lambda t, sl: jax.lax.dynamic_update_index_in_dim(
                        t, sl.astype(t.dtype), l_idx, 0),
                    cache_c, csl_new)
                if incremental:
                    return (h_c, aux_c + aux, cache_c,
                            pad_idx(idx)), None
                return (h_c, aux_c + aux, cache_c), None

            layer_ids = jnp.arange(a, b_end, dtype=jnp.int32)
            init = (h, aux_total, cache_kind, prev) if incremental \
                else (h, aux_total, cache_kind)
            carry, _ = jax.lax.scan(body, init, layer_ids)
            if incremental:
                h, aux_total, cache_kind, prev = carry
            else:
                h, aux_total, cache_kind = carry
        return h, {kind: cache_kind}, aux_total

    # Unrolled path: exact per-layer k; hybrid / SSM blocks recompute fully.
    per_kind_new: Dict[str, List] = {}
    for l in range(cfg.n_layers):
        kind = cfg.kind_of_layer(l)
        ki = cfg.kind_index(l)
        bp = jax.tree.map(lambda t: t[ki], params["blocks"][kind])
        if kind in ATTENTION_KINDS and strategy.uses_cache:
            csl = jax.tree.map(lambda t: t[ki], cache[kind])
            prox = (spa_proxies[kind][ki]
                    if uses_proxy_mat and spa_proxies else None)
            h, csl_new, aux, idx = spa_attn_block(
                cfg, kind, bp, prox, csl, h, ks[l], policy, strategy,
                scores_override=scores_override, prev_idx=prev,
                page_table=page_table, kv_len=kv_len)
            if incremental:
                prev = pad_idx(idx)
            per_kind_new.setdefault(kind, []).append(csl_new)
            aux_total = aux_total + aux
        else:
            h, aux, _ = apply_block_dense(cfg, kind, bp, h, kv_len=kv_len)
            aux_total = aux_total + aux
            # recurrent blocks recompute everything: downstream inputs all
            # changed -> fall back to full identification next layer
            if incremental and kind not in ATTENTION_KINDS:
                prev = None   # full identification next attention layer
            if kind in cache:  # identifier "none": keep cache untouched
                per_kind_new.setdefault(kind, []).append(
                    jax.tree.map(lambda t: t[ki], cache[kind]))
    new_cache = {
        kind: jax.tree.map(lambda *xs: jnp.stack(xs), *slices)
        for kind, slices in per_kind_new.items()
    }
    return h, new_cache, aux_total


def build_spa_proxies(params: Params, cfg: ModelConfig,
                      strategy: Optional[CacheStrategy] = None
                      ) -> Optional[Dict[str, jax.Array]]:
    """Offline proxy stacks {kind: [Lk,d,r]} for the resolved strategy
    (SVD of value projections for SPACache; None for every other)."""
    return resolve_strategy(cfg, strategy).build_proxies(params, cfg)


def spa_proxy_specs(cfg: ModelConfig,
                    strategy: Optional[CacheStrategy] = None
                    ) -> Optional[Dict[str, Any]]:
    """ShapeDtypeStructs of the proxy stacks (for the dry-run)."""
    return resolve_strategy(cfg, strategy).proxy_specs(cfg)
