"""DLM iterative-unmasking decoding primitives with pluggable caching.

  prefill    — full forward over the canvas that populates all layer caches
               (K, V, H^c, identifier vectors) per the CacheStrategy.
  serve_step — ONE diffusion refinement step: sparse layer updates driven
               by the strategy, candidate-limited logit evaluation, and
               the commit decision delegated to an ``UnmaskScheduler``
               (greedy confidence / Fast-dLLM parallel / entropy /
               stochastic / random-order / semi-AR blocks).

The step LOOP (prefill + jitted step + periodic refresh) lives in
``repro.dlm.session.DecodeSession``; ``decode`` and ``decode_semi_ar``
below are thin compatibility wrappers over it.

All caching policy dispatch goes through ``core.strategy.CacheStrategy``
(DESIGN.md §2) and all commit policy through
``dlm.scheduler.UnmaskScheduler`` (DESIGN.md §2.5) — this module never
inspects identifier strings or branches on schedule flags itself.

Candidate-limited logits: computing lm-head logits over the full 32k/500k
canvas each step would dominate all other costs, so logits are evaluated
only at ``n_candidates`` masked positions per step (a serving design
choice documented in DESIGN.md §3).

Active-position masks: ``DecodeState.active`` [B, N_text] bool marks the
canvas positions a session is allowed to commit. Slots outside a
request's prompt+gen span (serving) or outside the current semi-AR block
stay ``active=False`` — token ids are never overloaded as sentinels
(token 0 is a legal vocab id).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ATTENTION_KINDS, ModelConfig
from repro.core import cache as cache_lib
from repro.core import selection, spa_layer
from repro.core.cache import CachePolicy
from repro.core.strategy import CacheStrategy, resolve_strategy
from repro.dlm.scheduler import (CommitView, UnmaskScheduler,
                                 resolve_scheduler)
from repro.models import transformer

Params = Dict[str, Any]


class DecodeState(NamedTuple):
    tokens: jax.Array            # [B, N_text] canvas (mask_id at open slots)
    cache: Any                   # {kind: {name: [Lk,B,N,...]}}
    step: jax.Array              # scalar int32
    committed: jax.Array         # [B, C] recently committed positions (-1 pad)
    n_masked: jax.Array          # [B] remaining masked counts
    active: Optional[jax.Array] = None   # [B, N_text] bool commit mask
    # None (NOT a dict literal: NamedTuple defaults are shared across
    # every instance, so a mutable {} leaks writes between sessions);
    # DecodeSession normalizes to a fresh dict at construction.
    extras: Optional[Dict[str, jax.Array]] = None  # modality stubs (VLM)
    rng: Optional[jax.Array] = None      # stochastic-scheduler key chain
    # [B] valid canvas length per row (paged serving, DESIGN.md §5):
    # attention/selection mask positions >= kv_len[b].  None = full N.
    kv_len: Optional[jax.Array] = None


@dataclasses.dataclass(frozen=True)
class DecodeSettings:
    """Per-request decode knobs (hashable: used as an engine lane key).

    ``refresh_interval`` — periodic full cache rebuilds, single-sourced
    in ``DecodeSession``:  R > 0 rebuilds every R steps, 0 falls back to
    the strategy's own default (``CacheStrategy.refresh_interval``), and
    -1 explicitly DISABLES refresh even when the strategy has one.

    ``parallel_threshold``/``max_parallel`` are the legacy spec form of
    the commit policy; ``dlm.scheduler.resolve_scheduler`` maps them to
    a ``ParallelThresholdScheduler`` (byte-identical commits).  Prefer
    passing ``scheduler=`` to the decode surfaces directly.
    """
    n_candidates: int = 64
    parallel_threshold: float = 0.0   # 0 = commit exactly 1 token / step
    max_parallel: int = 0             # cap on tokens committed per step
    refresh_interval: int = 0         # rebuild cache every R steps
    commit_ring: int = 8              # size of "recently committed" buffer


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def prefill(params: Params, cfg: ModelConfig, inputs: Dict[str, jax.Array],
            spa_proxies=None, strategy: Optional[CacheStrategy] = None,
            kv_len: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Any]:
    """Full forward building the strategy's caches. Returns (h_final, cache).

    ``kv_len`` [B] masks each row's canvas tail in attention (paged
    serving) so a short row prefills exactly as on its own canvas."""
    strategy = resolve_strategy(cfg, strategy)
    policy = CachePolicy.from_config(cfg)
    h = transformer.embed_inputs(params, cfg, inputs)
    h, _, raw = transformer.forward_hidden(
        params, cfg, h, collect_cache=True, spa_proxies=spa_proxies,
        strategy=strategy, kv_len=kv_len)
    cache = {}
    for kind, entries in (raw or {}).items():
        out: Dict[str, jax.Array] = {}
        if policy.quantized:
            out["k"], out["k_scale"] = cache_lib.quantize_rows(entries["k"])
            out["v"], out["v_scale"] = cache_lib.quantize_rows(entries["v"])
            out["h"], out["h_scale"] = cache_lib.quantize_rows(entries["h"])
        else:
            cd = policy.compute_dtype
            out["k"] = entries["k"].astype(cd)
            out["v"] = entries["v"].astype(cd)
            out["h"] = entries["h"].astype(cd)
        if "proxy" in entries:
            out["proxy"] = entries["proxy"].astype(policy.compute_dtype)
            if strategy.incremental:
                out["proxy_now"] = out["proxy"]
        cache[kind] = out
    return h, cache


def partial_prefill_supported(cfg: ModelConfig) -> bool:
    """Whether ``prefill_partial`` can reproduce cold-prefill numerics
    for this architecture: every layer must be a cache-carrying
    attention kind (a recurrent block's suffix states depend on prefix
    states that carry no cache) and window-free (the cold prefill's
    banded kv scan visits a different kv-block range than the gathered
    path, so low bits could differ).  Architectures outside this set
    still get FULL prefix hits (no forward at all) — only partial hits
    degrade to misses."""
    from repro.models.transformer import layer_window
    kinds = set(cfg.layer_kinds)
    return (kinds <= set(ATTENTION_KINDS)
            and all(layer_window(cfg, k) == 0 for k in kinds))


def prefill_partial(params: Params, cfg: ModelConfig,
                    inputs: Dict[str, jax.Array],
                    kv_view: Dict[str, Dict[str, jax.Array]],
                    suffix_start: int,
                    kv_len: Optional[jax.Array] = None,
                    spa_proxies=None,
                    strategy: Optional[CacheStrategy] = None
                    ) -> Dict[str, Dict[str, jax.Array]]:
    """Prefill ONLY canvas positions >= ``suffix_start``, reading the
    already-cached K/V for [0, suffix_start) from ``kv_view``
    ({kind: {"k"/"v": [Lk, B, N, ...]}}, a dense gather of the shared
    prefix pages — DESIGN.md §6).

    Exactness: every per-row op of the cold prefill (embedding, norms,
    QKV, FFN) is row-local, and the flash-attention kv scan visits the
    same kv blocks in the same order whether the query set is the full
    canvas or a slice — so given exact prefix K/V (same prompt, same
    row span) the suffix states match the cold prefill's suffix rows up
    to XLA op-scheduling float error (~1e-6: the cold path compiles a
    layer scan, this path an unrolled loop, and fusion grouping
    differs; asserted per strategy in ``tests/test_prefix.py``).  This
    wobble only ever reaches decode through PARTIAL prefix hits, whose
    matched pages already carry the (much larger) cross-suffix
    staleness the strategy's drift identification manages — exact
    rematches are FULL hits, a pure page copy with no forward at all,
    and those are byte-identical end-to-end (DESIGN.md §6).

    Returns the same {kind: {name: [Lk, B, N, ...]}} layout as
    :func:`prefill`, with zeros at positions < suffix_start — callers
    scatter it through a write page table whose prefix entries alias
    the zero page, so the zeros never land anywhere.

    Requires :func:`partial_prefill_supported` and a non-quantized
    cache (int8 prefix pages dequantize, breaking bit-exactness).
    """
    from repro.models.attention import flash_attention
    from repro.distributed.hints import shard_hint
    strategy = resolve_strategy(cfg, strategy)
    policy = CachePolicy.from_config(cfg)
    assert partial_prefill_supported(cfg), cfg.layer_kinds
    assert not policy.quantized, "partial prefill needs a float cache"
    assert strategy.uses_cache
    from repro.models import common

    h_full = transformer.embed_inputs(params, cfg, inputs)
    b, n = h_full.shape[0], h_full.shape[1]
    s0 = int(suffix_start)
    assert 0 < s0 < n, (s0, n)
    h = h_full[:, s0:]
    positions = jnp.broadcast_to(jnp.arange(s0, n, dtype=jnp.int32)[None],
                                 (b, n - s0))
    cd = policy.compute_dtype
    per_kind: Dict[str, Dict[str, list]] = {}
    for l in range(cfg.n_layers):
        kind = cfg.kind_of_layer(l)
        ki = cfg.kind_index(l)
        bp = jax.tree.map(lambda t: t[ki], params["blocks"][kind])
        proxy_mat = (spa_proxies[kind][ki]
                     if strategy.uses_proxy_mat and spa_proxies else None)
        x = common.rms_norm(h, bp["norm1"], cfg.norm_eps)
        q, k_new, v_new = transformer.qkv_project(bp, x, cfg, positions)
        k_all = kv_view[kind]["k"][ki].astype(cd).at[:, s0:].set(
            k_new.astype(cd))
        v_all = kv_view[kind]["v"][ki].astype(cd).at[:, s0:].set(
            v_new.astype(cd))
        attn = flash_attention(q, k_all, v_all, q_positions=positions,
                               soft_cap=cfg.attn_softcap, kv_len=kv_len)
        attn_out = shard_hint(
            attn.reshape(b, n - s0, cfg.q_dim) @ bp["wo"],
            "batch", "keep", None)
        if cfg.post_norms:
            attn_out = common.rms_norm(attn_out, bp["norm_post_attn"],
                                       cfg.norm_eps)
        h_mid = h + attn_out
        y = common.rms_norm(h_mid, bp["norm2"], cfg.norm_eps)
        ffn_out, _ = transformer.apply_ffn_or_moe(bp, y, cfg)
        if cfg.post_norms:
            ffn_out = common.rms_norm(ffn_out, bp["norm_post_ffn"],
                                      cfg.norm_eps)
        h_out = h_mid + ffn_out
        entries = {"k": k_new, "v": v_new, "h": h_out}
        prox = strategy.prefill_proxy(bp, proxy_mat, h, x, attn_out, h_out)
        if prox is not None:
            entries["proxy"] = prox
        slot = per_kind.setdefault(kind, {})
        for name, val in entries.items():
            slot.setdefault(name, []).append(val)
        h = h_out

    cache: Dict[str, Dict[str, jax.Array]] = {}
    for kind, bufs in per_kind.items():
        out: Dict[str, jax.Array] = {}
        for name, vals in bufs.items():
            stacked = jnp.stack(vals).astype(cd)        # [Lk, B, S, ...]
            full = jnp.zeros(stacked.shape[:2] + (n,) + stacked.shape[3:],
                             cd)
            out[name] = full.at[:, :, s0:].set(stacked)
        if "proxy" in out and strategy.incremental:
            out["proxy_now"] = out["proxy"]
        cache[kind] = out
    return cache


# ---------------------------------------------------------------------------
# Serve step
# ---------------------------------------------------------------------------

def _candidate_positions(tokens: jax.Array, mask_id: int, n_cand: int,
                         active: Optional[jax.Array] = None) -> jax.Array:
    """First n_cand open (masked AND active) positions per row."""
    b, n = tokens.shape
    is_masked = tokens == mask_id
    if active is not None:
        is_masked = jnp.logical_and(is_masked, active)
    score = jnp.where(is_masked, -jnp.arange(n)[None, :].astype(jnp.float32),
                      -jnp.inf)
    _, idx = jax.lax.top_k(score, min(n_cand, n))
    return jnp.sort(idx, axis=-1).astype(jnp.int32), is_masked


def serve_step(params: Params, cfg: ModelConfig, state: DecodeState,
               settings: DecodeSettings, spa_proxies=None,
               strategy: Optional[CacheStrategy] = None,
               scheduler: Optional[UnmaskScheduler] = None
               ) -> Tuple[DecodeState, Dict[str, jax.Array]]:
    """One diffusion refinement step under the resolved CacheStrategy;
    the commit decision is the resolved ``UnmaskScheduler``'s.  Fully
    device-resident (no host syncs), so ``DecodeSession.run_compiled``
    can run it inside a single ``lax.while_loop``."""
    strategy = resolve_strategy(cfg, strategy)
    scheduler = resolve_scheduler(settings, scheduler)
    tokens, cache = state.tokens, state.cache
    b = tokens.shape[0]
    mask_id = cfg.mask_id

    inputs = dict(state.extras) if state.extras else {}
    inputs["tokens"] = tokens
    h = transformer.embed_inputs(params, cfg, inputs)
    n = h.shape[1]                     # full canvas (incl. patch tokens)
    offset = n - tokens.shape[1]       # VLM: text starts after patches
    # sequence-parallel residual stream (sets the layer-scan carry
    # sharding; see spa_layer h_out hint). Measured best for SSM archs
    # too (EXPERIMENTS.md §Perf: mamba2 with replicated weights +
    # sequence sharding is 2.3x over the TP baseline and fits HBM,
    # whereas dropping the sharding trades 44 GB of replicated scan
    # buffers for zero collectives).
    from repro.distributed.hints import shard_hint
    n_spec = ("pod", "data", "model") if b == 1 else "model"
    h = shard_hint(h, None if b == 1 else "batch", n_spec, None)

    scores_override = strategy.pre_scores(n, state.committed + offset)

    # Paged cache (DESIGN.md §5): the persistent state is a pooled page
    # arena + page table.  Per step, every buffer except the identifier
    # pages is gathered into the dense compute view through the page
    # table (the identifier pages are consumed in-layer by the paged
    # identification/commit kernels), and the stepped view scatters back
    # at the end — all through strategy.backend, so XLA stays the
    # byte-identical oracle for the Pallas paged kernels.
    paged = isinstance(cache, cache_lib.PagedCache)
    with jax.named_scope("cache_view"):
        view = (cache_lib.paged_step_view(cache, backend=strategy.backend)
                if paged else cache)
    page_table = cache.page_table if paged else None

    if not strategy.uses_cache or not view:
        h, _, _ = transformer.forward_hidden(params, cfg, h,
                                             kv_len=state.kv_len)
        new_cache = cache
    else:
        h, new_view, _ = spa_layer.spa_forward(
            params, cfg, view, h, spa_proxies=spa_proxies,
            scores_override=scores_override,
            changed_idx=state.committed, strategy=strategy,
            page_table=page_table, kv_len=state.kv_len)
        with jax.named_scope("cache_commit"):
            new_cache = (cache_lib.paged_step_commit(
                cache, new_view, backend=strategy.backend)
                if paged else new_view)

    # Candidate-limited logit evaluation + commit.
    with jax.named_scope("logits"):
        cand_idx, is_masked = _candidate_positions(
            tokens, mask_id, settings.n_candidates, state.active)
        h_cand = selection.gather_rows(h, cand_idx + offset)
        logits = transformer.logits_from_hidden(params, cfg, h_cand)
        # the model must never commit the [MASK] token itself
        logits = logits.at[..., mask_id].set(-jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        conf = jnp.max(probs, axis=-1)                   # [B, n_cand]
        pred = jnp.argmax(probs, axis=-1).astype(tokens.dtype)

        cand_is_masked = selection.gather_rows(
            is_masked[..., None], cand_idx)[..., 0]
        conf = jnp.where(cand_is_masked, conf, -jnp.inf)

    # Commit decision is the scheduler's (dlm/scheduler.py).  The rng
    # chain lives in DecodeState so stochastic schedules replay exactly
    # in both the host loop and run_compiled's while_loop.
    with jax.named_scope("unmask"):
        rng_next, step_rng = state.rng, None
        if scheduler.uses_rng:
            assert state.rng is not None, \
                f"scheduler {scheduler.name!r} needs an rng: pass rng= to " \
                "DecodeSession.prefill()/attach()"
            rng_next, step_rng = jax.random.split(state.rng)
        active = state.active if state.active is not None \
            else jnp.ones_like(tokens, bool)
        view = CommitView(
            logits=logits, conf=conf, pred=pred, cand_idx=cand_idx,
            cand_open=cand_is_masked, open_mask=is_masked, active=active,
            rng=step_rng)
        commit, pred = scheduler.select_commits(view)
        commit = jnp.logical_and(commit, cand_is_masked)

        new_vals = jnp.where(commit, pred, selection.gather_rows(
            tokens[..., None], cand_idx)[..., 0])
        new_tokens = selection.scatter_rows(
            tokens[..., None], cand_idx, new_vals[..., None])[..., 0]

        committed_pos = jnp.where(commit, cand_idx, -1)
        ring = settings.commit_ring
        _, order = jax.lax.top_k(committed_pos.astype(jnp.float32),
                                 min(ring, committed_pos.shape[-1]))
        committed = jnp.take_along_axis(committed_pos, order, axis=-1)
        if committed.shape[-1] < ring:
            committed = jnp.pad(committed, ((0, 0),
                                            (0, ring - committed.shape[-1])),
                                constant_values=-1)

        n_committed = jnp.sum(commit, axis=-1)
    new_state = DecodeState(
        tokens=new_tokens, cache=new_cache, step=state.step + 1,
        committed=committed,
        n_masked=state.n_masked - n_committed,
        active=state.active, extras=state.extras, rng=rng_next,
        kv_len=state.kv_len)
    info = {"n_committed": n_committed,
            "mean_conf": jnp.mean(jnp.where(jnp.isfinite(conf), conf, 0.0)),
            # per-row finiteness of this step's hidden states, consumed
            # by the supervisor's NaN/Inf canvas guard (DESIGN.md §10).
            # Only meaningful for rows with a live request: released /
            # inactive rows legitimately go non-finite under fully
            # masked attention.
            "row_finite": jnp.all(jnp.isfinite(h), axis=(1, 2))}
    return new_state, info


# ---------------------------------------------------------------------------
# Compatibility wrappers over DecodeSession
# ---------------------------------------------------------------------------

def init_decode_state(cfg: ModelConfig, params: Params, prompt: jax.Array,
                      gen_len: int, spa_proxies=None,
                      use_cache: bool = True,
                      strategy: Optional[CacheStrategy] = None,
                      settings: Optional[DecodeSettings] = None
                      ) -> DecodeState:
    """Deprecated: use ``DecodeSession.prefill``; kept for old callers."""
    from repro.dlm.session import DecodeSession
    sess = DecodeSession(params, cfg, strategy=strategy, settings=settings,
                         spa_proxies=spa_proxies)
    return sess.prefill(prompt, gen_len, use_cache=use_cache)


def decode(params: Params, cfg: ModelConfig, prompt: jax.Array,
           gen_len: int, settings: Optional[DecodeSettings] = None,
           spa_proxies=None, max_steps: Optional[int] = None,
           strategy: Optional[CacheStrategy] = None,
           scheduler: Optional[UnmaskScheduler] = None,
           rng: Optional[jax.Array] = None
           ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Run the unmasking loop until every slot is committed.

    Deprecated signature-compatible wrapper over ``DecodeSession``."""
    from repro.dlm.session import DecodeSession
    sess = DecodeSession(params, cfg, strategy=strategy, settings=settings,
                         spa_proxies=spa_proxies, scheduler=scheduler)
    sess.prefill(prompt, gen_len, rng=rng)
    return sess.run(max_steps)


def decode_semi_ar(params: Params, cfg: ModelConfig, prompt: jax.Array,
                   gen_len: int, block_len: int = 8,
                   settings: Optional[DecodeSettings] = None,
                   spa_proxies=None,
                   strategy: Optional[CacheStrategy] = None,
                   scheduler: Optional[UnmaskScheduler] = None,
                   rng: Optional[jax.Array] = None):
    """Block-wise semi-AR decoding (Wu et al. 2025: Fast-dLLM; Ma et al.
    2025 family): the canvas is unmasked block-by-block left-to-right;
    within the active block tokens commit per the scheduler (confidence
    by default, optionally in parallel). Positions outside the active
    block are excluded through the session's active-position mask — the
    restrictive trade-off the paper contrasts with SPA-Cache's
    arbitrary-order updates (§2.2).  ``BlockScheduler`` expresses the
    same schedule as data inside the step (no host loop, compatible
    with ``run_compiled``); this wrapper keeps the host ``run_blocks``
    path, which additionally refreshes caches at block boundaries.

    Deprecated signature-compatible wrapper over
    ``DecodeSession.run_blocks``."""
    from repro.dlm.session import DecodeSession
    sess = DecodeSession(params, cfg, strategy=strategy, settings=settings,
                         spa_proxies=spa_proxies, scheduler=scheduler)
    sess.prefill(prompt, gen_len, rng=rng)
    return sess.run_blocks(block_len)
