"""KernelBackend — pluggable kernel dispatch for the serve hot path.

The per-step hot loop of ``core.spa_layer.spa_attn_block`` has four
kernel-shaped stages: Phase-1 identification (projection + drift
scoring), the Phase-1 epilogue (gather + rms_norm of the selected
rows), Phase-2 gathered-query attention, and the Phase-2/3 cache
commits (row scatters).  A :class:`KernelBackend` owns all four, so the
whole layer step runs either through pure-XLA ops or through the Pallas
TPU kernel suite — selected per ``DecodeSession``/``spa_forward`` call
and threaded through ``CacheStrategy`` (a frozen-dataclass field), so
jitted steps close over the backend statically exactly like strategies
and schedulers: switching backend retraces once, switching request does
not.

  ``XlaBackend``    — the current jnp ops (the oracle; default).
  ``PallasBackend`` — TPU kernels (``kernels/*``); interpret mode on
                      CPU, where it decodes byte-identically to
                      ``XlaBackend`` for every registered strategy and
                      scheduler (tests/test_backend_parity.py) because
                      the kernels mirror the XLA numerics op-for-op.
                      On a TPU the two reduce in different orders and
                      are not promised identical tokens.

Dispatch rules (DESIGN.md §4.5): top-k/stratified SELECTION always
stays in XLA (tiny, latency-bound, and ``jax.lax.top_k`` is already
optimal on TPU); the Pallas identification path engages only when the
strategy's projection is a plain matrix (``projection_matrix``) or the
identity, and only when the strategy keeps the base cosine ``score`` —
anything else falls back to the strategy's own ops, so custom
strategies stay correct on either backend.
"""
from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Dict, Optional

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class KernelBackend:
    """Protocol base: the four hot-path stages of one SPA layer step."""

    name: ClassVar[str] = "abstract"

    def identifier_scores(self, strategy, bp: Params, proxy_mat,
                          x: jax.Array, p_cached: jax.Array,
                          page_table: Optional[jax.Array] = None):
        """Phase 1: project x and score drift. Returns (scores, p_now).

        With ``page_table`` ([B, n_log] int32), ``p_cached`` is a pooled
        page arena [P, page, r] instead of a dense [B, N, r] buffer
        (DESIGN.md §5): scoring reads the cached identifiers through
        page-table indirection."""
        raise NotImplementedError

    def score_drift(self, strategy, p_now: jax.Array,
                    p_cached: jax.Array,
                    page_table: Optional[jax.Array] = None) -> jax.Array:
        """Score-only drift (incremental rescore, attn_out momentum).
        ``page_table`` as in :meth:`identifier_scores`."""
        raise NotImplementedError

    def gather_norm(self, h: jax.Array, idx: jax.Array,
                    weight: jax.Array, eps: float):
        """Phase-1 epilogue: returns (rows [B,k,d], rms-normed rows)."""
        raise NotImplementedError

    def attention(self, q, k, v, *, k_scale=None, v_scale=None,
                  q_positions=None, window: int = 0, soft_cap: float = 0.0,
                  banded: bool = False, q_span: int = 0,
                  kv_len=None) -> jax.Array:
        """Phase 2: (gathered-)query flash attention vs the KV cache.
        ``kv_len`` [B]: per-row valid canvas length (paged serving)."""
        raise NotImplementedError

    def scatter_multi(self, buffers: Dict[str, jax.Array], idx: jax.Array,
                      rows: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        """Phase 2/3 commit: scatter row payloads into cache buffers."""
        raise NotImplementedError

    # -- paged cache pool stages (DESIGN.md §5) ---------------------

    def gather_pages(self, arena: jax.Array,
                     page_table: jax.Array) -> jax.Array:
        """arena [L, P, page, ...] + page table [B, n_log] -> dense view
        [L, B, n_log*page, ...]."""
        raise NotImplementedError

    def scatter_pages(self, arena: jax.Array, page_table: jax.Array,
                      dense: jax.Array) -> jax.Array:
        """Write a dense view back through the page table (writes to the
        reserved zero page are dropped)."""
        raise NotImplementedError

    def scatter_rows_paged(self, arena: jax.Array, page_table: jax.Array,
                           idx: jax.Array, rows: jax.Array) -> jax.Array:
        """Commit row payloads [B, k, ...] at logical canvas rows idx
        [B, k] into ONE layer's pooled arena [P, page, ...] through the
        page table (zero-page / out-of-range rows dropped)."""
        raise NotImplementedError

    # -- shared fallback helpers ------------------------------------

    @staticmethod
    def _base_score(strategy) -> bool:
        """Whether the strategy keeps the protocol's cosine ``score``."""
        from repro.core.strategy import CacheStrategy
        return type(strategy).score is CacheStrategy.score


@dataclasses.dataclass(frozen=True)
class XlaBackend(KernelBackend):
    """Pure-jnp ops (the oracle): exactly the pre-backend serve path."""

    name: ClassVar[str] = "xla"

    def identifier_scores(self, strategy, bp, proxy_mat, x, p_cached,
                          page_table=None):
        if page_table is not None:
            p_cached = self.gather_pages(p_cached[None], page_table)[0]
        p_now = strategy.project(x, bp, proxy_mat)
        return strategy.score(p_now, p_cached), p_now

    def score_drift(self, strategy, p_now, p_cached, page_table=None):
        if page_table is not None:
            p_cached = self.gather_pages(p_cached[None], page_table)[0]
        return strategy.score(p_now, p_cached)

    def gather_norm(self, h, idx, weight, eps):
        from repro.core import selection
        from repro.models import common
        rows = selection.gather_rows(h, idx)
        return rows, common.rms_norm(rows, weight, eps)

    def attention(self, q, k, v, *, k_scale=None, v_scale=None,
                  q_positions=None, window=0, soft_cap=0.0, banded=False,
                  q_span=0, kv_len=None):
        from repro.models.attention import flash_attention
        return flash_attention(q, k, v, k_scale=k_scale, v_scale=v_scale,
                               q_positions=q_positions, window=window,
                               soft_cap=soft_cap, banded=banded,
                               q_span=q_span, kv_len=kv_len)

    def scatter_multi(self, buffers, idx, rows):
        from repro.core import selection
        return {name: selection.scatter_rows(buffers[name], idx, r)
                for name, r in rows.items()}

    def gather_pages(self, arena, page_table):
        shape = arena.shape
        l, page = shape[0], shape[2]
        b, n_log = page_table.shape
        out = jnp.take(arena, page_table, axis=1)   # [L, B, n_log, page, .]
        return out.reshape((l, b, n_log * page) + shape[3:])

    def scatter_pages(self, arena, page_table, dense):
        shape = arena.shape
        l, p, page = shape[0], shape[1], shape[2]
        b, n_log = page_table.shape
        dense = dense.reshape((l, b, n_log, page) + shape[3:])
        # zero-page writes route out of bounds and drop (page 0 is the
        # pool's reserved all-zero page, shared by every short row's tail)
        pt_w = jnp.where(page_table > 0, page_table, p).astype(jnp.int32)
        return arena.at[:, pt_w].set(dense.astype(arena.dtype),
                                     mode="drop")

    def scatter_rows_paged(self, arena, page_table, idx, rows):
        shape = arena.shape
        p, page = shape[0], shape[1]
        b, n_log = page_table.shape
        idx = idx.astype(jnp.int32)
        lpage = idx // page
        pid = jnp.take_along_axis(
            page_table.astype(jnp.int32),
            jnp.clip(lpage, 0, n_log - 1), axis=1)
        phys = pid * page + idx % page
        # drop: sentinel / out-of-range logical rows and zero-page rows
        ok = jnp.logical_and(jnp.logical_and(idx >= 0, lpage < n_log),
                             pid > 0)
        phys = jnp.where(ok, phys, p * page)
        flat = arena.reshape((p * page,) + shape[2:])
        out = flat.at[phys.reshape(-1)].set(
            rows.reshape((-1,) + flat.shape[1:]).astype(arena.dtype),
            mode="drop")
        return out.reshape(shape)


@dataclasses.dataclass(frozen=True)
class PallasBackend(KernelBackend):
    """The Pallas TPU kernel suite on the hot path.

    ``interpret=None`` resolves per process (``ops.default_interpret``):
    real Mosaic lowering on a TPU backend, interpret mode on CPU (CPU CI
    validates the TPU program logic), an error on anything else.  ``block_q``/``block_k`` mirror the XLA flash
    defaults so the online-softmax block structure — and therefore the
    f32 accumulation order — is identical across backends.
    """

    interpret: Optional[bool] = None
    block_q: int = 512
    block_k: int = 512

    name: ClassVar[str] = "pallas"

    def _interp(self) -> bool:
        if self.interpret is not None:
            return self.interpret
        from repro.kernels.ops import default_interpret
        return default_interpret()

    def identifier_scores(self, strategy, bp, proxy_mat, x, p_cached,
                          page_table=None):
        from repro.kernels import proxy_score as ps
        if not self._base_score(strategy):
            return XLA_BACKEND.identifier_scores(strategy, bp, proxy_mat,
                                                 x, p_cached,
                                                 page_table=page_table)
        mat = strategy.projection_matrix(bp, proxy_mat)
        if page_table is not None:
            if mat is not None:
                return ps.proxy_score_paged(x, mat, p_cached, page_table,
                                            interpret=self._interp())
            p_now = strategy.project(x, bp, proxy_mat)
            if p_now is x:  # identity projection: paged score-only
                return ps.cosine_drift_paged(
                    x, p_cached, page_table,
                    interpret=self._interp()), p_now
            p_dense = self.gather_pages(p_cached[None], page_table)[0]
            return strategy.score(p_now, p_dense), p_now
        if mat is not None:
            return ps.proxy_score(x, mat, p_cached,
                                  interpret=self._interp())
        p_now = strategy.project(x, bp, proxy_mat)
        if p_now is x:      # identity projection (attn_in): score-only
            return ps.cosine_drift(x, p_cached,
                                   interpret=self._interp()), p_now
        # inexpressible projection: strategy's own ops (stays correct)
        return strategy.score(p_now, p_cached), p_now

    def score_drift(self, strategy, p_now, p_cached, page_table=None):
        from repro.kernels import proxy_score as ps
        if not self._base_score(strategy):
            if page_table is not None:
                p_cached = self.gather_pages(p_cached[None],
                                             page_table)[0]
            return strategy.score(p_now, p_cached)
        if page_table is not None:
            return ps.cosine_drift_paged(p_now, p_cached, page_table,
                                         interpret=self._interp())
        return ps.cosine_drift(p_now, p_cached, interpret=self._interp())

    def gather_norm(self, h, idx, weight, eps):
        from repro.kernels import proxy_score as ps
        return ps.gather_norm(h, idx, weight, eps,
                              interpret=self._interp())

    def attention(self, q, k, v, *, k_scale=None, v_scale=None,
                  q_positions=None, window=0, soft_cap=0.0, banded=False,
                  q_span=0, kv_len=None):
        from repro.kernels import sparse_attention as sa
        b, sq = q.shape[:2]
        if q_positions is None:     # contiguous canvas: span = q block
            q_positions = jnp.broadcast_to(jnp.arange(sq)[None], (b, sq))
            q_span = min(self.block_q, sq)
        return sa.sparse_attention(
            q, k, v, q_positions, k_scale=k_scale, v_scale=v_scale,
            window=window, soft_cap=soft_cap, banded=banded,
            q_span=q_span, block_q=self.block_q, block_k=self.block_k,
            kv_len=kv_len, interpret=self._interp())

    def scatter_multi(self, buffers, idx, rows):
        from repro.kernels import scatter_update as sc
        names = sorted(rows)        # deterministic kernel operand order
        outs = sc.scatter_update_multi(
            [buffers[n] for n in names], idx, [rows[n] for n in names],
            interpret=self._interp())
        return dict(zip(names, outs))

    def gather_pages(self, arena, page_table):
        from repro.kernels import scatter_update as sc
        return sc.gather_pages(arena, page_table,
                               interpret=self._interp())

    def scatter_pages(self, arena, page_table, dense):
        from repro.kernels import scatter_update as sc
        return sc.scatter_pages(arena, page_table, dense,
                                interpret=self._interp())

    def scatter_rows_paged(self, arena, page_table, idx, rows):
        from repro.kernels import scatter_update as sc
        return sc.scatter_rows_paged(arena, page_table, idx, rows,
                                     interpret=self._interp())


XLA_BACKEND = XlaBackend()
PALLAS_BACKEND = PallasBackend()

REGISTRY: Dict[str, KernelBackend] = {
    "xla": XLA_BACKEND,
    "pallas": PALLAS_BACKEND,
}


def resolve_backend(backend) -> KernelBackend:
    """Accept a KernelBackend instance or a registry name."""
    if isinstance(backend, str):
        try:
            return REGISTRY[backend]
        except KeyError:
            raise ValueError(f"unknown kernel backend {backend!r}; "
                             f"registered: {sorted(REGISTRY)}") from None
    return backend
