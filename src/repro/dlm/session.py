"""DecodeSession — the ONE decode loop (DESIGN.md §3).

Every decode surface in the repo (``decode``, ``decode_semi_ar``, the
benchmark timing loops, ``ServingEngine``) used to hand-roll its own
prefill + ``jax.jit(serve_step)`` + refresh loop.  ``DecodeSession``
owns all of it:

  * the canvas (tokens + active-position mask + masked counts),
  * the strategy cache and its lifecycle (prefill / periodic refresh),
  * the jitted step function (compiled once per
    (strategy, settings, scheduler) — the strategy's ``KernelBackend``
    (``backend=`` here, "xla" or "pallas") is part of that key),
  * the commit policy — an ``UnmaskScheduler`` (dlm/scheduler.py);
    legacy ``DecodeSettings.parallel_threshold`` resolves to one,
  * row-granular state surgery for continuous batching
    (``replace_rows`` — swap a finished request's slot for a queued one
    without touching sibling rows).

Refresh has ONE source of truth here: ``settings.refresh_interval`` > 0
wins, 0 falls back to the strategy's own ``refresh_interval`` default
(which ``strategy_from_spec`` lifts from ``cfg.spa.refresh_interval``),
and -1 explicitly disables refresh.

Two run modes with byte-identical outputs (asserted per scheduler in
``tests/test_scheduler.py``):

  * ``run()``        — host loop: one jitted step per iteration, a host
                       sync on ``n_masked`` per step; supports
                       streaming ``events()`` and mid-loop row surgery.
  * ``run_compiled()`` — the WHOLE loop as a single ``jax.lax.while_loop``
                       (periodic refresh folded in via ``lax.cond``):
                       no per-step dispatch, no host syncs until the
                       loop exits.  The serving hot path.

Typical use::

    sess = DecodeSession(params, cfg, strategy=SPACache(rank=16),
                         scheduler=ParallelThresholdScheduler(0.1))
    sess.prefill(prompt, gen_len)
    tokens, info = sess.run_compiled()
    # or streaming (host loop):
    for event in sess.events():
        print(event.step, event.n_committed)
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import cache as cache_lib
from repro.core import runtime
from repro.core.cache import PagedCache
from repro.core.strategy import CacheStrategy, resolve_strategy
from repro.dlm import decoding
from repro.dlm.decoding import DecodeSettings, DecodeState
from repro.dlm.scheduler import UnmaskScheduler, resolve_scheduler

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class SharedPrefix:
    """One batch row's shared-prefix attachment (DESIGN.md §6).

    ``pages``: physical pages (from the prefix index) mapped read-only
    at the row's logical pages [0, len(pages)); ``reserve``: the row's
    own private pages of the same count.  The session runs its prefill
    reads (and the partial prefill of the unmatched suffix) against
    ``pages``, then copies them into ``reserve`` and patches the page
    table immediately before its first cache write — commits never
    mutate another reader's view (copy-on-write, tests/test_prefix.py).
    """
    row: int
    pages: Tuple[int, ...]
    reserve: Tuple[int, ...]

    def __post_init__(self):
        assert len(self.pages) == len(self.reserve)


@dataclasses.dataclass(frozen=True)
class StepEvent:
    """One refinement step's outcome, for the streaming iterator."""
    step: int
    n_committed: np.ndarray      # [B] tokens committed this step
    committed: np.ndarray        # [B, ring] positions (-1 pad)
    done: bool
    refreshed: bool              # a full cache rebuild preceded this step
    # token VALUES at the committed ring positions (-1 at ring pads):
    # what a streaming consumer actually wants to print.  NOTE the ring
    # caps at ``settings.commit_ring`` positions per step — wide
    # parallel commits overflow it, so exact per-token streams should
    # diff ``tokens`` against the previous step instead (the serving
    # front-end does; DESIGN.md §8).
    committed_tokens: Optional[np.ndarray] = None
    tokens: Optional[np.ndarray] = None   # [B, N] full canvas snapshot


class DecodeSession:
    """Owns canvas, cache, jitted step, refresh and commit policy."""

    def __init__(self, params: Params, cfg: ModelConfig, *,
                 strategy: Optional[CacheStrategy] = None,
                 settings: Optional[DecodeSettings] = None,
                 scheduler: Optional[UnmaskScheduler] = None,
                 spa_proxies=None, backend=None,
                 profiler=None, label: str = ""):
        self.params = params
        self.cfg = cfg
        self.strategy = resolve_strategy(cfg, strategy)
        if backend is not None:
            # hot-path kernel dispatch (KernelBackend or "xla"/"pallas");
            # rides on the strategy so the jitted step/loop close over it
            # statically, exactly like the strategy and scheduler.
            self.strategy = self.strategy.with_backend(backend)
        self.settings = settings or DecodeSettings()
        self.scheduler = resolve_scheduler(self.settings, scheduler)
        # ONE source of truth for periodic refresh (see module docstring):
        # settings > 0 wins, 0 falls back to the strategy, -1 disables.
        ri = self.settings.refresh_interval
        self.refresh_interval = (0 if ri < 0
                                 else ri or self.strategy.refresh_interval)
        if spa_proxies is None:
            spa_proxies = self.strategy.build_proxies(params, cfg)
        self.spa_proxies = spa_proxies
        # step-time decomposition (DESIGN.md §12): a StepProfiler from
        # serving/profiling.py, or None (default — exact unprofiled
        # path).  ``label`` names this session's device track / lane
        # signature in traces and retrace accounting.
        self.profiler = profiler
        self.label = label or (
            f"{getattr(self.strategy, 'name', 'strategy')}"
            f"/{getattr(self.strategy.backend, 'name', 'backend')}")
        self._tracker = runtime.compile_tracker()
        # Weights and proxies are jit ARGUMENTS of every executable built
        # here, never closed over: a closed-over array is baked into the
        # program as a constant (gigabytes at published widths).
        self._step_fn = runtime.track_executables(jax.jit(
            self._tracker.wrap(self._serve_step, name="serve_step",
                               lane=self.label)))
        self._loop_fns: Dict[bool, Any] = {}   # run_compiled, by can_refresh
        self._partial_fns: Dict[int, Any] = {}  # prefill_partial, by s0
        # shared-prefix rows awaiting copy-on-write (DESIGN.md §6):
        # {batch row: SharedPrefix}; resolved before the first write
        self._shared_pending: Dict[int, SharedPrefix] = {}
        # called with the resolved specs right after a COW copy (the
        # engine releases its read holds on the shared pages here)
        self.cow_callback = None
        self.state: Optional[DecodeState] = None
        self.steps_taken = 0
        self.refresh_count = 0
        self._last_step_refreshed = False
        self._gen_span: Optional[Tuple[int, int]] = None  # semi-AR bounds
        # one host transfer of the canvas per step, shared by every
        # consumer (harvest, streaming diff, events()) — keyed on the
        # state object, which is replaced by each step/row surgery
        self._host_tokens: Optional[np.ndarray] = None
        self._host_tokens_for: Optional[DecodeState] = None
        # one-shot NaN fault payload armed by the engine's injector,
        # applied inside the next step() AFTER auto-refresh (§10)
        self._poison_pages: Optional[List[int]] = None
        # cache-dynamics telemetry (DESIGN.md §11): previous-step host
        # snapshots of the proxy identifier buffers + the previous
        # changed-row sets, diffed by cache_dynamics().  Host-side only
        # — never threaded into the jitted step.
        self._dyn_prev: Optional[Dict[str, np.ndarray]] = None
        self._dyn_prev_sel: Optional[Dict[str, List[set]]] = None

    # ------------------------------------------------------------------
    # State construction
    # ------------------------------------------------------------------

    def prefill(self, prompt: jax.Array, gen_len: int, *,
                use_cache: bool = True,
                extras: Optional[Dict[str, jax.Array]] = None,
                rng: Optional[jax.Array] = None,
                kv_len: Optional[jax.Array] = None,
                arenas=None,
                page_table: Optional[jax.Array] = None) -> DecodeState:
        """Build the canvas (prompt + gen_len [MASK] slots) and run the
        full prefill forward that populates the strategy's caches."""
        from repro.dlm.noise import mask_canvas
        canvas = mask_canvas(prompt, gen_len, self.cfg.mask_id)
        b, n = canvas.shape
        p_len = int(prompt.shape[1])
        active = jnp.zeros((b, n), bool).at[:, p_len:].set(True)
        n_masked = jnp.full((b,), gen_len, jnp.int32)
        state = self.attach(canvas, active=active, n_masked=n_masked,
                            extras=extras, use_cache=use_cache, rng=rng,
                            kv_len=kv_len, arenas=arenas,
                            page_table=page_table)
        self._gen_span = (p_len, p_len + gen_len)
        return state

    def attach(self, tokens: jax.Array, *,
               active: Optional[jax.Array] = None,
               n_masked: Optional[jax.Array] = None,
               extras: Optional[Dict[str, jax.Array]] = None,
               use_cache: bool = True,
               rng: Optional[jax.Array] = None,
               kv_len: Optional[jax.Array] = None,
               arenas=None,
               page_table: Optional[jax.Array] = None,
               shared: Optional[Sequence[SharedPrefix]] = None
               ) -> DecodeState:
        """Adopt an externally built canvas (serving engine path).

        Paged mode (DESIGN.md §5): pass pooled ``arenas``
        ({kind: {name: [Lk, P, page, ...]}}) plus a ``page_table``
        [B, n_log] — the prefilled dense cache is scattered into the
        arenas and the session's cache state becomes a
        :class:`~repro.core.cache.PagedCache`.  ``kv_len`` [B] marks each
        row's valid canvas length (shorter rows only own the pages that
        cover them; the tail aliases the zero page).

        ``shared`` (DESIGN.md §6): per-row shared-prefix attachments.
        A shared row's page-table prefix points at read-only pages from
        the prefix index; its prefill forward runs only over the
        unmatched suffix (``decoding.prefill_partial``) — or not at all
        when the whole row span is covered — and the shared pages are
        copied into the row's ``reserve`` pages right before the first
        cache write (copy-on-write)."""
        tokens = jnp.asarray(tokens)
        b = tokens.shape[0]
        if active is None:
            active = jnp.ones_like(tokens, bool)
        if n_masked is None:
            n_masked = jnp.sum(
                jnp.logical_and(tokens == self.cfg.mask_id, active),
                axis=-1).astype(jnp.int32)
        # fresh dict per state — never share or alias the caller's
        # (DecodeState's extras default used to be a shared {} literal).
        extras = dict(extras) if extras else {}
        if kv_len is not None:
            kv_len = jnp.asarray(kv_len, jnp.int32)
        self._shared_pending = {}
        if (shared and use_cache and self.strategy.uses_cache
                and arenas is not None):
            assert page_table is not None, "paged attach needs page_table"
            pt = jnp.asarray(page_table, jnp.int32)
            arenas = self._paged_fill(arenas, tokens, extras, kv_len,
                                      pt, shared)
            cache = cache_lib.PagedCache(arenas, pt)
            self._shared_pending = {s.row: s for s in shared}
        else:
            cache = (self._build_cache(tokens, extras, kv_len)
                     if use_cache else {})
            if arenas is not None and cache:
                assert page_table is not None, \
                    "paged attach needs page_table"
                cache = cache_lib.repage(
                    arenas, jnp.asarray(page_table, jnp.int32),
                    cache, self.strategy.backend)
        ring = self.settings.commit_ring
        self.state = DecodeState(
            tokens=tokens, cache=cache, step=jnp.zeros((), jnp.int32),
            committed=jnp.full((b, ring), -1, jnp.int32),
            n_masked=n_masked, active=active, extras=extras,
            rng=self._as_rng(rng), kv_len=kv_len)
        self.steps_taken = 0
        self.refresh_count = 0
        self._dyn_prev = None          # new canvas: old diffs meaningless
        self._dyn_prev_sel = None
        self._gen_span = None     # run_blocks needs a prefill()'d canvas
        return self.state

    def _as_rng(self, rng) -> Optional[jax.Array]:
        """Normalize the rng argument: ints become keys; stochastic
        schedulers get a default key so replay is seeded by default."""
        if rng is None:
            return (jax.random.PRNGKey(0) if self.scheduler.uses_rng
                    else None)
        if isinstance(rng, (int, np.integer)):
            return jax.random.PRNGKey(int(rng))
        return jnp.asarray(rng)

    def _serve_step(self, params, spa_proxies, state: DecodeState):
        return decoding.serve_step(
            params, self.cfg, state, settings=self.settings,
            spa_proxies=spa_proxies, strategy=self.strategy,
            scheduler=self.scheduler)

    def _build_cache(self, tokens, extras, kv_len=None):
        return self.strategy.refresh_cache(self.params, self.cfg, tokens,
                                           extras, self.spa_proxies,
                                           kv_len=kv_len)

    # ------------------------------------------------------------------
    # Shared-prefix attach + copy-on-write (DESIGN.md §6)
    # ------------------------------------------------------------------

    def _partial_fn(self, s0: int):
        """Jitted suffix-only prefill, one executable per suffix start
        (the engine's hit rows repeat the same few prompt layouts, so
        the compile amortizes like the lane step does)."""
        fn = self._partial_fns.get(s0)
        if fn is None:
            def run(params, spa_proxies, inputs, kv_view, kv_len):
                return decoding.prefill_partial(
                    params, self.cfg, inputs, kv_view, s0,
                    kv_len=kv_len, spa_proxies=spa_proxies,
                    strategy=self.strategy)
            fn = runtime.track_executables(jax.jit(self._tracker.wrap(
                run, name="prefill_partial", lane=self.label)))
            self._partial_fns[s0] = fn
        return fn

    def _paged_fill(self, arenas, tokens, extras, kv_len, read_pt,
                    shared: Sequence[SharedPrefix]):
        """Prefill a (sub-)batch into pooled arenas, honouring shared
        prefixes: rows without a spec get the normal full prefill, rows
        with one run only the unmatched suffix (grouped by suffix
        start, one jitted partial prefill per group), and fully covered
        rows run nothing.  All scatters go through a WRITE page table
        whose shared prefix entries alias the zero page, so the shared
        pages are never written here — ``shared[i].row`` indexes into
        THIS sub-batch."""
        m, n = tokens.shape
        n_log = read_pt.shape[1]
        page = n // n_log
        spec_by_row = {s.row: s for s in shared}
        wt = np.asarray(read_pt).copy()
        for s in spec_by_row.values():
            wt[s.row, :len(s.pages)] = 0
        kv_np = (np.asarray(kv_len) if kv_len is not None
                 else np.full((m,), n, np.int32))
        groups: Dict[int, list] = {}
        for r in range(m):
            s = spec_by_row.get(r)
            s0 = len(s.pages) * page if s else 0
            if s is not None and s0 >= int(kv_np[r]):
                continue                     # full hit: states are there
            groups.setdefault(s0, []).append(r)
        from repro.kernels.backend import XLA_BACKEND
        tokens = jnp.asarray(tokens)
        for s0, rows in sorted(groups.items()):
            idx = jnp.asarray(rows, jnp.int32)
            sub_tokens = tokens[idx]
            sub_extras = {k: jnp.asarray(v)[idx]
                          for k, v in (extras or {}).items()}
            sub_kv = kv_len[idx] if kv_len is not None else None
            sub_wt = jnp.asarray(wt[rows], jnp.int32)
            if s0 == 0:
                fresh = self._build_cache(sub_tokens, sub_extras, sub_kv)
            else:
                sub_rt = jnp.asarray(read_pt)[idx]
                kv_view = {
                    kind: {nm: XLA_BACKEND.gather_pages(bufs[nm], sub_rt)
                           for nm in ("k", "v")}
                    for kind, bufs in arenas.items()}
                inputs = dict(sub_extras)
                inputs["tokens"] = sub_tokens
                fresh = self._partial_fn(s0)(self.params, self.spa_proxies,
                                             inputs, kv_view, sub_kv)
            arenas = cache_lib.paged_from_dense(arenas, sub_wt, fresh,
                                                self.strategy.backend)
        return arenas

    def copy_cache_pages(self, src: Sequence[int],
                         dst: Sequence[int]) -> None:
        """Copy physical pages src[i] -> dst[i] in this session's paged
        cache (the engine's prefix-publication primitive: snapshot a
        row's prefill-time pages into index-owned pages BEFORE the first
        decode write evolves them)."""
        cache = self.state.cache
        assert isinstance(cache, PagedCache), "copy needs a paged cache"
        arenas = cache_lib.copy_arena_pages(cache.arenas, list(src),
                                            list(dst))
        self.state = self.state._replace(
            cache=PagedCache(arenas, cache.page_table))

    def read_cache_pages(self, pages: Sequence[int]):
        """Gather whole physical pages out of this session's LIVE paged
        arenas (the tier demotion read, DESIGN.md §9).  Mid-lane the
        pool's stored arenas are stale — the current values ride this
        session's step futures — so host-ward copies must come through
        here.  Returns device blocks {kind: {name: [Lk, n, page, ...]}}
        (callers ``np.asarray`` them, which syncs on the in-flight
        step)."""
        cache = self.state.cache
        assert isinstance(cache, PagedCache), "page read needs paging"
        return cache_lib.read_arena_pages(cache.arenas, list(pages))

    def write_cache_pages(self, pages: Sequence[int], blocks) -> None:
        """Scatter whole-page blocks into this session's LIVE paged
        arenas (the tier promotion write, §9).  The write is dispatched
        as an ``.at[].set`` on the step-future arenas, so it lands in
        dataflow order after the in-flight step without a host sync —
        which is what lets promotions overlap decode."""
        cache = self.state.cache
        assert isinstance(cache, PagedCache), "page write needs paging"
        arenas = cache_lib.write_arena_pages(cache.arenas, list(pages),
                                             blocks)
        self.state = self.state._replace(
            cache=PagedCache(arenas, cache.page_table))

    def cache_dynamics(self, max_rows: int = 2048
                       ) -> Optional[Dict[str, Any]]:
        """Host-side SPA cache-dynamics probe (DESIGN.md §11).

        Diffs the current ``proxy`` identifier buffers against the
        snapshot taken on the previous call; the rows whose proxies
        changed are exactly the rows the strategy selected AND committed
        that interval (``commit`` scatters the fresh proxy alongside the
        K/V rows), so the diff recovers — without touching the jitted
        step — per layer:

          * ``changed``: refreshed row count (→ budget utilization
            against ``k_schedule`` in the engine),
          * ``drift``: ``1 - cos(old_row, new_row)`` over the changed
            rows (the drift-score distribution the paper's adaptive
            budget responds to), sampled to ``max_rows`` rows,
          * ``overlap``: Jaccard overlap of this interval's changed-row
            set vs the previous one (selection stability).

        Returns None on the first call after ``attach`` (nothing to
        diff), for cache-less strategies, and when no proxy buffer
        exists.  Purely host-side: ``np.asarray`` reads sync on the
        in-flight step but never feed anything back, so decode outputs
        are byte-identical with sampling on (tests/test_telemetry.py).
        """
        if self.state is None:
            return None
        cache = self.state.cache
        bufs = cache.arenas if isinstance(cache, PagedCache) else cache
        if not isinstance(bufs, dict):
            return None
        cur: Dict[str, np.ndarray] = {}
        for kind, b in bufs.items():
            if isinstance(b, dict) and "proxy" in b:
                cur[kind] = np.asarray(b["proxy"])
        if not cur:
            return None
        prev, prev_sel = self._dyn_prev, self._dyn_prev_sel
        self._dyn_prev = cur
        if prev is None:
            return None
        out: Dict[str, Any] = {
            "refreshed": bool(self._last_step_refreshed), "kinds": {}}
        sel_now: Dict[str, List[set]] = {}
        for kind, now_arr in cur.items():
            p = prev.get(kind)
            if p is None or p.shape != now_arr.shape:
                continue
            n_layers = now_arr.shape[0]
            a = p.reshape(n_layers, -1, p.shape[-1])
            b2 = now_arr.reshape(n_layers, -1, now_arr.shape[-1])
            changed = np.any(a != b2, axis=-1)          # [L, rows]
            layers = []
            sel_now[kind] = []
            for l in range(n_layers):
                idx = np.nonzero(changed[l])[0]
                drift: List[float] = []
                if idx.size:
                    ii = idx[:max_rows]
                    va = a[l, ii].astype(np.float64)
                    vb = b2[l, ii].astype(np.float64)
                    denom = np.maximum(
                        np.linalg.norm(va, axis=-1)
                        * np.linalg.norm(vb, axis=-1), 1e-12)
                    cos = np.clip((va * vb).sum(-1) / denom, -1.0, 1.0)
                    drift = [float(x) for x in 1.0 - cos]
                cur_set = set(int(x) for x in idx)
                overlap = None
                if prev_sel is not None and kind in prev_sel \
                        and l < len(prev_sel[kind]):
                    ps = prev_sel[kind][l]
                    union = ps | cur_set
                    if union:
                        overlap = len(ps & cur_set) / len(union)
                layers.append({"changed": int(idx.size),
                               "rows": int(changed.shape[1]),
                               "drift": drift, "overlap": overlap})
                sel_now[kind].append(cur_set)
            out["kinds"][kind] = layers
        self._dyn_prev_sel = sel_now or prev_sel
        return out

    def poison_cache_pages(self, pages: Sequence[int]) -> None:
        """Overwrite the float buffers of physical ``pages`` with NaN —
        the ``step_nan`` fault payload (DESIGN.md §10).  The poisoned
        K/V entries propagate through the owning row's attention into
        its hidden states on the next step, where the supervisor's
        canvas guard catches them.  Integer buffers (page tables,
        identifier indices) are left intact: the fault models numeric
        bit-rot, not structural corruption."""
        blocks = self.read_cache_pages(pages)
        poisoned = {
            kind: {nm: (jnp.full_like(b, jnp.nan)
                        if jnp.issubdtype(b.dtype, jnp.floating) else b)
                   for nm, b in bufs.items()}
            for kind, bufs in blocks.items()}
        self.write_cache_pages(pages, poisoned)

    def poison_pages_after_refresh(self, pages: Sequence[int]) -> None:
        """Arm a one-shot :meth:`poison_cache_pages` applied inside the
        NEXT ``step()`` after its auto-refresh — so a
        ``refresh_interval=1`` strategy cannot heal the corruption
        before compute sees it (models bit-rot landing on the freshly
        rebuilt arena)."""
        self._poison_pages = list(pages)

    def _cow_if_shared(self) -> None:
        """Copy-on-write barrier: immediately before the first cache
        write (first step, compiled-loop entry, or an explicit refresh),
        copy every pending row's shared pages into its private reserve
        and patch the page table.  After this the shared pages are
        untouched forever — the other readers' (and the index's) view
        never changes."""
        if not self._shared_pending:
            return
        specs = list(self._shared_pending.values())
        self._shared_pending = {}
        cache = self.state.cache
        assert isinstance(cache, PagedCache), "shared rows need paging"
        src = [p for s in specs for p in s.pages]
        dst = [p for s in specs for p in s.reserve]
        arenas = cache_lib.copy_arena_pages(cache.arenas, src, dst)
        pt = cache.page_table
        for s in specs:
            pt = pt.at[s.row, :len(s.reserve)].set(
                jnp.asarray(s.reserve, jnp.int32))
        self.state = self.state._replace(cache=PagedCache(arenas, pt))
        if self.cow_callback is not None:
            self.cow_callback(specs)

    # ------------------------------------------------------------------
    # Stepping
    # ------------------------------------------------------------------

    def refresh(self) -> None:
        """Full cache rebuild from the current canvas.  A session running
        cache-less (``attach(use_cache=False)`` or ``NoCache``) never
        grows one — matching ``run_compiled``, whose carry structure is
        fixed at trace time.  Paged sessions rebuild dense and scatter
        back into their arenas (zero-page tails stay zero)."""
        if (not self.strategy.uses_cache or self.state is None
                or not self.state.cache):
            return
        self._cow_if_shared()     # the rebuild scatters into every page
        cache = self._build_cache(self.state.tokens, self.state.extras,
                                  self.state.kv_len)
        old = self.state.cache
        if isinstance(old, PagedCache):
            cache = cache_lib.repage(old.arenas, old.page_table, cache,
                                     self.strategy.backend)
        self.state = self.state._replace(cache=cache)
        self.refresh_count += 1

    def _maybe_refresh(self) -> bool:
        if (self.refresh_interval and self.steps_taken
                and self.steps_taken % self.refresh_interval == 0):
            before = self.refresh_count
            self.refresh()
            return self.refresh_count > before
        return False

    def step(self) -> Dict[str, jax.Array]:
        """One jitted refinement step (auto-refresh applied first).

        With a profiler attached and this step sampled, consecutive
        ``perf_counter`` fences decompose it into segments that TILE the
        step — ``refresh`` (COW + cache rebuild, synced), ``dispatch``
        (the jitted call returning futures) and ``device_wait`` (the
        sync on the step result) — so segment sums match the total
        (DESIGN.md §12).  The fences only add ``block_until_ready``:
        traced values are untouched, outputs stay byte-identical.
        """
        assert self.state is not None, "call prefill()/attach() first"
        prof = self.profiler
        if prof is not None and prof.should_sample(self.steps_taken):
            t0 = time.perf_counter()
            self._cow_if_shared()
            self._last_step_refreshed = self._maybe_refresh()
            if self._poison_pages:
                pages, self._poison_pages = self._poison_pages, None
                self.poison_cache_pages(pages)
            jax.block_until_ready(self.state)
            t1 = time.perf_counter()
            self.state, info = self._step_fn(self.params, self.spa_proxies,
                                             self.state)
            t2 = time.perf_counter()
            jax.block_until_ready(self.state)
            t3 = time.perf_counter()
            self.steps_taken += 1
            prof.observe_step(self.label,
                              {"refresh": t1 - t0, "dispatch": t2 - t1,
                               "device_wait": t3 - t2}, t3 - t0)
            return info
        self._cow_if_shared()     # first write: un-share prefix pages
        self._last_step_refreshed = self._maybe_refresh()
        if self._poison_pages:
            pages, self._poison_pages = self._poison_pages, None
            self.poison_cache_pages(pages)
        self.state, info = self._step_fn(self.params, self.spa_proxies,
                                         self.state)
        self.steps_taken += 1
        return info

    @property
    def done(self) -> bool:
        return int(jax.device_get(jnp.max(self.state.n_masked))) <= 0

    @property
    def tokens(self) -> jax.Array:
        return self.state.tokens

    def host_tokens(self) -> np.ndarray:
        """Host copy of the canvas, fetched AT MOST ONCE per state (the
        serving engine's per-step streaming diff and its harvest both
        read it; without the cache each would pay its own transfer)."""
        assert self.state is not None
        if self._host_tokens_for is not self.state:
            self._host_tokens = np.asarray(self.state.tokens)
            self._host_tokens_for = self.state
        return self._host_tokens

    def run(self, max_steps: Optional[int] = None
            ) -> Tuple[jax.Array, Dict[str, Any]]:
        """Step until every active slot is committed (or max_steps)."""
        assert self.state is not None, "call prefill()/attach() first"
        if max_steps is None:
            max_steps = int(jax.device_get(
                jnp.max(self.state.n_masked))) + 4
        n = 0
        for _ in range(max_steps):
            # check-first, like run_compiled's while_loop cond: an
            # already-finished session runs 0 steps in BOTH modes (and
            # never shifts the refresh cadence with no-commit steps)
            if self.done:
                break
            self.step()
            n += 1
        return self.state.tokens, {"steps": n,
                                   "refreshes": self.refresh_count}

    # ------------------------------------------------------------------
    # Device-resident loop
    # ------------------------------------------------------------------

    def run_compiled(self, max_steps: Optional[int] = None
                     ) -> Tuple[jax.Array, Dict[str, Any]]:
        """The whole decode loop as ONE ``jax.lax.while_loop``.

        Eliminates the per-step Python dispatch and the per-step host
        sync on ``n_masked`` that ``run()`` pays; periodic refresh is
        folded into the loop body via ``lax.cond`` on
        ``step % refresh_interval`` (same schedule as the host loop, so
        outputs are byte-identical — asserted per scheduler in
        ``tests/test_scheduler.py``).  ``max_steps`` is a dynamic
        argument: changing it never retraces.
        """
        assert self.state is not None, "call prefill()/attach() first"
        self._cow_if_shared()     # the loop body writes every page
        if max_steps is None:
            max_steps = int(jax.device_get(
                jnp.max(self.state.n_masked))) + 4
        can_refresh = bool(self.refresh_interval
                           and self.strategy.uses_cache
                           and self.state.cache)
        if can_refresh not in self._loop_fns:
            self._loop_fns[can_refresh] = self._build_loop_fn(can_refresh)
        prof = self.profiler
        t0 = time.perf_counter() if prof is not None else 0.0
        state, n_done, n_ref = self._loop_fns[can_refresh](
            self.params, self.spa_proxies, self.state,
            jnp.asarray(max_steps, jnp.int32))
        self.state = state
        n_done = int(jax.device_get(n_done))
        n_ref = int(jax.device_get(n_ref))
        if prof is not None:
            # whole-loop timing only: inside the while_loop there is no
            # host boundary to fence, so phases are not attributable
            # here (DESIGN.md §12); the device_get above synced the run.
            prof.observe_loop(self.label, n_done,
                              time.perf_counter() - t0)
        self.steps_taken += n_done
        self.refresh_count += n_ref
        return state.tokens, {"steps": n_done,
                              "refreshes": self.refresh_count}

    def _build_loop_fn(self, can_refresh: bool):
        """while_loop(cond=open slots remain, body=maybe-refresh + step).

        The refresh branch reuses ``CacheStrategy.refresh_cache`` — the
        exact function the host loop calls — under a ``lax.cond`` on the
        step counter (``state.step`` == completed steps, so the rebuild
        lands before steps R, 2R, ... exactly like ``_maybe_refresh``).
        """
        interval = self.refresh_interval
        cfg, strategy = self.cfg, self.strategy

        def loop(params, proxies, state0: DecodeState, max_steps: jax.Array):
            def rebuilt(state: DecodeState) -> DecodeState:
                cache = strategy.refresh_cache(params, cfg, state.tokens,
                                               state.extras, proxies,
                                               kv_len=state.kv_len)
                if isinstance(state.cache, PagedCache):
                    old = state.cache
                    cache = cache_lib.repage(old.arenas, old.page_table,
                                             cache, strategy.backend)
                return state._replace(cache=cache)

            def cond(carry):
                state, n_done, _ = carry
                return jnp.logical_and(n_done < max_steps,
                                       jnp.max(state.n_masked) > 0)

            def body(carry):
                state, n_done, n_ref = carry
                if can_refresh:
                    do = jnp.logical_and(state.step > 0,
                                         state.step % interval == 0)
                    state = jax.lax.cond(do, rebuilt, lambda s: s, state)
                    n_ref = n_ref + do.astype(jnp.int32)
                state, _ = self._serve_step(params, proxies, state)
                return state, n_done + 1, n_ref

            zero = jnp.zeros((), jnp.int32)
            return jax.lax.while_loop(cond, body, (state0, zero, zero))

        return runtime.track_executables(jax.jit(self._tracker.wrap(
            loop, name="decode_loop", lane=self.label)))

    def events(self, max_steps: Optional[int] = None
               ) -> Iterator[StepEvent]:
        """Streaming iterator: yields a StepEvent after every step."""
        assert self.state is not None, "call prefill()/attach() first"
        if max_steps is None:
            max_steps = int(jax.device_get(
                jnp.max(self.state.n_masked))) + 4
        for _ in range(max_steps):
            info = self.step()
            done = self.done
            committed = np.asarray(self.state.committed)
            toks = self.host_tokens()
            ctoks = np.where(committed >= 0,
                             np.take_along_axis(
                                 toks, np.maximum(committed, 0), axis=-1),
                             -1).astype(np.int32)
            yield StepEvent(
                step=self.steps_taken,
                n_committed=np.asarray(info["n_committed"]),
                committed=committed,
                done=done, refreshed=self._last_step_refreshed,
                committed_tokens=ctoks, tokens=toks)
            if done:
                break

    # ------------------------------------------------------------------
    # Active-position control (semi-AR blocks, serving slots)
    # ------------------------------------------------------------------

    def set_active(self, active: jax.Array) -> None:
        """Replace the commit mask; recounts open slots from the canvas."""
        assert self.state is not None
        n_masked = jnp.sum(
            jnp.logical_and(self.state.tokens == self.cfg.mask_id, active),
            axis=-1).astype(jnp.int32)
        self.state = self.state._replace(active=active, n_masked=n_masked)

    def set_active_span(self, start: int, stop: int) -> None:
        b, n = self.state.tokens.shape
        active = jnp.zeros((b, n), bool).at[:, start:stop].set(True)
        self.set_active(active)

    def run_blocks(self, block_len: int,
                   max_steps_per_block: Optional[int] = None
                   ) -> Tuple[jax.Array, Dict[str, Any]]:
        """Semi-AR block schedule: activate ``block_len``-wide windows
        left-to-right over the generation span, refreshing the cache at
        each block boundary (the committed block changes every row's
        context)."""
        assert self._gen_span is not None, "run_blocks needs prefill()"
        start, stop = self._gen_span
        total = 0
        for blk_start in range(start, stop, block_len):
            blk_end = min(blk_start + block_len, stop)
            self.set_active_span(blk_start, blk_end)
            if blk_start > start:
                self.refresh()
            cap = max_steps_per_block or 2 * block_len
            _, info = self.run(max_steps=cap)
            total += info["steps"]
        self.set_active_span(start, stop)
        return self.state.tokens, {"steps": total,
                                   "refreshes": self.refresh_count}

    # ------------------------------------------------------------------
    # Row surgery (continuous batching)
    # ------------------------------------------------------------------

    def replace_rows(self, rows: Sequence[int], row_tokens: np.ndarray,
                     row_active: np.ndarray,
                     row_extras: Optional[Dict[str, np.ndarray]] = None,
                     row_kv_len: Optional[np.ndarray] = None,
                     row_page_table: Optional[np.ndarray] = None,
                     row_committed: Optional[np.ndarray] = None,
                     row_shared: Optional[Sequence[SharedPrefix]] = None
                     ) -> None:
        """Swap canvas rows in-place and re-prefill ONLY those rows.

        The fresh cache is computed with a prefill over just the swapped
        rows (prefill is row-independent, so the per-row results match a
        full static-batch prefill — asserted byte-for-byte by the
        continuous-batching parity test) and spliced into the running
        cache at those batch rows — sibling rows keep their evolved
        partially-updated caches.

        Paged sessions take ``row_page_table`` [n_swap, n_log] (the
        incoming requests' freshly allocated pages; tail entries 0) and
        ``row_kv_len`` [n_swap]: the sub-row prefill scatters into those
        pages, sibling rows' pages are untouched.  ``row_committed``
        restores a preempted request's commit ring (resume); default
        clears it.  ``row_shared`` (DESIGN.md §6) attaches shared
        prefix pages for incoming rows exactly like ``attach(shared=)``
        — specs carry BATCH row ids (members of ``rows``).
        """
        assert self.state is not None
        idx = jnp.asarray(list(rows), jnp.int32)
        row_tokens = jnp.asarray(row_tokens)
        tokens = self.state.tokens.at[idx].set(row_tokens)
        active = self.state.active.at[idx].set(jnp.asarray(row_active))
        extras = dict(self.state.extras)
        for k, v in (row_extras or {}).items():
            extras[k] = extras[k].at[idx].set(jnp.asarray(v))
        sub_extras = {k: v[idx] for k, v in extras.items()}
        n_masked = jnp.sum(
            jnp.logical_and(tokens == self.cfg.mask_id, active),
            axis=-1).astype(jnp.int32)
        if row_committed is not None:
            committed = self.state.committed.at[idx].set(
                jnp.asarray(row_committed, jnp.int32))
        else:
            committed = self.state.committed.at[idx].set(-1)
        kv_len = self.state.kv_len
        sub_kv = None
        if kv_len is not None:
            assert row_kv_len is not None, "paged session needs row_kv_len"
            sub_kv = jnp.asarray(row_kv_len, jnp.int32)
            kv_len = kv_len.at[idx].set(sub_kv)
        cache = self.state.cache
        rows_list = list(rows)
        for r in rows_list:      # replaced rows' pending shares lapse
            self._shared_pending.pop(r, None)
        if self.strategy.uses_cache and cache:
            if isinstance(cache, PagedCache):
                assert row_page_table is not None
                row_pt = jnp.asarray(row_page_table, jnp.int32)
                if row_shared:
                    sub_specs = [dataclasses.replace(
                        s, row=rows_list.index(s.row)) for s in row_shared]
                    arenas = self._paged_fill(
                        cache.arenas, row_tokens, sub_extras, sub_kv,
                        row_pt, sub_specs)
                    for s in row_shared:
                        self._shared_pending[s.row] = s
                else:
                    fresh = self._build_cache(row_tokens, sub_extras,
                                              sub_kv)
                    arenas = cache_lib.paged_from_dense(
                        cache.arenas, row_pt, fresh,
                        self.strategy.backend)
                cache = PagedCache(arenas,
                                   cache.page_table.at[idx].set(row_pt))
            else:
                fresh = self._build_cache(row_tokens, sub_extras, sub_kv)
                cache = jax.tree.map(
                    lambda old, new: old.at[:, idx].set(new), cache, fresh)
        self.state = self.state._replace(
            tokens=tokens, active=active, n_masked=n_masked,
            committed=committed, cache=cache, extras=extras,
            kv_len=kv_len)

    def deactivate_rows(self, rows: Sequence[int]) -> None:
        """Park finished slots with no replacement request."""
        assert self.state is not None
        idx = jnp.asarray(list(rows), jnp.int32)
        # before the first step the attach()-provided buffers may still
        # be host numpy (watchdog recovery can fire that early)
        active = jnp.asarray(self.state.active).at[idx].set(False)
        n_masked = jnp.asarray(self.state.n_masked).at[idx].set(0)
        self.state = self.state._replace(active=active, n_masked=n_masked)

    def release_rows(self, rows: Sequence[int]) -> None:
        """Release finished/preempted slots AND their pages: the rows'
        page-table entries drop to the zero page and kv_len to 0, so the
        physical pages can be handed to the next admitted request without
        this session ever reading them again (a zero-kv_len row is fully
        masked out of attention and selection)."""
        assert self.state is not None
        self.deactivate_rows(rows)
        for r in rows:           # released rows never COW (the engine
            self._shared_pending.pop(r, None)   # releases their holds)
        idx = jnp.asarray(list(rows), jnp.int32)
        kv_len = self.state.kv_len
        if kv_len is not None:
            kv_len = jnp.asarray(kv_len).at[idx].set(0)
        cache = self.state.cache
        if isinstance(cache, PagedCache):
            pt = jnp.asarray(cache.page_table).at[idx].set(0)
            cache = PagedCache(cache.arenas, pt)
        self.state = self.state._replace(cache=cache, kv_len=kv_len)

    def snapshot_rows(self, rows: Sequence[int]) -> Dict[str, np.ndarray]:
        """Host copies of per-row canvas state (preemption snapshot):
        tokens, active mask and the commit ring.  Enough to resume the
        request later via ``replace_rows`` — the cache itself is NOT
        saved (resume re-prefills, which for ring-preserving resumes is
        byte-identical to a periodic refresh at the resume step)."""
        assert self.state is not None
        idx = np.asarray(list(rows))
        return {
            "tokens": np.asarray(self.state.tokens)[idx],
            "active": np.asarray(self.state.active)[idx],
            "committed": np.asarray(self.state.committed)[idx],
        }
