"""Batched DLM serving engine on DecodeSession (DESIGN.md §3.2, §5).

Requests (prompt + gen_len + optional per-request DecodeSettings /
CacheStrategy / UnmaskScheduler / priority) are padded onto fixed canvas
rows and served by a ``DecodeSession`` at **step granularity**: when a
row finishes, its slot is swapped for the next queued request mid-loop
(``DecodeSession.replace_rows``) while sibling rows keep stepping with
their evolved caches — no whole-batch re-prefill barrier.

Because the jitted step closes over settings, strategy and scheduler
statically, the queue is partitioned into *lanes* keyed on the full
``(DecodeSettings, CacheStrategy, UnmaskScheduler)`` triple: a lane's
batch only ever admits requests with an identical triple (one compiled
step per lane; all three are frozen hashable dataclasses).  Within a
lane, rows are independent (attention, top-k selection and commits are
all per-row), so for deterministic schedulers continuous batching is
byte-identical to serving the same requests in static batches —
asserted by ``tests/test_strategy_parity.py``.  Stochastic schedulers
(``uses_rng``) draw from ONE batch-global rng chain per lane, so their
sampled outputs depend on batch composition and swap order; runs are
reproducible per engine configuration but NOT invariant to scheduling.

Paged mode (``pool_pages > 0``, DESIGN.md §5): cache memory is a
managed resource.  A :class:`~repro.serving.pool.PagePool` owns one
device arena of fixed-size pages; each request allocates only the pages
covering its own (page-aligned) prompt+gen span, so heterogeneous
``gen_len`` requests share a lane without padding their cache to the
lane max — the canvas tail past a row's ``kv_len`` aliases the pool's
zero page and is masked out of attention and selection.  Admission is
gated on free pages; when the head of the queue cannot fit, the engine
preempts the lowest-priority running request (its pages are released,
its canvas+commit-ring snapshot requeued at the front) instead of
failing.  A resumed request re-prefills its cache from the snapshot —
byte-identical to a periodic refresh at the resume step, so a
preempted-then-resumed request matches a twin that refreshed there
(``tests/test_serving.py``).

Prefix reuse (``prefix_cache=True``, DESIGN.md §6): a
:class:`~repro.serving.prefix.PrefixIndex` maps (row span, strategy,
prompt token runs) to refcounted page runs holding PREFILL-TIME states.
Admission consults the index: a full hit attaches every page and skips
the prefill forward entirely; a partial hit attaches the matched prefix
read-only and prefills only the unmatched suffix
(``decoding.prefill_partial``).  Attached shared pages are copied into
the request's own reserve pages right before its first decode write
(copy-on-write in ``DecodeSession``), so index pages never change.
Cold requests publish their prefill pages (a page copy, skipped under
page pressure) back into the index at admission — harvest-time states
have evolved with the decode and would silently break the full-hit
byte-parity guarantee, so publication snapshots BEFORE the first step.
Under admission pressure, least-recently-used index entries with no
readers are evicted before any running request is preempted.

Online serving (DESIGN.md §8): the engine doubles as the backend of an
asyncio streaming front-end (``serving/frontend.py``).  Three pieces:

  * **Thread-safe intake** — ``submit_threadsafe``/``cancel_threadsafe``
    enqueue closures on a mailbox the engine thread drains at its
    *overlap point*; the engine's own state is only ever touched from
    the engine thread.
  * **Double-buffered dispatch** — each loop iteration dispatches the
    jitted device step, then does its host-side work (mailbox drain,
    SLO shedding, prefix planning for the next admission candidate)
    BEFORE the first host sync on the step's outputs, so admission and
    planning overlap the in-flight device step instead of sitting on
    the critical path.
  * **Per-token events** — requests submitted with a ``sink`` (or
    ``stream=True`` with an engine-level ``event_sink``) get a
    :class:`RequestEvent` per newly committed token batch, produced by
    diffing the canvas against a per-request emitted mask.  The mask
    lives on the ``Request``, so a preempted-then-resumed request's
    stream has no duplicated and no lost tokens (its committed canvas
    is snapshot/restored; ``tests/test_serving.py``).

SLO-aware scheduling (``serving/slo.py``): requests may carry an
:class:`~repro.serving.slo.SLO` (TTFT target + e2e deadline).  With an
engine-level :class:`~repro.serving.slo.SLOPolicy`, near-deadline
requests are boosted onto the existing strict-priority + preemption
machinery (and EDF-ordered within a priority), while hopeless requests
— TTFT already missed in queue, or e2e deadline passed — are shed
instead of burning pool pages for zero goodput.  ``EngineStats`` tracks
per-request TTFT/TPOT percentiles and goodput-under-SLO
(``benchmarks/bench_serving.py``).

Cancellation: ``cancel(uid)`` aborts a queued OR running request —
pages, prefix read holds and the canvas row are all released, and the
pool drain invariant (used == index-held pages after a full drain)
still holds (``tests/test_pool.py`` leak detector).

Slot bookkeeping uses the session's explicit active-position mask;
token ids are never overloaded as "committed filler" sentinels.
"""
from __future__ import annotations

import dataclasses
import functools
import queue as queue_mod
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.configs.base import ModelConfig
from repro.core import cache as cache_lib
from repro.core import runtime
from repro.core.cache import PagedCache, n_logical_pages
from repro.core.strategy import CacheStrategy, resolve_strategy
from repro.dlm.decoding import DecodeSettings, partial_prefill_supported
from repro.dlm.scheduler import UnmaskScheduler, resolve_scheduler
from repro.dlm.session import DecodeSession, SharedPrefix
from repro.serving.faults import FaultInjector, FaultPlan, choose_index
from repro.serving.hier import (HostPageCorruption, HostPagePool,
                                TierManager)
from repro.serving.pool import OutOfPages, PagePool, cache_signature
from repro.serving.prefix import PrefixIndex, PrefixMatch
from repro.serving.slo import SLO, SLOPolicy
from repro.serving.supervisor import EngineSupervisor, SupervisorConfig
from repro.serving.telemetry import (PID_ENGINE, PID_EVENTS, PID_REQUESTS,
                                     Histogram, Telemetry)

# (settings, strategy, scheduler): everything the compiled step closes
# over statically — one DecodeSession (one executable) per distinct key.
LaneKey = Tuple[DecodeSettings, CacheStrategy, UnmaskScheduler]


@dataclasses.dataclass(frozen=True)
class RequestEvent:
    """One streaming event for a request (DESIGN.md §8).

    ``kind``: "token" (``positions``/``tokens`` carry the gen-span
    offsets and values committed since the last event), "done" (final
    output in ``tokens``), "shed" (SLO policy dropped it), or
    "canceled".  Delivered to ``Request.sink`` if set, else the
    engine-level ``event_sink`` for ``stream=True`` requests — always
    on the engine thread (the front-end bridges to asyncio)."""
    kind: str
    uid: int
    step: int
    ts: float
    positions: Tuple[int, ...] = ()   # offsets into the gen span
    tokens: Tuple[int, ...] = ()


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray              # [P] int32
    gen_len: int
    settings: Optional[DecodeSettings] = None
    strategy: Optional[CacheStrategy] = None
    scheduler: Optional[UnmaskScheduler] = None
    priority: int = 0               # higher = preempts lower
    submitted_at: float = dataclasses.field(default_factory=time.time)
    started_at: Optional[float] = None   # first admission to a slot
    completed_at: Optional[float] = None
    output: Optional[np.ndarray] = None
    lane: Optional[LaneKey] = None  # resolved ONCE at submit()
    # paged bookkeeping
    row_len: int = 0                # page-aligned prompt+gen span
    n_pages: int = 0                # composite pages needed
    pages: Optional[List[int]] = None
    # shared-prefix attachment (DESIGN.md §6): read holds on index pages
    # mapped at logical [0, shared_n); pages[:shared_n] is the COW
    # reserve.  Released at COW time (or harvest/preempt if earlier).
    holds: Optional[List[int]] = None
    shared_n: int = 0
    shared_full: bool = False       # the hit covers the whole row span
    preemptions: int = 0
    served_steps: int = 0           # per-request max_steps budget
    snapshot: Optional[Dict[str, np.ndarray]] = None  # preempt resume
    # online serving (DESIGN.md §8)
    slo: Optional[SLO] = None       # TTFT target + e2e deadline
    stream: bool = False            # emit per-token events
    sink: Optional[Callable] = None  # per-request event callback
    canceled: bool = False          # set by cancel(); loop releases slot
    shed: bool = False              # canceled BY the SLO policy
    first_token_at: Optional[float] = None
    last_commit_at: Optional[float] = None
    tokens_done: int = 0            # committed so far (TPOT denominator)
    # per-request emitted mask [gen_len]: which gen-span offsets have
    # already been streamed — survives preemption, so a resumed
    # request's stream never duplicates or drops a token
    emitted: Optional[np.ndarray] = None
    plan_epoch: Optional[int] = None  # prefix plan validity (see §8)
    boosted: bool = False           # urgency transition already seen
    # host tier (DESIGN.md §9): a plan whose match lives (partly) in
    # host RAM parks here in the PROMOTING admission state until the
    # engine services it (overlap window or synchronously at admission)
    pending_promotion: Optional["PrefixMatch"] = None
    no_promote: bool = False        # sticky: promotion failed once —
    #                                 this admission runs device-only
    # fault containment (DESIGN.md §10): the fault class that aborted
    # this request ("nan", "pool_alloc", ...), plus the bounded
    # retry-with-backoff state for transient admission alloc failures
    fault: Optional[str] = None
    alloc_retries: int = 0
    retry_after_step: int = 0       # backoff gate on the step clock


@dataclasses.dataclass
class EngineStats:
    steps: int = 0
    tokens_committed: int = 0
    requests_done: int = 0
    swaps: int = 0                  # mid-loop slot replacements
    preemptions: int = 0            # out-of-pages victim evictions
    admission_stalls: int = 0       # admission attempts blocked on pages
    # shared-prefix index (DESIGN.md §6)
    prefix_hits: int = 0            # admissions that attached index pages
    prefix_full_hits: int = 0       # ... covering the whole row span
    prefix_tokens_saved: int = 0    # prompt+canvas rows NOT re-prefilled
    prefix_published: int = 0       # pages copied into the index
    prefix_publish_skipped: int = 0  # publications dropped (pool short)
    prefix_evicted_pages: int = 0   # index pages evicted under pressure
    # host tier (DESIGN.md §9): evicted splits into demoted vs dropped
    prefix_demoted_pages: int = 0   # ... demoted to the host tier
    prefix_dropped_pages: int = 0   # ... dropped (tier off/full/stable)
    prefix_promoted_pages: int = 0  # host pages promoted back
    prefix_promotions: int = 0      # promotion events serviced
    promotion_stalls: int = 0       # promotions abandoned (no headroom)
    peak_pool_util: float = 0.0
    steady_pool_util: float = 0.0
    peak_host_util: float = 0.0     # host-tier unit budget high-water
    # online serving / SLO accounting (DESIGN.md §8)
    requests_shed: int = 0          # dropped by the SLO policy
    requests_canceled: int = 0      # client cancel / disconnect
    slo_met: int = 0                # completed within their SLO
    slo_missed: int = 0             # completed but past TTFT/deadline
    # latency distributions are telemetry histograms (DESIGN.md §11):
    # fixed buckets feed Prometheus exposition while retained samples
    # keep percentiles EXACT (and `len(stats.e2e_latencies)` list-compat)
    e2e_latencies: Histogram = dataclasses.field(
        default_factory=functools.partial(
            Histogram, "spa_engine_e2e_latency_seconds",
            "request end-to-end latency (submit to harvest)"))
    queue_waits: Histogram = dataclasses.field(
        default_factory=functools.partial(
            Histogram, "spa_engine_queue_wait_seconds",
            "queue wait (submit to first admission)"))
    ttft_latencies: Histogram = dataclasses.field(
        default_factory=functools.partial(
            Histogram, "spa_engine_ttft_seconds",
            "time to first committed token"))
    tpot_latencies: Histogram = dataclasses.field(
        default_factory=functools.partial(
            Histogram, "spa_engine_tpot_seconds",
            "per-request time per output token"))
    # fault tolerance (DESIGN.md §10)
    faults_injected: int = 0        # injector fires (replay fingerprint)
    requests_faulted: int = 0       # aborted by fault containment
    alloc_faults: int = 0           # transient admission alloc failures
    host_checksum_failures: int = 0  # corrupt host pages caught
    cold_prefill_fallbacks: int = 0  # corrupted promotions served cold
    nan_quarantines: int = 0        # poisoned rows aborted by the guard
    disconnect_bursts: int = 0      # injected mass client hangups
    watchdog_fires: int = 0         # stuck lanes force-preempted
    invariant_checks: int = 0       # supervisor accounting audits run
    publish_paused_skips: int = 0   # publications skipped at ladder L1+
    degrade_level: int = 0          # current ladder rung (0 = full)
    degradations: int = 0           # upward ladder transitions
    restorations: int = 0           # downward ladder transitions
    degradation_events: List[Tuple[int, int]] = dataclasses.field(
        default_factory=list)       # (step, new level), both directions

    def tps(self, wall: float) -> float:
        return self.tokens_committed / max(wall, 1e-9)

    def goodput(self, wall: float) -> float:
        """Requests completed WITHIN their SLO per second — the online
        headline metric (a request without an SLO counts as met when it
        completes; shed/canceled/late requests never count)."""
        return self.slo_met / max(wall, 1e-9)

    def percentiles(self) -> Dict[str, float]:
        """p50/p95 end-to-end, queue-wait, TTFT and TPOT (seconds) —
        single-sourced through :meth:`Histogram.percentile`, which is
        exact (matches ``numpy.percentile``) over retained samples."""
        out: Dict[str, float] = {}
        for name, h in (("e2e", self.e2e_latencies),
                        ("wait", self.queue_waits),
                        ("ttft", self.ttft_latencies),
                        ("tpot", self.tpot_latencies)):
            out[f"{name}_p50"] = h.percentile(50)
            out[f"{name}_p95"] = h.percentile(95)
        return out


# EngineStats field -> Prometheus metric mirror (DESIGN.md §11 naming:
# spa_<subsystem>_<quantity>[_<unit>], monotonic counters end in _total).
_STATS_COUNTERS: Tuple[Tuple[str, str, str], ...] = (
    ("steps", "spa_engine_steps_total", "engine iterations"),
    ("tokens_committed", "spa_engine_tokens_committed_total",
     "tokens committed across all requests"),
    ("requests_done", "spa_engine_requests_done_total",
     "requests harvested with output"),
    ("swaps", "spa_engine_swaps_total", "mid-loop slot replacements"),
    ("preemptions", "spa_engine_preemptions_total",
     "running requests evicted for pages/priority"),
    ("admission_stalls", "spa_engine_admission_stalls_total",
     "admission attempts blocked on pages"),
    ("prefix_hits", "spa_prefix_hits_total",
     "admissions that attached index pages"),
    ("prefix_full_hits", "spa_prefix_full_hits_total",
     "prefix hits covering the whole row span"),
    ("prefix_tokens_saved", "spa_prefix_tokens_saved_total",
     "prompt+canvas rows not re-prefilled"),
    ("prefix_published", "spa_prefix_published_pages_total",
     "pages copied into the index"),
    ("prefix_publish_skipped", "spa_prefix_publish_skipped_total",
     "publications dropped (pool short)"),
    ("prefix_evicted_pages", "spa_prefix_evicted_pages_total",
     "index pages evicted under pressure"),
    ("prefix_demoted_pages", "spa_tier_demoted_pages_total",
     "evicted pages demoted to the host tier"),
    ("prefix_dropped_pages", "spa_tier_dropped_pages_total",
     "evicted pages dropped outright"),
    ("prefix_promoted_pages", "spa_tier_promoted_pages_total",
     "host pages promoted back to device"),
    ("prefix_promotions", "spa_tier_promotions_total",
     "promotion events serviced"),
    ("promotion_stalls", "spa_tier_promotion_stalls_total",
     "promotions abandoned (no headroom)"),
    ("requests_shed", "spa_slo_requests_shed_total",
     "requests dropped by the SLO policy / ladder"),
    ("requests_canceled", "spa_engine_requests_canceled_total",
     "client cancels / disconnects"),
    ("slo_met", "spa_slo_met_total", "completions within SLO"),
    ("slo_missed", "spa_slo_missed_total",
     "completions past TTFT/deadline (incl. shed)"),
    ("requests_faulted", "spa_fault_requests_faulted_total",
     "requests aborted by fault containment"),
    ("alloc_faults", "spa_fault_alloc_failures_total",
     "transient admission alloc failures"),
    ("host_checksum_failures", "spa_fault_host_checksum_failures_total",
     "corrupt host pages caught at promotion"),
    ("cold_prefill_fallbacks", "spa_fault_cold_prefill_fallbacks_total",
     "corrupted promotions served by cold prefill"),
    ("nan_quarantines", "spa_fault_nan_quarantines_total",
     "poisoned rows aborted by the NaN guard"),
    ("disconnect_bursts", "spa_fault_disconnect_bursts_total",
     "injected mass client hangups"),
    ("watchdog_fires", "spa_fault_watchdog_fires_total",
     "stuck lanes force-preempted"),
    ("invariant_checks", "spa_fault_invariant_checks_total",
     "supervisor accounting audits run"),
    ("publish_paused_skips", "spa_fault_publish_paused_skips_total",
     "publications skipped at ladder L1+"),
    ("degradations", "spa_fault_degradations_total",
     "upward ladder transitions"),
    ("restorations", "spa_fault_restorations_total",
     "downward ladder transitions"),
)

_RATIO_BUCKETS = (0.05, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9,
                  1.0, 1.5, 2.0, 4.0)
_DRIFT_BUCKETS = (1e-6, 1e-5, 1e-4, 1e-3, 3e-3, 0.01, 0.03, 0.1, 0.3,
                  0.6, 1.0, 1.5, 2.0)
_HIT_DEPTH_BUCKETS = (0.0, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0)


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 4,
                 canvas_len: int = 64,
                 settings: Optional[DecodeSettings] = None,
                 strategy: Optional[CacheStrategy] = None,
                 scheduler: Optional[UnmaskScheduler] = None,
                 continuous: bool = True,
                 pool_pages: int = 0, page_size: int = 16,
                 prefix_cache: bool = False,
                 host_pages: int = 0, host_dtype: str = "auto",
                 slo_policy: Optional[SLOPolicy] = None,
                 clock: Optional[Callable[[], float]] = None,
                 fault_plan: Optional[FaultPlan] = None,
                 supervise: bool = False,
                 supervisor_cfg: Optional[SupervisorConfig] = None,
                 telemetry: Optional[Telemetry] = None,
                 profiler=None):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.canvas_len = canvas_len
        self.settings = settings or DecodeSettings()
        self.strategy = resolve_strategy(cfg, strategy)
        self.scheduler = scheduler    # None -> derived from settings
        self.continuous = continuous
        self.paged = pool_pages > 0
        self.page_size = page_size
        self.pool: Optional[PagePool] = None
        self.prefix: Optional[PrefixIndex] = None
        if self.paged:
            n_logical_pages(canvas_len, page_size)  # divisibility check
            self.pool = PagePool(cfg, n_pages=pool_pages,
                                 page_size=page_size,
                                 strategy=self.strategy)
            if prefix_cache:
                self.prefix = PrefixIndex(page_size)
        # host-RAM page tier (DESIGN.md §9): evicted index entries
        # demote host-ward instead of dying; hits promote back
        self.host_pool: Optional[HostPagePool] = None
        self.tier: Optional[TierManager] = None
        if self.paged and self.prefix is not None and host_pages > 0:
            self.host_pool = HostPagePool(host_pages)
            self.tier = TierManager(self.host_pool, host_dtype=host_dtype,
                                    read_pages=self._tier_read)
            self.prefix.tier = self.tier
        # tier IO routing: mid-lane the live arenas ride the active
        # session's step futures, not the pool's stored copies
        self._active_sess: Optional[DecodeSession] = None
        self._active_sig: Optional[Tuple] = None
        # partial (suffix-only) reuse needs a window-free all-attention
        # stack and a float cache (DESIGN.md §6); full-run hits are an
        # exact page copy and work for any architecture/dtype
        self._partial_ok = (partial_prefill_supported(cfg)
                            and cfg.cache_dtype != "int8")
        self.queue: deque[Request] = deque()
        self.done: List[Request] = []
        self.stats = EngineStats()
        self._next_uid = 0            # monotonic: uids never recycle
        # admission re-scan gate: set by submit(), cleared after each
        # admission attempt — a stalled queue is not re-scanned (and
        # admission_stalls not re-counted) every step, only when a
        # finish/preemption or a new arrival can change the outcome
        self._admission_dirty = True
        self._sessions: Dict[LaneKey, DecodeSession] = {}
        # offline proxy artefacts are per STRATEGY, shared across lanes
        self._proxies: Dict[CacheStrategy, object] = {}
        # online serving (DESIGN.md §8)
        self.slo_policy = slo_policy
        self._clock = clock or time.time
        # unified telemetry (DESIGN.md §11): a registry (always present
        # — /metrics and bench snapshots read live engine state through
        # a collector) + a span tracer (disabled by default; every
        # trace call in the hot loop is gated on ``tracer.enabled``).
        # The tracer is re-stamped from the ENGINE clock so traces are
        # deterministic under virtual-clock replay.
        self.telemetry = telemetry or Telemetry.disabled()
        self.telemetry.tracer.clock = self._clock
        self._tr = self.telemetry.tracer
        self.telemetry.registry.add_collector(self._collect_metrics)
        # compute-path profiling (DESIGN.md §12): a StepProfiler from
        # serving/profiling.py, handed to every lane session.  None
        # (default) keeps the exact unprofiled step path.
        self.profiler = profiler
        self._lane_ids: Dict[LaneKey, int] = {}
        self.event_sink: Optional[Callable[[RequestEvent], None]] = None
        # thread-safe intake: closures enqueued by submit_threadsafe /
        # cancel_threadsafe, drained on the engine thread at the
        # double-buffer overlap point (and while idle in run_online)
        self._mailbox: "queue_mod.Queue[Callable[[], None]]" = \
            queue_mod.Queue()
        self._uid_lock = threading.Lock()
        self._running: Dict[int, Request] = {}   # uid -> in-flight req
        self._stop: Optional[threading.Event] = None
        self._prefix_epoch = 0        # bumps on any index mutation
        # fault tolerance (DESIGN.md §10): seeded injector threaded
        # through the seams + a supervisor wrapping the step loop.
        # A fault plan without a supervisor would deadlock on a lane
        # stall, so injection implies supervision.
        self.faults: Optional[FaultInjector] = None
        if fault_plan is not None:
            self.faults = FaultInjector(fault_plan)
            if self.pool is not None:
                self.pool.fault_hook = self.faults
            if self.tier is not None:
                self.tier.injector = self.faults
            # every injector fire becomes a trace event with the same
            # (site, probe) schema as FaultInjector.log, so a chaos
            # replay and its trace can be diffed (DESIGN.md §11)
            self.faults.on_fire = self._trace_fault
        # degradation-ladder flags, maintained by the supervisor
        self._publish_paused = False
        self._host_tier_paused = False
        self._shed_low_priority = False
        self._shed_below = 0
        self._hopeless_margin = 0.0
        self.supervisor: Optional[EngineSupervisor] = None
        if supervise or supervisor_cfg is not None or fault_plan is not None:
            EngineSupervisor(self, supervisor_cfg)  # attaches itself

    def _now(self) -> float:
        return self._clock()

    # ------------------------------------------------------------------
    # Telemetry (DESIGN.md §11)
    # ------------------------------------------------------------------

    def _trace_fault(self, site: str, probe: int) -> None:
        """Injector fire → instant trace event, schema-identical to the
        FaultInjector.log entry ``(site, probe)``."""
        self._tr.instant(PID_EVENTS, 1, f"fault:{site}", cat="fault",
                         args={"site": site, "probe": probe,
                               "step": self.stats.steps})

    def _lane_id(self, lane: LaneKey) -> int:
        lid = self._lane_ids.get(lane)
        if lid is None:
            lid = self._lane_ids[lane] = len(self._lane_ids)
            self._tr.name_track(PID_ENGINE, lid, f"lane{lid}")
        return lid

    def _phase(self, lid: int, name: str):
        """One engine phase of lane ``lid``: ``engine/<name>`` in a
        ``jax.profiler`` trace always; while tracing, also a span on
        the lane's track whose duration feeds the step-time-breakdown
        histogram."""
        return self._tr.span(PID_ENGINE, lid, name, cat="phase",
                             on_close=self._observe_phase)

    def _observe_phase(self, ev) -> None:
        self.telemetry.registry.histogram(
            "spa_engine_phase_seconds",
            "per-iteration step-time breakdown",
            labels={"phase": ev.name}).observe(ev.dur)

    def _note_cache_dynamics(self, sess: DecodeSession,
                             strategy: CacheStrategy, n_live: int) -> None:
        """Fold one DecodeSession.cache_dynamics() probe into the
        registry: per-layer refresh-budget utilization, proxy drift
        distribution, selection overlap.  Host-side, post-sync only."""
        dyn = sess.cache_dynamics()
        if dyn is None:
            return
        reg = self.telemetry.registry
        if dyn["refreshed"]:
            # a full refresh rewrites every row — budget utilization and
            # drift are about the *incremental* selection, so count the
            # event and skip the diff-derived metrics
            reg.counter("spa_cache_refresh_steps_total",
                        "steps that ran a full cache refresh").inc()
            return
        try:
            ks = strategy.k_schedule(self.cfg, self.canvas_len)
        except (NotImplementedError, AttributeError):
            ks = None
        for kind, layers in dyn["kinds"].items():
            for layer, d in enumerate(layers):
                labels = {"kind": kind, "layer": str(layer)}
                if ks is not None and layer < len(ks) and n_live:
                    util = d["changed"] / max(int(ks[layer]) * n_live, 1)
                    reg.histogram(
                        "spa_cache_budget_utilization_ratio",
                        "refreshed rows / (k_schedule budget * live "
                        "rows) per step", labels=labels,
                        buckets=_RATIO_BUCKETS).observe(util)
                if d["drift"]:
                    h = reg.histogram(
                        "spa_cache_proxy_drift",
                        "1 - cos(prev proxy row, new proxy row) over "
                        "refreshed rows", labels=labels,
                        buckets=_DRIFT_BUCKETS)
                    for x in d["drift"]:
                        h.observe(x)
                if d["overlap"] is not None:
                    reg.histogram(
                        "spa_cache_selection_overlap_ratio",
                        "Jaccard overlap of consecutive refreshed-row "
                        "sets", labels=labels,
                        buckets=_RATIO_BUCKETS).observe(d["overlap"])

    def _collect_metrics(self) -> None:
        """Registry collector: mirror live engine state (EngineStats
        counters, pool/tier occupancy, queue depth) into the registry
        right before every render()/snapshot().  EngineStats stays the
        engine-thread-owned source of truth (and stays zero-arg
        resettable); the registry is the exposition view over it."""
        reg, s = self.telemetry.registry, self.stats
        for field, metric, help_txt in _STATS_COUNTERS:
            reg.counter(metric, help_txt).set(getattr(s, field))
        if self.faults is not None:
            reg.counter("spa_fault_injected_total",
                        "fault-injector fires").set(self.faults.total_fired)
        reg.gauge("spa_fault_degrade_level",
                  "graceful-degradation ladder rung (0 = full service)"
                  ).set(s.degrade_level)
        reg.gauge("spa_engine_queue_depth",
                  "queued requests").set(len(self.queue))
        reg.gauge("spa_engine_running_requests",
                  "admitted in-flight requests").set(len(self._running))
        for h, name in ((s.e2e_latencies, "spa_engine_e2e_latency_seconds"),
                        (s.queue_waits, "spa_engine_queue_wait_seconds"),
                        (s.ttft_latencies, "spa_engine_ttft_seconds"),
                        (s.tpot_latencies, "spa_engine_tpot_seconds")):
            # re-adopt every collect: `eng.stats = EngineStats()` warm-up
            # resets swap the histogram objects out from under us
            reg.adopt(h, name, h.help)
        # each tier owns its exposition names (pool.py / prefix.py /
        # hier.py telemetry_gauges) — the collector just mirrors them
        for obj in (self.pool, self.prefix, self.host_pool):
            if obj is not None:
                for name, (help_txt, val) in obj.telemetry_gauges().items():
                    reg.gauge(name, help_txt).set(val)
        # compile/retrace accounting + live-executable count (§12):
        # spa_runtime_* series from the process-wide tracker
        runtime.compile_tracker().export_metrics(reg)
        if self.pool is not None:
            for sig, nbytes in self.pool.arena_bytes().items():
                reg.gauge("spa_pool_arena_bytes",
                          "device bytes per cache-signature arena",
                          labels={"signature": sig}).set(nbytes)

    def render_metrics(self) -> str:
        """Prometheus text exposition of the live registry (the
        frontend's ``GET /metrics``).  Reads race the engine thread
        benignly, like ``stats_snapshot`` — ints/floats only."""
        return self.telemetry.registry.render()

    def export_trace(self, path: str) -> None:
        """Write the tracer's Chrome-trace JSON (Perfetto-loadable)."""
        self._tr.export(path)

    def request_states(self, done_tail: int = 32) -> Dict[str, List[Dict]]:
        """JSON-able per-request lifecycle view (``GET /debug/requests``):
        queued / running / recently finished, with timings."""
        def row(r: Request, state: str) -> Dict:
            return {
                "uid": r.uid, "state": state, "priority": r.priority,
                "gen_len": r.gen_len, "pages": r.n_pages,
                "shared_pages": r.shared_n,
                "preemptions": r.preemptions,
                "tokens_done": r.tokens_done,
                "submitted_at": r.submitted_at,
                "started_at": r.started_at,
                "first_token_at": r.first_token_at,
                "completed_at": r.completed_at,
                "shed": r.shed, "canceled": r.canceled,
                "fault": r.fault,
                "slo": (None if r.slo is None else
                        {"ttft": (None if r.slo.ttft == float("inf")
                                  else r.slo.ttft),
                         "deadline": (None
                                      if r.slo.deadline == float("inf")
                                      else r.slo.deadline)}),
            }
        return {
            "queued": [row(r, "queued") for r in list(self.queue)],
            "running": [row(r, "running")
                        for r in list(self._running.values())],
            "done": [row(r, "done") for r in self.done[-done_tail:]],
        }

    def pool_debug_state(self) -> Dict:
        """JSON-able memory-observability view (``GET /debug/pool``,
        DESIGN.md §12): device-pool occupancy + fragmentation +
        per-signature bytes, host-tier slot accounting, tier-manager
        counters and the tracked live-executable count.  Reads race
        the engine thread benignly (ints/floats/strings only)."""
        out: Dict = {
            "paged": self.paged,
            "live_executables": runtime.live_executable_count(),
        }
        if self.pool is not None:
            out["pool"] = self.pool.debug_state()
        if self.host_pool is not None:
            out["host_pool"] = self.host_pool.debug_state()
        if self.tier is not None:
            t = self.tier
            out["tier"] = {
                "demoted_pages": t.demoted_pages,
                "promoted_pages": t.promoted_pages,
                "dropped_full": t.dropped_full,
                "dropped_stable": t.dropped_stable,
                "store_faults": t.store_faults,
                "checksum_failures": t.checksum_failures,
            }
        return out

    def submit(self, prompt: np.ndarray, gen_len: int,
               settings: Optional[DecodeSettings] = None,
               strategy: Optional[CacheStrategy] = None,
               scheduler: Optional[UnmaskScheduler] = None,
               priority: int = 0,
               row_len: Optional[int] = None,
               slo: Optional[SLO] = None,
               stream: bool = False,
               sink: Optional[Callable] = None) -> int:
        """Queue one request.  Rejects requests that can never be
        scheduled (``gen_len`` outside the canvas, or a page footprint
        beyond the whole pool) with a clear error instead of letting
        them starve the queue forever.

        ``row_len`` (paged mode) reserves a larger page-aligned canvas
        span than prompt+gen needs — cross-turn chat reserves the same
        span every turn so the prefix index's layout keys line up
        (DESIGN.md §6).  ``slo``/``stream``/``sink`` are the online
        serving surface (DESIGN.md §8).  Engine-thread only — remote
        threads use ``submit_threadsafe``."""
        req = self._build_request(prompt, gen_len, settings, strategy,
                                  scheduler, priority=priority,
                                  row_len=row_len, slo=slo,
                                  stream=stream, sink=sink)
        self._enqueue(req)
        return req.uid

    def submit_threadsafe(self, prompt: np.ndarray, gen_len: int,
                          **kw) -> int:
        """``submit`` from any thread: validation and lane resolution
        run on the caller (errors raise there), the queue append rides
        the mailbox onto the engine thread.  Returns the uid
        immediately — events may start arriving before this returns
        only on the request's own ``sink``, which is attached first."""
        req = self._build_request(prompt, gen_len, **kw)
        self._mailbox.put(lambda: self._enqueue(req))
        return req.uid

    def cancel(self, uid: int) -> bool:
        """Abort a queued or running request: its pages, prefix holds
        and canvas row are released and it finalizes with no output
        (``canceled`` on the request; "canceled" event).  Engine-thread
        only — remote threads use ``cancel_threadsafe``.  Returns False
        for unknown/already-finished uids."""
        for r in list(self.queue):
            if r.uid == uid:
                self.queue.remove(r)
                self._drop_plan(r)
                r.canceled = True
                self._finalize_aborted(r)
                return True
        r = self._running.get(uid)
        if r is not None and not r.canceled:
            r.canceled = True     # the step loop releases slot + pages
            return True
        return False

    def cancel_threadsafe(self, uid: int) -> None:
        self._mailbox.put(lambda: self.cancel(uid))

    def _enqueue(self, req: Request) -> None:
        self._admission_dirty = True
        self.queue.append(req)
        tr = self._tr
        if tr.enabled:
            tr.name_track(PID_REQUESTS, req.uid, f"req {req.uid}")
            tr.begin(PID_REQUESTS, req.uid, "request", cat="lifecycle",
                     args={"prompt_len": int(len(req.prompt)),
                           "gen_len": req.gen_len,
                           "priority": req.priority})
            tr.begin(PID_REQUESTS, req.uid, "queued", cat="lifecycle")

    def _drain_mailbox(self) -> None:
        while True:
            try:
                fn = self._mailbox.get_nowait()
            except queue_mod.Empty:
                return
            fn()

    def _build_request(self, prompt: np.ndarray, gen_len: int,
                       settings: Optional[DecodeSettings] = None,
                       strategy: Optional[CacheStrategy] = None,
                       scheduler: Optional[UnmaskScheduler] = None,
                       priority: int = 0,
                       row_len: Optional[int] = None,
                       slo: Optional[SLO] = None,
                       stream: bool = False,
                       sink: Optional[Callable] = None) -> Request:
        # full validation runs HERE, on the submitting thread — both
        # submit() and submit_threadsafe() route through this, so an
        # invalid request raises at the caller and a malformed mailbox
        # entry can never abort the engine loop mid-step (DESIGN.md §10)
        if not isinstance(gen_len, (int, np.integer)) \
                or isinstance(gen_len, bool):
            raise ValueError(f"gen_len must be an int, got "
                             f"{type(gen_len).__name__}")
        if gen_len <= 0 or gen_len > self.canvas_len:
            raise ValueError(
                f"gen_len {gen_len} cannot be scheduled on a "
                f"canvas_len={self.canvas_len} engine (need "
                f"0 < gen_len <= canvas_len)")
        prompt = np.asarray(prompt)
        if prompt.ndim != 1:
            raise ValueError(f"prompt must be a 1-D token array, got "
                             f"shape {prompt.shape}")
        if prompt.size and not np.issubdtype(prompt.dtype, np.integer):
            raise ValueError(f"prompt must hold integer token ids, got "
                             f"dtype {prompt.dtype}")
        # monotonic counter — NOT len(done)+len(queue): with requests
        # in-flight (popped but not done) that length dips and reuses
        # live uids (regression-tested in tests/test_serving.py).
        # Locked so submit_threadsafe callers never race the engine.
        with self._uid_lock:
            uid = self._next_uid
            self._next_uid += 1
        req = Request(uid, np.asarray(prompt, np.int32), gen_len,
                      settings, strategy, scheduler, priority=priority,
                      submitted_at=self._now(), slo=slo, stream=stream,
                      sink=sink)
        req.lane = self._lane_of(req)   # freeze vs later default changes
        if self.paged:
            p_len = min(len(req.prompt), self.canvas_len - gen_len)
            span = max(p_len + gen_len, row_len or 0)
            req.row_len = min(
                -(-span // self.page_size) * self.page_size,
                self.canvas_len)
            strategy_r = req.lane[1]
            req.n_pages = (self.pool.pages_for(req.row_len)
                           if strategy_r.uses_cache else 0)
            if req.n_pages > self.pool.capacity:
                raise OutOfPages(
                    f"request uid={uid} needs {req.n_pages} pages; pool "
                    f"capacity is {self.pool.capacity} — it can never "
                    f"be admitted (grow --pool-pages or shrink the "
                    f"request)")
        else:
            req.row_len = self.canvas_len
        return req

    # ------------------------------------------------------------------

    def _lane_of(self, req: Request) -> LaneKey:
        """Resolve a request's lane: per-request overrides win WHOLESALE
        (a request that passes settings gets that settings' commit
        policy, including ``parallel_threshold=0.0`` = sequential),
        engine defaults fill the gaps, legacy settings knobs map to
        their scheduler equivalent.  The parallel knobs are normalized
        OUT of the keyed settings once the scheduler is resolved
        (serve_step never reads them again), so a request submitted
        with ``parallel_threshold=0.1`` shares an executable with one
        submitted with ``ParallelThresholdScheduler(0.1)``."""
        settings = req.settings or self.settings
        strategy = req.strategy or self.strategy
        # precedence: request scheduler > request settings knobs >
        # engine scheduler > engine settings knobs > confidence default
        if req.scheduler is not None:
            scheduler = req.scheduler
        elif req.settings is not None:
            scheduler = resolve_scheduler(req.settings)
        else:
            scheduler = resolve_scheduler(self.settings, self.scheduler)
        settings = dataclasses.replace(settings, parallel_threshold=0.0,
                                       max_parallel=0)
        return settings, strategy, scheduler

    def _proxies_for(self, strategy: CacheStrategy):
        if strategy not in self._proxies:
            self._proxies[strategy] = strategy.build_proxies(
                self.params, self.cfg)
        return self._proxies[strategy]

    def _session_for(self, lane: LaneKey) -> DecodeSession:
        if lane not in self._sessions:
            settings, strategy, scheduler = lane
            label = (f"{getattr(strategy, 'name', 'strategy')}"
                     f"/{getattr(strategy.backend, 'name', 'backend')}"
                     f"/{type(scheduler).__name__}"
                     f"#{self._lane_id(lane)}")
            self._sessions[lane] = DecodeSession(
                self.params, self.cfg, strategy=strategy,
                settings=settings, scheduler=scheduler,
                spa_proxies=self._proxies_for(strategy),
                profiler=self.profiler, label=label)
        return self._sessions[lane]

    # ------------------------------------------------------------------
    # Online serving: events, SLO shedding, cancellation (DESIGN.md §8)
    # ------------------------------------------------------------------

    def _emit(self, req: Request, kind: str,
              positions: Tuple[int, ...] = (),
              tokens: Tuple[int, ...] = ()) -> None:
        sink = req.sink or (self.event_sink if req.stream else None)
        if sink is None:
            return
        sink(RequestEvent(kind=kind, uid=req.uid, step=self.stats.steps,
                          ts=self._now(), positions=positions,
                          tokens=tokens))

    def _eff_priority(self, req: Request, now: float) -> int:
        if self.slo_policy is None:
            return req.priority
        return self.slo_policy.effective_priority(req, now)

    def _shed_hopeless(self) -> None:
        """Drop queued requests that can no longer contribute goodput
        (missed TTFT while waiting / e2e deadline passed).  At ladder
        L3 (DESIGN.md §10) low-priority queued work is shed outright
        and the SLO deadlines tighten by ``hopeless_margin``."""
        if self._shed_low_priority:
            for r in list(self.queue):
                if r.priority < self._shed_below:
                    self.queue.remove(r)
                    self._drop_plan(r)
                    r.shed = True
                    self._finalize_aborted(r)
        if self.slo_policy is None or not self.slo_policy.shed:
            return
        now = self._now()
        for r in list(self.queue):
            if r.slo is not None and self.slo_policy.hopeless(
                    r, now, margin=self._hopeless_margin):
                self.queue.remove(r)
                self._drop_plan(r)
                r.shed = True
                self._finalize_aborted(r)

    def _finalize_aborted(self, req: Request) -> None:
        """Common exit for canceled and shed requests: release every
        resource (read holds were dropped by the caller for queued
        requests; running requests still own pages) and finalize with
        no output."""
        if self.paged:
            self._release_holds(req)
            if req.pages:
                self.pool.free(req.pages)
                req.pages = None
        req.completed_at = self._now()
        self._running.pop(req.uid, None)
        self._admission_dirty = True   # a slot/pages may have freed
        self.done.append(req)
        if self._tr.enabled:
            # the request may be mid-"queued" or mid-"running"; close
            # whatever is open on its track so no span is orphaned
            outcome = ("shed" if req.shed
                       else "fault" if req.fault is not None
                       else "canceled")
            self._tr.close_track(PID_REQUESTS, req.uid,
                                 args={"outcome": outcome})
        if req.shed:
            self.stats.requests_shed += 1
            if req.slo is not None:   # a shed request IS a missed SLO
                self.stats.slo_missed += 1
            self._emit(req, "shed")
        elif req.fault is not None:
            # fault containment killed it (§10): distinct from a client
            # cancel so chaos tests can assert the aborted-uid set
            self.stats.requests_faulted += 1
            self._emit(req, "aborted")
        else:
            self.stats.requests_canceled += 1
            self._emit(req, "canceled")

    def _host_overlap(self, lane: LaneKey,
                      slots: List[Optional[Request]]) -> None:
        """Host-side work double-buffered against the in-flight device
        step (DESIGN.md §8): runs after the step is dispatched but
        before the first host sync on its outputs.  Everything here is
        host-only — mailbox intake, SLO shedding, and the prefix-trie
        lookup + read holds for the next admission candidate (which
        ``_admit_one`` then reuses via ``plan_epoch``)."""
        self._drain_mailbox()
        self._shed_hopeless()
        pol = self.slo_policy
        if pol is not None and self.queue and not self._admission_dirty:
            # a queued request crossing the urgency threshold changes
            # the admission outcome (boost can preempt a running row) —
            # re-scan even though no finish/arrival event fired
            now = self._now()
            for r in self.queue:
                if not r.boosted and pol.urgent(r, now):
                    r.boosted = True
                    self._admission_dirty = True
        if pol is not None and pol.shed:
            now = self._now()
            for s in slots:
                if (s is not None and not s.canceled and s.slo is not None
                        and now > s.submitted_at + s.slo.deadline):
                    s.canceled = True    # running past deadline: shed
                    s.shed = True
        if self._stop is not None and self._stop.is_set():
            for s in slots:              # clean shutdown: abort in-flight
                if s is not None:
                    s.canceled = True
        if (self.paged and self.prefix is not None
                and self._admission_dirty):
            for req in self._lane_candidates(lane)[:1]:
                if req.n_pages:
                    # plans AND services a PROMOTING candidate inside
                    # the dispatch window: the host->device write rides
                    # the live arenas in dataflow order, overlapping
                    # the in-flight decode step (DESIGN.md §9)
                    self._plan_with_promotion(req)

    def _stream_tokens(self, slots: List[Optional[Request]],
                       sess: DecodeSession,
                       p_lens: List[int]) -> None:
        """Emit token events for streaming slots: diff the gen span of
        the canvas against each request's emitted mask.  Canvas
        diffing (not the commit ring) so wide parallel commits that
        overflow the ring never drop stream tokens."""
        live = [(i, s) for i, s in enumerate(slots)
                if s is not None and not s.canceled and s.fault is None
                and (s.sink is not None
                     or (s.stream and self.event_sink is not None))]
        if not live:
            return
        toks = sess.host_tokens()
        mask_id = self.cfg.mask_id
        for i, req in live:
            span = toks[i, p_lens[i]: p_lens[i] + req.gen_len]
            if req.emitted is None:
                req.emitted = np.zeros((req.gen_len,), bool)
            fresh = (span != mask_id) & ~req.emitted
            if not fresh.any():
                continue
            pos = np.nonzero(fresh)[0]
            req.emitted[pos] = True
            self._emit(req, "token", positions=tuple(int(p) for p in pos),
                       tokens=tuple(int(t) for t in span[pos]))

    # ------------------------------------------------------------------
    # Shared-prefix index (DESIGN.md §6)
    # ------------------------------------------------------------------

    def _prompt_in_canvas(self, req: Request) -> np.ndarray:
        """The prompt tokens that actually land on the canvas (the
        index key must describe the canvas, not the raw request)."""
        return req.prompt[: self.canvas_len - req.gen_len]

    def _prefix_key(self, req: Request):
        return (req.row_len, req.lane[1].prefix_key())

    def _prefix_plan(self, req: Request) -> None:
        """Consult the index for an admission candidate: on a hit, take
        read holds on the matched pages — they will be mapped at the
        row's logical prefix, with ``req.pages[:shared_n]`` as the
        copy-on-write reserve.  Runs BEFORE the shortage check so the
        holds protect the matched entry from this admission's own index
        eviction; a stalled candidate releases them again.  Resumed
        requests never match: their canvas holds committed generation
        the publisher prefilled as [MASK]."""
        self._drop_plan(req)    # releases stale holds, never leaks them
        if (self.prefix is None or req.preemptions > 0
                or not req.n_pages):
            return
        match = self.prefix.lookup(self._prefix_key(req),
                                   self._prompt_in_canvas(req),
                                   partial_ok=self._partial_ok,
                                   promote_ok=(self.tier is not None
                                               and not req.no_promote
                                               and not
                                               self._host_tier_paused))
        if match is None:
            return
        if match.needs_promotion:
            # PROMOTING: the match lives (partly) in the host tier —
            # no holds yet; _promote_now converts this to a device plan
            req.pending_promotion = match
            return
        self.pool.retain(list(match.pages))
        req.holds = list(match.pages)
        req.shared_n = match.n_pages
        req.shared_full = match.full

    def _drop_plan(self, req: Request) -> None:
        self._release_holds(req)
        req.shared_n, req.shared_full = 0, False
        req.plan_epoch = None
        req.pending_promotion = None

    def _count_prefix_hit(self, req: Request) -> None:
        """Admission succeeded: account the planned hit."""
        self.telemetry.registry.histogram(
            "spa_prefix_hit_depth_pages",
            "index pages attached per admission (0 = miss)",
            buckets=_HIT_DEPTH_BUCKETS).observe(req.shared_n
                                                if req.holds else 0)
        if not req.holds:
            return
        self.stats.prefix_hits += 1
        if req.shared_full:
            self.stats.prefix_full_hits += 1
            self.stats.prefix_tokens_saved += req.row_len
        else:
            self.stats.prefix_tokens_saved += (req.shared_n
                                               * self.page_size)

    def _attach_spec(self, req: Request, row: int):
        """(page-table row, SharedPrefix|None) for one slot."""
        if not req.holds:
            return self._pt_row(req), None
        m = req.shared_n
        pt_pages = req.holds + (req.pages or [])[m:]
        spec = SharedPrefix(row=row, pages=tuple(req.holds),
                            reserve=tuple((req.pages or [])[:m]))
        return self.pool.page_table_row(pt_pages, self.canvas_len), spec

    def _on_cow(self, slots: List[Optional[Request]],
                specs) -> None:
        """Session copy-on-write fired: drop the read holds — the rows
        now run entirely on their own pages."""
        for s in specs:
            req = slots[s.row]
            if req is not None and req.holds:
                self.pool.release(req.holds)
                req.holds = None

    def _release_holds(self, req: Request) -> None:
        if req.holds:
            self.pool.release(req.holds)
            req.holds = None

    def _maybe_publish(self, req: Request, sess: DecodeSession) -> None:
        """Publish an attached request's prefill-time pages into the
        index (admission time — BEFORE the first decode write evolves
        them; harvest-time states would break full-hit byte parity).
        Cold requests publish their whole run (prompt path + all-[MASK]
        tail); partial hits publish only the depths past their match,
        extending the trie.  A page copy pays for it; skipped when the
        pool has no slack."""
        if self.prefix is None or req.preemptions > 0 or not req.n_pages:
            return
        if self._publish_paused:
            # ladder L1 (§10): stop growing shared state under fault
            # pressure — the cheapest capability to shed, since misses
            # only cost prefill compute, never correctness
            self.stats.publish_paused_skips += 1
            return
        n_run = req.row_len // self.page_size
        m = req.shared_n if req.holds else 0
        if m >= n_run:
            return                       # full hit: already indexed
        key = self._prefix_key(req)
        prompt = self._prompt_in_canvas(req)
        # read-only probe first: duplicate prompts admitted in one batch
        # all plan before the first publishes, so later ones would
        # otherwise alloc + device-copy a full run just to have insert
        # reject every page
        missing = [d for d in self.prefix.missing_slots(key, prompt,
                                                        n_run) if d >= m]
        if not missing:
            return
        pub = self.pool.alloc(len(missing))
        if pub is None:
            self.stats.prefix_publish_skipped += 1
            return
        sess.copy_cache_pages([(req.pages or [])[d] for d in missing],
                              pub)
        pages: List[Optional[int]] = [None] * n_run
        for d, p in zip(missing, pub):
            pages[d] = p
        rejected = self.prefix.insert(key, prompt, pages)
        if rejected:
            self.pool.release(rejected)
        adopted = [p for p in pub if p not in rejected]
        if adopted and self.tier is not None:
            # register signature + per-page stability (from the
            # identifier rows just copied) so a later demotion knows
            # which arenas to read and how cold-worthy each page is
            self.tier.note_published(
                cache_signature(self.cfg, req.lane[1]), adopted,
                self._proxy_blocks(sess, adopted))
        self.stats.prefix_published += len(pub) - len(rejected)
        self._prefix_epoch += 1       # pre-planned misses may now hit

    def drop_prefix_cache(self) -> int:
        """Release every index hold, free every host-tier ref, and
        clear the trie (tests, or explicit memory reclamation).
        Returns device pages released."""
        if self.prefix is None:
            return 0
        self._prefix_epoch += 1
        return self.prefix.clear(self.pool)

    # ------------------------------------------------------------------
    # Host tier: demote/promote IO + promotion service (DESIGN.md §9)
    # ------------------------------------------------------------------

    def _tier_read(self, sig: Tuple, pages: List[int]):
        """Demotion read: whole physical pages as host (numpy) blocks.
        Mid-lane the live arenas are the active session's step futures
        — the pool's stored copies are stale — so reads route through
        the session (np.asarray syncs on the in-flight step)."""
        if self._active_sess is not None and self._active_sig == sig:
            blocks = self._active_sess.read_cache_pages(pages)
        else:
            arenas = self.pool.peek_arenas(sig)
            assert arenas is not None, (
                "demoting pages from a signature with no arenas")
            blocks = cache_lib.read_arena_pages(arenas, pages)
        return {kind: {name: np.asarray(b) for name, b in bufs.items()}
                for kind, bufs in blocks.items()}

    def _tier_write(self, sig: Tuple, pages: List[int], blocks) -> None:
        """Promotion write: scatter host blocks into the signature's
        device arenas.  Through the live session mid-lane the write is
        dispatched (not synced), landing in dataflow order after the
        in-flight step — promotions overlap decode."""
        if self._active_sess is not None and self._active_sig == sig:
            self._active_sess.write_cache_pages(pages, blocks)
            return
        arenas = self.pool.peek_arenas(sig)
        assert arenas is not None, (
            "promoting pages into a signature with no arenas")
        self.pool.put_arenas(
            sig, cache_lib.write_arena_pages(arenas, pages, blocks))

    def _proxy_blocks(self, sess: DecodeSession, pages: List[int]):
        """Per-page singular-proxy identifier rows for stability
        scoring (hier.page_stability) — None for proxy-less caches."""
        cache = sess.state.cache
        sub = {kind: {"proxy": bufs["proxy"]}
               for kind, bufs in cache.arenas.items() if "proxy" in bufs}
        if not sub:
            return None
        kind = next(iter(sub))
        blk = np.asarray(
            cache_lib.read_arena_pages(sub, list(pages))[kind]["proxy"])
        return {p: blk[:, i] for i, p in enumerate(pages)}

    def _evict_index(self, n_pages: int) -> int:
        """Index eviction with the §9 telemetry split: evicted device
        pages divide into demoted (moved host-ward) and dropped.
        Delta-accounted off the prefix counters so warm-up resets of
        ``stats`` don't double-count."""
        d0 = self.prefix.demoted_pages
        x0 = self.prefix.dropped_pages
        freed = self.prefix.evict(self.pool, n_pages)
        self.stats.prefix_demoted_pages += self.prefix.demoted_pages - d0
        self.stats.prefix_dropped_pages += self.prefix.dropped_pages - x0
        if freed:
            self.stats.prefix_evicted_pages += freed
            self._prefix_epoch += 1
            self._tr.instant(
                PID_EVENTS, 2, "demote", cat="tier",
                args={"freed": freed, "step": self.stats.steps,
                      "demoted": self.prefix.demoted_pages - d0,
                      "dropped": self.prefix.dropped_pages - x0})
        return freed

    def _promote_now(self, req: Request) -> bool:
        """Service a PROMOTING request: allocate device pages for the
        match's host refs, write the (dequantized) blocks into the
        signature's arenas, re-point the trie entries, and leave the
        request with a normal device plan + read holds.  Returns True
        on success.  On failure the plan is dropped — a stale match
        replans; a headroom failure marks the request ``no_promote`` so
        its replan runs device-only instead of retrying forever."""
        match = req.pending_promotion
        req.pending_promotion = None
        if match is None:
            return False
        if not self.prefix.sites_intact(match):
            req.plan_epoch = None       # trie moved: replan fresh
            return False
        n = len(match.host_refs)
        # hold the match's device prefix while we make headroom — the
        # eviction below must not cannibalize our own plan
        self.pool.retain(list(match.pages))
        short = max(0, n - self.pool.available)
        if short and self.prefix.evictable_total(self.pool) >= short:
            self._evict_index(short)
        pages = self.pool.alloc(n)
        if pages is None or not self.prefix.sites_intact(match):
            if pages is not None:
                self.pool.free(pages)
            else:
                req.no_promote = True
            self.pool.release(list(match.pages))
            req.plan_epoch = None
            self.stats.promotion_stalls += 1
            return False
        refs = list(match.host_refs)
        try:
            sig, blocks = self.tier.promote(refs)
        except HostPageCorruption:
            # §10: corrupt host bytes never reach the device.  The tier
            # already freed the whole entry's slots; scrub the trie's
            # now-dangling host refs (no free_refs — the slots are
            # gone), drop the fresh alloc and the match holds, and fall
            # back to a cold prefill on replan.
            self.pool.free(pages)
            self.pool.release(list(match.pages))
            self.prefix.scrub_host_sites(match)
            self.stats.host_checksum_failures += 1
            self.stats.cold_prefill_fallbacks += 1
            if self.supervisor is not None:
                self.supervisor.note_pressure("host_corrupt")
            req.plan_epoch = None
            self._prefix_epoch += 1     # the scrubbed entries are gone
            self._admission_dirty = True
            return False
        self._tier_write(sig, pages, blocks)
        all_pages = self.prefix.install_promoted(match, pages)
        self.tier.note_promoted(sig, pages, refs)
        self.pool.retain(pages)         # index owns rc1; reader hold
        req.holds = all_pages
        req.shared_n = len(all_pages)
        req.shared_full = match.full
        self.stats.prefix_promoted_pages += n
        self.stats.prefix_promotions += 1
        self._tr.instant(PID_REQUESTS, req.uid, "promote", cat="tier",
                         args={"pages": n, "step": self.stats.steps})
        self._prefix_epoch += 1         # planned misses may now hit
        req.plan_epoch = self._prefix_epoch
        self._admission_dirty = True
        return True

    def _plan_with_promotion(self, req: Request) -> None:
        """Plan an admission candidate, resolving a PROMOTING state
        synchronously.  A failed promotion replans once against the
        fresh trie (a second PROMOTING outcome is only possible after
        another concurrent mutation — promote again or give up cold)."""
        if req.plan_epoch != self._prefix_epoch:
            self._prefix_plan(req)
            req.plan_epoch = self._prefix_epoch
        if req.pending_promotion is None:
            return
        if not self._promote_now(req) and req.plan_epoch is None:
            self._prefix_plan(req)
            req.plan_epoch = self._prefix_epoch
            if req.pending_promotion is not None \
                    and not self._promote_now(req):
                # two promotion failures in one planning pass: give up
                # on the host tier for this admission and replan
                # device-only (no_promote is sticky, so this
                # terminates) instead of admitting plan-less
                req.no_promote = True
                self._prefix_plan(req)
                req.plan_epoch = self._prefix_epoch

    # ------------------------------------------------------------------
    # Admission control + preemption (paged mode)
    # ------------------------------------------------------------------

    def _lane_candidates(self, lane: LaneKey) -> List[Request]:
        """Lane-matching queued requests in admission order: strict
        (effective) priority first; within a priority, queue order —
        or, under an SLO policy, earliest TTFT deadline first (EDF),
        with queue order breaking slack ties.  The SLO boost folds into
        the effective priority, so a near-deadline request jumps ahead
        of (and may preempt) slack-rich peers."""
        matches = [(i, r) for i, r in enumerate(self.queue)
                   if r.lane == lane]
        if self.slo_policy is None:
            return [r for _, r in
                    sorted(matches, key=lambda ir: (-ir[1].priority,
                                                    ir[0]))]
        pol, now = self.slo_policy, self._now()
        return [r for _, r in sorted(matches, key=lambda ir: (
            -pol.effective_priority(ir[1], now),
            pol.ttft_slack(ir[1], now), ir[0]))]

    def _preempt(self, slot: int, victim: Request,
                 slots: List[Optional[Request]],
                 sess: DecodeSession) -> None:
        """Evict a running request: snapshot its canvas + commit ring,
        release its slot/pages, requeue it at the FRONT of the queue."""
        snap = sess.snapshot_rows([slot])
        victim.snapshot = {k: v[0] for k, v in snap.items()}
        sess.release_rows([slot])
        self._release_holds(victim)      # un-COW'd shared pages go back
        victim.shared_n = 0
        if self.paged:                   # dense lanes have no pool (the
            self.pool.free(victim.pages or [])   # watchdog preempts too)
        victim.pages = None
        victim.preemptions += 1
        self.stats.preemptions += 1
        slots[slot] = None
        self._running.pop(victim.uid, None)
        self.queue.appendleft(victim)
        tr = self._tr
        if tr.enabled:
            tr.end(PID_REQUESTS, victim.uid, "running",
                   args={"exit": "preempt"})
            tr.instant(PID_REQUESTS, victim.uid, "preempt",
                       cat="lifecycle",
                       args={"step": self.stats.steps,
                             "preemptions": victim.preemptions})
            tr.begin(PID_REQUESTS, victim.uid, "queued", cat="lifecycle",
                     args={"resumed": True})

    # ------------------------------------------------------------------
    # fault handling (§10)

    def _inject_nan(self, slots: List[Optional[Request]],
                    sess: DecodeSession) -> None:
        """Arm a deterministic NaN poisoning of one live row's cache
        pages.  The poison is applied inside ``sess.step()`` AFTER the
        refresh rebuild (so refresh_interval=1 lanes can't wash it out)
        — modelling bit-rot on the freshly built arena.  Rows still
        holding un-COW'd shared pages are never picked: poisoning a
        shared page would taint other requests through the index."""
        if not self.paged or self.faults is None:
            return
        victims = [s for s in slots
                   if s is not None and not s.canceled and s.fault is None
                   and s.pages and not s.holds]
        if not victims:
            return
        k = self.faults.fired["step_nan"] - 1   # this probe already fired
        pick = victims[choose_index(self.faults.plan.seed, "nan_row",
                                    k, len(victims))]
        sess.poison_pages_after_refresh(pick.pages)

    def _disconnect_burst(self, slots: List[Optional[Request]]) -> None:
        """Client disconnect burst: every streaming request in the batch
        loses its consumer at once.  Modelled as cancellation — the dead
        scan reaps the rows and their pages on this same iteration."""
        hit = 0
        for s in slots:
            if (s is not None and not s.canceled and s.fault is None
                    and (s.stream or s.sink is not None)):
                s.canceled = True
                hit += 1
        if hit:
            self.stats.disconnect_bursts += 1
            if self.supervisor is not None:
                self.supervisor.note_pressure("disconnect")

    def _watchdog_recover(self, lane: LaneKey,
                          slots: List[Optional[Request]],
                          sess: DecodeSession) -> None:
        """Watchdog fired: the lane made no progress for a full budget
        window (stuck device / livelocked batch).  Recovery is a device
        reset in miniature: finalize rows already canceled or faulted,
        force-preempt the rest back to the queue via their snapshots,
        and clear any injected stall so the rebuilt lane can run."""
        self.stats.watchdog_fires += 1
        dead = [i for i, s in enumerate(slots)
                if s is not None and (s.canceled or s.fault is not None)]
        for i in dead:
            req = slots[i]
            slots[i] = None
            self._finalize_aborted(req)
        if dead:
            if self.paged:
                sess.release_rows(dead)
            else:
                sess.deactivate_rows(dead)
        for i, r in enumerate(slots):
            if r is not None:
                self._preempt(i, r, slots, sess)
        if self.faults is not None:
            self.faults.clear_stall(lane)
        if self.supervisor is not None:
            self.supervisor.note_pressure("watchdog")
            self.supervisor.lane_started()

    def _admit_one(self, lane: LaneKey, slots: List[Optional[Request]],
                   sess: Optional[DecodeSession],
                   protected: Tuple[int, ...] = ()) -> Optional[Request]:
        """Admit one lane request: it needs a free SLOT and (paged mode)
        enough free PAGES.  When either is short, strictly
        lower-priority running requests are preempted — lowest priority
        first, most recently started first within a priority (the
        oldest work keeps its progress) — until the candidate fits; if
        the eligible victims can't cover it, the candidate stalls and
        smaller/lower-priority candidates get a chance.  Returns the
        admitted request (popped from the queue, pages allocated) or
        None.

        ``protected`` slots are admitted-but-not-yet-attached this swap
        round: the session has no state for them, so they cannot be
        preemption victims."""
        stalled = False
        now = self._now()
        for req in self._lane_candidates(lane):
            if req.retry_after_step > self.stats.steps:
                stalled = True      # backing off a transient alloc fault
                continue
            slot_free = any(s is None for s in slots)
            if not self.paged:
                if not slot_free:
                    return None     # dense mode: no preemption
                self.queue.remove(req)
                self._admit_bookkeep(req)
                return req
            # plan the prefix hit FIRST: the read holds protect the
            # matched entry from this admission's own index eviction.
            # A plan made at the current index epoch (the double-buffer
            # overlap pre-plans the head candidate while the device
            # step is in flight) is reused as-is; a PROMOTING plan is
            # serviced synchronously here (the overlap window is the
            # async fast path for the head candidate).
            self._plan_with_promotion(req)
            page_short = (max(0, req.n_pages - self.pool.available)
                          if req.n_pages else 0)
            victims = []
            if sess is not None:
                req_eff = self._eff_priority(req, now)
                victims = [(i, r) for i, r in enumerate(slots)
                           if r is not None and i not in protected
                           and self._eff_priority(r, now) < req_eff]
                victims.sort(key=lambda ir: (
                    self._eff_priority(ir[1], now),
                    -(ir[1].started_at or 0.0)))
            if page_short and self.prefix is not None:
                # admission pressure: evict LRU reader-less index
                # entries before touching any RUNNING request — but
                # only when eviction (plus the preemptible victims)
                # can actually admit this candidate; destroying LRU
                # entries for a request that stalls anyway trades
                # future hits for nothing
                freeable = sum(len(r.pages or []) for _, r in victims)
                feasible = (
                    (slot_free or victims)
                    and self.pool.available + freeable
                    + self.prefix.evictable_total(self.pool)
                    >= req.n_pages)
                freed = (self._evict_index(page_short)
                         if feasible else 0)
                if freed:
                    page_short = max(0, req.n_pages - self.pool.available)
            if page_short or not slot_free:
                if sess is None:
                    self._drop_plan(req)
                    stalled = True
                    continue
                freeable = sum(len(r.pages or []) for _, r in victims)
                if (self.pool.available + freeable < req.n_pages
                        or (not slot_free and not victims)):
                    self._drop_plan(req)
                    stalled = True
                    continue        # a smaller/later candidate may fit
                for i, r in victims:
                    self._preempt(i, r, slots, sess)
                    if (self.pool.available >= req.n_pages
                            and any(s is None for s in slots)):
                        break
            pages = self.pool.alloc(req.n_pages) if req.n_pages else []
            if pages is None:
                # transient alloc failure (the §10 pool_alloc fault — a
                # genuine shortage was resolved above by eviction /
                # preemption): bounded retry with exponential backoff
                # on the virtual step clock, then a clean fault abort
                self._drop_plan(req)
                self.stats.alloc_faults += 1
                req.alloc_retries += 1
                max_r = (self.supervisor.cfg.max_alloc_retries
                         if self.supervisor is not None else 3)
                if req.alloc_retries > max_r:
                    self.queue.remove(req)
                    req.fault = "pool_alloc"
                    self._finalize_aborted(req)
                else:
                    req.retry_after_step = (
                        self.stats.steps + (1 << (req.alloc_retries - 1)))
                if self.supervisor is not None:
                    self.supervisor.note_pressure("pool_alloc")
                stalled = True
                continue
            self.queue.remove(req)
            req.pages = pages
            self._count_prefix_hit(req)
            self._admit_bookkeep(req)
            return req
        if stalled:
            self.stats.admission_stalls += 1
        return None

    def _admit_bookkeep(self, req: Request) -> None:
        self._running[req.uid] = req   # cancel() finds in-flight by uid
        tr = self._tr
        if tr.enabled:
            tr.end(PID_REQUESTS, req.uid, "queued")
            kind = ("resume" if req.preemptions > 0
                    else "full_hit" if req.shared_full
                    else "partial_prefill" if req.shared_n
                    else "prefill")
            tr.begin(PID_REQUESTS, req.uid, "running", cat="lifecycle",
                     args={"prefill": kind, "pages": req.n_pages,
                           "shared_pages": req.shared_n})

    # ------------------------------------------------------------------
    # Canvas rows
    # ------------------------------------------------------------------

    def _canvas_row(self, req: Request):
        """(tokens [N], active [N], committed_or_None, prompt_len) for
        one slot.  A preempted request resumes from its snapshot: the
        partially committed canvas, active mask and commit ring."""
        if req.snapshot is not None:
            snap = req.snapshot
            req.snapshot = None
            p_len = min(len(req.prompt), self.canvas_len - req.gen_len)
            return (snap["tokens"].copy(), snap["active"].copy(),
                    snap["committed"].copy(), p_len)
        mask_id = self.cfg.mask_id
        row = np.full((self.canvas_len,), mask_id, np.int32)
        p = req.prompt[: self.canvas_len - req.gen_len]
        row[: len(p)] = p
        active = np.zeros((self.canvas_len,), bool)
        active[len(p): len(p) + req.gen_len] = True
        return row, active, None, len(p)

    def _pt_row(self, req: Request) -> List[int]:
        return self.pool.page_table_row(req.pages or [], self.canvas_len)

    def _harvest(self, req: Request, toks_row: np.ndarray,
                 p_len: int) -> None:
        req.output = toks_row[p_len: p_len + req.gen_len]
        req.completed_at = self._now()
        e2e = req.completed_at - req.submitted_at
        self.stats.e2e_latencies.append(e2e)
        if req.started_at is not None:
            self.stats.queue_waits.append(
                req.started_at - req.submitted_at)
        ttft = float("inf")
        if req.first_token_at is not None:
            ttft = req.first_token_at - req.submitted_at
            self.stats.ttft_latencies.append(ttft)
            if req.last_commit_at is not None and req.tokens_done > 1:
                self.stats.tpot_latencies.append(
                    (req.last_commit_at - req.first_token_at)
                    / (req.tokens_done - 1))
        if req.slo is None or req.slo.met(ttft, e2e):
            self.stats.slo_met += 1
        else:
            self.stats.slo_missed += 1
        if self.paged:
            self._release_holds(req)
            if req.pages:
                self.pool.free(req.pages)
                req.pages = None
        self._running.pop(req.uid, None)
        self.done.append(req)
        self.stats.requests_done += 1
        tr = self._tr
        if tr.enabled:
            tr.end(PID_REQUESTS, req.uid, "running",
                   args={"exit": "done", "steps": req.served_steps})
            tr.end(PID_REQUESTS, req.uid, "request",
                   args={"outcome": "done", "tokens": req.tokens_done,
                         "preemptions": req.preemptions})
        self._emit(req, "done",
                   tokens=tuple(int(t) for t in req.output))

    # ------------------------------------------------------------------

    def run(self, max_steps: int = 256, on_step=None) -> EngineStats:
        """Serve the queue to completion.  ``on_step(engine)`` (if given)
        fires after every engine step — submissions made from it join
        the live run and are admitted mid-loop (the arrival path that
        exercises preemption)."""
        t0 = self._now()
        while True:
            self._drain_mailbox()
            self._shed_hopeless()
            if not self.queue:
                break
            lane = self.queue[0].lane
            steps0 = self.stats.steps
            self._run_lane(lane, max_steps, on_step)
            if self.queue and self.stats.steps == steps0:
                # every candidate is backing off a transient alloc
                # fault: idle-tick the virtual step clock so backoffs
                # can expire instead of busy-spinning forever (bounded
                # by max_alloc_retries → fault abort)
                self.stats.steps += 1
        self._wall = self._now() - t0
        self._note_pool_stats()
        return self.stats

    def run_online(self, stop: threading.Event, *, max_steps: int = 256,
                   idle_wait: float = 0.01, on_step=None) -> EngineStats:
        """Serve arrivals until ``stop`` is set — the online front-end's
        engine-thread loop (DESIGN.md §8).  While idle it blocks on the
        mailbox; while serving, arrivals ride the double-buffer overlap
        point into the live batch.  On stop, in-flight requests are
        aborted cleanly (canceled, resources released) and queued
        requests stay queued with their prefix plans dropped — the
        engine can be resumed or drained later."""
        self._stop = stop
        t0 = self._now()
        try:
            while not stop.is_set():
                self._drain_mailbox()
                self._shed_hopeless()
                if self.queue:
                    steps0 = self.stats.steps
                    self._run_lane(self.queue[0].lane, max_steps, on_step)
                    if self.queue and self.stats.steps == steps0:
                        self.stats.steps += 1   # alloc-backoff idle tick
                    continue
                try:
                    fn = self._mailbox.get(timeout=idle_wait)
                except queue_mod.Empty:
                    continue
                fn()
        finally:
            self._stop = None
            self._drain_mailbox()
            for r in list(self.queue):   # shutdown never leaks holds
                self._drop_plan(r)
            self._wall = self._now() - t0
            self._note_pool_stats()
        return self.stats

    def _note_pool_stats(self) -> None:
        if self.faults is not None:
            self.stats.faults_injected = self.faults.total_fired
        if self.paged:
            self.stats.peak_pool_util = (self.pool.peak_used
                                         / max(self.pool.capacity, 1))
            self.stats.steady_pool_util = self.pool.steady_utilization
        if self.host_pool is not None:
            self.stats.peak_host_util = (
                self.host_pool.peak_units
                / max(self.host_pool.capacity_units, 1))

    def _run_lane(self, lane: LaneKey, max_steps: int,
                  on_step=None) -> None:
        sess = self._session_for(lane)
        strategy = lane[1]
        tr = self._tr
        lid = self._lane_id(lane)
        slots: List[Optional[Request]] = [None] * self.max_batch
        batch: List[Request] = []
        while len(batch) < self.max_batch:
            req = self._admit_one(lane, slots, sess=None)
            if req is None:
                break
            batch.append(req)
        if not batch:
            return
        # dense lanes size the canvas to the actual batch (an underfilled
        # lane never pays full-width placeholder rows); paged lanes keep
        # max_batch rows so slots freed later (pages permitting) can
        # admit without a reshape/recompile
        b = self.max_batch if self.paged else len(batch)
        slots = [None] * b
        now = self._now()
        mask_id = self.cfg.mask_id
        tokens = np.full((b, self.canvas_len), mask_id, np.int32)
        active = np.zeros((b, self.canvas_len), bool)
        committed0 = np.full((b, lane[0].commit_ring), -1, np.int32)
        kv = np.zeros((b,), np.int32)
        n_log = (n_logical_pages(self.canvas_len, self.page_size)
                 if self.paged else 0)
        pt = np.zeros((b, n_log), np.int32)
        p_lens = [0] * b
        ages = [0] * b                 # max_steps budget is PER REQUEST
        shared_specs: List[SharedPrefix] = []
        for i, req in enumerate(batch):
            row, act, com, p_len = self._canvas_row(req)
            tokens[i], active[i] = row, act
            if com is not None:
                committed0[i] = com
            slots[i] = req
            p_lens[i] = p_len
            ages[i] = req.served_steps
            kv[i] = req.row_len
            if self.paged and strategy.uses_cache:
                pt[i], spec = self._attach_spec(req, i)
                if spec is not None:
                    shared_specs.append(spec)
            if req.started_at is None:
                req.started_at = now
        if self.paged:
            sess.cow_callback = functools.partial(self._on_cow, slots)
            arenas = (self.pool.arenas_for(strategy)
                      if strategy.uses_cache else None)
            sess.attach(tokens, active=active, kv_len=kv,
                        arenas=arenas, page_table=pt,
                        shared=shared_specs or None)
            if strategy.uses_cache:
                # tier reads/writes route through this session until
                # the lane ends (the pool's copies are stale, §9)
                self._active_sess = sess
                self._active_sig = cache_signature(self.cfg, strategy)
            for req in batch:
                self._maybe_publish(req, sess)
        else:
            sess.attach(tokens, active=active)
        if (committed0 != -1).any():
            sess.state = sess.state._replace(
                committed=sess.state.committed.at[:].set(committed0))

        sup = self.supervisor
        if sup is not None:
            sup.lane_started()
        while any(s is not None for s in slots):
            if self.faults is not None and self.faults.stall_lane(lane):
                # stuck lane (§10): the device step is never dispatched
                # (models a hung device).  Host-side work and the
                # virtual clock still advance, so the watchdog fires
                # within its budget and force-preempts the lane.
                self._host_overlap(lane, slots)
                self.stats.steps += 1
                if on_step is not None:
                    on_step(self)
                if sup is not None:
                    if sup.watchdog(progressed=False):
                        self._watchdog_recover(lane, slots, sess)
                    sup.on_iteration()
                continue
            if self.faults is not None and self.faults.fire("step_nan"):
                self._inject_nan(slots, sess)
            with self._phase(lid, "dispatch"):
                info = sess.step()
            # double-buffered dispatch (DESIGN.md §8): the jitted step
            # is dispatched but NOT synced yet — mailbox intake, SLO
            # shedding and next-candidate prefix planning run on the
            # host while the device step is in flight.
            with self._phase(lid, "host_overlap"):
                self._host_overlap(lane, slots)
            self.stats.steps += 1
            if self.paged:
                self.pool.note_step()
            with self._phase(lid, "host_sync"):
                n_comm = np.asarray(info["n_committed"])  # first host sync
            if tr.enabled:
                if self.paged:
                    tr.counter(PID_ENGINE, "pool_pages",
                               {"used": self.pool.used,
                                "free": self.pool.available})
                if self.host_pool is not None:
                    tr.counter(PID_ENGINE, "host_tier_units",
                               {"used": self.host_pool.used_units})
                tr.counter(PID_ENGINE, "queue_depth",
                           {"queued": len(self.queue),
                            "running": len(self._running)})
            self.stats.tokens_committed += int(n_comm.sum())
            # cache-dynamics sampling (DESIGN.md §11): host-side proxy
            # diffing AFTER the step's first host sync — never on the
            # dispatch path, never into the compiled graph
            dyn = self.telemetry.dynamics_every
            if dyn and strategy.uses_cache \
                    and self.stats.steps % dyn == 0:
                self._note_cache_dynamics(
                    sess, strategy,
                    n_live=sum(s is not None for s in slots))
            if self.faults is not None and self.faults.fire("disconnect"):
                self._disconnect_burst(slots)
            nan_rows = (sup.nan_guard(info, slots)
                        if sup is not None and self.paged else [])
            if on_step is not None:
                on_step(self)
            now = self._now()
            for i, s in enumerate(slots):     # TTFT / TPOT bookkeeping
                if s is None or s.fault is not None or n_comm[i] <= 0:
                    continue
                if s.first_token_at is None:
                    s.first_token_at = now
                s.last_commit_at = now
                s.tokens_done += int(n_comm[i])
            with self._phase(lid, "stream"):
                self._stream_tokens(slots, sess, p_lens)
                n_masked = np.asarray(sess.state.n_masked)
            finished, dead = [], []
            for i, s in enumerate(slots):
                if s is None:
                    continue
                ages[i] += 1
                s.served_steps = ages[i]
                # a request that exhausts its own step budget is
                # harvested as-is (same semantics as the old
                # run-to-max_steps static batch loop)
                if s.canceled or s.fault is not None:
                    dead.append(i)
                elif n_masked[i] <= 0 or ages[i] >= max_steps:
                    finished.append(i)
            progressed = bool(int(n_comm.sum()) > 0 or finished or dead)
            if sup is not None:
                with self._phase(lid, "supervisor"):
                    fired = sup.watchdog(progressed)
                    if fired:
                        self._watchdog_recover(lane, slots, sess)
                    else:
                        sup.on_iteration()
                if fired:
                    continue
            if not (finished or dead) and not (self.continuous
                                               and self._admission_dirty):
                continue
            if finished or dead:
                with self._phase(lid, "release"):
                    toks = sess.host_tokens()
                    for i in finished:
                        self._harvest(slots[i], toks[i], p_lens[i])
                        slots[i] = None
                    for i in dead:
                        req = slots[i]
                        slots[i] = None
                        self._finalize_aborted(req)
                    if self.paged:
                        # zero the finished rows' page-table entries
                        # BEFORE their freed pages can be re-allocated
                        # below — a stale entry would let the dead
                        # row's next write-back corrupt the new owner's
                        # pages
                        sess.release_rows(finished + dead)
            if nan_rows:
                # NaN quarantine (§10): the poisoned rows died above;
                # force-preempt every surviving lane-mate so the batch
                # rebuilds from preemption snapshots — one poisoned
                # canvas never taints its neighbours' outputs.
                self.stats.nan_quarantines += len(nan_rows)
                for i, r in enumerate(slots):
                    if r is not None:
                        self._preempt(i, r, slots, sess)
                continue
            with self._phase(lid, "admit"):
                swap_rows, swap_tokens, swap_active = [], [], []
                swap_kv, swap_pt, swap_com = [], [], []
                swap_shared: List[SharedPrefix] = []
                while self.continuous:
                    # fill every empty slot — and let _admit_one MAKE one by
                    # preempting a lower-priority row when a high-priority
                    # arrival finds the batch/pool full — until admission
                    # stalls or the queue drains
                    req = self._admit_one(lane, slots, sess,
                                          protected=tuple(swap_rows))
                    if req is None:
                        break
                    empty = [i for i, s in enumerate(slots) if s is None]
                    i = empty[0]
                    row, act, com, p_len = self._canvas_row(req)
                    slots[i] = req
                    p_lens[i] = p_len
                    ages[i] = req.served_steps
                    if req.started_at is None:
                        req.started_at = self._now()
                    swap_rows.append(i)
                    swap_tokens.append(row)
                    swap_active.append(act)
                    swap_kv.append(req.row_len)
                    if self.paged and strategy.uses_cache:
                        pt_row, spec = self._attach_spec(req, i)
                        swap_pt.append(pt_row)
                        if spec is not None:
                            swap_shared.append(spec)
                    else:
                        swap_pt.append([0] * n_log)
                    swap_com.append(com if com is not None else np.full(
                        (committed0.shape[1],), -1, np.int32))
                self._admission_dirty = False
                if swap_rows:
                    if self.paged:
                        sess.replace_rows(
                            swap_rows, np.stack(swap_tokens),
                            np.stack(swap_active),
                            row_kv_len=np.asarray(swap_kv, np.int32),
                            row_page_table=np.asarray(swap_pt, np.int32),
                            row_committed=np.stack(swap_com),
                            row_shared=swap_shared or None)
                        for i in swap_rows:
                            self._maybe_publish(slots[i], sess)
                    else:
                        sess.replace_rows(swap_rows, np.stack(swap_tokens),
                                          np.stack(swap_active))
                    self.stats.swaps += len(swap_rows)
            parked = [i for i in finished + dead if i not in swap_rows
                      and slots[i] is None]
            if parked and not self.paged:   # paged rows released above
                sess.deactivate_rows(parked)
        if (self.paged and strategy.uses_cache and sess.state is not None
                and isinstance(sess.state.cache, PagedCache)):
            self.pool.store_arenas(strategy, sess.state.cache.arenas)
        self._active_sess = None
        self._active_sig = None
