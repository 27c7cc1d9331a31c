"""Process-wide JAX runtime accounting (DESIGN.md §12).

Three small facilities that every layer above core can share:

  * **Executable tracking** — ``track_executables`` registers a jitted
    callable in a process-wide weak set; ``live_executable_count`` sums
    the per-function executable-cache sizes (``PjitFunction._cache_size``
    — compiled executables live in C++ and are invisible to ``gc``, so
    counting them any other way reads zero).  Coverage is best-effort by
    construction: whoever jits a function registers it, and the decode
    sessions (the dominant executable source — one step fn + loop fns +
    partial prefills per lane) all do.
  * **The ONE executable-cache dropper** — ``drop_executables`` wraps
    ``jax.clear_caches()`` and reports how many live executables it
    cleared.  ``tests/conftest.py`` and ``benchmarks/bench_serving.py``
    used to hand-roll the same call; both now come through here.
  * **Compile/retrace accounting** — :class:`CompileTracker` counts
    every retrace exactly (a Python wrapper around the function handed
    to ``jax.jit`` only executes at trace time, so its invocation count
    IS the trace count — and it is a no-op on traced values, so decode
    outputs are byte-identical with counting on).  ``jax.monitoring``
    duration events add backend-compile wall time; its persistent
    compile-cache hit events tell true compiles from cache reads.

Beside them, :func:`enable_compile_cache` places JAX's persistent
compilation cache for the entry points (serve launcher, benchmarks,
chip smoke).

Counting is passive and always-on: it is host-side, fires only at trace
time (never per step), and costs one dict increment per compile — so
unlike the :mod:`repro.serving.profiling` step decomposition it needs
no enable flag.
"""
from __future__ import annotations

import functools
import os
import threading
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

__all__ = [
    "track_executables", "live_executable_count", "drop_executables",
    "CompileTracker", "compile_tracker", "enable_compile_cache",
]

# <repo>/.jax_cache: a fixed path (the path is part of every cache key,
# so a directory that moves between runs never hits); git-ignored.
REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and nothing else is set here; otherwise the cache lives at
    :data:`REPO_CACHE_DIR`.  Call before the first compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path

_LOCK = threading.Lock()
_TRACKED: "weakref.WeakSet" = weakref.WeakSet()


def track_executables(fn: Any) -> Any:
    """Register a jitted callable for live-executable accounting and
    return it unchanged (chainable around ``jax.jit(...)``)."""
    if hasattr(fn, "_cache_size"):
        with _LOCK:
            _TRACKED.add(fn)
    return fn


def live_executable_count() -> int:
    """Total compiled executables across tracked jitted functions."""
    total = 0
    with _LOCK:
        fns = list(_TRACKED)
    for fn in fns:
        try:
            total += int(fn._cache_size())
        except Exception:      # fn mid-teardown: count what we can
            pass
    return total


def drop_executables(note: str = "") -> int:
    """Clear every jitted executable cache (the tests/bench memory
    valve: accumulated lane/prefill executables deterministically crash
    XLA's CPU JIT late in a long run).  Returns the tracked
    live-executable count that was dropped; prints ``note`` when given
    so bench logs show part boundaries."""
    import jax
    n = live_executable_count()
    jax.clear_caches()
    if note:
        print(f"[runtime] {note} (dropped {n} tracked executables)",
              flush=True)
    return n


class CompileTracker:
    """Process-wide retrace/compile accounting.

    ``wrap(fn, name=..., lane=...)`` returns a function whose body runs
    only when JAX traces it — wrap BEFORE ``jax.jit``.  Each execution
    increments the per-name and per-lane trace counters exactly once
    per (re)trace.  A ``jax.monitoring`` listener adds compile
    wall-time totals.

    ``event_counts["backend_compile"]`` counts backend compile
    REQUESTS: JAX reports one whether the persistent compilation cache
    serves the program or XLA compiles it.  ``event_counts
    ["cache_hits"]`` counts the ones the cache served, and the
    snapshot's ``compiles`` the difference — the programs XLA compiled.
    """

    # monitoring event -> short key in the seconds table
    _EVENTS = {
        "/jax/core/compile/backend_compile_duration": "backend_compile",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowering",
        "/jax/core/compile/jaxpr_trace_duration": "tracing",
    }
    # monitoring count event -> key in event_counts
    _COUNTS = {"/jax/compilation_cache/cache_hits": "cache_hits"}

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.traces: Dict[str, int] = {}        # fn name -> trace count
        self.lane_traces: Dict[str, int] = {}   # lane signature -> count
        self.event_counts: Dict[str, int] = {}
        self.event_seconds: Dict[str, float] = {}
        self._listener_installed = False

    # ---- trace counting ----------------------------------------------

    def wrap(self, fn: Callable, *, name: str,
             lane: str = "") -> Callable:
        """Count (re)traces of ``fn``.  The wrapper body only runs at
        trace time, never per step, and passes arguments through
        untouched — traced values are unaffected."""
        @functools.wraps(fn)
        def counted(*args, **kwargs):
            with self._lock:
                self.traces[name] = self.traces.get(name, 0) + 1
                if lane:
                    self.lane_traces[lane] = \
                        self.lane_traces.get(lane, 0) + 1
            return fn(*args, **kwargs)
        return counted

    def trace_count(self, name: Optional[str] = None) -> int:
        with self._lock:
            if name is not None:
                return self.traces.get(name, 0)
            return sum(self.traces.values())

    def top_retraced(self, k: int = 3) -> List[Tuple[str, int]]:
        """Lane signatures by descending trace count (serve.py
        ``--profile`` summary)."""
        with self._lock:
            items = sorted(self.lane_traces.items(),
                           key=lambda kv: (-kv[1], kv[0]))
        return items[:k]

    # ---- jax.monitoring compile durations ----------------------------

    def install_monitoring(self) -> None:
        """Attach the compile-duration listener once; safe to call
        repeatedly."""
        from jax import monitoring
        with self._lock:
            if self._listener_installed:
                return
            self._listener_installed = True
        monitoring.register_event_duration_secs_listener(self._on_event)
        monitoring.register_event_listener(self._on_count)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        key = self._EVENTS.get(event)
        if key is None:
            return
        with self._lock:
            self.event_counts[key] = self.event_counts.get(key, 0) + 1
            self.event_seconds[key] = \
                self.event_seconds.get(key, 0.0) + float(duration)

    def _on_count(self, event: str, **kw) -> None:
        key = self._COUNTS.get(event)
        if key is None:
            return
        with self._lock:
            self.event_counts[key] = self.event_counts.get(key, 0) + 1

    # ---- exposition --------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """JSON-safe dump (bench metrics artifact embeds this)."""
        with self._lock:
            hits = self.event_counts.get("cache_hits", 0)
            compiles = self.event_counts.get("backend_compile", 0) - hits
            return {
                "traces": dict(self.traces),
                "lane_traces": dict(self.lane_traces),
                "event_counts": dict(self.event_counts),
                "event_seconds": {k: round(v, 6) for k, v in
                                  self.event_seconds.items()},
                "compiles": compiles,
                "live_executables": live_executable_count(),
            }

    def export_metrics(self, registry) -> None:
        """Mirror the counters into a §11 registry (engine collector):
        ``spa_runtime_*`` series on /metrics."""
        with self._lock:
            traces = dict(self.traces)
            events = dict(self.event_counts)
            seconds = dict(self.event_seconds)
        for name, n in sorted(traces.items()):
            registry.counter(
                "spa_runtime_trace_total",
                "function (re)traces by jitted entry point",
                labels={"fn": name}).set(n)
        hits = events.pop("cache_hits", 0)
        for key, n in sorted(events.items()):
            registry.counter(
                "spa_runtime_compile_events_total",
                "jax.monitoring compile events by stage",
                labels={"stage": key}).set(n)
        registry.counter(
            "spa_runtime_compile_cache_hits_total",
            "compile requests the persistent compilation cache served",
        ).set(hits)
        for key, s in sorted(seconds.items()):
            registry.counter(
                "spa_runtime_compile_seconds_total",
                "compile wall time by stage",
                labels={"stage": key}).set(s)
        registry.gauge(
            "spa_runtime_live_executables",
            "compiled executables across tracked jitted functions",
        ).set(live_executable_count())

    def reset(self) -> None:
        """Zero all counters (bench part boundaries, tests)."""
        with self._lock:
            self.traces.clear()
            self.lane_traces.clear()
            self.event_counts.clear()
            self.event_seconds.clear()


_TRACKER = CompileTracker()


def compile_tracker() -> CompileTracker:
    """The process-wide tracker (monitoring listener attached lazily)."""
    _TRACKER.install_monitoring()
    return _TRACKER
