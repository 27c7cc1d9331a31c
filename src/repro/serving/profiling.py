"""Compute-path profiling beneath the §11 telemetry facade (DESIGN.md §12).

Two cooperating pieces, all OFF by default (construct nothing and the
decode path is untouched):

  * :class:`StepProfiler` — device-time decomposition of the decode
    step.  In host-loop mode (``DecodeSession.run``/``step``) the
    session fences consecutive segments — ``refresh`` (cache rebuild +
    its sync), ``dispatch`` (Python → jitted-step call returning
    futures) and ``device_wait`` (``block_until_ready`` on the step
    result) — with ``time.perf_counter`` at each boundary, so the
    segments TILE the step: their sum equals the independently measured
    total up to clock granularity (tests assert this).  In
    ``run_compiled`` mode the whole ``lax.while_loop`` is one dispatch,
    so only loop-level timing is attributable (per-step averages are
    derived).  Observations land in the §11 registry
    (``spa_profile_*``) and, when a tracer is live, as slices on a
    dedicated device track in the Perfetto export.
  * :class:`ProfileStore` — persisted per-(kernel, shape, backend,
    block-config) timing records (``BENCH_artifacts/
    kernel_profiles.json``), written by ``benchmarks/bench_kernels.py``
    and read by ``launch/hillclimb.py`` as its warm-start cache.

Everything here is host-side: observations happen between jitted calls,
never inside them, so decode outputs are byte-identical with profiling
on (tests/test_profiling.py asserts it per strategy × run mode ×
backend).
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.serving.telemetry import (PID_DEVICE, Telemetry, TraceEvent)

__all__ = [
    "time_compile_steady", "StepProfiler", "ProfileStore",
    "default_profile_path",
]


def time_compile_steady(fn: Callable, *args,
                        reps: int = 5) -> Tuple[float, float]:
    """(first-call seconds, best-of-reps steady seconds) for a jitted
    callable.  The first call pays trace + lowering + backend compile;
    hiding it behind an untimed warmup (what the kernel bench used to
    do) makes amortization claims dishonest — ProfileStore records keep
    both numbers."""
    import jax
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    compile_s = time.perf_counter() - t0
    best = float("inf")
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return compile_s, best


class StepProfiler:
    """Fenced step-segment observation into registry + device track.

    ``sample_every=N`` fences every Nth step (1 = all); unsampled steps
    run the exact unprofiled path.  The profiler is handed to
    ``DecodeSession(profiler=...)`` / ``ServingEngine(profiler=...)``;
    sessions call :meth:`observe_step` / :meth:`observe_loop` with
    durations they measured around their own jitted calls.
    """

    SEGMENTS = ("refresh", "dispatch", "device_wait")

    def __init__(self, telemetry: Optional[Telemetry] = None, *,
                 sample_every: int = 1,
                 jax_trace_dir: Optional[str] = None):
        self.telemetry = telemetry or Telemetry.disabled()
        self.registry = self.telemetry.registry
        self.tracer = self.telemetry.tracer
        self.sample_every = max(int(sample_every), 1)
        self.jax_trace_dir = jax_trace_dir
        self.steps_observed = 0
        self.loops_observed = 0
        self._lane_tids: Dict[str, int] = {}

    # ---- sampling ----------------------------------------------------

    def should_sample(self, step_idx: int) -> bool:
        return step_idx % self.sample_every == 0

    # ---- observation (called by DecodeSession) -----------------------

    def _tid(self, lane: str) -> int:
        tid = self._lane_tids.get(lane)
        if tid is None:
            tid = len(self._lane_tids) + 1
            self._lane_tids[lane] = tid
            self.tracer.name_track(PID_DEVICE, tid, f"device:{lane}")
        return tid

    def _hist(self, segment: str):
        return self.registry.histogram(
            "spa_profile_step_seconds",
            "fenced decode-step segment durations (host-loop mode)",
            labels={"segment": segment})

    def observe_step(self, lane: str, segments: Dict[str, float],
                     total_s: float) -> None:
        """One fenced host-loop step: ``segments`` tile ``total_s``."""
        self.steps_observed += 1
        for seg, dt in segments.items():
            self._hist(seg).observe(dt)
        self._hist("total").observe(total_s)
        if self.tracer.enabled:
            tid = self._tid(lane)
            end = float(self.tracer.clock())
            t = end - total_s
            for seg, dt in segments.items():
                self.tracer.events.append(TraceEvent(
                    name=f"step:{seg}", ph="X", ts=t, dur=dt,
                    pid=PID_DEVICE, tid=tid, cat="device"))
                t += dt

    def observe_loop(self, lane: str, steps: int,
                     total_s: float) -> None:
        """One ``run_compiled`` while_loop: loop-level only (per-step
        averages derived; phases are not attributable — DESIGN.md §12)."""
        self.loops_observed += 1
        self.registry.histogram(
            "spa_profile_loop_seconds",
            "whole compiled-loop durations (run_compiled mode)",
        ).observe(total_s)
        self.registry.counter(
            "spa_profile_loop_steps_total",
            "decode steps executed inside compiled loops").inc(steps)
        if steps > 0:
            self.registry.histogram(
                "spa_profile_loop_step_seconds",
                "derived per-step average inside compiled loops",
            ).observe(total_s / steps)
        if self.tracer.enabled:
            tid = self._tid(lane)
            end = float(self.tracer.clock())
            self.tracer.events.append(TraceEvent(
                name=f"loop[{steps} steps]", ph="X", ts=end - total_s,
                dur=total_s, pid=PID_DEVICE, tid=tid, cat="device"))

    # ---- optional jax.profiler wrap ----------------------------------

    @contextlib.contextmanager
    def jax_trace(self):
        """Wrap a run in ``jax.profiler.trace`` when a trace dir was
        requested; no-op otherwise.  A trace that fails to start or stop
        raises: a run asked to be traced does not pass silently
        untraced."""
        if not self.jax_trace_dir:
            yield
            return
        import jax.profiler
        with jax.profiler.trace(self.jax_trace_dir):
            yield

    # ---- summaries ---------------------------------------------------

    def step_breakdown(self) -> Dict[str, Dict[str, float]]:
        """{segment: {count, mean_s, p50_s, p95_s, share}} from the
        recorded histograms (share = segment sum / total-segment sum).
        Empty when nothing was observed — zero-request safe."""
        out: Dict[str, Dict[str, float]] = {}
        total_sum = 0.0
        hists = {}
        for seg in self.SEGMENTS + ("total",):
            h = self._hist(seg)
            if h.count:
                hists[seg] = h
                if seg == "total":
                    total_sum = h.sum
        for seg, h in hists.items():
            out[seg] = {
                "count": h.count, "mean_s": h.mean,
                "p50_s": h.percentile(50), "p95_s": h.percentile(95),
                "share": (h.sum / total_sum) if total_sum else 0.0,
            }
        return out

    def format_summary(self) -> str:
        """Human-oriented decomposition for serve.py ``--profile``."""
        lines: List[str] = []
        bd = self.step_breakdown()
        if bd:
            lines.append("step-time decomposition (host-loop, fenced):")
            for seg in self.SEGMENTS + ("total",):
                row = bd.get(seg)
                if row is None:
                    continue
                lines.append(
                    f"  {seg:<12s} n={row['count']:<6d}"
                    f" mean={row['mean_s'] * 1e3:8.3f}ms"
                    f" p95={row['p95_s'] * 1e3:8.3f}ms"
                    f" share={row['share']:6.1%}")
        loop_h = self.registry.histogram(
            "spa_profile_loop_seconds",
            "whole compiled-loop durations (run_compiled mode)")
        if loop_h.count:
            step_h = self.registry.histogram(
                "spa_profile_loop_step_seconds",
                "derived per-step average inside compiled loops")
            lines.append(
                f"compiled loops: n={loop_h.count}"
                f" mean={loop_h.mean * 1e3:.3f}ms"
                f" per-step={step_h.mean * 1e3:.3f}ms (derived)")
        if not lines:
            return "  (no profiled steps recorded)"
        return "\n".join("  " + ln for ln in lines)


def default_profile_path() -> str:
    """``BENCH_artifacts/kernel_profiles.json`` at the repo root (next
    to the other bench artifacts), wherever the caller runs from."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    return os.path.join(root, "BENCH_artifacts", "kernel_profiles.json")


class ProfileStore:
    """JSON-persisted timing records keyed on canonical key strings.

    Records are arbitrary JSON dicts keyed by sorted ``k=v`` pairs
    (``backend=xla|kernel=sparse_attention|shape=b2n256...``) — the
    kernel bench writes per-(kernel, shape, backend, block-config)
    entries and ``launch/hillclimb.py`` reads/writes per-(arch, shape,
    mesh, variant) entries into the same file, which is what makes the
    store the autotuner's warm-start cache.
    """

    VERSION = 1

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_profile_path()
        self._records: Dict[str, Dict[str, Any]] = {}
        self.load()

    @staticmethod
    def key_of(**key: Any) -> str:
        return "|".join(f"{k}={key[k]}" for k in sorted(key))

    def load(self) -> None:
        if not os.path.exists(self.path):
            return
        try:
            with open(self.path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError):
            return
        if isinstance(data, dict):
            recs = data.get("records")
            if isinstance(recs, dict):
                self._records = recs

    def save(self) -> None:
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        with open(self.path, "w") as f:
            json.dump({"version": self.VERSION,
                       "records": self._records}, f, indent=1,
                      sort_keys=True)

    def get(self, **key: Any) -> Optional[Dict[str, Any]]:
        return self._records.get(self.key_of(**key))

    def put(self, record: Dict[str, Any], **key: Any) -> None:
        self._records[self.key_of(**key)] = {
            "key": {k: key[k] for k in sorted(key)}, **record}

    def __len__(self) -> int:
        return len(self._records)

    @property
    def records(self) -> Dict[str, Dict[str, Any]]:
        return dict(self._records)
