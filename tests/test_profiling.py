"""Compute-path profiling (DESIGN.md §12).

The contracts the profiling PR makes:

* zero interference — decode with a StepProfiler attached is
  byte-identical to its profiler-off twin, per strategy × run mode ×
  kernel backend (everything is host-side, fenced BETWEEN jitted
  calls);
* exact tiling — the fenced host-loop segments (refresh / dispatch /
  device_wait) share their perf_counter boundaries, so per step they
  sum to the independently recorded total;
* off means off — a run without a profiler adds zero ``spa_profile_*``
  series to the registry;
* retrace accounting — the trace-count wrapper counts (re)traces
  exactly and the ``spa_runtime_*`` / ``spa_pool_*`` series land in a
  valid Prometheus render;
* ``/debug/pool`` — valid JSON mid-churn (preemption + demotion
  traffic live);
* ProfileStore — round-trips through JSON and short-circuits the
  hillclimb re-search on a warm-start hit.
"""
import asyncio
import json
import re

import jax
import numpy as np
import pytest

from repro.core import runtime
from repro.core.strategy import NoCache, SPACache, ValueProxyCache
from repro.dlm.session import DecodeSession
from repro.kernels.backend import PallasBackend
from repro.serving.engine import ServingEngine
from repro.serving.profiling import (ProfileStore, StepProfiler,
                                     time_compile_steady)
from repro.serving.telemetry import Telemetry

PAGE, CANVAS = 4, 16
PALLAS = PallasBackend(interpret=True)

STRATEGIES = {
    "spa": SPACache(rank=16, schedule="uniform", rho_peak=0.3),
    "value": ValueProxyCache(rho=0.3),
    "none": NoCache(),
}


@pytest.fixture(scope="module")
def small():
    from repro.configs import get_arch, reduced
    from repro.models import transformer
    cfg = reduced(get_arch("internlm2-1.8b"))
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0,
                                cfg.vocab_size - 1)
    return cfg, params, prompt


def _decode(cfg, params, prompt, strategy, backend, mode, profiler):
    sess = DecodeSession(params, cfg, strategy=strategy, backend=backend,
                         profiler=profiler, label="test-lane")
    sess.prefill(prompt, gen_len=6)
    toks, info = getattr(sess, mode)()
    return np.asarray(toks), info["steps"]


# ---------------------------------------------------------------------------
# Zero interference: profiling on == profiling off, byte for byte
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["run", "run_compiled"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("name", sorted(STRATEGIES))
def test_profiling_on_is_byte_identical(small, name, backend, mode):
    cfg, params, prompt = small
    strat = STRATEGIES[name]
    bk = None if backend == "xla" else PALLAS
    prof = StepProfiler(Telemetry.enabled(dynamics_every=0))
    t_off, s_off = _decode(cfg, params, prompt, strat, bk, mode, None)
    t_on, s_on = _decode(cfg, params, prompt, strat, bk, mode, prof)
    np.testing.assert_array_equal(t_off, t_on)
    assert s_off == s_on
    # and the profiler actually saw the run
    if mode == "run":
        assert prof.steps_observed == s_on
    else:
        assert prof.loops_observed == 1


# ---------------------------------------------------------------------------
# Segment tiling: per-step segments sum to the recorded total
# ---------------------------------------------------------------------------

def test_step_segments_tile_total(small):
    cfg, params, prompt = small
    prof = StepProfiler(Telemetry.enabled(dynamics_every=0))
    _decode(cfg, params, prompt, STRATEGIES["spa"], None, "run", prof)
    assert prof.steps_observed > 0
    snap = prof.registry.snapshot()
    seg_sum = sum(
        snap[f'spa_profile_step_seconds{{segment="{seg}"}}']["sum"]
        for seg in StepProfiler.SEGMENTS)
    total = snap['spa_profile_step_seconds{segment="total"}']["sum"]
    # boundaries are SHARED perf_counter reads, so the telescoping sum
    # is exact up to float summation noise (+ snapshot rounding)
    assert seg_sum == pytest.approx(total, rel=1e-6, abs=1e-7)
    bd = prof.step_breakdown()
    assert set(StepProfiler.SEGMENTS) <= set(bd)
    assert sum(bd[s]["share"] for s in StepProfiler.SEGMENTS) \
        == pytest.approx(1.0, abs=1e-6)
    assert "step-time decomposition" in prof.format_summary()


def test_compiled_loop_records_loop_level_only(small):
    cfg, params, prompt = small
    prof = StepProfiler(Telemetry.enabled(dynamics_every=0))
    _decode(cfg, params, prompt, STRATEGIES["spa"], None, "run_compiled",
            prof)
    snap = prof.registry.snapshot()
    assert snap["spa_profile_loop_seconds"]["count"] == 1
    assert snap["spa_profile_loop_steps_total"] > 0
    # phases are not attributable inside the while_loop: no fenced
    # step segments may appear
    assert not any(k.startswith("spa_profile_step_seconds")
                   for k in snap)


def test_sample_every_skips_steps(small):
    cfg, params, prompt = small
    prof = StepProfiler(Telemetry.enabled(dynamics_every=0),
                        sample_every=2)
    _, steps = _decode(cfg, params, prompt, STRATEGIES["spa"], None,
                       "run", prof)
    assert 0 < prof.steps_observed < steps


def test_profiler_summary_safe_when_empty():
    prof = StepProfiler()
    assert "no profiled steps" in prof.format_summary()
    assert prof.step_breakdown() == {}


# ---------------------------------------------------------------------------
# Off means off: no spa_profile_* series without a profiler
# ---------------------------------------------------------------------------

def test_disabled_profiling_adds_no_registry_entries(small):
    cfg, params, prompt = small
    tel = Telemetry.enabled(dynamics_every=1)
    eng = ServingEngine(cfg, params, max_batch=2, canvas_len=CANVAS,
                        strategy=STRATEGIES["spa"], pool_pages=9,
                        page_size=PAGE, telemetry=tel)
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(0, cfg.vocab_size - 1, 8).astype(np.int32),
               gen_len=8)
    eng.run()
    assert not any(k.startswith("spa_profile_")
                   for k in tel.registry.snapshot())


# ---------------------------------------------------------------------------
# Retrace accounting + Prometheus exposition
# ---------------------------------------------------------------------------

def test_compile_tracker_counts_traces_exactly():
    tracker = runtime.CompileTracker()

    def f(x):
        return x * 2

    jf = jax.jit(tracker.wrap(f, name="f", lane="laneA"))
    jf(np.ones((2,), np.float32))
    jf(np.ones((2,), np.float32))          # cache hit: no retrace
    jf(np.ones((3,), np.float32))          # new shape: one retrace
    assert tracker.trace_count("f") == 2
    assert tracker.top_retraced(1) == [("laneA", 2)]
    snap = tracker.snapshot()
    assert snap["traces"] == {"f": 2}


def test_compile_tracker_tells_cache_hits_from_compiles(tmp_path):
    """A program the persistent compilation cache serves is a compile
    request but no compile: ``backend_compile`` counts both, the new
    ``cache_hits`` key the second, ``compiles`` the difference."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from repro.serving.telemetry import MetricsRegistry
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    tracker = runtime.compile_tracker()

    def fresh():                     # same program, new jit cache entry
        def cache_probe(x):
            return jax.numpy.sin(x) * 3.0 + 1.0
        return jax.jit(cache_probe)

    x = np.ones((7,), np.float32)
    jax.config.update(names[0], str(tmp_path))
    jax.config.update(names[1], 0)
    jax.config.update(names[2], 0)
    cc.reset_cache()
    try:
        s0 = tracker.snapshot()
        fresh()(x).block_until_ready()       # compiled, then cached
        s1 = tracker.snapshot()
        fresh()(x).block_until_ready()       # served by the cache
        s2 = tracker.snapshot()
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        cc.reset_cache()

    def delta(a, b, key):
        return b["event_counts"].get(key, 0) - a["event_counts"].get(key, 0)

    assert delta(s0, s1, "backend_compile") == 1
    assert delta(s0, s1, "cache_hits") == 0
    assert s1["compiles"] - s0["compiles"] == 1
    assert delta(s1, s2, "backend_compile") == 1
    assert delta(s1, s2, "cache_hits") == 1
    assert s2["compiles"] == s1["compiles"]
    reg = MetricsRegistry()
    tracker.export_metrics(reg)
    snap = reg.snapshot()
    assert snap["spa_runtime_compile_cache_hits_total"] \
        == tracker.snapshot()["event_counts"]["cache_hits"]
    assert 'spa_runtime_compile_events_total{stage="cache_hits"}' \
        not in snap


def test_session_trace_counts_are_shape_stable(small):
    """A second identically shaped decode through the SAME session adds
    zero retraces; the bench_serving Part 6 budget gate relies on this
    invariant."""
    cfg, params, prompt = small
    tracker = runtime.compile_tracker()
    sess = DecodeSession(params, cfg, strategy=STRATEGIES["spa"])
    sess.prefill(prompt, gen_len=6)
    sess.run()
    before = tracker.trace_count("serve_step")
    assert before > 0
    sess.prefill(prompt, gen_len=6)
    sess.run()
    assert tracker.trace_count("serve_step") == before


def test_metrics_render_includes_runtime_and_pool_series(small):
    from test_telemetry import _assert_prometheus_text
    cfg, params, prompt = small
    tel = Telemetry.enabled(dynamics_every=0)
    eng = ServingEngine(cfg, params, max_batch=2, canvas_len=CANVAS,
                        strategy=STRATEGIES["spa"], pool_pages=9,
                        page_size=PAGE, telemetry=tel,
                        profiler=StepProfiler(tel))
    rng = np.random.default_rng(1)
    eng.submit(rng.integers(0, cfg.vocab_size - 1, 8).astype(np.int32),
               gen_len=8)
    eng.run()
    text = tel.registry.render()
    _assert_prometheus_text(text)
    for series in ("spa_runtime_trace_total",
                   "spa_runtime_live_executables",
                   "spa_pool_peak_pages_used",
                   "spa_pool_max_contiguous_free_run",
                   "spa_pool_arena_bytes_total",
                   "spa_profile_step_seconds"):
        assert series in text, f"missing {series} in /metrics render"


def test_retrace_budget_file_parses():
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "retrace_budget.json")
    with open(path) as f:
        budgets = json.load(f)
    for key in ("quick", "full"):
        assert {"serve_step", "prefill_partial", "decode_loop"} \
            <= set(budgets[key])
        assert all(v > 0 for v in budgets[key].values())


# ---------------------------------------------------------------------------
# /debug/pool: valid JSON mid-churn
# ---------------------------------------------------------------------------

def test_debug_pool_json_mid_churn(small):
    """pool_debug_state() stays JSON-serializable at EVERY step of a
    preempting + demoting workload, and the live /debug/pool endpoint
    serves it mid-stream."""
    from repro.serving.frontend import AsyncFrontend, fetch_debug_pool
    cfg, params, prompt = small
    eng = ServingEngine(cfg, params, max_batch=2, canvas_len=CANVAS,
                        strategy=SPACache(rank=16, schedule="uniform",
                                          rho_peak=0.3,
                                          refresh_interval=1),
                        pool_pages=9, page_size=PAGE, prefix_cache=True,
                        host_pages=16, host_dtype="f32",
                        telemetry=Telemetry.enabled(dynamics_every=0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size - 1, 8).astype(np.int32)
               for _ in range(4)]
    eng.submit(prompts[0], gen_len=8)
    eng.run()
    for p in prompts[1:3]:
        eng.submit(p, gen_len=8)
    s0 = eng.stats.steps
    states = []

    def on_step(e):
        if e.stats.steps == s0 + 2:
            e.submit(prompts[3], gen_len=8, priority=5)
        states.append(json.loads(json.dumps(e.pool_debug_state())))

    eng.run(on_step=on_step)
    assert eng.stats.preemptions > 0, "churn never preempted"
    assert states
    for st in states:
        assert st["paged"] is True
        assert st["pool"]["used"] <= st["pool"]["capacity"]
        frag = st["pool"]["fragmentation"]
        assert frag["max_contiguous_run"] <= frag["free_pages"]
        assert st["live_executables"] >= 0
    assert any(st["tier"]["demoted_pages"] > 0 for st in states), \
        "churn never demoted"

    # live endpoint, scraped while a request streams
    async def main():
        from repro.serving.frontend import stream_request
        front = AsyncFrontend(eng, max_steps=2048)
        await front.start(serve_http=True)
        try:
            mid = None
            async for ev in stream_request(front.host, front.port,
                                           prompts[0], 6):
                if ev["kind"] == "token" and mid is None:
                    mid = await fetch_debug_pool(front.host, front.port)
        finally:
            await front.stop()
        return mid

    mid = asyncio.run(main())
    assert mid is not None and mid["paged"] is True
    assert set(mid["pool"]) >= {"capacity", "used", "fragmentation",
                                "arena_bytes"}
    assert mid["host_pool"]["unit_budget"] > 0


def test_time_compile_steady_orders():
    f = jax.jit(lambda x: x * x + 1.0)
    compile_s, steady_s = time_compile_steady(
        f, np.ones((64,), np.float32), reps=3)
    assert compile_s > 0 and steady_s > 0
    assert compile_s > steady_s            # first call paid the compile


# ---------------------------------------------------------------------------
# ProfileStore + hillclimb warm start
# ---------------------------------------------------------------------------

def test_profile_store_round_trip(tmp_path):
    path = tmp_path / "profiles.json"
    store = ProfileStore(str(path))
    assert len(store) == 0
    store.put({"steady_us": 12.5}, kind="kernel", kernel="gather_norm",
              shape="b2n256", backend="xla", block="bq512")
    store.save()
    again = ProfileStore(str(path))
    rec = again.get(kernel="gather_norm", shape="b2n256", backend="xla",
                    block="bq512", kind="kernel")   # key order-free
    assert rec is not None and rec["steady_us"] == 12.5
    assert rec["key"]["kernel"] == "gather_norm"
    # corrupt stores load as empty, never raise
    path.write_text("{not json")
    assert len(ProfileStore(str(path))) == 0


def test_hillclimb_warm_start_short_circuits(tmp_path, monkeypatch):
    import os
    flags = os.environ.get("XLA_FLAGS")
    from repro.launch import hillclimb
    if flags is None:
        monkeypatch.delenv("XLA_FLAGS", raising=False)
    else:
        monkeypatch.setenv("XLA_FLAGS", flags)
    calls = []

    def fake_run_one(arch, shape, mesh, cfg_override=None, tag=""):
        calls.append(tag)
        return {"arch": arch, "shape": shape, "mesh": mesh, "tag": tag,
                "status": "ok", "step_ms": 1.25}

    monkeypatch.setattr(hillclimb, "run_one", fake_run_one)
    store = tmp_path / "profiles.json"
    out = tmp_path / "hillclimb.jsonl"
    argv = ["--arch", "internlm2-1.8b", "--shape", "decode_32k",
            "--variant", "baseline", "--out", str(out),
            "--profile-store", str(store)]
    assert hillclimb.main(argv) == 0
    assert calls == ["baseline"]           # cold: searched + persisted
    assert hillclimb.main(argv) == 0
    assert calls == ["baseline"], "warm start must skip the re-search"
    recs = [json.loads(ln) for ln in
            out.read_text().strip().split("\n")]
    assert len(recs) == 2
    assert "warm_start" not in recs[0]
    assert recs[1]["warm_start"] is True
    assert recs[1]["step_ms"] == recs[0]["step_ms"]
    # a different variant misses the cache and searches again
    argv2 = argv[:5] + ["rank_64"] + argv[6:]
    assert hillclimb.main(argv2) == 0
    assert calls == ["baseline", "rank_64"]
