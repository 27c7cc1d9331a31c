"""Names inside the program (DESIGN.md §12).

* the serve step's phases carry ``jax.named_scope``s — metadata only,
  so they reach a device trace's op paths without changing a token;
* ``Tracer.span`` names the engine's host work on the profiler's clock
  (``engine/<phase>`` in a ``jax.profiler`` trace) whether or not the
  Chrome tracer is on, and records the Chrome spans and the phase
  histogram exactly as before when it is.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.strategy import AttnOutCache, SPACache
from repro.dlm.session import DecodeSession
from repro.serving.engine import ServingEngine
from repro.serving.pool import PagePool
from repro.serving.telemetry import PID_ENGINE, Telemetry, Tracer

PAGE, CANVAS = 4, 16
N_LOG = CANVAS // PAGE
SCOPES = ("cache_view", "spa_identify", "spa_attend", "spa_ffn",
          "cache_commit", "logits", "unmask")
ENGINE_PHASES = {"dispatch", "host_overlap", "host_sync", "stream",
                 "release", "admit"}


def _paged_session(cfg, params, strategy, backend):
    """A two-row session on a paged cache, attached and ready to step."""
    b = 2
    tokens = np.full((b, CANVAS), cfg.mask_id, np.int32)
    tokens[:, :8] = np.arange(1, 9)
    active = np.zeros((b, CANVAS), bool)
    active[:, 8:] = True
    pool = PagePool(cfg, n_pages=1 + b * N_LOG, page_size=PAGE,
                    strategy=strategy)
    pt = np.stack([pool.page_table_row(pool.alloc(N_LOG), CANVAS)
                   for _ in range(b)]).astype(np.int32)
    sess = DecodeSession(params, cfg, strategy=strategy, backend=backend)
    sess.attach(tokens, active=jnp.asarray(active),
                kv_len=np.full((b,), CANVAS, np.int32),
                arenas=pool.arenas_for(strategy), page_table=pt)
    return sess


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("strategy", [
    SPACache(rank=16, schedule="uniform", rho_peak=0.5),
    AttnOutCache(rho=0.5)], ids=["singular", "attn_out"])
def test_serve_step_carries_phase_scopes(tiny_cfg, tiny_params, strategy,
                                         backend):
    """Both identifier paths: every phase scope is on the lowered
    step's op locations."""
    sess = _paged_session(tiny_cfg, tiny_params, strategy, backend)
    text = sess._step_fn.lower(sess.params, sess.spa_proxies,
                               sess.state).as_text(debug_info=True)
    for scope in SCOPES:
        assert f"/{scope}/" in text, scope


def test_span_records_like_begin_end():
    clock = iter(range(100)).__next__
    seen = []
    tr = Tracer(clock=clock)
    with tr.span(PID_ENGINE, 3, "host_sync", cat="phase",
                 on_close=seen.append):
        pass
    tr.begin(PID_ENGINE, 3, "host_sync", cat="phase")
    tr.end(PID_ENGINE, 3, "host_sync")
    a, b = tr.events
    assert (a.name, a.ph, a.pid, a.tid, a.cat, a.dur) \
        == (b.name, b.ph, b.pid, b.tid, b.cat, b.dur) \
        == ("host_sync", "X", PID_ENGINE, 3, "phase", 1)
    assert seen == [a] and tr.open_spans() == []

    off = Tracer(enabled=False)
    with off.span(PID_ENGINE, 0, "dispatch", on_close=seen.append):
        pass
    assert off.events == [] and seen == [a]


def _host_event_names(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    return [ev.name for plane in pd.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


@pytest.mark.parametrize("tracing", [True, False], ids=["chrome", "off"])
def test_engine_spans_reach_the_profiler_trace(tiny_cfg, tiny_params,
                                               tmp_path, tracing):
    """Three requests through two rows: a release and an admission in
    the traced run.  The host plane holds ``engine/<phase>`` for every
    phase with the Chrome tracer on or off; with it on, the Chrome
    spans keep their plain names, one per annotation, and feed the
    phase histogram."""
    tel = Telemetry.enabled() if tracing else Telemetry.disabled()
    eng = ServingEngine(tiny_cfg, tiny_params, max_batch=2,
                        canvas_len=CANVAS,
                        strategy=SPACache(rank=16, schedule="uniform",
                                          rho_peak=0.5),
                        pool_pages=9, page_size=PAGE, telemetry=tel)
    rng = np.random.default_rng(0)
    for gen in (4, 8, 4):
        eng.submit(rng.integers(0, tiny_cfg.vocab_size - 1, 8)
                   .astype(np.int32), gen_len=gen)
    with jax.profiler.trace(str(tmp_path)):
        eng.run()
    assert len(eng.done) == 3
    host = _host_event_names(str(tmp_path))
    assert {f"engine/{p}" for p in ENGINE_PHASES} <= set(host)
    tr = tel.tracer
    if not tracing:
        assert tr.events == []
        return
    phases = [e.name for e in tr.span_events(PID_ENGINE)]
    assert set(phases) == ENGINE_PHASES and tr.open_spans() == []
    snap = tel.registry.snapshot()
    for p in ENGINE_PHASES:
        assert host.count(f"engine/{p}") == phases.count(p)
        key = f'spa_engine_phase_seconds{{phase="{p}"}}'
        assert snap[key]["count"] == phases.count(p)
