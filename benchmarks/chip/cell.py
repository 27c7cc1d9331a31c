"""One cell: a configuration served under one traffic mix.

Builds the serving engine the way the cell states (paged pool of
16-row pages, prefix cache on, the configuration's SPA strategy on the
Pallas backend, supervisor and host tier off), warms up every shape the
mix can produce, and drives the measured window:

* closed loop — the queue is topped up from ``on_step`` so no row ever
  waits for work; the window closes at the first step boundary past
  ``seconds``;
* open loop — a generator thread feeds ``submit_threadsafe`` on the
  mix's arrival schedule while the engine runs ``run_online``; the
  window closes ``seconds`` after it opened.

Every request streams to a sink of its own, which records each step's
commits (the canvas history the correctness check replays) and the
harvest.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import traffic
from traffic import Request

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

WARM_GEN = 8           # generation length of the warm-up requests


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench: Dict[str, Any], workload: str
              ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, entry


def mask_id(cfg: Dict[str, Any]) -> int:
    """The [MASK] token's id: the file's ``mask_token_id``, or the last
    vocabulary id where it has none (as the program places it)."""
    return cfg.get("mask_token_id") or cfg["vocab_size"] - 1


def load_module(path: str, name: str):
    """The Python file at ``path``, loaded as a module of its own."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(cfg: Dict[str, Any], bench_dir: str = HERE):
    """The model family a configuration file names: the module
    ``families/<family>.py`` under ``bench_dir``, which holds all the
    harness knows of the architecture (``families/dense.py`` says
    what).  A file that names none, or one not there, is an error."""
    fdir = os.path.join(bench_dir, "families")
    known = sorted(f[:-3] for f in os.listdir(fdir) if f.endswith(".py"))
    name = cfg.get("family")
    if name not in known:
        raise SystemExit(f"configuration {cfg.get('name')!r} names model "
                         f"family {name!r}; known families: {known}")
    return load_module(os.path.join(fdir, f"{name}.py"), f"family_{name}")


def build_engine(mcfg, params, mix: Dict[str, Any]):
    from repro.core.strategy import strategy_from_spec
    from repro.dlm.scheduler import scheduler_from_name
    from repro.serving.engine import ServingEngine
    sched = mix.get("scheduler")
    scheduler = (scheduler_from_name(
        sched["name"], **{k: v for k, v in sched.items() if k != "name"})
        if sched else None)
    return ServingEngine(
        mcfg, params, max_batch=mix["max_batch"],
        canvas_len=mix["canvas"],
        strategy=strategy_from_spec(mcfg.spa).with_backend("pallas"),
        scheduler=scheduler, pool_pages=mix["pool_pages"],
        page_size=mix["page_size"], prefix_cache=True, host_pages=0,
        supervise=False)


def row_len(mix: Dict[str, Any], prompt_len: int, gen_len: int) -> int:
    page = mix["page_size"]
    return min(-(-(prompt_len + gen_len) // page) * page, mix["canvas"])


def _bucket(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def warm_plan(mix: Dict[str, Any]) -> Tuple[List[int], List[Tuple[int, int]]]:
    """(row counts, [(prompt_len, row_len)]) the warm-up must cover.

    Row counts: how many rows one admission or harvest can swap — only
    ``max_batch`` when every request has the same sizes and the queue
    is always full, else every count up to it.  Row spans: one per
    power-of-two page bucket that a prefix publication can copy."""
    b = mix["max_batch"]
    fixed = all(mix[k]["dist"] == "fixed" for k in ("prompt_len", "gen_len"))
    counts = [b] if fixed and mix["loop"] == "closed" else list(range(1, b + 1))
    p, g = mix["prompt_len"], mix["gen_len"]
    lo = (p["value"], g["value"]) if fixed else (p["min"], g["min"])
    hi = (p["value"], g["value"]) if fixed else (p["max"], g["max"])
    page = mix["page_size"]
    spans: Dict[int, Tuple[int, int]] = {}
    for rl in range(row_len(mix, *lo), row_len(mix, *hi) + 1, page):
        plen = min(rl - 2 * WARM_GEN, hi[0])
        spans.setdefault(_bucket(rl // page), (plen, rl))
    return counts, sorted(spans.values())


def warm_lanes(mix: Dict[str, Any], vocab: int,
               mask: Optional[int] = None) -> List[List[Request]]:
    """The warm-up lanes, the same work for every seed.  For each row
    count n the lane starts full, n rows finish first and n queued
    requests swap in, then every row finishes at one step: so a lane
    start, a swap of n, and releases of n and of the whole batch all
    run.  The first count runs twice, because a lane that starts on the
    arenas a previous lane left behind runs programs of its own."""
    rng = np.random.default_rng(0)
    mask = vocab - 1 if mask is None else mask
    counts, spans = warm_plan(mix)
    b = mix["max_batch"]
    lanes, served = [], 0
    for n in counts + counts[:1]:
        lane = []
        for gen in [WARM_GEN] * n + [2 * WARM_GEN] * (b - n) + [WARM_GEN] * n:
            plen, rl = spans[served % len(spans)]
            lane.append(Request(traffic.tokens(rng, plen, vocab, mask),
                                gen, 0.0, row_len=rl))
            served += 1
        lanes.append(lane)
    return lanes


@dataclasses.dataclass
class Record:
    """One request of the window, as the harness saw it."""
    req: Request
    due: float = math.nan            # scheduled arrival (wall clock)
    submitted: float = math.nan
    uid: int = -1
    commits: List[Tuple[float, Tuple[int, ...], Tuple[int, ...]]] = \
        dataclasses.field(default_factory=list)
    done_at: Optional[float] = None
    output: Optional[Tuple[int, ...]] = None

    def sink(self, ev) -> None:
        if ev.kind == "token":
            self.commits.append((ev.ts, ev.positions, ev.tokens))
        elif ev.kind == "done":
            self.done_at = ev.ts
            self.output = ev.tokens


@dataclasses.dataclass
class WindowResult:
    t0: float
    t_end: float
    tokens: int                       # committed inside the window
    step_times: List[float]           # on_step wall times in the window
    records: List[Record]
    admitted: Dict[int, Optional[float]]   # uid -> admission time
    trace_steps: int = 0

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0


class Window:
    """The measured window of one run.  With ``trace_dir`` set, the
    profiler records ``trace_len`` seconds starting ``trace_at`` seconds
    into the window, and the harness's own spans go into that trace."""

    def __init__(self, engine, mix: Dict[str, Any], requests: List[Request],
                 seconds: float, trace_dir: Optional[str] = None,
                 trace_at: float = 0.0, trace_len: float = 0.0):
        self.engine, self.mix, self.seconds = engine, mix, seconds
        self.records = [Record(r) for r in requests]
        self.trace_dir, self.trace_at, self.trace_len = (
            trace_dir, trace_at, trace_len)
        self.stop = threading.Event()
        self.step_times: List[float] = []
        self._next = 0
        self._tracing = False
        self._trace_span: Optional[Tuple[float, float]] = None
        self._trace_steps = 0
        self._iter_span = None
        self._tokens_end: Optional[int] = None
        self._t_end: Optional[float] = None
        self._left = len(self.records)

    # ---- spans ---------------------------------------------------------

    def _span(self, name: str):
        if self._tracing:
            import jax
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def _trace_tick(self, now: float) -> None:
        if self.trace_dir is None:
            return
        import jax
        if self._tracing:
            self._trace_steps += 1
            if self._iter_span is not None:
                self._iter_span.__exit__(None, None, None)
                self._iter_span = None
            if now >= self._trace_span[0] + self.trace_len:
                jax.profiler.stop_trace()
                self._tracing = False
                self._trace_span = (self._trace_span[0], time.time())
                return
            self._iter_span = jax.profiler.TraceAnnotation("engine_iteration")
            self._iter_span.__enter__()
        elif self._trace_span is None and now >= self.t0 + self.trace_at:
            jax.profiler.start_trace(self.trace_dir)
            self._tracing = True
            self._trace_span = (time.time(), math.nan)

    # ---- requests --------------------------------------------------------

    def _submit(self, rec: Record, threadsafe: bool) -> None:
        def sink(ev, rec=rec):
            if ev.kind == "done":
                with self._span("harvest"):
                    rec.sink(ev)
                self._left -= 1
                if self.seconds is None and not self._left:
                    self._t_end = time.time()
                    self._tokens_end = self.engine.stats.tokens_committed
                    self.stop.set()
            else:
                rec.sink(ev)
        with self._span("submit"):
            rec.submitted = time.time()
            fn = (self.engine.submit_threadsafe if threadsafe
                  else self.engine.submit)
            rec.uid = fn(rec.req.prompt, rec.req.gen_len, sink=sink,
                         row_len=rec.req.row_len)

    def _top_up(self) -> None:
        while ((self.seconds is None
                or len(self.engine.queue) < self.mix["max_batch"])
               and self._next < len(self.records)):
            rec = self.records[self._next]
            rec.due = time.time()
            self._submit(rec, threadsafe=False)
            self._next += 1

    def _on_step(self, engine) -> None:
        now = time.time()
        if self.stop.is_set():
            return
        self.step_times.append(now)
        self._trace_tick(now)
        if self.mix["loop"] == "closed" and self.seconds is not None:
            if self._closes(now):
                self._t_end = now
                self._tokens_end = engine.stats.tokens_committed
                self._finish_trace()
                self.stop.set()
                return
            self._top_up()

    def _closes(self, now: float) -> bool:
        """Whether a closed-loop window closes at this step boundary."""
        return now >= self.t0 + self.seconds

    def _finish_trace(self) -> None:
        if self._iter_span is not None:
            self._iter_span.__exit__(None, None, None)
            self._iter_span = None
        if self._tracing:
            import jax
            jax.profiler.stop_trace()
            self._tracing = False
            self._trace_span = (self._trace_span[0], time.time())

    def _generator(self) -> None:
        t_end = self.t0 + self.seconds
        for rec in self.records:
            rec.due = self.t0 + rec.req.arrival
            if rec.due >= t_end:
                break
            time.sleep(max(0.0, rec.due - time.time()))
            self._submit(rec, threadsafe=True)
        time.sleep(max(0.0, t_end - time.time()))
        self._t_end = time.time()
        self._tokens_end = self.engine.stats.tokens_committed
        self.stop.set()

    def run(self) -> WindowResult:
        """Serve the window.  The engine loop is entered from this one
        line whatever the loop or phase: the Pallas kernels' lowering
        records the Python call stack, so a program compiled in the
        warm-up is found in the compile cache by the window only if both
        reach it along the same stack."""
        engine = self.engine
        tokens0 = engine.stats.tokens_committed
        self.t0 = time.time()
        gen = None
        if self.mix["loop"] == "closed" or self.seconds is None:
            self._top_up()
        else:
            gen = threading.Thread(target=self._generator, daemon=True)
            gen.start()
        try:
            engine.run_online(self.stop, max_steps=self.mix["canvas"],
                              on_step=self._on_step)
        finally:
            self.stop.set()
            if gen is not None:
                gen.join()
            self._finish_trace()
        if self._t_end is None:
            raise RuntimeError("the window closed before it ran out: the "
                               "engine ran out of requests")
        admitted = {r.uid: r.started_at for r in engine.done}
        admitted.update({r.uid: None for r in engine.queue})
        return WindowResult(
            t0=self.t0, t_end=self._t_end,
            tokens=self._tokens_end - tokens0,
            step_times=[t for t in self.step_times if t <= self._t_end],
            records=[r for r in self.records if r.uid >= 0],
            admitted=admitted, trace_steps=self._trace_steps)
