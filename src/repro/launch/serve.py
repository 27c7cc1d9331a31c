"""Serving launcher: run the batched SPA-Cache engine on a model
checkpoint (or a freshly initialized reduced model for demo purposes).

The caching policy is selected per run with ``--strategy`` (any
registered CacheStrategy identifier: singular, value, window, attn_out,
none, ...) without touching the model config.

  PYTHONPATH=src python -m repro.launch.serve --arch llada-8b \
      --requests 8 --gen-len 16 --strategy singular

``--serve`` switches from the offline batch loop to the online
front-end (DESIGN.md §8): an asyncio HTTP server on ``--port`` that
streams per-token ndjson events per request, with SLO-aware admission
(``--slo-ttft`` / ``--slo-deadline``, seconds; 0 disables the policy).
``--client HOST:PORT`` instead runs a demo streaming client against a
running server (see also ``examples/serve_stream.py``).

Without ``--full-size`` the model is the reduced same-family variant
(CPU-sized); with it, the architecture's published widths (a TPU run):

  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
      --full-size --pool-pages 257 --page-size 16 --canvas 1024 \
      --max-batch 4 --requests 8 --gen-len 64
"""
from __future__ import annotations

import argparse
import dataclasses
import sys

import jax
import numpy as np

from repro.configs import get_arch, reduced
from repro.core import runtime
from repro.core.strategy import REGISTRY, strategy_from_spec
from repro.dlm.decoding import DecodeSettings
from repro.models import transformer
from repro.serving.engine import ServingEngine
from repro.training import checkpoint


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llada-8b")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--full-size", action="store_true",
                    help="serve the architecture at its published widths "
                         "(real hardware only); default: the reduced "
                         "CPU-sized variant")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--canvas", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--parallel-threshold", type=float, default=0.0)
    ap.add_argument("--strategy", default="",
                    choices=[""] + sorted(REGISTRY),
                    help="cache strategy override (default: cfg.spa)")
    ap.add_argument("--kernel-backend", default="",
                    choices=["", "xla", "pallas"],
                    help="hot-path kernel backend (DESIGN.md §4.5; "
                         "default xla; pallas = TPU kernel suite, "
                         "interpret mode off-TPU)")
    ap.add_argument("--static-batching", action="store_true",
                    help="disable step-granular continuous batching")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="paged serving (DESIGN.md §5): total pages in "
                         "the device cache pool (page 0 is the reserved "
                         "zero page); 0 = dense per-lane slabs")
    ap.add_argument("--page-size", type=int, default=16,
                    help="canvas rows per cache page (the canvas length "
                         "must be a multiple)")
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true", default=True,
                    help="shared-prefix radix cache (DESIGN.md §6): "
                         "reuse prefill pages across requests with "
                         "matching prompt prefixes + canvas layout "
                         "(paged mode only; default on)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false")
    ap.add_argument("--host-pages", type=int, default=0,
                    help="hierarchical cache (DESIGN.md §9): host-RAM "
                         "page tier capacity in exact-page units — "
                         "evicted prefix entries demote there instead "
                         "of dying and promote back on a hit; 0 = off "
                         "(needs --pool-pages and the prefix cache)")
    ap.add_argument("--host-dtype", default="auto",
                    choices=["auto", "f32", "int8"],
                    help="cold-tier representation: f32 = every "
                         "promotion byte-identical; int8 = ~2x host "
                         "capacity, promoted prefixes allclose-class; "
                         "auto = int8 only for stability-scored pages")
    ap.add_argument("--serve", action="store_true",
                    help="online mode (DESIGN.md §8): run the asyncio "
                         "streaming front-end instead of the offline "
                         "batch loop")
    ap.add_argument("--port", type=int, default=8411)
    ap.add_argument("--slo-ttft", type=float, default=0.0,
                    help="TTFT target (s) attached to demo/client "
                         "requests; enables the SLO-aware policy")
    ap.add_argument("--slo-deadline", type=float, default=0.0,
                    help="e2e deadline (s) for demo/client requests")
    ap.add_argument("--client", default="",
                    help="HOST:PORT — run a streaming client against a "
                         "running --serve front-end and exit")
    ap.add_argument("--supervise", action="store_true",
                    help="wrap the engine in the fault supervisor "
                         "(DESIGN.md §10): invariant checking, NaN "
                         "quarantine, watchdog, degradation ladder")
    ap.add_argument("--chaos-seed", type=int, default=-1,
                    help="enable deterministic fault injection with "
                         "this seed (DESIGN.md §10); -1 = off")
    ap.add_argument("--chaos-rate", type=float, default=0.02,
                    help="per-probe fire rate for every fault site "
                         "when --chaos-seed is set")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome-trace/Perfetto JSON of the run "
                         "(request lifecycle spans + engine phase "
                         "breakdown, DESIGN.md §11) to this path")
    ap.add_argument("--metrics", action="store_true",
                    help="sample SPA cache-dynamics every step and "
                         "print the full metrics-registry dump at exit "
                         "(the compact non-zero dump always prints)")
    ap.add_argument("--profile", action="store_true",
                    help="compute-path profiling (DESIGN.md §12): fence "
                         "per-step device time, print the step-time "
                         "decomposition and the top-3 most-retraced "
                         "lane signatures at exit")
    ap.add_argument("--jax-trace-dir", default="",
                    help="with --profile: also wrap the run in "
                         "jax.profiler.trace writing to this directory "
                         "(a trace that cannot start raises)")
    args = ap.parse_args(argv)

    if args.client:
        return _run_client(args)
    runtime.enable_compile_cache()

    cfg = get_arch(args.arch)
    if not args.full_size:
        cfg = reduced(cfg)
    if args.ckpt:
        params, meta = checkpoint.load_checkpoint(args.ckpt)
        print(f"loaded checkpoint {args.ckpt} ({meta})")
    else:
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        print(f"no checkpoint given; serving an untrained "
              f"{'full-size' if args.full_size else 'reduced'} "
              f"{cfg.name} (random weights, seed 0)")

    if cfg.is_encoder_only:
        print(f"{cfg.name} is encoder-only; no decode serving path")
        return 0

    strategy = None
    if args.strategy:
        strategy = strategy_from_spec(
            dataclasses.replace(cfg.spa, identifier=args.strategy))
    if args.kernel_backend:
        strategy = (strategy or strategy_from_spec(cfg.spa)) \
            .with_backend(args.kernel_backend)

    slo_policy = None
    if args.slo_ttft or args.slo_deadline:
        from repro.serving.slo import SLOPolicy
        slo_policy = SLOPolicy()
    fault_plan = None
    if args.chaos_seed >= 0:
        from repro.serving.faults import FAULT_SITES, FaultPlan
        fault_plan = FaultPlan(
            seed=args.chaos_seed,
            rates={s: args.chaos_rate for s in FAULT_SITES})
        print(f"chaos: seed={args.chaos_seed} "
              f"rate={args.chaos_rate} on all sites")
    telemetry = None
    if args.trace_out or args.metrics or args.profile:
        from repro.serving.telemetry import Telemetry, Tracer
        telemetry = Telemetry(
            tracer=Tracer(enabled=bool(args.trace_out)),
            dynamics_every=1 if args.metrics else 0)
    profiler = None
    if args.profile:
        from repro.serving.profiling import StepProfiler
        profiler = StepProfiler(
            telemetry, jax_trace_dir=args.jax_trace_dir or None)
    engine = ServingEngine(
        cfg, params, max_batch=args.max_batch, canvas_len=args.canvas,
        strategy=strategy, continuous=not args.static_batching,
        pool_pages=args.pool_pages, page_size=args.page_size,
        prefix_cache=args.prefix_cache, host_pages=args.host_pages,
        host_dtype=args.host_dtype, slo_policy=slo_policy,
        fault_plan=fault_plan, supervise=args.supervise,
        telemetry=telemetry, profiler=profiler,
        settings=DecodeSettings(
            parallel_threshold=args.parallel_threshold,
            max_parallel=4 if args.parallel_threshold else 0))
    if args.serve:
        return _serve_online(engine, args)
    import contextlib
    trace_ctx = profiler.jax_trace() if profiler is not None \
        else contextlib.nullcontext()
    with trace_ctx:
        _run_offline(engine, args)
    _summarize(engine, args)
    for req in engine.done[:3]:
        out = "<faulted>" if req.output is None else f"{req.output[:10]}..."
        print(f"  req {req.uid}: out={out}")
    return 0


def _run_offline(engine, args) -> None:
    """The offline batch loop (the pre-``--serve`` demo path)."""
    cfg = engine.cfg
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size - 1,
                            int(rng.integers(6, 18))).astype(np.int32)
               for _ in range(args.requests)]
    if args.prefix_cache and args.requests > 1:
        # half unique prompts, then repeats, staged so the §6/§9
        # machinery actually fires (visible in --metrics/--trace-out):
        # cold prompts run SOLO — publication allocs a whole run's
        # worth of pages on top of the row, so a concurrent cold pass
        # mostly fails to publish; the repeats then churn CONCURRENTLY
        # — admission pressure evicts the LRU entries (demoting them
        # to host RAM under --host-pages); the last repeat runs solo
        # against the drained pool, where its promotion alloc can
        # succeed (mid-churn it would only stall).
        uniq = prompts[: max(1, args.requests // 2)]
        wall = 0.0
        for prompt in uniq:
            engine.submit(prompt, args.gen_len)
            engine.run()
            wall += getattr(engine, "_wall", 0.0)
        repeats = [uniq[(i + 1) % len(uniq)]
                   for i in range(args.requests - len(uniq))]
        churn, late = repeats[:-1], []
        if len(churn) > 1:
            # hold one back and land it mid-churn at high priority on
            # the full pool — the §5 preemption path, live in the trace
            churn, late = churn[:-1], [churn[-1]]

        def on_step(e):
            if late and e.stats.steps >= 2:
                e.submit(late.pop(), args.gen_len, priority=5)

        for prompt in churn:
            engine.submit(prompt, args.gen_len)
        engine.run(on_step=on_step)
        wall += getattr(engine, "_wall", 0.0)
        while late:                  # churn drained before step 2
            engine.submit(late.pop(), args.gen_len, priority=5)
            engine.run()
            wall += getattr(engine, "_wall", 0.0)
        engine.submit(repeats[-1], args.gen_len)
        engine.run()
        engine._wall = getattr(engine, "_wall", 0.0) + wall
    else:
        for prompt in prompts:
            engine.submit(prompt, args.gen_len)
        engine.run()


def _summarize(engine, args) -> None:
    """End-of-run report: a one-line headline, exact latency
    percentiles when anything completed, and the metrics-registry dump
    (DESIGN.md §11) in place of the old ad-hoc per-subsystem prints.
    Renders cleanly when zero requests complete."""
    stats = engine.stats
    wall = getattr(engine, "_wall", 0.0)
    print(f"served {stats.requests_done} requests, "
          f"{stats.tokens_committed} tokens, {stats.steps} steps, "
          f"{stats.swaps} slot swaps, {stats.tps(wall):.1f} tok/s")
    if stats.requests_done:
        _print_latency(stats)
    else:
        print("latency: no requests completed")
    if getattr(args, "profile", False) and engine.profiler is not None:
        _print_profile(engine)
    print("metrics registry " + "-" * 46)
    print(engine.telemetry.registry.format_summary(
        skip_zero=not args.metrics))
    if args.trace_out:
        engine.export_trace(args.trace_out)
        n_ev = len(engine.telemetry.tracer.events)
        print(f"trace: {n_ev} events -> {args.trace_out} "
              f"(load in Perfetto / chrome://tracing)")


def _print_profile(engine) -> None:
    """``--profile`` report: step-time decomposition + the top-3
    most-retraced lane signatures (DESIGN.md §12).  Renders cleanly
    when zero steps were profiled (e.g. zero requests completed)."""
    print("step-time decomposition " + "-" * 39)
    print(engine.profiler.format_summary())
    top = runtime.compile_tracker().top_retraced(3)
    if top:
        print("most-retraced lane signatures:")
        for lane, n in top:
            print(f"  {n:4d} traces  {lane or '<unlabeled>'}")
    else:
        print("most-retraced lane signatures: none recorded")


def _print_latency(stats) -> None:
    pct = stats.percentiles()
    print(f"latency: e2e p50={pct['e2e_p50'] * 1e3:.0f}ms "
          f"p95={pct['e2e_p95'] * 1e3:.0f}ms | queue-wait "
          f"p50={pct['wait_p50'] * 1e3:.0f}ms "
          f"p95={pct['wait_p95'] * 1e3:.0f}ms")
    print(f"streaming: TTFT p50={pct['ttft_p50'] * 1e3:.0f}ms "
          f"p95={pct['ttft_p95'] * 1e3:.0f}ms | TPOT "
          f"p50={pct['tpot_p50'] * 1e3:.0f}ms "
          f"p95={pct['tpot_p95'] * 1e3:.0f}ms | SLO "
          f"{stats.slo_met} met / {stats.slo_missed} missed, "
          f"{stats.requests_shed} shed, "
          f"{stats.requests_canceled} canceled")


def _serve_online(engine, args) -> int:
    """``--serve``: run the asyncio streaming front-end until ^C."""
    import asyncio

    from repro.serving.frontend import AsyncFrontend

    async def amain():
        front = AsyncFrontend(engine, port=args.port, max_steps=4096)
        await front.start(serve_http=True)
        print(f"serving on http://{front.host}:{front.port} — "
              f"POST /generate {{prompt, gen_len, slo?}} streams "
              f"ndjson; GET /stats | /metrics (Prometheus) | "
              f"/debug/requests")
        try:
            await asyncio.Event().wait()      # until interrupted
        finally:
            await front.stop()
            _summarize(engine, args)

    try:
        asyncio.run(amain())
    except KeyboardInterrupt:
        pass
    return 0


def _run_client(args) -> int:
    """``--client HOST:PORT``: stream one demo request and print the
    per-event arrivals (see also examples/serve_stream.py)."""
    import asyncio
    import time as _time

    from repro.serving.frontend import fetch_stats, stream_request

    host, _, port = args.client.partition(":")
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 128, 8).astype(np.int32)
    slo = None
    if args.slo_ttft or args.slo_deadline:
        slo = {"ttft": args.slo_ttft or 1e9,
               "deadline": args.slo_deadline or 1e9}

    async def amain():
        t0 = _time.time()
        n = 0
        async for ev in stream_request(host, int(port), prompt,
                                       args.gen_len, slo=slo):
            dt = _time.time() - t0
            if ev["kind"] == "token":
                n += len(ev["tokens"])
                print(f"  +{dt * 1e3:7.1f}ms step {ev['step']:4d} "
                      f"tokens {ev['tokens']}")
            else:
                print(f"  +{dt * 1e3:7.1f}ms {ev['kind']} "
                      f"({n} tokens streamed)")
        stats = await fetch_stats(host, int(port))
        print(f"server: {stats['requests_done']} done, "
              f"TTFT p50={stats['ttft_p50'] * 1e3:.0f}ms, "
              f"TPOT p50={stats['tpot_p50'] * 1e3:.0f}ms")

    asyncio.run(amain())
    return 0


if __name__ == "__main__":
    sys.exit(main())
