#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

From the root of a checkout, on a machine whose JAX sees the TPU chips
the cell asks for (it exits 3, printing no result, on any other
device).  Everything is found by name: the cell in ``BENCHMARK.json``,
its configuration file, the model family it names
(``families/<family>.py``), ``traffic/<mix>.json``,
``checks/<cell>.json`` and one reader per metric in ``metrics/``.

One run: seeded bf16 weights made on the chip, the serving engine, a
warm-up of every shape the mix produces (set-up ends here), the window
of ``--seconds`` (exit 4 if anything compiles inside it), the device's
peak memory, then — with the engine freed — the check against the
float32 reference.  ``--trace 1`` records a few seconds of the window
with the profiler and reports the per-layer metrics instead of the
end-to-end ones.  The numbers compared, each beside its limit, close
standard error and the result line.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import cell  # noqa: E402
import check  # noqa: E402
import context  # noqa: E402
import xplane  # noqa: E402
import traffic  # noqa: E402

TRACE_AT, TRACE_LEN = 0.4, 0.15     # share of the window: start, length
TRACE_MAX_S = 6.0


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def tpu_devices(chips: int):
    """The TPU devices, or exit 3 without a result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"run.py: needs {chips} TPU chip(s); JAX sees "
              f"{len(devs)} {devs[0].platform} device(s). No result.",
              file=sys.stderr)
        sys.exit(3)
    return devs


_CACHE_HITS = [0]


def _on_event(event: str, **kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _CACHE_HITS[0] += 1


def compile_counts() -> Dict[str, int]:
    """Compile requests (the program's tracker), how many the persistent
    cache served, and programs lowered — cumulative for the process."""
    from repro.core import runtime
    counts = runtime.compile_tracker().event_counts
    return {"requests": counts.get("backend_compile", 0),
            "cache_hits": _CACHE_HITS[0],
            "lowered": counts.get("lowering", 0)}


def compiled_between(c0: Dict[str, int], c1: Dict[str, int]) -> int:
    """XLA compilations between two counts: requests not served by the
    persistent cache."""
    return ((c1["requests"] - c0["requests"])
            - (c1["cache_hits"] - c0["cache_hits"]))


def execute(args, root: str = ROOT, bench_dir: str = HERE, devices=None,
            t_start: float = T_START):
    """One run; returns the result object (printing nothing to stdout).
    ``devices`` skips the look for a chip (tests pass the CPU)."""
    bench = cell.load_json(root, "BENCHMARK.json")
    cel, entry = cell.find_cell(bench, args.workload)
    cfg = cell.load_json(root, entry["file"])
    fam = cell.family(cfg, bench_dir)
    mix = traffic.load_mix(cel["traffic"], bench_dir)
    limits = check.load_limits(args.workload, bench_dir)
    devs = devices if devices is not None else tpu_devices(cel["chips"])
    dev = devs[0]
    print(f"device: {dev.platform} | {dev.device_kind} | count {len(devs)}",
          file=sys.stderr, flush=True)

    import jax
    mcfg = fam.model_config(cfg)
    params = jax.block_until_ready(fam.make_params(cfg, args.seed))
    engine = cell.build_engine(mcfg, params, mix)
    from jax import monitoring
    if _on_event not in getattr(execute, "_listening", ()):
        monitoring.register_event_listener(_on_event)
        execute._listening = (_on_event,)
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(bench_dir, ".traces", args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    # the warm-up lanes, then the window, all from one call site (see
    # cell.Window.run): only the last phase is timed and traced
    phases = [(lane, None, None) for lane in
              cell.warm_lanes(mix, cfg["vocab_size"], cell.mask_id(cfg))]
    phases.append((traffic.generate(mix, cfg["vocab_size"], args.seed,
                                    args.seconds, cell.mask_id(cfg)),
                   args.seconds, trace_dir))
    for i, (requests, seconds, tdir) in enumerate(phases):
        if i == len(phases) - 1:
            compiles0 = compile_counts()
            setup_s = time.time() - t_start
            print(f"set-up {setup_s:.3f} s ({i} warm-up lanes)",
                  file=sys.stderr, flush=True)
            jax.config.update("jax_log_compiles", True)  # names any compile
        win = cell.Window(engine, mix, requests, seconds, tdir,
                          trace_at=TRACE_AT * args.seconds,
                          trace_len=min(TRACE_MAX_S,
                                        TRACE_LEN * args.seconds)).run()
    jax.config.update("jax_log_compiles", False)
    compiles1 = compile_counts()
    in_window = compiled_between(compiles0, compiles1)
    print(f"inside the window: {in_window} compilations, "
          f"{compiles1['lowered'] - compiles0['lowered']} programs lowered, "
          f"{compiles1['cache_hits'] - compiles0['cache_hits']} served by "
          f"the compile cache", file=sys.stderr, flush=True)
    if in_window:
        print("run.py: the window compiled. No result.", file=sys.stderr)
        sys.exit(4)
    late = [r.submitted - r.due for r in win.records
            if r.due < win.t_end and r.submitted == r.submitted]
    print(f"window {win.seconds:.3f} s: {len(win.records)} requests "
          f"submitted, {sum(r.output is not None for r in win.records)} "
          f"finished, {win.tokens} tokens, {len(win.step_times)} steps; "
          f"generator late by at most {1e3 * max(late, default=0):.2f} ms",
          file=sys.stderr, flush=True)
    gaps = sorted(b - a for a, b in zip(win.step_times, win.step_times[1:]))
    if gaps:
        print(f"engine iterations: median {1e3 * gaps[len(gaps) // 2]:.2f} "
              f"ms, longest {1e3 * gaps[-1]:.2f} ms, "
              f"{sum(g > 2 * gaps[len(gaps) // 2] for g in gaps)} over "
              f"twice the median", file=sys.stderr, flush=True)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)

    del engine, params
    gc.collect()
    nums = check.numbers(fam, cfg, fam.make_params(cfg, args.seed), win,
                         mix, limits, args.seed)
    correct, rows = check.judge(nums, limits)

    ctx = context.Ctx(cel, cfg, mix, win, setup_s, dev.device_kind,
                      family=fam)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct),
              "attempted": len(win.records),
              "failed": int(nums["stalled_requests"]),
              "metrics": {}, "device": device}
    kind = "per_layer" if args.trace else "end_to_end"
    if args.trace:
        ctx.trace = xplane.load(trace_dir)
        device["busy_s"] = xplane.busy_s(ctx.trace)
        device["window_s"] = ctx.trace.window_s
        top = sorted(xplane.by_name(ctx.trace.ops[0], xplane.short).items(),
                     key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in xplane.idle_gaps(ctx.trace)]}
        print(f"traced {ctx.trace.window_s:.3f} s, {win.trace_steps} steps",
              file=sys.stderr)
    for m in bench[kind]:
        if args.workload not in m.get("workloads", [args.workload]):
            continue
        val = context.reader(os.path.join(bench_dir, "metrics"),
                             m["name"])(ctx)
        if val is None:
            if kind == "end_to_end":
                raise RuntimeError(f"{m['name']} read nothing")
            continue
        result["metrics"][m["name"]] = {"value": float(val),
                                        "unit": m["unit"]}
    result["checks"] = {name: {"value": val, "limit": lim}
                        for name, val, lim in rows}
    for name, val, lim in rows:
        print(f"check {name}: {val!r} (limit {lim})", file=sys.stderr)
    return result


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at the program's fixed path
    inside the checkout (or ``JAX_COMPILATION_CACHE_DIR``), keeping
    every program, however quick to compile, so that only a cell's
    first run in a checkout compiles."""
    import jax
    from repro.core import runtime
    path = runtime.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(f"compile cache: {path}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    tpu_devices(1)
    enable_compile_cache()
    result = execute(args)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
