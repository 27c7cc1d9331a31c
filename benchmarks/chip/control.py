#!/usr/bin/env python3
"""Readings of the correctness numbers for the program and its control.

    python3 benchmarks/chip/control.py --workload <cell> \\
        --seeds 1,2,3 --seconds 20

For each seed, in one process: the cell's engine serves a short window
at the cell's own size and load, exactly as a run does; then, with the
engine freed, the replayed steps (the same sample a run draws) are read
twice — the served tokens against the float32 reference (the program's
numbers), and the token the int8 control puts first at the same
positions against the same reference (the control's numbers).  Both go
through the cell's limits: the program has to read correct and the
control not.  Prints one JSON line per seed and exits 1 when either
fails.  The cell may be one of ``BENCHMARK.json`` or of
``prepared.json``.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402  (puts the program on the path)
import cell  # noqa: E402
import check  # noqa: E402
import traffic  # noqa: E402


def benchmark(root: str = run.ROOT, bench_dir: str = HERE):
    """BENCHMARK.json with the cells of ``prepared.json`` added."""
    bench = cell.load_json(root, "BENCHMARK.json")
    prepared = cell.load_json(bench_dir, "prepared.json")
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        bench[key] = bench[key] + prepared.get(key, [])
    return bench


def readings(workload: str, seed: int, seconds: float, root: str = run.ROOT,
             bench_dir: str = HERE):
    import jax
    cel, entry = cell.find_cell(benchmark(root, bench_dir), workload)
    cfg = cell.load_json(root, entry["file"])
    fam = cell.family(cfg, bench_dir)
    mix = traffic.load_mix(cel["traffic"], bench_dir)
    limits = check.load_limits(workload, bench_dir)
    params = jax.block_until_ready(fam.make_params(cfg, seed))
    engine = cell.build_engine(fam.model_config(cfg), params, mix)
    mask = cell.mask_id(cfg)
    phases = [(lane, None)
              for lane in cell.warm_lanes(mix, cfg["vocab_size"], mask)]
    phases.append((traffic.generate(mix, cfg["vocab_size"], seed, seconds,
                                    mask), seconds))
    for requests, secs in phases:
        win = cell.Window(engine, mix, requests, secs).run()
    del engine, params
    gc.collect()
    params = fam.make_params(cfg, seed)
    done = [r for r in win.records if r.output is not None]
    steps = check.sample_steps(done, limits["canvases"], seed)
    out = {"workload": workload, "seed": seed, "finished": len(done)}
    for name, ctl in (("program", False), ("control", True)):
        nums = check.gap_numbers(
            check.gap_readings(fam, cfg, params, done, mix, steps,
                               control=ctl))
        nums["finished"] = float(len(done))
        nums["bookkeeping_faults"] = nums["stalled_requests"] = 0.0
        if not ctl:
            nums["bookkeeping_faults"] = float(sum(
                check.bookkeeping_faults(r, mask, cfg["vocab_size"])
                for r in done))
            nums["stalled_requests"] = float(check.stalled(win))
        ok, _ = check.judge(nums, limits)
        out[name] = dict(nums, correct=ok)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    run.tpu_devices(1)
    run.enable_compile_cache()
    failed = False
    for s in args.seeds.split(","):
        out = readings(args.workload, int(s), args.seconds)
        failed |= out["control"]["correct"] or not out["program"]["correct"]
        print(json.dumps(out), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
