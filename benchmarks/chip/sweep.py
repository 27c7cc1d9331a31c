#!/usr/bin/env python3
"""Offered-load sweep of an open-loop cell, to find its knee.

    python3 benchmarks/chip/sweep.py --workload <cell> \\
        --rates 1,2,3,4 --seconds 20 --seed 1

One process, one engine, one warm-up; then one window per rate, in the
order given, each on the cell's mix with only ``rate_per_s`` changed.
Prints one JSON line per rate: requests offered and finished per
second, tokens per second, the queue left when the window closed, and
TTFT percentiles.  The knee is the highest rate whose completions keep
pace with arrivals without a growing queue; the cell runs at 4/5 of it,
written into its mix file.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402  (puts the program on the path)
import cell  # noqa: E402
import control  # noqa: E402
import traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    run.tpu_devices(1)
    run.enable_compile_cache()
    import jax
    cel, entry = cell.find_cell(control.benchmark(), args.workload)
    cfg = cell.load_json(run.ROOT, entry["file"])
    fam = cell.family(cfg)
    mix = traffic.load_mix(cel["traffic"])
    params = jax.block_until_ready(fam.make_params(cfg, args.seed))
    engine = cell.build_engine(fam.model_config(cfg), params, mix)
    rates = [float(r) for r in args.rates.split(",")]
    # warm-up lanes, then one window per rate, from one call site
    phases = [(mix, lane, None) for lane in
              cell.warm_lanes(mix, cfg["vocab_size"], cell.mask_id(cfg))]
    for rate in rates:
        m = dict(mix, rate_per_s=rate)
        phases.append((m, traffic.generate(m, cfg["vocab_size"], args.seed,
                                           args.seconds, cell.mask_id(cfg)),
                       args.seconds))
    for m, reqs, seconds in phases:
        win = cell.Window(engine, m, reqs, seconds).run()
        if seconds is None:
            continue
        due = [r for r in win.records if r.due < win.t_end]
        done = [r for r in due if r.done_at is not None
                and r.done_at <= win.t_end]
        ttft = [(min(r.commits[0][0], win.t_end) if r.commits else win.t_end)
                - r.due for r in due]
        queued = sum(1 for r in due if win.admitted.get(r.uid) is None)
        for r in list(engine.queue):     # no backlog into the next rate
            engine.cancel(r.uid)
        print(json.dumps({
            "rate_per_s": m["rate_per_s"],
            "offered_per_s": len(due) / win.seconds,
            "finished_per_s": len(done) / win.seconds,
            "tokens_per_s": win.tokens / win.seconds,
            "queued_at_close": queued,
            "ttft_p50_ms": 1e3 * float(np.percentile(ttft, 50)),
            "ttft_p90_ms": 1e3 * float(np.percentile(ttft, 90))}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
