"""Benchmark entry point: one function per paper table/figure.

``python -m benchmarks.run``            — full pass
``python -m benchmarks.run --quick``    — reduced iteration counts
``python -m benchmarks.run --only t2``  — single benchmark
``python -m benchmarks.run --smoke``    — CI wiring check: table2+table3
                                          at the tiniest configs plus the
                                          kernel microbench (fails fast
                                          on strategy/scheduler/backend
                                          plumbing regressions)
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="t1|t2|t3|t4|t5|fig2|fig4|fig5|roofline")
    ap.add_argument("--smoke", action="store_true",
                    help="CI: quick table2+table3 only (numbers are "
                         "meaningless; exercises decode wiring)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.quick = True
    from repro.core.runtime import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (bench_kernels, bench_serving, fig2_drift,
                            fig4_latency, fig5_anisotropy, roofline,
                            table1_identifiers, table2_main,
                            table3_parallel, table4_ablation, table5_rank)
    registry = {
        "t1": ("Table 1 identifiers", table1_identifiers.run),
        "t2": ("Table 2 main speedups", table2_main.run),
        "t3": ("Table 3 parallel decoding", table3_parallel.run),
        "t4": ("Table 4 ablation", table4_ablation.run),
        "t5": ("Table 5 rank sweep", table5_rank.run),
        "fig2": ("Fig 2 drift profile", fig2_drift.run),
        "fig4": ("Fig 4 latency decomposition", fig4_latency.run),
        "fig5": ("Fig 5 anisotropy", fig5_anisotropy.run),
        "roofline": ("Roofline table", roofline.run),
        "kernels": ("Kernel microbench (BENCH_kernels.json)",
                    bench_kernels.run),
        "serving": ("Serving runtime: paged pool, prefix cache, online "
                    "goodput-under-SLO + front-end smoke, host-tier "
                    "hit-rate gain (BENCH_serving.json)",
                    bench_serving.run),
    }
    if args.smoke:
        names = ["t2", "t3", "kernels", "serving"]
    elif args.only:
        names = [args.only]
    else:
        names = list(registry)
    for name in names:
        title, fn = registry[name]
        t0 = time.time()
        print(f"\n##### {title} #####", flush=True)
        try:
            fn(quick=args.quick)
        except Exception as e:  # noqa: BLE001
            print(f"BENCH {name} FAILED: {e!r}")
            raise
        print(f"[{name} done in {time.time() - t0:.1f}s]", flush=True)


if __name__ == "__main__":
    main()
