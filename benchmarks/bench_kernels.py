"""Per-kernel microbench: Pallas kernels vs their jnp oracles.

Times each serve-hot-path kernel (proxy_score, cosine_drift,
gather_norm, sparse_attention, scatter_update_multi) against the
equivalent XLA-op implementation at paper-flavoured shapes, and emits
``BENCH_kernels.json`` to seed the perf trajectory.

On this CPU container the Pallas side runs in INTERPRET mode, so its
wall-clock is a correctness-wiring check, not a speed claim — the
meaningful CPU numbers are the XLA-side baselines and the recorded
shapes; on a TPU backend the same file reports real Mosaic timings.
The JSON records which flavor ran (``pallas_mode``).

Timing separates FIRST-CALL (compile) from STEADY-STATE wall time —
the old warm-up-and-discard loop silently threw the compile number
away, which is exactly what the §12 retrace accounting wants on
record.  Every (kernel, shape, backend, block-config) measurement is
also persisted into the shared ProfileStore
(``BENCH_artifacts/kernel_profiles.json``) that
``launch/hillclimb.py`` warm-starts from.
"""
from __future__ import annotations

import json
from typing import Dict

import jax
import jax.numpy as jnp

from repro.core import selection
from repro.kernels import ops
from repro.serving.profiling import ProfileStore, time_compile_steady
from repro.models import common
from repro.models.attention import flash_attention
from repro.core.svd_proxy import cosine_similarity

OUT_PATH = "BENCH_kernels.json"

# Pallas grid tiling the kernel suite defaults to (sparse_attention
# block_q/block_k=512, scatter block_k=128); recorded per-measurement
# so a future autotuner can distinguish configs in the store.
BLOCK_CONFIG = "bq512_bk512_sc128"


def _shapes(quick: bool) -> Dict[str, int]:
    if quick:
        return dict(b=2, n=256, d=128, r=32, h=4, kvh=2, hd=32, k=64)
    # LLaDA-8B-flavoured serve step: 4k canvas, rank-128 proxy, k=rho*N
    return dict(b=2, n=4096, d=2048, r=128, h=16, kvh=16, hd=128, k=1024)


def run(quick: bool = False) -> None:
    s = _shapes(quick)
    b, n, d, r, h, kvh, hd, k = (s[x] for x in
                                 "b n d r h kvh hd k".split())
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    x = jax.random.normal(ks[0], (b, n, d))
    w_r = jax.random.normal(ks[1], (d, r))
    pc = jax.random.normal(ks[2], (b, n, r))
    q = jax.random.normal(ks[3], (b, k, h, hd))
    kv_k = jax.random.normal(ks[4], (b, n, kvh, hd))
    kv_v = jax.random.normal(ks[5], (b, n, kvh, hd))
    idx = jnp.sort(jax.random.randint(ks[6], (b, k), 0, n))
    norm_w = jax.random.normal(ks[7], (d,)) * 0.1
    h_rows = jax.random.normal(ks[0], (b, k, d))
    kv_rows = jax.random.normal(ks[1], (b, k, kvh, hd))

    # Arrays go in as jit ARGUMENTS on both sides: a nullary closure
    # bakes them into the HLO as constants and XLA folds the whole op at
    # compile time (the "timing" is then a constant fetch, ~45x off).
    xla: Dict[str, tuple] = {
        "proxy_score": (jax.jit(lambda a, w, p: (
            cosine_similarity((a @ w).astype(a.dtype), p))), (x, w_r, pc)),
        "cosine_drift": (jax.jit(lambda a, p: cosine_similarity(a, p)),
                         (pc, pc)),
        "gather_norm": (jax.jit(lambda a, i, w: common.rms_norm(
            selection.gather_rows(a, i), w)), (x, idx, norm_w)),
        "sparse_attention": (jax.jit(lambda qq, kk, vv, i: flash_attention(
            qq, kk, vv, q_positions=i)), (q, kv_k, kv_v, idx)),
        "scatter_update_multi": (jax.jit(lambda ck, cv, ch, i, rk, rv, rh: (
            selection.scatter_rows(ck, i, rk),
            selection.scatter_rows(cv, i, rv),
            selection.scatter_rows(ch, i, rh))),
            (kv_k, kv_v, x, idx, kv_rows, kv_rows, h_rows)),
    }
    pallas: Dict[str, tuple] = {
        "proxy_score": (ops.proxy_score, (x, w_r, pc)),
        "cosine_drift": (ops.cosine_drift, (pc, pc)),
        "gather_norm": (ops.gather_norm, (x, idx, norm_w)),
        "sparse_attention": (ops.sparse_attention, (q, kv_k, kv_v, idx)),
        "scatter_update_multi": (
            lambda ck, cv, ch, i, rk, rv, rh: ops.scatter_update_multi(
                [ck, cv, ch], i, [rk, rv, rh]),
            (kv_k, kv_v, x, idx, kv_rows, kv_rows, h_rows)),
    }

    mode = "interpret" if ops.default_interpret() else "mosaic"
    results: Dict[str, Dict] = {
        "_meta": {"backend": jax.default_backend(), "pallas_mode": mode,
                  "quick": quick, "shapes": s}}
    store = ProfileStore()
    store.load()
    shape_tag = "x".join(f"{k2}{v}" for k2, v in sorted(s.items()))
    print(f"{'kernel':24s} {'xla_us':>12s} {'pallas_us':>12s} "
          f"{'xla_compile_us':>15s} {'pallas_compile_us':>18s}   "
          f"(pallas={mode})")
    for name in xla:
        fn_x, args_x = xla[name]
        fn_p, args_p = pallas[name]
        c_x, t_x = time_compile_steady(fn_x, *args_x)
        c_p, t_p = time_compile_steady(fn_p, *args_p)
        t_x, t_p, c_x, c_p = (v * 1e6 for v in (t_x, t_p, c_x, c_p))
        results[name] = {"xla_us": round(t_x, 1),
                         "pallas_us": round(t_p, 1),
                         "xla_compile_us": round(c_x, 1),
                         "pallas_compile_us": round(c_p, 1)}
        print(f"{name:24s} {t_x:12.1f} {t_p:12.1f} "
              f"{c_x:15.1f} {c_p:18.1f}")
        for backend, steady, compile_ in (
                ("xla", t_x, c_x), (f"pallas-{mode}", t_p, c_p)):
            store.put(
                {"steady_us": round(steady, 1),
                 "compile_us": round(compile_, 1),
                 "device": jax.default_backend()},
                kind="kernel", kernel=name, shape=shape_tag,
                backend=backend, block=BLOCK_CONFIG)
    with open(OUT_PATH, "w") as f:
        json.dump(results, f, indent=1)
    store.save()
    print(f"wrote {OUT_PATH} and {len(store)} profile records "
          f"-> {store.path}")


if __name__ == "__main__":
    import sys
    run(quick="--quick" in sys.argv or "-q" in sys.argv)
