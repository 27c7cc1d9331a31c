#!/usr/bin/env python3
"""Bring-up smoke test: serve full-width internlm2-1.8b on one TPU chip.

    python chip_smoke.py

Runs in one process, in this order, and exits non-zero at the first
failure:

  (a) device check — the default JAX device must be a TPU;
  (b) every ``PallasBackend`` kernel at the model's serving widths
      (d=2048, r=128, 16 heads / 8 kv heads of 128, a 4-row batch on a
      1024-token canvas, k=256 selected rows, 16-row pages) against its
      XLA / ``kernels/ref.py`` oracle, each compiled program holding a
      ``tpu_custom_call``;
  (c) the paged ``ServingEngine`` (prefix cache on, ``singular``
      strategy, bf16 weights drawn from a fixed seed) serving 8 seeded
      requests on the XLA backend;
  (d) the same requests on the Pallas backend, with the token agreement
      against (c) printed (not asserted: on a TPU the two backends reduce
      in different orders).

Timings are printed as information for the bring-up record.  The last
line of standard output is one JSON object naming the device, printed
only when every phase passed.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ARCH = "internlm2-1.8b"
SEED = 0

# kernel phase: the serving widths of internlm2-1.8b
D, R, H, KVH, HD = 2048, 128, 16, 8, 128
B, N, K, PAGE = 4, 1024, 256, 16

# engine phases: 4 rows of a 512-token canvas; the pool holds every row
# twice over, so prefix publications fit beside the live rows.  The
# described-v5e compile of this step (bf16 weights) needs 4.25 GB of
# arguments, 0.83 GB of outputs and 2.8 GB of temporaries.
CANVAS, MAX_BATCH = 512, 4
POOL_PAGES = 1 + 2 * MAX_BATCH * (CANVAS // PAGE)
N_REQUESTS = 8
PROMPT_LEN = (64, 256)          # inclusive ranges, drawn per request
GEN_LEN = (64, 128)             # multiples of 8


def model_config():
    from repro.configs import get_arch
    return get_arch(ARCH)


def device_check():
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: the default JAX device is {dev.platform!r}, "
              f"not a TPU; nothing was run", file=sys.stderr)
        sys.exit(1)
    print(f"device: {dev.device_kind} | count {len(devices)} | "
          f"jax {jax.__version__}", flush=True)
    return dev, len(devices)


class Failure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise Failure(msg)


# ---------------------------------------------------------------------------
# (b) kernels
# ---------------------------------------------------------------------------

def _normalized_err(got, want):
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def phase_kernels():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import selection
    from repro.core.svd_proxy import cosine_similarity
    from repro.kernels import proxy_score as ps
    from repro.kernels import ref
    from repro.kernels import scatter_update as sc
    from repro.kernels import sparse_attention as sa
    from repro.kernels.backend import XLA_BACKEND
    from repro.models import common
    from repro.models.attention import reference_attention

    d, r, h, kvh, hd = D, R, H, KVH, HD
    n_log = N // PAGE
    pool = 1 + B * n_log
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 32))
    rng = np.random.default_rng(SEED)

    def normal(shape, dtype, scale=1.0):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    bf16 = jnp.bfloat16
    x = normal((B, N, d), bf16)
    w = normal((d, r), jnp.float32, d ** -0.5)
    pc = normal((B, N, r), bf16)
    # page table: disjoint pages per row; row 1 ends early (zero-page
    # tail), so the zero-page paths run too
    perm = rng.permutation(np.arange(1, pool))
    pt_np = perm[: B * n_log].reshape(B, n_log).astype(np.int32)
    pt_np[1, n_log // 2:] = 0
    pt = jnp.asarray(pt_np)
    idx_np = np.sort(np.stack([rng.choice(N, K, replace=False)
                               for _ in range(B)]), axis=1)
    idx_np[0, -3:] = N                       # dropped sentinels
    idx = jnp.asarray(idx_np.astype(np.int32))

    def proj_ref(xx, pcc):
        with jax.default_matmul_precision("highest"):
            p = (xx.astype(jnp.float32) @ w).astype(xx.dtype)
        return cosine_similarity(p, pcc), p

    def run(name, fn, args, compare):
        compiled = jax.jit(fn).lower(*args).compile()
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: compiled program holds no tpu_custom_call")
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        dt = time.perf_counter() - t0
        detail = compare(out)
        print(f"  kernel {name:<22} ok  {detail}  ({dt * 1e3:.2f} ms, "
              f"first call)", flush=True)

    def cmp_proxy(dense_pc):
        s_ref, p_ref = proj_ref(x, dense_pc)

        def compare(out):
            s, p = out
            ep, es = _normalized_err(p, p_ref), _normalized_err(s, s_ref)
            # p: f32 dot of bf16 x against W_r, rounded to bf16 — the
            # MXU's f32 pass differs from XLA's 'highest' by well under
            # 1% of the largest |p|; scores inherit it
            check(ep < 1e-2 and es < 2e-2,
                  f"proxy scores off: p err {ep:.3g}, score err {es:.3g}")
            return f"p err {ep:.2e} score err {es:.2e}  (tol 1e-2 / 2e-2)"
        return compare

    run("proxy_score", lambda a, b, c: ps.proxy_score(a, b, c),
        (x, w, pc), cmp_proxy(pc))

    p32 = normal((B, N, r), jnp.float32)

    def cmp_cos(dense_pc):
        want = cosine_similarity(p32, dense_pc)

        def compare(out):
            err = float(np.max(np.abs(np.asarray(out) - np.asarray(want))))
            # f32 VPU sums of 128 terms in another order: a few ulp
            check(err < 1e-5, f"cosine drift off by {err:.3g}")
            return f"abs err {err:.2e}  (tol 1e-5)"
        return compare

    run("cosine_drift", lambda a, b: ps.cosine_drift(a, b), (p32, pc),
        cmp_cos(pc))

    arena = normal((pool, PAGE, r), bf16).at[0].set(0)
    dense_arena = XLA_BACKEND.gather_pages(arena[None], pt)[0]
    run("proxy_score_paged",
        lambda a, b, c, e: ps.proxy_score_paged(a, b, c, e),
        (x, w, arena, pt), cmp_proxy(dense_arena))
    run("cosine_drift_paged",
        lambda a, b, c: ps.cosine_drift_paged(a, b, c),
        (p32, arena, pt), cmp_cos(dense_arena))

    hs = normal((B, N, d), bf16)
    wn = normal((d,), bf16, 0.1)
    rows_ref = selection.gather_rows(hs, idx)
    normed_ref = common.rms_norm(rows_ref, wn, 1e-6)

    def cmp_gather(out):
        rows, normed = out
        check(np.array_equal(np.asarray(rows, np.float32),
                             np.asarray(rows_ref, np.float32)),
              "gather_norm rows differ from the gathered rows")
        err = _normalized_err(normed, normed_ref)
        # one bf16 ulp of the normed rows (rsqrt on the VPU vs XLA)
        check(err < 2 ** -7, f"gather_norm normed off by {err:.3g}")
        return f"rows exact, normed err {err:.2e}  (tol 2^-7)"

    run("gather_norm", lambda a, b, c: ps.gather_norm(a, b, c, 1e-6),
        (hs, idx, wn), cmp_gather)

    q = normal((B, K, h, hd), bf16)
    kk = normal((B, N, kvh, hd), bf16)
    vv = normal((B, N, kvh, hd), bf16)
    kv_len = jnp.asarray([N, N // 2, N, 3 * N // 4][:B], jnp.int32)
    qpos = jnp.minimum(idx, N - 1)
    with jax.default_matmul_precision("highest"):
        attn_ref = reference_attention(q, kk, vv, q_positions=qpos,
                                       kv_len=kv_len)

    def cmp_attn(out):
        err = _normalized_err(out, attn_ref)
        # bf16 operands on the MXU, f32 softmax state: well under 2%
        # of the largest output magnitude
        check(err < 2e-2, f"sparse_attention off by {err:.3g}")
        return f"err {err:.2e}  (tol 2e-2 of max|ref|)"

    run("sparse_attention",
        lambda a, b, c, e, f: sa.sparse_attention(a, b, c, e, kv_len=f),
        (q, kk, vv, qpos, kv_len), cmp_attn)

    hc = normal((B, N, d), bf16)
    pcache = normal((B, N, r), bf16)
    rk, rv = normal((B, K, kvh, hd), bf16), normal((B, K, kvh, hd), bf16)
    rh = normal((B, K, d), jnp.float32)
    rp = normal((B, K, r), jnp.float32)
    want = [selection.scatter_rows(c, idx, rr)
            for c, rr in ((kk, rk), (vv, rv), (hc, rh), (pcache, rp))]

    def cmp_exact(want_list):
        def compare(out):
            outs = out if isinstance(out, (tuple, list)) else (out,)
            for o, wnt in zip(outs, want_list):
                check(np.array_equal(np.asarray(o, np.float32),
                                     np.asarray(wnt, np.float32)),
                      "scatter/gather result differs from XLA")
            return "exact"
        return compare

    run("scatter_update_multi",
        lambda a, b, c, e, i, f, g, hh, j: sc.scatter_update_multi(
            [a, b, c, e], i, [f, g, hh, j]),
        (kk, vv, hc, pcache, idx, rk, rv, rh, rp), cmp_exact(want))
    run("scatter_update",
        lambda a, i, f: sc.scatter_update(a, i, f),
        (hc[0], jnp.minimum(idx[0], N - 1), rh[0]),
        cmp_exact([ref.scatter_update_ref(hc[0], jnp.minimum(idx[0], N - 1),
                                          rh[0])]))

    arena_kv = normal((2, pool, PAGE, kvh, hd), bf16).at[:, 0].set(0)
    arena_h = normal((2, pool, PAGE, d), bf16).at[:, 0].set(0)
    run("gather_pages", lambda a, p: sc.gather_pages(a, p), (arena_kv, pt),
        cmp_exact([XLA_BACKEND.gather_pages(arena_kv, pt)]))
    dense_h = normal((2, B, N, d), bf16)
    run("scatter_pages", lambda a, p, e: sc.scatter_pages(a, p, e),
        (arena_h, pt, dense_h),
        cmp_exact([XLA_BACKEND.scatter_pages(arena_h, pt, dense_h)]))
    run("scatter_rows_paged",
        lambda a, p, i, f: sc.scatter_rows_paged(a, p, i, f),
        (arena, pt, idx, rp),
        cmp_exact([XLA_BACKEND.scatter_rows_paged(arena, pt, idx, rp)]))


# ---------------------------------------------------------------------------
# (c) / (d) engine
# ---------------------------------------------------------------------------

def make_requests(vocab: int):
    """8 seeded requests: prompts of 64-256 tokens, gen_len 64-128; two
    repeat earlier ones exactly, so the prefix cache serves them."""
    import numpy as np
    rng = np.random.default_rng(SEED)
    reqs = []
    for _ in range(N_REQUESTS):
        plen = int(rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1))
        gen = int(rng.integers(GEN_LEN[0] // 8, GEN_LEN[1] // 8 + 1)) * 8
        reqs.append((rng.integers(0, vocab - 1, plen).astype(np.int32),
                     gen))
    reqs[5] = reqs[0]
    reqs[7] = reqs[2]
    return reqs


def phase_engine(label, cfg, params, strategy, requests, step_text=False):
    import jax
    import numpy as np

    from repro.core import runtime
    from repro.serving.engine import ServingEngine
    from repro.serving.profiling import StepProfiler

    tracker = runtime.compile_tracker()
    compile0 = tracker.event_seconds.get("backend_compile", 0.0)
    profiler = StepProfiler()
    engine = ServingEngine(
        cfg, params, max_batch=MAX_BATCH, canvas_len=CANVAS,
        strategy=strategy, pool_pages=POOL_PAGES, page_size=PAGE,
        prefix_cache=True, supervise=True, profiler=profiler)
    uids = [engine.submit(p, g) for p, g in requests]
    t0 = time.perf_counter()
    engine.run()
    wall = time.perf_counter() - t0
    stats = engine.stats
    compile_s = tracker.event_seconds.get("backend_compile", 0.0) - compile0

    by_uid = {r.uid: r for r in engine.done}
    check(len(by_uid) == len(requests) and all(u in by_uid for u in uids),
          f"{label}: {len(by_uid)}/{len(requests)} requests completed")
    outputs = []
    for uid, (_, gen) in zip(uids, requests):
        req = by_uid[uid]
        check(req.output is not None and req.fault is None,
              f"{label}: request {uid} faulted ({req.fault})")
        out = np.asarray(req.output)
        check(out.shape == (gen,) and req.tokens_done == gen,
              f"{label}: request {uid} committed {req.tokens_done} of "
              f"{gen} tokens")
        check(bool(np.all((out >= 0) & (out < cfg.vocab_size)
                          & (out != cfg.mask_id))),
              f"{label}: request {uid} holds tokens outside the vocabulary")
        outputs.append(out)
    total = sum(g for _, g in requests)
    check(stats.tokens_committed == total,
          f"{label}: {stats.tokens_committed} tokens committed, "
          f"{total} requested")
    check(stats.nan_quarantines == 0 and stats.requests_faulted == 0,
          f"{label}: NaN quarantine fired ({stats.nan_quarantines})")
    check(stats.prefix_hits > 0, f"{label}: the prefix cache never hit")
    step = profiler.step_breakdown().get("total", {})
    mem = jax.devices()[0].memory_stats() or {}
    print(f"  engine {label}: {len(outputs)}/{len(requests)} requests, "
          f"{stats.tokens_committed} tokens, {stats.steps} steps, "
          f"wall {wall:.2f} s (compiles included), "
          f"{stats.tokens_committed / wall:.1f} tok/s, "
          f"step p50 {step.get('p50_s', float('nan')) * 1e3:.2f} ms, "
          f"backend compile {compile_s:.1f} s, prefix hits "
          f"{stats.prefix_hits} ({stats.prefix_full_hits} full), "
          f"preemptions {stats.preemptions}, device peak in use so far "
          f"{mem.get('peak_bytes_in_use', 0) / 1e9:.2f} GB", flush=True)
    text = ""
    if step_text:
        sess = next(iter(engine._sessions.values()))
        text = sess._step_fn.lower(sess.params, sess.spa_proxies,
                                   sess.state).compile().as_text()
    return engine, outputs, text


def main() -> int:
    dev, count = device_check()
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.core import runtime
    print(f"compile cache: {runtime.enable_compile_cache()}", flush=True)

    import jax
    import numpy as np

    from repro.core.strategy import strategy_from_spec
    from repro.models import transformer

    t0 = time.perf_counter()
    print("(b) Pallas kernels at serving widths", flush=True)
    phase_kernels()
    print(f"(b) done in {time.perf_counter() - t0:.1f} s", flush=True)

    cfg = model_config()
    t1 = time.perf_counter()
    params = jax.block_until_ready(
        transformer.init_params(cfg, jax.random.PRNGKey(SEED)))
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    print(f"{cfg.name}: {cfg.n_layers} layers, d={cfg.d_model}, "
          f"{n_params / 1e9:.3f} B params ({cfg.param_dtype}), "
          f"init {time.perf_counter() - t1:.1f} s", flush=True)
    requests = make_requests(cfg.vocab_size)
    strategy = strategy_from_spec(cfg.spa)

    t2 = time.perf_counter()
    print("(c) ServingEngine, XLA backend", flush=True)
    engine, xla_out, _ = phase_engine("xla", cfg, params,
                                      strategy.with_backend("xla"), requests)
    del engine
    gc.collect()
    print(f"(c) done in {time.perf_counter() - t2:.1f} s", flush=True)

    t3 = time.perf_counter()
    print("(d) ServingEngine, Pallas backend", flush=True)
    engine, pallas_out, step_text = phase_engine(
        "pallas", cfg, params, strategy.with_backend("pallas"), requests,
        step_text=True)
    check("tpu_custom_call" in step_text,
          "pallas: the compiled serve step holds no tpu_custom_call")
    del engine
    gc.collect()
    same = sum(int(np.sum(a == b)) for a, b in zip(xla_out, pallas_out))
    total = sum(len(a) for a in xla_out)
    print(f"(d) done in {time.perf_counter() - t3:.1f} s; token agreement "
          f"pallas vs xla {same}/{total} = {same / total:.4f}; compiled "
          f"step holds {step_text.count('tpu_custom_call')} "
          f"tpu_custom_call ops", flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
