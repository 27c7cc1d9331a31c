"""Paged cache pool (DESIGN.md §5): allocator, paged kernels, and
paged-vs-dense decode byte-parity for every registered strategy on both
kernel backends."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.core import cache as cache_lib
from repro.core import strategy as strategy_lib
from repro.core.strategy import (AttnOutCache, SPACache, ValueProxyCache,
                                 WindowCache)
from repro.dlm.session import DecodeSession
from repro.kernels import proxy_score as ps
from repro.kernels import scatter_update as sc
from repro.kernels.backend import XLA_BACKEND
from repro.models import transformer
from repro.serving.pool import OutOfPages, PagePool

PAGE = 4
CANVAS = 16
N_LOG = CANVAS // PAGE


# ---------------------------------------------------------------------------
# Allocator
# ---------------------------------------------------------------------------

def test_pool_allocator_basics(tiny_cfg):
    pool = PagePool(tiny_cfg, n_pages=5, page_size=PAGE)
    assert pool.capacity == 4 and pool.available == 4
    a = pool.alloc(3)
    assert a is not None and len(a) == 3 and 0 not in a
    assert pool.alloc(2) is None          # all-or-nothing
    b = pool.alloc(1)
    assert pool.available == 0 and pool.used == 4
    assert pool.peak_used == 4
    pool.free(a)
    assert pool.available == 3
    c = pool.alloc(3)
    assert sorted(c) == sorted(a)         # pages recycle
    pool.free(b + c)
    assert pool.available == pool.capacity


def test_pool_page_table_row(tiny_cfg):
    pool = PagePool(tiny_cfg, n_pages=9, page_size=PAGE)
    pages = pool.alloc(2)
    row = pool.page_table_row(pages, CANVAS)
    assert row[:2] == pages and row[2:] == [0, 0]  # tail = zero page


def test_pool_arena_shapes_and_sharing(tiny_cfg):
    pool = PagePool(tiny_cfg, n_pages=6, page_size=PAGE,
                    strategy=SPACache(rank=16))
    arenas = pool.arenas_for(SPACache(rank=16))
    (kind, bufs), = arenas.items()
    lk = tiny_cfg.n_layers_of_kind(kind)
    assert bufs["k"].shape[:3] == (lk, 6, PAGE)
    assert bufs["proxy"].shape == (lk, 6, PAGE, 16)
    # same signature -> same arena object; different -> new arenas
    assert pool.arenas_for(SPACache(rank=16, rho_peak=0.9)) is arenas
    assert pool.arenas_for(WindowCache()) is not arenas
    assert pool.arenas_for(strategy_lib.NoCache()) == {}


# ---------------------------------------------------------------------------
# Paged kernels vs XLA oracle
# ---------------------------------------------------------------------------

@pytest.fixture()
def paged_fixture():
    rng = np.random.default_rng(0)
    arena = jnp.asarray(rng.normal(size=(3, 9, PAGE, 8)).astype(np.float32))
    arena = arena.at[:, 0].set(0.0)       # zero page
    pt = jnp.asarray([[1, 2, 0, 0], [3, 4, 5, 6]], jnp.int32)
    return rng, arena, pt


def test_gather_scatter_pages_kernels_match_oracle(paged_fixture):
    rng, arena, pt = paged_fixture
    dense_o = XLA_BACKEND.gather_pages(arena, pt)
    dense_k = sc.gather_pages(arena, pt, interpret=True)
    np.testing.assert_array_equal(np.asarray(dense_o),
                                  np.asarray(dense_k))
    new = jnp.asarray(
        rng.normal(size=(3, 2, CANVAS, 8)).astype(np.float32))
    back_o = XLA_BACKEND.scatter_pages(arena, pt, new)
    back_k = sc.scatter_pages(arena, pt, new, interpret=True)
    np.testing.assert_array_equal(np.asarray(back_o), np.asarray(back_k))
    # zero page never written
    assert np.abs(np.asarray(back_k)[:, 0]).max() == 0.0
    # roundtrip: valid pages carry the new values
    again = sc.gather_pages(back_k, pt, interpret=True)
    np.testing.assert_array_equal(np.asarray(again)[0, 0, :8],
                                  np.asarray(new)[0, 0, :8])


def test_scatter_rows_paged_matches_oracle(paged_fixture):
    rng, arena, pt = paged_fixture
    arena1 = arena[0]
    # sorted rows, an out-of-range sentinel, and zero-page rows (row 0's
    # logical pages 2/3 alias the zero page -> dropped)
    idx = jnp.asarray([[0, 1, 2, 3, 9, CANVAS],
                       [2, 4, 5, 6, 7, 15]], jnp.int32)
    rows = jnp.asarray(rng.normal(size=(2, 6, 8)).astype(np.float32))
    out_o = XLA_BACKEND.scatter_rows_paged(arena1, pt, idx, rows)
    out_k = sc.scatter_rows_paged(arena1, pt, idx, rows, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_o), np.asarray(out_k))
    assert np.abs(np.asarray(out_k)[0]).max() == 0.0  # zero page intact


def test_proxy_score_paged_matches_dense(paged_fixture):
    rng, _, pt = paged_fixture
    d, r = 8, 8
    x = jnp.asarray(rng.normal(size=(2, CANVAS, d)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(d, r)).astype(np.float32))
    parena = jnp.asarray(
        rng.normal(size=(9, PAGE, r)).astype(np.float32)).at[0].set(0.0)
    dense = XLA_BACKEND.gather_pages(parena[None], pt)[0]
    s_p, p_p = ps.proxy_score_paged(x, w, parena, pt, interpret=True)
    s_d, p_d = ps.proxy_score(x, w, dense, interpret=True)
    # The paged and dense kernels are different programs: XLA fuses the
    # cosine's r-length f32 sums differently around the page DMAs, so a
    # score may move by a few f32 ulp (|cos| <= 1, r = 8 terms:
    # reassociation error <= r * 2^-24 * 2 < 1e-6).  The projection has
    # the same block shape in both and stays exact.
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_d),
                               rtol=0, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(p_p), np.asarray(p_d))
    c_p = ps.cosine_drift_paged(p_p, parena, pt, interpret=True)
    c_d = ps.cosine_drift(p_p, dense, interpret=True)
    np.testing.assert_allclose(np.asarray(c_p), np.asarray(c_d),
                               rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# Paged decode == dense decode, every strategy x both backends
# ---------------------------------------------------------------------------

def _test_instance(ident: str):
    inc = ident.endswith("+inc")
    base = ident.split("+")[0]
    cls = strategy_lib.REGISTRY[base]
    if cls is SPACache:
        return SPACache(rank=16, schedule="uniform", rho_peak=0.3,
                        incremental_ident=inc)
    if cls is ValueProxyCache:
        return ValueProxyCache(projection=base, rho=0.3)
    if cls is WindowCache:
        return WindowCache(locality_window=8, rho=0.3)
    if cls is AttnOutCache:
        return AttnOutCache(rho=0.5)
    return cls()


def _paged_session_run(cfg, params, strat, backend, rows, gen_lens,
                       kv_lens, run_compiled=False):
    """Serve the rows through a PagedCache session; rows shorter than the
    canvas own only the pages covering kv_len (tail = zero page)."""
    b = len(rows)
    tokens = np.full((b, CANVAS), cfg.mask_id, np.int32)
    active = np.zeros((b, CANVAS), bool)
    for i, (p, g) in enumerate(zip(rows, gen_lens)):
        tokens[i, : len(p)] = p
        active[i, len(p): len(p) + g] = True
    pool = PagePool(cfg, n_pages=1 + b * N_LOG, page_size=PAGE,
                    strategy=strat)
    arenas = pool.arenas_for(strat)
    pt = np.zeros((b, N_LOG), np.int32)
    for i in range(b):
        pages = pool.alloc(kv_lens[i] // PAGE) or []
        pt[i] = pool.page_table_row(pages, CANVAS)
    sess = DecodeSession(params, cfg, strategy=strat, backend=backend)
    sess.attach(tokens, active=jnp.asarray(active),
                kv_len=np.asarray(kv_lens, np.int32),
                arenas=arenas or None, page_table=pt)
    toks, _ = sess.run_compiled() if run_compiled else sess.run()
    return np.asarray(toks)


ALL_IDENTS = sorted(strategy_lib.REGISTRY) + ["singular+inc"]


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("ident", ALL_IDENTS)
def test_paged_decode_matches_dense(tiny_cfg, tiny_params, ident, backend):
    """Acceptance: paged and dense layouts decode byte-identically for
    every registered strategy on the XLA oracle AND the Pallas-interpret
    kernel suite (full-length rows: dense has no kv_len masking)."""
    cfg, params = tiny_cfg, tiny_params
    strat = _test_instance(ident)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size - 1)
    sess = DecodeSession(params, cfg, strategy=strat)
    sess.prefill(prompt, gen_len=CANVAS - 8)
    dense_toks, _ = sess.run()

    rows = [np.asarray(prompt[0]), np.asarray(prompt[1])]
    paged = _paged_session_run(cfg, params, strat, backend, rows,
                               [CANVAS - 8] * 2, [CANVAS] * 2)
    np.testing.assert_array_equal(np.asarray(dense_toks), paged)


def test_paged_short_rows_match_alone(tiny_cfg, tiny_params):
    """Mixed-gen_len batching: same-lane rows of different lengths are
    byte-identical to running each alone (tail pages alias the zero page
    and are masked out of attention + selection)."""
    cfg, params = tiny_cfg, tiny_params
    strat = SPACache(rank=16, schedule="uniform", rho_peak=0.3)
    rng = np.random.default_rng(3)
    p0 = rng.integers(0, cfg.vocab_size - 1, 4).astype(np.int32)
    p1 = rng.integers(0, cfg.vocab_size - 1, 8).astype(np.int32)
    mixed = _paged_session_run(cfg, params, strat, "xla", [p0, p1],
                               [4, 8], [8, 16])
    alone0 = _paged_session_run(cfg, params, strat, "xla", [p0], [4], [8])
    alone1 = _paged_session_run(cfg, params, strat, "xla", [p1], [8],
                                [16])
    np.testing.assert_array_equal(mixed[0, :8], alone0[0, :8])
    np.testing.assert_array_equal(mixed[1], alone1[0])


def test_paged_run_compiled_matches_host_loop(tiny_cfg, tiny_params):
    """The device-resident while_loop steps the PagedCache carry (incl.
    the lax.cond refresh -> arena scatter) identically to the host."""
    cfg, params = tiny_cfg, tiny_params
    strat = SPACache(rank=16, schedule="uniform", rho_peak=0.3,
                     refresh_interval=3)
    rng = np.random.default_rng(5)
    rows = [rng.integers(0, cfg.vocab_size - 1, 4).astype(np.int32)]
    host = _paged_session_run(cfg, params, strat, "xla", rows, [8], [12])
    dev = _paged_session_run(cfg, params, strat, "xla", rows, [8], [12],
                             run_compiled=True)
    np.testing.assert_array_equal(host, dev)


def test_paged_int8_cache_matches_dense(tiny_cfg, tiny_params):
    cfg = dataclasses.replace(tiny_cfg, cache_dtype="int8")
    params = tiny_params
    strat = SPACache(rank=16, schedule="uniform", rho_peak=0.3)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0,
                                cfg.vocab_size - 1)
    sess = DecodeSession(params, cfg, strategy=strat)
    sess.prefill(prompt, gen_len=8)
    dense_toks, _ = sess.run()
    paged = _paged_session_run(cfg, params, strat, "pallas",
                               [np.asarray(prompt[0])], [8], [CANVAS])
    np.testing.assert_array_equal(np.asarray(dense_toks), paged)


def test_preempt_resume_matches_refresh_twin(tiny_cfg, tiny_params):
    """A preempted-then-resumed request (pages released, cache rebuilt
    from the canvas snapshot at resume) is byte-identical to a twin that
    ran a periodic refresh at the same step — the documented resume
    semantics (DESIGN.md §5)."""
    cfg, params = tiny_cfg, tiny_params
    strat = SPACache(rank=16, schedule="uniform", rho_peak=0.3)
    rng = np.random.default_rng(7)
    p = rng.integers(0, cfg.vocab_size - 1, 4).astype(np.int32)

    def setup():
        pool = PagePool(cfg, n_pages=1 + N_LOG, page_size=PAGE,
                        strategy=strat)
        arenas = pool.arenas_for(strat)
        pages = pool.alloc(N_LOG)
        pt = np.asarray([pool.page_table_row(pages, CANVAS)], np.int32)
        tokens = np.full((1, CANVAS), cfg.mask_id, np.int32)
        tokens[0, :4] = p
        active = np.zeros((1, CANVAS), bool)
        active[0, 4:12] = True
        sess = DecodeSession(params, cfg, strategy=strat)
        sess.attach(tokens, active=jnp.asarray(active),
                    kv_len=np.asarray([CANVAS], np.int32),
                    arenas=arenas, page_table=pt)
        return sess, pt

    # twin A: 3 steps, preempt (snapshot + release), resume, finish
    sa, pt = setup()
    for _ in range(3):
        sa.step()
    snap = sa.snapshot_rows([0])
    sa.release_rows([0])
    sa.replace_rows([0], snap["tokens"], snap["active"],
                    row_kv_len=np.asarray([CANVAS], np.int32),
                    row_page_table=pt,
                    row_committed=snap["committed"])
    toks_a, _ = sa.run()

    # twin B: 3 steps, periodic refresh at the same point, finish
    sb, _ = setup()
    for _ in range(3):
        sb.step()
    sb.refresh()
    toks_b, _ = sb.run()
    np.testing.assert_array_equal(np.asarray(toks_a), np.asarray(toks_b))


def test_submit_larger_than_pool_raises(tiny_cfg, tiny_params):
    from repro.serving.engine import ServingEngine
    eng = ServingEngine(tiny_cfg, tiny_params, max_batch=1,
                        canvas_len=CANVAS, pool_pages=3, page_size=PAGE,
                        strategy=SPACache(rank=16))
    with pytest.raises(OutOfPages):
        eng.submit(np.arange(8, dtype=np.int32), gen_len=8)


def test_pool_refcounts(tiny_cfg):
    pool = PagePool(tiny_cfg, n_pages=5, page_size=PAGE)
    pages = pool.alloc(2)
    assert all(pool.refcount(p) == 1 for p in pages)
    pool.retain(pages)
    pool.release(pages)               # reader hold dropped, still owned
    assert pool.used == 2 and all(pool.refcount(p) == 1 for p in pages)
    pool.release(pages)               # last hold: pages return
    assert pool.used == 0 and not pool.refcounts
    with pytest.raises(AssertionError):
        pool.retain(pages)            # retaining freed pages is a bug


@pytest.mark.parametrize("host_pages", [0, 16])
def test_engine_page_accounting_leak_free(tiny_cfg, tiny_params,
                                          host_pages):
    """Leak detector: an engine run mixing completions, preemptions,
    prefix hits, publications and index evictions fully drains with
    every page back in the free list and every refcount at zero (the
    prefix index's own holds released via ``drop_prefix_cache``).  With
    the §9 host tier attached the same churn must ALSO keep the host
    pool in lockstep with the trie's host refs, and the drop empties
    both tiers."""
    from repro.serving.engine import ServingEngine
    strat = SPACache(rank=16, schedule="uniform", rho_peak=0.3)
    eng = ServingEngine(tiny_cfg, tiny_params, max_batch=2,
                        canvas_len=CANVAS, pool_pages=13, page_size=PAGE,
                        strategy=strat, prefix_cache=True,
                        host_pages=host_pages)

    def both_tiers_consistent():
        assert eng.pool.used == eng.prefix.held_pages
        assert all(rc == 1 for rc in eng.pool.refcounts.values())
        if eng.host_pool is not None:
            assert (eng.host_pool.used_pages
                    == eng.prefix.host_held_pages)
    rng = np.random.default_rng(21)
    shared = rng.integers(0, tiny_cfg.vocab_size - 1, 8).astype(np.int32)
    decoy = rng.integers(0, tiny_cfg.vocab_size - 1, 8).astype(np.int32)
    eng.submit(shared, gen_len=8)     # cold, publishes 4 pages
    eng.submit(decoy, gen_len=8)      # cold, publishes 4 more (LRU-er)
    eng.run()
    # full hit (its plan protects the shared entry) + a small filler;
    # admitting them under pressure evicts the decoy's pages
    eng.submit(shared, gen_len=8)
    eng.submit(rng.integers(0, tiny_cfg.vocab_size - 1, 4)
               .astype(np.int32), gen_len=4)
    big = rng.integers(0, tiny_cfg.vocab_size - 1, 8).astype(np.int32)
    s0 = eng.stats.steps              # stats accumulate across runs

    def on_step(e):
        if e.stats.steps == s0 + 2:   # full batch + 2 free pages:
            e.submit(big, gen_len=8, priority=5)   # evicts AND preempts

    eng.run(on_step=on_step)
    assert eng.stats.requests_done == 5
    assert eng.stats.prefix_full_hits >= 1
    assert eng.stats.preemptions > 0
    assert eng.stats.prefix_evicted_pages > 0
    # after the drain, the ONLY pages still held belong to the index
    both_tiers_consistent()

    # --- cancellation (DESIGN.md §8) must uphold the same invariant:
    # cancel-while-running releases the row's pages mid-decode,
    # cancel-while-queued drops the request (and its prefix-plan holds)
    # before it ever owns a row
    run_victim = eng.submit(rng.integers(0, tiny_cfg.vocab_size - 1, 8)
                            .astype(np.int32), gen_len=8)
    filler = rng.integers(0, tiny_cfg.vocab_size - 1, 4).astype(np.int32)
    eng.submit(filler, gen_len=4)
    queue_victim = eng.submit(shared, gen_len=8)  # full hit: plan holds
    s1 = eng.stats.steps

    def on_step_cancel(e):
        if e.stats.steps == s1 + 2:
            assert e.cancel(run_victim)       # in-flight: owns pages
            assert e.cancel(queue_victim)     # still queued
    eng.run(on_step=on_step_cancel)
    assert eng.stats.requests_canceled == 2
    canceled = {r.uid: r for r in eng.done
                if r.uid in (run_victim, queue_victim)}
    assert canceled[run_victim].canceled
    assert canceled[run_victim].output is None
    assert canceled[queue_victim].canceled
    assert not eng.cancel(run_victim)         # already finalized
    both_tiers_consistent()

    if host_pages:
        # --- cancel during PROMOTING (DESIGN.md §10): a queued request
        # parked on a host-tier match holds NO device pages yet; the
        # cancel must clear the parked plan without touching either
        # tier, and the host entry must stay promotable afterwards.
        parked = None
        for p in (shared, decoy, big, filler):
            u = eng.submit(p, gen_len=len(p))
            req = next(r for r in eng.queue if r.uid == u)
            eng._prefix_plan(req)
            if parked is None and req.pending_promotion is not None:
                parked = (u, req, p)
            else:
                eng._drop_plan(req)
            assert eng.cancel(u)
        assert parked is not None, "churn left no host-resident entry"
        u, req, p = parked
        assert req.canceled and req.pending_promotion is None
        assert not req.holds and req.pages is None
        both_tiers_consistent()
        # a fresh request still warms from the host tier
        p0 = eng.stats.prefix_promotions
        eng.submit(p, gen_len=len(p))
        eng.run()
        assert eng.stats.prefix_promotions == p0 + 1
        both_tiers_consistent()

    eng.drop_prefix_cache()
    assert eng.pool.used == 0
    assert eng.pool.available == eng.pool.capacity
    assert not eng.pool.refcounts
    if eng.host_pool is not None:
        # drop emptied BOTH tiers, and the churn exercised them
        assert eng.host_pool.used_pages == 0
        assert eng.host_pool.used_units == 0
        assert eng.stats.prefix_demoted_pages > 0
