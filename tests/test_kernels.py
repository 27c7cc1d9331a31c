"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.proxy_score import (cosine_drift, gather_norm,
                                       proxy_score)
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.scatter_update import scatter_update, scatter_update_multi
from repro.kernels.sparse_attention import sparse_attention


@pytest.mark.parametrize("n,d,r", [(64, 32, 8), (200, 96, 32),
                                   (33, 128, 16), (8, 64, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_proxy_score(n, d, r, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (n, d), dtype)
    w = jax.random.normal(ks[1], (d, r), dtype)
    pc = jax.random.normal(ks[2], (n, r), dtype)
    s, p = proxy_score(x, w, pc, interpret=True)
    s_r, p_r = ref.proxy_score_ref(x, w, pc)
    tol = 1e-4 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(s, s_r, rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(p, np.float32),
                               np.asarray(p_r, np.float32),
                               rtol=tol * 10, atol=tol * 10)


@pytest.mark.parametrize("kq,n,h,kvh,hd", [
    (16, 64, 4, 4, 16),      # MHA
    (50, 300, 4, 2, 32),     # GQA, ragged
    (8, 128, 8, 1, 16),      # MQA
])
def test_sparse_attention_shapes(kq, n, h, kvh, hd):
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (kq, h, hd))
    k = jax.random.normal(ks[1], (n, kvh, hd))
    v = jax.random.normal(ks[2], (n, kvh, hd))
    qp = jnp.sort(jax.random.randint(ks[3], (kq,), 0, n))
    out = sparse_attention(q, k, v, qp, interpret=True, block_q=16,
                           block_k=32)
    out_ref = ref.sparse_attention_ref(q, k, v, qp)
    np.testing.assert_allclose(out, out_ref, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("window,soft_cap", [(0, 0.0), (32, 0.0),
                                             (16, 30.0), (0, 50.0)])
def test_sparse_attention_features(window, soft_cap):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (24, 4, 16))
    k = jax.random.normal(ks[1], (160, 2, 16))
    v = jax.random.normal(ks[2], (160, 2, 16))
    qp = jnp.sort(jax.random.randint(ks[3], (24,), 0, 160))
    out = sparse_attention(q, k, v, qp, window=window,
                           soft_cap=soft_cap, interpret=True,
                           block_q=8, block_k=32)
    out_ref = ref.sparse_attention_ref(q, k, v, qp, window=window,
                                       soft_cap=soft_cap)
    np.testing.assert_allclose(out, out_ref, rtol=2e-3, atol=2e-3)


def test_sparse_attention_int8():
    from repro.core.cache import quantize_rows
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (16, 2, 16))
    k = jax.random.normal(ks[1], (96, 2, 16))
    v = jax.random.normal(ks[2], (96, 2, 16))
    qp = jnp.sort(jax.random.randint(ks[3], (16,), 0, 96))
    kq, kscale = quantize_rows(k)
    vq, vscale = quantize_rows(v)
    out = sparse_attention(kq * 0 + q if False else q, kq, vq, qp,
                           k_scale=kscale, v_scale=vscale,
                           interpret=True, block_q=8, block_k=32)
    out_ref = ref.sparse_attention_ref(q, kq, vq, qp, k_scale=kscale,
                                       v_scale=vscale)
    np.testing.assert_allclose(out, out_ref, rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("n,d,k", [(64, 16, 8), (128, 48, 40),
                                   (32, 8, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_scatter_update(n, d, k, dtype):
    rng = np.random.default_rng(0)
    if dtype == jnp.int8:
        cache = jnp.asarray(rng.integers(-100, 100, (n, d)), jnp.int8)
        rows = jnp.asarray(rng.integers(-100, 100, (k, d)), jnp.int8)
    else:
        cache = jax.random.normal(jax.random.PRNGKey(0), (n, d), dtype)
        rows = jax.random.normal(jax.random.PRNGKey(1), (k, d), dtype)
    idx = jnp.asarray(rng.choice(n, k, replace=False), jnp.int32)
    out = scatter_update(cache, idx, rows, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.scatter_update_ref(
            cache, idx, rows)))


def test_proxy_score_batched_grid():
    """The batch dim is a real grid axis: per-row results match the
    unbatched oracle for every batch row."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(ks[0], (3, 65, 96), jnp.bfloat16)
    w = jax.random.normal(ks[1], (96, 32), jnp.bfloat16)
    pc = jax.random.normal(ks[2], (3, 65, 32), jnp.bfloat16)
    s, p = proxy_score(x, w, pc, interpret=True, block_n=16)
    assert s.shape == (3, 65) and p.shape == (3, 65, 32)
    xf, wf = np.asarray(x, np.float32), np.asarray(w, np.float32)
    for i in range(3):
        s_r, p_r = ref.proxy_score_ref(x[i], w, pc[i])
        np.testing.assert_allclose(s[i], s_r, rtol=4e-2, atol=4e-2)
        # The kernel projects 16-row blocks, the oracle all 65 rows in
        # one matmul: XLA reduces the d-length f32 dot products in a
        # different order, and the bf16 rounding of p can then land one
        # ulp apart.  Bound: d * 2^-24 * sum|x||w| (f32 reassociation)
        # plus one bf16 ulp (2^-7 relative).
        p_k = np.asarray(p[i], np.float32)
        p_o = np.asarray(p_r, np.float32)
        bound = (2.0 ** -7 * np.abs(p_o)
                 + 96 * 2.0 ** -24 * (np.abs(xf[i]) @ np.abs(wf)))
        assert np.all(np.abs(p_k - p_o) <= bound)


def test_cosine_drift_matches_cosine_similarity():
    """Score-only kernel (attn_in / incremental rescore) is bitwise the
    jitted cosine_similarity."""
    from repro.core.svd_proxy import cosine_similarity
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    x = jax.random.normal(ks[0], (2, 100, 48))
    pc = jax.random.normal(ks[1], (2, 100, 48))
    out = cosine_drift(x, pc, interpret=True, block_n=32)
    expect = jax.jit(cosine_similarity)(x, pc)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_norm_fused_epilogue(dtype):
    """One pass emits raw gathered rows AND rms-normed rows, bitwise
    equal to gather_rows + rms_norm (incl. clip-mode OOB clamping)."""
    from repro.core import selection
    from repro.models import common
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    h = jax.random.normal(ks[0], (2, 40, 64), dtype)
    wt = jax.random.normal(ks[1], (64,), dtype)
    idx = jnp.sort(jax.random.randint(ks[2], (2, 7), 0, 45))  # OOB clamps
    rows, normed = gather_norm(h, idx, wt, 1e-6, interpret=True,
                               block_g=4)
    rows_x = selection.gather_rows(h, idx)
    normed_x = common.rms_norm(rows_x, wt, 1e-6)
    np.testing.assert_array_equal(np.asarray(rows, np.float32),
                                  np.asarray(rows_x, np.float32))
    np.testing.assert_array_equal(np.asarray(normed, np.float32),
                                  np.asarray(normed_x, np.float32))


def test_sparse_attention_batched_grid():
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    q = jax.random.normal(ks[0], (2, 24, 4, 16))
    k = jax.random.normal(ks[1], (2, 160, 2, 16))
    v = jax.random.normal(ks[2], (2, 160, 2, 16))
    qp = jnp.sort(jax.random.randint(ks[3], (2, 24), 0, 160))
    out = sparse_attention(q, k, v, qp, window=32, interpret=True,
                           block_q=8, block_k=32)
    for i in range(2):
        out_ref = ref.sparse_attention_ref(q[i], k[i], v[i], qp[i],
                                           window=32)
        np.testing.assert_allclose(out[i], out_ref, rtol=2e-3, atol=2e-3)


def test_sparse_attention_banded_matches_flash():
    """Banded path (scalar-prefetched kv starts) visits the same kv
    blocks as the XLA banded flash path at matched blocks (agreement to
    ulp-level XLA-fusion noise), and matches the dense oracle."""
    from repro.core import selection
    from repro.core.spa_layer import q_span_bound
    from repro.models.attention import flash_attention
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    n, kq, nb, window, bq, bk = 2048, 128, 8, 64, 32, 64
    q = jax.random.normal(ks[0], (1, kq, 2, 16))
    k = jax.random.normal(ks[1], (1, n, 2, 16))
    v = jax.random.normal(ks[2], (1, n, 2, 16))
    # REAL stratified selection: per-block top-(k/nb) guarantees the
    # q_span bound the banded path relies on (DESIGN.md §4)
    qp = selection.select_stratified(jax.random.uniform(ks[3], (1, n)),
                                     kq, nb)
    span = q_span_bound(n, kq, nb, block_q=bq)
    assert n > span + 2 * window + 2 * bk
    out = sparse_attention(q, k, v, qp, window=window, banded=True,
                           q_span=span, block_q=bq, block_k=bk,
                           interpret=True)
    out_flash = jax.jit(lambda *a: flash_attention(
        a[0], a[1], a[2], q_positions=a[3], window=window, banded=True,
        q_span=span, block_q=bq, block_k=bk))(q, k, v, qp)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_flash),
                               rtol=1e-6, atol=1e-6)
    out_ref = ref.sparse_attention_ref(q[0], k[0], v[0], qp[0],
                                       window=window)
    np.testing.assert_allclose(out[0], out_ref, rtol=2e-3, atol=2e-3)


def test_banded_partial_q_block_matches_oracle():
    """Regression: a partially-padded final q block (sq not a multiple of
    block_q) must keep its kv band anchored at its REAL positions — pad
    sentinels used to pull ``banded_starts``'s min to 0, masking the real
    rows' windows entirely (zero output). Both paths share the helper."""
    from repro.models.attention import flash_attention, reference_attention
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    sq, n, window, bq, bk = 33, 512, 24, 32, 32
    q = jax.random.normal(ks[0], (1, sq, 4, 16))
    k = jax.random.normal(ks[1], (1, n, 2, 16))
    v = jax.random.normal(ks[2], (1, n, 2, 16))
    out_ref = reference_attention(q, k, v, window=window)
    out_flash = flash_attention(q, k, v, window=window, banded=True,
                                block_q=bq, block_k=bk)
    qp = jnp.broadcast_to(jnp.arange(sq)[None], (1, sq))
    out_pallas = sparse_attention(q, k, v, qp, window=window, banded=True,
                                  q_span=bq, block_q=bq, block_k=bk,
                                  interpret=True)
    np.testing.assert_allclose(out_flash, out_ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(out_pallas, out_ref, rtol=2e-5, atol=2e-5)


def test_scatter_update_multi_buffers():
    """K/V/H/proxy-style multi-buffer commit in one aliased call: mixed
    dtypes/widths, sorted runs sharing a tile slab, and sentinel (>= N)
    drops."""
    rng = np.random.default_rng(3)
    ks = jax.random.split(jax.random.PRNGKey(10), 3)
    b, n, kk = 2, 64, 16
    c_f = jax.random.normal(ks[0], (b, n, 2, 8), jnp.bfloat16)
    c_i = jnp.asarray(rng.integers(-100, 100, (b, n, 12)), jnp.int8)
    c_s = jax.random.normal(ks[1], (b, n), jnp.float16)
    # sorted with a contiguous run (one slab load/store) + sentinel pads
    idx = jnp.asarray(np.sort(np.stack([
        np.r_[rng.choice(40, 10, replace=False), 50, 51, 52, 53, n, n],
        np.r_[rng.choice(n, 14, replace=False), n, n]]), axis=-1),
        jnp.int32)
    r_f = jax.random.normal(ks[2], (b, kk, 2, 8), jnp.float32)
    r_i = jnp.asarray(rng.integers(-100, 100, (b, kk, 12)), jnp.int8)
    r_s = jax.random.normal(ks[0], (b, kk), jnp.float32)
    outs = scatter_update_multi([c_f, c_i, c_s], idx, [r_f, r_i, r_s],
                                interpret=True, block_k=8)
    for c, r, o in zip([c_f, c_i, c_s], [r_f, r_i, r_s], outs):
        expect = jax.vmap(lambda ci, ii, ri: ci.at[ii].set(
            ri.astype(ci.dtype), mode="drop"))(c, idx, r)
        assert o.dtype == c.dtype and o.shape == c.shape
        np.testing.assert_array_equal(np.asarray(o, np.float32),
                                      np.asarray(expect, np.float32))


def test_scatter_update_unsorted_endpoint_collision():
    """Regression: an unsorted chunk whose endpoints differ by exactly
    its length-1 (e.g. [5,20,7,9,2,3,4,12]) leaves and re-enters tile
    slabs — every row has to land at its own index, not at first + t."""
    cache = jnp.zeros((1, 32, 8))
    idx = jnp.asarray([[5, 20, 7, 9, 2, 3, 4, 12]], jnp.int32)
    rows = jax.random.normal(jax.random.PRNGKey(12), (1, 8, 8))
    (out,) = scatter_update_multi([cache], idx, [rows], interpret=True)
    expect = ref.scatter_update_ref(cache[0], idx[0], rows[0])
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(expect))


def test_scatter_update_donation_contract():
    """ops.scatter_update must NOT donate (callers re-read the cache);
    the donating form deletes its input — reading it afterwards raises."""
    cache = jnp.zeros((32, 8))
    idx = jnp.arange(4, dtype=jnp.int32)
    rows = jnp.ones((4, 8))
    out = ops.scatter_update(cache, idx, rows)
    # non-donating: the input stays readable and unchanged
    np.testing.assert_array_equal(np.asarray(cache), 0.0)
    np.testing.assert_array_equal(np.asarray(out[:4]), 1.0)
    donated = jnp.zeros((32, 8))
    out2 = ops.scatter_update_donated(donated, idx, rows)
    np.testing.assert_array_equal(np.asarray(out2[:4]), 1.0)
    assert donated.is_deleted()
    with pytest.raises(RuntimeError, match="deleted"):
        _ = donated + 1


@pytest.mark.parametrize("n,d", [(64, 32), (300, 64), (128, 8)])
def test_rglru_scan(n, d):
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (n, d)))
    b = jax.random.normal(ks[1], (n, d)) * 0.1
    out = rglru_scan(a, b, interpret=True, chunk=32, block_d=32)
    out_ref = ref.rglru_scan_ref(a, b)
    np.testing.assert_allclose(out, out_ref, rtol=1e-4, atol=1e-4)
