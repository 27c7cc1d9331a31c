"""Singular proxy (paper §3.3) — Theorem 3.4 bound checked numerically."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import svd_proxy


def test_full_rank_proxy_exact():
    rng = np.random.default_rng(0)
    w = rng.standard_normal((32, 32)).astype(np.float32)
    proxy, bound = svd_proxy.build_proxy(w, 32)
    h = rng.standard_normal((8, 32)).astype(np.float32)
    v = h @ w
    p = h @ proxy
    # full-rank proxy preserves cosine similarities exactly
    s_v = svd_proxy.cosine_similarity(jnp.asarray(v[:4]), jnp.asarray(v[4:]))
    s_p = svd_proxy.cosine_similarity(jnp.asarray(p[:4]), jnp.asarray(p[4:]))
    np.testing.assert_allclose(s_v, s_p, atol=1e-5)
    assert bound == 0.0


@given(st.integers(4, 24), st.integers(1, 4))
@settings(max_examples=15, deadline=None)
def test_theorem_3_4_bound(r, seed):
    """|S_cos(v1,v2) - S_cos(p1,p2)| <= 2 (s_{r+1}/s_r)^2 for inputs in
    span(V_r) — verified on random matrices with decaying spectra."""
    rng = np.random.default_rng(seed)
    d = 32
    u, _ = np.linalg.qr(rng.standard_normal((d, d)))
    vt, _ = np.linalg.qr(rng.standard_normal((d, d)))
    s = np.exp(-np.arange(d) * 0.4)           # decaying spectrum
    w = (u * s) @ vt.T
    proxy, bound = svd_proxy.build_proxy(w.astype(np.float32), r)

    # inputs restricted to the retained left subspace of W (= span of the
    # top-r right singular vectors of W_paper = W^T)
    u_r = np.linalg.svd(w, full_matrices=False)[0][:, :r]
    h = rng.standard_normal((6, r)) @ u_r.T
    v = h @ w
    p = h @ np.asarray(proxy)
    for i in range(3):
        s_v = float(svd_proxy.cosine_similarity(
            jnp.asarray(v[i]), jnp.asarray(v[i + 3])))
        s_p = float(svd_proxy.cosine_similarity(
            jnp.asarray(p[i]), jnp.asarray(p[i + 3])))
        assert abs(s_v - s_p) <= bound + 1e-4


def test_bound_monotone_in_rank():
    rng = np.random.default_rng(0)
    d = 48
    u, _ = np.linalg.qr(rng.standard_normal((d, d)))
    # super-exponential spectrum: consecutive ratios strictly shrink
    s = np.exp(-0.01 * np.arange(d) ** 2)
    w = (u * s) @ u.T
    bounds = [svd_proxy.build_proxy(w.astype(np.float32), r)[1]
              for r in (4, 16, 40)]
    assert bounds[0] >= bounds[1] >= bounds[2]


def test_proxy_stack_shapes():
    rng = np.random.default_rng(1)
    stack = jnp.asarray(rng.standard_normal((3, 16, 8)).astype(np.float32))
    out = svd_proxy.build_proxy_stack(stack, 4)
    assert out.shape == (3, 16, 4)
