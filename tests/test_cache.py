"""Cache state + int8 quantization tests."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.configs import get_arch, reduced
from repro.core import cache as cache_lib
from repro.core.cache import CachePolicy


@given(st.integers(0, 5), st.floats(0.01, 100.0))
@settings(max_examples=20, deadline=None)
def test_quantize_roundtrip_error(seed, scale):
    rng = np.random.default_rng(seed)
    x = jnp.asarray((rng.standard_normal((8, 32)) * scale)
                    .astype(np.float32))
    q, s = cache_lib.quantize_rows(x)
    back = cache_lib.dequantize_rows(q, s)
    amax = np.abs(np.asarray(x)).max(axis=-1, keepdims=True)
    err = np.abs(np.asarray(back) - np.asarray(x))
    assert (err <= amax / 127.0 + 1e-6).all()
    assert q.dtype == jnp.int8


def test_init_model_cache_shapes():
    cfg = reduced(get_arch("internlm2-1.8b"))
    cache = cache_lib.init_model_cache(cfg, batch=2, n=32)
    assert set(cache) == {"attn"}
    c = cache["attn"]
    assert c["k"].shape == (2, 2, 32, cfg.n_kv_heads, cfg.head_dim)
    assert c["h"].shape == (2, 2, 32, cfg.d_model)
    assert c["proxy"].shape == (2, 2, 32, cfg.spa.rank)


def test_int8_cache_write_read():
    cfg = reduced(get_arch("internlm2-1.8b"), cache_dtype="int8")
    policy = CachePolicy.from_config(cfg)
    c = cache_lib.init_attn_layer_cache(cfg, 2, 16, policy)
    rng = np.random.default_rng(0)
    idx = jnp.asarray([[1, 5, 9], [0, 7, 15]], jnp.int32)
    k_rows = jnp.asarray(rng.standard_normal(
        (2, 3, cfg.n_kv_heads, cfg.head_dim)).astype(np.float32))
    v_rows = k_rows * 2
    c = cache_lib.write_kv(c, idx, k_rows, v_rows, policy)
    kf, vf, ks, vs = cache_lib.read_kv_for_attention(c, policy)
    assert kf.dtype == jnp.int8 and ks is not None
    k_back = cache_lib.dequantize_rows(
        jnp.take(kf[0], idx[0], axis=0), jnp.take(ks[0], idx[0], axis=0))
    np.testing.assert_allclose(k_back, k_rows[0], atol=0.05, rtol=0.05)

    h_rows = jnp.asarray(rng.standard_normal(
        (2, 3, cfg.d_model)).astype(np.float32))
    c = cache_lib.write_h(c, idx, h_rows, policy)
    back = cache_lib.read_h_rows(c, idx, policy, jnp.float32)
    np.testing.assert_allclose(back, h_rows, atol=0.05, rtol=0.05)
    # untouched rows stay zero
    full = cache_lib.read_h_full(c, policy, jnp.float32)
    assert float(jnp.abs(full[0, 2]).max()) == 0.0


def test_fill_from_prefill_matches_write():
    cfg = reduced(get_arch("internlm2-1.8b"), cache_dtype="int8")
    policy = CachePolicy.from_config(cfg)
    rng = np.random.default_rng(1)
    k = jnp.asarray(rng.standard_normal(
        (2, 8, cfg.n_kv_heads, cfg.head_dim)).astype(np.float32))
    h = jnp.asarray(rng.standard_normal((2, 8, cfg.d_model))
                    .astype(np.float32))
    c = cache_lib.fill_from_prefill(cfg, k, k, h, None, policy)
    back = cache_lib.read_h_full(c, policy, jnp.float32)
    np.testing.assert_allclose(back, h, atol=0.05, rtol=0.05)
