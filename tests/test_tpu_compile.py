"""Every ``PallasBackend`` kernel compiles for a TPU v5e at the widths of
internlm2-1.8b (d=2048, r=128, 16 heads / 8 kv heads of 128, a 4-row
batch on a 1024-token canvas, k=256 selected rows, 16-row pages).

The TPU compiler runs here against a described (not attached) v5e, so
these tests catch what interpret mode cannot: block shapes off the
(8, 128) tiling, memory-space misuse, VMEM overflow.  The topology is
described inside a module fixture, never at import time: only one
process may load the TPU library, and the suite runs under several
workers that all import this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import proxy_score as ps
from repro.kernels import scatter_update as sc
from repro.kernels import sparse_attention as sa

D, R, H, KVH, HD = 2048, 128, 16, 8, 128
B, N, K, PAGE, LAYERS = 4, 1024, 256, 16, 24
N_LOG = N // PAGE
POOL = 1 + B * N_LOG                 # zero page + every row's pages
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _attention(q, k, v, qpos, kv_len):
    return sa.sparse_attention(q, k, v, qpos, kv_len=kv_len)


def _attention_int8(q, k, v, ks, vs, qpos, kv_len):
    return sa.sparse_attention(q, k, v, qpos, k_scale=ks, v_scale=vs,
                               kv_len=kv_len)


def _attention_banded(q, k, v, qpos):
    return sa.sparse_attention(q, k, v, qpos, window=1024, banded=True,
                               q_span=4096)


def _commit(k, v, h, proxy, idx, rk, rv, rh, rp):
    return sc.scatter_update_multi([k, v, h, proxy], idx,
                                   [rk, rv, rh, rp])


# name -> (function, [(shape, dtype), ...]) at the serving widths
KERNELS = {
    "proxy_score": (
        ps.proxy_score,
        [((B, N, D), BF16), ((D, R), F32), ((B, N, R), BF16)]),
    "cosine_drift": (
        ps.cosine_drift, [((B, N, R), F32), ((B, N, R), BF16)]),
    "proxy_score_paged": (
        ps.proxy_score_paged,
        [((B, N, D), BF16), ((D, R), F32), ((POOL, PAGE, R), BF16),
         ((B, N_LOG), I32)]),
    "cosine_drift_paged": (
        ps.cosine_drift_paged,
        [((B, N, R), F32), ((POOL, PAGE, R), BF16), ((B, N_LOG), I32)]),
    "gather_norm": (
        lambda h, idx, w: ps.gather_norm(h, idx, w),
        [((B, N, D), BF16), ((B, K), I32), ((D,), BF16)]),
    "sparse_attention": (
        _attention,
        [((B, K, H, HD), BF16), ((B, N, KVH, HD), BF16),
         ((B, N, KVH, HD), BF16), ((B, K), I32), ((B,), I32)]),
    "sparse_attention_int8": (
        _attention_int8,
        [((B, K, H, HD), BF16), ((B, N, KVH, HD), jnp.int8),
         ((B, N, KVH, HD), jnp.int8), ((B, N, KVH), jnp.float16),
         ((B, N, KVH), jnp.float16), ((B, K), I32), ((B,), I32)]),
    "sparse_attention_banded": (
        _attention_banded,
        [((1, 2048, H, HD), BF16), ((1, 16384, KVH, HD), BF16),
         ((1, 16384, KVH, HD), BF16), ((1, 2048), I32)]),
    "scatter_update_multi": (
        _commit,
        [((B, N, KVH, HD), BF16), ((B, N, KVH, HD), BF16),
         ((B, N, D), BF16), ((B, N, R), BF16), ((B, K), I32),
         ((B, K, KVH, HD), BF16), ((B, K, KVH, HD), BF16),
         ((B, K, D), F32), ((B, K, R), F32)]),
    "scatter_update": (
        sc.scatter_update, [((N, D), F32), ((K,), I32), ((K, D), F32)]),
    "gather_pages": (
        sc.gather_pages,
        [((LAYERS, POOL, PAGE, KVH, HD), BF16), ((B, N_LOG), I32)]),
    "gather_pages_h": (
        sc.gather_pages,
        [((LAYERS, POOL, PAGE, D), BF16), ((B, N_LOG), I32)]),
    "scatter_pages": (
        sc.scatter_pages,
        [((LAYERS, POOL, PAGE, D), BF16), ((B, N_LOG), I32),
         ((LAYERS, B, N, D), BF16)]),
    "scatter_rows_paged": (
        sc.scatter_rows_paged,
        [((POOL, PAGE, R), BF16), ((B, N_LOG), I32), ((B, K), I32),
         ((B, K, R), F32)]),
}


# the ``name`` each kernel's pallas_call passes: its public function's
KERNEL_NAMES = {k: k for k in KERNELS}
KERNEL_NAMES.update({"sparse_attention_int8": "sparse_attention",
                     "sparse_attention_banded": "sparse_attention",
                     "gather_pages_h": "gather_pages"})


@pytest.fixture(scope="module")
def compiled_text(one_chip):
    """The compiled v5e text of a kernel of ``KERNELS``, compiled once
    for every test of this file."""
    texts = {}

    def text(name):
        if name not in texts:
            fn, specs = KERNELS[name]
            args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                    for s, d in specs]
            texts[name] = jax.jit(fn).lower(*args).compile().as_text()
        return texts[name]
    return text


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(compiled_text, name):
    assert "tpu_custom_call" in compiled_text(name)


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_carries_its_name_on_v5e(compiled_text, name):
    """The kernel's custom call is named after it, and its op path ends
    in it, so a device trace finds it by name and not by its shapes."""
    kname = KERNEL_NAMES[name]
    calls = [ln for ln in compiled_text(name).splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls
    for ln in calls:
        assert re.match(rf"\s*(ROOT )?%{kname}(\.\d+)? = ", ln), ln[:200]
        assert re.search(rf'op_name="[^"]*/{kname}/pallas_call"', ln), \
            ln[:200]
