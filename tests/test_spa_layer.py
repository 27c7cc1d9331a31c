"""SPA-Cache block semantics (Algorithm 1) — exactness + update tests."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jax_core

from repro.configs import get_arch, reduced
from repro.configs.base import SPAConfig
from repro.core import spa_layer
from repro.core.cache import CachePolicy
from repro.dlm import decoding
from repro.models import transformer


def setup(identifier="singular", rho=1.0, arch="internlm2-1.8b",
          schedule="uniform", cache_dtype="float32", n=24):
    cfg = reduced(get_arch(arch), cache_dtype=cache_dtype)
    cfg = dataclasses.replace(cfg, spa=SPAConfig(
        identifier=identifier, rank=16, schedule=schedule, rho_peak=rho,
        rho_first=min(0.05, rho), rho_last=min(0.1, rho)))
    key = jax.random.PRNGKey(0)
    params = transformer.init_params(cfg, key)
    proxies = spa_layer.build_spa_proxies(params, cfg)
    tokens = jax.random.randint(key, (2, n), 0, cfg.vocab_size - 1)
    _, cache = decoding.prefill(params, cfg, {"tokens": tokens}, proxies)
    h0 = transformer.embed_inputs(params, cfg, {"tokens": tokens})
    return cfg, params, proxies, cache, h0


@pytest.mark.parametrize("identifier", ["singular", "value", "query",
                                        "key", "attn_in"])
def test_rho_one_equals_dense(identifier):
    """With full budget every row is refreshed -> must equal the vanilla
    forward exactly (core soundness invariant)."""
    cfg, params, proxies, cache, h0 = setup(identifier=identifier)
    h_spa, _, _ = spa_layer.spa_forward(params, cfg, cache, h0, proxies)
    h_dense, _, _ = transformer.forward_hidden(params, cfg, h0)
    np.testing.assert_allclose(np.asarray(h_spa), np.asarray(h_dense),
                               rtol=1e-4, atol=1e-4)


def test_partial_rho_bounded_divergence():
    """At rho<1 with UNCHANGED inputs, the step is a no-op approximation:
    outputs equal the cached states (selected rows recompute to the same
    values)."""
    cfg, params, proxies, cache, h0 = setup(rho=0.3)
    h_spa, new_cache, _ = spa_layer.spa_forward(params, cfg, cache, h0,
                                                proxies)
    h_dense, _, _ = transformer.forward_hidden(params, cfg, h0)
    np.testing.assert_allclose(np.asarray(h_spa), np.asarray(h_dense),
                               rtol=1e-3, atol=1e-3)


def test_cache_untouched_rows_preserved():
    cfg, params, proxies, cache, h0 = setup(rho=0.25)
    # Perturb one token's embedding strongly
    h0 = h0.at[:, 3].add(5.0)
    _, new_cache, _ = spa_layer.spa_forward(params, cfg, cache, h0,
                                            proxies)
    old_k = np.asarray(cache["attn"]["k"])
    new_k = np.asarray(new_cache["attn"]["k"])
    # at most k rows per layer changed
    n = old_k.shape[2]
    changed = (np.abs(new_k - old_k).sum(axis=(3, 4)) > 0)  # [L,B,N]
    from repro.core import budget
    ks = budget.k_schedule(cfg.spa, cfg.n_layers, n)
    for l in range(changed.shape[0]):
        assert changed[l].sum(axis=-1).max() <= ks[l]


def test_drifted_token_gets_selected():
    cfg, params, proxies, cache, h0 = setup(rho=0.2)
    h0p = h0.at[:, 5].add(10.0)   # strong drift at position 5
    from repro.core import identifiers
    x = jax.vmap(lambda hh: hh)(h0p)
    # run one spa block manually and check row 5 was refreshed in layer 0
    _, new_cache, _ = spa_layer.spa_forward(params, cfg, cache, h0p,
                                            proxies)
    old_k = np.asarray(cache["attn"]["k"][0])
    new_k = np.asarray(new_cache["attn"]["k"][0])
    assert np.abs(new_k[:, 5] - old_k[:, 5]).sum() > 0


def test_int8_cache_close_to_fp():
    cfg, params, proxies, cache, h0 = setup(rho=1.0)
    cfg8, params8, proxies8, cache8, h08 = setup(rho=1.0,
                                                 cache_dtype="int8")
    h_fp, _, _ = spa_layer.spa_forward(params, cfg, cache, h0, proxies)
    h_8, _, _ = spa_layer.spa_forward(params8, cfg8, cache8, h08,
                                      proxies8)
    # same params (same seed) -> int8 cache path stays close
    err = np.abs(np.asarray(h_fp) - np.asarray(h_8)).mean()
    scale = np.abs(np.asarray(h_fp)).mean()
    assert err < 0.1 * scale


def test_attn_out_identifier_runs():
    cfg, params, proxies, cache, h0 = setup(identifier="attn_out",
                                            rho=0.5)
    h, new_cache, _ = spa_layer.spa_forward(params, cfg, cache, h0,
                                            proxies)
    assert not bool(jnp.isnan(h).any())


def _setup_8_layers():
    cfg = reduced(get_arch("internlm2-1.8b"), n_layers=8)
    cfg = dataclasses.replace(cfg, spa=SPAConfig(
        identifier="singular", rank=16, schedule="uniform",
        rho_peak=0.4))
    key = jax.random.PRNGKey(0)
    params = transformer.init_params(cfg, key)
    proxies = spa_layer.build_spa_proxies(params, cfg)
    tokens = jax.random.randint(key, (2, 24), 0, cfg.vocab_size - 1)
    _, cache = decoding.prefill(params, cfg, {"tokens": tokens}, proxies)
    h0 = transformer.embed_inputs(params, cfg, {"tokens": tokens})
    return cfg, params, proxies, cache, h0.at[:, 2].add(1.0)


def _fixed_segments(monkeypatch, bounds):
    """Make ``budget.bucketize`` return the segments ``bounds`` gives."""
    if bounds is not None:
        monkeypatch.setattr(
            spa_layer.budget, "bucketize",
            lambda ks, n_buckets: [(a, b, max(ks[a:b])) for a, b in bounds])


SEGMENTATIONS = {
    "bucketize": None,
    "one": [(0, 8)],
    "cell": [(0, 3)] + [(l, l + 1) for l in range(3, 8)],
    "offset": [(0, 2), (2, 5), (5, 8)],
}


@pytest.mark.parametrize("bounds", list(SEGMENTATIONS.values()),
                         ids=list(SEGMENTATIONS))
def test_bucketed_scan_matches_unrolled(monkeypatch, bounds):
    """8-layer homogeneous model: the bucketed lax.scan serve path must
    match the exact unrolled path up to bucket over-provisioning (which
    only ever refreshes MORE rows, so we compare at uniform rho where
    buckets are exact).  Segments that start past layer 0 check that
    each scan reads and writes its layers at their absolute index."""
    _fixed_segments(monkeypatch, bounds)
    cfg, params, proxies, cache, h0 = _setup_8_layers()
    cfg_scan = dataclasses.replace(cfg, scan_layers=True)
    cfg_unroll = dataclasses.replace(cfg, scan_layers=False)
    h_scan, cache_s, _ = spa_layer.spa_forward(params, cfg_scan, cache,
                                               h0, proxies)
    h_unroll, cache_u, _ = spa_layer.spa_forward(params, cfg_unroll,
                                                 cache, h0, proxies)
    np.testing.assert_allclose(np.asarray(h_scan), np.asarray(h_unroll),
                               rtol=1e-4, atol=1e-4)
    for name in ("k", "v", "h", "proxy"):
        np.testing.assert_allclose(
            np.asarray(cache_s["attn"][name]),
            np.asarray(cache_u["attn"][name]), rtol=1e-4, atol=1e-4)


def _in(v, stacks):
    return isinstance(v, jax_core.Var) and v in stacks


def _walk(jaxpr, stacks, found):
    """Record (primitive, operand-is-a-stack, output shapes) of every
    equation, following ``stacks`` (vars holding a whole weight or proxy
    stack) into sub-jaxprs whose inputs line up with the equation's."""
    for eqn in jaxpr.eqns:
        found.append((eqn.primitive.name,
                      any(_in(v, stacks) for v in eqn.invars),
                      [tuple(v.aval.shape) for v in eqn.outvars]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            ins = eqn.invars[len(eqn.invars) - len(sub.invars):]
            inner = {s for s, v in zip(sub.invars, ins) if _in(v, stacks)}
            _walk(sub, inner, found)


def test_bucketed_scan_copies_no_stack(monkeypatch):
    """No segment slices a weight or proxy stack (a slice feeding the
    scan's ``while`` is a copy every step) and no concatenate rebuilds a
    cache buffer: each scan indexes the full stacks by layer."""
    _fixed_segments(monkeypatch, SEGMENTATIONS["offset"])
    cfg, params, proxies, cache, h0 = _setup_8_layers()
    cfg = dataclasses.replace(cfg, scan_layers=True)
    closed = jax.make_jaxpr(
        lambda p, px, c, h: spa_layer.spa_forward(p, cfg, c, h, px))(
            params, proxies, cache, h0)
    # make_jaxpr's inputs are the leaves of (params, proxies, cache, h0)
    # in order; the stacks are params["blocks"] and every proxy leaf.
    paths = [path for path, _ in
             jax.tree_util.tree_flatten_with_path((params, proxies))[0]]
    is_stack = [path[0].idx == 1 or path[1].key == "blocks"
                for path in paths]
    stacks = {v for v, st in zip(closed.jaxpr.invars, is_stack) if st}
    assert len(stacks) == (len(jax.tree.leaves(params["blocks"]))
                           + len(jax.tree.leaves(proxies)))
    found = []
    _walk(closed.jaxpr, stacks, found)
    assert any(prim == "scan" for prim, _, _ in found)
    assert not [f for f in found if f[0] == "slice" and f[1]]
    cache_shapes = {tuple(x.shape) for x in jax.tree.leaves(cache)}
    assert not [f for f in found if f[0] == "concatenate"
                and cache_shapes & set(f[2])]
