"""Adaptive budget allocation (paper Eq. 5) — unit + property tests."""
import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import SPAConfig
from repro.core import budget


def spa(rho_p=0.25, rho_1=0.03, rho_L=0.13, lp=None, schedule="adaptive"):
    return SPAConfig(schedule=schedule, rho_peak=rho_p, rho_first=rho_1,
                     rho_last=rho_L, layer_peak=lp)


def test_peak_at_lp():
    s = spa(lp=24)
    rhos = budget.rho_schedule(s, 32)
    assert np.argmax(rhos) == 23          # 1-indexed l_p = 24
    assert rhos[23] == pytest.approx(0.25)


def test_boundary_values_match_eq5():
    s = spa(lp=24)
    rhos = budget.rho_schedule(s, 32)
    assert rhos[0] == pytest.approx(0.03, rel=1e-6)    # rho_1 at l=1
    assert rhos[31] == pytest.approx(0.13, rel=1e-6)   # rho_L at l=L


def test_uniform_schedule():
    rhos = budget.rho_schedule(spa(schedule="uniform"), 16)
    assert np.allclose(rhos, 0.25)


def test_paper_table6_llada():
    """LLaDA-8B hyperparameters (Appendix C Table 6): avg rho ~16% at
    rho_p=25% (paper Table 4 reports a-bar = 16%)."""
    s = SPAConfig(rho_peak=0.25, rho_first=0.03, rho_last=0.13,
                  layer_peak=24)
    avg = budget.average_rho(s, 32)
    assert 0.10 < avg < 0.20


@given(st.integers(2, 96), st.integers(1, 8))
@settings(max_examples=30, deadline=None)
def test_bucketize_never_underallocates(n_layers, n_buckets):
    s = spa(lp=max(1, int(0.6 * n_layers)))
    ks = budget.k_schedule(s, n_layers, 1024)
    segs = budget.bucketize(ks, n_buckets)
    # contiguous, ordered cover
    assert segs[0][0] == 0 and segs[-1][1] == n_layers
    for (a0, b0, _), (a1, _, _) in zip(segs, segs[1:]):
        assert b0 == a1
    # never under-allocate
    for a, b, kseg in segs:
        assert kseg == max(ks[a:b])
        for l in range(a, b):
            assert kseg >= ks[l]
    assert budget.over_provision_ratio(ks, segs) >= 1.0


@given(st.floats(0.05, 0.9), st.integers(4, 64), st.integers(64, 4096))
@settings(max_examples=30, deadline=None)
def test_k_schedule_bounds(rho_p, n_layers, seq_len):
    s = spa(rho_p=rho_p, rho_1=rho_p / 8, rho_L=rho_p / 2)
    ks = budget.k_schedule(s, n_layers, seq_len)
    # k rounds UP to a multiple of 16 for shardability (never under)
    assert all(1 <= k <= min(seq_len, math.ceil(rho_p * seq_len) + 16)
               for k in ks)
    assert all(k % 16 == 0 or k == seq_len or seq_len < 16 for k in ks)


def test_more_buckets_less_waste():
    s = spa(lp=24)
    ks = budget.k_schedule(s, 32, 4096)
    waste = [budget.over_provision_ratio(ks, budget.bucketize(ks, nb))
             for nb in (1, 2, 4, 8, 16)]
    assert all(w1 >= w2 - 1e-9 for w1, w2 in zip(waste, waste[1:]))
