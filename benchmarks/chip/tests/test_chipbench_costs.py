"""Work from shapes and the peaks table, on the CPU."""
from __future__ import annotations

import json
import math
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.join(ROOT, "src"))

import cell  # noqa: E402
import costs  # noqa: E402


def _cfg(name):
    with open(os.path.join(CHIP, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["llada-8b-l8", "internlm2-1.8b"])
def test_rho_schedule_is_eq5_as_the_program_has_it(name):
    from repro.configs.base import SPAConfig
    from repro.core import budget
    cfg = _cfg(name)
    mine = costs.rho_schedule(cfg["spa"], cfg["n_layers"])
    theirs = budget.rho_schedule(SPAConfig(**cfg["spa"]), cfg["n_layers"])
    assert mine == pytest.approx(list(theirs), rel=1e-12)
    # required k is never above what the program compiles
    ks = costs.k_exact(cfg, 768)
    assert ks == [max(1, math.ceil(r * 768)) for r in mine]
    assert all(a <= b for a, b in zip(ks, budget.k_schedule(
        SPAConfig(**cfg["spa"]), cfg["n_layers"], 768)))


def test_sparse_attention_counts():
    cfg = _cfg("internlm2-1.8b")           # 16 q heads, 8 kv heads of 128
    flops, nbytes = costs.sparse_attention(cfg, 10, 100)
    assert flops == 4 * 10 * 2048 * 100
    assert nbytes == 2 * (2 * 10 * 2048 + 2 * 100 * 1024)
    # k is capped by the valid rows
    assert costs.sparse_attention(cfg, 500, 100)[0] == 4 * 100 * 2048 * 100


def test_proxy_score_counts():
    cfg = _cfg("llada-8b-l8")
    flops, nbytes = costs.proxy_score(cfg, 768, 4)
    assert flops == 4 * (2 * 768 * 4096 * 128 + 6 * 768 * 128)
    assert nbytes == 2 * 4096 * 128 + 4 * (2 * 2 * 768 * 128 + 4 * 768)


def test_step_flops_parts():
    cfg = _cfg("llada-8b-l8")
    fam = cell.family(cfg)
    d, ff, v = 4096, 12288, 126464
    dense = 2 * (d * 3 * d + d * d + 3 * d * ff)
    ks = costs.k_exact(cfg, 768)
    want = sum(k * dense + 4 * k * d * 768 + 2 * 768 * d * 128 for k in ks)
    want += 2 * 56.0 * d * v
    assert fam.step_flops(cfg, 768, 768, 56.0) == pytest.approx(want)


def test_mean_candidates():
    block = {"gen_len": {"value": 256},
             "scheduler": {"name": "block", "block_len": 32}}
    assert costs.mean_candidates(block) == pytest.approx(16.5)
    conf = {"gen_len": {"value": 16}}
    assert costs.mean_candidates(conf) == pytest.approx(8.5)


def test_roofline_time_names_its_bound():
    pk = costs.peaks("TPU v5 lite")
    assert costs.roofline_time(197e12, 1.0, pk) == (1.0, "compute")
    t, bound = costs.roofline_time(1.0, 819e9 * 2, pk)
    assert (t, bound) == (2.0, "memory")


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        costs.peaks("TPU v9 imaginary")
