"""The traffic generator and the configuration files, on the CPU."""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path.insert(0, CHIP)

import traffic  # noqa: E402

MIXES = ("blockwise-offline", "chat-online", "batch-offline")
SEED = 2 ** 31 + 123


def _mix(name):
    return traffic.load_mix(name)


@pytest.mark.parametrize("name", MIXES)
def test_seed_reproduces_mix(name):
    a = traffic.generate(_mix(name), 1000, SEED, 45)
    b = traffic.generate(_mix(name), 1000, SEED, 45)
    c = traffic.generate(_mix(name), 1000, SEED + 1, 45)
    assert len(a) == len(b) == len(c)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.gen_len, x.arrival) == (y.gen_len, y.arrival)
    assert any(not np.array_equal(x.prompt, z.prompt) for x, z in zip(a, c))
    # every seed offers the same sizes, in an order of its own
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert sorted(r.gen_len for r in a) == sorted(r.gen_len for r in c)
    assert all(r.prompt.max() < 999 and r.prompt.min() >= 0 for r in a)


def test_chat_lengths_follow_the_mix():
    mix = _mix("chat-online")
    reqs = traffic.generate(mix, 92544, SEED, 2000 / mix["rate_per_s"])
    p = np.array([len(r.prompt) for r in reqs])
    g = np.array([r.gen_len for r in reqs])
    assert len(reqs) == 2000
    assert p.min() >= 16 and p.max() <= 384
    assert abs(np.median(p) - 160) <= 2
    # one sigma either side of the median, inside the clipped range
    lo, hi = np.percentile(p, [15.87, 84.13])
    assert abs(lo / (160 * np.exp(-0.7)) - 1) < 0.02
    assert abs(hi / (160 * np.exp(0.7)) - 1) < 0.02
    assert g.min() >= 16 and g.max() <= 128 and np.all(g % 8 == 0)
    assert np.median(g) == 64
    gaps = np.diff(sorted(r.arrival for r in reqs))
    assert abs(gaps.mean() * mix["rate_per_s"] - 1) < 0.02


@pytest.mark.parametrize("name,prompt,gen", [("blockwise-offline", 512, 256),
                                             ("batch-offline", 768, 256)])
def test_closed_mixes_are_fixed(name, prompt, gen):
    reqs = traffic.generate(_mix(name), 1000, SEED, 45)
    assert {len(r.prompt) for r in reqs} == {prompt}
    assert {r.gen_len for r in reqs} == {gen}
    assert {r.arrival for r in reqs} == {0.0}


PUBLISHED = {
    "llada-8b-l8": dict(d_model=4096, n_heads=32, n_kv_heads=32, head_dim=128,
                        d_ff=12288, vocab_size=126464, tie_embeddings=False),
    "internlm2-1.8b": dict(n_layers=24, d_model=2048, n_heads=16,
                           n_kv_heads=8, head_dim=128, d_ff=8192,
                           vocab_size=92544),
}


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_configs_keep_published_widths(name):
    with open(os.path.join(CHIP, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    for key, val in PUBLISHED[name].items():
        assert cfg[key] == val, key
    assert cfg["spa"]["rank"] == 128
    assert cfg["param_dtype"] == "bfloat16"
    sys.path.insert(0, os.path.join(CHIP, "tests"))
    import tiny
    entry = next(c for c in tiny.benchmark()["configs"] if c["name"] == name)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_llada_cut_keeps_relative_peak():
    import costs
    with open(os.path.join(CHIP, "configs", "llada-8b-l8.json")) as f:
        cfg = json.load(f)
    assert cfg["n_layers"] == 8 and cfg["spa"]["layer_peak"] == 6
    rho = costs.rho_schedule(cfg["spa"], 8)
    assert max(rho) == rho[5] == cfg["spa"]["rho_peak"]
    assert abs(sum(rho) / 8 - cfg["published"]["mean_rho_8_layers"]) < 1e-4
