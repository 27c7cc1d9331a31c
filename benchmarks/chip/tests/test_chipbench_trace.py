"""The trace reduction and the per-layer readers, on a small recorded
trace in the TPU layout (``trace_small.textproto``): two serve steps of
10 ms and one 5 ms admission program, with host spans."""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path.insert(0, CHIP)

import cell  # noqa: E402
import context  # noqa: E402
import costs  # noqa: E402
import xplane  # noqa: E402
from cell import WindowResult  # noqa: E402


@pytest.fixture(scope="module")
def tr():
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, "trace_small.textproto")) as f:
        return xplane.from_profile(ProfileData.from_text_proto(f.read()))


def _ctx(tr, workload="llada-8b-l8.blockwise-offline"):
    with open(os.path.join(CHIP, "configs", "llada-8b-l8.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(CHIP, "traffic", "blockwise-offline.json")) as f:
        mix = json.load(f)
    win = WindowResult(0.0, 1.0, 0, [], [], {})
    return context.Ctx({"name": workload}, cfg, mix, win, 1.0,
                       "TPU v5 lite", trace=tr, family=cell.family(cfg))


def _read(name, ctx):
    return context.reader(os.path.join(CHIP, "metrics"), name)(ctx)


def test_busy_window_and_idle(tr):
    assert tr.window_s == pytest.approx(0.033)
    assert xplane.busy_s(tr) == pytest.approx(0.018)
    gaps = xplane.idle_gaps(tr)
    assert gaps[0] == ("host_sync", pytest.approx(0.007))
    assert gaps[1] == ("engine_iteration", pytest.approx(0.006))
    assert len(gaps) == 4


def test_union_merges_overlaps():
    ev = xplane.Event
    spans = xplane.union([ev("a", 0, 2), ev("b", 1, 2), ev("c", 5, 1)])
    assert spans == [(0, 3), (5, 6)]


def test_step_readers(tr):
    ctx = _ctx(tr)
    assert len(ctx.step_modules()) == 2
    assert _read("step_ms", ctx) == pytest.approx(10.0)
    assert _read("prefill_share", ctx) == pytest.approx(100 * 4 / 18)
    assert _read("cache_pass_share", ctx) == pytest.approx(100 * 4 / 20)
    assert _read("idle_share.offline", ctx) == pytest.approx(
        100 * (1 - 18 / 33))


def test_work_readers_follow_the_costs(tr):
    ctx = _ctx(tr)
    cfg, mix, pk = ctx.cfg, ctx.mix, costs.peaks("TPU v5 lite")
    rows, kv = mix["max_batch"], 768
    need = sum(costs.roofline_time(*costs.sparse_attention(cfg, k, kv),
                                   pk)[0]
               for k in costs.k_exact(cfg, 768)) * rows * 2
    assert _read("sparse_attention_roofline", ctx) == pytest.approx(
        100 * need / 0.004)
    one = costs.roofline_time(*costs.proxy_score(cfg, kv, rows), pk)[0]
    assert _read("proxy_score_roofline", ctx) == pytest.approx(
        100 * 2 * 8 * one / 0.002)
    flops = 2 * rows * ctx.family.step_flops(
        cfg, 768, kv, costs.mean_candidates(mix))
    assert _read("mfu", ctx) == pytest.approx(
        100 * flops / (0.033 * pk["bf16_flops"]))


def test_readers_find_nothing_without_their_kernels(tr):
    ctx = _ctx(tr)
    empty = xplane.Trace([[xplane.Event("x", 0, 1)]], [[]], [], (0, 1))
    ctx.trace = empty
    for name in ("step_ms", "mfu", "sparse_attention_roofline",
                 "proxy_score_roofline", "cache_pass_share"):
        assert _read(name, ctx) is None, name


def test_short_names_and_kernel_signatures(tr):
    import kernels
    names = {xplane.short(e.name) for e in tr.ops[0]}
    assert "%closed_call.171 bf16[4,192,4096] custom-call" in names
    assert "%closed_call.138 (f32[4,768,1], bf16[4,768,128]) custom-call" \
        in names
    ops = tr.ops[0]
    for pattern, n in ((kernels.SPARSE_ATTENTION, 2),
                       (kernels.PROXY_SCORE_PAGED, 2),
                       (kernels.GATHER_PAGES, 2), (kernels.SCATTER_PAGES, 2),
                       (kernels.SCATTER_ROWS_PAGED, 0)):
        assert len(xplane.matching(ops, pattern)) == n, pattern
