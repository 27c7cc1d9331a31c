"""A run with the timed path broken underneath must read not correct;
the int8 control must read wider gaps than the program; and the cells
whose SPA step never refreshes the [MASK] candidate rows read not
correct, as on the chip."""
from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path.insert(0, CHIP)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import run  # noqa: E402
import tiny  # noqa: E402

WORKLOAD = "llada-8b-l8-rho1.blockwise-offline"
SEED = 2 ** 31 + 11


def _unchanged(orig):
    def step(params, cfg, state, settings, **kw):
        import jax.numpy as jnp
        b = state.tokens.shape[0]
        return state, {"n_committed": jnp.zeros((b,), jnp.int32),
                       "mean_conf": jnp.zeros((), jnp.float32),
                       "row_finite": jnp.ones((b,), bool)}
    return step


def _half_batch(orig):
    def step(params, cfg, state, settings, **kw):
        import jax.numpy as jnp
        new, info = orig(params, cfg, state, settings, **kw)
        b = state.tokens.shape[0]
        skip = jnp.arange(b) >= b // 2
        info = dict(info, n_committed=jnp.where(skip, 0,
                                                info["n_committed"]))
        return new._replace(
            tokens=jnp.where(skip[:, None], state.tokens, new.tokens),
            n_masked=jnp.where(skip, state.n_masked, new.n_masked)), info
    return step


def _altered_token(orig):
    def step(params, cfg, state, settings, **kw):
        import jax.numpy as jnp
        new, info = orig(params, cfg, state, settings, **kw)
        fresh = new.tokens != state.tokens
        bad = jnp.where(fresh, (new.tokens + 1) % (cfg.vocab_size - 1),
                        new.tokens)
        return new._replace(tokens=bad), info
    return step


def _half_keys(orig):
    """Sparse attention over the first half of each row's keys only."""
    def attention(self, q, k, v, **kw):
        if kw.get("kv_len") is not None:
            kw["kv_len"] = kw["kv_len"] // 2
        return orig(self, q, k, v, **kw)
    return attention


def _break(monkeypatch, fault):
    from repro.dlm import decoding
    from repro.kernels.backend import PallasBackend
    if fault is _half_keys:
        monkeypatch.setattr(PallasBackend, "attention",
                            fault(PallasBackend.attention))
    else:
        monkeypatch.setattr(decoding, "serve_step",
                            fault(decoding.serve_step))


def _run(tmp_path, workload, seconds=3):
    import jax
    root = tiny.make_root(str(tmp_path / "root"))
    args = run.parse(["--workload", workload, "--seed", str(SEED),
                      "--seconds", str(seconds), "--trace", "0"])
    with tiny.compile_cache(str(tmp_path / "jax_cache")):
        return run.execute(args, root=root,
                           bench_dir=os.path.join(root, "benchmarks", "chip"),
                           devices=jax.devices())


# The number each fault moves past its limit.  A step that commits
# nothing in some rows stalls them; its window closes after a few steps,
# before any row could finish and be refilled.  The faults that still
# finish requests close the window once tiny.FINISHED have.
MOVES = {_unchanged: ("stalled_requests", 0),
         _half_batch: ("stalled_requests", 0),
         _altered_token: ("gap_max", tiny.TINY_GAP_LIMIT),
         _half_keys: ("gap_max", tiny.TINY_GAP_LIMIT)}
STALLS = (_unchanged, _half_batch)


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered_token,
                                   _half_keys])
def test_broken_step_reads_not_correct(tmp_path, monkeypatch, fault):
    _break(monkeypatch, fault)
    if fault in STALLS:
        tiny.close_by_steps(monkeypatch)
    else:
        tiny.close_by_finished(monkeypatch)
    try:
        res = _run(tmp_path, WORKLOAD)
    except SystemExit as exc:
        # With half the batch stuck, the warm-up's rows finish apart, so
        # the window opens with a swap of the whole batch that no warm-up
        # ran; where the process has not run that swap before, its row
        # updates compile inside the window and the run gives no result.
        assert fault is _half_batch and exc.code == 4, exc
        return
    checks = res["checks"]
    assert res["correct"] is False, checks
    if fault not in STALLS:
        assert checks["finished"]["value"] >= tiny.FINISHED, checks
    name, limit = MOVES[fault]
    assert checks[name]["value"] > limit, checks


@pytest.mark.parametrize("workload", tiny.at_fault())
def test_cell_at_fault_reads_not_correct(tmp_path, monkeypatch, workload):
    """The SPA step leaves the candidate rows as the admission prefill
    left them, so served tokens lie far below the reference's best on
    the canvas their step saw."""
    tiny.close_by_finished(monkeypatch)   # the closed-loop cells
    res = _run(tmp_path, workload, seconds=8)
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["gap_max"]["value"] > tiny.TINY_GAP_LIMIT


def test_int8_control_reads_wider_than_the_program(tmp_path, monkeypatch):
    import control
    tiny.close_by_finished(monkeypatch)
    root = tiny.make_root(str(tmp_path / "root"))
    with tiny.compile_cache(str(tmp_path / "jax_cache")):
        out = control.readings(
            WORKLOAD, SEED, 4.0, root=root,
            bench_dir=os.path.join(root, "benchmarks", "chip"))
    assert out["finished"] >= tiny.FINISHED
    prog, ctl = out["program"], out["control"]
    assert prog["correct"] is True, out
    assert prog["tokens_checked"] == ctl["tokens_checked"] > 0
    assert ctl["gap_max"] > 2 * prog["gap_max"], out
    assert ctl["gap_mean"] > 2 * prog["gap_mean"], out
