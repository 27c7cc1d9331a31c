"""Model families: every configuration names one, the harness reaches
the architecture only through it, the dense family gives what the
harness gave before families (weights, reference, FLOPs, recorded at
the parent commit), and a family added as a file runs a cell."""
from __future__ import annotations

import glob
import hashlib
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path.insert(0, CHIP)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import cell  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import tiny  # noqa: E402

SEED = 2 ** 31 + 7
# Read on the commit before families, from the same code then outside
# them: the weight draw, the reference's hidden rows and gaps, and the
# FLOPs of a step, on the configurations below.
PINNED_WEIGHTS = \
    "e88e49c00813fb2fc3468d074cd58e9376232bd35b6db095a8da6db91cdec7d6"
PINNED_REFERENCE = {   # int8 -> (rows, gaps, best tokens)
    False: ("7963d91f419f12f1293989cdb40b6484fe86e68d7597fad7e735af84006a3acc",
            "ade21d4f205ff2bf6da7461e3b975b1b968d2c5449cf1eb706b46482c5dd3a62",
            [220, 220, 220, 220, 298, 203, 131, 80, 181, 119, 119, 119]),
    True: ("484f7683c4dac39931a992d08bc0690074e6f141411e489cd31780a431e7ce68",
           "21e4aee34b81340ba5d613e05ba6e85ec647766d0ade4158592a600d3e81e277",
           [220, 220, 220, 220, 419, 203, 131, 80, 181, 119, 119, 119]),
}
PINNED_FLOPS = {   # (canvas 768, kv 768, 16.5), (canvas 1024, kv 1000, 8.5)
    "llada-8b-l8-rho1": (2780905340928.0, 3637927477248.0),
    "llada-8b-l8": (442706690048.0, 580218519552.0),
    "internlm2-1.8b": (419810770944.0, 561806966784.0),
}


def _cfg(name):
    with open(os.path.join(CHIP, "configs", f"{name}.json")) as f:
        return json.load(f)


def _dense():
    return cell.family({"name": "probe", "family": "dense"})


def _tiny_dense():
    return _dense().tiny(_cfg("llada-8b-l8-rho1"))


def test_every_configuration_names_its_family():
    files = glob.glob(os.path.join(CHIP, "configs", "*.json"))
    known = {os.path.basename(p)[:-3]
             for p in glob.glob(os.path.join(CHIP, "families", "*.py"))}
    assert files and "dense" in known
    for path in files:
        with open(path) as f:
            cfg = json.load(f)
        assert cfg["family"] in known, path
        fam = cell.family(cfg)
        for fn in ("model_config", "make_params", "reference_layer",
                   "step_flops", "tiny"):
            assert callable(getattr(fam, fn)), (path, fn)


@pytest.mark.parametrize("family", [None, "no-such-family"])
def test_missing_or_unknown_family_stops_the_run(tmp_path, family):
    root = tiny.make_root(str(tmp_path / "root"))
    path = os.path.join(root, "benchmarks/chip/configs/llada-8b-l8-rho1.json")
    with open(path) as f:
        cfg = json.load(f)
    if family is None:
        del cfg["family"]
    else:
        cfg["family"] = family
    with open(path, "w") as f:
        json.dump(cfg, f)
    args = run.parse(["--workload", tiny.PLUG_IN_LIKE, "--seed", "1",
                      "--seconds", "1"])
    with pytest.raises(SystemExit) as exc:
        run.execute(args, root=root,
                    bench_dir=os.path.join(root, "benchmarks", "chip"),
                    devices=[])
    msg = str(exc.value)
    assert "known families" in msg and "'dense'" in msg and \
        "'tiny_moe'" in msg, msg


def _tree_digest(params):
    import jax
    h = hashlib.sha256()
    for path, leaf in sorted(jax.tree_util.tree_leaves_with_path(params),
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _digest(a):
    return hashlib.sha256(np.asarray(a, np.float32).tobytes()).hexdigest()


def test_dense_weights_as_before_families():
    assert _tree_digest(_dense().make_params(_tiny_dense(), SEED)) == \
        PINNED_WEIGHTS


@pytest.mark.parametrize("int8", [False, True], ids=["float32", "int8"])
def test_dense_reference_as_before_families(int8):
    fam, cfg = _dense(), _tiny_dense()
    params = fam.make_params(cfg, SEED)
    rng = np.random.default_rng(5)
    c, n, m = 3, 64, 4
    tokens = rng.integers(0, cfg["vocab_size"] - 1, (c, n)).astype(np.int32)
    tokens[:, 40:] = cfg["mask_token_id"]
    kv = np.array([64, 48, 56], np.int32)
    pos = np.sort(rng.choice(np.arange(24, 48), (c, m), replace=True),
                  axis=1).astype(np.int32)
    toks = rng.integers(0, cfg["vocab_size"] - 1, (c * m,))
    rows = reference.hidden_at(fam, cfg, params, tokens, kv, pos,
                               int8=int8, block=2)
    gaps, best = reference.gaps_at(cfg, params,
                                   rows.reshape(-1, rows.shape[-1]), toks,
                                   int8=int8)
    assert (_digest(rows), _digest(gaps), best.tolist()) == \
        PINNED_REFERENCE[int8]


@pytest.mark.parametrize("name", sorted(PINNED_FLOPS))
def test_dense_step_flops_as_before_families(name):
    fam = _dense()
    cfg = _cfg(name)
    assert (fam.step_flops(cfg, 768, 768, 16.5),
            fam.step_flops(cfg, 1024, 1000, 8.5)) == PINNED_FLOPS[name]


# ---- a family added as a file ------------------------------------------------

@pytest.fixture(scope="module")
def jax_cache(tmp_path_factory):
    return str(tmp_path_factory.mktemp("jax_cache"))


def _plug_in_run(tmp_path, jax_cache, monkeypatch, break_reference=False):
    import jax
    root = tiny.make_root(str(tmp_path / "root"))
    chip = os.path.join(root, "benchmarks", "chip")
    if break_reference:
        # the reference routes each token to one expert, not two
        with open(os.path.join(chip, "families", "tiny_moe.py"), "a") as f:
            f.write("\n\n_right = reference_layer\n\n\n"
                    "def reference_layer(cfg, l, h, w, kv_len, int8):\n"
                    "    return _right(dict(cfg, experts_per_token=1), l, h,"
                    " w, kv_len, int8)\n")
    tiny.close_by_finished(monkeypatch)
    args = run.parse(["--workload", tiny.PLUG_IN, "--seed", str(SEED),
                      "--seconds", "4", "--trace", "0"])
    with tiny.compile_cache(jax_cache):
        return run.execute(args, root=root, bench_dir=chip,
                           devices=jax.devices())


def test_family_added_as_a_file_runs_correct(tmp_path, jax_cache,
                                             monkeypatch):
    res = _plug_in_run(tmp_path, jax_cache, monkeypatch)
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["finished"]["value"] >= tiny.FINISHED
    assert res["failed"] == 0 and res["attempted"] >= tiny.FINISHED


def test_family_reference_decides_correct(tmp_path, jax_cache, monkeypatch):
    res = _plug_in_run(tmp_path, jax_cache, monkeypatch,
                       break_reference=True)
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["finished"]["value"] >= tiny.FINISHED
    assert res["checks"]["gap_max"]["value"] > tiny.TINY_GAP_LIMIT
