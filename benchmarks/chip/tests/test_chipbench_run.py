"""Each cell's runner driven in-process on the CPU at a tiny size: the
whole run but the look for a chip, on the cell's own path.  The window
closes once ``tiny.FINISHED`` requests have finished, not after wall
seconds, so a loaded CPU only makes it longer."""
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

CELLS = [w["name"] for w in tiny.benchmark()["workloads"]
         if w["name"] not in tiny.at_fault()]
SEED = 2 ** 31 + 7


def tiny_run(tmp_path, monkeypatch, workload, seconds=4.0):
    import jax
    tiny.close_by_finished(monkeypatch)
    root = tiny.make_root(str(tmp_path / "root"))
    args = run.parse(["--workload", workload, "--seed", str(SEED),
                      "--seconds", str(seconds), "--trace", "0"])
    with tiny.compile_cache(str(tmp_path / "jax_cache")):
        return run.execute(args, root=root,
                           bench_dir=os.path.join(root, "benchmarks", "chip"),
                           devices=jax.devices())


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_correct(tmp_path, monkeypatch, workload):
    res = tiny_run(tmp_path, monkeypatch, workload)
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["finished"]["value"] >= tiny.FINISHED
    assert res["failed"] == 0 and res["attempted"] > 0
    want = {m["name"] for m in tiny.benchmark()["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["checks"]["tokens_checked"]["value"] > 0 \
        if "tokens_checked" in res["checks"] else True


def test_refuses_a_cpu(capsys):
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert exc.value.code == 3
    assert capsys.readouterr().out == ""
