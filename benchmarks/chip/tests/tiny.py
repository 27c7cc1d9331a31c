"""A tiny copy of the benchmark's files for CPU tests: the real metric
readers and model families, cells as in BENCHMARK.json, configurations
(each shrunk by its family's ``tiny``) and mixes shrunk so that a run
fits a test.  The copy also holds the test-only family of
``tests/families/`` and a cell whose configuration names it
(``PLUG_IN``)."""
from __future__ import annotations

import contextlib
import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))

TINY_GAP_LIMIT = 0.1
# a cell of the test-only family (tests/families/tiny_moe.py), served
# under the first live cell's mix and limits
PLUG_IN = "tiny-moe-rho1.blockwise-offline"
PLUG_IN_CONFIG = "benchmarks/chip/tests/configs/tiny-moe-rho1.json"
PLUG_IN_LIKE = "llada-8b-l8-rho1.blockwise-offline"


def _dump(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def benchmark() -> dict:
    """BENCHMARK.json with the cells prepared but not in it
    (``prepared.json``) added back, so their files and readers run in
    the tests too."""
    import control
    return control.benchmark(ROOT, CHIP)


def at_fault() -> list:
    """The prepared cells whose program reads not correct (PERF.md,
    Open questions)."""
    with open(os.path.join(CHIP, "prepared.json")) as f:
        return json.load(f)["at_fault"]


def _with_plug_in(bench: dict) -> dict:
    like = next(w for w in bench["workloads"] if w["name"] == PLUG_IN_LIKE)
    name = PLUG_IN.split(".")[0]
    return dict(
        bench,
        configs=bench["configs"] + [{
            "name": name, "source": "test-only", "file": PLUG_IN_CONFIG,
            "reduced": [], "why": "a family added as a file"}],
        workloads=bench["workloads"] + [dict(
            like, name=PLUG_IN, config=name, why="a family added as a file")])


def make_root(tmp: str, rate_per_s: float = 20.0) -> str:
    """Write a tiny benchmark under ``tmp``; returns its root."""
    import cell
    bench = _with_plug_in(benchmark())
    chip = os.path.join(tmp, "benchmarks", "chip")
    shutil.copytree(os.path.join(CHIP, "metrics"),
                    os.path.join(chip, "metrics"))
    shutil.copytree(os.path.join(CHIP, "families"),
                    os.path.join(chip, "families"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(HERE, "families", "tiny_moe.py"),
                os.path.join(chip, "families"))
    shutil.copy(os.path.join(CHIP, "prepared.json"), chip)
    for entry in bench["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            cfg = json.load(f)
        _dump(os.path.join(tmp, entry["file"]),
              cell.family(cfg, chip).tiny(cfg))
    for cel in bench["workloads"]:
        with open(os.path.join(CHIP, "traffic", cel["traffic"] + ".json")) as f:
            mix = json.load(f)
        mix.update(canvas=64, max_batch=2, pool_pages=1 + 4 * 4,
                   closed_requests=12)
        for key, fixed, lo, hi in (("prompt_len", 24, 8, 32),
                                   ("gen_len", 16, 8, 24)):
            spec = mix[key]
            if spec["dist"] == "fixed":
                spec["value"] = fixed
            else:
                spec.update(median=(lo + hi) // 2, min=lo, max=hi)
        if mix["loop"] == "open":
            mix["rate_per_s"] = rate_per_s
        _dump(os.path.join(chip, "traffic", cel["traffic"] + ".json"), mix)
        limits_of = PLUG_IN_LIKE if cel["name"] == PLUG_IN else cel["name"]
        with open(os.path.join(CHIP, "checks", limits_of + ".json")) as f:
            limits = json.load(f)
        # the cell's gap limits are set for its published widths; at
        # these widths sound runs read 0 and an altered token reads
        # several tenths
        limits["limits"]["gap_max"] = {"max": TINY_GAP_LIMIT}
        limits["limits"]["gap_mean"] = {"max": TINY_GAP_LIMIT / 10}
        _dump(os.path.join(chip, "checks", cel["name"] + ".json"), limits)
    _dump(os.path.join(tmp, "BENCHMARK.json"), bench)
    return tmp


# A closed-loop window closes once this many requests have finished
# (the first batch of two and one of the next), or after
# FINISHED_DEADLINE_S if they never do: what a tiny run checks does not
# then depend on how fast a loaded CPU steps.
FINISHED = 3
FINISHED_DEADLINE_S = 300.0


def close_by_finished(monkeypatch) -> None:
    """Put in place of ``cell.Window`` one that closes by FINISHED
    instead of by the wall clock."""
    import cell

    class Finished(cell.Window):
        def _closes(self, now: float) -> bool:
            done = sum(r.output is not None for r in self.records)
            return (done >= FINISHED
                    or now >= self.t0 + FINISHED_DEADLINE_S)
    monkeypatch.setattr(cell, "Window", Finished)


# A window that must show a stalled row closes after this many engine
# steps: enough for a row to see two steps with no commit, too few for
# any request to finish (each needs 16), so no row is refilled.
STALL_STEPS = 6


def close_by_steps(monkeypatch, steps: int = STALL_STEPS) -> None:
    """Put in place of ``cell.Window`` one that closes after ``steps``
    engine steps instead of by the wall clock."""
    import cell

    class Steps(cell.Window):
        def _closes(self, now: float) -> bool:
            return len(self.step_times) >= steps
    monkeypatch.setattr(cell, "Window", Steps)


@contextlib.contextmanager
def compile_cache(path: str):
    """JAX's persistent compilation cache in ``path``, keeping every
    program, as a run keeps it in its checkout; restored afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    try:
        yield
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()
