"""A tiny copy of the benchmark's files for CPU tests: the real metric
readers, cells as in BENCHMARK.json, configurations and mixes shrunk so
that a run fits a test."""
from __future__ import annotations

import contextlib
import json
import os
import shutil

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))

TINY_GAP_LIMIT = 0.1
TINY_WIDTHS = dict(n_layers=8, d_model=128, n_heads=4, n_kv_heads=2,
                   head_dim=32, d_ff=256, vocab_size=512)


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


def make_root(tmp: str, rate_per_s: float = 20.0) -> str:
    """Write a tiny benchmark under ``tmp``; returns its root."""
    bench = benchmark()
    chip = os.path.join(tmp, "benchmarks", "chip")
    shutil.copytree(os.path.join(CHIP, "metrics"),
                    os.path.join(chip, "metrics"))
    shutil.copy(os.path.join(CHIP, "prepared.json"), chip)
    for entry in bench["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            cfg = json.load(f)
        cfg.update(TINY_WIDTHS)
        if "mask_token_id" in cfg:       # an id inside the vocabulary
            cfg["mask_token_id"] = TINY_WIDTHS["vocab_size"] - 12
        cfg["spa"] = dict(cfg["spa"], rank=16, layer_peak=None)
        _dump(os.path.join(tmp, entry["file"]), cfg)
    for cell in bench["workloads"]:
        with open(os.path.join(CHIP, "traffic", cell["traffic"] + ".json")) as f:
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
        _dump(os.path.join(chip, "traffic", cell["traffic"] + ".json"), mix)
        with open(os.path.join(CHIP, "checks", cell["name"] + ".json")) as f:
            limits = json.load(f)
        # the cell's gap limits are set for its published widths; at
        # these widths sound runs read 0 and an altered token reads
        # several tenths
        limits["limits"]["gap_max"] = {"max": TINY_GAP_LIMIT}
        limits["limits"]["gap_mean"] = {"max": TINY_GAP_LIMIT / 10}
        _dump(os.path.join(chip, "checks", cell["name"] + ".json"), limits)
    _dump(os.path.join(tmp, "BENCHMARK.json"), bench)
    return tmp


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
