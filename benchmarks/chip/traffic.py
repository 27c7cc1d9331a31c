"""The one traffic generator: reads a mix's parameters, draws requests.

A mix file (``traffic/<mix>.json``) gives the loop (``closed``: the
queue is kept full; ``open``: Poisson arrivals at ``rate_per_s``), the
prompt and generation length distributions, the engine's canvas, batch
and page pool, and the commit scheduler.

Every seed gets the same multiset of sizes and inter-arrival gaps —
drawn at evenly spaced quantiles of their distributions — in an order
of its own, and prompt tokens of its own.  So two seeds offer the same
work, and a seed always offers the same requests.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import statistics
from typing import Any, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Request:
    prompt: np.ndarray      # [P] int32, never the mask token
    gen_len: int
    arrival: float          # seconds after the window opens (open loop)
    row_len: Optional[int] = None   # a larger canvas span to reserve


def load_mix(name: str, root: str = HERE) -> Dict[str, Any]:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        return json.load(f)


def quantile_sizes(spec: Dict[str, Any], n: int) -> List[int]:
    """``n`` sizes at the midpoints of ``n`` equal quantile bins."""
    if spec["dist"] == "fixed":
        return [int(spec["value"])] * n
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    mult = int(spec.get("multiple", 1))
    z = statistics.NormalDist()
    out = []
    for i in range(n):
        x = spec["median"] * math.exp(spec["sigma"] * z.inv_cdf((i + 0.5) / n))
        x = round(x / mult) * mult
        out.append(int(min(max(x, spec["min"]), spec["max"])))
    return out


def quantile_gaps(rate: float, n: int) -> List[float]:
    """Exponential inter-arrival gaps at ``n`` quantile midpoints."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def n_requests(mix: Dict[str, Any], seconds: float) -> int:
    if mix["loop"] == "open":
        return max(1, round(mix["rate_per_s"] * seconds))
    return int(mix["closed_requests"])


def tokens(rng: np.random.Generator, n: int, vocab: int,
           mask_id: int) -> np.ndarray:
    """``n`` prompt tokens: ids of ``[0, vocab - 1)``, with the mask
    token's id, where it lies there, read as the last id instead."""
    out = rng.integers(0, vocab - 1, n).astype(np.int32)
    out[out == mask_id] = vocab - 1
    return out


def generate(mix: Dict[str, Any], vocab: int, seed: int, seconds: float,
             mask_id: Optional[int] = None) -> List[Request]:
    """The requests of one run; no prompt holds the mask token
    (``mask_id``, the last id when not given)."""
    mask_id = vocab - 1 if mask_id is None else mask_id
    n = n_requests(mix, seconds)
    rng = np.random.default_rng([int(seed), 1])
    prompts = rng.permutation(quantile_sizes(mix["prompt_len"], n))
    gens = rng.permutation(quantile_sizes(mix["gen_len"], n))
    if mix["loop"] == "open":
        gaps = rng.permutation(quantile_gaps(mix["rate_per_s"], n))
        arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    else:
        arrivals = np.zeros(n)
    return [Request(tokens(rng, int(p), vocab, mask_id), int(g), float(a))
            for p, g, a in zip(prompts, gens, arrivals)]
