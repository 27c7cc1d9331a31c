"""Peaks of the chip, and the work of the shared kernels, from shapes
alone.  The work of a whole serve step depends on the architecture and
is its family's (``families/<family>.py``, ``step_flops``).

Every count here is of REQUIRED work: the exact per-layer update count
``k(l) = ceil(rho(l) * canvas)`` of the paper's Eq. (5) (not the
16-row rounding or the layer buckets the program compiles), and only
the ``kv_len`` valid positions of each row.  So no implementation can
need less, and a share computed from it cannot pass 100% unless the
time is short of the work.

Dtypes are those the program stores: bf16 weights, activations and
cache pages (the cache's ``float32`` setting means "not int8"; its
storage follows the weights), float32 scores.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

# device_kind -> peaks.  Google Cloud documentation, "TPU v5e": 197
# TFLOP/s bf16, 819 GB/s HBM, per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes": 819e9},
}
BF16, F32 = 2, 4


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def rho_schedule(spa: Dict[str, Any], n_layers: int) -> List[float]:
    """Eq. (5): the piecewise-Gaussian update ratio of each layer."""
    if spa["schedule"] == "uniform" or n_layers == 1:
        return [spa["rho_peak"]] * n_layers
    lp = spa["layer_peak"] or max(1, math.ceil(0.6 * n_layers))
    lp = min(lp, n_layers)
    rp = spa["rho_peak"]
    r1, rl = min(spa["rho_first"], rp), min(spa["rho_last"], rp)
    out = []
    for l in range(1, n_layers + 1):
        if l <= lp:
            t, end = (l - lp) / max(lp - 1, 1), r1
        else:
            t, end = (l - lp) / max(n_layers - lp, 1), rl
        out.append(rp * math.exp(math.log(max(end, 1e-9) / rp) * t * t))
    return out


def k_exact(cfg: Dict[str, Any], canvas: int) -> List[int]:
    return [max(1, math.ceil(r * canvas))
            for r in rho_schedule(cfg["spa"], cfg["n_layers"])]


def attention_dims(cfg: Dict[str, Any]) -> Tuple[int, int]:
    """(query width, key/value width) of one layer's attention."""
    hd = cfg["head_dim"]
    return cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd


def sparse_attention(cfg: Dict[str, Any], k: int, kv_len: int
                     ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one row's sparse attention in one layer: ``k``
    selected queries against ``kv_len`` cached keys and values."""
    q, kv = attention_dims(cfg)
    k = min(k, kv_len)
    flops = 4.0 * k * q * kv_len
    nbytes = BF16 * (2 * k * q + 2 * kv_len * kv)
    return flops, nbytes


def proxy_score(cfg: Dict[str, Any], kv_len: int, rows: int
                ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one paged identification call (one layer) over
    ``rows`` rows: project ``kv_len`` positions of each to rank r,
    cosine against the cached proxy pages, write scores and the new
    proxies.  The layer input it projects is not counted: the previous
    layer leaves it on chip (the trace shows it in VMEM), so the HBM
    traffic the call needs is the cached pages, the projection matrix
    and what it writes."""
    d, r = cfg["d_model"], cfg["spa"]["rank"]
    flops = rows * (2.0 * kv_len * d * r + 6.0 * kv_len * r)
    nbytes = BF16 * d * r + rows * (BF16 * 2 * kv_len * r + F32 * kv_len)
    return flops, nbytes


# the open positions the program's confidence schedulers choose from:
# the first ``DecodeSettings.n_candidates`` of each row
CANDIDATE_SET = 64


def mean_candidates(mix: Dict[str, Any]) -> float:
    """Candidate rows a step requires, averaged over a request's life
    with one commit per step: the open positions the mix's scheduler
    may commit from — those of the current block for ``block``, else
    the open positions of the candidate set."""
    g = mix["gen_len"]["value"]
    sched = mix.get("scheduler") or {}
    if sched.get("name") == "block":
        blk = sched["block_len"]
        return sum(blk - done % blk for done in range(g)) / g
    return sum(min(CANDIDATE_SET, g - done) for done in range(g)) / g


def roofline_time(flops: float, nbytes: float, pk: Dict[str, float]
                  ) -> Tuple[float, str]:
    """The least time the chip needs, and which term bounds it."""
    tf, tb = flops / pk["bf16_flops"], nbytes / pk["hbm_bytes"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
