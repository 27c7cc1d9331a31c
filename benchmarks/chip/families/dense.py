"""The dense family: pre-norm decoder blocks of grouped-query attention
(rotary positions, bidirectional over a row's valid positions) and a
gated SiLU feed-forward, as LLaDA-8B and InternLM2 have them.

A family is what the harness knows of one architecture; a
configuration file names it (``"family": "dense"``) and the harness
reaches it through ``cell.family``.  It gives:

* ``model_config(cfg)`` — the program's ``ModelConfig``;
* ``make_params(cfg, seed)`` — the whole seeded weight tree;
* ``reference_layer(cfg, l, h, w, kv_len, int8)`` — layer ``l`` of the
  float32 reference (``reference.py`` runs the rest);
* ``step_flops(cfg, canvas, kv_len, candidates)`` — the FLOPs one live
  row's serve step requires;
* ``tiny(cfg)`` — the configuration shrunk for the CPU tests.

``attention_weights`` and ``attention`` are its grouped-query attention
half, for a family that keeps this attention and changes the rest.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

import cell
import costs
from reference import HIGHEST, mm_in, rms, rope
from weights import NORM_STD, normal, stacked_params

# configuration-file keys handed to the program's ModelConfig
MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab_size", "tie_embeddings", "act", "rope_theta",
              "norm_eps", "param_dtype", "cache_dtype")
WEIGHT_KEYS = ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
               "vocab_size", "n_layers", "tie_embeddings", "param_dtype")
TINY_WIDTHS = dict(n_layers=8, d_model=128, n_heads=4, n_kv_heads=2,
                   head_dim=32, d_ff=256, vocab_size=512)


def model_config(cfg: Dict[str, Any]):
    """The program's ModelConfig for a configuration file."""
    from repro.configs import get_arch
    from repro.configs.base import SPAConfig
    return dataclasses.replace(
        get_arch(cfg["arch"]), name=cfg["name"],
        mask_token_id=cell.mask_id(cfg), spa=SPAConfig(**cfg["spa"]),
        **{k: cfg[k] for k in MODEL_KEYS})


# ---- weights ---------------------------------------------------------------

def attention_weights(cfg: Dict[str, Any], dtype, k) -> Dict[str, Any]:
    """The attention half of a pre-norm grouped-query block, from keys
    ``k[0]`` to ``k[5]``: ``norm1``, ``wq``, ``wk``, ``wv``, ``wo`` and
    ``norm2``."""
    d, q = cfg["d_model"], cfg["n_heads"] * cfg["head_dim"]
    kv = cfg["n_kv_heads"] * cfg["head_dim"]
    return {
        "norm1": normal(k[0], (d,), NORM_STD, dtype),
        "wq": normal(k[1], (d, q), d ** -0.5, dtype),
        "wk": normal(k[2], (d, kv), d ** -0.5, dtype),
        "wv": normal(k[3], (d, kv), d ** -0.5, dtype),
        "wo": normal(k[4], (q, d), q ** -0.5, dtype),
        "norm2": normal(k[5], (d,), NORM_STD, dtype),
    }


def _layer(cfg: Dict[str, Any], dtype, key) -> Dict[str, Any]:
    d, ff = cfg["d_model"], cfg["d_ff"]
    k = jax.random.split(key, 9)
    return dict(attention_weights(cfg, dtype, k), ffn={
        "w_gate": normal(k[6], (d, ff), d ** -0.5, dtype),
        "w_up": normal(k[7], (d, ff), d ** -0.5, dtype),
        "w_down": normal(k[8], (ff, d), ff ** -0.5, dtype),
    })


def make_params(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """All weights of ``cfg`` for ``seed``, in one jitted call."""
    return stacked_params(_layer, cfg, WEIGHT_KEYS, seed)


# ---- reference ---------------------------------------------------------------

def attention(h, w, kv_len, n_heads: int, n_kv: int, hd: int,
              theta: float, eps: float, int8: bool):
    """``h`` [B, N, d] plus its pre-norm grouped-query attention under
    layer weights ``w`` (``norm1``, ``wq``, ``wk``, ``wv``, ``wo``):
    rotary positions, bidirectional over the first ``kv_len[b]``
    positions of each canvas."""
    b, n, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(n)[None], (b, n))
    x = rms(h, w["norm1"], eps)
    q = rope(mm_in(x, w["wq"], int8).reshape(b, n, n_heads, hd), pos, theta)
    k = rope(mm_in(x, w["wk"], int8).reshape(b, n, n_kv, hd), pos, theta)
    v = mm_in(x, w["wv"], int8).reshape(b, n, n_kv, hd)
    g = n_heads // n_kv
    qg = q.reshape(b, n, n_kv, g, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, precision=HIGHEST) / hd ** 0.5
    valid = jnp.arange(n)[None, :] < kv_len[:, None]
    s = jnp.where(valid[:, None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v, precision=HIGHEST)
    return h + mm_in(o.reshape(b, n, n_heads * hd), w["wo"], int8)


@functools.partial(jax.jit, static_argnums=(0,))
def _reference_layer(shape_cfg, h, w, kv_len):
    n_heads, n_kv, hd, theta, eps, int8 = shape_cfg
    h = attention(h, w, kv_len, n_heads, n_kv, hd, theta, eps, int8)
    y = rms(h, w["norm2"], eps)
    f = w["ffn"]
    act = (jax.nn.silu(mm_in(y, f["w_gate"], int8))
           * mm_in(y, f["w_up"], int8))
    return h + mm_in(act, f["w_down"], int8)


def reference_layer(cfg: Dict[str, Any], l: int, h, w, kv_len, int8: bool):
    """Layer ``l`` on hidden rows ``h`` [B, N, d], given its float32
    weights ``w`` (quantized already where ``int8``): bidirectional
    attention over the first ``kv_len[b]`` positions of each canvas, then
    the gated feed-forward."""
    shape_cfg = (cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"],
                 float(cfg["rope_theta"]), float(cfg["norm_eps"]), int8)
    return _reference_layer(shape_cfg, h, w, kv_len)


# ---- work -----------------------------------------------------------------

def step_flops(cfg: Dict[str, Any], canvas: int, kv_len: int,
               candidates: float) -> float:
    """FLOPs one live row's serve step requires: per layer, the exact
    k(l) rows through QKV, O and the gated MLP, their attention over
    ``kv_len`` keys and the rank-r projection of every valid row; then
    the logits of the candidates the scheduler may commit from."""
    d, ff = cfg["d_model"], cfg["d_ff"]
    q, kv = costs.attention_dims(cfg)
    r = cfg["spa"]["rank"]
    dense = 2.0 * (d * (q + 2 * kv) + q * d + 3 * d * ff)
    total = 0.0
    for k in costs.k_exact(cfg, canvas):
        k = min(k, kv_len)
        total += k * dense + costs.sparse_attention(cfg, k, kv_len)[0]
        total += 2.0 * kv_len * d * r
    return total + 2.0 * candidates * d * cfg["vocab_size"]


def tiny(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``cfg`` at the CPU tests' widths: a [MASK] id inside the smaller
    vocabulary, SPA rank 16 and the layer peak at its default."""
    cfg = dict(cfg, **TINY_WIDTHS)
    if "mask_token_id" in cfg:
        cfg["mask_token_id"] = TINY_WIDTHS["vocab_size"] - 12
    cfg["spa"] = dict(cfg["spa"], rank=16, layer_peak=None)
    return cfg
