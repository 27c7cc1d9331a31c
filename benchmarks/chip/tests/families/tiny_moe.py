"""A test-only model family: the dense family's attention with a
mixture-of-experts feed-forward — a softmax router over
``n_experts`` experts, the top ``experts_per_token`` of them with their
weights renormalised, each a gated SiLU feed-forward of width
``expert_d_ff``.  The program runs it with the capacity factor
``n_experts / experts_per_token``, at which its capacity dispatch
drops no token, so the served tokens can match this reference.

It shows that the harness takes an architecture as a new family file:
``tiny.make_root`` copies it into a tiny root's ``families/`` and adds
a cell whose configuration names it.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Dict

import jax
import jax.numpy as jnp

import cell
import costs
from reference import mm_in, rms
from weights import normal, stacked_params

# the dense family's attention: its weights and its reference block
dense = cell.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "dense.py"),
    "family_dense")

MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "vocab_size", "tie_embeddings", "act", "rope_theta",
              "norm_eps", "param_dtype", "cache_dtype")
WEIGHT_KEYS = ("d_model", "n_heads", "n_kv_heads", "head_dim", "n_experts",
               "expert_d_ff", "vocab_size", "n_layers", "tie_embeddings",
               "param_dtype")


def model_config(cfg: Dict[str, Any]):
    from repro.configs import get_arch
    from repro.configs.base import MoEConfig, SPAConfig
    e, k = cfg["n_experts"], cfg["experts_per_token"]
    return dataclasses.replace(
        get_arch(cfg["arch"]), name=cfg["name"], arch_type="moe", d_ff=0,
        moe=MoEConfig(n_experts=e, top_k=k, d_ff_expert=cfg["expert_d_ff"],
                      capacity_factor=e / k),
        mask_token_id=cell.mask_id(cfg), spa=SPAConfig(**cfg["spa"]),
        **{key: cfg[key] for key in MODEL_KEYS})


def _layer(cfg: Dict[str, Any], dtype, key) -> Dict[str, Any]:
    d, e, ff = cfg["d_model"], cfg["n_experts"], cfg["expert_d_ff"]
    k = jax.random.split(key, 10)
    return dict(dense.attention_weights(cfg, dtype, k), moe={
        "router": normal(k[6], (d, e), d ** -0.5, dtype),
        "w_gate": normal(k[7], (e, d, ff), d ** -0.5, dtype),
        "w_up": normal(k[8], (e, d, ff), d ** -0.5, dtype),
        "w_down": normal(k[9], (e, ff, d), ff ** -0.5, dtype),
    })


def make_params(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    return stacked_params(_layer, cfg, WEIGHT_KEYS, seed)


@functools.partial(jax.jit, static_argnums=(0,))
def _reference_layer(shape_cfg, h, w, kv_len):
    n_heads, n_kv, hd, theta, eps, top_k, int8 = shape_cfg
    h = dense.attention(h, w, kv_len, n_heads, n_kv, hd, theta, eps, int8)
    y = rms(h, w["norm2"], eps)
    m = w["moe"]
    probs = jax.nn.softmax(mm_in(y, m["router"], int8), axis=-1)
    top, idx = jax.lax.top_k(probs, top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    out = jnp.zeros_like(h)
    for e in range(m["router"].shape[-1]):
        gate = jnp.sum(jnp.where(idx == e, top, 0.0), axis=-1)
        act = (jax.nn.silu(mm_in(y, m["w_gate"][e], int8))
               * mm_in(y, m["w_up"][e], int8))
        out = out + gate[..., None] * mm_in(act, m["w_down"][e], int8)
    return h + out


def reference_layer(cfg: Dict[str, Any], l: int, h, w, kv_len, int8: bool):
    shape_cfg = (cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"],
                 float(cfg["rope_theta"]), float(cfg["norm_eps"]),
                 cfg["experts_per_token"], int8)
    return _reference_layer(shape_cfg, h, w, kv_len)


def step_flops(cfg: Dict[str, Any], canvas: int, kv_len: int,
               candidates: float) -> float:
    """As the dense family's, with the chosen experts' feed-forwards and
    the router in place of the dense feed-forward."""
    d = cfg["d_model"]
    q, kv = costs.attention_dims(cfg)
    ffn = cfg["experts_per_token"] * 3 * d * cfg["expert_d_ff"]
    row = 2.0 * (d * (q + 2 * kv) + q * d + ffn + d * cfg["n_experts"])
    total = 0.0
    for k in costs.k_exact(cfg, canvas):
        k = min(k, kv_len)
        total += k * row + costs.sparse_attention(cfg, k, kv_len)[0]
        total += 2.0 * kv_len * d * cfg["spa"]["rank"]
    return total + 2.0 * candidates * d * cfg["vocab_size"]


def tiny(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Its configurations are written at the tests' size already."""
    return cfg
