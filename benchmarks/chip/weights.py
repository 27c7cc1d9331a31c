"""Seeded model weights, made on the device in one jitted call.

The tree has the layout the serving program takes (``embed``,
``final_norm``, optional ``lm_head``, and ``blocks.attn`` stacks with a
leading layer axis), in the dtype the configuration serves
(``param_dtype``).  Each layer draws from its own key,
``fold_in(blocks_key, layer)``, so the same seed always gives the same
weights, whichever program draws them.

Scales: projections N(0, 1/fan_in), embedding N(0, 0.02^2), norm
weights N(0, 0.1^2) (applied as ``1 + w``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

NORM_STD = 0.1
EMBED_STD = 0.02


def seed_key(seed: int) -> jax.Array:
    """The weights' PRNG key for a run seed.  Seeds may exceed 32 bits,
    so they pass through numpy's SeedSequence first."""
    words = np.random.SeedSequence([int(seed), 0]).generate_state(
        2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _layer(cfg: Dict[str, Any], dtype, key) -> Dict[str, Any]:
    d, q = cfg["d_model"], cfg["n_heads"] * cfg["head_dim"]
    kv, ff = cfg["n_kv_heads"] * cfg["head_dim"], cfg["d_ff"]
    k = jax.random.split(key, 9)
    return {
        "norm1": _normal(k[0], (d,), NORM_STD, dtype),
        "wq": _normal(k[1], (d, q), d ** -0.5, dtype),
        "wk": _normal(k[2], (d, kv), d ** -0.5, dtype),
        "wv": _normal(k[3], (d, kv), d ** -0.5, dtype),
        "wo": _normal(k[4], (q, d), q ** -0.5, dtype),
        "norm2": _normal(k[5], (d,), NORM_STD, dtype),
        "ffn": {
            "w_gate": _normal(k[6], (d, ff), d ** -0.5, dtype),
            "w_up": _normal(k[7], (d, ff), d ** -0.5, dtype),
            "w_down": _normal(k[8], (ff, d), ff ** -0.5, dtype),
        },
    }


@functools.partial(jax.jit, static_argnums=(0,))
def _make(frozen_cfg, key):
    cfg = dict(frozen_cfg)
    dtype = jnp.dtype(cfg["param_dtype"])
    d, v = cfg["d_model"], cfg["vocab_size"]
    k_embed, k_norm, k_head, k_blocks = jax.random.split(key, 4)
    layer_keys = jax.vmap(lambda l: jax.random.fold_in(k_blocks, l))(
        jnp.arange(cfg["n_layers"]))
    params = {
        "embed": _normal(k_embed, (v, d), EMBED_STD, dtype),
        "final_norm": _normal(k_norm, (d,), NORM_STD, dtype),
        "blocks": {"attn": jax.vmap(
            functools.partial(_layer, cfg, dtype))(layer_keys)},
    }
    if not cfg["tie_embeddings"]:
        params["lm_head"] = _normal(k_head, (d, v), d ** -0.5, dtype)
    return params


WEIGHT_KEYS = ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
               "vocab_size", "n_layers", "tie_embeddings", "param_dtype")


def make_params(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """All weights of ``cfg`` (a configuration file's dict) for ``seed``."""
    frozen = tuple(sorted((k, cfg[k]) for k in WEIGHT_KEYS))
    return _make(frozen, seed_key(seed))
