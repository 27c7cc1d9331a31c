"""Helpers every family's weight draw shares.

A family (``families/<family>.py``) draws the whole tree its
configurations serve, on the device, from the run seed's key, in the
dtype the configuration serves (``param_dtype``).  The tree has the
layout the serving program takes: ``embed``, ``final_norm``, optional
``lm_head``, and the ``blocks.attn`` stack with a leading layer axis.
So the same seed always gives the same weights, whichever program
draws them.

Scales: projections N(0, 1/fan_in), embedding N(0, 0.02^2), norm
weights N(0, 0.1^2) (applied as ``1 + w``).

``stacked_params`` draws a tree whose layers all fit one jitted call;
a family too large for that draws its own, layer by layer.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Sequence

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


def normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _stacked(layer, frozen_cfg, key):
    cfg = dict(frozen_cfg)
    dtype = jnp.dtype(cfg["param_dtype"])
    d, v = cfg["d_model"], cfg["vocab_size"]
    k_embed, k_norm, k_head, k_blocks = jax.random.split(key, 4)
    layer_keys = jax.vmap(lambda l: jax.random.fold_in(k_blocks, l))(
        jnp.arange(cfg["n_layers"]))
    params = {
        "embed": normal(k_embed, (v, d), EMBED_STD, dtype),
        "final_norm": normal(k_norm, (d,), NORM_STD, dtype),
        "blocks": {"attn": jax.vmap(
            functools.partial(layer, cfg, dtype))(layer_keys)},
    }
    if not cfg["tie_embeddings"]:
        params["lm_head"] = normal(k_head, (d, v), d ** -0.5, dtype)
    return params


def stacked_params(layer: Callable, cfg: Dict[str, Any],
                   keys: Sequence[str], seed: int) -> Dict[str, Any]:
    """All weights of ``cfg`` for ``seed``, in one jitted call: each
    layer drawn by ``layer(cfg, dtype, key)`` from its own key,
    ``fold_in(blocks_key, layer)``.  ``keys`` are the configuration keys
    the draw reads."""
    frozen = tuple(sorted((k, cfg[k]) for k in keys))
    return _stacked(layer, frozen, seed_key(seed))
