"""Plain float32 reference of the served model, and its int8 control.

A straightforward forward pass — token embedding, the layers, the final
RMSNorm (weights applied as ``1 + w``) and the LM head — in float32 at
``Precision.HIGHEST``, with no cache, no kernels and nothing of the
serving program.  What a layer computes is its family's
(``families/<family>.py``, ``reference_layer``); this module embeds,
blocks the canvases, hands each layer its weights and reads the
logits.  It is computed layer by layer over blocks of canvases, so it
fits beside nothing else on the chip.

``hidden_at`` runs whole canvases and keeps the final hidden rows at
the positions asked for; ``gaps_at`` turns them into logits and reads
how far a given token lies below the best.  ``int8=True`` is the
control, the step below the bf16 the configuration serves: every
matrix product with a weight matrix — the layers' and the LM head's —
computed in int8, weights quantized per output channel (every leaf of
rank 2 or more along its input axis, -2), inputs per row (symmetric,
round to nearest), as an int8 matrix unit takes them, with attention
and the norms left in float32.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w)


def rope(x, positions, theta):
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (np.arange(half, dtype=np.float32) * 2 / hd))
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(freqs)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def quantize_int8(w: jax.Array, in_axis: int) -> jax.Array:
    """Symmetric int8 fake-quantization, one scale per slice along
    every axis but ``in_axis`` (the max over ``in_axis``), returned
    dequantized in float32."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=in_axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def mm_in(x, w, int8: bool):
    """A matrix product; with ``int8`` its input rows are quantized too
    (the weights already are)."""
    return mm(quantize_int8(x, -1) if int8 else x, w)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _gaps(mask_id, int8, h_rows, table_t, toks):
    logits = mm_in(h_rows, table_t, int8).at[:, mask_id].set(-jnp.inf)
    best = jnp.max(logits, axis=-1)
    mine = jnp.take_along_axis(logits, toks[:, None], axis=-1)[:, 0]
    return best - mine, jnp.argmax(logits, axis=-1)


def _layer_weights(params, l: int, int8: bool):
    """Layer ``l``'s weights in float32; with ``int8`` every matrix
    quantized along its input axis."""
    def one(t):
        t = t[l].astype(jnp.float32)
        return quantize_int8(t, -2) if int8 and t.ndim >= 2 else t
    return jax.tree.map(one, params["blocks"]["attn"])


def _embed(params, int8: bool):
    embed = params["embed"].astype(jnp.float32)
    return quantize_int8(embed, 1) if int8 else embed


def hidden_at(family, cfg: Dict[str, Any], params, tokens: np.ndarray,
              kv_len: np.ndarray, pos: np.ndarray, *, int8: bool = False,
              block: int = 8) -> jax.Array:
    """Final-normed hidden rows [C, M, d] at positions ``pos`` [C, M] of
    canvases ``tokens`` [C, N], each valid for its first ``kv_len[c]``
    positions.  Canvases run ``block`` at a time, layer by layer, each
    layer as ``family.reference_layer`` computes it."""
    c = tokens.shape[0]
    pad = (-c) % block
    tokens = np.concatenate([tokens, np.repeat(tokens[:1], pad, 0)])
    kv_len = np.concatenate([kv_len, np.repeat(kv_len[:1], pad)])
    embed = _embed(params, int8)
    hs = [jnp.take(embed, jnp.asarray(tokens[i:i + block]), axis=0)
          for i in range(0, len(tokens), block)]
    del embed
    kvs = [jnp.asarray(kv_len[i:i + block], jnp.int32)
           for i in range(0, len(tokens), block)]
    for l in range(cfg["n_layers"]):
        w = _layer_weights(params, l, int8)
        hs = [family.reference_layer(cfg, l, h, w, kv, int8)
              for h, kv in zip(hs, kvs)]
        del w
    rows = jnp.concatenate(hs)[:c]
    del hs
    rows = jnp.take_along_axis(rows, jnp.asarray(pos)[..., None], axis=1)
    return rms(rows, params["final_norm"].astype(jnp.float32),
                float(cfg["norm_eps"]))


def gaps_at(cfg: Dict[str, Any], params, rows: jax.Array, toks: np.ndarray,
            *, int8: bool = False, chunk: int = 256
            ) -> Tuple[np.ndarray, np.ndarray]:
    """For hidden ``rows`` [R, d] and tokens ``toks`` [R]: how far each
    token's logit lies below the best (the mask token never counts),
    and which token is best."""
    if cfg["tie_embeddings"]:
        table_t = _embed(params, int8).T
    else:
        table_t = params["lm_head"].astype(jnp.float32)
        if int8:
            table_t = quantize_int8(table_t, 0)
    mask_id = cfg.get("mask_token_id") or cfg["vocab_size"] - 1
    r = rows.shape[0]
    pad = (-r) % chunk
    rows = jnp.pad(rows, ((0, pad), (0, 0)))
    toks = np.concatenate([np.asarray(toks, np.int32), np.zeros(pad, np.int32)])
    gap, best = [], []
    for i in range(0, r + pad, chunk):
        g, b = _gaps(mask_id, int8, rows[i:i + chunk], table_t,
                     jnp.asarray(toks[i:i + chunk]))
        gap.append(np.asarray(g))
        best.append(np.asarray(b))
    return np.concatenate(gap)[:r], np.concatenate(best)[:r]
