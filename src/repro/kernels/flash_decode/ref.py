"""Pure-jnp oracle for the flash-decoding kernel."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["decode_ref"]


def decode_ref(q, k, v, layer, length) -> jax.Array:
    """q [N, G, hd], k/v [L, N, W, hd] -> [N, G, hd] (f32 math): attention
    over slots ``[0, length)`` of layer ``layer``."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    kl = k[layer].astype(jnp.float32)
    vl = v[layer].astype(jnp.float32)
    live = jnp.arange(k.shape[2]) < length
    s = jnp.einsum("ngh,nsh->ngs", q.astype(jnp.float32), kl) * scale
    s = jnp.where(live, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    vl = jnp.where(live[:, None], vl, 0.0)
    return jnp.einsum("ngs,nsh->ngh", p, vl).astype(q.dtype)
