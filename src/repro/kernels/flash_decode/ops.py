"""Model-layout adapter for the flash-decoding kernel.

``flash_decode_cache(q, k_cache, v_cache, at, length)`` takes one decode
position's queries [B, 1, H, hd] and a cache stacked over layers
[*stack, B, KV, W, hd], as a decode scan carries it. It merges the stack
dimensions into one and the batch with the kv heads (reshapes of
contiguous dimensions, so bitcasts and no copies), flattens the layer's
indices ``at`` into that one stack index, and restores the query layout.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from .kernel import flash_decode

__all__ = ["flash_decode_cache"]


def flash_decode_cache(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                       at: Sequence[jax.Array], length: jax.Array, *,
                       interpret: bool = False) -> jax.Array:
    B, _, H, hd = q.shape
    KV, W = k_cache.shape[-3], k_cache.shape[-2]
    kv = [c.reshape(-1, B * KV, W, hd) for c in (k_cache, v_cache)]
    o = flash_decode(q.reshape(B * KV, H // KV, hd), *kv,
                     _flat(at, k_cache.shape), length, interpret=interpret)
    return o.reshape(B, 1, H, hd)


def _flat(at: Sequence[jax.Array], shape) -> jax.Array:
    """The index of layer ``at`` in its stack ``shape[:len(at)]``, with the
    stack dimensions merged into one."""
    layer = jnp.int32(0)
    for i, n in zip(at, shape):
        layer = layer * n + i
    return layer
