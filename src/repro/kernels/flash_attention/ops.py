"""Jit'd public wrapper: model-layout adapter for the flash kernel.

``flash_mha(q, k, v)`` takes the model's [B, S, H, hd] / [B, S, KV, hd]
layout, flattens heads into the batch dim, dispatches to the Pallas kernel
(compiled for the TPU; ``interpret=True`` on the CPU) and restores the layout.
"""
from __future__ import annotations

import functools

import jax

from .kernel import flash_attention

__all__ = ["flash_mha"]


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_mha(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, block_q: int = 128, block_k: int = 128,
              interpret: bool = False) -> jax.Array:
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    # [B,S,H,hd] -> [B*H, S, hd] with q-heads grouped per kv head so the
    # kernel's i//G kv indexing lines up
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, k.shape[1], hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, v.shape[1], hd)
    o = flash_attention(qf, kf, vf, n_q_heads_per_kv=G, causal=causal,
                        block_q=block_q, block_k=block_k,
                        interpret=interpret)
    return o.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
