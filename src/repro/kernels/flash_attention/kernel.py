"""Flash attention Pallas TPU kernel (online-softmax, causal, GQA).

Motivation (from the dry-run artifacts): the jnp attention path
materializes [*, Sq, Sk] scores in HBM — for smollm-135m/train_4k that is
~0.9 TB of per-chip HBM traffic per step, the dominant memory-roofline
term. This kernel keeps the running (m, l, acc) statistics in VMEM scratch
across the sequential k-block grid dimension, so score traffic never
leaves VMEM — the classic flash-attention scheme re-blocked for the MXU:
block shapes are multiples of 128 lanes, accumulation in f32.

Layout: q [BH, Sq, hd], k/v [BKV, Sk, hd] (heads flattened into batch;
GQA mapping done by the BlockSpec index maps: q-head i reads kv-head
(i % H) // G of batch i // H).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_kernel", "flash_attention"]

NEG = -1e30


def flash_attention_kernel(q_ref, k_ref, v_ref, o_ref,
                           acc_ref, m_ref, l_ref, *,
                           scale: float, causal: bool,
                           block_q: int, block_k: int, n_k: int):
    jq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)            # [bq, hd]
    k = k_ref[0].astype(jnp.float32)            # [bk, hd]
    v = v_ref[0].astype(jnp.float32)            # [bk, hd]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        rows = jq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = jk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(cols <= rows, s, NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    # guard: fully-masked rows keep p = 0 (not exp(0))
    p = jnp.where(s <= NEG / 2, 0.0, jnp.exp(s - m_new[:, None]))
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(jk == n_k - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,               # [BH, Sq, hd]
    k: jax.Array,               # [BKV, Sk, hd]
    v: jax.Array,
    *,
    n_q_heads_per_kv: int = 1,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    BH, Sq, hd = q.shape
    BKV, Sk, _ = k.shape
    G = n_q_heads_per_kv
    assert BH == BKV * G, (BH, BKV, G)
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    n_q = -(-Sq // block_q)
    n_k = -(-Sk // block_k)

    kernel = functools.partial(
        flash_attention_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, n_k=n_k)

    return pl.pallas_call(
        kernel,
        grid=(BH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda i, jq, jk: (i, jq, 0)),
            pl.BlockSpec((1, block_k, hd), lambda i, jq, jk: (i // G, jk, 0)),
            pl.BlockSpec((1, block_k, hd), lambda i, jq, jk: (i // G, jk, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda i, jq, jk: (i, jq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
