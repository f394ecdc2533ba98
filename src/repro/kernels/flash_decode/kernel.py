"""Flash-decoding Pallas TPU kernel: one new token's attention over a KV
cache stacked over layers, read in place and only as far as it is filled.

The decode variant of ``flash_attention``. A decode step's attention reads
every layer's cache once; the plain einsum reads each layer out of the
stack and contracts the query with all ``W`` slots, masking the unused
ones afterwards. This kernel takes the stack itself, left in HBM, and two
scalars prefetched into SMEM, the layer and the length ``n`` (the slots
``[0, n)`` hold keys). It copies the layer's k/v blocks from the stack
into VMEM itself, in a loop that runs over the ``ceil(n / block_k)``
blocks that hold keys and no further, so that:

* no per-layer copy of the stack is made, and no block past the length is
  read or computed;
* ``DEPTH - 1`` blocks of k and of v are in flight while one is computed,
  so that the copies, not the grid's step-by-step pipeline, set the pace.

Layout: q [N, G, hd] (N = batch × kv heads, each with its G query heads),
k/v [L, N, W, hd]. The grid runs over row blocks; within one, the running
(m, l, acc) statistics of the online softmax stay in float32 VMEM scratch
and the output is written after the last block. Scores and softmax are
float32; q, k, v and the probabilities feed the MXU in the cache's dtype.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_blocks", "flash_decode", "flash_decode_kernel"]

NEG = -1e30
# bytes of one operand's block: large enough that a block's fixed cost is
# small beside its copy, small enough that DEPTH blocks of k and of v stay
# far under the VMEM Mosaic scopes by default
_BLOCK_BYTES = 1 << 20
# blocks of each operand in VMEM: one computed, the others being copied
_DEPTH = 3


def decode_blocks(n_rows: int, slots: int, hd: int,
                  itemsize: int) -> Tuple[int, int]:
    """(rows, slots) of one k/v block. Few slots, so the last block that
    holds keys reads little past the length: the most of 64, 32 or 16 that
    divides the cache and lets all rows fit the block bytes (the fewest
    that divides it where none fits, the whole cache where none divides
    it); then as many rows as fit and divide ``n_rows``."""
    row_bytes = hd * itemsize
    sizes = [c for c in (64, 32, 16) if slots % c == 0] or [slots]
    block_k = next((c for c in sizes
                    if n_rows * c * row_bytes <= _BLOCK_BYTES), sizes[-1])
    rows = max(1, min(n_rows, _BLOCK_BYTES // (block_k * row_bytes)))
    while n_rows % rows:
        rows -= 1
    return rows, block_k


def flash_decode_kernel(layer_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                        k_buf, v_buf, sems, m_ref, l_ref, acc_ref, *,
                        scale: float, block_k: int):
    depth, rows = k_buf.shape[0], k_buf.shape[1]
    r0 = pl.program_id(0) * rows
    layer, n = layer_ref[0], len_ref[0]
    n_blocks = pl.cdiv(n, block_k)

    def copies(j):
        slot = jax.lax.rem(j, depth)
        src = (layer, pl.ds(r0, rows), pl.ds(j * block_k, block_k))
        return [pltpu.make_async_copy(hbm.at[src], buf.at[slot],
                                      sems.at[c, slot])
                for c, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf)))]

    def fetch(j):
        @pl.when(j < n_blocks)
        def _():
            for c in copies(j):
                c.start()

    for j in range(min(depth - 1, k_hbm.shape[2] // block_k)):
        fetch(j)
    m_ref[...] = jnp.full_like(m_ref, NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(j, masked: bool):
        slot = jax.lax.rem(j, depth)
        k, v = k_buf[slot], v_buf[slot]                     # [r, bk, hd]
        s = jnp.einsum("rgh,rsh->rgs", q_ref[...], k,
                       preferred_element_type=jnp.float32) * scale
        if masked:
            # slots at or past the length: no score, and a zero value, so
            # that whatever they hold (stale or NaN) cannot reach the sum
            start = j * block_k
            live = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            s = jnp.where(live < n, s, NEG)
            live_v = start + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
            v = jnp.where(live_v < n, v, jnp.zeros_like(v))
        m_prev = m_ref[...]                                 # [r, G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.einsum(
            "rgs,rsh->rgh", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    def body(j, carry):
        fetch(j + depth - 1)
        for c in copies(j):
            c.wait()
        # blocks wholly inside the length take no mask; the one the
        # length ends in does
        full = (j + 1) * block_k <= n
        pl.when(full)(lambda: accumulate(j, False))
        pl.when(jnp.logical_not(full))(lambda: accumulate(j, True))
        return carry

    jax.lax.fori_loop(0, n_blocks, body, 0)
    o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def flash_decode(
    q: jax.Array,               # [N, G, hd]
    k: jax.Array,               # [L, N, W, hd]
    v: jax.Array,
    layer: jax.Array,           # int32 scalar: which of the L layers
    length: jax.Array,          # int32 scalar: slots [0, length) hold keys
    *,
    interpret: bool = False,
) -> jax.Array:
    """Attention of q over slots ``[0, length)`` of layer ``layer`` of k/v;
    ``length`` is at least 1."""
    N, G, hd = q.shape
    W = k.shape[2]
    rows, block_k = decode_blocks(N, W, hd, k.dtype.itemsize)

    def row_map(i, layer_ref, len_ref):
        return i, 0, 0

    kv_buf = pltpu.VMEM((_DEPTH, rows, block_k, hd), k.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N // rows,),
        in_specs=[pl.BlockSpec((rows, G, hd), row_map),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((rows, G, hd), row_map),
        scratch_shapes=[
            kv_buf, kv_buf,
            pltpu.SemaphoreType.DMA((2, _DEPTH)),
            pltpu.VMEM((rows, G, 1), jnp.float32),
            pltpu.VMEM((rows, G, 1), jnp.float32),
            pltpu.VMEM((rows, G, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(flash_decode_kernel,
                               scale=1.0 / math.sqrt(hd), block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      jnp.reshape(length, (1,)).astype(jnp.int32), q, k, v)
