"""Chunked diagonal-SSM scan Pallas kernel.

Computes h_t = a_t * h_{t-1} + b_t along time for [S, C] channel-diagonal
state (the Mamba/mLSTM-style recurrence core). The grid is
(channel blocks, time blocks) with time minor-most: TPU executes the grid
sequentially, so a VMEM scratch row carries the running state across time
blocks while each block's work is fully vectorized over channels — the
VMEM-resident re-blocking of a GPU-style scan kernel (no warp shuffles on
TPU; the systolic/vector units want [time x channel] tiles).

Inside a block, a ``fori_loop`` walks the rows one sublane tile at a time
(8 rows for f32, 16 for bf16): each tile is loaded and stored whole, and
its rows are stepped through with static slices, so every memory access
stays tile-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssm_scan_kernel", "ssm_scan"]


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _sublane_rows(dtype) -> int:
    """Rows of one (sublane x 128-lane) tile: 8 at 32 bits, 16 at 16."""
    return 8 * max(4 // jnp.dtype(dtype).itemsize, 1)


def ssm_scan_kernel(a_ref, b_ref, o_ref, h_ref, *, block_t: int, tile: int):
    jt = pl.program_id(1)

    @pl.when(jt == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def chunk(c, h):                            # h [1, bc] f32
        r0 = pl.multiple_of(c * tile, tile)
        a = a_ref[pl.ds(r0, tile), :].astype(jnp.float32)   # [tile, bc]
        b = b_ref[pl.ds(r0, tile), :].astype(jnp.float32)
        rows = []
        for i in range(tile):
            h = a[i:i + 1] * h + b[i:i + 1]
            rows.append(h)
        o_ref[pl.ds(r0, tile), :] = jnp.concatenate(rows, 0).astype(
            o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, block_t // tile, chunk, h_ref[...])


def ssm_scan(a: jax.Array, b: jax.Array, *, block_t: int = 128,
             block_c: int = 512, interpret: bool = False) -> jax.Array:
    """a, b [S, C] -> h [S, C] with h_t = a_t*h_{t-1} + b_t (h_{-1} = 0).

    Shapes that the blocks do not divide are padded at the end (the scan
    is causal, so padded rows and channels never reach real outputs) and
    the result is cut back to [S, C].
    """
    S, C = a.shape
    tile = _sublane_rows(a.dtype)
    if block_t % tile or block_c % 128:
        raise ValueError(f"block_t must be a multiple of {tile} and "
                         f"block_c of 128, got {block_t}, {block_c}")
    bt = min(block_t, _round_up(S, tile))
    bc = min(block_c, _round_up(C, 128))
    Sp, Cp = _round_up(S, bt), _round_up(C, bc)
    if (Sp, Cp) != (S, C):
        pad = ((0, Sp - S), (0, Cp - C))
        a, b = jnp.pad(a, pad), jnp.pad(b, pad)
    out = pl.pallas_call(
        functools.partial(ssm_scan_kernel, block_t=bt, tile=tile),
        grid=(Cp // bc, Sp // bt),
        in_specs=[pl.BlockSpec((bt, bc), lambda jc, jt: (jt, jc)),
                  pl.BlockSpec((bt, bc), lambda jc, jt: (jt, jc))],
        out_specs=pl.BlockSpec((bt, bc), lambda jc, jt: (jt, jc)),
        out_shape=jax.ShapeDtypeStruct((Sp, Cp), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, bc), jnp.float32)],
        interpret=interpret,
    )(a, b)
    return out[:S, :C]
