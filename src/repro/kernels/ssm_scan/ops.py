"""Jit'd wrapper for the SSM scan kernel (batched over leading dims)."""
from __future__ import annotations

import functools

import jax

from .kernel import ssm_scan

__all__ = ["ssm_scan_batched"]


@functools.partial(jax.jit, static_argnames=("block_t", "block_c",
                                             "interpret"))
def ssm_scan_batched(a: jax.Array, b: jax.Array, *, block_t: int = 128,
                     block_c: int = 512, interpret: bool = False) -> jax.Array:
    """a, b [B, S, C] (or [S, C]) -> h, scanning axis -2."""
    if a.ndim == 2:
        return ssm_scan(a, b, block_t=block_t, block_c=block_c,
                        interpret=interpret)
    B = a.shape[0]
    flat_a = a.reshape((-1,) + a.shape[-2:])
    flat_b = b.reshape((-1,) + b.shape[-2:])
    out = jax.vmap(lambda x, y: ssm_scan(x, y, block_t=block_t,
                                         block_c=block_c,
                                         interpret=interpret))(flat_a, flat_b)
    return out.reshape(a.shape)
