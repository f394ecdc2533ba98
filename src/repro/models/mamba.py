"""Mamba-style selective SSM (diagonal state) for the Hymba hybrid heads.

Training/prefill uses ``jax.lax.associative_scan`` over the sequence —
O(S log S) depth, fully parallel, which is what makes the hybrid arch
eligible for ``long_500k``. The inner channel dim is sharded over the
'model' mesh axis (logical axis ``ssm_inner``), bounding the scan's
[B, S, di, n] state tensor per chip.

Decode is the O(1) recurrent update on (conv_state, ssm_state).

Shapes: x_in [B, S, di]; A_log [di, n]; W_x projects di -> (dt_rank + 2n);
conv is depthwise causal, width K.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..distributed.sharding import constrain

__all__ = ["selective_scan", "mamba_mix", "mamba_decode_mix", "MambaState"]


class MambaState(NamedTuple):
    conv: jax.Array   # [B, di, K-1] last inputs (for causal depthwise conv)
    ssm: jax.Array    # [B, di, n]   diagonal SSM state


def _causal_depthwise_conv(x: jax.Array, w: jax.Array,
                           carry: Optional[jax.Array] = None):
    """x [B,S,di], w [di,K] -> y [B,S,di]; optional left context carry."""
    B, S, di = x.shape
    K = w.shape[-1]
    if carry is None:
        pad = jnp.zeros((B, K - 1, di), x.dtype)
    else:
        pad = carry.transpose(0, 2, 1).astype(x.dtype)      # [B,K-1,di]
    xp = jnp.concatenate([pad, x], axis=1)                   # [B,S+K-1,di]
    # sum_k w[:,k] * x[t-K+1+k] — K is tiny (4): unrolled adds, no conv op.
    y = sum(xp[:, k : k + S] * w[None, None, :, k] for k in range(K))
    new_carry = xp[:, S:, :].transpose(0, 2, 1)              # [B,di,K-1]
    return y, new_carry


def selective_scan(a: jax.Array, bu: jax.Array) -> jax.Array:
    """h_t = a_t * h_{t-1} + bu_t along axis 1. a, bu [B, S, di, n] (f32)."""

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, ar * bl + br

    _, h = jax.lax.associative_scan(combine, (a, bu), axis=1)
    return h


def _ssm_inner(x_conv, dt, Bm, Cm, A, D, state: Optional[jax.Array]):
    """Shared SSM math. x_conv [B,S,di], dt [B,S,di], Bm/Cm [B,S,n].

    Returns y [B,S,di] (f32) and final state [B,di,n].
    """
    a = jnp.exp(dt[..., None] * A[None, None])              # [B,S,di,n]
    bu = (dt * x_conv)[..., None] * Bm[:, :, None, :]       # [B,S,di,n]
    if state is not None:
        # fold carried state into the first step: h_0' = a_0*h_prev + bu_0
        bu = bu.at[:, 0].add(a[:, 0] * state)
    h = selective_scan(a, bu)                               # [B,S,di,n]
    y = jnp.einsum("bsdn,bsn->bsd", h, Cm) + D[None, None] * x_conv
    return y, h[:, -1]


def mamba_mix(
    x_in: jax.Array,
    z: jax.Array,
    conv_w: jax.Array,
    w_x: jax.Array,
    w_dt: jax.Array,
    b_dt: jax.Array,
    a_log: jax.Array,
    d_skip: jax.Array,
    *,
    n_state: int,
    dt_rank: int,
    state: Optional[MambaState] = None,
) -> Tuple[jax.Array, MambaState]:
    """Full Mamba mixing on a pre-projected pair (x_in, z) [B,S,di].

    Caller provides in/out projections; this is the conv + selective-scan +
    gate core. Returns the output and the final state (the cache prefill
    hands to ``mamba_decode_mix``).
    """
    B, S, di = x_in.shape
    xc, conv_carry = _causal_depthwise_conv(
        x_in, conv_w, None if state is None else state.conv
    )
    xc = jax.nn.silu(xc.astype(jnp.float32))
    xc = constrain(xc, "batch", None, "ssm_inner")
    proj = jnp.einsum("bsd,dr->bsr", xc.astype(x_in.dtype), w_x)
    proj = proj.astype(jnp.float32)
    dt_in, Bm, Cm = jnp.split(proj, [dt_rank, dt_rank + n_state], axis=-1)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,rd->bsd", dt_in, w_dt.astype(jnp.float32))
        + b_dt.astype(jnp.float32)
    )
    dt = constrain(dt, "batch", None, "ssm_inner")
    A = -jnp.exp(a_log.astype(jnp.float32))                 # [di,n]
    y, ssm_final = _ssm_inner(
        xc, dt, Bm, Cm, A, d_skip.astype(jnp.float32),
        None if state is None else state.ssm.astype(jnp.float32),
    )
    out = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x_in.dtype)
    out = constrain(out, "batch", None, "ssm_inner")
    return out, MambaState(conv=conv_carry, ssm=ssm_final)


def mamba_decode_mix(
    x_in: jax.Array,
    z: jax.Array,
    conv_w: jax.Array,
    w_x: jax.Array,
    w_dt: jax.Array,
    b_dt: jax.Array,
    a_log: jax.Array,
    d_skip: jax.Array,
    *,
    n_state: int,
    dt_rank: int,
    state: MambaState,
) -> Tuple[jax.Array, MambaState]:
    """One-token step: x_in, z [B,1,di]. O(1) state update."""
    B, _, di = x_in.shape
    K = conv_w.shape[-1]
    # conv: append new token to carry, take one output step
    hist = jnp.concatenate(
        [state.conv.astype(x_in.dtype), x_in.transpose(0, 2, 1)], axis=-1
    )  # [B,di,K]
    xc = jnp.einsum("bdk,dk->bd", hist, conv_w)[:, None]    # [B,1,di]
    new_conv = hist[..., 1:]
    xc = jax.nn.silu(xc.astype(jnp.float32))
    proj = jnp.einsum("bsd,dr->bsr", xc.astype(x_in.dtype), w_x).astype(
        jnp.float32
    )
    dt_in, Bm, Cm = jnp.split(proj, [dt_rank, dt_rank + n_state], axis=-1)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,rd->bsd", dt_in, w_dt.astype(jnp.float32))
        + b_dt.astype(jnp.float32)
    )
    A = -jnp.exp(a_log.astype(jnp.float32))
    a = jnp.exp(dt[..., None] * A[None, None])[:, 0]
    bu = ((dt * xc)[..., None] * Bm[:, :, None, :])[:, 0]   # [B,di,n]
    h = a * state.ssm.astype(jnp.float32) + bu
    y = jnp.einsum("bdn,bn->bd", h, Cm[:, 0]) + d_skip.astype(jnp.float32) * xc[:, 0]
    out = (y[:, None] * jax.nn.silu(z.astype(jnp.float32))).astype(x_in.dtype)
    return out, MambaState(conv=new_conv, ssm=h)
