"""xLSTM cells: mLSTM (matrix memory, chunk-parallel) + sLSTM (scalar memory).

mLSTM training/prefill uses the **chunkwise-parallel form**: within a chunk
of length L the contribution is a masked [L, L] decay-weighted attention
matrix; across chunks a small ``lax.scan`` carries the stabilized state
(C [dk, dv], n [dk], m scalar per head). This keeps FLOPs O(S·L·d) and
memory O(B·H·L²) instead of O(S²) — the property that makes xLSTM eligible
for the ``long_500k`` shape.

All gate math is float32 and log-space stabilized (running max ``m``),
matching the xLSTM paper's numerics. Decode is the O(1) recurrent step.

Shapes: q, k [B, S, H, dk], v [B, S, H, dv], gate preacts [B, S, H].
State: C [B, H, dk, dv], n [B, H, dk], m [B, H]  (stored pre-scaled by
exp(-m), i.e. "hatted").
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "mlstm_chunked",
    "mlstm_decode_step",
    "mlstm_state_init",
    "slstm_scan",
    "slstm_decode_step",
    "slstm_state_init",
]


def _logsigmoid(x):
    return -jax.nn.softplus(-x)


def mlstm_state_init(B: int, H: int, dk: int, dv: int, dtype=jnp.float32):
    return (
        jnp.zeros((B, H, dk, dv), dtype),
        jnp.zeros((B, H, dk), dtype),
        jnp.full((B, H), -1e30, dtype),
    )


def mlstm_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    i_pre: jax.Array,
    f_pre: jax.Array,
    state: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
    *,
    chunk: int = 256,
):
    """Chunk-parallel mLSTM. Returns h [B, S, H, dv] and the final state
    (C, n, m)."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    L = min(chunk, S)
    while S % L:
        L -= 1
    Nc = S // L

    # [B,S,H,*] -> [Nc, B, H, L, *] for the chunk scan
    def to_chunks(x):
        x = x.reshape(B, Nc, L, H, -1).transpose(1, 0, 3, 2, 4)
        return x

    qf = to_chunks(q).astype(jnp.float32)
    kf = to_chunks(k).astype(jnp.float32) / jnp.sqrt(jnp.float32(dk))
    vf = to_chunks(v).astype(jnp.float32)
    lf = _logsigmoid(to_chunks(f_pre[..., None]).astype(jnp.float32))[..., 0]
    li = to_chunks(i_pre[..., None]).astype(jnp.float32)[..., 0]  # [Nc,B,H,L]

    if state is None:
        C0, n0, m0 = mlstm_state_init(B, H, dk, dv)
    else:
        C0, n0, m0 = (s.astype(jnp.float32) for s in state)

    tri = jnp.tril(jnp.ones((L, L), jnp.bool_))            # s <= t

    def chunk_step(carry, xs):
        Ch, nh, m = carry                                  # hatted state
        qc, kc, vc, lfc, lic = xs                          # [B,H,L,*]
        b = jnp.cumsum(lfc, axis=-1)                       # [B,H,L] inclusive
        btot = b[..., -1:]
        G = jax.lax.cummax(lic - b, axis=lic.ndim - 1)     # [B,H,L]
        m_t = b + jnp.maximum(m[..., None], G)             # stabilizer per t
        # intra-chunk decay matrix D[t,s] = exp(b_t - b_s + li_s - m_t), s<=t
        logD = b[..., :, None] - b[..., None, :] + lic[..., None, :] \
            - m_t[..., :, None]
        logD = jnp.where(tri, logD, -jnp.inf)
        D = jnp.exp(logD)                                  # [B,H,L,L]
        Sqk = jnp.einsum("bhtd,bhsd->bhts", qc, kc)        # [B,H,L,L]
        E = Sqk * D
        num = jnp.einsum("bhts,bhsv->bhtv", E, vc)         # intra numerator
        den = jnp.sum(E, axis=-1)                          # [B,H,L]
        # inter-chunk (carry) contribution
        a = jnp.exp(b + m[..., None] - m_t)                # [B,H,L]
        num = num + a[..., None] * jnp.einsum("bhtd,bhdv->bhtv", qc, Ch)
        den = den + a * jnp.einsum("bhtd,bhd->bht", qc, nh)
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_t))[..., None]
        # state update to chunk end
        m_new = btot[..., 0] + jnp.maximum(m, G[..., -1])
        g = jnp.exp(btot - b + lic - m_new[..., None])     # [B,H,L]
        decay = jnp.exp(btot[..., 0] + m - m_new)          # [B,H]
        C_new = decay[..., None, None] * Ch + jnp.einsum(
            "bhs,bhsd,bhsv->bhdv", g, kc, vc
        )
        n_new = decay[..., None] * nh + jnp.einsum("bhs,bhsd->bhd", g, kc)
        return (C_new, n_new, m_new), h

    (Cf, nf, mf), hs = jax.lax.scan(
        chunk_step, (C0, n0, m0), (qf, kf, vf, lf, li)
    )
    h = hs.transpose(1, 0, 3, 2, 4).reshape(B, S, H, dv).astype(v.dtype)
    return h, (Cf, nf, mf)


def mlstm_decode_step(q, k, v, i_pre, f_pre, state):
    """One-token recurrent mLSTM step. q,k,v [B,1,H,d*]; gates [B,1,H]."""
    B, _, H, dk = q.shape
    Ch, nh, m = (s.astype(jnp.float32) for s in state)
    qf = q[:, 0].astype(jnp.float32)                       # [B,H,dk]
    kf = k[:, 0].astype(jnp.float32) / jnp.sqrt(jnp.float32(dk))
    vf = v[:, 0].astype(jnp.float32)
    lf = _logsigmoid(f_pre[:, 0].astype(jnp.float32))      # [B,H]
    li = i_pre[:, 0].astype(jnp.float32)
    m_new = jnp.maximum(lf + m, li)
    fs = jnp.exp(lf + m - m_new)
    is_ = jnp.exp(li - m_new)
    C_new = fs[..., None, None] * Ch + is_[..., None, None] * (
        kf[..., :, None] * vf[..., None, :]
    )
    n_new = fs[..., None] * nh + is_[..., None] * kf
    num = jnp.einsum("bhd,bhdv->bhv", qf, C_new)
    den = jnp.einsum("bhd,bhd->bh", qf, n_new)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_new))[..., None]
    return h[:, None].astype(v.dtype), (C_new, n_new, m_new)


# ---------------------------------------------------------------------------
# sLSTM — scalar memory, exponential gating, strictly sequential (the paper
# notes sLSTM is not parallelizable; we scan over time).
# ---------------------------------------------------------------------------

def slstm_state_init(B: int, H: int, hd: int, dtype=jnp.float32):
    return (
        jnp.zeros((B, H, hd), dtype),   # c
        jnp.ones((B, H, hd), dtype),    # n
        jnp.zeros((B, H, hd), dtype),   # h
        jnp.full((B, H, hd), -1e30, dtype),  # m
    )


def _slstm_cell(state, gates_x, R):
    """gates_x [B,H,4,hd] (input contribution); R [H,hd,4,hd] recurrent."""
    c, n, h, m = state
    pre = gates_x + jnp.einsum("bhd,hdgk->bhgk", h, R)
    zi, fi, ii, oi = (pre[:, :, g] for g in range(4))
    z = jnp.tanh(zi)
    o = jax.nn.sigmoid(oi)
    m_new = jnp.maximum(fi + m, ii)
    fs = jnp.exp(fi + m - m_new)
    is_ = jnp.exp(ii - m_new)
    c_new = fs * c + is_ * z
    n_new = fs * n + is_
    h_new = o * (c_new / jnp.maximum(n_new, 1e-9))
    return (c_new, n_new, h_new, m_new), h_new


def slstm_scan(gates_x: jax.Array, R: jax.Array, state=None):
    """gates_x [B,S,H,4,hd] -> h [B,S,H,hd] (float32 internally)."""
    B, S, H, _, hd = gates_x.shape
    if state is None:
        state = slstm_state_init(B, H, hd)
    gx = gates_x.astype(jnp.float32).transpose(1, 0, 2, 3, 4)   # [S,B,H,4,hd]
    Rf = R.astype(jnp.float32)
    state, hs = jax.lax.scan(lambda s, g: _slstm_cell(s, g, Rf), state, gx)
    return hs.transpose(1, 0, 2, 3).astype(gates_x.dtype), state


def slstm_decode_step(gates_x: jax.Array, R: jax.Array, state):
    """gates_x [B,1,H,4,hd] one step."""
    state, h = _slstm_cell(state, gates_x[:, 0].astype(jnp.float32),
                           R.astype(jnp.float32))
    return h[:, None].astype(gates_x.dtype), state
