"""Attention for train/prefill/decode, memory-bounded and GSPMD-shardable.

Four code paths:

  * ``attention``        — train/prefill. Scans over query chunks so scores
    never materialize beyond [B, Sc, KV, G, Skv]; sliding-window attention is
    *banded* (keys dynamically sliced to window+chunk) so SWA FLOPs are
    O(S·w), not O(S²). GQA is a grouped einsum (no kv repeat).
  * ``cross_attention``  — q from text, kv from (small) image-token set.
  * ``decode_attention`` — one new token against a KV cache whose sequence
    dim is sharded over the 'model' mesh axis: the softmax max/sum and the
    PV contraction reduce over that dim, which GSPMD lowers to the
    flash-decoding collective pattern (small all-reduces), never an
    all-gather of the cache.
  * ``stacked_decode_attention`` — one new token against one layer of a
    cache stacked over layers, on one device: the flash-decoding Pallas
    kernel reads the layer in place and only its filled slots.

Shapes: q [B,S,H,hd], k/v [B,Skv,KV,hd], cache k/v [B,KV,Smax,hd].
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..distributed.sharding import constrain
from ..kernels.flash_decode.ops import flash_decode_cache

__all__ = ["attention", "cross_attention", "decode_attention",
           "stacked_decode_attention"]

NEG_INF = -1e30


def _grouped_scores(q, k, scale):
    """q [B,Sq,KV,G,hd] · k [B,Sk,KV,hd] -> [B,KV,G,Sq,Sk] (fp32)."""
    return jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32) * scale


def _softmax_apply(scores, v):
    """scores [B,KV,G,Sq,Sk] (masked, fp32) · v [B,Sk,KV,hd] -> [B,Sq,KV,G,hd]."""
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqs,bskh->bqkgh", probs, v)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    n_sink: int = 0,
    q_chunk: int = 512,
    scale: Optional[float] = None,
    remat_chunk: bool = True,
) -> jax.Array:
    """Chunked attention. Returns [B,S,H,hd].

    window>0: causal sliding window (banded key slice). n_sink>0: the first
    ``n_sink`` positions are always attended (Hymba meta tokens).

    remat_chunk: checkpoint each q-chunk so the [Sc, Skv] scores/masks are
    recomputed in backward instead of being stacked as map residuals —
    without this, the stacked f32 scores + pred masks are ~70% of the
    per-chip HBM traffic of a train step (measured on the smollm-135m
    dry-run artifact; §Perf iteration 1).
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, S, KV, G, hd)

    n_chunks = max(1, S // q_chunk)
    while S % n_chunks:
        n_chunks -= 1
    Sc = S // n_chunks

    banded = causal and window > 0 and (window + Sc) < S
    band = -(-(window + Sc) // 128) * 128 if banded else S  # key-slice length

    qs = qg.reshape(B, n_chunks, Sc, KV, G, hd).swapaxes(0, 1)  # [n,B,Sc,KV,G,hd]
    col_full = jnp.arange(S)

    def chunk(i, qc):
        row = i * Sc + jnp.arange(Sc)                      # [Sc] global rows
        if banded:
            start = jnp.clip(i * Sc + Sc - band, 0, S - band)
            kc = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, start, band, axis=1)
            col = start + jnp.arange(band)
        else:
            kc, vc, col = k, v, col_full
        scores = _grouped_scores(qc, kc, scale)            # [B,KV,G,Sc,Skv]
        if causal:
            ok = col[None, :] <= row[:, None]
            if window > 0:
                ok &= col[None, :] > (row[:, None] - window)
            if n_sink > 0:
                ok |= col[None, :] < n_sink
                ok &= col[None, :] <= row[:, None]
            scores = jnp.where(ok[None, None, None], scores, NEG_INF)
        if banded and n_sink > 0:
            # sink keys live outside the band: handled by caller via concat.
            pass
        out = _softmax_apply(scores, vc)                   # [B,Sc,KV,G,hd]
        return out

    if remat_chunk:
        chunk = jax.checkpoint(
            chunk, policy=jax.checkpoint_policies.nothing_saveable)
    if n_chunks == 1:
        out = chunk(jnp.int32(0), qs[0])[None]
    else:
        out = jax.lax.map(lambda xs: chunk(xs[0], xs[1]),
                          (jnp.arange(n_chunks), qs))
    out = out.swapaxes(0, 1).reshape(B, S, H, hd)
    return constrain(out, "batch", "act_seq", "heads", None)


def sink_banded_attention(
    q, k, v, *, window: int, n_sink: int, q_chunk: int = 512, scale=None
) -> jax.Array:
    """SWA + always-attend sinks, keeping the banded key slice. Computes the
    band part and the sink part separately and merges with a joint softmax
    (two-piece logsumexp), so FLOPs stay O(S·(w+sink))."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    if S <= (window + q_chunk) or n_sink == 0:
        return attention(q, k, v, causal=True, window=window, n_sink=n_sink,
                         q_chunk=q_chunk, scale=scale)
    qg = q.reshape(B, S, KV, G, hd)
    n_chunks = max(1, S // q_chunk)
    while S % n_chunks:
        n_chunks -= 1
    Sc = S // n_chunks
    band = -(-(window + Sc) // 128) * 128
    band = min(band, S)
    k_sink, v_sink = k[:, :n_sink], v[:, :n_sink]
    qs = qg.reshape(B, n_chunks, Sc, KV, G, hd).swapaxes(0, 1)

    def chunk(i, qc):
        row = i * Sc + jnp.arange(Sc)
        start = jnp.clip(i * Sc + Sc - band, 0, S - band)
        kc = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, start, band, axis=1)
        col = start + jnp.arange(band)
        sb = _grouped_scores(qc, kc, scale)
        ok = (col[None, :] <= row[:, None]) & (col[None, :] > row[:, None] - window)
        # avoid double counting sink columns that fall inside the band
        ok &= col[None, :] >= n_sink
        sb = jnp.where(ok[None, None, None], sb, NEG_INF)
        ss = _grouped_scores(qc, k_sink, scale)            # [B,KV,G,Sc,n_sink]
        ok_s = (jnp.arange(n_sink)[None, :] <= row[:, None])
        ss = jnp.where(ok_s[None, None, None], ss, NEG_INF)
        joint = jnp.concatenate([ss, sb], axis=-1)
        probs = jax.nn.softmax(joint, axis=-1).astype(v.dtype)
        ps, pb = probs[..., :n_sink], probs[..., n_sink:]
        out = jnp.einsum("bkgqs,bskh->bqkgh", ps, v_sink)
        out += jnp.einsum("bkgqs,bskh->bqkgh", pb, vc)
        return out

    out = jax.lax.map(lambda xs: chunk(xs[0], xs[1]), (jnp.arange(n_chunks), qs))
    out = out.swapaxes(0, 1).reshape(B, S, H, hd)
    return constrain(out, "batch", "act_seq", "heads", None)


def cross_attention(q, k_img, v_img, *, scale=None) -> jax.Array:
    """q [B,S,H,hd] x image kv [B,I,KV,hd] (no mask, I is small)."""
    B, S, H, hd = q.shape
    KV = k_img.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, S, KV, G, hd)
    scores = _grouped_scores(qg, k_img, scale)
    out = _softmax_apply(scores, v_img)
    return out.reshape(B, S, H, hd)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    valid: jax.Array,
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """One-token decode: q [B,1,H,hd] vs cache [B,KV,Smax,hd] (kv_seq-sharded).

    ``valid`` [Smax] bool marks live cache slots (caller encodes causal /
    ring-buffer semantics). Softmax + PV reduce over the sharded Smax dim ->
    flash-decoding collectives under GSPMD (all-reduce of max/sum), never an
    all-gather of the cache.
    """
    B, _, H, hd = q.shape
    KV = k_cache.shape[1]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, 1, KV, G, hd)
    scores = jnp.einsum("bqkgh,bksh->bkgqs", qg, k_cache).astype(jnp.float32) * scale
    scores = jnp.where(valid[None, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgqs,bksh->bqkgh", probs, v_cache)
    return out.reshape(B, 1, H, hd)


def stacked_decode_attention(q, k_cache, v_cache, at, length) -> jax.Array:
    """One-token decode: q [B,1,H,hd] vs layer ``at`` of a cache stacked
    [*stack,B,KV,W,hd], over its slots ``[0, length)``, block by block up
    to ``length``. On a TPU the compiled Mosaic kernel reads the stack in
    place; elsewhere the same kernel runs interpreted on the layer's view
    (chosen when the program is lowered, by its platform)."""
    def tpu(q, k, v, n, *at):
        return flash_decode_cache(q, k, v, at, n)

    def default(q, k, v, n, *at):
        # interpreted, the kernel's operands become loop state, which the
        # compiler copies: it gets the layer's own view, not the stack
        k, v = (c[at] for c in (k, v))
        return flash_decode_cache(q, k, v, (), n, interpret=True)

    return jax.lax.platform_dependent(q, k_cache, v_cache, length, *at,
                                      tpu=tpu, default=default)
