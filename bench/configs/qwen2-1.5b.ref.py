"""Plain reference of Qwen2-1.5B's forward pass, in float32.

The published architecture (arXiv:2407.10671; Hugging Face
``Qwen2ForCausalLM``): token embedding; per layer a pre-RMSNorm block of
grouped-query attention (q, k and v projections with biases, rotary
embedding with ``rope_theta`` on the rotate-half layout, causal softmax
scaled by 1/sqrt(head size), o projection) and a pre-RMSNorm SwiGLU MLP,
each added to the residual stream; a final RMSNorm; logits against the
tied embedding table. Every matrix product runs in float32 at
``highest`` precision, one layer at a time.

It reads the benchmark's weights by name, in the layout the served
program keeps them: ``embed`` [V, d], ``final_norm`` [d] and, stacked
over layers in ``segments[0]``, ``ln1``/``ln2`` [L, d], ``wq`` [L, d, H,
hd], ``wk``/``wv`` [L, d, KV, hd], ``bq`` [L, H, hd], ``bk``/``bv`` [L,
KV, hd], ``wo`` [L, H, hd, d], ``wg``/``wi`` [L, d, f] and ``wo2`` [L, f,
d]. Query head ``h`` reads key/value head ``h // (H / KV)``.

``quant="fp8"`` is the control: the same forward with both operands of
every matrix product rounded to float8 (e4m3) with a per-tensor scale.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["logits"]


def _q8(x):
    """Round to float8 e4m3 with one scale per tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, quant):
    if quant == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [B, S, n, hd], positions 0..S-1, rotate-half layout."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "quant"))
def _layer(seg, i, x, *, eps, theta, quant):
    p = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
        .astype(jnp.float32), seg)
    B, S, _ = x.shape
    H, hd = p["wq"].shape[1], p["wq"].shape[2]
    KV = p["wk"].shape[1]
    h = _rms(x, p["ln1"], eps)
    q = _mm("bsd,dhk->bshk", h, p["wq"], quant) + p["bq"]
    k = _mm("bsd,dhk->bshk", h, p["wk"], quant) + p["bk"]
    v = _mm("bsd,dhk->bshk", h, p["wv"], quant) + p["bv"]
    q, k = _rope(q, theta), _rope(k, theta)
    rep = H // KV
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = _mm("bqhk,bshk->bhqs", q, k, quant) / math.sqrt(hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _mm("bhqs,bshk->bqhk", probs, v, quant)
    x = x + _mm("bqhk,hkd->bqd", o, p["wo"], quant)
    h = _rms(x, p["ln2"], eps)
    g = _mm("bsd,df->bsf", h, p["wg"], quant)
    u = _mm("bsd,df->bsf", h, p["wi"], quant)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, p["wo2"], quant)


@functools.partial(jax.jit, static_argnames=("first", "eps", "quant"))
def _head(embed, final_norm, x, *, first, eps, quant):
    h = _rms(x[:, first:], final_norm.astype(jnp.float32), eps)
    return _mm("bsd,vd->bsv", h, embed.astype(jnp.float32), quant)


def logits(cfg, params, tokens, first: int, quant=None):
    """Logits [B, S - first, V] at positions ``first..S-1`` of ``tokens``
    [B, S]: what the model predicts for positions ``first+1..S``."""
    if not cfg["tie_word_embeddings"]:
        raise ValueError("this reference reads the tied embedding as head")
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    embed = params["embed"]
    x = jnp.take(embed, jnp.asarray(tokens), axis=0).astype(jnp.float32)
    seg = params["segments"][0]
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(seg, i, x, eps=eps, theta=theta, quant=quant)
    return _head(embed, params["final_norm"], x, first=first, eps=eps,
                 quant=quant)
