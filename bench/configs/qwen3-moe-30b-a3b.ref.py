"""Plain reference of Qwen3-30B-A3B's forward pass, in float32.

The published architecture (arXiv:2505.09388; Hugging Face
``Qwen3MoeForCausalLM``): token embedding; per layer a pre-RMSNorm block
of grouped-query attention (q, k and v projections without biases, q and
k RMS-normalised per head, rotary embedding with ``rope_theta`` on the
rotate-half layout, causal softmax scaled by 1/sqrt(head size), o
projection) and a pre-RMSNorm sparse MoE, each added to the residual
stream; a final RMSNorm; logits against the untied head. The MoE: router
logits ``h @ router``, a softmax over all experts, each token's top
``num_experts_per_tok`` kept and renormalised to sum to one
(``norm_topk_prob``), and the token's output the sum of its chosen
experts' SwiGLU outputs weighted so. Every token goes to its own choice,
with no capacity and nothing dropped. Every matrix product runs in
float32 at ``highest`` precision, one layer at a time; the experts run
one at a time over every token, weighted by the token's gate for that
expert (zero where the token did not choose it), so that only one
expert's weights are upcast at once beside the bfloat16 weights.

It reads the benchmark's weights by name, in the layout the served
program keeps them: ``embed`` [V, d], ``head`` [d, V], ``final_norm`` [d]
and, stacked over layers in ``segments[0]``, ``ln1``/``ln2`` [L, d],
``wq`` [L, d, H, hd], ``wk``/``wv`` [L, d, KV, hd], ``q_norm``/``k_norm``
[L, hd], ``wo`` [L, H, hd, d], ``router`` [L, d, E], ``we_gate``/``we_up``
[L, E, d, f] and ``we_down`` [L, E, f, d]. Query head ``h`` reads
key/value head ``h // (H / KV)``.

``quant="fp8"`` is the control: the same forward with both operands of
every matrix product rounded to float8 (e4m3) with a per-tensor scale.

``routes`` gives, over the same forward, each token's chosen experts in
each layer, and the choice when the router's input, weights and logits
are rounded to bfloat16 as a bfloat16 program computes them;
``route_tips`` counts the (token, layer) pairs where the two differ: how
often routing tips on a near tie.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["logits", "routes", "route_tips"]


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


def _topk_sets(logits, k):
    """[..., E] -> one-hot [..., E] of the k largest."""
    _, ids = jax.lax.top_k(logits, k)
    return jnp.sum(jax.nn.one_hot(ids, logits.shape[-1], dtype=jnp.int32),
                   -2)


def _experts(seg, i, h, gates, quant):
    """sum_e gates[..., e] * SwiGLU_e(h), one expert at a time."""
    E = gates.shape[-1]

    def one(e, acc):
        def w(name):
            a = seg[name]
            return jax.lax.dynamic_slice(
                a, (i, e, 0, 0), (1, 1) + a.shape[2:])[0, 0].astype(
                    jnp.float32)
        g = _mm("bsd,df->bsf", h, w("we_gate"), quant)
        u = _mm("bsd,df->bsf", h, w("we_up"), quant)
        y = _mm("bsf,fd->bsd", jax.nn.silu(g) * u, w("we_down"), quant)
        return acc + jnp.take(gates, e, axis=-1)[..., None] * y

    return jax.lax.fori_loop(0, E, one, jnp.zeros_like(h))


@functools.partial(jax.jit, static_argnames=("eps", "theta", "k", "quant"))
def _layer(seg, i, x, *, eps, theta, k, quant):
    """One layer: (x out, chosen experts [B, S, E] as 0/1, the same chosen
    by a bfloat16 router)."""
    p = {n: jax.lax.dynamic_index_in_dim(seg[n], i, keepdims=False)
         .astype(jnp.float32)
         for n in ("ln1", "ln2", "wq", "wk", "wv", "q_norm", "k_norm", "wo",
                   "router")}
    B, S, _ = x.shape
    H, hd = p["wq"].shape[1], p["wq"].shape[2]
    KV = p["wk"].shape[1]
    h = _rms(x, p["ln1"], eps)
    q = _rms(_mm("bsd,dhk->bshk", h, p["wq"], quant), p["q_norm"], eps)
    kk = _rms(_mm("bsd,dhk->bshk", h, p["wk"], quant), p["k_norm"], eps)
    v = _mm("bsd,dhk->bshk", h, p["wv"], quant)
    q, kk = _rope(q, theta), _rope(kk, theta)
    rep = H // KV
    kk = jnp.repeat(kk, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = _mm("bqhk,bshk->bhqs", q, kk, quant) / math.sqrt(hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _mm("bhqs,bshk->bqhk", probs, v, quant)
    x = x + _mm("bqhk,hkd->bqd", o, p["wo"], quant)
    h = _rms(x, p["ln2"], eps)
    logits = _mm("bsd,de->bse", h, p["router"], quant)
    chosen = _topk_sets(logits, k)
    top = jax.nn.softmax(logits, axis=-1) * chosen
    gates = top / jnp.sum(top, -1, keepdims=True)
    # the router as a bfloat16 program computes it: input and weights in
    # bfloat16, logits rounded to bfloat16
    bf = jnp.einsum("bsd,de->bse", h.astype(jnp.bfloat16),
                    p["router"].astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32).astype(jnp.bfloat16)
    return (x + _experts(seg, i, h, gates, quant), chosen,
            _topk_sets(bf.astype(jnp.float32), k))


@functools.partial(jax.jit, static_argnames=("first", "eps", "quant"))
def _head(head, final_norm, x, *, first, eps, quant):
    h = _rms(x[:, first:], final_norm.astype(jnp.float32), eps)
    return _mm("bsd,dv->bsv", h, head.astype(jnp.float32), quant)


def _forward(cfg, params, tokens, quant):
    """(final hidden states, [(chosen, chosen by bf16 router)] by layer)."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    x = jnp.take(params["embed"], jnp.asarray(tokens), axis=0).astype(
        jnp.float32)
    seg = params["segments"][0]
    chosen = []
    for i in range(cfg["num_hidden_layers"]):
        x, c, cb = _layer(seg, i, x, eps=eps, theta=theta,
                          k=cfg["num_experts_per_tok"], quant=quant)
        chosen.append((c, cb))
    return x, chosen


def logits(cfg, params, tokens, first: int, quant=None):
    """Logits [B, S - first, V] at positions ``first..S-1`` of ``tokens``
    [B, S]: what the model predicts for positions ``first+1..S``."""
    if cfg["tie_word_embeddings"]:
        raise ValueError("this reference reads the untied head")
    x, _ = _forward(cfg, params, tokens, quant)
    return _head(params["head"], params["final_norm"], x, first=first,
                 eps=cfg["rms_norm_eps"], quant=quant)


def routes(cfg, params, tokens):
    """Each layer's chosen experts, as 0/1 [L, B, S, E], for every token
    of ``tokens`` [B, S]: with the float32 router, and with a bfloat16
    one."""
    _, chosen = _forward(cfg, params, tokens, None)
    return (jnp.stack([c for c, _ in chosen]),
            jnp.stack([cb for _, cb in chosen]))


def route_tips(cfg, params, tokens):
    """(routings that tip in bfloat16, routings): over every token of
    ``tokens`` [B, S] and every layer."""
    c, cb = routes(cfg, params, tokens)
    return int(jnp.sum(jnp.any(c != cb, -1))), int(c[..., 0].size)
