"""Layer blocks: parameter templates + prefill/decode forward paths.

Each block *kind* is a ``Block`` record with three functions sharing one
numeric core. Training's forward is ``prefill``'s hidden states (the cache
it also builds is dead code there, and the compiler drops it), so the
smoke tests (train path) validate the very math the serving paths use:

  template(cfg)                   -> pytree of PT
  prefill(cfg, p, x, ctx)         -> (x, cache_slice)
  decode(cfg, p, x, cache, ctx, at=()) -> (x, new_cache)
  cache_template(cfg, B, ctx)     -> pytree of PT (cache shapes/axes/dtypes)

In decode, ``at`` holds the layer's indices into a cache stacked over
layers (empty: ``cache`` is the layer's own). A scanned segment carries
its whole stacked cache through the scan, and each block updates its own
layer in place, by the kind of its state: attention writes the new
position's k/v into its slot of layer ``at`` and reads its layer's view of
the result; recurrent states (mLSTM, sLSTM, Mamba) are written back whole
at ``at``; image k/v are only read.

An MoE layer's expert weights may come whole, stacked over the segment's
layers (the serving scans pass them so, with no sharding rules): the
expert kernel reads them in place at the layer ``at`` (the attention
block's prefill takes ``at`` too). Its cache holds the routing's
counters, int32 [2, 3]: prefill's row and the decode steps' row, each
[rows routed, experts that got a row, most rows one expert got], the
decode row summed over steps (its last entry the largest).

Blocks are assembled into models by ``model.py`` as *segments* (scanned
stacks of identical blocks, or single unrolled blocks where the arch is
non-uniform: Hymba's 3 global-attention layers, xLSTM's sLSTM positions).

Each part of a block runs under a ``jax.named_scope`` from
``scopes.SCOPES`` (``attn``, ``kv_write``, ``mlp``, ``moe``, ``ssm``,
``cross_attn``), so a profiler trace names it; scopes are metadata only.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..configs.base import ArchConfig
from ..distributed.sharding import constrain
from .attention import attention, cross_attention, decode_attention, sink_banded_attention
from .layers import PT, apply_rope, rms_norm, swiglu
from .mamba import MambaState, mamba_decode_mix, mamba_mix
from .moe import moe_ffn
from .ssm import (
    mlstm_chunked,
    mlstm_decode_step,
    slstm_decode_step,
    slstm_scan,
)

__all__ = ["Block", "BlockCtx", "BLOCKS", "stackify", "rope_at"]


@dataclass(frozen=True)
class BlockCtx:
    """Per-segment static + per-call dynamic context."""

    rope: Optional[Tuple[jax.Array, jax.Array]] = None  # cos/sin [S, hd/2]
    window: int = 0            # 0 = full attention
    n_sink: int = 0            # always-attended prefix (Hymba meta tokens)
    causal: bool = True
    img: Optional[jax.Array] = None     # [B, I, d] image embeddings (VLM)
    pos: Optional[jax.Array] = None     # scalar int32 decode position
    smax: int = 0              # cache capacity (decode)
    q_chunk: int = 512


@dataclass(frozen=True)
class Block:
    kind: str
    template: Callable[[ArchConfig], Any]
    prefill: Callable[..., Tuple[jax.Array, Any]]
    decode: Callable[..., Tuple[jax.Array, Any]]
    cache_template: Callable[[ArchConfig, int, BlockCtx], Any]


def stackify(tmpl, n: int):
    """Add a leading 'stack' dim of size n to every PT in a template tree."""
    return jax.tree_util.tree_map(
        lambda t: replace(t, shape=(n,) + t.shape, axes=("stack",) + t.axes),
        tmpl,
        is_leaf=lambda x: isinstance(x, PT),
    )


def rope_at(pos: jax.Array, head_dim: int, theta: float):
    """cos/sin [1, hd/2] at a single (traced) position."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32) * freqs
    return jnp.cos(ang)[None], jnp.sin(ang)[None]


def _layer_of(a: jax.Array, at: Tuple[jax.Array, ...]) -> jax.Array:
    """The layer's view of a cache array stacked over the indices ``at``."""
    n = len(at)
    start = tuple(at) + (0,) * (a.ndim - n)
    return jax.lax.dynamic_slice(a, start, (1,) * n + a.shape[n:]).reshape(
        a.shape[n:])


def _put_at(a: jax.Array, new: jax.Array, at: Tuple[jax.Array, ...],
            *offsets) -> jax.Array:
    """Write ``new`` into layer ``at`` of the stacked array ``a``, at
    ``offsets`` within the layer (zero where not given)."""
    n = len(at)
    start = tuple(at) + offsets + (0,) * (a.ndim - n - len(offsets))
    return jax.lax.dynamic_update_slice(
        a, new.astype(a.dtype).reshape((1,) * n + new.shape), start)


def _store(cache, new, at: Tuple[jax.Array, ...]):
    """Write a layer's whole new recurrent state back at ``at``, in the
    cache's dtype."""
    return {k: _put_at(cache[k], v, at) for k, v in new.items()}


def _res_scale(cfg: ArchConfig) -> float:
    # MiniCPM depth-scaled residuals: scale_depth / sqrt(n_layers).
    return cfg.scale_depth / math.sqrt(cfg.n_layers) if cfg.scale_depth > 0 else 1.0


# ---------------------------------------------------------------------------
# attention (+ dense-FFN / MoE-FFN) block — dense, moe, encoder families
# ---------------------------------------------------------------------------

def _attn_template(cfg: ArchConfig) -> Dict[str, Any]:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    p: Dict[str, Any] = {
        "ln1": PT((d,), (None,), init="ones"),
        "wq": PT((d, H, hd), ("embed", "heads", None), fan_in=d),
        "wk": PT((d, KV, hd), ("embed", "kv_heads", None), fan_in=d),
        "wv": PT((d, KV, hd), ("embed", "kv_heads", None), fan_in=d),
        "wo": PT((H, hd, d), ("heads", None, "embed"), fan_in=H * hd),
        "ln2": PT((d,), (None,), init="ones"),
    }
    if cfg.qkv_bias:
        p["bq"] = PT((H, hd), ("heads", None), init="zeros")
        p["bk"] = PT((KV, hd), ("kv_heads", None), init="zeros")
        p["bv"] = PT((KV, hd), ("kv_heads", None), init="zeros")
    if cfg.qk_norm:
        p["q_norm"] = PT((hd,), (None,), init="ones")
        p["k_norm"] = PT((hd,), (None,), init="ones")
    if cfg.is_moe:
        E, f = cfg.n_experts, cfg.d_ff
        p["router"] = PT((d, E), ("embed", None))
        p["we_gate"] = PT((E, d, f), ("expert", "embed", None))
        p["we_up"] = PT((E, d, f), ("expert", "embed", None))
        p["we_down"] = PT((E, f, d), ("expert", None, "embed"))
    else:
        f = cfg.d_ff
        p["wg"] = PT((d, f), ("embed", "ff"))
        p["wi"] = PT((d, f), ("embed", "ff"))
        p["wo2"] = PT((f, d), ("ff", "embed"))
    return p


def _qkv(cfg: ArchConfig, p, h, rope):
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope is not None:
        cos, sin = rope
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = constrain(q, "batch", "act_seq", "heads", None)
    return q, k, v


def _ffn_scope(cfg: ArchConfig):
    return jax.named_scope("moe" if cfg.is_moe else "mlp")


def _moe(cfg: ArchConfig, p, h, at=()):
    """The MoE FFN of ``h``: (y, counters). Expert weights stacked over
    layers (4-d) are read at layer ``at``."""
    layer = at[0] if p["we_gate"].ndim == 4 else None
    return moe_ffn(
        h, p["router"], p["we_gate"], p["we_up"], p["we_down"],
        k=cfg.experts_per_token, n_experts=cfg.n_experts,
        capacity_factor=cfg.capacity_factor, layer=layer,
    )


def _ffn(cfg: ArchConfig, p, x, res, at=()):
    """x + FFN(x); returns (x, routing counters or None)."""
    counts = None
    with _ffn_scope(cfg):
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        h2 = constrain(h2, "batch", "act_seq", None)
        if cfg.is_moe:
            f, counts = _moe(cfg, p, h2, at)
        else:
            f = swiglu(h2, p["wg"], p["wi"], p["wo2"])
        x = x + f * res
        return constrain(x, "batch", "act_seq", None), counts


def _attn_cache_len(cfg: ArchConfig, ctx: BlockCtx) -> int:
    if ctx.window > 0:
        return ctx.n_sink + ctx.window
    return ctx.smax


def _kv_axes(ctx: BlockCtx):
    """Logical axes of a layer's k/v cache [B, KV, W, hd]: each head's
    slots are one [W, hd] block, the operand layout of decode's score and
    PV contractions."""
    return ("batch", "kv_heads", "kv_seq" if ctx.window == 0 else None, None)


def _attn_cache_template(cfg: ArchConfig, B: int, ctx: BlockCtx):
    KV, hd = cfg.n_kv_heads, cfg.hd
    W = _attn_cache_len(cfg, ctx)
    spec = PT((B, KV, W, hd), _kv_axes(ctx), init="zeros")
    c = {"k": spec, "v": spec}
    if cfg.is_moe:
        c["moe"] = PT((2, 3), (None, None), init="zeros", dtype="int32")
    return c


def _prompt_attn(cfg: ArchConfig, p, x, ctx: BlockCtx):
    """Self-attention over the prompt, before ``wo``: ``ln1``, q/k/v, then
    sink-banded or plain attention. Returns (h, o [B,S,H,hd], k, v)."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    h = constrain(h, "batch", "act_seq", None)
    q, k, v = _qkv(cfg, p, h, ctx.rope)
    if ctx.window > 0 and ctx.n_sink > 0:
        o = sink_banded_attention(q, k, v, window=ctx.window,
                                  n_sink=ctx.n_sink, q_chunk=ctx.q_chunk)
    else:
        o = attention(q, k, v, causal=ctx.causal, window=ctx.window,
                      q_chunk=ctx.q_chunk)
    return h, o, k, v


def _step_attn(cfg: ArchConfig, p, h, cache, ctx: BlockCtx, at=()):
    """One decode position's self-attention on ``h`` = ln1(x) [B,1,d]:
    writes its k/v into layer ``at`` of the cache and attends over that
    layer. Returns (o [B,1,d] after ``wo``, new k cache, new v cache)."""
    rope = (rope_at(ctx.pos, cfg.hd, cfg.rope_theta)
            if ctx.rope is not None else None)
    q, k, v = _qkv(cfg, p, h, rope)
    # decode shards the CACHE over 'model' (flash-decoding); q must keep
    # heads replicated or GSPMD all-gathers the cache slice every layer
    q = constrain(q, "batch", None, None, None)
    ck, cv, valid = _write_kv(cache, k, v, ctx.pos, ctx, at)
    o = decode_attention(q, _layer_of(ck, at), _layer_of(cv, at), valid)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), ck, cv


def _attn_prefill(cfg: ArchConfig, p, x, ctx: BlockCtx, at=()):
    """The layer over the prompt, and its cache slice from this layer's K/V
    (and the routing's counters)."""
    res = _res_scale(cfg)
    with jax.named_scope("attn"):
        _, o, k, v = _prompt_attn(cfg, p, x, ctx)
        # named for selective remat: policy 'save-attn' keeps this
        # [B,S,H,hd] tensor so backward never re-runs the O(S^2) score
        # pipeline
        o = checkpoint_name(o, "attn_out")
        o = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        x = x + o * res
    x, counts = _ffn(cfg, p, x, res, at)
    with jax.named_scope("kv_write"):
        cache = _pack_attn_cache(cfg, k, v, ctx)
        cache = {n: constrain(a, *_kv_axes(ctx)) for n, a in cache.items()}
    if counts is not None:
        cache["moe"] = jnp.stack([counts, jnp.zeros_like(counts)])
    return x, cache


def _attn_decode(cfg: ArchConfig, p, x, cache, ctx: BlockCtx, at=()):
    """x [B,1,d]; cache {k,v [*stack,B,KV,W,hd]}; ctx.pos = absolute
    position."""
    res = _res_scale(cfg)
    with jax.named_scope("attn"):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        o, ck, cv = _step_attn(cfg, p, h, cache, ctx, at)
        x = x + o * res
    x, counts = _ffn_decode(cfg, p, x, res, at)
    new = {"k": ck, "v": cv}
    if counts is not None:
        new["moe"] = _add_counts(cache["moe"], counts, at)
    return x, new


def _add_counts(stacked, counts, at):
    """Add one decode step's counters to the decode row of layer ``at``
    (the largest group: the larger of the two)."""
    old = _layer_of(stacked, at)[1]
    row = jnp.concatenate([old[:2] + counts[:2],
                           jnp.maximum(old[2:], counts[2:])])
    return _put_at(stacked, row[None], at, 1)


def _write_kv(cache, k, v, pos, ctx: BlockCtx, at=()):
    """Write one position's k/v [B,1,KV,hd] into layer ``at`` of the cache
    at its slot (ring slots past the sink for windowed layers), in place;
    returns the new cache arrays and the mask of valid slots."""
    W = cache["k"].shape[len(at) + 2]
    if ctx.window == 0:
        slot = pos
        valid = jnp.arange(W) <= pos
    else:
        ns = ctx.n_sink
        slot = jnp.where(pos < ns, pos, ns + (pos - ns) % ctx.window)
        valid = (jnp.arange(W) <= pos) | (pos >= W)
    axes = ("stack",) * len(at) + _kv_axes(ctx)
    with jax.named_scope("kv_write"):
        # [B,1,KV,hd] -> [B,KV,1,hd], through a flat barrier: the compiler
        # then lays this small update out like the cache, instead of laying
        # a carried cache out like the projection that made the update
        # (which costs two copies of the whole cache per step)
        B, _, KV, hd = k.shape
        k, v = (a.reshape(B, KV, 1, hd) for a in
                jax.lax.optimization_barrier((k.reshape(-1), v.reshape(-1))))
        ck = constrain(_put_at(cache["k"], k, at, 0, 0, slot), *axes)
        cv = constrain(_put_at(cache["v"], v, at, 0, 0, slot), *axes)
    return ck, cv, valid


def _ffn_decode(cfg: ArchConfig, p, x, res, at=()):
    counts = None
    with _ffn_scope(cfg):
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.is_moe:
            f, counts = _moe(cfg, p, h2, at)
        else:
            f = swiglu(h2, p["wg"], p["wi"], p["wo2"])
        return x + f * res, counts


ATTN_BLOCK = Block(
    kind="attn",
    template=_attn_template,
    prefill=_attn_prefill,
    decode=_attn_decode,
    cache_template=_attn_cache_template,
)


# ---------------------------------------------------------------------------
# cross-attention block (Llama-3.2-Vision): q from text, kv from image tokens
# ---------------------------------------------------------------------------

def _cross_template(cfg: ArchConfig) -> Dict[str, Any]:
    d, H, KV, hd, f = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_ff
    return {
        "ln1": PT((d,), (None,), init="ones"),
        "wq": PT((d, H, hd), ("embed", "heads", None), fan_in=d),
        "wk": PT((d, KV, hd), ("embed", "kv_heads", None), fan_in=d),
        "wv": PT((d, KV, hd), ("embed", "kv_heads", None), fan_in=d),
        "wo": PT((H, hd, d), ("heads", None, "embed"), fan_in=H * hd),
        "q_norm": PT((hd,), (None,), init="ones"),
        "k_norm": PT((hd,), (None,), init="ones"),
        "gate_attn": PT((), (), init="zeros"),
        "ln2": PT((d,), (None,), init="ones"),
        "wg": PT((d, f), ("embed", "ff")),
        "wi": PT((d, f), ("embed", "ff")),
        "wo2": PT((f, d), ("ff", "embed")),
        "gate_ffn": PT((), (), init="zeros"),
    }


def _img_kv(p, img, eps):
    with jax.named_scope("cross_attn"):
        k = jnp.einsum("bid,dkh->bikh", img, p["wk"])
        v = jnp.einsum("bid,dkh->bikh", img, p["wv"])
        k = rms_norm(k, p["k_norm"], eps)
        return k, v


def _cross_core(cfg, p, x, k_img, v_img):
    with jax.named_scope("cross_attn"):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        q = constrain(q, "batch", "act_seq", "heads", None)
        o = cross_attention(q, k_img, v_img)
        o = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        x = x + jnp.tanh(p["gate_attn"]) * o
    with jax.named_scope("mlp"):
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        f = swiglu(h2, p["wg"], p["wi"], p["wo2"])
        x = x + jnp.tanh(p["gate_ffn"]) * f
        return constrain(x, "batch", "act_seq", None)


def _cross_prefill(cfg, p, x, ctx: BlockCtx):
    k_img, v_img = _img_kv(p, ctx.img, cfg.norm_eps)
    return _cross_core(cfg, p, x, k_img, v_img), {"k": k_img, "v": v_img}


def _cross_decode(cfg, p, x, cache, ctx: BlockCtx, at=()):
    # the image k/v are read only: the stacked cache passes through whole
    x = _cross_core(cfg, p, x, _layer_of(cache["k"], at),
                    _layer_of(cache["v"], at))
    return x, cache


def _cross_cache_template(cfg: ArchConfig, B: int, ctx: BlockCtx):
    KV, hd, I = cfg.n_kv_heads, cfg.hd, cfg.n_image_tokens
    spec = PT((B, I, KV, hd), ("batch", None, "kv_heads", None), init="zeros")
    return {"k": spec, "v": spec}


CROSS_BLOCK = Block(
    kind="cross",
    template=_cross_template,
    prefill=_cross_prefill,
    decode=_cross_decode,
    cache_template=_cross_cache_template,
)


# ---------------------------------------------------------------------------
# hybrid block (Hymba): parallel attention + Mamba heads on the same input,
# outputs normalized and fused, then dense FFN.
# ---------------------------------------------------------------------------

def _dt_rank(cfg: ArchConfig) -> int:
    return max(1, -(-cfg.d_model // 16))


def _hybrid_template(cfg: ArchConfig) -> Dict[str, Any]:
    d, H, KV, hd, f = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_ff
    di = cfg.ssm_expand * d
    n, K, dtr = cfg.ssm_state, cfg.ssm_conv, _dt_rank(cfg)
    return {
        "ln1": PT((d,), (None,), init="ones"),
        # attention branch
        "wq": PT((d, H, hd), ("embed", "heads", None), fan_in=d),
        "wk": PT((d, KV, hd), ("embed", "kv_heads", None), fan_in=d),
        "wv": PT((d, KV, hd), ("embed", "kv_heads", None), fan_in=d),
        "wo": PT((H, hd, d), ("heads", None, "embed"), fan_in=H * hd),
        "norm_attn": PT((d,), (None,), init="ones"),
        # mamba branch
        "w_in": PT((d, 2 * di), ("embed", "ssm_inner")),
        "conv_w": PT((di, K), ("ssm_inner", None), init="small"),
        "w_x": PT((di, dtr + 2 * n), ("ssm_inner", None)),
        "w_dt": PT((dtr, di), (None, "ssm_inner")),
        "b_dt": PT((di,), ("ssm_inner",), init="small"),
        "a_log": PT((di, n), ("ssm_inner", None), init="small"),
        "d_skip": PT((di,), ("ssm_inner",), init="ones"),
        "wo_m": PT((di, d), ("ssm_inner", "embed")),
        "norm_ssm": PT((d,), (None,), init="ones"),
        # fusion + FFN
        "ln2": PT((d,), (None,), init="ones"),
        "wg": PT((d, f), ("embed", "ff")),
        "wi": PT((d, f), ("embed", "ff")),
        "wo2": PT((f, d), ("ff", "embed")),
    }


def _hybrid_mamba(cfg, p, h, state=None):
    """The Mamba heads on ``h`` = ln1(x): over the prompt from a zero
    state, or one decode step from ``state``. Returns (out, final state)."""
    with jax.named_scope("ssm"):
        xz = jnp.einsum("bsd,de->bse", h, p["w_in"])
        xz = constrain(xz, "batch", None, "ssm_inner")
        x_in, z = jnp.split(xz, 2, axis=-1)
        args = (x_in, z, p["conv_w"], p["w_x"], p["w_dt"], p["b_dt"],
                p["a_log"], p["d_skip"])
        kw = dict(n_state=cfg.ssm_state, dt_rank=_dt_rank(cfg))
        if state is None:
            y, st = mamba_mix(*args, **kw)
        else:
            y, st = mamba_decode_mix(*args, state=state, **kw)
        return jnp.einsum("bsd,de->bse", y, p["wo_m"]), st


def _hybrid_fuse(cfg, p, x, o_attn, o_ssm):
    with jax.named_scope("mlp"):
        fused = 0.5 * (rms_norm(o_attn, p["norm_attn"], cfg.norm_eps)
                       + rms_norm(o_ssm, p["norm_ssm"], cfg.norm_eps))
        x = x + fused
        x = constrain(x, "batch", "act_seq", None)
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        f = swiglu(h2, p["wg"], p["wi"], p["wo2"])
        return constrain(x + f, "batch", "act_seq", None)


def _hybrid_cache_template(cfg: ArchConfig, B: int, ctx: BlockCtx):
    di = cfg.ssm_expand * cfg.d_model
    c = _attn_cache_template(cfg, B, ctx)
    c["conv"] = PT((B, di, cfg.ssm_conv - 1), ("batch", "ssm_inner", None),
                   init="zeros", dtype="float32")
    c["ssm"] = PT((B, di, cfg.ssm_state), ("batch", "ssm_inner", None),
                  init="zeros", dtype="float32")
    return c


def _hybrid_prefill(cfg, p, x, ctx: BlockCtx):
    with jax.named_scope("attn"):
        h, o, k, v = _prompt_attn(cfg, p, x, ctx)
        o_attn = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    o_ssm, st = _hybrid_mamba(cfg, p, h)
    xo = _hybrid_fuse(cfg, p, x, o_attn, o_ssm)

    # attention cache (same ring layout as ATTN_BLOCK.prefill)
    with jax.named_scope("kv_write"):
        cache = _pack_attn_cache(cfg, k, v, ctx)
    with jax.named_scope("ssm"):
        cache["conv"] = st.conv.astype(jnp.float32)
        cache["ssm"] = st.ssm.astype(jnp.float32)
    return xo, cache


def _pack_attn_cache(cfg, k, v, ctx: BlockCtx):
    B, S = k.shape[0], k.shape[1]
    W = _attn_cache_len(cfg, ctx)
    KV, hd = cfg.n_kv_heads, cfg.hd
    ck = jnp.zeros((B, W, KV, hd), k.dtype)
    cv = jnp.zeros((B, W, KV, hd), v.dtype)
    if ctx.window == 0:
        n = min(S, W)
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k[:, :n], 0, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v[:, :n], 0, axis=1)
    else:
        ns = ctx.n_sink
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k[:, :ns], 0, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v[:, :ns], 0, axis=1)
        tail = min(ctx.window, S - ns)
        start = (S - tail - ns) % ctx.window
        idx = ns + (start + jnp.arange(tail)) % ctx.window
        ck = ck.at[:, idx].set(k[:, S - tail:])
        cv = cv.at[:, idx].set(v[:, S - tail:])
    return {"k": ck.swapaxes(1, 2), "v": cv.swapaxes(1, 2)}


def _hybrid_decode(cfg, p, x, cache, ctx: BlockCtx, at=()):
    with jax.named_scope("attn"):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        o_attn, ck, cv = _step_attn(cfg, p, h, cache, ctx, at)
    st = MambaState(conv=_layer_of(cache["conv"], at),
                    ssm=_layer_of(cache["ssm"], at))
    o_ssm, st = _hybrid_mamba(cfg, p, h, state=st)
    xo = _hybrid_fuse(cfg, p, x, o_attn, o_ssm)
    with jax.named_scope("ssm"):
        state = _store(cache, {"conv": st.conv, "ssm": st.ssm}, at)
    return xo, {"k": ck, "v": cv, **state}


HYBRID_BLOCK = Block(
    kind="hybrid",
    template=_hybrid_template,
    prefill=_hybrid_prefill,
    decode=_hybrid_decode,
    cache_template=_hybrid_cache_template,
)


# ---------------------------------------------------------------------------
# mLSTM block (xLSTM) — cell is the whole layer (no separate FFN)
# ---------------------------------------------------------------------------

def _mlstm_template(cfg: ArchConfig) -> Dict[str, Any]:
    d, H, hd = cfg.d_model, cfg.n_heads, cfg.hd
    return {
        "ln": PT((d,), (None,), init="ones"),
        "wq": PT((d, H, hd), ("embed", "heads", None), fan_in=d),
        "wk": PT((d, H, hd), ("embed", "heads", None), fan_in=d),
        "wv": PT((d, H, hd), ("embed", "heads", None), fan_in=d),
        "w_if": PT((d, H, 2), ("embed", "heads", None), init="small"),
        "b_if": PT((H, 2), ("heads", None), init="zeros"),
        "wz": PT((d, d), ("embed", None)),
        "norm_cell": PT((d,), (None,), init="ones"),
        "wo": PT((d, d), (None, "embed")),
    }


def _mlstm_io(cfg, p, x):
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    h = constrain(h, "batch", "act_seq", None)
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
    gates = jnp.einsum("bsd,dhg->bshg", h, p["w_if"]) + p["b_if"]
    z = jnp.einsum("bsd,de->bse", h, p["wz"])
    return q, k, v, gates[..., 0], gates[..., 1], z


def _mlstm_out(cfg, p, x, hc, z):
    B, S = z.shape[0], z.shape[1]
    hc = rms_norm(hc.reshape(B, S, cfg.d_model), p["norm_cell"], cfg.norm_eps)
    out = hc * jax.nn.silu(z.astype(jnp.float32)).astype(hc.dtype)
    out = jnp.einsum("bsd,de->bse", out, p["wo"])
    return constrain(x + out, "batch", "act_seq", None)


def _mlstm_cache_template(cfg: ArchConfig, B: int, ctx: BlockCtx):
    H, hd = cfg.n_heads, cfg.hd
    return {
        "C": PT((B, H, hd, hd), ("batch", "heads", None, None),
                init="zeros", dtype="float32"),
        "n": PT((B, H, hd), ("batch", "heads", None),
                init="zeros", dtype="float32"),
        "m": PT((B, H), ("batch", "heads"), init="neg_inf", dtype="float32"),
    }


def _mlstm_prefill(cfg, p, x, ctx: BlockCtx):
    with jax.named_scope("ssm"):
        q, k, v, ig, fg, z = _mlstm_io(cfg, p, x)
        hc, (C, n, m) = mlstm_chunked(q, k, v, ig, fg)
        return _mlstm_out(cfg, p, x, hc, z), {"C": C, "n": n, "m": m}


def _mlstm_decode(cfg, p, x, cache, ctx: BlockCtx, at=()):
    with jax.named_scope("ssm"):
        q, k, v, ig, fg, z = _mlstm_io(cfg, p, x)
        hc, (C, n, m) = mlstm_decode_step(
            q, k, v, ig, fg, tuple(_layer_of(cache[s], at) for s in "Cnm")
        )
        return (_mlstm_out(cfg, p, x, hc, z),
                _store(cache, {"C": C, "n": n, "m": m}, at))


MLSTM_BLOCK = Block(
    kind="mlstm",
    template=_mlstm_template,
    prefill=_mlstm_prefill,
    decode=_mlstm_decode,
    cache_template=_mlstm_cache_template,
)


# ---------------------------------------------------------------------------
# sLSTM block (xLSTM) — sequential scalar-memory cell + gated FFN
# ---------------------------------------------------------------------------

def _slstm_template(cfg: ArchConfig) -> Dict[str, Any]:
    d, H, hd = cfg.d_model, cfg.n_heads, cfg.hd
    f2 = 2 * d
    return {
        "ln": PT((d,), (None,), init="ones"),
        "w_gates": PT((d, H, 4, hd), ("embed", "heads", None, None), fan_in=d),
        "b_gates": PT((H, 4, hd), ("heads", None, None), init="zeros"),
        "r_gates": PT((H, hd, 4, hd), ("heads", None, None, None), init="small"),
        "norm_cell": PT((d,), (None,), init="ones"),
        "wo": PT((d, d), (None, "embed")),
        "ln2": PT((d,), (None,), init="ones"),
        "wg": PT((d, f2), ("embed", "ff")),
        "wi": PT((d, f2), ("embed", "ff")),
        "wo2": PT((f2, d), ("ff", "embed")),
    }


def _slstm_gates(cfg, p, x):
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    gx = jnp.einsum("bsd,dhgk->bshgk", h, p["w_gates"]) + p["b_gates"]
    return gx


def _slstm_post(cfg, p, x, hs):
    with jax.named_scope("ssm"):
        B, S = x.shape[0], x.shape[1]
        hc = rms_norm(hs.reshape(B, S, cfg.d_model), p["norm_cell"],
                      cfg.norm_eps)
        x = x + jnp.einsum("bsd,de->bse", hc, p["wo"])
    with jax.named_scope("mlp"):
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        f = swiglu(h2, p["wg"], p["wi"], p["wo2"])
        return constrain(x + f, "batch", "act_seq", None)


def _slstm_cache_template(cfg: ArchConfig, B: int, ctx: BlockCtx):
    H, hd = cfg.n_heads, cfg.hd
    v = PT((B, H, hd), ("batch", "heads", None), init="zeros", dtype="float32")
    return {"c": v, "n": PT((B, H, hd), ("batch", "heads", None), init="ones",
                            dtype="float32"),
            "h": v, "m": PT((B, H, hd), ("batch", "heads", None),
                            init="neg_inf", dtype="float32")}


def _slstm_prefill(cfg, p, x, ctx: BlockCtx):
    with jax.named_scope("ssm"):
        gx = _slstm_gates(cfg, p, x)
        hs, (c, n, h, m) = slstm_scan(gx, p["r_gates"])
    return _slstm_post(cfg, p, x, hs), {"c": c, "n": n, "h": h, "m": m}


def _slstm_decode(cfg, p, x, cache, ctx: BlockCtx, at=()):
    with jax.named_scope("ssm"):
        gx = _slstm_gates(cfg, p, x)
        hs, (c, n, h, m) = slstm_decode_step(
            gx, p["r_gates"], tuple(_layer_of(cache[s], at) for s in "cnhm")
        )
        state = _store(cache, {"c": c, "n": n, "h": h, "m": m}, at)
    return _slstm_post(cfg, p, x, hs), state


SLSTM_BLOCK = Block(
    kind="slstm",
    template=_slstm_template,
    prefill=_slstm_prefill,
    decode=_slstm_decode,
    cache_template=_slstm_cache_template,
)


BLOCKS: Dict[str, Block] = {
    b.kind: b for b in (ATTN_BLOCK, CROSS_BLOCK, HYBRID_BLOCK, MLSTM_BLOCK,
                        SLSTM_BLOCK)
}
