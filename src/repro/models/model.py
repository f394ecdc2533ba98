"""Model assembly: configs -> segments -> full train/prefill/decode programs.

An architecture is a list of **segments**: a scanned stack of identical
blocks (``jax.lax.scan`` over stacked params for O(1) compile scaling) or a
single unrolled block where the arch is non-uniform:

  dense / moe / audio   [attn x L]
  vlm                   [vlm_group x G]           (nested scan: 4 self + 1 cross)
  ssm (xLSTM)           [mlstm runs] + [slstm singles] at cfg.slstm_layers
  hybrid (Hymba)        [SWA-hybrid runs] + [global-attn hybrid singles]

Each block kind has one forward path, its ``prefill``, and two walkers run
it over the segments. ``_apply_segment`` is training's forward: it
rematerialises each layer, scans over per-layer expert weights (each
layer's gradient then covers its own experts, not the whole stack) and
drops the caches. ``_prefill_segment`` is serving's: it collects the caches. Decode
carries a scanned segment's stacked cache through its scan, so each step
updates the donated cache in place (``_decode_segment``). With no sharding
rules, serving scans pass an MoE segment's expert weights whole and the
expert kernel reads each layer's in place (``_whole_weights``); the
routing's counters ride in the cache (``moe_counters``).
Each part runs under a ``jax.named_scope`` of ``scopes.SCOPES``: ``embed``,
``layers`` (each segment's stack), ``final_norm`` and ``lm_head`` here, the
block's parts in ``blocks.py``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig, ShapeSpec
from ..distributed.sharding import active_rules, constrain
from .blocks import BLOCKS, BlockCtx, stackify
from .moe import EXPERT_WEIGHTS
from .layers import (
    PT,
    abstract_params,
    cross_entropy_chunked,
    init_params,
    param_pspecs,
    rms_norm,
    rope_table,
)

__all__ = ["Model", "Segment", "plan_segments", "build_model"]


@dataclass(frozen=True)
class Segment:
    kind: str                  # block kind, or "vlm_group"
    n: int                     # number of layers in this segment
    scanned: bool
    window: int = 0
    n_sink: int = 0
    causal: bool = True
    inner: int = 0             # vlm_group: self layers per group


def _runs(total: int, singles: Tuple[int, ...]):
    """Split [0, total) into (is_single, start, length) runs."""
    out = []
    i = 0
    singles = sorted(singles)
    for s in singles:
        if s > i:
            out.append((False, i, s - i))
        out.append((True, s, 1))
        i = s + 1
    if i < total:
        out.append((False, i, total - i))
    return out


def plan_segments(cfg: ArchConfig) -> List[Segment]:
    if cfg.family in ("dense", "moe"):
        return [Segment("attn", cfg.n_layers, True, window=cfg.sliding_window)]
    if cfg.family == "audio":
        return [Segment("attn", cfg.n_layers, True, causal=False)]
    if cfg.family == "vlm":
        g = cfg.n_layers // (cfg.cross_attn_every + 1)
        return [Segment("vlm_group", g, True, inner=cfg.cross_attn_every)]
    if cfg.family == "ssm":
        segs = []
        for single, start, n in _runs(cfg.n_layers, cfg.slstm_layers):
            segs.append(Segment("slstm" if single else "mlstm", n, not single))
        return segs
    if cfg.family == "hybrid":
        segs = []
        for single, start, n in _runs(cfg.n_layers, cfg.global_attn_layers):
            if single:
                segs.append(Segment("hybrid", 1, False, window=0,
                                    n_sink=0))
            else:
                segs.append(Segment("hybrid", n, True,
                                    window=cfg.sliding_window,
                                    n_sink=cfg.n_meta_tokens))
        return segs
    raise ValueError(f"unknown family {cfg.family}")


def _uses_rope(cfg: ArchConfig) -> bool:
    return cfg.family not in ("ssm", "audio")


class Model:
    """One architecture's full program set, built from its ArchConfig."""

    def __init__(self, cfg: ArchConfig, *, remat: bool = True,
                 remat_policy: str = "full", ce_chunks: int = 8,
                 q_chunk: int = 512):
        self.cfg = cfg
        self.segments = plan_segments(cfg)
        self.remat = remat
        # 'full' = nothing saveable (paper-faithful baseline);
        # 'save-attn' = keep the named attention outputs (skips the O(S^2)
        # score recompute in backward — §Perf iteration; costs
        # L*B*S*H*hd*2 bytes of HBM, use where that fits)
        self.remat_policy = remat_policy
        self.ce_chunks = ce_chunks
        self.q_chunk = q_chunk

    # ------------------------------------------------------------------
    # parameter templates
    # ------------------------------------------------------------------
    def template(self) -> Dict[str, Any]:
        cfg = self.cfg
        d = cfg.d_model
        t: Dict[str, Any] = {}
        if cfg.family == "audio":
            # frontend stub: frames arrive at d_model; learned input norm
            t["in_norm"] = PT((d,), (None,), init="ones")
        else:
            t["embed"] = PT((cfg.padded_vocab, d), (None, "embed"),
                            fan_in=d)
        if cfg.n_meta_tokens:
            t["meta"] = PT((cfg.n_meta_tokens, d), (None, None), init="small")
        segs = []
        for seg in self.segments:
            segs.append(self._seg_template(seg))
        t["segments"] = segs
        t["final_norm"] = PT((d,), (None,), init="ones")
        if not cfg.tie_embeddings:
            t["head"] = PT((d, cfg.padded_vocab), ("embed", "vocab"),
                           fan_in=d)
        return t

    def _seg_template(self, seg: Segment):
        cfg = self.cfg
        if seg.kind == "vlm_group":
            grp = {
                "self": stackify(stackify(BLOCKS["attn"].template(cfg),
                                          seg.inner), seg.n),
                "cross": stackify(BLOCKS["cross"].template(cfg), seg.n),
            }
            return grp
        tmpl = BLOCKS[seg.kind].template(cfg)
        return stackify(tmpl, seg.n) if seg.scanned else tmpl

    def init(self, key: jax.Array, dtype=jnp.bfloat16):
        return init_params(self.template(), key, dtype)

    def abstract(self, dtype=jnp.bfloat16):
        return abstract_params(self.template(), dtype)

    def pspecs(self, rules):
        return param_pspecs(self.template(), rules)

    # ------------------------------------------------------------------
    # batch templates (inputs)
    # ------------------------------------------------------------------
    def batch_template(self, shape: ShapeSpec) -> Dict[str, Any]:
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        tok = PT((B, S), ("batch", None), init="zeros", dtype="int32")
        if shape.kind == "train":
            b = {"labels": PT((B, S), ("batch", None), init="zeros",
                              dtype="int32")}
            if cfg.family == "audio":
                b["frames"] = PT((B, S, cfg.d_model), ("batch", None, None))
            else:
                b["tokens"] = tok
            if cfg.family == "vlm":
                b["images"] = PT((B, cfg.n_image_tokens, cfg.d_model),
                                 ("batch", None, None))
            return b
        if shape.kind == "prefill":
            b = {}
            if cfg.family == "audio":
                b["frames"] = PT((B, S, cfg.d_model), ("batch", None, None))
            else:
                b["tokens"] = tok
            if cfg.family == "vlm":
                b["images"] = PT((B, cfg.n_image_tokens, cfg.d_model),
                                 ("batch", None, None))
            return b
        # decode: one new token; the big inputs are the cache
        return {"tokens": PT((B, 1), ("batch", None), init="zeros",
                             dtype="int32")}

    # ------------------------------------------------------------------
    # cache templates
    # ------------------------------------------------------------------
    def cache_template(self, B: int, smax: int) -> Dict[str, Any]:
        cfg = self.cfg
        smax_tot = smax + cfg.n_meta_tokens
        segs = []
        for seg in self.segments:
            ctx = self._ctx(seg, smax=smax_tot)
            if seg.kind == "vlm_group":
                grp = {
                    "self": stackify(stackify(
                        BLOCKS["attn"].cache_template(cfg, B, ctx), seg.inner),
                        seg.n),
                    "cross": stackify(
                        BLOCKS["cross"].cache_template(cfg, B, ctx), seg.n),
                }
                segs.append(grp)
            else:
                c = BLOCKS[seg.kind].cache_template(cfg, B, ctx)
                segs.append(stackify(c, seg.n) if seg.scanned else c)
        return {"pos": PT((), (), init="zeros", dtype="int32"),
                "segments": segs}

    def abstract_cache(self, B: int, smax: int, dtype=jnp.bfloat16):
        return abstract_params(self.cache_template(B, smax), dtype)

    def init_cache(self, B: int, smax: int, dtype=jnp.bfloat16):
        # caches are all zeros/ones/neg_inf inits — key is unused
        return init_params(self.cache_template(B, smax),
                           jax.random.PRNGKey(0), dtype)

    def cache_pspecs(self, B: int, smax: int, rules):
        return param_pspecs(self.cache_template(B, smax), rules)

    # ------------------------------------------------------------------
    # forward paths
    # ------------------------------------------------------------------
    def _ctx(self, seg: Segment, rope=None, img=None, pos=None,
             smax: int = 0) -> BlockCtx:
        return BlockCtx(rope=rope, window=seg.window, n_sink=seg.n_sink,
                        causal=seg.causal, img=img, pos=pos, smax=smax,
                        q_chunk=self.q_chunk)

    def _embed(self, params, batch):
        cfg = self.cfg
        with jax.named_scope("embed"):
            if cfg.family == "audio":
                x = batch["frames"].astype(params["in_norm"].dtype)
                x = rms_norm(x, params["in_norm"], cfg.norm_eps)
                # fixed sinusoidal positions (frontend stub has none)
                S, d = x.shape[1], x.shape[2]
                pos = jnp.arange(S, dtype=jnp.float32)[:, None]
                div = jnp.exp(jnp.arange(0, d, 2, jnp.float32) * (-math.log(1e4) / d))
                pe = jnp.zeros((S, d), jnp.float32)
                pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
                pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
                x = x + pe.astype(x.dtype)[None]
            else:
                x = jnp.take(params["embed"], batch["tokens"], axis=0)
                if cfg.scale_emb != 1.0:
                    x = x * cfg.scale_emb
            if cfg.n_meta_tokens:
                B = x.shape[0]
                meta = jnp.broadcast_to(params["meta"][None],
                                        (B,) + params["meta"].shape)
                x = jnp.concatenate([meta.astype(x.dtype), x], axis=1)
            return constrain(x, "batch", "act_seq", None)

    def _rope_for(self, S: int):
        if not _uses_rope(self.cfg):
            return None
        return rope_table(S, self.cfg.hd, self.cfg.rope_theta)

    def _maybe_remat(self, fn):
        if not self.remat:
            return fn
        if self.remat_policy == "save-attn":
            policy = jax.checkpoint_policies.save_only_these_names(
                "attn_out")
        else:
            policy = jax.checkpoint_policies.nothing_saveable
        return jax.checkpoint(fn, policy=policy)

    def forward(self, params, batch, *, for_train: bool = True) -> jax.Array:
        """Embedding -> all segments -> final norm. Returns [B, S(+M), d]."""
        cfg = self.cfg
        x = self._embed(params, batch)
        S = x.shape[1]
        rope = self._rope_for(S)
        img = batch.get("images")
        if img is not None:
            img = img.astype(x.dtype)
        for seg, p in zip(self.segments, params["segments"]):
            ctx = self._ctx(seg, rope=rope, img=img)
            with jax.named_scope("layers"):
                x = self._apply_segment(seg, p, x, ctx, remat=for_train)
        with jax.named_scope("final_norm"):
            return rms_norm(x, params["final_norm"], cfg.norm_eps)

    def _apply_segment(self, seg: Segment, p, x, ctx: BlockCtx, remat: bool):
        cfg = self.cfg
        if seg.kind == "vlm_group":
            attn, cross = BLOCKS["attn"], BLOCKS["cross"]

            def group(xc, gp):
                def one(xc2, lp):
                    return attn.prefill(cfg, lp, xc2, ctx)[0], None
                body = self._maybe_remat(one) if remat else one
                xc, _ = jax.lax.scan(body, xc, gp["self"])
                xc = cross.prefill(cfg, gp["cross"], xc, ctx)[0]
                return xc, None

            gbody = self._maybe_remat(group) if remat else group
            x, _ = jax.lax.scan(gbody, x, p)
            return x
        blk = BLOCKS[seg.kind]

        def fwd(xc, lp):
            return blk.prefill(cfg, lp, xc, ctx)[0]

        if not seg.scanned:
            return (self._maybe_remat(fwd) if remat else fwd)(x, p)

        def body(xc, lp):
            return fwd(xc, lp), None

        body = self._maybe_remat(body) if remat else body
        x, _ = jax.lax.scan(body, x, p)
        return x

    # -- training loss --------------------------------------------------
    def loss(self, params, batch) -> jax.Array:
        cfg = self.cfg
        h = self.forward(params, batch, for_train=True)
        if cfg.n_meta_tokens:
            h = h[:, cfg.n_meta_tokens:]
        with jax.named_scope("lm_head"):
            head = (params["embed"].T if cfg.tie_embeddings
                    else params["head"])
            scale = (1.0 / (cfg.d_model / cfg.dim_model_base)
                     if cfg.dim_model_base else 1.0)
            return cross_entropy_chunked(h, head, batch["labels"],
                                         logit_scale=scale,
                                         n_chunks=self.ce_chunks)

    # -- serving ----------------------------------------------------------
    def _logits(self, params, h_last: jax.Array) -> jax.Array:
        cfg = self.cfg
        with jax.named_scope("lm_head"):
            head = (params["embed"].T if cfg.tie_embeddings
                    else params["head"])
            scale = (1.0 / (cfg.d_model / cfg.dim_model_base)
                     if cfg.dim_model_base else 1.0)
            logits = jnp.einsum("bd,dv->bv", h_last,
                                head).astype(jnp.float32)
            # keep logits vocab-sharded: without this constraint GSPMD
            # chooses to all-gather the (d x V) head in f32 per decode step
            # (~200MB for 150k vocabs) — found via TPU-EM replay of the
            # compiled program
            logits = constrain(logits, "batch", "vocab")
            return logits * scale

    def prefill(self, params, batch, smax: int):
        """Process the prompt; returns (last-token logits [B,V], cache)."""
        cfg = self.cfg
        x = self._embed(params, batch)
        S = x.shape[1]
        rope = self._rope_for(S)
        img = batch.get("images")
        if img is not None:
            img = img.astype(x.dtype)
        smax_tot = smax + cfg.n_meta_tokens
        caches = []
        for seg, p in zip(self.segments, params["segments"]):
            ctx = self._ctx(seg, rope=rope, img=img, smax=smax_tot)
            with jax.named_scope("layers"):
                x, c = self._prefill_segment(seg, p, x, ctx)
            caches.append(c)
        with jax.named_scope("final_norm"):
            h = rms_norm(x, params["final_norm"], cfg.norm_eps)[:, -1]
        logits = self._logits(params, h)
        # pos counts REAL sequence tokens (meta prefix excluded); decode adds
        # the meta offset back when computing absolute cache slots.
        cache = {"pos": jnp.asarray(S - cfg.n_meta_tokens, jnp.int32),
                 "segments": caches}
        return logits, cache

    def _prefill_segment(self, seg: Segment, p, x, ctx: BlockCtx):
        cfg = self.cfg
        if seg.kind == "vlm_group":
            attn, cross = BLOCKS["attn"], BLOCKS["cross"]

            def group(xc, gp):
                def one(xc2, lp):
                    return attn.prefill(cfg, lp, xc2, ctx)
                xc, cs = jax.lax.scan(one, xc, gp["self"])
                xc, cc = cross.prefill(cfg, gp["cross"], xc, ctx)
                return xc, {"self": cs, "cross": cc}

            return jax.lax.scan(group, x, p)
        blk = BLOCKS[seg.kind]
        if not seg.scanned:
            return blk.prefill(cfg, p, x, ctx)
        whole, p = _whole_weights(p)
        if whole:
            def body_at(xc, lpi):
                lp, i = lpi
                return blk.prefill(cfg, {**lp, **whole}, xc, ctx, at=(i,))

            return jax.lax.scan(body_at, x, (p, jnp.arange(seg.n)))

        def body(xc, lp):
            return blk.prefill(cfg, lp, xc, ctx)

        return jax.lax.scan(body, x, p)

    def decode_step(self, params, cache, tokens: jax.Array):
        """One decode step. tokens [B,1] -> (logits [B,V], new cache)."""
        cfg = self.cfg
        pos = cache["pos"] + cfg.n_meta_tokens  # absolute slot incl. meta
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0)
            if cfg.scale_emb != 1.0:
                x = x * cfg.scale_emb
            x = constrain(x, "batch", None, None)
        rope_flag = self._rope_for(1)  # non-None => blocks compute rope_at(pos)
        new_caches = []
        for seg, p, c in zip(self.segments, params["segments"],
                             cache["segments"]):
            ctx = self._ctx(seg, rope=rope_flag, pos=pos)
            with jax.named_scope("layers"):
                x, nc = self._decode_segment(seg, p, x, c, ctx)
            new_caches.append(nc)
        with jax.named_scope("final_norm"):
            h = rms_norm(x, params["final_norm"], cfg.norm_eps)[:, 0]
        logits = self._logits(params, h)
        return logits, {"pos": cache["pos"] + 1, "segments": new_caches}

    def _decode_segment(self, seg: Segment, p, x, c, ctx: BlockCtx):
        """One segment's decode. A scanned segment carries its stacked
        cache through the scan (the donated buffer, updated in place) and
        scans over the layer weights and indices; each block updates its
        own layer of the cache."""
        cfg = self.cfg
        if seg.kind == "vlm_group":
            attn, cross = BLOCKS["attn"], BLOCKS["cross"]

            def group(carry, gpi):
                xc, gc = carry
                gp, g = gpi

                def one(carry2, lpj):
                    lp, j = lpj
                    return attn.decode(cfg, lp, *carry2, ctx, at=(g, j)), None

                (xc, sc), _ = jax.lax.scan(one, (xc, gc["self"]),
                                           (gp["self"], jnp.arange(seg.inner)))
                xc, cc = cross.decode(cfg, gp["cross"], xc, gc["cross"], ctx,
                                      at=(g,))
                return (xc, {"self": sc, "cross": cc}), None

            (x, c), _ = jax.lax.scan(group, (x, c), (p, jnp.arange(seg.n)))
            return x, c
        blk = BLOCKS[seg.kind]
        if not seg.scanned:
            return blk.decode(cfg, p, x, c, ctx)
        whole, p = _whole_weights(p)

        def body(carry, lpi):
            lp, i = lpi
            if whole:
                lp = {**lp, **whole}
            return blk.decode(cfg, lp, *carry, ctx, at=(i,)), None

        (x, c), _ = jax.lax.scan(body, (x, c), (p, jnp.arange(seg.n)))
        return x, c

    @staticmethod
    def moe_counters(cache) -> Dict[str, int]:
        """The routing counters a cache carries, fetched in one transfer
        and summed over layers: rows routed, experts that got a row
        (summed over layers and steps) and the most rows one expert got,
        for prefill and for the decode steps since. Empty for a model
        without experts."""
        arrs = [c["moe"] for c in cache["segments"]
                if isinstance(c, dict) and "moe" in c]
        if not arrs:
            return {}
        a = np.concatenate([np.asarray(x).reshape(-1, 2, 3)
                            for x in jax.device_get(arrs)])
        out = {}
        for r, phase in enumerate(("prefill", "decode")):
            out[f"moe_{phase}_rows"] = int(a[:, r, 0].sum())
            out[f"moe_{phase}_experts"] = int(a[:, r, 1].sum())
            out[f"moe_{phase}_largest"] = int(a[:, r, 2].max())
        return out


def _whole_weights(p):
    """(expert weights the serving scan passes whole, the rest to scan
    over): an MoE segment's stacked expert weights when no sharding rules
    are active, which the expert kernel reads in place per layer; else
    ({}, p)."""
    if active_rules() is not None or EXPERT_WEIGHTS[0] not in p:
        return {}, p
    return ({n: p[n] for n in EXPERT_WEIGHTS},
            {n: v for n, v in p.items() if n not in EXPERT_WEIGHTS})


def build_model(cfg: ArchConfig, **kw) -> Model:
    return Model(cfg, **kw)
