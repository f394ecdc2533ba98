"""Operations and bytes of a decoder whose every layer is GQA attention and a
routed-expert SwiGLU (Qwen3-MoE), from its shapes and the routing's counters.

The configuration is the benchmark's own JSON (Hugging Face key names).
Counts are of what the algorithm requires, as in ``bench/flops.py``:

* matrix multiplications count 2 operations per multiply-add;
* attention: the q, k, v and o projections, causal scores over the lower
  triangle in prefill, and ``ctx`` positions in a decode step;
* the router: a ``hidden_size x num_experts`` product per token;
* the experts: each row routed (a token's choice of one expert, so
  ``num_experts_per_tok`` rows a token) counts one SwiGLU of width
  ``moe_intermediate_size``; an expert that got no row counts nothing;
* prefill's LM head counts only the last position; a decode step counts
  it for every row. Norms, rotary embedding and softmax are left out;
* bytes are every weight read once, except the experts: only those the
  counters report as having got a row are read (``experts`` sums them
  over layers). The token rows of the embedding table are read, the rest
  of it not; the untied head is read whole. The cache: a decode step
  reads the ``ctx - 1`` earlier positions' keys and values and writes the
  new one; prefill writes the prompt's. Activations are left out, but for
  the expert kernel's own bytes (``expert_bytes``): each routed row read
  in and written out once.
"""
from __future__ import annotations

from typing import Mapping

__all__ = ["expert_weight_bytes", "prefill_flops", "decode_flops",
           "prefill_bytes", "decode_bytes", "expert_flops", "expert_bytes"]


def _dims(cfg: Mapping):
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return (d, cfg["num_hidden_layers"], H, cfg["num_key_value_heads"],
            cfg.get("head_dim") or d // H, cfg["moe_intermediate_size"],
            cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["vocab_size"])


def _dense_layer_weights(cfg: Mapping) -> int:
    """Matrix parameters of one layer outside its experts: q, k, v, o and
    the router."""
    d, _, H, KV, hd, _, E, _, _ = _dims(cfg)
    return d * H * hd + 2 * d * KV * hd + H * hd * d + d * E


def expert_weight_bytes(cfg: Mapping, dtype_bytes: int = 2) -> float:
    """One expert's gate, up and down matrices."""
    d, _, _, _, _, f, _, _, _ = _dims(cfg)
    return 3.0 * d * f * dtype_bytes


def _fixed_bytes(cfg: Mapping, tokens: int, dtype_bytes: int) -> float:
    """Weights every call reads whatever the routing: the layers outside
    their experts (with the two norms and q/k norms), the embedding rows
    of ``tokens``, the final norm and the untied head."""
    d, L, _, _, hd, _, _, _, V = _dims(cfg)
    per_layer = _dense_layer_weights(cfg) + 2 * d + 2 * hd
    head = 0 if cfg.get("tie_word_embeddings", False) else V * d
    return float(L * per_layer + tokens * d + d + head) * dtype_bytes


def _kv_bytes_per_position(cfg: Mapping, dtype_bytes: int) -> float:
    _, L, _, KV, hd, _, _, _, _ = _dims(cfg)
    return float(L * 2 * KV * hd * dtype_bytes)


def expert_flops(cfg: Mapping, rows: int) -> float:
    """The experts' operations for ``rows`` routed rows (summed over
    layers)."""
    d, _, _, _, _, f, _, _, _ = _dims(cfg)
    return 6.0 * d * f * rows


def expert_bytes(cfg: Mapping, rows: int, experts: int,
                 dtype_bytes: int = 2) -> float:
    """The expert kernel's bytes: the weights of ``experts`` touched and
    each of ``rows`` routed rows read in and written out once (both
    summed over layers)."""
    d = cfg["hidden_size"]
    return (experts * expert_weight_bytes(cfg, dtype_bytes)
            + rows * 2.0 * d * dtype_bytes)


def prefill_flops(cfg: Mapping, batch: int, seq: int) -> float:
    d, L, H, _, hd, _, _, k, V = _dims(cfg)
    tokens = batch * seq
    attn = 4.0 * H * hd * batch * seq * (seq + 1) / 2
    return (L * (2.0 * _dense_layer_weights(cfg) * tokens + attn)
            + expert_flops(cfg, L * tokens * k) + 2.0 * d * V * batch)


def decode_flops(cfg: Mapping, batch: int, ctx: int) -> float:
    d, L, H, _, hd, _, _, k, V = _dims(cfg)
    attn = 4.0 * H * hd * batch * ctx
    return (L * (2.0 * _dense_layer_weights(cfg) * batch + attn)
            + expert_flops(cfg, L * batch * k) + 2.0 * d * V * batch)


def prefill_bytes(cfg: Mapping, batch: int, seq: int, experts: int,
                  dtype_bytes: int = 2) -> float:
    """Weights once (``experts`` of them touched, summed over layers), the
    prompt's keys and values written once."""
    return (_fixed_bytes(cfg, batch * seq, dtype_bytes)
            + experts * expert_weight_bytes(cfg, dtype_bytes)
            + batch * seq * _kv_bytes_per_position(cfg, dtype_bytes))


def decode_bytes(cfg: Mapping, batch: int, ctx: int, experts: int,
                 dtype_bytes: int = 2) -> float:
    """One step: weights once (``experts`` touched, summed over layers),
    the cache read up to ``ctx - 1`` and the new position written."""
    kv = _kv_bytes_per_position(cfg, dtype_bytes)
    return (_fixed_bytes(cfg, batch, dtype_bytes)
            + experts * expert_weight_bytes(cfg, dtype_bytes)
            + batch * (ctx - 1) * kv + batch * kv)
