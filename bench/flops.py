"""Operations and bytes a dense GQA decoder needs, from its shapes alone.

The configuration is the benchmark's own JSON (Hugging Face key names).
Counts are of what the algorithm requires, not of what a program happens
to compute:

* matrix multiplications count 2 operations per multiply-add;
* causal attention counts only the lower triangle (position ``i``
  attends ``i + 1`` keys), and a decode step attends the ``ctx``
  positions that hold keys, the new one included;
* prefill's LM head counts only the last position, which is all that a
  serving prefill returns; a decode step counts it for every row;
* norms, biases, rotary embedding and softmax are left out (well under
  1% of the operations at these widths);
* a decode step's bytes are every weight read once (the embedding table
  once, as the tied LM head), the keys and values of the ``ctx - 1``
  earlier positions read, and the new position's keys and values
  written. Activations and logits are left out.
"""
from __future__ import annotations

from typing import Mapping

__all__ = ["layer_weights", "weight_bytes", "prefill_flops", "decode_flops",
           "prefill_bytes", "decode_bytes"]


def _dims(cfg: Mapping):
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // H
    return (d, cfg["num_hidden_layers"], H, cfg["num_key_value_heads"], hd,
            cfg["intermediate_size"], cfg["vocab_size"])


def layer_weights(cfg: Mapping) -> int:
    """Matrix parameters of one layer: q, k, v, o and the SwiGLU MLP."""
    d, _, H, KV, hd, f, _ = _dims(cfg)
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f


def weight_bytes(cfg: Mapping, dtype_bytes: int = 2) -> float:
    """Every weight once: layers (with norms and q/k/v biases), the
    embedding table, the final norm and an untied head."""
    d, L, H, KV, hd, _, V = _dims(cfg)
    per_layer = layer_weights(cfg) + 2 * d + (H + 2 * KV) * hd
    head = 0 if cfg.get("tie_word_embeddings", False) else V * d
    return float(L * per_layer + V * d + d + head) * dtype_bytes


def _kv_bytes_per_position(cfg: Mapping, dtype_bytes: int) -> float:
    _, L, _, KV, hd, _, _ = _dims(cfg)
    return float(L * 2 * KV * hd * dtype_bytes)


def prefill_flops(cfg: Mapping, batch: int, seq: int) -> float:
    d, L, H, _, hd, _, V = _dims(cfg)
    tokens = batch * seq
    attn = 4.0 * H * hd * batch * seq * (seq + 1) / 2
    return L * (2.0 * layer_weights(cfg) * tokens + attn) + 2.0 * d * V * batch


def decode_flops(cfg: Mapping, batch: int, ctx: int) -> float:
    d, L, H, _, hd, _, V = _dims(cfg)
    attn = 4.0 * H * hd * batch * ctx
    return L * (2.0 * layer_weights(cfg) * batch + attn) + 2.0 * d * V * batch


def prefill_bytes(cfg: Mapping, batch: int, seq: int,
                  dtype_bytes: int = 2) -> float:
    """Weights once, the prompt's keys and values written once."""
    return (weight_bytes(cfg, dtype_bytes)
            + batch * seq * _kv_bytes_per_position(cfg, dtype_bytes))


def decode_bytes(cfg: Mapping, batch: int, ctx: int,
                 dtype_bytes: int = 2) -> float:
    kv = _kv_bytes_per_position(cfg, dtype_bytes)
    return weight_bytes(cfg, dtype_bytes) + batch * (ctx - 1) * kv + batch * kv
