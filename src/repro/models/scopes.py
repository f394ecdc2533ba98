"""The program scopes the model sets with ``jax.named_scope``.

Every operation of a compiled step carries its scope path in its HLO
``op_name`` metadata, and a profiler trace of the chip keeps that path as
the operation's ``tf_op``. The innermost name of this vocabulary in the
path says which part of the model the operation belongs to:

* model level: ``embed`` (token lookup, frontend, meta tokens),
  ``layers`` (each segment's layer stack: the scan's own slicing of its
  stacked weights, and in prefill the stacking of each layer's new cache;
  decode carries the stacked cache through the scan and each block
  updates its own layer in place, under the block's scopes),
  ``final_norm``, ``lm_head`` (logits or the training loss);
* block level: ``attn`` (norm, q/k/v, scores, output projection),
  ``kv_write`` (writing the key/value cache), ``attn_cache`` (inside
  ``attn`` in decode: attention over the cached keys and values, that is
  scores, softmax and the weighted sum), ``mlp`` (dense FFN),
  ``moe`` (routed experts), ``ssm`` (recurrent mixers: Mamba, mLSTM,
  sLSTM), ``cross_attn`` (attention to image tokens);
* inside ``moe``, on its dropless sparse path (``moe.moe_sparse``):
  ``router`` (router product, softmax, top-k), ``dispatch`` (sorting the
  rows by expert, gathering them, un-sorting the results and adding them
  up by their gates) and ``experts`` (the grouped products and the SwiGLU
  between them).

The benchmark's trace reader imports this tuple, so a scope renamed in
the model without renaming it here reads as unscoped time.
"""

SCOPES = ("embed", "layers", "final_norm", "lm_head", "attn", "kv_write",
          "attn_cache", "mlp", "moe", "ssm", "cross_attn", "router",
          "dispatch", "experts")
