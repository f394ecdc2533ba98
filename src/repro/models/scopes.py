"""The program scopes the model sets with ``jax.named_scope``.

Every operation of a compiled step carries its scope path in its HLO
``op_name`` metadata, and a profiler trace of the chip keeps that path as
the operation's ``tf_op``. The innermost name of this vocabulary in the
path says which part of the model the operation belongs to:

* model level: ``embed`` (token lookup, frontend, meta tokens),
  ``layers`` (each segment's layer stack: the scan's own slicing of its
  stacked weights and cache and the stacking of its outputs),
  ``final_norm``, ``lm_head`` (logits or the training loss);
* block level: ``attn`` (norm, q/k/v, scores, output projection),
  ``kv_write`` (writing the key/value cache), ``mlp`` (dense FFN),
  ``moe`` (routed experts), ``ssm`` (recurrent mixers: Mamba, mLSTM,
  sLSTM), ``cross_attn`` (attention to image tokens).

The benchmark's trace reader imports this tuple, so a scope renamed in
the model without renaming it here reads as unscoped time.
"""

SCOPES = ("embed", "layers", "final_norm", "lm_head", "attn", "kv_write",
          "mlp", "moe", "ssm", "cross_attn")
