"""Per-arch smoke tests: reduced configs of all 10 assigned architectures
run one forward on CPU (shapes + finiteness asserted) and serve: prefill and
decode against the forward. The train step is in ``test_models_train.py``,
which imports ``ARCHS`` and ``_batch`` from here. Full configs are
exercised only via the dry-run (ShapeDtypeStructs)."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY
from repro.models import build_model

# whole-module: every case compiles + runs a real model step (2-30s each)
pytestmark = pytest.mark.slow

ARCHS = list(REGISTRY)


def _batch(cfg, B=2, S=32, seed=0):
    rng = np.random.default_rng(seed)
    batch = {}
    if cfg.family == "audio":
        batch["frames"] = jnp.asarray(
            rng.standard_normal((B, S, cfg.d_model), np.float32))
    else:
        batch["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int32))
    if cfg.family == "vlm":
        batch["images"] = jnp.asarray(rng.standard_normal(
            (B, cfg.n_image_tokens, cfg.d_model), np.float32))
    batch["labels"] = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int32))
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = REGISTRY[arch].reduced()
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    h = model.forward(params, batch, for_train=False)
    B, S = 2, 32
    assert h.shape == (B, S + cfg.n_meta_tokens, cfg.d_model)
    assert bool(jnp.isfinite(h.astype(jnp.float32)).all())


@pytest.mark.parametrize("arch", ["smollm-135m", "qwen3-32b", "xlstm-125m",
                                  "hymba-1.5b", "qwen3-moe-30b-a3b",
                                  "qwen2-1.5b", "llama-3.2-vision-90b"])
def test_decode_matches_forward(arch):
    """Prefill + 2 decode steps == full forward logits (f32, exact-ish)."""
    cfg = REGISTRY[arch].reduced()
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0), jnp.float32)
    B, S, SMAX = 2, 20, 40
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S + 2),
                                    dtype=np.int32))
    extra = {}
    if cfg.family == "vlm":
        # open the cross-attention gates (zero at init) so that decode
        # reads the image k/v its cache carries
        cross = params["segments"][0]["cross"]
        for gate in ("gate_attn", "gate_ffn"):
            cross[gate] = jnp.ones_like(cross[gate])
        extra["images"] = jnp.asarray(rng.standard_normal(
            (B, cfg.n_image_tokens, cfg.d_model), np.float32))
    batch = {"tokens": toks[:, :S], **extra}
    lg, cache = model.prefill(params, batch, SMAX)
    lg1, cache = model.decode_step(params, cache, toks[:, S:S + 1])
    lg2, cache = model.decode_step(params, cache, toks[:, S + 1:S + 2])

    def ref(n):
        h = model.forward(params, {"tokens": toks[:, :n], **extra},
                          for_train=False)
        if cfg.n_meta_tokens:
            h = h[:, cfg.n_meta_tokens:]
        return model._logits(params, h[:, -1])

    for got, n in ((lg, S), (lg1, S + 1), (lg2, S + 2)):
        want = ref(n)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "smollm-135m",
                                  "qwen3-moe-30b-a3b"])
def test_decode_updates_stacked_cache_in_place(arch):
    """Decode writes one position into the donated stacked k/v cache: no
    copy of the whole stack, and scratch memory well under its size. (On
    the CPU the MoE's grouped-product kernel runs interpreted, which keeps
    its operands, the stacked expert weights, in scratch; its scratch on
    the chip is checked in ``tests/test_tpu_compile.py``.)"""
    cfg = dataclasses.replace(REGISTRY[arch].reduced(), n_layers=4)
    model = build_model(cfg, remat=False)
    B, SMAX = 8, 1024
    cache = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype if jnp.issubdtype(x.dtype, jnp.integer)
            else jnp.float32),
        model.abstract_cache(B, SMAX))
    params = model.abstract(jnp.float32)
    compiled = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, cache, jax.ShapeDtypeStruct((B, 1), jnp.int32)).compile()
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(cache))
    interpreted = sum(x.size * x.dtype.itemsize
                      for n, x in params["segments"][0].items()
                      if n.startswith("we_"))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.5 * cache_bytes + interpreted, (temp, cache_bytes)
    kv = cache["segments"][0]["k"].shape
    stacked = "f32[%s]" % ",".join(map(str, kv))
    copies = [ln for ln in compiled.as_text().splitlines()
              if re.search(r"= " + re.escape(stacked) + r"\S* copy\(", ln)]
    assert not copies, copies


def test_vlm_needs_images():
    cfg = REGISTRY["llama-3.2-vision-90b"].reduced()
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    # cross-attn gates init at 0 (llama-3.2 behavior: image influence is
    # learned); open them so the path is observable
    params["segments"][0]["cross"]["gate_attn"] = jnp.ones(
        params["segments"][0]["cross"]["gate_attn"].shape, jnp.bfloat16)
    batch = _batch(cfg)
    # changing the image tokens changes the output (cross-attn is live)
    h1 = model.forward(params, batch, for_train=False)
    batch2 = dict(batch)
    batch2["images"] = batch["images"] + 1.0
    h2 = model.forward(params, batch2, for_train=False)
    assert float(jnp.max(jnp.abs(h1.astype(jnp.float32)
                                 - h2.astype(jnp.float32)))) > 1e-3


def test_encoder_bidirectional():
    """HuBERT is not causal: flipping a late frame changes early outputs."""
    cfg = REGISTRY["hubert-xlarge"].reduced()
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    h1 = model.forward(params, batch, for_train=False)
    frames2 = batch["frames"].at[:, -1].add(10.0)
    h2 = model.forward(params, {**batch, "frames": frames2},
                       for_train=False)
    delta_early = float(jnp.max(jnp.abs(
        (h1 - h2)[:, :4].astype(jnp.float32))))
    assert delta_early > 1e-4


def test_param_counts_near_nominal():
    """Analytic parameter counts are in the right ballpark for the
    name-plate sizes (within a factor ~2 — embeddings/untied heads vary)."""
    nominal = {
        "smollm-135m": 135e6, "minicpm-2b": 2.4e9, "qwen2-1.5b": 1.5e9,
        "qwen3-32b": 32e9, "qwen3-moe-30b-a3b": 30e9,
        "phi3.5-moe-42b-a6.6b": 42e9, "xlstm-125m": 125e6,
        "hymba-1.5b": 1.5e9,
    }
    for name, n in nominal.items():
        got = REGISTRY[name].param_count()
        assert 0.45 * n < got < 2.2 * n, (name, got, n)
