"""``bench/flops.py`` against XLA's own count of the program it describes.

XLA's cost analysis of the served qwen2-1.5b programs, lowered on the
CPU at published widths with one layer (the analysis counts a scanned
layer stack's body once), batch 2. XLA counts every operation the
program runs: the whole square of attention scores where ``flops.py``
counts the causal triangle, and norms, softmax and rotary embedding that
``flops.py`` leaves out. So ``flops.py`` reads a little under XLA, never
over. Bytes: XLA counts every operand and result of every operation
(copies, converts and the f32 logits among them), so the minimal bytes
of ``flops.py`` read well under it and never over.

Readings when the bands were set (CPU, JAX 0.9.0): prefill 0.991 of
XLA's operations, decode 0.9995, decode bytes 0.593.
"""
import json
from pathlib import Path

import pytest

from bench import flops

CFG = json.loads((Path(__file__).parents[1] / "configs" /
                  "qwen2-1.5b.json").read_text())
ONE_LAYER = dict(CFG, num_hidden_layers=1)
B, S, SMAX = 2, 256, 272


@pytest.fixture(scope="module")
def programs():
    import jax
    import jax.numpy as jnp

    from bench.drivers.serve_lm import arch_config
    from repro.models import build_model

    model = build_model(arch_config("qwen2-1.5b", ONE_LAYER))
    params = model.abstract()
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    pre = jax.jit(lambda p, t: model.prefill(p, {"tokens": t}, SMAX)).lower(
        params, tok).cost_analysis()
    cache = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, jnp.int32 if x.shape == () else x.dtype),
        model.abstract_cache(B, SMAX))
    dec = jax.jit(model.decode_step).lower(
        params, cache, jax.ShapeDtypeStruct((B, 1), jnp.int32)).cost_analysis()
    return pre, dec


def test_prefill_flops_within_band_of_xla(programs):
    pre, _ = programs
    ratio = flops.prefill_flops(ONE_LAYER, B, S) / pre["flops"]
    assert 0.97 <= ratio <= 1.0, ratio   # band: causal half + elementwise


def test_decode_flops_within_band_of_xla(programs):
    _, dec = programs
    # the program attends the whole cache of SMAX slots, masked
    ratio = flops.decode_flops(ONE_LAYER, B, SMAX) / dec["flops"]
    assert 0.99 <= ratio <= 1.0, ratio   # band: norms, softmax, rope


def test_decode_bytes_never_over_xla(programs):
    _, dec = programs
    ratio = flops.decode_bytes(ONE_LAYER, B, SMAX) / dec["bytes accessed"]
    assert 0.5 <= ratio <= 1.0, ratio


def test_counts_scale_with_depth_and_shapes():
    one = flops.decode_flops(ONE_LAYER, B, 100)
    assert flops.decode_flops(dict(ONE_LAYER, num_hidden_layers=3), B, 100) \
        > 2.5 * one - 2 * flops.decode_flops(
            dict(ONE_LAYER, num_hidden_layers=0), B, 100)
    # qwen2-1.5b: 1.54e9 parameters, 3.09 GB in bf16
    assert flops.weight_bytes(CFG) == pytest.approx(3.087e9, rel=1e-3)
    # causal: position i attends i + 1 keys, 8 * 9 / 2 scores a head
    d, L, H, hd, V = 1536, 28, 12, 128, 151936
    mm = 2.0 * flops.layer_weights(CFG) * 8
    assert flops.prefill_flops(CFG, 1, 8) == pytest.approx(
        L * (mm + 4 * H * hd * 8 * 9 / 2) + 2 * d * V)
