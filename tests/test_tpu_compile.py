"""Compile the chip's programs for a described (not attached) TPU v5e.

The TPU compiler is installed with JAX, so these run without a chip: they
refuse what interpret mode accepts (unaligned slices, loops Mosaic cannot
lower, too much VMEM). Each kernel compiles at the real widths of the
models that use it and must lower to a Mosaic ``tpu_custom_call``; the
campaign pre-screen compiles for one ``lm_full_pod`` layer body.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and pytest-xdist workers all import
this file. The persistent compilation cache is off around these
compiles, since an entry written for a described chip cannot be read back
without one.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            from jax.experimental import topologies
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
            yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_compiles_qwen2_widths(one_chip):
    from repro.kernels.flash_attention.ops import flash_mha

    q = _sds(one_chip, (4, 2048, 12, 128), jnp.bfloat16)
    kv = _sds(one_chip, (4, 2048, 2, 128), jnp.bfloat16)
    text = _compiled_text(functools.partial(flash_mha, causal=True), q, kv, kv)
    assert "tpu_custom_call" in text


def test_rmsnorm_compiles_qwen2_width(one_chip):
    from repro.kernels.rmsnorm.kernel import fused_rmsnorm

    x = _sds(one_chip, (8192, 1536), jnp.bfloat16)
    w = _sds(one_chip, (1536,), jnp.bfloat16)
    assert "tpu_custom_call" in _compiled_text(fused_rmsnorm, x, w)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssm_scan_compiles_hymba_width(one_chip, dtype):
    from repro.configs import REGISTRY
    from repro.kernels.ssm_scan.ops import ssm_scan_batched

    cfg = REGISTRY["hymba-1.5b"]
    inner = cfg.ssm_expand * cfg.d_model            # 3200
    a = _sds(one_chip, (4, 2048, inner), dtype)
    assert "tpu_custom_call" in _compiled_text(ssm_scan_batched, a, a)


def test_prescreen_compiles_lm_full_pod_body(one_chip):
    from repro.core.vectorized import (_schedule_many_stats_impl,
                                       from_tasks, params_of)
    from repro.graph.compiler import CompileOptions, compile_ops
    from repro.graph.workloads import model_parts
    from repro.sweep.spec import load_spec

    spec = load_spec("lm_full_pod")
    cell = spec.cells()[0]
    parts = model_parts(cell.workload)
    cw = compile_ops(parts.body(), cell.base_cfg(),
                     CompileOptions(n_tiles=cell.n_tiles, **spec.compile_opts))
    arrays = from_tasks(cw.tasks)
    pm = np.stack([params_of(p.cfg(spec)) for p in cell.points])
    args = jax.tree_util.tree_map(
        lambda x: _sds(one_chip, np.shape(x), jnp.asarray(x).dtype),
        (arrays, pm))
    compiled = _schedule_many_stats_impl.lower(*args, repeats=1).compile()
    mk, busy = compiled.out_info
    assert mk.shape == (len(cell.points),)
    assert busy.shape == (len(cell.points), 4)


def test_decode_updates_stacked_cache_in_place(one_chip):
    """qwen2-1.5b's decode step at the serving cell's shapes (batch 32,
    768 slots), two layers: the layer scan carries the donated stacked
    cache and writes one position into it, with no copy of the whole
    cache, not even to change its layout."""
    import dataclasses
    import re

    from repro.configs import REGISTRY
    from repro.models import build_model

    model = build_model(dataclasses.replace(REGISTRY["qwen2-1.5b"],
                                            n_layers=2))
    B, SMAX = 32, 768

    def sds(x):
        return _sds(one_chip, x.shape, jnp.int32 if x.shape == () else x.dtype)

    cache = jax.tree_util.tree_map(sds, model.abstract_cache(B, SMAX))
    compiled = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        jax.tree_util.tree_map(sds, model.abstract()), cache,
        _sds(one_chip, (B, 1), jnp.int32)).compile()
    stacked = "bf16[%s]" % ",".join(map(str, cache["segments"][0]["k"].shape))
    copies = re.findall(r"= " + re.escape(stacked) + r"\S* copy\(",
                        compiled.as_text())
    assert not copies, copies
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(cache))
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1 * cache_bytes


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_qwen3_moe_serving_fits_one_chip(one_chip, program):
    """Qwen3-30B-A3B's serving programs at published widths and the
    benchmark's cut (8 layers, all 128 experts), batch 32, 512-token
    prompts, 768 slots: the dropless sparse expert layer makes no buffer
    of E·T·d or E·T·f elements (the dense oracle's broadcast of every
    token to every expert), and the program's scratch fits beside its
    weights and cache in the v5e's 16 GB."""
    import dataclasses
    import re

    from repro.configs import REGISTRY
    from repro.models import build_model
    from repro.serve.engine import make_decode_fn, make_prefill_fn

    cfg = dataclasses.replace(REGISTRY["qwen3-moe-30b-a3b"], n_layers=8)
    model = build_model(cfg)
    B, P, SMAX = 32, 512, 768
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff

    def sds(x):
        return _sds(one_chip, x.shape, x.dtype)

    params = jax.tree_util.tree_map(sds, model.abstract())
    if program == "prefill":
        T = B * P
        compiled = jax.jit(make_prefill_fn(model, None, SMAX)).lower(
            params, {"tokens": _sds(one_chip, (B, P), jnp.int32)}).compile()
    else:
        T = B
        cache = jax.tree_util.tree_map(sds, model.abstract_cache(B, SMAX))
        compiled = jax.jit(make_decode_fn(model, None),
                           donate_argnums=(1,)).lower(
            params, cache, _sds(one_chip, (B, 1), jnp.int32)).compile()
    # a weight's shape, a layer of it, or its experts of all layers in a
    # row, may hold as many elements
    weights = set()
    for x in jax.tree_util.tree_leaves(params):
        weights |= {x.shape, x.shape[1:],
                    (int(np.prod(x.shape[:2])),) + x.shape[2:]}
    dense = {E * T * d, E * T * f}
    found = set()
    for m in re.finditer(r"= \w+\[([\d,]+)\]", compiled.as_text()):
        shape = tuple(int(s) for s in m.group(1).split(","))
        if (int(np.prod(shape)) in dense
                and tuple(s for s in shape if s != 1) not in weights):
            found.add(shape)
    assert not found, found
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert total < 16e9, (total, ma.temp_size_in_bytes)
    assert "tpu_custom_call" in compiled.as_text()


def _attn_cache_kernels(text):
    """The Mosaic kernels of a compiled program that run under the
    ``attn_cache`` scope, as their HLO lines."""
    return [ln for ln in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln
            and "/attn_cache/" in ln]


@pytest.mark.parametrize("arch,layers", [("qwen2-1.5b", 28),
                                         ("qwen3-moe-30b-a3b", 8)])
def test_decode_attention_reads_the_stacked_cache_in_place(one_chip, arch,
                                                           layers):
    """The serving cells' decode steps (batch 32, 768 slots): attention
    over the cache is one Mosaic kernel per layer, fed the whole stacked
    cache (a bitcast of it: layers, batch × kv heads, slots, head), with no
    copy of the cache and scratch under a tenth of it."""
    import dataclasses
    import re

    from repro.configs import REGISTRY
    from repro.models import build_model
    from repro.serve.engine import make_decode_fn

    cfg = dataclasses.replace(REGISTRY[arch], n_layers=layers)
    model = build_model(cfg)
    B, SMAX = 32, 768

    def sds(x):
        return _sds(one_chip, x.shape, x.dtype)

    cache = jax.tree_util.tree_map(sds, model.abstract_cache(B, SMAX))
    compiled = jax.jit(make_decode_fn(model, None), donate_argnums=(1,)).lower(
        jax.tree_util.tree_map(sds, model.abstract()), cache,
        _sds(one_chip, (B, 1), jnp.int32)).compile()
    text = compiled.as_text()
    (kernel,) = _attn_cache_kernels(text)
    merged = "bf16[%d,%d,%d,%d]{3,2,1,0}" % (
        layers, B * cfg.n_kv_heads, SMAX, cfg.hd)
    assert kernel.count(merged) == 2, kernel[:400]
    stacked = "bf16[%s]" % ",".join(map(str, cache["segments"][0]["k"].shape))
    copies = re.findall(r"= (?:%s|%s)\S* copy\(" % (
        re.escape(stacked), re.escape(merged.split("{")[0])), text)
    assert not copies, copies
    kv_bytes = sum(cache["segments"][0][n].size * 2 for n in ("k", "v"))
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1 * kv_bytes


def test_sharded_decode_keeps_the_einsum(topo):
    """Under sharding rules (a 2x2 mesh, the cache's ``kv_seq`` axis over
    'model'), decode attention stays the einsum, which the partitioner
    turns into flash-decoding collectives: no Mosaic kernel runs under
    ``attn_cache``, while the scope itself is there."""
    import dataclasses

    from jax.sharding import AxisType, Mesh

    from repro.configs import REGISTRY
    from repro.configs.base import ShapeSpec
    from repro.launch.programs import build_program

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    cfg = dataclasses.replace(REGISTRY["qwen2-1.5b"], n_layers=2)
    prog = build_program(cfg, ShapeSpec("decode_768", 768, 32, "decode"),
                         mesh)
    assert prog.rules.table["kv_seq"] == "model"
    text = prog.lower().compile().as_text()
    assert not _attn_cache_kernels(text)
    assert "/attn_cache/" in text
