"""HLO replay mapping, roofline MODEL_FLOPS model, Chrome-trace export,
Stack-EM task cloning — coverage for the reporting/replay layer."""
import json

import pytest

from benchmarks.roofline import model_flops
from repro.configs import REGISTRY, SHAPES
from repro.graph.hlo_parser import Collective, TaskSpec
from repro.graph.stackem import _clone_tasks
from repro.graph.tasks import Task
from repro.hw.mxu import GemmSpec
from repro.hw.pod import _gemm_dims, hlo_to_tasks
from repro.hw.presets import V5E
from repro.hw.chip import simulate


def test_gemm_dims_reconstruction():
    spec = _gemm_dims(flops=2 * 256 * 512 * 1024, bytes_in=0,
                      bytes_out=256 * 512 * 2)
    assert spec.m * spec.n == pytest.approx(256 * 512, rel=0.01)
    assert 2 * spec.m * spec.n * spec.k == pytest.approx(
        2 * 256 * 512 * 1024, rel=0.05)


def test_hlo_to_tasks_deps_and_streaming():
    specs = [
        TaskSpec("a", "mxu", flops=1e9, bytes_in=8 * 2**20,
                 bytes_out=8 * 2**20),
        TaskSpec("b", "vector", elems=1e6, bytes_in=1024, bytes_out=1024,
                 deps=(0,)),
        TaskSpec("c", "ici", collective=Collective(
            "all-reduce", 2**20, 16, 1, 1.0, False), deps=(1,)),
    ]
    tasks = hlo_to_tasks(specs, stream_io=True, io_threshold=2**20)
    # the big MXU task gains a DMA prologue; small vector task does not
    names = [t.name for t in tasks]
    assert "a.io" in names and "b.io" not in names
    rep = simulate(tasks, V5E)
    assert rep.makespan_ns > 0
    recs = {r.task: r for r in []}  # determinism covered elsewhere


def test_model_flops_orders():
    cfg = REGISTRY["qwen3-32b"]
    train = model_flops(cfg, SHAPES["train_4k"])
    prefill = model_flops(cfg, SHAPES["prefill_32k"])
    decode = model_flops(cfg, SHAPES["decode_32k"])
    # 6ND for train ~ 6 * 32e9 * 1M tokens
    assert train == pytest.approx(6 * cfg.param_count() * 4096 * 256,
                                  rel=0.25)
    assert decode < prefill
    # decode >= 2*N*B
    assert decode >= 2 * cfg.param_count() * 128


def test_model_flops_swa_discount():
    hy = REGISTRY["hymba-1.5b"]
    full = model_flops(hy, SHAPES["prefill_32k"])
    # a pure-full-attention config of the same size would cost more
    import dataclasses

    dense_like = dataclasses.replace(hy, sliding_window=0,
                                     global_attn_layers=(), family="dense",
                                     ssm_state=0)
    assert model_flops(dense_like, SHAPES["prefill_32k"]) > full


def test_chrome_trace_export():
    tasks = [Task("tile0.mxu", GemmSpec(m=256, n=256, k=256), name="mm")]
    from repro.hw.chip import System
    from repro.obs.perfetto import TraceBuilder, _tracer_events

    sysm = System(V5E)
    sysm.run_workload(tasks)
    tb = TraceBuilder()
    _tracer_events(tb, sysm.tracer)
    trace = tb.trace()
    assert any(e.get("name") == "mm" for e in trace["traceEvents"])
    json.dumps(trace)  # serializable


def test_stackem_clone_isolates_barriers():
    t = Task("tile0.mxu", GemmSpec(m=8, n=8, k=8), waits=((5, 1),),
             signals=(6,), name="x")
    c1 = _clone_tasks([t], "a")[0]
    c2 = _clone_tasks([t], "b")[0]
    assert c1.waits[0][0] != 5 and c2.waits[0][0] != 5
    assert c1.waits[0][0] != c2.waits[0][0]
    assert c1.signals[0] != c2.signals[0]
