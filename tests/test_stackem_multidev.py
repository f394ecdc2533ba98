"""Stack-EM multi-context scheduling, power gating (paper §6.2 future work,
implemented), and a subprocess multi-device GSPMD guard."""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.core import Tracer
from repro.graph.compiler import CompileOptions, compile_ops
from repro.graph.stackem import StackContext, run_stack
from repro.graph.workloads import mobilenet_v2, tiny_yolo_v2
from repro.hw.presets import V5E, paper_skew
from repro.power.powerem import PowerEM


def _ctx(name, builder, period_ns, priority, cfg, n=3):
    cw = compile_ops(builder(), cfg, CompileOptions(n_tiles=1))
    return StackContext(name=name, tasks=cw.tasks, period_ns=period_ns,
                        n_requests=n, priority=priority)


def test_stackem_two_contexts_complete():
    cfg = paper_skew()
    rep = run_stack([
        _ctx("cam", mobilenet_v2, period_ns=1e6, priority=0, cfg=cfg),
        _ctx("det", tiny_yolo_v2, period_ns=2e6, priority=1, cfg=cfg),
    ], cfg)
    assert len(rep.latencies_ns["cam"]) == 3
    assert len(rep.latencies_ns["det"]) == 3
    assert all(l > 0 for l in rep.latencies_ns["cam"])


def test_stackem_contention_raises_latency():
    """A co-running heavy context inflates the light context's e2e latency
    — the software-stack effect Stack-EM exists to expose."""
    cfg = paper_skew()
    solo = run_stack([_ctx("cam", mobilenet_v2, 1e6, 0, cfg)], cfg)
    shared = run_stack([
        _ctx("cam", mobilenet_v2, 1e6, 1, cfg),
        _ctx("det", tiny_yolo_v2, 5e5, 0, cfg),   # higher priority hog
    ], cfg)
    assert shared.avg_latency_ms("cam") > solo.avg_latency_ms("cam")


def test_power_gating_saves_idle_energy():
    tr = Tracer()
    cfg = V5E
    # busy 1 PTI, then idle 8 PTIs
    rate = cfg.macs * cfg.clock_ghz
    tr.emit("tile0.mxu", "ops", 0, 1000, rate * 1000)
    pem = PowerEM(cfg)
    plain = pem.analyze(tr, pti_ns=1000, t_end_ns=9000)
    gated = pem.analyze(tr, pti_ns=1000, t_end_ns=9000, power_gating=True)
    assert gated.energy_j() < plain.energy_j()
    # active PTI unaffected
    assert gated.series["tile0.mxu"][0] == plain.series["tile0.mxu"][0]


MULTIDEV_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    import jax.numpy as jnp
    from repro.configs import REGISTRY, SHAPES
    from repro.launch.mesh import make_mesh
    from repro.launch.programs import build_program
    from repro.train.data import SyntheticData

    cfg = REGISTRY["qwen2-1.5b"].reduced()
    shape = SHAPES["train_4k"]
    mesh = make_mesh((2, 4), ("data", "model"))
    prog = build_program(cfg, shape, mesh)
    # run REAL values through the partitioned program on 8 fake devices
    # (jit bakes shardings, not shapes — a smaller batch recompiles fine)
    from repro.train.loop import init_state
    state = init_state(prog.model, jax.random.PRNGKey(0))
    data = SyntheticData(cfg, shape, batch_override=8, seq_override=64)
    fn = prog.jitted()
    state2, metrics = fn(state, data.batch_at(0))
    loss = float(metrics["loss"])
    assert np.isfinite(loss), loss
    print("MULTIDEV_OK", loss)
""")


@pytest.mark.slow
def test_multidevice_gspmd_subprocess():
    """End-to-end GSPMD guard: a REAL partitioned train step on 8 host
    devices (subprocess because the device count locks at jax init)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", MULTIDEV_SNIPPET],
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.join(os.path.dirname(__file__), ".."),
                       env=env)
    assert "MULTIDEV_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
