"""Device time by program scope and idle time by engine phase
(``bench/metrics/_scopes.py``), on synthetic events and on two traces
recorded on a v5e.

``data/small_tpu.xplane.pb`` (see ``test_trace.py``) comes from a program
that sets no scope: its ops carry JAX's primitive names only.
``data/scoped_tpu.xplane.pb`` was recorded on one TPU v5e by
``record_scoped_trace.py``: a 2-layer model at qwen2-1.5b widths serves
two batches of 4 requests, 128-token prompts and 8 new tokens each,
through ``ServeEngine`` under the span ``bench.window``, with the model's
scopes and the engine's ``serve.*`` spans.
"""
from pathlib import Path

import pytest

from bench.metrics._scopes import (NO_SCOPE, NO_SPAN, clock_offset,
                                   decode_host_gap_ms, decode_scope_share,
                                   innermost_scope, read_op_metadata,
                                   reduce_events, summarize)
from bench.trace import reduce_trace
from repro.models.scopes import SCOPES

DATA = Path(__file__).with_name("data")
SMALL = str(DATA / "small_tpu.xplane.pb")
SCOPED = str(DATA / "scoped_tpu.xplane.pb")


# -- the wire reader, on the recorded trace of an unscoped program ----------

@pytest.fixture(scope="module")
def small_meta():
    return read_op_metadata(SMALL)


def test_metadata_is_read_for_the_device_plane_only(small_meta):
    assert list(small_meta) == ["/device:TPU:0"]


def test_same_op_name_is_told_apart_by_program(small_meta):
    ops = small_meta["/device:TPU:0"]
    lm_head = {pid: tf_op for (pid, name), tf_op in ops.items()
               if name.startswith("%convolution_convert_fusion = ")}
    # the LM head of prefill and of decode bear one HLO name
    assert sorted(lm_head.values()) == [
        "jit(decode)/bd,dv->bv/dot_general",
        "jit(prefill)/bd,dv->bv/dot_general"]
    by_tf = {v: k for k, v in lm_head.items()}
    assert by_tf["jit(decode)/bd,dv->bv/dot_general"] == 664781338461659642


def test_scan_ops_carry_the_loop_body_path(small_meta):
    ops = small_meta["/device:TPU:0"]
    tf = {name.split(" = ")[0]: t for (pid, name), t in ops.items()
          if pid == 664781338461659642}
    assert tf["%copy_dynamic-update-slice_fusion.4"] == \
        "jit(decode)/while/body/dynamic_update_slice"
    assert tf["%constant_dynamic-slice_fusion.10"] == \
        "jit(decode)/while/body/dynamic_slice"
    assert "%copy.86" not in tf          # XLA's own copy has no tf_op


def test_unscoped_program_reads_all_no_scope_and_sums_to_op_time():
    s = summarize(SMALL, "bench.serve")
    assert {scope for _, scope in s.op_s} == {NO_SCOPE}
    assert s.executions["jit_decode"] == 7
    assert sum(s.op_s.values()) == pytest.approx(
        sum(reduce_trace(SMALL, "bench.serve").op_time.values()), rel=1e-9)
    # no engine spans: nothing to align, no gap metric
    assert s.offset_ns == [None] and s.shift_ns == [0.0]
    assert decode_host_gap_ms(s) is None


# -- the recorded trace of the scoped program and the engine's spans --------

@pytest.fixture(scope="module")
def scoped():
    return summarize(SCOPED)


def test_scoped_ops_carry_the_model_scopes():
    tf_ops = read_op_metadata(SCOPED)["/device:TPU:0"].values()
    decode = [t for t in tf_ops if t.startswith("jit(decode)/")]
    assert "jit(decode)/layers/while/body/closed_call/attn/kv_write/" \
        "dynamic_update_slice" in decode
    assert {innermost_scope(t) for t in decode} >= {
        "embed", "layers", "attn", "kv_write", "mlp", "lm_head"}


def test_every_step_op_is_attributed_and_sums_to_op_time(scoped):
    assert scoped.executions["jit_decode"] == 14      # 2 batches x 7
    assert scoped.executions["jit_prefill"] == 2
    assert scoped.serve_spans == 2 + 2 + 16 + 16 + 14
    trace = reduce_trace(SCOPED, "bench.window")
    assert sum(scoped.op_s.values()) == pytest.approx(
        sum(trace.op_time.values()), rel=1e-9)
    assert scoped.busy_s == pytest.approx(trace.busy_s, rel=1e-9)
    for prog in ("jit_decode", "jit_prefill"):
        scopes = {sc for p, sc in scoped.op_s if p == prog}
        assert scopes <= set(SCOPES) | {NO_SCOPE}
        assert {"layers", "attn", "mlp", "lm_head"} <= scopes


def test_scoped_trace_reads_all_four_metrics(scoped):
    shares = [decode_scope_share(k, scoped)
              for k in ("kv_write", "layers", NO_SCOPE)]
    assert all(v is not None and v > 0 for v in shares)
    assert sum(shares) <= 100
    gap = decode_host_gap_ms(scoped)
    assert gap is not None and gap > 0
    # the device clock read early: every execution shifted by the same
    assert scoped.offset_ns[0] < 0
    assert scoped.shift_ns == [-scoped.offset_ns[0]]
    assert sum(scoped.gap_s.values()) == pytest.approx(
        scoped.window_s - scoped.busy_s)


# -- the reduction, on synthetic events -------------------------------------

def test_innermost_scope_of_a_path():
    p = "jit(decode)/layers/while/body/closed_call/attn/kv_write/dus"
    assert innermost_scope(p) == "kv_write"
    assert innermost_scope("jit(decode)/layers/while/body/dynamic_slice") \
        == "layers"
    assert innermost_scope("jit(decode)/bd,dv->bv/dot_general") == NO_SCOPE
    assert innermost_scope(None) == NO_SCOPE


def test_a_program_without_the_vocabulary_reads_no_share(monkeypatch):
    # the parent of the scoped program has no repro.models.scopes: its
    # shares read None, not 0 or 100
    from bench.metrics import _scopes

    s = reduce_events(*_decode_trace())
    monkeypatch.setattr(_scopes, "SCOPES", ())
    assert decode_scope_share(NO_SCOPE, s) is None
    assert decode_scope_share("kv_write", s) is None


MS = 1_000_000  # ns


def _decode_trace(skew=0.0, host_extra=()):
    """Two decode steps of 10 ms on the device, dispatched by the
    ``serve.decode`` spans at 2 and 16 ms; the host then waits in
    ``serve.sample``. The device clock reads ``skew`` ns late (negative:
    early)."""
    host = [(0, 40 * MS, "bench.window"),
            (1 * MS, 39 * MS, "serve.batch"),
            (2 * MS, 3 * MS, "serve.decode"),
            (3 * MS, 15 * MS, "serve.sample"),
            (15 * MS, 16 * MS, "serve.schedule"),
            (16 * MS, 17 * MS, "serve.decode"),
            (17 * MS, 30 * MS, "serve.sample"), *host_extra]
    modules, ops = [], []
    for k, t0 in enumerate((2.5 * MS, 16.5 * MS)):
        t0 += skew
        modules.append((t0, t0 + 10 * MS, "jit_decode(7)"))
        ops += [(t0, t0 + 10 * MS, f"%while.{k} = (...) while(...)"),
                (t0, t0 + 4 * MS, "%fusion.1 = bf16[] fusion(...)"),
                (t0 + 4 * MS, t0 + 5 * MS, "%dus.2 = bf16[] fusion(...)"),
                (t0 + 5 * MS, t0 + 8 * MS, "%slice.3 = bf16[] fusion(...)"),
                (t0 + 8 * MS, t0 + 10 * MS, "%copy.4 = bf16[] copy(...)")]
    tf_ops = {(7, "%fusion.1 = bf16[] fusion(...)"):
              "jit(decode)/layers/while/body/closed_call/attn/dot_general",
              (7, "%dus.2 = bf16[] fusion(...)"):
              "jit(decode)/layers/while/body/closed_call/attn/kv_write/dus",
              (7, "%slice.3 = bf16[] fusion(...)"):
              "jit(decode)/layers/while/body/dynamic_slice"}
    return (0, 40 * MS), sorted(host), [(modules, ops, tf_ops)]


def test_ops_are_named_by_scope_and_containers_left_out():
    s = reduce_events(*_decode_trace())
    # the while containers span each whole step and count toward no scope
    assert s.op_s == pytest.approx({("jit_decode", "attn"): 0.008,
                                    ("jit_decode", "kv_write"): 0.002,
                                    ("jit_decode", "layers"): 0.006,
                                    ("jit_decode", NO_SCOPE): 0.004})
    assert s.program_op_s("jit_decode") == pytest.approx(0.020)
    assert s.executions == {"jit_decode": 2}
    assert s.busy_s == pytest.approx(0.020)
    assert decode_scope_share("kv_write", s) == pytest.approx(10.0)
    assert decode_scope_share("layers", s) == pytest.approx(30.0)
    assert decode_scope_share(NO_SCOPE, s) == pytest.approx(20.0)
    assert s.scope_share("jit_prefill", "attn") is None


def test_an_op_outside_every_execution_has_no_program():
    window, host, [(modules, ops, tf_ops)] = _decode_trace()
    ops = ops + [(35 * MS, 36 * MS, "%fusion.9 = f32[] fusion(...)")]
    s = reduce_events(window, host, [(modules, ops, tf_ops)])
    assert s.op_s[("(no program)", NO_SCOPE)] == pytest.approx(0.001)


def test_gaps_are_labelled_by_the_innermost_serve_span():
    s = reduce_events(*_decode_trace())
    # each step starts 0.5 ms after its dispatch span: nothing to shift
    assert s.offset_ns == [0.5 * MS] and s.shift_ns == [0.0]
    # idle [0, 2.5) mid 1.25 in the batch; [12.5, 16.5) mid 14.5 while
    # the host samples; [26.5, 40) mid 33.25 in the batch
    assert s.gap_s == pytest.approx({"serve.batch": 0.016,
                                     "serve.sample": 0.004})
    assert sum(s.gap_s.values()) == pytest.approx(s.window_s - s.busy_s)
    assert decode_host_gap_ms(s) == pytest.approx(4.0 / 2)


def test_clock_is_aligned_before_gaps_are_labelled():
    # the device clock reads 0.7 ms early: each step appears to start
    # 0.2 ms before its dispatch span, and the first gap's midpoint
    # (0.9 ms) would fall before the batch began
    s = reduce_events(*_decode_trace(skew=-0.7 * MS))
    assert s.offset_ns == [pytest.approx(-0.2 * MS)]
    assert s.shift_ns == [pytest.approx(0.2 * MS)]
    # shifted, it reads as a trace whose steps start with their spans
    exact = reduce_events(*_decode_trace(skew=-0.5 * MS))
    assert exact.shift_ns == [0.0]
    assert s.gap_s == pytest.approx(exact.gap_s)
    assert NO_SPAN not in s.gap_s


def test_clock_offset_pairs_the_kth_execution_with_the_kth_span():
    spans = [(0, 1, "serve.decode"), (100, 101, "serve.decode"),
             (50, 51, "serve.prefill")]
    assert clock_offset([(3, "jit_decode"), (99, "jit_decode")], spans) == -1
    # a device clock 60 early: each step starts nearer the span before it
    assert clock_offset([(-57, "jit_decode"), (43, "jit_decode")],
                        spans) == -57
    assert clock_offset([(52, "jit_prefill")], spans) == 2
    # counts that differ pair nothing
    assert clock_offset([(3, "jit_decode")], spans) is None
    assert clock_offset([(10, "jit_other")], spans) is None
    assert clock_offset([(10, "jit_decode")], []) is None


def test_host_gap_counts_sample_schedule_and_decode_per_step():
    # the host dispatches the second step late: a schedule span covers
    # the last gap's midpoint, and the first gap has no span at all
    s = reduce_events(*_decode_trace(
        host_extra=[(30 * MS, 38 * MS, "serve.schedule")]))
    assert s.gap_s == pytest.approx({"serve.batch": 0.0025,
                                     "serve.sample": 0.004,
                                     "serve.schedule": 0.0135})
    assert decode_host_gap_ms(s) == pytest.approx((4.0 + 13.5) / 2)


def test_scope_vocabulary_is_the_models():
    assert "kv_write" in SCOPES and "layers" in SCOPES
    assert NO_SCOPE not in SCOPES
