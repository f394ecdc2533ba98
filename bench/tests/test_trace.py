"""The trace reduction, on intervals and on a trace recorded on a v5e.

``data/small_tpu.xplane.pb`` was recorded on one TPU v5e: a 4096x4096
bf16 matmul and an elementwise program, three times each under the span
``bench.step``, then four requests of 128 tokens with 8 new tokens each
through ``ServeEngine`` for a 2-layer model at qwen2-1.5b widths, under
the span ``bench.serve``.
"""
from pathlib import Path

import pytest

from bench.trace import idle_gaps, reduce_trace, union_length

DATA = Path(__file__).with_name("data") / "small_tpu.xplane.pb"


def test_union_merges_overlaps_and_keeps_gaps():
    assert union_length([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == 4
    assert union_length([]) == 0


def test_idle_gaps_are_the_uncovered_stretches():
    assert idle_gaps([(1, 2), (1.5, 3), (4, 5)], 0, 6) == [
        (0, 1), (3, 4), (5, 6)]
    assert idle_gaps([(0, 10)], 2, 8) == []


@pytest.fixture(scope="module")
def serve():
    return reduce_trace(str(DATA), "bench.serve")


def test_programs_in_the_window(serve):
    # one prefill, 7 decode steps (8 new tokens, the first from prefill)
    assert len(serve.programs["jit_prefill"]) == 1
    assert len(serve.programs["jit_decode"]) == 7
    assert "jit_mm" not in serve.programs
    assert serve.chips == 1


def test_busy_and_idle_within_the_window(serve):
    assert 0 < serve.busy_s < serve.window_s
    assert 0 < serve.idle_share < 1
    # the device is busy at least while the step programs run
    steps = serve.program_time("jit_prefill") + serve.program_time(
        "jit_decode")
    assert serve.busy_s >= 0.95 * steps


def test_idle_gaps_are_labelled_by_host_spans(serve):
    labels = serve.idle_by_host
    assert sum(labels.values()) == pytest.approx(
        serve.window_s - serve.busy_s, rel=1e-6)
    assert all(k.startswith("bench.serve") for k in labels)
    # the host waits for each step's logits
    assert max(labels, key=labels.get) == \
        "bench.serve > np.asarray(jax.Array)"


def test_breakdown_is_capped_and_skips_loop_containers(serve):
    b = serve.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert not any(n.startswith("while") for n, _ in b["device_ops"])


def test_other_window():
    # the first of the three ``bench.step`` spans
    step = reduce_trace(str(DATA), "bench.step")
    assert len(step.programs["jit_mm"]) == 1
    assert len(step.programs["jit_ew"]) == 1
    assert "jit_decode" not in step.programs
