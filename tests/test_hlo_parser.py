"""HLO parser: trip counts, dot flops, replica groups, task extraction.

The trip-count test builds a scan-vs-unrolled pair on the fly and checks
the parser's trip-aware totals against XLA's own cost_analysis of the
UNROLLED module (which needs no trip accounting).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph.hlo_parser import (decode_replica_groups, extract_tasks,
                                    parse_module, summarize)

ART = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                   "artifacts", "dryrun")


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_scan_trip_count_vs_unrolled():
    L, M = 12, 128
    w = jax.ShapeDtypeStruct((L, M, M), jnp.float32)
    x = jax.ShapeDtypeStruct((8, M), jnp.float32)

    def f_scan(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        y, _ = jax.lax.scan(body, x, w)
        return y

    def f_unroll(x, w):
        for i in range(L):
            x = jnp.tanh(x @ w[i])
        return x

    s_scan = summarize(_hlo(f_scan, x, w))
    s_unroll = summarize(_hlo(f_unroll, x, w))
    expected = 2.0 * 8 * M * M * L
    assert s_scan.dot_flops == pytest.approx(expected, rel=0.01)
    assert s_unroll.dot_flops == pytest.approx(expected, rel=0.01)
    # cross-check against XLA's analysis of the unrolled module
    ca = jax.jit(f_unroll).lower(x, w).compile().cost_analysis()
    assert s_unroll.dot_flops == pytest.approx(ca["flops"], rel=0.05)


def test_dot_flops_with_batch_dims():
    a = jax.ShapeDtypeStruct((4, 64, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 32, 16), jnp.float32)

    def f(a, b):
        return jax.lax.dot_general(a, b, (((2,), (1,)), ((0,), (0,))))

    s = summarize(_hlo(f, a, b))
    assert s.dot_flops == pytest.approx(2 * 4 * 64 * 16 * 32, rel=0.01)


def test_replica_groups_decoding():
    g = decode_replica_groups("replica_groups=[128,2]<=[256]")
    assert g.shape == (128, 2)
    assert list(g[0]) == [0, 1] and list(g[1]) == [2, 3]
    g2 = decode_replica_groups("replica_groups=[16,16]<=[16,16]T(1,0)")
    assert g2.shape == (16, 16)
    assert list(g2[0][:3]) == [0, 16, 32]      # transposed iota
    g3 = decode_replica_groups("replica_groups={{0,8},{1,9}}")
    assert g3.shape == (2, 2) and list(g3[1]) == [1, 9]


def test_cross_pod_detection():
    g = decode_replica_groups("replica_groups=[2,256]<=[512]")
    pods = g // 256
    assert bool(np.any(pods.max(axis=1) != pods.min(axis=1))) is False
    g2 = decode_replica_groups("replica_groups=[256,2]<=[2,256]T(1,0)")
    pods2 = g2 // 256
    assert bool(np.any(pods2.max(axis=1) != pods2.min(axis=1))) is True


def test_parse_module_structure():
    def f(x):
        return jnp.sum(x * 2.0)

    text = _hlo(f, jax.ShapeDtypeStruct((64, 64), jnp.float32))
    mod = parse_module(text)
    assert mod.entry in mod.computations
    entry = mod.computations[mod.entry]
    assert any(i.opcode in ("fusion", "reduce", "multiply")
               for i in entry.instrs)


def test_extract_tasks_dag():
    L, M = 4, 64

    def f(x, w):
        def body(c, wi):
            return c @ wi, None
        y, _ = jax.lax.scan(body, x, w)
        return y

    text = _hlo(f, jax.ShapeDtypeStruct((8, M), jnp.float32),
                jax.ShapeDtypeStruct((L, M, M), jnp.float32))
    tasks = extract_tasks(text)
    mxu = [t for t in tasks if t.engine == "mxu"]
    assert len(mxu) == L                       # one dot per unrolled trip
    # deps are acyclic and in-range
    for i, t in enumerate(tasks):
        assert all(0 <= d < i + 1 for d in t.deps)


# -- regression: gaps ingestion hit (synthetic HLO — CPU-compiled modules
# -- never carry async -start collectives or exotic dtypes) ---------------

_ASYNC_AR = """\
HloModule m

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (p0: f32[1024]) -> f32[1024] {
  %p0 = f32[1024] parameter(0)
  %ar = (f32[1024], f32[1024]) all-reduce-start(%p0), replica_groups=[4,2]<=[8], to_apply=%add
  ROOT %done = f32[1024] all-reduce-done(%ar)
}
"""


def test_all_reduce_start_payload_not_double_counted():
    """An async ``-start`` op types its output as a tuple carrying BOTH
    the operand alias and the result — naive output-byte accounting
    counts the 4 KiB payload twice. The payload must equal the operand
    bytes and the ``-done`` half must contribute nothing."""
    s = summarize(_ASYNC_AR)
    assert len(s.collectives) == 1
    c = s.collectives[0]
    assert c.op == "all-reduce"                  # -start suffix stripped
    assert c.payload_bytes == 1024 * 4           # NOT 2x
    assert c.group_size == 2
    # hbm side: operand read + effective output write, not the tuple
    assert s.hbm_bytes == 2 * 1024 * 4

    tasks = extract_tasks(_ASYNC_AR)
    ici = [t for t in tasks if t.engine == "ici"]
    assert len(ici) == 1                         # -done emits no task
    assert ici[0].collective.payload_bytes == 1024 * 4
    assert ici[0].bytes_out == 1024 * 4


def test_all_gather_start_payload():
    text = _ASYNC_AR.replace(
        "(f32[1024], f32[1024]) all-reduce-start(%p0), "
        "replica_groups=[4,2]<=[8], to_apply=%add",
        "(f32[1024], f32[4096]) all-gather-start(%p0), "
        "replica_groups=[2,4]<=[8], dimensions={0}").replace(
        "f32[1024] all-reduce-done", "f32[4096] all-gather-done")
    s = summarize(text)
    assert len(s.collectives) == 1
    # gather output is genuinely larger than the operand: payload is the
    # de-aliased output (4096 elems), not operand + output
    assert s.collectives[0].payload_bytes == 4096 * 4
    assert s.collectives[0].group_size == 4


def test_sync_all_reduce_unchanged():
    """Non-start collectives (bare array output) keep exact payloads —
    the de-aliasing is a no-op for them."""
    text = _ASYNC_AR.replace(
        "(f32[1024], f32[1024]) all-reduce-start(%p0)",
        "f32[1024] all-reduce(%p0)").replace(
        "f32[1024] all-reduce-done(%ar)", "f32[1024] negate(%ar)")
    s = summarize(text)
    assert s.collectives[0].payload_bytes == 1024 * 4


def test_unknown_dtype_warns_once():
    import warnings as w

    from repro.graph import hlo_parser

    text = _ASYNC_AR.replace("f32[1024]", "f4e2m1[1024]")
    hlo_parser._WARNED_DTYPES.discard("f4e2m1")
    with w.catch_warnings(record=True) as rec:
        w.simplefilter("always")
        summarize(text)
        first = [x for x in rec if "f4e2m1" in str(x.message)]
    assert len(first) == 1                       # once, not per shape
    assert "DTYPE_BYTES" in str(first[0].message)
    with w.catch_warnings(record=True) as rec2:
        w.simplefilter("always")
        summarize(text)
    assert not [x for x in rec2 if "f4e2m1" in str(x.message)]


def test_known_dtypes_do_not_warn():
    import warnings as w

    with w.catch_warnings():
        w.simplefilter("error")
        summarize(_ASYNC_AR)


@pytest.mark.skipif(not os.path.isdir(ART), reason="no dry-run artifacts")
def test_artifact_sanity():
    import gzip
    import json

    f = os.path.join(ART, "smollm-135m__train_4k__pod16x16")
    if not os.path.exists(f + ".json"):
        pytest.skip("smollm artifact missing")
    cell = json.load(open(f + ".json"))
    if cell.get("status") != "ok":
        pytest.skip("cell not ok")
    text = gzip.open(f + ".hlo.txt.gz", "rt").read()
    s = summarize(text, pod_size=256)
    # trip-aware flops must exceed XLA's scan-blind count
    assert s.dot_flops > 2 * cell["cost_analysis"]["flops"]
    # 6ND per-chip lower bound (param_count from the config)
    n, d = cell["param_count"], 4096 * 256
    assert s.dot_flops > 6 * n * d / 256 * 0.8
    assert s.collective_bytes() > 0
