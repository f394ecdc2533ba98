"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

A traced run records one ``.xplane.pb`` with ``jax.profiler``. On a TPU it
holds, on one common clock:

* a plane ``/device:TPU:<n>`` per chip, whose line ``XLA Ops`` has one
  event per operation executed and whose line ``XLA Modules`` has one
  event per program execution, named ``jit_<function>(<fingerprint>)``;
* the plane ``/host:CPU``, with one line per host thread. The thread that
  runs the benchmark holds its ``jax.profiler.TraceAnnotation`` spans
  and JAX's own host events (dispatch, transfers, waits).

``reduce_trace`` takes the measured window from the benchmark's window
span and gives, within it:

* ``busy_s``: the union of operation intervals, averaged over chips;
* ``programs``: the device time of each program execution, by name;
* ``op_time``: device time by operation, summed over executions;
* ``idle_by_host``: the device's idle time, each gap attributed to what
  the host thread was doing at its midpoint (the benchmark's spans that
  enclose it and the innermost host event).
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["TraceSummary", "start_trace", "stop_trace", "find_xplane",
           "reduce_trace", "union_length", "idle_gaps"]

Interval = Tuple[float, float]


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # averaged over chips
    chips: int
    programs: Dict[str, List[float]] = field(default_factory=dict)
    op_time: Dict[str, float] = field(default_factory=dict)
    idle_by_host: Dict[str, float] = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program_time(self, name: str) -> float:
        """Device seconds of every execution of the program ``name``."""
        return sum(self.programs.get(name, ()))

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_time.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def start_trace(log_dir) -> None:
    """Start the profiler into an emptied ``log_dir``: device and host
    events, no Python function tracing and no HLO protos, which would
    slow the host and swell the file."""
    import shutil

    import jax

    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def stop_trace(log_dir, window_span: str) -> "TraceSummary":
    """Stop the profiler and reduce what it wrote over ``window_span``."""
    import jax

    jax.profiler.stop_trace()
    return reduce_trace(find_xplane(str(log_dir)), window_span)


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(files)}")
    return files[0]


def union_length(intervals: Sequence[Interval]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals: Sequence[Interval], lo: float,
              hi: float) -> List[Interval]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    gaps, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(s, e) for s, e in gaps if e > s]


def _short_op(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


# control-flow operations span the operations of their bodies: they count
# toward the busy union but not toward time by operation
_CONTAINERS = ("while", "conditional", "call")


def _program(name: str) -> str:
    """``jit_decode(123456)`` -> ``jit_decode``."""
    return name.split("(", 1)[0]


def _events(line) -> List[Tuple[float, float, str]]:
    return [(e.start_ns, e.end_ns, e.name) for e in line.events]


def _label_gaps(gaps: List[Interval], host: List[Tuple[float, float, str]],
                span_prefix: str) -> Dict[str, float]:
    """Sum the gaps by what the host thread was doing at each midpoint.

    Host events on one thread nest, so a sweep in time order keeps the
    stack of events that enclose the current midpoint."""
    host = sorted(host, key=lambda ev: (ev[0], -ev[1]))
    out: Dict[str, float] = defaultdict(float)
    stack: List[Tuple[float, float, str]] = []
    j = 0
    for s, e in sorted(gaps):
        mid = 0.5 * (s + e)
        while j < len(host) and host[j][0] <= mid:
            while stack and stack[-1][1] <= host[j][0]:
                stack.pop()
            stack.append(host[j])
            j += 1
        while stack and stack[-1][1] <= mid:
            stack.pop()
        spans = [ev[2] for ev in stack if ev[2].startswith(span_prefix)]
        inner = stack[-1][2] if stack else "(no host event)"
        if not spans or spans[-1] != inner:
            spans.append(inner)
        out[" > ".join(spans)] += (e - s) * 1e-9
    return dict(out)


def reduce_trace(path: str, window_span: str,
                 span_prefix: str = "bench.") -> TraceSummary:
    """Reduce the trace at ``path`` over the host span ``window_span``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window: Optional[Interval] = None
    host: List[Tuple[float, float, str]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = _events(line)
                win = [ev for ev in evs if ev[2] == window_span]
                if win:
                    window = (win[0][0], win[0][1])
                    host = evs
    if window is None:
        raise RuntimeError(f"no host span {window_span!r} in {path}")
    if not devices:
        raise RuntimeError(f"no TPU device plane in {path}")
    lo, hi = window
    busy_total = 0.0
    programs: Dict[str, List[float]] = defaultdict(list)
    op_time: Dict[str, float] = defaultdict(float)
    gaps_all: List[Interval] = []
    for plane in devices:
        ops: List[Interval] = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                # the device clock is mapped onto the host's to within
                # about a millisecond: an execution belongs to the window
                # by its midpoint
                for s, e, name in _events(line):
                    if lo <= 0.5 * (s + e) <= hi:
                        programs[_program(name)].append((e - s) * 1e-9)
            elif line.name == "XLA Ops":
                for s, e, name in _events(line):
                    s, e = max(s, lo), min(e, hi)
                    if e > s:
                        ops.append((s, e))
                        op = _short_op(name)
                        if not op.startswith(_CONTAINERS):
                            op_time[op] += (e - s) * 1e-9
        busy_total += union_length(ops) * 1e-9
        gaps_all.extend(idle_gaps(ops, lo, hi))
    n = len(devices)
    idle = _label_gaps(gaps_all, host, span_prefix)
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total / n, chips=n,
        programs=dict(programs), op_time=dict(op_time),
        idle_by_host={k: v / n for k, v in idle.items()})
