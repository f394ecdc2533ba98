"""Device time by program scope, and device idle time by engine phase,
from the traced window's ``.xplane.pb``.

``bench/trace.py`` names a device operation by its HLO name, which changes
with every edit to the model and repeats across programs. Here each
operation of the ``XLA Ops`` line is placed in the program execution of
the ``XLA Modules`` line that contains it (``jit_decode(<n>)``: ``n`` is
the program id) and named by the innermost program scope of its
``tf_op`` path (the model's vocabulary, ``repro.models.scopes.SCOPES``),
or ``(no scope)``. ``ProfileData`` does not expose the event metadata
that holds ``tf_op``, so a small protobuf wire reader takes it from each
device plane's ``event_metadata`` map, keyed by (program id, op name).
Control-flow containers (``while``, ``conditional``, ``call``) span the
operations of their bodies and count toward no scope, as in
``bench/trace.py``.

The device clock is mapped onto the host's only roughly. A program
cannot start on the device before the host dispatches it, so the offset
is the most negative (device start of a ``jit_decode`` / ``jit_prefill``
execution - start of the ``serve.decode`` / ``serve.prefill`` span that
dispatched it, the k-th execution with the k-th span), and device events
are shifted later by it (not at all where none is negative) before the
idle gaps are labelled by the engine span at their midpoints.

The trace is the one ``bench/run.py`` writes under ``.bench_out/trace``,
parsed once per process for all the metrics that read it.
"""
from __future__ import annotations

import functools
from bisect import bisect_right
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from bench.trace import (_CONTAINERS, _program, _short_op, find_xplane,
                         idle_gaps, union_length)

try:  # a program that sets no scope has no vocabulary
    from repro.models.scopes import SCOPES
except ImportError:
    SCOPES = ()

TRACE_DIR = Path(__file__).resolve().parents[2] / ".bench_out" / "trace"
WINDOW = "bench.window"
NO_SCOPE = "(no scope)"
NO_SPAN = "(no serve span)"
# the engine's phases between two decode steps: the host's share of a step
HOST_PHASES = ("serve.sample", "serve.schedule", "serve.decode")
# program -> the engine span that dispatches it
DISPATCH = {"jit_decode": "serve.decode", "jit_prefill": "serve.prefill"}

Event = Tuple[float, float, str]


# -- protobuf wire format ----------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int):
    """(field number, value) of the message ``buf[lo:hi]``: an int for a
    varint or fixed field, ``(start, end)`` for a length-delimited one."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield num, v


def _str(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_value(buf: bytes, span: Tuple[int, int]):
    """The value of a map entry {1: key, 2: value}, as a byte range."""
    for num, v in _fields(buf, *span):
        if num == 2:
            return v
    return (span[0], span[0])


def _stats(buf: bytes, span, stat_names: Dict[int, str]) -> Dict[str, object]:
    """XStat: metadata_id 1; uint64 3, int64 4, str 5, ref 7."""
    out = {}
    for num, st in _fields(buf, *span):
        if num != 5:
            continue
        name, value = None, None
        for f, v in _fields(buf, *st):
            if f == 1:
                name = stat_names.get(v)
            elif f in (3, 4):
                value = v
            elif f == 5:
                value = _str(buf, v)
            elif f == 7:
                value = stat_names.get(v)
        if name is not None:
            out[name] = value
    return out


def read_op_metadata(path: str) -> Dict[str, Dict[Tuple[int, str], str]]:
    """For each device plane, ``(program id, op name) -> tf_op`` of its
    event metadata, the trailing ``:`` of ``tf_op`` stripped. The plane's
    event lines are skipped by their length, never decoded."""
    buf = Path(path).read_bytes()
    out: Dict[str, Dict[Tuple[int, str], str]] = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:                          # XSpace.planes
            continue
        name, events, stats = "", [], []
        for f, v in _fields(buf, *plane):
            if f == 2:                        # XPlane.name
                name = _str(buf, v)
            elif f == 4:                      # event_metadata
                events.append(_map_value(buf, v))
            elif f == 5:                      # stat_metadata
                stats.append(_map_value(buf, v))
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {}
        for sm in stats:                      # XStatMetadata: id 1, name 2
            sid, sname = None, ""
            for f, v in _fields(buf, *sm):
                if f == 1:
                    sid = v
                elif f == 2:
                    sname = _str(buf, v)
            stat_names[sid] = sname
        ops = {}
        for em in events:                     # XEventMetadata: name 2, stats 5
            op = ""
            for f, v in _fields(buf, *em):
                if f == 2:
                    op = _str(buf, v)
            st = _stats(buf, em, stat_names)
            tf_op, pid = st.get("tf_op"), st.get("program_id")
            if isinstance(tf_op, str) and pid is not None:
                ops[(pid % 2 ** 64, op)] = tf_op.rstrip(":")
        out[name] = ops
    return out


# -- reduction ---------------------------------------------------------------

def innermost_scope(tf_op: Optional[str]) -> str:
    """The innermost name of ``SCOPES`` in a ``tf_op`` path."""
    for part in reversed((tf_op or "").split("/")):
        if part in SCOPES:
            return part
    return NO_SCOPE


def clock_offset(executions: List[Tuple[float, str]],
                 spans: List[Event]) -> Optional[float]:
    """The most negative (device start - start of its dispatching span)
    over ``executions`` of (start, program). Each dispatching span
    dispatches one execution, so the k-th execution of a program pairs
    with the k-th span of its phase, however far the clocks disagree. A
    program whose count of executions differs from its count of spans is
    left out; ``None`` where none is left."""
    best = None
    for prog, phase in DISPATCH.items():
        ex = sorted(t for t, p in executions if p == prog)
        ss = sorted(s for s, _, name in spans if name == phase)
        if ex and len(ex) == len(ss):
            d = min(t - s for t, s in zip(ex, ss))
            best = d if best is None else min(best, d)
    return best


def label_at(mid: float, phases: List[Event], batches: List[Event]) -> str:
    """The innermost ``serve.*`` span at ``mid``: a phase (phases do not
    overlap one another), else its batch."""
    for evs in (phases, batches):
        k = bisect_right(evs, (mid, float("inf"), "")) - 1
        if k >= 0 and evs[k][0] <= mid < evs[k][1]:
            return evs[k][2]
    return NO_SPAN


@dataclass
class ScopeSummary:
    window_s: float
    # (program, scope) -> device seconds of its operations in the window
    op_s: Dict[Tuple[str, str], float] = field(default_factory=dict)
    # program -> executions in the window
    executions: Dict[str, float] = field(default_factory=dict)
    # innermost serve span at the gap's midpoint -> device idle seconds
    gap_s: Dict[str, float] = field(default_factory=dict)
    busy_s: float = 0.0
    serve_spans: int = 0
    # per chip: the measured offset, and the shift applied (ns)
    offset_ns: List[Optional[float]] = field(default_factory=list)
    shift_ns: List[float] = field(default_factory=list)

    def program_op_s(self, program: str) -> float:
        return sum(v for (p, _), v in self.op_s.items() if p == program)

    def scope_share(self, program: str, scope: str) -> Optional[float]:
        """Percent of ``program``'s op time under ``scope``."""
        total = self.program_op_s(program)
        if not self.executions.get(program) or total <= 0:
            return None
        return 100.0 * self.op_s.get((program, scope), 0.0) / total


def _program_id(module: str) -> Optional[int]:
    """``jit_decode(664781338461659642)`` -> 664781338461659642."""
    try:
        return int(module[module.index("(") + 1:module.rindex(")")]) % 2 ** 64
    except ValueError:
        return None


def _line(plane, name: str) -> List[Event]:
    for line in plane.lines:
        if line.name == name:
            return [(e.start_ns, e.end_ns, e.name) for e in line.events]
    return []


def summarize(path: str, window_span: str = WINDOW) -> ScopeSummary:
    """Reduce the trace at ``path`` over the host span ``window_span``."""
    from jax.profiler import ProfileData

    meta = read_op_metadata(path)
    host: List[Event] = []
    window = None
    planes = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            planes.append((_line(plane, "XLA Modules"),
                           _line(plane, "XLA Ops"), meta.get(plane.name, {})))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [(e.start_ns, e.end_ns, e.name) for e in line.events]
                win = [ev for ev in evs if ev[2] == window_span]
                if win:
                    window, host = win[0][:2], evs
    if window is None:
        raise RuntimeError(f"no host span {window_span!r} in {path}")
    if not planes:
        raise RuntimeError(f"no TPU device plane in {path}")
    return reduce_events(window, host, planes)


def reduce_events(window: Tuple[float, float], host: List[Event],
                  planes: List[tuple]) -> ScopeSummary:
    """The summary over ``window`` (ns) of the host thread's events and of
    each device plane's (``XLA Modules`` events, ``XLA Ops`` events,
    ``(program id, op name) -> tf_op``); times are averaged over planes."""
    lo, hi = window
    serve = sorted(ev for ev in host if ev[2].startswith("serve."))
    batches = [ev for ev in serve if ev[2] == "serve.batch"]
    phases = [ev for ev in serve if ev[2] != "serve.batch"]
    out = ScopeSummary(window_s=(hi - lo) * 1e-9, serve_spans=len(serve))
    op_s: Dict[Tuple[str, str], float] = defaultdict(float)
    gap_s: Dict[str, float] = defaultdict(float)
    executions: Dict[str, float] = defaultdict(float)
    busy = 0.0
    for modules, ops, tf_ops in planes:
        modules = sorted(modules)
        offset = clock_offset([(s, _program(n)) for s, _, n in modules],
                              phases)
        shift = max(0.0, -offset) if offset is not None else 0.0
        out.offset_ns.append(offset)
        out.shift_ns.append(shift)
        mod_starts = [s for s, _, _ in modules]
        for s, e, n in modules:
            if lo <= 0.5 * (s + e) + shift <= hi:
                executions[_program(n)] += 1
        intervals = []
        for s, e, name in ops:
            cs, ce = max(s + shift, lo), min(e + shift, hi)
            if ce <= cs:
                continue
            intervals.append((cs, ce))
            if _short_op(name).startswith(_CONTAINERS):
                continue
            k = bisect_right(mod_starts, s) - 1
            if k >= 0 and s <= modules[k][1]:
                mod = modules[k][2]
                prog = _program(mod)
                scope = innermost_scope(tf_ops.get((_program_id(mod), name)))
            else:
                prog, scope = "(no program)", NO_SCOPE
            op_s[(prog, scope)] += (ce - cs) * 1e-9
        busy += union_length(intervals) * 1e-9
        for gs, ge in idle_gaps(intervals, lo, hi):
            gap_s[label_at(0.5 * (gs + ge), phases, batches)] += \
                (ge - gs) * 1e-9
    n = len(planes)
    out.op_s = {k: v / n for k, v in op_s.items()}
    out.gap_s = {k: v / n for k, v in gap_s.items()}
    out.executions = {k: v / n for k, v in executions.items()}
    out.busy_s = busy / n
    return out


@functools.lru_cache(maxsize=1)
def _load(path: str) -> ScopeSummary:
    return summarize(path)


def window_summary() -> ScopeSummary:
    """The summary of the run's traced window, parsed once per process."""
    return _load(find_xplane(str(TRACE_DIR)))


def decode_scope_share(scope: str, summary: Optional[ScopeSummary] = None):
    """Percent of ``jit_decode``'s device op time whose innermost scope is
    ``scope``; ``None`` where the program sets no scope or the window ran
    no decode step."""
    if not SCOPES:
        return None
    return (summary or window_summary()).scope_share("jit_decode", scope)


def decode_host_gap_ms(summary: Optional[ScopeSummary] = None):
    """Device idle ms per decode step while the host is in the engine's
    sample, schedule or decode-dispatch phase; ``None`` without engine
    spans or decode steps."""
    s = summary or window_summary()
    steps = s.executions.get("jit_decode", 0)
    if not s.serve_spans or not steps:
        return None
    return 1e3 * sum(s.gap_s.get(k, 0.0) for k in HOST_PHASES) / steps
