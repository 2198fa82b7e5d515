"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time in the traced window, time per device
operation, the events of named kernels, and the idle gaps labelled by the
benchmark's own host spans.

Device planes are those named ``/device:TPU:<n>``; their operations are the
events of the ``XLA Ops`` line, each named by the program module it ran in
(the ``XLA Modules`` line) and its HLO instruction, as in
``jit_traced(123)/%masked_matmul.5``. Busy time is the union of the
operations' intervals inside the window, averaged over the chips. The
window is the host span ``bench.trace_window`` that the run puts around the
traced seconds, on the same clock.
"""
from __future__ import annotations

import bisect
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.trace_window"
SPAN_PREFIX = "bench."


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                                  # mean over chips
    n_devices: int
    op_seconds: Dict[str, float] = field(default_factory=dict)
    op_events: Dict[str, List[float]] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def reduce_events(device_ops: Dict[str, List[Tuple[str, float, float]]],
                  host_spans: List[Tuple[str, float, float]],
                  ) -> TraceSummary:
    """The reduction itself, over plain tuples in nanoseconds:
    ``device_ops`` maps each device plane to its (name, start, duration)
    operations, ``host_spans`` lists the host's (name, start, duration)
    ``bench.*`` spans, among them the window."""
    wins = [(s, s + d) for n, s, d in host_spans if n == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(wins)}")
    w0, w1 = wins[0]
    op_s: Dict[str, float] = defaultdict(float)
    op_ev: Dict[str, List[float]] = defaultdict(list)
    busy_total = 0.0
    merged_first: List[Tuple[float, float]] = []
    for k, (plane, ops) in enumerate(sorted(device_ops.items())):
        ivs = []
        for name, s, d in ops:
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            ivs.append((a, b))
            op_s[name] += (b - a) * 1e-9
            op_ev[name].append(d * 1e-9)
        merged = _union(ivs)
        busy_total += sum(b - a for a, b in merged) * 1e-9
        if k == 0:
            merged_first = merged
    spans = [(n, s, s + d) for n, s, d in host_spans if n != WINDOW_SPAN]
    gaps = []
    edges = [w0] + [x for iv in merged_first for x in iv] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            mid = (a + b) / 2
            held = Counter(n for n, s, e in spans if s <= mid <= e)
            label = "+".join(f"{n} x{k}" for n, k in sorted(held.items()))
            label = label or "no bench span"
            gaps.append((label, (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    n_dev = max(1, len(device_ops))
    return TraceSummary(window_s=(w1 - w0) * 1e-9, busy_s=busy_total / n_dev,
                        n_devices=len(device_ops), op_seconds=dict(op_s),
                        op_events=dict(op_ev), gaps=gaps)


def op_name(event_name: str) -> str:
    """The HLO instruction of an ``XLA Ops`` event (``%fusion.5`` of
    ``%fusion.5 = f32[...] fusion(...)``)."""
    return event_name.split(" = ", 1)[0]


def _label_ops(lines) -> List[Tuple[str, float, float]]:
    """(module/instruction, start, duration) of a device plane's ops."""
    mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                  for line in lines if line.name == MODULES_LINE
                  for ev in line.events)
    starts = [m[0] for m in mods]
    out = []
    for line in lines:
        if line.name != OPS_LINE:
            continue
        for ev in line.events:
            k = bisect.bisect_right(starts, ev.start_ns) - 1
            mod = (mods[k][2] if k >= 0 and ev.start_ns < mods[k][1]
                   else "?")
            out.append((f"{mod}/{op_name(ev.name)}", ev.start_ns,
                        ev.duration_ns))
    return out


def read(path: str) -> TraceSummary:
    """Reduce one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_ops: Dict[str, List[Tuple[str, float, float]]] = {}
    host_spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            device_ops[plane.name] = _label_ops(list(plane.lines))
        elif plane.name.startswith("/host:"):
            host_spans.extend((ev.name, ev.start_ns, ev.duration_ns)
                              for line in plane.lines for ev in line.events
                              if ev.name.startswith(SPAN_PREFIX))
    return reduce_events(device_ops, host_spans)


def options():
    """Profiler options of a traced run: host annotations, no Python
    function tracer (it records every call and slows the host)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def breakdown(summary: TraceSummary, n: int = 10) -> Dict[str, list]:
    """The result line's ``breakdown``: the device operations that took the
    most time, and the longest idle gaps with what the host was doing."""
    ops = sorted(summary.op_seconds.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in summary.gaps[:n]]}
