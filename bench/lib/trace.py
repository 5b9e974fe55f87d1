"""Reduction of a profiler trace to device busy time, idle share, kernel
time and the breakdown of where the window went.

The profiler writes ``<dir>/plugins/profile/<run>/*.xplane.pb``.  Device
planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation run.  The benchmark's own spans
(``jax.profiler.TraceAnnotation``: ``window``, ``search``, ``more``,
``wait_arrival``) are events on the host plane's thread lines.  Times are
in nanoseconds on the trace's one clock.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPANS = ("window", "search", "more", "wait_arrival")


@dataclass
class Event:
    name: str
    start: float
    end: float
    stats: dict = field(default_factory=dict)

    def mentions(self, words) -> bool:
        text = " ".join([self.name, *map(str, self.stats.values())])
        return any(w in text for w in words)


@dataclass
class Trace:
    devices: dict            # plane name -> [Event] of its operations
    spans: list              # [Event] of the benchmark's host spans


def load(log_dir) -> Trace:
    from jax.profiler import ProfileData

    files = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    devices: dict = {}
    spans = []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        lines = list(plane.lines)
        if DEVICE_PLANE.match(plane.name):
            ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
            devices[plane.name] = [
                Event(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                for ln in ops
                for e in ln.events
            ]
        elif plane.name.startswith("/host:"):
            spans += [
                Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                for ln in lines
                for e in ln.events
                if e.name in SPANS
            ]
    return Trace(devices, spans)


def union(intervals) -> list[tuple[float, float]]:
    """Disjoint, sorted union of ``(start, end)`` intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


@dataclass
class Reduction:
    window_s: float
    busy_s: float            # mean over the devices of their busy union
    kernel_s: dict           # kernel label -> summed device seconds
    device_ops: list         # [[name, seconds]] top operations by time
    idle_gaps: list          # [[host activity, seconds]] longest idle gaps

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(trace: Trace, kernels: dict, top: int = 10) -> Reduction:
    """``kernels`` maps a label to the words that name its events (in the
    event's name or its stats).  The window is the ``window`` span, or
    the extent of all events when there is none."""
    win = [s for s in trace.spans if s.name == "window"]
    every = [e for evs in trace.devices.values() for e in evs] + trace.spans
    if win:
        lo, hi = win[0].start, win[0].end
    elif every:
        lo, hi = min(e.start for e in every), max(e.end for e in every)
    else:
        lo, hi = 0.0, 1.0
    busy, gaps = [], []
    kernel_ns: dict = defaultdict(float)
    op_ns: dict = defaultdict(float)
    for evs in trace.devices.values():
        inside = [e for e in evs if e.end > lo and e.start < hi]
        merged = union(clip([(e.start, e.end) for e in inside], lo, hi))
        busy.append(sum(e - s for s, e in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
        for e in inside:
            op_ns[op_name(e.name)] += e.end - e.start
            for label, words in kernels.items():
                if e.mentions(words):
                    kernel_ns[label] += e.end - e.start
    gaps.sort(key=lambda g: g[0] - g[1])
    return Reduction(
        window_s=(hi - lo) / 1e9,
        busy_s=(sum(busy) / len(busy) if busy else 0.0) / 1e9,
        kernel_s={k: v / 1e9 for k, v in kernel_ns.items()},
        device_ops=[[n, t / 1e9] for n, t in sorted(op_ns.items(), key=lambda x: -x[1])[:top]],
        idle_gaps=[[activity(trace.spans, s, e), (e - s) / 1e9] for s, e in gaps[:top]],
    )


def op_name(text: str) -> str:
    """The operation's name without its HLO text: ``%fusion.3 = f32[...]
    fusion(...)`` is ``fusion.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


def activity(spans, start: float, end: float) -> str:
    """What the host was doing over ``[start, end]``: the benchmark spans
    that cover most of it, joined by ``+``, or ``other``."""
    cover: dict = defaultdict(float)
    for s in spans:
        if s.name != "window":
            cover[s.name] += max(0.0, min(s.end, end) - max(s.start, start))
    names = sorted(n for n, t in cover.items() if t >= 0.5 * (end - start))
    return "+".join(names) or "other"
