"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy time, time per kernel, the top device operations,
and the device's idle gaps by what the host was doing.

The window is the span of the harness's ``bench.step`` annotations, from
the first step's start to the last step's end, on the host clock the
profiler puts every plane on.  Device operations are the events of the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane, clipped to the window;
busy time is the length of their union, averaged over the devices.  A
kernel's time is the summed duration of the operations named for it
(``KERNELS``; an operation is named by its HLO instruction, ``%name.N``).  An idle gap is a stretch of the window in
which no operation ran on the device; it is put down to the innermost host
event that covers its middle on the thread that ran the steps.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path

KERNELS = ("compressed_matmul", "fused_page_attention")
# ops that only hold other ops (a scan's loop): their time is their body's
CONTAINERS = ("while", "conditional", "call")
STEP = "bench.step"
TOP = 10


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def op_name(event_name: str) -> str:
    """A TPU op event is named by its HLO text, ``%fusion.12 = f32[...]
    fusion(...)``; its name is the instruction's, ``fusion.12``."""
    m = re.match(r"%?([^\s=]+)", event_name)
    return m.group(1) if m else event_name


def _op_group(name: str) -> str:
    """``fusion.12`` and ``fusion.7`` are one kind of operation."""
    return re.sub(r"[.:]\d+$", "", name)


def _host_events(planes) -> tuple[list, tuple[int, int] | None]:
    """Events of the host thread that ran the steps, and the window."""
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for e in line.events]
            steps = [e for e in evs if e[2] == STEP]
            if steps:
                return evs, (min(s[0] for s in steps),
                             max(s[1] for s in steps))
    return [], None


def _innermost(events: list, starts: list, t: float) -> str:
    """The latest-starting event that covers ``t``; events of one thread
    nest, so that is the innermost."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        a, b, name = events[i]
        if b > t:
            return name
        i -= 1
    return "no host event"


def reduce(planes) -> dict | None:
    """``planes``: the ``planes`` of a ``jax.profiler.ProfileData``.
    Returns None where the trace holds no step annotation or no device."""
    planes = list(planes)
    host, window = _host_events(planes)
    if window is None:
        return None
    w0, w1 = window
    devices = [p for p in planes if re.match(r"/device:TPU:\d+$", p.name)]
    if not devices:
        return None
    kernels: dict[str, int] = defaultdict(int)
    groups: dict[str, int] = defaultdict(int)
    busy_ns = 0
    gaps: list[tuple[int, int]] = []
    for dev in devices:
        ops = []
        for line in dev.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                a, b = max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1)
                if b <= a:
                    continue
                ops.append((a, b))
                group = _op_group(op_name(e.name))
                if group not in CONTAINERS:
                    groups[group] += b - a
                if group in KERNELS:
                    kernels[group] += b - a
        merged = _union(ops)
        busy_ns += sum(b - a for a, b in merged)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(devices)
    idle: dict[str, int] = defaultdict(int)
    host.sort(key=lambda e: (e[0], -e[1]))
    starts = [e[0] for e in host]
    for a, b in gaps:
        idle[_innermost(host, starts, (a + b) / 2)] += b - a
    top_ops = sorted(groups.items(), key=lambda kv: -kv[1])[:TOP]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return {"window_s": (w1 - w0) * 1e-9,
            "busy_s": busy_ns / n * 1e-9,
            "kernels": {k: v / n for k, v in kernels.items()},
            "device_ops": [[k, v / n * 1e-9] for k, v in top_ops],
            "idle_gaps": [[k, v / n * 1e-9] for k, v in top_idle]}


def reduce_dir(tdir: str | Path) -> dict | None:
    """Reduce the newest ``.xplane.pb`` under ``tdir``."""
    import jax
    files = sorted(Path(tdir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    return reduce(jax.profiler.ProfileData.from_file(str(files[-1])).planes)
