"""From a profiler trace (``.xplane.pb``) to what the metrics read.

The window is the ``bench.traced`` host span.  On each device plane the
operations of the op line are clipped to it; busy time is the union of
their intervals (averaged over the devices), idle gaps are what the union
leaves out, and each gap is named by the innermost benchmark host span
that covers its middle (``engine.step``, ``bench.bookkeeping``, ...).
Host spans and device operations share the profiler's clock.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

#: host spans the benchmark writes around its own calls
HOST_SPANS = ("bench.traced", "engine.step", "bench.submit",
              "bench.bookkeeping", "bench.idle")
#: device-plane lines that hold the operations, in order of preference
OP_LINES = ("XLA Ops",)
_DIGITS = re.compile(r"[.\-_]\d+$")


#: operations that only contain others (their time is their body's)
CONTAINERS = ("while", "conditional", "call")


def op_label(name: str) -> str:
    """An operation's HLO name without its instance number: the TPU trace
    names an op by its HLO text (``%fusion.12 = bf16[...] fusion(...)``),
    and ``fusion.12`` and ``fusion.3`` are both ``fusion``."""
    return _DIGITS.sub("", name.split(" = ", 1)[0].lstrip("%"))


def union(intervals) -> list:
    """Disjoint, sorted cover of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def covered(cover: list, starts: list, s: float, e: float) -> float:
    """Length of ``[s, e]`` that the disjoint, sorted ``cover`` (whose
    interval starts are ``starts``) covers."""
    total, i = 0.0, max(bisect.bisect_right(starts, s) - 1, 0)
    while i < len(cover) and cover[i][0] < e:
        total += max(0.0, min(e, cover[i][1]) - max(s, cover[i][0]))
        i += 1
    return total


def _device_ops(plane):
    lines = {ln.name: ln for ln in plane.lines}
    for name in OP_LINES:
        if name in lines:
            return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in lines[name].events]
    return []


def collect(profile):
    """(host spans {name: [(start, end)]}, device op lists) in ns."""
    spans = defaultdict(list)
    devices = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            ops = _device_ops(plane)
            if ops:
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name in HOST_SPANS:
                        spans[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    return dict(spans), devices


def reduce(spans: dict, devices: list, top: int = 10) -> dict:
    """Busy and idle time, per-operation device time, device time inside
    each ``engine.step`` span and idle time by host span, in seconds."""
    if not spans.get("bench.traced") or not devices:
        raise ValueError("trace has no bench.traced span or no device ops")
    w0, w1 = spans["bench.traced"][0]
    steps = sorted(s for s in spans.get("engine.step", []) if w0 <= s[0] < w1)
    # the benchmark's own spans follow one another without nesting
    inner = sorted((s, e, name) for name, ss in spans.items()
                   if name != "bench.traced" for s, e in ss)
    starts = [s for s, _, _ in inner]
    busy = 0.0
    ops_s: dict = defaultdict(float)
    step_busy = [0.0] * len(steps)
    idle_by: dict = defaultdict(float)
    for ops in devices:
        clipped = [(max(s, w0), min(e, w1), n) for n, s, e in ops
                   if e > w0 and s < w1]
        for s, e, n in clipped:
            label = op_label(n)
            if label not in CONTAINERS:
                ops_s[label] += (e - s) / 1e9
        cover = union((s, e) for s, e, _ in clipped)
        busy += sum(e - s for s, e in cover)
        cover_starts = [s for s, _ in cover]
        for i, (s, e) in enumerate(steps):
            step_busy[i] += covered(cover, cover_starts, s, e)
        edges = [w0] + [x for iv in cover for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                mid = (a + b) / 2
                i = bisect.bisect_right(starts, mid) - 1
                owner = (inner[i][2] if i >= 0 and inner[i][1] >= mid
                         else "host outside benchmark spans")
                idle_by[owner] += (b - a) / 1e9
    n = len(devices)
    ranked = sorted(ops_s.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy / n / 1e9,
        "ops_s": {k: v / n for k, v in ops_s.items()},
        "step_busy_s": [b / n / 1e9 for b in step_busy],
        "device_ops": [[k, v / n] for k, v in ranked[:top]],
        "idle_gaps": [[k, v / n] for k, v in
                      sorted(idle_by.items(), key=lambda kv: -kv[1])[:top]],
    }


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce(*collect(ProfileData.from_file(path)))
