"""The engine's step phases in a profiler trace (``.xplane.pb``).

``repro.monitoring.trace.phase`` writes a host span for each phase of an
engine step (``engine.admit``, ``engine.plan``, ``engine.dispatch``,
``engine.sync``, ``engine.commit``); they nest inside the benchmark's
``engine.step`` span, which ``bench.trace_reduce`` keeps out of its own
reduction.  Here, for the same ``bench.traced`` window:

* ``idle_phases``: the device-idle time that ``trace_reduce.reduce`` puts
  under ``engine.step``, by the innermost engine phase covering each
  gap's middle (``engine.step`` where none does);
* ``step_host_s``: each step's length less what its ``engine.sync``
  phases cover (the trace counterpart of ``step_host_ms``; device work
  still queued may run under it);
* ``programs``: device time per jitted program, from the device plane's
  ``XLA Modules`` line (``jit_mixed(2103...)`` counts as ``jit_mixed``).

A trace with no engine phases gives empty ``idle_phases`` and
``step_host_s``.  ``python3 -m bench.engine_trace <trace>[.gz]`` prints
them for a trace that ``bench/run.py --trace 1 --keep-trace DIR`` kept.
"""
from __future__ import annotations

import bisect
import gzip
import json
import sys
from collections import defaultdict

from bench.trace_reduce import collect, covered, reduce, union

#: host spans the engine writes around the phases of each step
PHASES = ("engine.admit", "engine.plan", "engine.dispatch", "engine.sync",
          "engine.commit")
#: the device-plane line that holds one event per program run
MODULE_LINE = "XLA Modules"
#: an idle gap inside ``engine.step`` that no engine phase covers
UNPHASED = "engine.step"


def program_label(name: str) -> str:
    """A program's name without its fingerprint."""
    return name.split("(", 1)[0]


def collect_engine(profile):
    """(engine phase spans {name: [(start, end)]}, per-device program runs
    [(name, start, end)]) in ns."""
    phases = defaultdict(list)
    modules = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            for ln in plane.lines:
                if ln.name == MODULE_LINE:
                    runs = [(ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns)
                            for ev in ln.events]
                    if runs:
                        modules.append(runs)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name in PHASES:
                        phases[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    return dict(phases), modules


def _clip(events, w0: float, w1: float) -> list:
    """``(start, end, name)`` of the ``(name, start, end)`` events that
    overlap ``[w0, w1]``, clipped to it."""
    return [(max(s, w0), min(e, w1), n) for n, s, e in events
            if e > w0 and s < w1]


def _covering(intervals: list, starts: list, t: float):
    """The name of the one of the sorted ``(start, end, name)`` intervals
    (with their ``starts``) that started last among those covering ``t``:
    the innermost, where they nest."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        s, e, name = intervals[i]
        if e >= t:
            return name
        i -= 1
    return None


def reduce_engine(spans: dict, devices: list, phases: dict, modules: list,
                  top: int = 10) -> dict:
    """``idle_phases``, ``step_host_s`` and ``programs`` in seconds, from
    ``trace_reduce.collect``'s spans and device ops and ``collect_engine``'s
    phases and program runs."""
    w0, w1 = spans["bench.traced"][0]
    steps = sorted(s for s in spans.get("engine.step", []) if w0 <= s[0] < w1)
    # the benchmark's spans, as trace_reduce.reduce names a gap by them
    bench = sorted((s, e, name) for name, ss in spans.items()
                   if name != "bench.traced" for s, e in ss)
    bench_starts = [s for s, _, _ in bench]
    nested = sorted((s, e, name) for name, ss in phases.items()
                    for s, e in ss)
    starts = [s for s, _, _ in nested]
    idle: dict = defaultdict(float)
    for ops in devices if nested else []:
        cover = union((s, e) for s, e, _ in _clip(ops, w0, w1))
        edges = [w0] + [x for iv in cover for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            mid = (a + b) / 2
            i = bisect.bisect_right(bench_starts, mid) - 1
            if b <= a or i < 0 or bench[i][1] < mid \
                    or bench[i][2] != "engine.step":
                continue
            idle[_covering(nested, starts, mid) or UNPHASED] += (b - a) / 1e9
    host = []
    if nested:
        sync = union(phases.get("engine.sync", []))
        sync_starts = [s for s, _ in sync]
        host = [(e - s - covered(sync, sync_starts, s, e)) / 1e9
                for s, e in steps]
    programs: dict = defaultdict(float)
    for runs in modules:
        for s, e, name in _clip(runs, w0, w1):
            programs[program_label(name)] += (e - s) / 1e9
    n, m = max(len(devices), 1), max(len(modules), 1)
    return {
        "idle_phases": [[k, v / n] for k, v in
                        sorted(idle.items(), key=lambda kv: -kv[1])[:top]],
        "step_host_s": host,
        "programs": [[k, v / m] for k, v in
                     sorted(programs.items(), key=lambda kv: -kv[1])[:top]],
    }


def load(path: str):
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path) as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def main(argv=None) -> dict:
    profile = load((argv or sys.argv[1:])[0])
    spans, devices = collect(profile)
    out = {k: v for k, v in reduce(spans, devices).items() if k != "ops_s"}
    out.update(reduce_engine(spans, devices, *collect_engine(profile)))
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
