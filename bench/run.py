#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process.  It checks first that JAX sees a TPU with the chips the cell
asks for, and exits non-zero with no result otherwise (it never falls back
to the CPU).  Then it makes the weights on the device from the seed,
builds the engine from the cell's configuration file, warms up every
program the cell's traffic reaches (all of that is ``setup_s``), offers
the traffic for ``--seconds``, waits for the window's requests, and holds
a sample of them against the float32 reference.  ``--trace 1`` profiles a
few seconds in the middle of the window and reports the per-layer metrics
instead of the end-to-end ones.

Earlier lines, on stderr, report set-up facts (compiles, cache hits,
memory).  The last lines of stderr are the numbers compared, each beside
its limit; the last line of stdout is one JSON object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
#: JAX's persistent compilation cache: a fixed directory of the checkout
CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def fail(msg: str, code: int = 3):
    log(f"FAILED: {msg}")
    raise SystemExit(code)


class CompileLog:
    """Backend compiles and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.seconds = collections.defaultdict(list)
        self.names: list = []          # every compile, in order
        self.events = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds[kw.get("fun_name", "?")].append(secs)
            self.names.append(kw.get("fun_name", "?"))

    def _event(self, event, **kw):
        if event.startswith("/jax/compilation_cache/"):
            self.events[event.rsplit("/", 1)[1]] += 1

    def count(self) -> int:
        return len(self.names)

    def report(self) -> None:
        total = sum(sum(v) for v in self.seconds.values())
        log(f"compile: {self.count()} backend compiles, {total:.1f}s; cache "
            f"{self.events['cache_hits']} hits, "
            f"{self.events['cache_misses']} misses")


def device_check(chips: int):
    """The TPU devices, or exit non-zero: no chip, no result."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        fail(f"JAX backend is {backend!r}, not 'tpu'")
    devices = jax.devices()
    if len(devices) < chips:
        fail(f"{len(devices)} TPU device(s) visible, the cell needs {chips}")
    log(f"{len(devices)} x {devices[0].device_kind}")
    return devices


def peak_of(kind: str) -> dict:
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if kind not in peaks:
        fail(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def parse(argv):
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="also copy the profiler trace into DIR")
    return ap.parse_args(argv)


def prepare():
    """Import paths and the compile cache, before anything compiles."""
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def measure(cell, seed: int, seconds: float, trace: bool, devices,
            peak, compile_log=None, keep_trace=None, engine_hook=None):
    """Set-up, window, drain; returns the record and the sample for the
    check.  ``engine_hook(engine)`` may wrap the engine (tests plant
    faults with it)."""
    from bench import harness, trace_reduce
    from bench.check import sample

    params = harness.program_params(cell,
                                    harness.weights(cell, seed, devices[0]))
    engine = harness.build_engine(cell, params)
    del params
    if engine_hook is not None:
        engine_hook(engine)
    warmed = harness.warm_up(engine, cell, seed)
    schedule = harness.make_schedule(cell, seed, seconds)
    rec = harness.Record(cell=cell, seed=seed, seconds=seconds,
                         open_loop=schedule.open_loop, peak=peak)
    tracing, logdir = None, None
    if trace:
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        tracing = harness.Tracing(logdir, start=0.4 * seconds,
                                  length=min(3.0, 0.3 * seconds))
    rec.setup_s = time.perf_counter() - T_START
    log(f"set-up {rec.setup_s:.1f}s ({warmed} warm-up requests)")
    n0 = compile_log.count() if compile_log else 0
    harness.drive(engine, schedule, rec, tracing=tracing,
                  compiles=compile_log.count if compile_log else None)
    if rec.window_compiles:
        log("compiled in the window: "
            + ", ".join(compile_log.names[n0:n0 + rec.window_compiles]))
    stats = [d.memory_stats() or {} for d in devices[:cell.chips]]
    memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    log(f"window {rec.window_s:.2f}s, drain until {rec.end_s:.2f}s, "
        f"{rec.window_compiles} compiles in the window, peak "
        f"{memory_peak} bytes of {stats[0].get('bytes_limit')}")
    log("window counters: " + json.dumps(rec.counters))
    if trace:
        files = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
        if keep_trace and files:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(files[0], keep_trace)
        rec.trace = trace_reduce.reduce_file(files[0])
        shutil.rmtree(logdir, ignore_errors=True)
    pairs = sample(rec.requests, seed, cell.config["check"]["sample_tokens"])
    engine = schedule = None
    gc.collect()
    return rec, pairs, memory_peak


def check(cell, seed: int, rec, pairs, device) -> dict:
    """The numbers compared, each with its value and limit."""
    from bench.check import max_logit_gap
    from bench.harness import weights
    gap, n = max_logit_gap(cell.family.reference,
                           weights(cell, seed, device), cell.dims, pairs)
    return {
        "max_logit_gap": {"value": gap,
                          "limit": cell.config["check"]["max_logit_gap"]},
        "tokens_compared": {"value": n, "limit": 1},
    }


def passed(numbers: dict) -> bool:
    """The widest gap within its limit, over at least one served token.
    A request still unanswered when the run stops waiting is late, not
    wrong: it counts in the tails and in ``failed``."""
    gap = numbers["max_logit_gap"]
    return (gap["value"] == gap["value"] and gap["value"] <= gap["limit"]
            and numbers["tokens_compared"]["value"]
            >= numbers["tokens_compared"]["limit"])


def read_metrics(cell, rec, trace: bool) -> dict:
    from bench.harness import BENCH, load_module
    out = {}
    for entry in (cell.per_layer if trace else cell.end_to_end):
        value = load_module(BENCH / "metrics" / f"{entry['name']}.py").read(rec)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool, devices, peak,
             keep_trace=None, engine_hook=None) -> dict:
    """One run of ``cell``: its result object, ``check`` last."""
    compile_log = CompileLog()
    rec, pairs, memory_peak = measure(
        cell, seed, seconds, trace, devices, peak, compile_log, keep_trace,
        engine_hook)
    compile_log.report()
    numbers = check(cell, seed, rec, pairs, devices[0])
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": passed(numbers), "attempted": len(rec.requests),
              "failed": sum(r.done_at is None for r in rec.requests),
              "metrics": read_metrics(cell, rec, trace),
              "device": device}
    if rec.trace is not None:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        result["breakdown"] = {"device_ops": rec.trace["device_ops"],
                               "idle_gaps": rec.trace["idle_gaps"]}
    result["check"] = numbers
    return result


def main(argv=None) -> dict:
    args = parse(argv)
    prepare()
    from bench import harness
    cell = harness.load_cell(args.workload)
    devices = device_check(cell.chips)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, peak_of(devices[0].device_kind),
                      args.keep_trace)
    for name, n in result["check"].items():
        print(f"check {name} {n['value']} limit {n['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
    os._exit(0)     # nothing may print after the result line
