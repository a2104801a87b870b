"""Benchmark harness entry point: ``PYTHONPATH=src python -m benchmarks.run``.

One module per paper table/claim:
  bench_scheduler    §3.2.3  scheduling throughput, FIFO vs backfill
  bench_parallelism  §7      ZeRO/TP per-device bytes, step wall time
  bench_serving      §3.2.1  (TensorRT role) decode throughput, prefill
  bench_kernels      §3.1.2  Pallas kernels vs oracle (interpret)
  bench_roofline     —       §Roofline table from the dry-run artifacts

Prints ``name,us_per_call,derived`` CSV rows plus the roofline table.
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks.run")
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset: scheduler parallelism serving kernels "
                         "roofline")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as a JSON artifact")
    ap.add_argument("--compare", default=None, metavar="BASELINE_JSON",
                    help="regression gate: exit 1 if any benchmark runs "
                         ">20%% slower than the named --json baseline")
    ap.add_argument("--compare-threshold", type=float, default=0.20,
                    help="allowed fractional slowdown vs baseline "
                         "(default 0.20)")
    ap.add_argument("--update-baseline", nargs="?", const=BASELINE_PATH,
                    default=None, metavar="PATH",
                    help="write this run's results as the JSON baseline "
                         "--compare reads (default benchmarks/"
                         "baseline.json) — replaces hand-editing the CI "
                         "baseline; with --compare, the gate runs against "
                         "the OLD baseline first")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (
        bench_kernels, bench_parallelism, bench_roofline, bench_scheduler,
        bench_serving,
    )
    suites = {
        "scheduler": bench_scheduler,
        "parallelism": bench_parallelism,
        "serving": bench_serving,
        "kernels": bench_kernels,
        "roofline": bench_roofline,
    }
    picked = args.only or list(suites)
    results: list[tuple[str, float, str]] = []
    t0 = time.perf_counter()
    for name in picked:
        mod = suites[name]
        t = time.perf_counter()
        mod.run(results)
        print(f"[suite {name}: {time.perf_counter() - t:.1f}s]",
              file=sys.stderr)

    print("name,us_per_call,derived")
    for name, us, derived, *_ in results:
        print(f"{name},{us:.1f},{derived}")
    if args.json:
        write_results(results, args.json)
    print(f"\n{len(results)} benchmarks in "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)
    rc = 0
    if args.compare:
        rc = compare_against(results, args.compare, args.compare_threshold)
    if args.update_baseline:
        # update AFTER the gate so the comparison ran against the old
        # baseline; the refresh happens even on a failed gate (the caller
        # decided this run is the new reference by passing the flag)
        write_results(results, args.update_baseline)
    return rc


#: where the CI regression gate looks for its committed baseline
BASELINE_PATH = "benchmarks/baseline.json"


def write_results(results, path: str):
    """Serialize results in the artifact/baseline JSON schema (shared by
    --json, --update-baseline, and the --compare reader).  A benchmark
    may append a 4th tuple element — a dict of named latency percentiles
    (e.g. ``ttft_p99_ms``) — which lands under a ``percentiles`` key and
    becomes part of the --compare regression gate."""
    import json
    rows = []
    for name, us, derived, *rest in results:
        row = {"name": name, "us_per_call": round(us, 1),
               "derived": derived}
        if rest and rest[0]:
            row["percentiles"] = rest[0]
        rows.append(row)
    with open(path, "w") as f:
        json.dump(rows, f, indent=2)
    print(f"[wrote {path}]", file=sys.stderr)


def compare_against(results, baseline_path: str,
                    threshold: float = 0.20) -> int:
    """CI regression gate: compare this run against a ``--json`` baseline
    artifact and fail (exit 1) on any >``threshold`` slowdown — i.e. a
    >20%% throughput drop by default.  Percentile keys a benchmark
    records (e.g. ``serving_latency_slo``'s ``ttft_p99_ms``) gate at the
    same threshold when present on BOTH sides, so a p99 TTFT regression
    fails CI even if mean throughput held.  Benchmarks (or percentile
    keys) present on only one side are reported but never fail the gate
    (suites evolve)."""
    import json
    with open(baseline_path) as f:
        rows = json.load(f)
    base = {row["name"]: row["us_per_call"] for row in rows}
    base_pct = {row["name"]: row.get("percentiles", {}) for row in rows}
    regressions = []

    def check(label, old, new):
        ratio = new / old if old > 0 else 1.0
        verdict = "REGRESSION" if ratio > 1.0 + threshold else "ok"
        print(f"[compare] {label}: {old:.1f} -> {new:.1f} "
              f"({ratio:.2f}x) {verdict}", file=sys.stderr)
        if ratio > 1.0 + threshold:
            regressions.append((label, old, new, ratio))

    for name, us, _, *rest in results:
        old = base.get(name)
        if old is None:
            print(f"[compare] {name}: no baseline (new benchmark)",
                  file=sys.stderr)
            continue
        check(name, old, us)
        pct = rest[0] if rest else {}
        old_pct = base_pct.get(name, {})
        shared = set(pct) & set(old_pct)
        if (pct or old_pct) and not shared:
            # a renamed/retyped percentile key silently un-gates the
            # benchmark — name both sides so the drift is visible
            print(f"[compare] WARNING {name}: no shared percentile keys "
                  f"— percentile gate skipped (current: "
                  f"{sorted(pct) or '-'}, baseline: "
                  f"{sorted(old_pct) or '-'})", file=sys.stderr)
        for key in sorted(shared):
            check(f"{name}.{key}", old_pct[key], pct[key])
    missing = sorted(set(base) - {row[0] for row in results})
    if missing:
        # a bench present in the baseline but absent from this run would
        # otherwise sail through the gate unexamined (a renamed bench, or
        # a partial `--only` run against a full baseline) — name the
        # missing keys loudly, but never fail on them (suites evolve and
        # CI legitimately gates subsets)
        print(f"[compare] WARNING: {len(missing)} baseline bench(es) not "
              f"in this run, gate skipped for: {', '.join(missing)}",
              file=sys.stderr)
    if regressions:
        print(f"[compare] FAIL: {len(regressions)} regression(s) beyond "
              f"{threshold:.0%}", file=sys.stderr)
        return 1
    print("[compare] gate passed", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
