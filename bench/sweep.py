#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate with no
growing backlog and at least 90% of requests within the mix's TTFT and
TPOT limits (its ``slo``).

    python3 bench/sweep.py --workload <cell> --rates 2,4,6 --seconds 30

One process and one engine; each rate gets the cell's traffic at that
rate, a window and its drain, then the engine empties before the next.
One JSON line per rate.  The benchmark's runs use the rate fixed in the
traffic file; this only informs that choice.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402


def in_system(requests, t: float) -> int:
    return sum(r.sent <= t and (r.done_at is None or r.done_at > t)
               for r in requests)


def summary(rec, slo: dict) -> dict:
    from bench.harness import BENCH, load_module
    ok, ttfts, tpots = 0, [], []
    for r in rec.requests:
        ttft = (r.first if r.first is not None else rec.end_s) - r.spec.due
        tpot = ((r.last - r.first) / (r.n_out - 1) * 1e3
                if r.done_at is not None and r.n_out >= 2 else 0.0)
        ok += (r.done_at is not None and ttft <= slo["ttft_s"]
               and tpot <= slo["tpot_ms"])
        ttfts.append(round(ttft, 3))
        tpots.append(round(tpot, 1))
    read = {name: load_module(BENCH / "metrics" / f"{name}.py").read(rec)
            for name in ("output_tok_s", "ttft_p95_s", "tpot_p95_ms")}
    return dict(read, attained=ok / len(rec.requests),
                backlog_mid=in_system(rec.requests, rec.seconds / 2),
                backlog_end=in_system(rec.requests, rec.seconds),
                requests=len(rec.requests), ttft_s=ttfts, tpot_ms=tpots)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="bench/sweep.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    run.prepare()
    from bench import harness
    cell = harness.load_cell(args.workload)
    devices = run.device_check(cell.chips)
    engine = harness.build_engine(cell, harness.program_params(
        cell, harness.weights(cell, args.seed, devices[0])))
    harness.warm_up(engine, cell, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        c = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                   rate_per_s=rate))
        schedule = harness.make_schedule(c, args.seed, args.seconds)
        rec = harness.Record(cell=c, seed=args.seed, seconds=args.seconds,
                             open_loop=True)
        harness.drive(engine, schedule, rec)
        while engine.step():            # empty the engine for the next rate
            pass
        print(json.dumps(dict(summary(rec, cell.traffic["slo"]),
                              rate=rate, workload=cell.name)), flush=True)


if __name__ == "__main__":
    main()
