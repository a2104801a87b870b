#!/usr/bin/env python3
"""Readings that set a cell's correctness limit: the program's widest
logit gap on many seeds, and the float8 control's (the family reference's
``fp8`` path) on the same prompts and served tokens.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

One process and one engine for all seeds (set-up is long): each seed gets
its own weights and traffic, a short window at the cell's own load, a
drain that finishes the window's requests, and then both readings over
the same sample the benchmark's check takes, each also judged by the
benchmark's own ``run.passed`` against the committed limit, and the
number of distinct tokens the sample served.  One JSON line per seed;
the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402


class _WindowOnly:
    """The cell's schedule, sending nothing due after the window: the
    drain then finishes the window's requests alone, and the engine is
    empty for the next seed's weights."""

    def __init__(self, schedule, seconds: float):
        self._s, self._end = schedule, seconds
        self.open_loop = schedule.open_loop

    def pop_due(self, now: float) -> list:
        return self._s.pop_due(now) if now < self._end else []

    def next_due(self):
        nd = self._s.next_due()
        return nd if nd is not None and nd < self._end else None

    def finished(self, spec, now: float) -> None:
        self._s.finished(spec, now)


def readings(cell, seeds, seconds: float, devices, peak=None) -> list:
    from bench import harness
    from bench.check import max_logit_gap, sample

    fam, m = cell.family, cell.dims
    limit = cell.config["check"]["max_logit_gap"]
    engine = None
    out = []
    for k, seed in enumerate(seeds):
        w = harness.weights(cell, seed, devices[0])
        if engine is None:
            engine = harness.build_engine(cell,
                                          harness.program_params(cell, w))
            harness.warm_up(engine, cell, seed)
        else:
            engine.params = harness.program_params(cell, w)
        schedule = _WindowOnly(harness.make_schedule(cell, seed, seconds),
                               seconds)
        rec = harness.Record(cell=cell, seed=seed, seconds=seconds,
                             open_loop=schedule.open_loop, peak=peak)
        harness.drive(engine, schedule, rec, first_rid=k << 32)
        while engine.step():        # anything the drain left: the next
            pass                    # seed brings other weights
        pairs = sample(rec.requests, seed,
                       cell.config["check"]["sample_tokens"])
        program, n = max_logit_gap(fam.reference, w, m, pairs)
        control, _ = max_logit_gap(fam.reference, w, m, pairs, control=True)
        r = {"seed": seed, "program": program, "control": control,
             "tokens": n, "limit": limit,
             "distinct": len(set(np.concatenate([s for _, s in pairs])
                                 .tolist())) if pairs else 0,
             "unanswered": sum(t.done_at is None for t in rec.requests)}
        for side in ("program", "control"):
            r[f"{side}_passes"] = run.passed({
                "max_logit_gap": {"value": r[side], "limit": limit},
                "tokens_compared": {"value": n, "limit": 1}})
        out.append(r)
        engine.params = w = None
        gc.collect()
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="bench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    run.prepare()
    from bench import harness
    cell = harness.load_cell(args.workload)
    devices = run.device_check(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",")]
    for r in readings(cell, seeds, args.seconds, devices):
        print(json.dumps(dict(r, workload=cell.name)), flush=True)


if __name__ == "__main__":
    main()
