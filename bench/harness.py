"""One run of one cell: set-up, the measured window, and its record.

The harness is driven by data.  ``BENCHMARK.json`` names the cell; the
cell names a configuration file and a traffic mix
(``bench/traffic/<mix>.json``), the configuration names its model family
(``bench/families/<family>.py``: sizes, weights, reference, counts and
the check of the program's configuration), the mix names its generator
(``bench/generators/<name>.py``), and every metric is a reader of its
own (``bench/metrics/<name>.py``).  Adding any of them adds files and
entries; nothing here changes.

The system under test is ``repro.serving.DecodeEngine``, driven only
through ``submit`` and ``step``.  Its counters (``serve_stats`` and the
prefix-cache counters) and the requests' own outputs are all the harness
reads from it.
"""
from __future__ import annotations

import importlib.util
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


# ------------------------------------------------------------- loading ----

_MODULES: dict = {}


def load_module(path: Path):
    """The module in ``path``, run once per process: a family's ``Dims``
    stays one class, and what its reference caches stays cached."""
    path = Path(path).resolve()
    if path not in _MODULES:
        name = "_".join(("bench", path.parent.name, path.stem))
        spec = importlib.util.spec_from_file_location(
            name.replace("-", "_").replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod    # dataclasses look their module up
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict                  # the configuration file
    traffic: dict                 # the traffic file
    end_to_end: list              # BENCHMARK.json metric entries
    per_layer: list
    family: object = None         # the bench/families/<family>.py module

    @property
    def dims(self):
        return self.family.dims(self.config)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return cell_of(name, root / cfg["file"], w["traffic"], int(w["chips"]),
                   bench, root)


def cell_of(name: str, config_file: Path, traffic: str, chips: int,
            bench: dict, root: Path = ROOT) -> Cell:
    """A cell from its configuration file and traffic mix, with the
    metrics of ``bench`` (a parsed ``BENCHMARK.json``) that apply to it
    and the family module its configuration names, both under ``root``."""
    config = json.loads(Path(config_file).read_text())
    return Cell(
        name=name, chips=chips, config=config,
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{traffic}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        family=load_module(
            root / "bench" / "families" / f"{config['family']}.py"))


def weights(cell: Cell, seed: int, device=None) -> dict:
    """The seed's weights in the layout of the cell's family, as the
    reference takes them; ``program_params`` hands them to the program."""
    from bench.weights import make_weights
    return make_weights(cell.family.layout(cell.dims), seed, device)


def program_params(cell: Cell, w: dict):
    return cell.family.to_program(w, cell.dims)


def make_schedule(cell: Cell, seed: int, seconds: float):
    gen = load_module(BENCH / "generators" / f"{cell.traffic['generator']}.py")
    return gen.make(cell.traffic, seed, seconds, cell.dims.V)


# ---------------------------------------------------------- the engine ----

def program_config(cell: Cell):
    """The program's own configuration for the cell's architecture in the
    file's types, checked by the cell's family against the sizes the
    configuration file states."""
    import dataclasses

    from repro.configs import get_config
    c = cell.config
    pc = dataclasses.replace(get_config(c["arch"]),
                             param_dtype=c["dtypes"]["weights"],
                             dtype=c["dtypes"]["compute"])
    return cell.family.check_program(c, cell.dims, pc)


def build_engine(cell: Cell, params, engine_overrides: Optional[dict] = None):
    from repro.configs.base import RunConfig
    from repro.serving import DecodeEngine
    e = dict(cell.config["engine"], **(engine_overrides or {}))
    return DecodeEngine(
        program_config(cell), params, num_slots=e["slots"],
        cache_len=e["cache_len"],
        run=RunConfig(remat="none", use_pallas=e["use_pallas"]),
        decode_chunk=e["decode_chunk"], kv_page_size=e["page_size"],
        kv_pages=e["kv_pages"], prefix_cache=e["prefix_cache"],
        max_batch_tokens=e["max_batch_tokens"])


def _request(rid: int, prompt, max_new: int):
    from repro.serving import Request
    return Request(rid=rid, prompt=np.asarray(prompt, np.int32),
                   max_new_tokens=int(max_new), temperature=0.0)


def warm_up(engine, cell: Cell, seed: int) -> int:
    """Compile every program the cell's traffic can reach, through
    ``submit``/``step`` alone, with as few decode steps as will do: each
    prefill chunk bucket alone (the standalone chunk program) and beside
    one decode lane (the fused one), the decode program, the one-token
    decode program where enough lanes overflow the token budget, and a
    few requests of the cell's own mix (which also puts the mix's shared
    prefixes in the prefix cache).  Returns the number of requests."""
    e = cell.config["engine"]
    T, d, slots = e["max_batch_tokens"], e["decode_chunk"], e["slots"]
    V = cell.dims.V
    rng = np.random.default_rng([seed, 7])
    buckets = [1 << i for i in range(T.bit_length()) if 1 << i <= T]
    rid = iter(range(-1, -10**9, -1))
    served = []

    def req(prompt, max_new):
        if isinstance(prompt, int):
            prompt = rng.integers(2, V, prompt)
        r = _request(next(rid), prompt, max_new)
        engine.submit(r)
        served.append(r)
        return r

    def until(reqs, first_token_only=False):
        while not all(r.output if first_token_only else r.done
                      for r in reqs):
            engine.step()

    # the first program an engine runs sees its fresh (uncommitted) pool
    # and compiles for that alone; a throwaway request takes that compile
    until([req(1, 2)])
    for b in buckets:
        until([req(b, 2)])
    lane = req(8, d * (len(buckets) + 4))
    until([lane], first_token_only=True)
    for b in buckets:
        if b <= T - d:
            until([req(b, 2)], first_token_only=True)
    until(served)
    if slots * d > T:
        # more live lanes than the budget holds at d tokens each: the
        # engine decodes one token per lane beside a standalone chunk
        lanes = [req(8, 6 * d) for _ in range(min(slots - 1, T // d + 1))]
        until(lanes, first_token_only=True)
        until([req(1, 2)], first_token_only=True)
        until(served)
    # a traffic seed no run uses, so no warm-up prompt is a window's
    mix = make_schedule(cell, seed + (1 << 63), 0.0).pop_due(float("inf"))
    until([req(s.prompt, 2) for s in mix[:min(slots, 8)]])
    return len(served)


# ---------------------------------------------------------- the window ----

@dataclass
class Tracked:
    spec: object
    req: object
    sent: float
    in_window: bool
    n_out: int = 0
    first: Optional[float] = None
    last: Optional[float] = None
    done_at: Optional[float] = None


@dataclass
class Record:
    """What the metric readers read.  Times are seconds after the window
    opened, by the host clock."""
    cell: Cell
    seed: int
    seconds: float
    open_loop: bool
    setup_s: float = 0.0
    window_s: float = 0.0            # until the last step begun in time ends
    end_s: float = 0.0               # when the run stopped waiting
    window_tokens: int = 0           # generated by steps begun in the window
    # runs (first_ctx, n), which the family's counts read: n tokens of
    # one request, the first attending over first_ctx lines, each next
    # over one more.  Decoded: of the window's tokens, those not from
    # prefill; prompts: (1, P) for each whose first token came in it
    decode_runs: list = field(default_factory=list)
    prompt_runs: list = field(default_factory=list)
    requests: list = field(default_factory=list)     # window-due Tracked
    counters: dict = field(default_factory=dict)     # engine, over window
    budget: int = 0                  # engine max_batch_tokens
    traced_steps: list = field(default_factory=list)  # decode runs a step
    trace: Optional[dict] = None     # bench.trace_reduce.reduce output
    peak: Optional[dict] = None      # bench/peaks.json entry
    window_compiles: int = 0


def engine_counters(engine) -> dict:
    from repro.monitoring.metrics import (
        METRIC_SERVE_PREEMPTIONS, METRIC_SERVE_PREFIX_REUSED_TOKENS,
    )
    out = dict(engine.serve_stats)
    for key, name in (("prefix_reused_tokens",
                       METRIC_SERVE_PREFIX_REUSED_TOKENS),
                      ("preemptions", METRIC_SERVE_PREEMPTIONS),
                      ("page_starvations", "serve_page_starvations")):
        out[key] = engine.metrics.counter(name).value()
    return out


def _annotation(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Tracing:
    """The traced part of a ``--trace 1`` window: a stretch of whole engine
    steps, from ``start`` for ``length`` seconds, under one
    ``bench.traced`` host span.  The device is drained before the trace
    stops, so every device operation of the traced steps is inside it."""

    def __init__(self, logdir: str, start: float, length: float):
        self.logdir, self.start, self.stop = logdir, start, start + length
        self.on = self.done = False
        self._span = None

    def maybe_start(self, now: float) -> None:
        import jax
        if not self.on and not self.done and now >= self.start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host spans, not every call
            jax.profiler.start_trace(self.logdir, profiler_options=opts)
            self._span = _annotation("bench.traced")
            self._span.__enter__()
            self.on = True

    def maybe_stop(self, now: float, engine, force: bool = False) -> None:
        import jax
        if self.on and (force or now >= self.stop):
            jax.block_until_ready(engine.cache)
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.on, self.done = False, True


def drive(engine, schedule, rec: Record, clock=time.perf_counter,
          sleep=time.sleep, tracing: Optional[Tracing] = None,
          compiles=None, first_rid: int = 0) -> Record:
    """Offer the schedule to the engine for ``rec.seconds``, then keep
    offering it while the window's requests finish (at most the mix's
    ``drain_s`` more).  Each request is timed from its due time; tokens
    and counters are those of the steps begun inside the window.  Request
    ids count up from ``first_rid``."""
    seconds = rec.seconds
    drain = float(rec.cell.traffic["drain_s"])
    inflight: list[Tracked] = []
    window_open = True
    rid = first_rid
    c0 = engine_counters(engine)
    n_compiles0 = compiles() if compiles else 0
    t0 = clock()
    while True:
        now = clock() - t0
        with _annotation("bench.submit"):
            for spec in schedule.pop_due(now):
                req = _request(rid, spec.prompt, spec.max_new)
                rid += 1
                engine.submit(req)
                tr = Tracked(spec, req, sent=now, in_window=spec.due < seconds)
                inflight.append(tr)
                if tr.in_window:
                    rec.requests.append(tr)
        if window_open and now >= seconds:
            window_open = False
            rec.window_s = now
            rec.counters = {k: v - c0[k]
                            for k, v in engine_counters(engine).items()}
            if compiles:
                rec.window_compiles = compiles() - n_compiles0
        if not window_open and (now >= seconds + drain or all(
                tr.done_at is not None for tr in rec.requests)):
            break
        if tracing is not None:
            tracing.maybe_start(now)
        if not inflight:
            nd = schedule.next_due()
            wake = seconds if nd is None else nd
            if window_open:
                wake = min(wake, seconds)
            with _annotation("bench.idle"):
                sleep(max(0.0, min(wake - now, 0.005)))
            continue
        with _annotation("engine.step"):
            engine.step()
        end = clock() - t0
        with _annotation("bench.bookkeeping"):
            gen = 0
            runs = []
            still = []
            for tr in inflight:
                n = len(tr.req.output)
                if n > tr.n_out:
                    P = len(tr.spec.prompt)
                    if tr.n_out == 0:
                        tr.first = end
                        if window_open and tr.in_window:
                            rec.prompt_runs.append((1, P))
                    lo = max(tr.n_out, 1)
                    if n > lo:          # token i decodes over P + i lines
                        runs.append((P + lo, n - lo))
                    gen += n - tr.n_out
                    tr.n_out, tr.last = n, end
                if tr.req.done:
                    tr.done_at = end
                    schedule.finished(tr.spec, end)
                else:
                    still.append(tr)
            inflight = still
            if window_open:
                rec.window_tokens += gen
                rec.decode_runs += runs
            if tracing is not None and tracing.on:
                rec.traced_steps.append(runs)
                tracing.maybe_stop(end, engine)
    if tracing is not None:
        tracing.maybe_stop(clock() - t0, engine, force=True)
    rec.end_s = clock() - t0
    rec.budget = engine.max_batch_tokens or 0
    return rec

