"""The window's arithmetic on a scripted engine and clock: open-loop
requests are timed from their due time, the generator's lateness is
reported, a stalled request stays in the tail, and rates cover the
whole window."""
import numpy as np
import pytest

from bench import harness
from bench.harness import BENCH, Record, drive, load_module
from bench.schedule import OpenSchedule, RequestSpec


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += max(s, 1e-4)


class _Counter:
    def value(self, **_):
        return 0.0


class _Metrics:
    def counter(self, *_):
        return _Counter()


class FakeEngine:
    """Each step takes ``step_s`` and gives every live request one token;
    requests whose rid is in ``stall`` never get one."""

    def __init__(self, clock, step_s=0.1, stall=()):
        self.clock, self.step_s, self.stall = clock, step_s, set(stall)
        self.live = []
        self.serve_stats = {"iterations": 0, "decode_tokens": 0,
                            "prefill_tokens": 0, "prefill_chunks": 0}
        self.metrics = _Metrics()
        self.max_batch_tokens = 8

    def submit(self, req):
        self.live.append(req)

    def step(self):
        self.clock.t += self.step_s
        self.serve_stats["iterations"] += 1
        for r in list(self.live):
            if r.rid in self.stall:
                continue
            r.output.append(7)
            self.serve_stats["decode_tokens"] += 1
            if len(r.output) >= r.max_new_tokens:
                r.done = True
                self.live.remove(r)


def _spec(i, due, out=3):
    return RequestSpec(index=i, prompt=np.arange(2, 6, dtype=np.int32),
                       max_new=out, due=due)


def _run(specs, seconds, engine_kw=None, drain=5.0):
    cell = harness.Cell(name="fake", chips=1, config={}, traffic={
        "drain_s": drain}, end_to_end=[], per_layer=[])
    clock = Clock()
    eng = FakeEngine(clock, **(engine_kw or {}))
    rec = Record(cell=cell, seed=0, seconds=seconds, open_loop=True)
    drive(eng, OpenSchedule(specs), rec, clock=clock, sleep=clock.sleep)
    return rec


def _read(name, rec):
    return load_module(BENCH / "metrics" / f"{name}.py").read(rec)


def test_open_loop_ttft_is_timed_from_the_due_time():
    # the engine is busy 0.35 s per step, so the request due at 0.05 is
    # sent late; its first token is timed from 0.05, not from sending
    rec = _run([_spec(0, 0.0), _spec(1, 0.05)], seconds=1.0,
               engine_kw={"step_s": 0.35})
    r0, r1 = rec.requests
    assert r1.sent == pytest.approx(0.35)
    assert r1.first == pytest.approx(0.70)
    lag = _read("gen_lag_p95_ms", rec)
    assert lag == pytest.approx(np.percentile([0.0, 300.0], 95))
    ttft = _read("ttft_p95_s", rec)
    assert ttft == pytest.approx(np.percentile([0.35, 0.65], 95))


def test_a_stalled_request_stays_in_the_tail():
    specs = [_spec(i, 0.1 * i) for i in range(10)]
    rec = _run(specs, seconds=1.0, engine_kw={"stall": {4}}, drain=3.0)
    stalled = rec.requests[4]
    assert stalled.first is None and stalled.done_at is None
    # waited from its due time (0.4) until the run stopped looking
    assert rec.end_s >= 1.0 + 3.0
    ttft = sorted((r.first if r.first is not None else rec.end_s)
                  - r.spec.due for r in rec.requests)
    assert ttft[-1] == pytest.approx(rec.end_s - 0.4)
    assert _read("ttft_p95_s", rec) == pytest.approx(np.percentile(ttft, 95))
    assert _read("ttft_p95_s", rec) > 2.0


def test_tpot_and_rate_cover_the_whole_window():
    rec = _run([_spec(i, 0.0, out=5) for i in range(3)], seconds=0.45,
               engine_kw={"step_s": 0.1})
    # steps begin at 0, .1, .2, .3, .4: five steps, three tokens each
    assert rec.window_s == pytest.approx(0.5)
    assert rec.window_tokens == 15
    assert _read("output_tok_s", rec) == pytest.approx(30.0)
    assert _read("tpot_p95_ms", rec) == pytest.approx(100.0)
    # decoded tokens are all but each request's first, one a step; token
    # i of a 4-token prompt attends over 4 + i lines
    assert sorted(rec.decode_runs) == sorted([(4 + i, 1)
                                              for i in range(1, 5)] * 3)
    assert rec.prompt_runs == [(1, 4)] * 3
    assert _read("batch_fill", rec) == pytest.approx(15 / (5 * 8))


def test_requests_due_after_the_window_are_load_not_results():
    rec = _run([_spec(0, 0.0), _spec(1, 0.9), _spec(2, 1.2)], seconds=1.0)
    assert [r.spec.index for r in rec.requests] == [0, 1]


def test_readers_without_their_source_report_nothing():
    rec = _run([_spec(0, 0.0)], seconds=0.5)
    for name in ("decode_step_ms", "paged_decode_roofline",
                 "device_idle_frac", "mfu", "prefix_reuse_frac"):
        assert _read(name, rec) is None
