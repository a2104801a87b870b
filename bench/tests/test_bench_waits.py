"""The split of each request's wait for its first token, as the engine
keeps it: the readers on scripted requests worked by hand, and a real
engine at reduced size whose split fits inside the harness's time to
first token."""
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness
from bench.harness import BENCH, Record, Tracked, drive, load_module
from bench.schedule import OpenSchedule, RequestSpec

_CELL = harness.Cell(name="fake", chips=1, config={}, traffic={},
                     end_to_end=[], per_layer=[])


def _read(name, rec):
    return load_module(BENCH / "metrics" / f"{name}.py").read(rec)


def _tracked(sent, first, **req):
    spec = RequestSpec(index=0, prompt=np.arange(2, 6, dtype=np.int32),
                       max_new=4, due=sent)
    return Tracked(spec, SimpleNamespace(**req), sent=sent, in_window=True,
                   first=first)


def _scripted():
    """Five window requests; the run stopped waiting 12 s after the window
    opened, which the program's clock (an offset of 99.5 s) reads 111.5."""
    life = dict(queue_s=0.0, prefill_s=None, queued_at=None,
                admitted_at=None)
    return Record(cell=_CELL, seed=0, seconds=10.0, open_loop=False,
                  end_s=12.0, requests=[
        # answered: the engine's split as is
        _tracked(0.0, 4.5, **dict(life, submitted_at=99.5, queue_s=1.0,
                                  prefill_s=3.0)),
        _tracked(0.0, 9.2, **dict(life, submitted_at=99.5, queue_s=4.0,
                                  prefill_s=4.0)),
        # prefilling since 105.5 when the run stopped waiting
        _tracked(0.5, None, **dict(life, submitted_at=100.0, queue_s=5.5,
                                   admitted_at=105.5)),
        # queued since 101.5
        _tracked(1.5, None, **dict(life, submitted_at=101.0,
                                   queued_at=101.5)),
        # a program that keeps no split
        _tracked(2.0, 3.0, rid=4)])


def test_wait_readers_by_hand():
    rec = _scripted()
    assert _read("queue_wait_p95_s", rec) == pytest.approx(
        np.percentile([1.0, 4.0, 5.5, 10.0], 95))
    assert _read("prefill_wait_p95_s", rec) == pytest.approx(
        np.percentile([3.0, 4.0, 6.0, 0.0], 95))


@pytest.mark.parametrize("counters,want", [
    ({"host_s": 0.033, "iterations": 2}, 16.5),
    ({"host_s": 0.0, "iterations": 0}, None),
    ({"iterations": 5}, None)])              # a program without the counter
def test_step_host_reader_by_hand(counters, want):
    rec = Record(cell=_CELL, seed=0, seconds=1.0, open_loop=False,
                 counters=counters)
    got = _read("step_host_ms", rec)
    assert got == (None if want is None else pytest.approx(want))


def test_a_queued_request_splits_its_ttft(monkeypatch):
    """Six requests on four slots: each one's queue and prefill waits fit
    inside the harness's time to first token, the two that queued behind
    full slots waited longest, the tracer's QUEUED and PREFILL spans read
    the very stamps the waits come from, and the engine's own TTFT series
    counts from submission, queue included."""
    from repro.models import init_params
    from repro.monitoring import Tracer
    from repro.monitoring.trace import METRIC_SERVE_TTFT

    from bench.tests import tiny
    tiny.use_reduced_program(monkeypatch)
    cell = tiny.cell("starcoder2-3b.code_decode")
    eng = harness.build_engine(
        cell, init_params(harness.program_config(cell), 0))
    eng.tracer = eng.admission.tracer = Tracer(clock=time.perf_counter)
    rng = np.random.default_rng(5)
    specs = [RequestSpec(index=i, prompt=rng.integers(2, 500, 6 + 3 * i)
                         .astype(np.int32), max_new=6, due=0.0)
             for i in range(6)]
    rec = Record(cell=cell, seed=0, seconds=0.01, open_loop=True)
    drive(eng, OpenSchedule(specs), rec)
    assert all(r.done_at is not None for r in rec.requests)
    reqs = [r.req for r in rec.requests]
    ttft = [r.first - r.spec.due for r in rec.requests]
    for req, t in zip(reqs, ttft):
        assert req.queue_s >= 0 and req.prefill_s > 0
        assert req.queue_s + req.prefill_s <= t
    rid_of = {sp.sid: sp.attrs["rid"]
              for sp in eng.tracer.spans(cat="request")}
    queued, prefill = {}, {}
    for sp in eng.tracer.spans(name="QUEUED"):
        queued[rid_of[sp.parent]] = queued.get(rid_of[sp.parent], 0.0) \
            + sp.duration
    for sp in eng.tracer.spans(name="PREFILL"):
        prefill[rid_of[sp.parent]] = sp
    for req in reqs:
        sp = prefill[req.rid]
        assert queued[req.rid] == pytest.approx(req.queue_s, abs=1e-12)
        assert sp.duration == pytest.approx(req.prefill_s, abs=1e-12)
        assert sp.attrs["iterations"] >= 1 and sp.attrs["chunks"] >= 1
    slots = cell.config["engine"]["slots"]
    assert min(r.queue_s for r in reqs[slots:]) \
        > max(r.queue_s for r in reqs[:slots])
    hist = eng.tracer.metrics.histogram(METRIC_SERVE_TTFT)
    assert hist.count(tenant="default", qos="normal") == len(specs)
    assert sum(r.queue_s + r.prefill_s for r in reqs) \
        <= hist.sum(tenant="default", qos="normal") <= sum(ttft)
    # the harness's window counters carry the step's host time
    assert 0 < eng.serve_stats["host_s"] and "host_s" in rec.counters
