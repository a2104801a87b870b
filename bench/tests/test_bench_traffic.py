"""The traffic generators: the same seed gives the same requests, and
every seed offers the same work in another order."""
import numpy as np

from bench.harness import make_schedule
from bench.schedule import ClosedSchedule, RequestSpec
from bench.tests.tiny import full as load_cell


def _all(schedule):
    return schedule.pop_due(float("inf"))


def _key(spec):
    return (spec.due, spec.prompt.tobytes(), spec.max_new, spec.stream)


def test_same_seed_gives_the_same_requests():
    for name in ("stablelm-3b.chat_shared", "starcoder2-3b.code_decode"):
        cell = load_cell(name)
        a = _all(make_schedule(cell, 2**35 + 1, 30))
        b = _all(make_schedule(cell, 2**35 + 1, 30))
        assert [_key(s) for s in a] == [_key(s) for s in b]
        c = _all(make_schedule(cell, 2**35 + 2, 30))
        assert [_key(s) for s in a] != [_key(s) for s in c]


def test_open_loop_offers_the_same_work_to_every_seed():
    cell = load_cell("stablelm-3b.chat_shared")
    rate, seconds = cell.traffic["rate_per_s"], 30
    sizes, gaps = [], []
    for seed in (1, 99, 2**40):
        specs = _all(make_schedule(cell, seed, seconds))
        window = [s for s in specs if s.due < seconds]
        assert len(window) == round(rate * seconds)
        sizes.append(sorted((len(s.prompt), s.max_new) for s in window))
        dues = [s.due for s in window] + [seconds]
        gaps.append(np.sort(np.diff(dues)))
        sp = cell.traffic["system_prompts"]
        heads = {s.prompt[:sp["tokens"]].tobytes() for s in specs}
        assert len(heads) == sp["count"]          # every prompt shares one
    assert sizes[0] == sizes[1] == sizes[2]
    np.testing.assert_allclose(gaps[0], gaps[1])
    np.testing.assert_allclose(gaps[0], gaps[2])


def test_lengths_stay_inside_their_clips():
    cell = load_cell("stablelm-3b.chat_shared")
    t = cell.traffic
    lo = t["system_prompts"]["tokens"] + t["prompt_tokens"]["min"]
    hi = t["system_prompts"]["tokens"] + t["prompt_tokens"]["max"]
    for s in _all(make_schedule(cell, 5, 30)):
        assert lo <= len(s.prompt) <= hi
        assert t["output_tokens"]["min"] <= s.max_new \
            <= t["output_tokens"]["max"]
        assert s.prompt.min() >= 2 and s.prompt.max() < cell.dims.V


def test_closed_loop_sends_the_next_request_when_the_last_finishes():
    cell = load_cell("starcoder2-3b.code_decode")
    sched = make_schedule(cell, 3, 30)
    first = sched.pop_due(0.0)
    assert len(first) == cell.traffic["streams"]
    assert sched.pop_due(10.0) == []
    sched.finished(first[0], 4.5)
    (nxt,) = sched.pop_due(5.0)
    assert nxt.stream == first[0].stream and nxt.due == 4.5


def test_closed_loop_deals_one_multiset_to_every_seed():
    cell = load_cell("starcoder2-3b.code_decode")

    def sizes(seed):
        sched = make_schedule(cell, seed, 30)
        specs = [s for st in sched._streams for s in st]
        specs += [s for _, _, s in sched._ready]
        return sorted((len(s.prompt), s.max_new) for s in specs)

    assert sizes(1) == sizes(2**33)


def test_closed_schedule_runs_streams_dry():
    specs = [[RequestSpec(index=i, prompt=np.zeros(3, np.int32), max_new=2,
                          stream=0)] for i in range(1)]
    sched = ClosedSchedule(specs)
    (only,) = sched.pop_due(0.0)
    sched.finished(only, 1.0)
    assert sched.next_due() is None


def test_closed_loop_opens_with_the_same_burst_for_every_seed():
    cell = load_cell("starcoder2-3b.code_decode")

    def burst(seed):
        return sorted((len(s.prompt), s.max_new)
                      for s in make_schedule(cell, seed, 30).pop_due(0.0))

    assert burst(7) == burst(2**34 + 5)
