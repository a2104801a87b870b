"""The engine's step phases in a profiler trace: they leave
``trace_reduce`` as it was, and ``engine_trace`` splits the idle time
inside the steps by them, worked by hand on a synthetic trace and read
off a small trace recorded on the chip."""
import gzip
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import engine_trace as et
from bench import trace_reduce as tr

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"


def _events(*spans):
    return [SimpleNamespace(name=n, start_ns=a * 1_000_000,
                            duration_ns=(b - a) * 1_000_000)
            for n, a, b in spans]


#: in ms: two engine steps with their phases (a sync nested in step 1's
#: commit), device ops leaving gaps inside both steps and in the
#: bookkeeping after each, and three program runs
_BENCH = [("bench.traced", 10, 110), ("engine.step", 10, 50),
          ("engine.step", 60, 100), ("bench.bookkeeping", 50, 60),
          ("bench.bookkeeping", 100, 110)]
_PHASES = [("engine.admit", 11, 12), ("engine.plan", 12, 15),
           ("engine.dispatch", 15, 20), ("engine.sync", 20, 40),
           ("engine.commit", 40, 50), ("engine.sync", 44, 48),
           ("engine.admit", 60, 61), ("engine.plan", 61, 70),
           ("engine.dispatch", 70, 72), ("engine.sync", 72, 95),
           ("engine.commit", 95, 100)]
_OPS = [("%fusion.1 = f32[8] fusion(f32[8] %p)", 5, 10.2),
        ("%fusion.3 = f32[8] fusion(f32[8] %p)", 10.8, 30),
        ("%fusion.2 = f32[8] fusion(f32[8] %p)", 20, 41),
        ("%copy.1 = f32[8] copy(f32[8] %p)", 49, 50),
        ("%while.3 = (s32[]) while((s32[]) %t)", 60, 90),
        ("%flash_decode_paged_bkgd.7 = f32[8] custom-call()", 70, 90),
        ("%copy.3 = f32[8] copy(f32[8] %p)", 95, 100)]
_MODULES = [("jit_mixed(456)", 5, 12), ("jit_mixed(123)", 15, 45),
            ("jit_chunk_fn(9)", 70, 92)]


def _profile(host):
    device = SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name="XLA Modules", events=_events(*_MODULES)),
        SimpleNamespace(name="XLA Ops", events=_events(*_OPS))])
    return SimpleNamespace(planes=[device, SimpleNamespace(
        name="/host:CPU",
        lines=[SimpleNamespace(name="python", events=_events(*host))])])


def _engine(profile):
    spans, devices = tr.collect(profile)
    return et.reduce_engine(spans, devices, *et.collect_engine(profile))


def test_engine_phases_leave_the_reduction_as_it_was():
    """The phases nest inside engine.step; ``collect``/``reduce`` give
    what they give without them."""
    out = tr.reduce(*tr.collect(_profile(_BENCH + _PHASES)))
    assert out == tr.reduce(*tr.collect(_profile(_BENCH)))
    idle = dict(out["idle_gaps"])
    # gaps [10.2, 10.8], [41, 49] (step 1) and [90, 95] (step 2);
    # [50, 60] and [100, 110] (bookkeeping)
    assert idle["engine.step"] == pytest.approx(0.0136)
    assert idle["bench.bookkeeping"] == pytest.approx(0.020)
    assert out["step_busy_s"] == pytest.approx([0.0314, 0.035])


def test_engine_phases_by_hand():
    profile = _profile(_BENCH + _PHASES)
    out = _engine(profile)
    # [41, 49] (mid 45) lies in the sync nested in step 1's commit,
    # [90, 95] in step 2's sync, [10.2, 10.8] before step 1's admit
    assert dict(out["idle_phases"]) == pytest.approx(
        {"engine.sync": 0.013, "engine.step": 0.0006})
    assert sum(v for _, v in out["idle_phases"]) == pytest.approx(
        dict(tr.reduce(*tr.collect(profile))["idle_gaps"])["engine.step"])
    # step 1: 40 ms less syncs [20, 40] and [44, 48]; step 2: 40 less 23
    assert out["step_host_s"] == pytest.approx([0.016, 0.017])
    # jit_mixed(456) is clipped to the window from 10 ms
    assert out["programs"] == [["jit_mixed", pytest.approx(0.032)],
                               ["jit_chunk_fn", pytest.approx(0.022)]]


def test_a_trace_without_engine_phases_has_none():
    out = _engine(_profile(_BENCH))
    assert out["idle_phases"] == [] and out["step_host_s"] == []
    assert [k for k, _ in out["programs"]] == ["jit_mixed", "jit_chunk_fn"]


@pytest.mark.parametrize("name,label", [
    ("jit_mixed(2103722845389188327)", "jit_mixed"),
    ("jit__argmax(1234620111088917813)", "jit__argmax"),
    ("jit_chunk_fn", "jit_chunk_fn")])
def test_program_labels(name, label):
    assert et.program_label(name) == label


def test_the_roofline_reader_finds_the_paged_kernel():
    from bench.harness import BENCH, load_module
    kernel = load_module(BENCH / "metrics" / "paged_decode_roofline.py").KERNEL
    out = tr.reduce(*tr.collect(_profile(_BENCH + _PHASES)))
    assert [n for n in out["ops_s"] if kernel.search(n)] == [
        "flash_decode_paged_bkgd"]


def _recorded():
    from jax.profiler import ProfileData
    with gzip.open(TESTDATA / "stablelm-3b.chat_shared.xplane.pb.gz") as f:
        return ProfileData.from_serialized_xspace(f.read())


def test_recorded_chip_trace_reduces_to_the_last_bit():
    """``reduce`` on the recorded trace, every key as it read before the
    engine had phases."""
    out = tr.reduce(*tr.collect(_recorded()))
    assert out["window_s"] == 3.407475181
    assert out["busy_s"] == 3.364825155
    assert out["step_busy_s"] == [1.106424825, 1.109980122, 1.108714549]
    assert out["idle_gaps"] == [["engine.step", 0.04061879499999988],
                                ["host outside benchmark spans", 0.002031231]]
    assert out["device_ops"][:3] == [
        ["flash_decode_paged_bkgd", 1.9805545019999988],
        ["copy", 0.46903528700000036],
        ["copy_bitcast_fusion", 0.20997701299999977]]
    assert len(out["ops_s"]) == 51
    assert out["ops_s"]["multiply_reduce_fusion"] == 0.01454971599999992


def test_recorded_chip_trace_names_its_programs():
    """The recorded trace predates the engine phases: it names the
    programs but has no phases to split the idle time by."""
    out = _engine(_recorded())
    assert [k for k, _ in out["programs"][:2]] == ["jit_mixed",
                                                   "jit_chunk_fn"]
    assert out["programs"][0][1] == pytest.approx(3.2045, abs=1e-3)
    assert out["idle_phases"] == [] and out["step_host_s"] == []


def test_recorded_engine_phases():
    """3.3 s of starcoder2-3b.code_decode traced on one v5e with the
    engine's phases: five mixed steps.  The phases name all of the idle
    time inside the steps."""
    profile = et.load(
        str(TESTDATA / "starcoder2-3b.code_decode.xplane.pb.gz"))
    spans, devices = tr.collect(profile)
    out = et.reduce_engine(spans, devices, *et.collect_engine(profile))
    inside = dict(tr.reduce(spans, devices)["idle_gaps"])["engine.step"]
    named = sum(v for k, v in out["idle_phases"] if k != et.UNPHASED)
    assert inside == pytest.approx(0.0751, abs=1e-4)
    assert named >= 0.9 * inside
    assert [k for k, _ in out["idle_phases"]] == ["engine.sync",
                                                  "engine.dispatch"]
    assert out["step_host_s"] == pytest.approx(
        [0.0124, 0.0113, 0.0114, 0.0109, 0.0122], abs=1e-4)
    assert [k for k, _ in out["programs"][:2]] == ["jit_mixed",
                                                   "jit_chunk_fn"]


def test_main_prints_both_reductions(capsys):
    out = et.main([str(TESTDATA / "stablelm-3b.chat_shared.xplane.pb.gz")])
    assert {"window_s", "idle_gaps", "idle_phases", "step_host_s",
            "programs"} <= set(out)
    assert '"programs"' in capsys.readouterr().out
