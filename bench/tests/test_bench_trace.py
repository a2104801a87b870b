"""The reduction from trace to metrics: on a synthetic trace worked by
hand, and on a small trace recorded on the chip."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"


def _ms(*pairs):
    return [(a * 1_000_000, b * 1_000_000) for a, b in pairs]


def test_synthetic_trace_by_hand():
    spans = {"bench.traced": _ms((10, 110)),
             "engine.step": _ms((10, 50), (60, 100)),
             "bench.bookkeeping": _ms((50, 60), (100, 110))}
    # device ops: two overlapping in step 1, one in step 2, one spilling
    # out of the window on the left
    ops = [("%fusion.1 = f32[8] fusion(f32[8] %p)", 5e6, 30e6),
           ("%fusion.2 = f32[8] fusion(f32[8] %p)", 20e6, 40e6),
           ("%while.3 = (s32[]) while((s32[]) %t)", 60e6, 90e6),
           ("%flash_decode_paged_bkgd.7 = f32[8] custom-call()", 70e6, 90e6),
           ("%copy.3 = f32[8] copy(f32[8] %p)", 95e6, 100e6)]
    out = tr.reduce(spans, [ops])
    assert out["window_s"] == pytest.approx(0.100)
    # busy: [10, 40] + [60, 90] + [95, 100] = 65 ms
    assert out["busy_s"] == pytest.approx(0.065)
    assert out["step_busy_s"] == pytest.approx([0.030, 0.035])
    assert out["ops_s"]["fusion"] == pytest.approx(0.040)
    assert out["ops_s"]["flash_decode_paged_bkgd"] == pytest.approx(0.020)
    assert "while" not in out["ops_s"]          # a container, not work
    idle = dict(out["idle_gaps"])
    # gaps: [40, 60] (mid 50: step 1 ends at 50, bookkeeping starts
    # there), [90, 95] (step 2), [100, 110] (bookkeeping)
    assert idle["bench.bookkeeping"] == pytest.approx(0.030)
    assert idle["engine.step"] == pytest.approx(0.005)
    assert out["device_ops"][0][0] == "fusion"


def test_two_devices_average():
    spans = {"bench.traced": _ms((0, 100))}
    out = tr.reduce(spans, [[("a.1", 0, 50e6)], [("a.2", 0, 100e6)]])
    assert out["busy_s"] == pytest.approx(0.075)


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce({}, [[("a", 0, 1)]])


def test_union_and_labels():
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]
    assert tr.op_label("fusion.123") == "fusion"
    assert tr.op_label("%copy-start.15 = (f32[16]) copy-start()") \
        == "copy-start"
    assert tr.op_label("custom-call") == "custom-call"


def test_recorded_chip_trace():
    """3 s of stablelm-3b.chat_shared traced on one v5e (16 slots, 128-line
    pages, three decode dispatches), gzipped."""
    import gzip

    from jax.profiler import ProfileData
    path = TESTDATA / "stablelm-3b.chat_shared.xplane.pb.gz"
    with gzip.open(path) as f:
        profile = ProfileData.from_serialized_xspace(f.read())
    out = tr.reduce(*tr.collect(profile))
    assert out["window_s"] == pytest.approx(3.4075, abs=1e-3)
    assert out["busy_s"] == pytest.approx(3.3648, abs=1e-3)
    assert [round(b, 2) for b in out["step_busy_s"]] == [1.11, 1.11, 1.11]
    assert out["device_ops"][0][0] == "flash_decode_paged_bkgd"
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["step_busy_s"] and all(b >= 0 for b in out["step_busy_s"])
    assert sum(out["step_busy_s"]) <= out["busy_s"] + 1e-9
    names = [n for n, _ in out["device_ops"]]
    assert any(n.startswith("flash_decode_paged") for n in out["ops_s"]), \
        names
