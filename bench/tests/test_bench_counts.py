"""FLOP and byte counts, through the configuration's family, against
numbers worked by hand, and the peaks table."""
import json
from pathlib import Path

import pytest

from bench import flops
from bench.tests.tiny import full as load_cell

BENCH = Path(__file__).resolve().parents[1]


def test_stablelm_counts_by_hand():
    cell = load_cell("stablelm-3b.chat_shared")
    fam, m = cell.family, cell.dims
    # per layer: q,k,v,o 4 * 2560*2560 = 26,214,400; SwiGLU 3 * 2560*6912
    # = 53,084,160; 32 layers = 2,537,553,920; head 50304*2560 =
    # 128,778,240
    assert fam.matmul_params(m) == 2_666_332_160
    # one token at context 1000: 2 * params + 4 * 32 * 32 * 80 * 1000
    assert fam.model_flops(m, [(1000, 1)]) == \
        2 * 2_666_332_160 + 327_680_000
    f, b = fam.paged_decode_work(m, [(1000, 1)])
    assert f == 327_680_000
    # 32 layers * (K and V: 2 * 32 heads * 80 * 1000 lines * 2 bytes
    # + q and o: 2 * 32 * 80 * 2 bytes)
    assert b == 32 * (10_240_000 + 10_240)
    assert m.kv_bytes_per_token == 327_680


def test_starcoder2_counts_by_hand():
    cell = load_cell("starcoder2-3b.code_decode")
    fam, m = cell.family, cell.dims
    # q 3072*3072 + k,v 2 * 3072*256 + o 3072*3072 = 20,447,232; GELU MLP
    # 2 * 3072*12288 = 75,497,472; 30 layers = 2,878,341,120; head
    # 49152*3072 = 150,994,944
    assert fam.matmul_params(m) == 3_029_336_064
    # two tokens over 3000 lines in all: one at 1000, one at 2000
    f, b = fam.paged_decode_work(m, [(1000, 1), (2000, 1)])
    assert f == 4 * 30 * 24 * 128 * 3000
    assert b == 30 * 2 * 2 * 128 * 2 * 3000 + 30 * 2 * 24 * 128 * 2 * 2
    assert m.kv_bytes_per_token == 30_720


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(500.0, 20.0, peak) == 5.0
    assert flops.roofline_seconds(100.0, 40.0, peak) == 4.0


def test_peaks_table_has_the_v5e():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error():
    from bench import run
    with pytest.raises(SystemExit):
        run.peak_of("TPU v99")
