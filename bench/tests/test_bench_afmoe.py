"""The ``afmoe`` family (trinity-mini): its weights pinned, its counts by
hand, the program's chunked prefill and paged decode against its
reference, its expert-layer metrics, and a correct cell whose float8
control is not."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, harness, run
from bench.harness import BENCH, Record, load_module
from bench.tests import tiny
from bench.tests.test_bench_families import _digest
from bench.weights import make_weights

CELL = "trinity-mini.code_decode"


@pytest.fixture
def reduced(monkeypatch):
    tiny.use_reduced_program(monkeypatch)


def test_weights_are_bit_for_bit_as_pinned():
    """sha256 of (make_weights, to_program) at the reduced size for seed
    2**33 + 5: a change of any leaf's name, shape, scale or draw shows."""
    cell = tiny.cell(CELL)
    fam, m = cell.family, cell.dims
    w = make_weights(fam.layout(m), 2**33 + 5)
    assert (_digest(w), _digest(fam.to_program(w, m))) == (
        "875f1e4e0306c433f7d24a715b7bda94202ff327c24534a43c8fb2dbdfcf2b60",
        "0498bfc5996de49e23c0ee74eb69aa05d19ac73fd355607b303c91b061a7a6c2")


def test_the_cut_is_layers_0_to_11_and_experts_0_to_31():
    cell = tiny.full(CELL)
    m = cell.dims
    assert (m.L, m.first, m.n, m.E, m.k, m.dense, m.period) == (
        12, 0, 32, 128, 8, 2, 12)
    assert (m.d, m.H, m.K, m.Dh, m.f, m.fe, m.V) == (
        2048, 32, 4, 128, 6144, 1024, 200192)
    assert m.windows == (2048, 2048, 2048, None) * 3
    assert m.kv_bytes_per_token == 24_576     # 1025 pages of 128: 3.22 GB
    assert cell.config["source_values"]["num_experts"] == 128
    assert sorted(cell.config["reduced"]) == sorted(
        ["num_hidden_layers", "num_experts", "layer_types"])


def test_counts_by_hand():
    m = tiny.full(CELL).dims
    fam = tiny.full(CELL).family
    # per layer: q and gate 2*2048*4096, k and v 2*2048*512, o 4096*2048
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048       # 27262976
    dense = 3 * 2048 * 6144                                     # 37748736
    # router 2048*128, shared 3*2048*1024, routed 8 of 128 with 32 here
    expert = 2048 * 128 + 3 * 2048 * 1024 + 3 * 2048 * 1024 * 2  # 19136512
    params = 12 * attn + 2 * dense + 10 * expert + 200192 * 2048
    assert params == fam.matmul_params(m) == 1004011520
    # one token at its first line: 12 layers attend one line each
    assert fam.model_flops(m, [(1, 1)]) == 2.0 * params + 4 * 32 * 128 * 12
    # 3 tokens at 100, 101, 102 lines, in all 12 layers
    lines = 12 * (100 + 101 + 102)
    assert fam.paged_decode_work(m, [(100, 3)]) == (
        4.0 * 32 * 128 * lines,
        float(2 * 4 * 128 * 2 * lines + 12 * 2 * 32 * 128 * 2 * 3))
    # at 2047-2050 lines the 9 sliding layers see at most 2048
    f, _ = fam.paged_decode_work(m, [(2047, 4)])
    assert f == 4.0 * 32 * 128 * (9 * (2047 + 3 * 2048)
                                  + 3 * (2047 + 2048 + 2049 + 2050))
    # 128 pairs over 31 reached experts: 6*d*fe FLOPs a pair, each
    # expert's three bf16 matrices once, each pair's bf16 row in and out
    assert fam.moe_work(m, {"moe_routed_pairs": 128,
                            "moe_experts_reached": 31}) == (
        6.0 * 2048 * 1024 * 128,
        float(31 * 3 * 2048 * 1024 * 2 + 128 * 2 * 2048 * 2))


def test_paged_program_matches_the_reference(reduced):
    """Chunked prefill (a 16-token chunk, then a padded 14-token one) and
    paged decode through the program's own entry points, against the
    float32 reference's logits at the same positions."""
    from repro.configs.base import RunConfig
    from repro.models.model import decode_step, init_cache, prefill_chunk
    from repro.models.paging import PagedKVConfig
    from repro.serving import DecodeEngine
    cell = tiny.cell(CELL)
    fam, m = cell.family, cell.dims
    cfg = harness.program_config(cell)
    assert cfg.dtype == "float32" and cfg.moe.held() == (0, 2)
    run_cfg = RunConfig(remat="none")
    w = make_weights(fam.layout(m), 2**35 + 1)
    params = fam.to_program(w, m)
    cache = init_cache(cfg, 1, 128, paging=PagedKVConfig(
        page_size=16, num_pages=9, pages_per_seq=8))
    cache["moe_stats"] = jnp.zeros(4, jnp.int32)
    row = jnp.arange(1, 9, dtype=jnp.int32)[None]
    tokens = np.random.default_rng(1).integers(2, m.V, 40).astype(np.int32)
    got = []
    for start, n in ((0, 16), (16, 14)):
        chunk = np.zeros(16, np.int32)
        chunk[:n] = tokens[start:start + n]
        pos = start + np.arange(16)
        logits, slices = prefill_chunk(
            params, {"tokens": jnp.asarray(chunk)[None]}, cache, row,
            jnp.int32(start), cfg, run_cfg, last_pos=jnp.int32(n - 1))
        cache = DecodeEngine._scatter_chunk(
            cache, slices, row[0, pos // 16], jnp.asarray(pos % 16))
    got.append(logits[0, 0])
    for p in range(30, 39):
        logits, cache = decode_step(params, cache,
                                    jnp.asarray(tokens[p:p + 1])[None],
                                    jnp.asarray([p], jnp.int32), cfg,
                                    run_cfg, page_table=row)
        got.append(logits[0, 0])
    want = fam.reference.logits(w, m, tokens, np.arange(29, 39))
    np.testing.assert_allclose(np.stack(got), want, atol=2e-2, rtol=0)
    # decode steps alone are counted (a step whose token chose no held
    # expert adds no pass), the chunks' 30 tokens not at all
    pairs, passes = (int(v) for v in cache["moe_stats"][:2])
    assert 1 <= passes <= 9 and passes <= pairs <= 9 * 2


def test_reference_leaves_out_positions_routed_at_a_near_tie(capsys):
    """Where some expert layer's k-th and (k+1)-th selection scores lie
    closer than ``route_margin``, bfloat16 may choose another expert than
    float32: the reference's row there reads a gap of 0 for any token, the
    other rows are untouched, and the count of rows left out is written."""
    import dataclasses
    cell = tiny.cell(CELL)
    fam, m = cell.family, cell.dims
    w = make_weights(fam.layout(m), 2**35 + 3)
    tokens = np.random.default_rng(3).integers(2, m.V, 40).astype(np.int32)
    rows = np.arange(40)
    margin = fam.reference.margins(w, m, tokens)
    assert margin.shape == (40,) and (margin >= 0).all()
    b = float(np.median(margin))
    raw = fam.reference.logits(w, dataclasses.replace(m, route_margin=0.0),
                               tokens, rows)
    capsys.readouterr()
    cut = fam.reference.logits(w, dataclasses.replace(m, route_margin=b),
                               tokens, rows)
    near = margin < b
    assert 0 < near.sum() < 40
    np.testing.assert_array_equal(cut[near], 0.0)
    np.testing.assert_array_equal(cut[~near], raw[~near])
    assert f"{near.sum()} of 40 positions skipped" in capsys.readouterr().err
    # the layer's own routing: its margin is the k-th less the (k+1)-th
    import jax.numpy as jnp
    h = jnp.asarray(np.random.default_rng(4).normal(size=(5, m.d)),
                    jnp.float32)
    router = fam.reference.teams(jnp.asarray(w["s01.router"][0],
                                             jnp.float32), m)
    bias = fam.reference.teams(jnp.asarray(w["s01.expert_bias"][0],
                                           jnp.float32), m)
    assert router.shape == (m.d, m.E) and bias.shape == (m.E,)
    # expert e is in team e % (E / k): every share holds part of each team
    np.testing.assert_array_equal(np.asarray(router[:, 1]),
                                  np.asarray(router[:, 1 + m.E // m.k]))
    _, _, mg = fam.reference.route(h, router, bias, m)
    pick = np.sort(np.asarray(jax.nn.sigmoid(h @ router) + bias), -1)
    np.testing.assert_allclose(np.asarray(mg),
                               pick[:, -m.k] - pick[:, -m.k - 1], rtol=1e-5)


def _record(cell, counters, ops_s, traced, window):
    return Record(cell=cell, seed=0, seconds=40.0, open_loop=False,
                  window_s=40.0, counters=counters,
                  peak={"bf16_flops_per_s": 197e12,
                        "hbm_bytes_per_s": 819e9},
                  decode_runs=window, traced_steps=traced,
                  trace=None if ops_s is None else {"ops_s": ops_s})


def test_expert_metrics_read_the_counters():
    cell = tiny.full(CELL)
    fam, m = cell.family, cell.dims
    c = {"moe_routed_pairs": 12800, "moe_expert_passes": 100,
         "moe_load_max_sum": 900, "moe_experts_reached": 3150}
    traced = [[(300, 8)] * 64]                    # one step, 512 tokens
    window = [(300, 8)] * 64 * 10                 # ten such steps
    rec = _record(cell, c, {"moe_experts_up": 0.003,
                            "moe_experts_down": 0.002, "fusion": 1.0,
                            "moe_experts_prefill_up": 0.004,
                            "moe_experts_prefill_down": 0.002},
                  traced, window)
    roof = load_module(BENCH / "metrics" / "moe_roofline.py").read(rec)
    flops, nbytes = fam.moe_work(m, c)
    assert roof == pytest.approx(
        100 * max(flops / 10 / 197e12, nbytes / 10 / 819e9) / 0.005)
    load = load_module(BENCH / "metrics" / "expert_load_max_mean.py")
    # busiest 9 a pass against a mean of 12800 / (100 * 32) = 4
    assert load.read(rec) == pytest.approx(9 / 4)


def test_expert_metrics_read_nothing_without_their_sources():
    """A dense cell, or a program without the counters (the parent's),
    gives no expert metric and raises nothing."""
    for cell in (tiny.full("starcoder2-3b.code_decode"), tiny.full(CELL)):
        rec = _record(cell, {"iterations": 3}, {"fusion": 1.0},
                      [[(300, 8)]], [(300, 8)])
        for name in ("moe_roofline", "expert_load_max_mean"):
            assert load_module(BENCH / "metrics" / f"{name}.py").read(
                rec) is None


def test_cell_is_correct_and_its_float8_control_is_not(reduced):
    """At the reduced size the program computes in float32 (the family's
    ``tiny``): with 4 experts, bfloat16's near-tie routing flips would
    swamp the comparison there (PERF.md, section 2)."""
    cell = tiny.cell(CELL)
    res = run.run_cell(cell, 2**34 + 9, 2.0, False, jax.devices(), None)
    assert res["correct"] and res["failed"] == 0, res["check"]
    assert res["check"]["tokens_compared"]["value"] >= 150
    for r in control.readings(cell, [2**33 + 22], 2.0, jax.devices()):
        assert r["tokens"] >= 150 and r["distinct"] >= 100
        assert r["program_passes"] and not r["control_passes"], r


def _routed_dropped(engine):
    """The held experts add nothing: every expert layer serves its shared
    expert alone."""
    for layer in engine.params["layers"]:
        if "moe" in layer:
            layer["moe"]["w2"] = jnp.zeros_like(layer["moe"]["w2"])


def _bias_ignored(engine):
    """The selection bias is left out of the choice of experts."""
    for layer in engine.params["layers"]:
        if "moe" in layer:
            layer["moe"]["expert_bias"] = jnp.zeros_like(
                layer["moe"]["expert_bias"])


@pytest.mark.parametrize("fault", [_routed_dropped, _bias_ignored])
def test_a_fault_in_the_expert_layer_is_not_correct(reduced, fault):
    res = run.run_cell(tiny.cell(CELL), 2**34 + 9, 2.0, False,
                       jax.devices(), None, engine_hook=fault)
    assert res["check"]["tokens_compared"]["value"] >= 150
    assert not res["correct"], res["check"]
