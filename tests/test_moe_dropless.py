"""The dropless expert layer and what serves it: a token's result does not
depend on its batch-mates, shares of the experts add up to the whole
layer, the expert kernels match ``lax.ragged_dot``, the engine pages a
windowed configuration only where the window is inert and counts the
expert work on the device, and a configuration without the new options
lowers to the same operations as before them."""
import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.configs.base import RunConfig
from repro.models import init_params
from repro.models.mlp import mlp_apply
from repro.models.moe import MOE_STATS, moe_dropless, route
from repro.serving import DecodeEngine, Request


def _moe_layer(cfg, seed=0):
    """The reduced config's expert-layer parameters, with a nonzero
    selection bias so that it takes part."""
    params = init_params(cfg, seed)
    slot = next(i for i, (_, ffn) in enumerate(
        zip(cfg.layer_kinds(), cfg.ffn_kinds())) if ffn == "moe")
    p = jax.tree.map(lambda a: a[0], params["layers"][slot]["moe"])
    if cfg.moe.expert_bias:
        p["expert_bias"] = 0.05 * jax.random.normal(
            jax.random.PRNGKey(seed + 1), p["expert_bias"].shape)
    return p


def _trinity(dtype="float32", **moe):
    cfg = get_reduced_config("trinity-mini")
    return dataclasses.replace(cfg, dtype=dtype, param_dtype=dtype,
                               moe=dataclasses.replace(cfg.moe, **moe))


@pytest.mark.parametrize("use_pallas", [False, True])
def test_a_token_reads_the_same_alone_and_among_63_others(use_pallas):
    cfg = _trinity("bfloat16")
    p = _moe_layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(7), (64, 1, cfg.d_model),
                          jnp.bfloat16)
    batch, _ = moe_dropless(p, x, cfg, use_pallas)
    for i in (0, 17, 63):
        alone, _ = moe_dropless(p, x[i:i + 1], cfg, use_pallas)
        np.testing.assert_array_equal(np.asarray(alone[0], np.float32),
                                      np.asarray(batch[i], np.float32))


def test_held_shares_add_up_to_the_uncut_layer():
    """Four chips each hold a quarter of the experts: their results, with
    the shared expert (which every chip computes alike) counted once, add
    up to the layer that holds them all."""
    cfg = _trinity()
    E = cfg.moe.num_experts
    p = _moe_layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole, _ = moe_dropless(p, x, cfg)
        shared = mlp_apply(p["shared"], x, cfg.mlp_type)
        parts = []
        for first in range(0, E, E // 4):
            held = slice(first, first + E // 4)
            share = dataclasses.replace(cfg, moe=dataclasses.replace(
                cfg.moe, experts_held=(first, E // 4)))
            y, _ = moe_dropless(dict(p, w1=p["w1"][held], w2=p["w2"][held],
                                     w3=p["w3"][held]), x, share)
            parts.append(y - shared)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)


def test_routing_keeps_softmax_with_renormalisation_selectable():
    cfg = get_reduced_config("qwen2-moe-a2.7b")
    assert (cfg.moe.score_func, cfg.moe.route_scale) == ("softmax", 1.0)
    p = _moe_layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (6, cfg.d_model))
    idx, w = route(p, x, cfg.moe)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    np.testing.assert_array_equal(
        np.asarray(idx), np.asarray(jax.lax.top_k(probs, cfg.moe.top_k)[1]))


def test_sigmoid_routing_selects_with_the_bias_and_weighs_without_it():
    cfg = _trinity()
    p = _moe_layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(9), (32, cfg.d_model))
    idx, w = route(p, x, cfg.moe)
    scores = jax.nn.sigmoid(x @ p["router"])
    want = jax.lax.top_k(scores + p["expert_bias"], cfg.moe.top_k)[1]
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want))
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(chosen / chosen.sum(-1, keepdims=True)
                                  * cfg.moe.route_scale), rtol=1e-5)


@pytest.mark.parametrize("sizes", [[0, 0, 0, 0], [5, 0, 130, 1],
                                   [0, 0, 0, 300], [60, 70, 80, 90]])
def test_expert_kernels_match_ragged_dot(sizes):
    from repro.kernels.moe_gmm import expert_swiglu
    G, d, f, rows = 4, 256, 256, 384
    k = jax.random.split(jax.random.PRNGKey(sum(sizes)), 4)
    x = jax.random.normal(k[0], (rows, d), jnp.bfloat16)
    w1, w3, w2 = ((jax.random.normal(key, shape) * shape[1] ** -0.5)
                  .astype(jnp.bfloat16) for key, shape in
                  ((k[1], (G, d, f)), (k[2], (G, d, f)), (k[3], (G, f, d))))
    gs = jnp.asarray(sizes, jnp.int32)
    got = expert_swiglu(x, w1, w3, w2, gs, block_n=128, interpret=True)

    def gdot(a, w):
        return jax.lax.ragged_dot(a, w, gs,
                                  preferred_element_type=jnp.float32)

    h = (jax.nn.silu(gdot(x, w1)) * gdot(x, w3)).astype(jnp.bfloat16)
    want = gdot(h, w2)
    n = int(sum(sizes))
    np.testing.assert_allclose(np.asarray(got)[:n], np.asarray(want)[:n],
                               rtol=2e-2, atol=2e-2)


def test_expert_kernels_take_a_width_that_512_does_not_divide():
    """qwen2-moe's experts are 1408 wide (11 x 128): the kernels tile the
    columns by 128 there and still match ``lax.ragged_dot``."""
    from repro.kernels.moe_gmm import col_tile, expert_swiglu
    assert get_reduced_config("qwen2-moe-a2.7b").moe.d_ff != 1408
    from repro.configs import get_config
    f = get_config("qwen2-moe-a2.7b").moe.d_ff
    assert (f, col_tile(f, 512), col_tile(1024, 512)) == (1408, 128, 512)
    G, d, rows = 2, 128, 128
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(k[0], (rows, d), jnp.bfloat16)
    w1, w3, w2 = ((jax.random.normal(key, shape) * shape[1] ** -0.5)
                  .astype(jnp.bfloat16) for key, shape in
                  ((k[1], (G, d, f)), (k[2], (G, d, f)), (k[3], (G, f, d))))
    gs = jnp.asarray([50, 70], jnp.int32)
    got = expert_swiglu(x, w1, w3, w2, gs, interpret=True)

    def gdot(a, w):
        return jax.lax.ragged_dot(a, w, gs,
                                  preferred_element_type=jnp.float32)

    h = (jax.nn.silu(gdot(x, w1)) * gdot(x, w3)).astype(jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(got)[:120],
                               np.asarray(gdot(h, w2))[:120],
                               rtol=2e-2, atol=2e-2)


def test_counts_leave_out_rows_not_valid():
    """Finished lanes and bucket padding are computed but not counted: the
    counts of a batch with two rows marked are those of the two alone."""
    cfg = _trinity()
    p = _moe_layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(11), (4, 1, cfg.d_model))
    valid = jnp.asarray([True, False, False, True])[:, None]
    y, counted = moe_dropless(p, x, cfg, valid=valid)
    pair, alone = moe_dropless(p, x[jnp.asarray([0, 3])], cfg)
    np.testing.assert_array_equal(np.asarray(counted), np.asarray(alone))
    np.testing.assert_array_equal(np.asarray(y[jnp.asarray([0, 3])]),
                                  np.asarray(pair))


# --------------------------------------------------------------- engine ----

def _engine(cfg, cache_len, **kw):
    kw = dict(dict(num_slots=2, kv_page_size=16, max_batch_tokens=16,
                   decode_chunk=4), **kw)
    return DecodeEngine(cfg, init_params(cfg, 0), cache_len=cache_len,
                        run=RunConfig(remat="none"), **kw)


def test_engine_pages_a_window_only_where_it_is_inert():
    cfg = _trinity()
    assert cfg.sliding_window == 128
    _engine(cfg, 128)
    with pytest.raises(ValueError, match=r"cfg.sliding_window=128 .*"
                       r"cache_len=256.*Reach item 1"):
        _engine(cfg, 256)


def test_classic_path_refuses_per_layer_kinds():
    with pytest.raises(ValueError, match="cfg.layer_types"):
        _engine(_trinity(), 128, kv_page_size=0, max_batch_tokens=None)


def test_expert_counters_add_up_on_the_device():
    cfg = _trinity()
    eng = _engine(cfg, 128)
    assert eng.serve_stats["moe_expert_passes"] == 0
    for rid, n in enumerate((5, 23)):
        eng.submit(Request(rid=rid, prompt=np.arange(2, 2 + n,
                                                     dtype=np.int32),
                           max_new_tokens=6, temperature=0.0))
    eng.run_to_completion()
    st = eng.serve_stats
    held = cfg.moe.held()[1]
    passes = st["moe_expert_passes"]
    assert passes > 0 and set(MOE_STATS) <= set(st)
    assert 0 < st["moe_routed_pairs"] <= st["moe_load_max_sum"] * held
    assert st["moe_load_max_sum"] <= st["moe_routed_pairs"]
    assert passes <= st["moe_experts_reached"] <= passes * held
    # decode passes' live lanes alone: the prompts' passes and the lanes
    # that finished or never filled add nothing
    assert st["moe_routed_pairs"] <= st["decode_tokens"] * cfg.moe.top_k


def test_dense_engine_has_no_expert_counters():
    eng = _engine(get_reduced_config("starcoder2-3b"), 64)
    assert "moe_stats" not in eng.cache
    assert not set(MOE_STATS) & set(eng.serve_stats)


#: stablehlo operation counts of starcoder2-3b's reduced paged decode step
#: and prefill chunk, taken on the tree before per-layer attention kinds,
#: QK-norm, the output gate, sandwich norms, the embedding scale and the
#: dropless expert layer: with every option off, no operation is added
STARCODER_OPS = {
    "decode": {
        "chlo.square": 3, "mhlo.num_partitions": 1, "mhlo.num_replicas": 1,
        "stablehlo.add": 33, "stablehlo.and": 4,
        "stablehlo.broadcast_in_dim": 139, "stablehlo.compare": 26,
        "stablehlo.concatenate": 7, "stablehlo.constant": 94,
        "stablehlo.convert": 31, "stablehlo.cosine": 2,
        "stablehlo.divide": 7, "stablehlo.dot_general": 9,
        "stablehlo.dynamic_slice": 12, "stablehlo.exponential": 1,
        "stablehlo.gather": 8, "stablehlo.iota": 5, "stablehlo.maximum": 2,
        "stablehlo.multiply": 23, "stablehlo.negate": 2,
        "stablehlo.power": 2, "stablehlo.reduce": 6,
        "stablehlo.remainder": 2, "stablehlo.reshape": 18,
        "stablehlo.return": 4, "stablehlo.rsqrt": 3, "stablehlo.scatter": 4,
        "stablehlo.select": 20, "stablehlo.sign": 2, "stablehlo.sine": 2,
        "stablehlo.slice": 6, "stablehlo.subtract": 4, "stablehlo.tanh": 1,
        "stablehlo.transpose": 4, "stablehlo.while": 1},
    "chunk": {
        "chlo.square": 3, "mhlo.num_partitions": 1, "mhlo.num_replicas": 1,
        "stablehlo.add": 25, "stablehlo.and": 2,
        "stablehlo.broadcast_in_dim": 101, "stablehlo.compare": 11,
        "stablehlo.concatenate": 5, "stablehlo.constant": 81,
        "stablehlo.convert": 36, "stablehlo.cosine": 2,
        "stablehlo.divide": 6, "stablehlo.dot_general": 11,
        "stablehlo.dynamic_slice": 13, "stablehlo.dynamic_update_slice": 2,
        "stablehlo.exponential": 1, "stablehlo.gather": 6,
        "stablehlo.iota": 5, "stablehlo.maximum": 2,
        "stablehlo.multiply": 24, "stablehlo.negate": 2,
        "stablehlo.power": 2, "stablehlo.reduce": 6,
        "stablehlo.reshape": 16, "stablehlo.return": 2,
        "stablehlo.rsqrt": 3, "stablehlo.select": 9, "stablehlo.sine": 2,
        "stablehlo.slice": 6, "stablehlo.subtract": 3, "stablehlo.tanh": 1,
        "stablehlo.transpose": 6, "stablehlo.while": 1},
}


def test_starcoder2_paged_programs_gain_no_operation():
    from repro.models.model import decode_step, init_cache, prefill_chunk
    from repro.models.paging import PagedKVConfig
    cfg = get_reduced_config("starcoder2-3b")
    run = RunConfig(remat="none")
    params = jax.eval_shape(lambda: init_params(cfg, 0))
    cache = init_cache(cfg, 2, 64, abstract=True,
                       paging=PagedKVConfig(page_size=16, num_pages=9,
                                            pages_per_seq=4))

    def i32(*s):
        return jax.ShapeDtypeStruct(s, jnp.int32)

    programs = {
        "decode": (lambda p, c, t, pos, pt: decode_step(
            p, c, t, pos, cfg, run, page_table=pt),
            (params, cache, i32(2, 1), i32(2), i32(2, 4))),
        "chunk": (lambda p, c, t, pt, st, lp: prefill_chunk(
            p, {"tokens": t}, c, pt, st, cfg, run, last_pos=lp),
            (params, cache, i32(1, 8), i32(1, 4), i32(), i32())),
    }
    for name, (fn, args) in programs.items():
        text = jax.jit(fn).lower(*args).as_text()
        ops = collections.Counter(
            re.findall(r"(?:stablehlo|chlo|mhlo)\.[a-z_]+", text))
        assert dict(ops) == STARCODER_OPS[name], name
