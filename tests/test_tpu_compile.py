"""Compile the Pallas kernels for a described TPU v5e, at real widths.

Interpret mode (every other kernel test) runs the kernel bodies as plain
JAX and never applies Mosaic's rules: block shapes whose last two dims are
neither (8, 128)-aligned nor the full array dims, primitives Mosaic cannot
lower (``cumsum``), broadcasts it does not implement.  These tests hand the
real TPU compiler abstract shapes on a chip that is described, not
attached, so such a kernel fails here instead of on the chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# stablelm-3b: MHA, head_dim 80; qwen2-7b: GQA 28 query / 4 kv heads, 128
STABLELM = dict(H=32, K=32, Dh=80)
QWEN2 = dict(H=28, K=4, Dh=128)
# the benchmark's cells: starcoder2-3b GQA 24:2, trinity-mini 32:4, heads
# of 128
STARCODER2 = dict(H=24, K=2, Dh=128)
TRINITY = dict(H=32, K=4, Dh=128)
B = 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no libtpu, or another process holds it
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _compile(fn, *shapes):
    """Compile ``fn`` for the described chip; return the compiled text."""
    compiled = jax.jit(fn).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "kernel was not lowered by Mosaic"
    return text


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_dense_decode_many_kv_blocks(one_chip):
    """S=2048 over 512-line blocks: nk=4 partials per (b, kv head)."""
    H, K, Dh = STABLELM.values()
    S = 2048
    _compile(lambda q, k, v, p: ops.flash_decode(q, k, v, p,
                                                  interpret=False),
             _sds(one_chip, (B, 1, H, Dh)), _sds(one_chip, (B, S, K, Dh)),
             _sds(one_chip, (B, S, K, Dh)),
             _sds(one_chip, (B,), jnp.int32))


def test_ring_decode(one_chip):
    """Sliding-window ring: 1024 slots, masked through the wrapped map."""
    H, K, Dh = QWEN2.values()
    S = 1024
    _compile(lambda q, k, v, p: ops.flash_decode(q, k, v, p, window=S,
                                                  interpret=False),
             _sds(one_chip, (B, 1, H, Dh)), _sds(one_chip, (B, S, K, Dh)),
             _sds(one_chip, (B, S, K, Dh)),
             _sds(one_chip, (B,), jnp.int32))


def _pallas_grids(jaxpr):
    """The grid of every pallas_call in ``jaxpr``, nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield tuple(eqn.params["grid_mapping"].grid)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_grids(sub)


@pytest.mark.parametrize("widths,lanes,layers,pages,ps,per_seq", [
    (STABLELM, B, 4, 257, 16, 64),
    (QWEN2, B, 4, 257, 16, 64),
    (STARCODER2, 64, 30, 1025, 128, 16),
    (TRINITY, 64, 12, 1025, 128, 16),
], ids=["mha_dh80", "gqa_dh128", "starcoder2_3b", "trinity_mini"])
def test_paged_decode(one_chip, widths, lanes, layers, pages, ps, per_seq):
    """A page table of ``per_seq`` pages over a stacked pool, one layer
    (traced) read in place: 16-line pages under a 64-page table, and the
    cells' 128-line pages under a 16-page table for 64 lanes.  Where the
    head dim fills the 128-lane tiling, the kernel's grid is one cell per
    lane: the table's width is no grid axis."""
    H, K, Dh = widths.values()

    def fn(q, k, v, pt, p, l):
        return ops.flash_decode_paged(q, k, v, pt, p, l, interpret=False)

    shapes = (_sds(one_chip, (lanes, 1, H, Dh)),
              _sds(one_chip, (layers, pages, K, ps, Dh)),
              _sds(one_chip, (layers, pages, K, ps, Dh)),
              _sds(one_chip, (lanes, per_seq), jnp.int32),
              _sds(one_chip, (lanes,), jnp.int32),
              _sds(one_chip, (), jnp.int32))
    _compile(fn, *shapes)
    grids = list(_pallas_grids(jax.make_jaxpr(fn)(*shapes).jaxpr))
    assert len(grids) == 1, grids
    if Dh % 128 == 0:
        assert grids[0] == (lanes,) and per_seq not in grids[0], grids


def test_flash_attention(one_chip):
    """Causal prefill over 2048 tokens in 512x512 blocks (MHA, Dh=80)."""
    H, K, Dh = STABLELM.values()
    S = 2048
    _compile(lambda q, k, v: ops.flash_attention(q, k, v, interpret=False),
             _sds(one_chip, (1, S, H, Dh)), _sds(one_chip, (1, S, K, Dh)),
             _sds(one_chip, (1, S, K, Dh)))


def test_ssd_scan_mamba2_780m(one_chip):
    """mamba2-780m: 48 SSD heads of 64, state 128, 256-token chunks."""
    H, P, N, S = 48, 64, 128, 2048
    _compile(lambda x, dt, A, Bm, Cm: ops.ssd_scan(x, dt, A, Bm, Cm,
                                                    chunk=256,
                                                    interpret=False),
             _sds(one_chip, (1, S, H, P)),
             _sds(one_chip, (1, S, H), jnp.float32),
             _sds(one_chip, (H,), jnp.float32),
             _sds(one_chip, (1, S, N)), _sds(one_chip, (1, S, N)))


def test_paged_decode_program_copies_no_pool(one_chip, monkeypatch):
    """The engine's fused serve step (a prefill chunk beside a decode
    chunk), bfloat16 pool and compiled paged kernel: its scratch stays
    below one layer's K+V pool.  The compiler must neither slice a
    layer's pool out of the stack nor re-lay the pool for the kernel
    (a scatter whose window spans heads and lines makes it do so).
    starcoder2's heads of 128 and 128-line pages, so a page is the
    chip's (8, 128) tiling exactly, in a pool too large for VMEM."""
    import dataclasses

    import numpy as np
    from repro.configs import RunConfig, get_reduced_config
    from repro.models import init_params
    from repro.serving import DecodeEngine
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)
    cfg = dataclasses.replace(get_reduced_config("starcoder2-3b"),
                              head_dim=128)
    eng = DecodeEngine(cfg, init_params(cfg, 0), num_slots=8, cache_len=256,
                       kv_page_size=128, kv_pages=513, decode_chunk=4,
                       max_batch_tokens=64,
                       run=RunConfig(remat="none", use_pallas=True))
    pool = eng.cache["layers"][0]["k"]
    layer_kv = 2 * pool.nbytes // pool.shape[0]

    def put(x):
        return _sds(one_chip, np.shape(x), x.dtype)

    n, pps, C = eng.num_slots, eng.paging.pages_per_seq, 16
    i32 = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
    args = jax.tree.map(put, (
        eng.params, eng.cache, i32(n), i32(n), i32(n), np.ones(n, bool),
        i32(n), np.zeros(n, np.float32), np.asarray(eng._key), i32(n, pps),
        i32(n), i32(1, C), i32(1, pps), np.int32(0), np.int32(0), i32(C),
        i32(C)))
    compiled = eng._mixed_step.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_kv, (temp, layer_kv)


def test_expert_kernels_at_trinity_widths(one_chip):
    """trinity-mini's held experts: 32 SwiGLU experts of 1024 at d 2048,
    over a decode pass's 512 pair rows, under their trace names."""
    from repro.kernels.moe_gmm import expert_swiglu
    G, d, f = 32, 2048, 1024
    text = _compile(expert_swiglu, _sds(one_chip, (512, d)),
                    _sds(one_chip, (G, d, f)), _sds(one_chip, (G, d, f)),
                    _sds(one_chip, (G, f, d)),
                    _sds(one_chip, (G,), jnp.int32))
    assert "%moe_experts_up" in text and "%moe_experts_down" in text
