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


@pytest.mark.parametrize("widths", [STABLELM, QWEN2],
                         ids=["mha_dh80", "gqa_dh128"])
def test_paged_decode(one_chip, widths):
    """A 1024-line page table over a 257-page pool of 16-line pages."""
    H, K, Dh = widths.values()
    pages, ps, per_seq = 257, 16, 64
    _compile(lambda q, k, v, pt, p: ops.flash_decode_paged(
                 q, k, v, pt, p, interpret=False),
             _sds(one_chip, (B, 1, H, Dh)),
             _sds(one_chip, (pages, ps, K, Dh)),
             _sds(one_chip, (pages, ps, K, Dh)),
             _sds(one_chip, (B, per_seq), jnp.int32),
             _sds(one_chip, (B,), jnp.int32))


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
