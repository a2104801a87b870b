"""Pallas TPU flash-decode: single-query attention against a long KV cache.

Decode is the inverse regime of prefill: ONE query per sequence, thousands
of KV lines.  The prefill kernel's q-block grid collapses to a single row,
so the parallelism has to come from the KV axis instead — the classic
*split-KV* flash-decode trick:

  * the KV sequence is split into ``block_k`` chunks across the grid; each
    grid cell computes an **online-softmax partial** over its chunk — the
    unnormalized accumulator ``acc = exp(s - m) @ v``, the chunk max ``m``
    and the chunk sum ``l`` — entirely in VMEM;
  * a cheap **cross-block combine** (O(num_chunks), pure jnp in the
    wrapper) rescales the partials to the global max and normalizes:
    ``out = Σ acc_c·exp(m_c - m*) / Σ l_c·exp(m_c - m*)``.

Chunks are independent, so nothing is carried across grid cells — on
hardware with a parallel KV grid dimension every chunk runs concurrently,
which is what keeps decode latency flat as the cache grows.

GQA-aware like ``flash_attention.py``: the grid iterates KV heads and each
cell processes all G query heads sharing that KV head as one (G, Dh)
block — the KV chunk is fetched from HBM once per group, not once per
query head.

Validity is a per-sequence position: the cache holds ``S`` slots of which
``[0, pos_b]`` are live (the serving engine's non-ring full cache — slot i
holds absolute position i).  ``pos`` rides in as a scalar-prefetch operand
so the mask is computed from SMEM, not HBM.

**Sliding-window ring caches** (``window`` set): slot ``i`` no longer
holds absolute position ``i`` but the *latest* written position congruent
to ``i`` — ``slot_pos = pos - ((pos - i) mod slots)``.  The mask becomes a
second masked range over that wrapped position map (written at all, and
within the window), computed from the same SMEM scalars; the split-KV
math is otherwise unchanged, so ring decode keeps the flat-latency
property of the full-cache kernel.

Layout contract (from ops.py): q (B, K, G, Dh) grouped queries;
k/v (B, K, S, Dh); pos (B,) int32.  The paged kernel reads the serving
pool in its stored layout, (layers, num_pages, K, page_size, Dh).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_K = 512
_NEG_INF = -1e30
#: the per-chunk softmax max/sum partials are written broadcast over one
#: lane-width trailing axis: Mosaic tiles the last two block dims by
#: (8, 128) unless they span the whole array, and a (G,) row per grid cell
#: over an ``nk``-long axis fits neither rule
_LANES = 128


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
                   block_k: int, scale: float, window=None, slots=None):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    pos = pos_ref[b]

    q = q_ref[0, 0].astype(jnp.float32)               # (G, Dh)
    k = k_ref[0, 0].astype(jnp.float32)               # (bk, Dh)
    v = v_ref[0, 0].astype(jnp.float32)               # (bk, Dh)
    G = q.shape[0]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (G, bk)
    kv_slot = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (G, block_k), 1)
    if window is None:
        # full cache: slot i holds absolute position i
        mask = kv_slot <= pos
    else:
        # ring: slot i holds the latest position congruent to i.  The
        # floor-mod keeps slot_pos in (pos - slots, pos], so only "ever
        # written" (>= 0) and "inside the window" need checking — the
        # second masked range of the wrapped slot -> position map
        slot_pos = pos - ((pos - kv_slot) % slots)
        mask = (slot_pos >= 0) & ((pos - slot_pos) < window)
    s = jnp.where(mask, s, _NEG_INF)

    m = jnp.max(s, axis=-1)                           # (G,)
    p = jnp.exp(s - m[:, None])
    p = jnp.where(mask, p, 0.0)                       # fully-masked chunk: 0
    l = jnp.sum(p, axis=-1)                           # (G,)
    acc = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # (G, Dh)

    o_ref[0, 0, 0] = acc
    m_ref[0, 0, 0] = jnp.broadcast_to(m[:, None], (G, _LANES))
    l_ref[0, 0, 0] = jnp.broadcast_to(l[:, None], (G, _LANES))


def _combine(o_part, m_part, l_part, dtype):
    """Cross-block combine: rescale every chunk's partial to the global
    max and normalize.  o_part: (B, K, n, G, Dh); m/l: (B, K, n, G, lanes),
    every lane holding the same value."""
    m_part, l_part = m_part[..., 0], l_part[..., 0]
    m_star = jnp.max(m_part, axis=2, keepdims=True)          # (B, K, 1, G)
    w = jnp.exp(m_part - m_star)                             # (B, K, n, G)
    num = jnp.sum(o_part * w[..., None], axis=2)             # (B, K, G, Dh)
    den = jnp.sum(l_part * w, axis=2)                        # (B, K, G)
    den = jnp.where(den == 0.0, 1.0, den)
    return (num / den[..., None]).astype(dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_k", "interpret", "window"))
def flash_decode_bkgd(q, k, v, pos, *, block_k: int = DEFAULT_BLOCK_K,
                      interpret: bool = False, window=None):
    """q: (B, K, G, Dh); k/v: (B, K, S, Dh); pos: (B,) int32 — each
    sequence attends kv slots [0, pos_b].  Returns (B, K, G, Dh).

    ``window`` (static) marks k/v as a sliding-window RING of S slots
    (slot = position mod S, S = min(cache_len, window)): sequence b then
    attends the wrapped slots holding positions (pos_b - window, pos_b]."""
    B, K, G, Dh = q.shape
    S = k.shape[2]
    block_k = min(block_k, S)
    assert S % block_k == 0, (S, block_k)
    nk = S // block_k
    scale = Dh ** -0.5

    kernel = functools.partial(_decode_kernel, block_k=block_k, scale=scale,
                               window=window,
                               slots=(S if window is not None else None))
    o_part, m_part, l_part = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, K, nk),
            in_specs=[
                pl.BlockSpec((1, 1, G, Dh),
                             lambda b, h, ki, pos: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_k, Dh),
                             lambda b, h, ki, pos: (b, h, ki, 0)),
                pl.BlockSpec((1, 1, block_k, Dh),
                             lambda b, h, ki, pos: (b, h, ki, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, 1, G, Dh),
                             lambda b, h, ki, pos: (b, h, ki, 0, 0)),
                pl.BlockSpec((1, 1, 1, G, _LANES),
                             lambda b, h, ki, pos: (b, h, ki, 0, 0)),
                pl.BlockSpec((1, 1, 1, G, _LANES),
                             lambda b, h, ki, pos: (b, h, ki, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, K, nk, G, Dh), jnp.float32),
            jax.ShapeDtypeStruct((B, K, nk, G, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, K, nk, G, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(pos.astype(jnp.int32), q, k, v)

    return _combine(o_part, m_part, l_part, q.dtype)


# ----------------------------------------------------------- paged cache ----
#
# Same split-KV scheme against a PAGED pool: the KV lines live in
# (num_pages, page_size) physical pages and each sequence's logical chunk
# ``pi`` resolves through a scalar-prefetched page table.  The page table
# rides in SMEM, so the *BlockSpec index_map itself* does the indirection —
# grid cell (b, h, pi) DMAs physical page ``pt[b, pi]`` from HBM.  The
# kernel body is the dense one: logical positions are ``pi*ps + iota``
# regardless of which physical page backs them, so masking (and therefore
# numerics) is identical to the dense split-KV kernel with block_k = ps.
# Unallocated tails resolve to the null page and are fully masked.
#
# The kernel reads the model's stacked pool where it lies: k/v are
# (layers, num_pages, K, page_size, Dh), heads inside a page, so one
# (page_size, Dh) block is one contiguous tile, and the layer rides in
# SMEM beside the table.  Nothing is sliced or transposed per call.


def _paged_decode_kernel(layer_ref, pt_ref, pos_ref, q_ref, k_ref, v_ref,
                         o_ref, m_ref, l_ref, *, page_size: int,
                         scale: float):
    del layer_ref                                     # used by index_maps
    b = pl.program_id(0)
    pi = pl.program_id(2)
    pos = pos_ref[b]

    q = q_ref[0, 0].astype(jnp.float32)               # (G, Dh)
    k = k_ref[0, 0, 0].astype(jnp.float32)            # (ps, Dh)
    v = v_ref[0, 0, 0].astype(jnp.float32)            # (ps, Dh)
    G = q.shape[0]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (G, ps)
    kv_pos = pi * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (G, page_size), 1)
    mask = kv_pos <= pos
    s = jnp.where(mask, s, _NEG_INF)

    m = jnp.max(s, axis=-1)                           # (G,)
    p = jnp.exp(s - m[:, None])
    p = jnp.where(mask, p, 0.0)                       # fully-masked page: 0
    l = jnp.sum(p, axis=-1)                           # (G,)
    acc = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # (G, Dh)

    o_ref[0, 0, 0] = acc
    m_ref[0, 0, 0] = jnp.broadcast_to(m[:, None], (G, _LANES))
    l_ref[0, 0, 0] = jnp.broadcast_to(l[:, None], (G, _LANES))


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_decode_paged_bkgd(q, k, v, page_table, pos, layer, *,
                            interpret: bool = False):
    """Split-KV decode against one layer of a stacked paged pool.

    q: (B, K, G, Dh) grouped queries; k/v: (layers, num_pages, K,
    page_size, Dh) pools; page_table: (B, n_pages) int32 (logical page ->
    physical page, 0 = null page); pos: (B,) int32 — sequence b attends
    logical KV positions [0, pos_b]; layer: int32 scalar, the pool layer
    read.  Returns (B, K, G, Dh).

    One grid cell per (sequence, kv head, logical page); each fetches its
    physical page of ``layer`` via the prefetched scalars and emits an
    online-softmax partial, combined across pages in jnp exactly like
    the dense kernel.
    """
    B, K, G, Dh = q.shape
    page_size = k.shape[3]
    n_pages = page_table.shape[1]
    scale = Dh ** -0.5

    kernel = functools.partial(_paged_decode_kernel, page_size=page_size,
                               scale=scale)
    kv_spec = pl.BlockSpec(
        (1, 1, 1, page_size, Dh),
        lambda b, h, pi, lyr, pt, pos: (lyr[0], pt[b, pi], h, 0, 0))
    o_part, m_part, l_part = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, K, n_pages),
            in_specs=[
                pl.BlockSpec((1, 1, G, Dh),
                             lambda b, h, pi, lyr, pt, pos: (b, h, 0, 0)),
                kv_spec,
                kv_spec,
            ],
            out_specs=[
                pl.BlockSpec((1, 1, 1, G, Dh),
                             lambda b, h, pi, lyr, pt, pos: (b, h, pi, 0, 0)),
                pl.BlockSpec((1, 1, 1, G, _LANES),
                             lambda b, h, pi, lyr, pt, pos: (b, h, pi, 0, 0)),
                pl.BlockSpec((1, 1, 1, G, _LANES),
                             lambda b, h, pi, lyr, pt, pos: (b, h, pi, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, K, n_pages, G, Dh), jnp.float32),
            jax.ShapeDtypeStruct((B, K, n_pages, G, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, K, n_pages, G, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      page_table.astype(jnp.int32), pos.astype(jnp.int32), q, k, v)

    return _combine(o_part, m_part, l_part, q.dtype)
