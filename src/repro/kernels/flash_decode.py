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

The **paged** kernel (below) is not split-KV: its grid is one cell per
sequence, which loops over that sequence's live pages only, all kv heads
at once, and finishes the softmax itself — on the chip a grid cell costs
more than a page's work, and most of a page table is past its sequence.

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
# Decode against a PAGED pool: the KV lines live in (num_pages, page_size)
# physical pages and each sequence's logical page ``i`` resolves through a
# scalar-prefetched page table.  A grid step costs a fixed ~0.35 us on the
# chip whatever it computes, so the paged kernel runs ONE grid cell per
# lane and loops inside it over that lane's live pages alone,
# ``cdiv(pos_b + 1, page_size)`` of them; pages past the lane's position
# are never read.  Each step of the loop attends all K kv heads of one
# page, and the online-softmax state (m, l, acc) runs across the loop in
# float32, so the kernel writes the normalised (K, G, Dh) output itself:
# no per-page partials and no combine.
#
# The kernel reads the model's stacked pool where it lies: k/v are
# (layers, num_pages, K, page_size, Dh), heads inside a page, so one page
# of one layer for all kv heads, ``k[layer, page]``, is one contiguous
# block.  It copies those blocks by hand into two VMEM buffers, starting
# the next page's copy (the next lane's first page, at a lane's last)
# before it computes on the current one.  Nothing is sliced or transposed
# per call.
#
# Mosaic cannot cut a page out of a pool whose head dim is not a multiple
# of the 128-lane tiling (stablelm's 80), so for such a pool the pages come
# through the grid's own pipeline instead: a (lane, page) grid whose page
# index stops at the lane's last live page, so the cells past it fetch
# nothing and compute nothing.  The page math is the same.


def _live_pages(pos, page_size: int, n_pages: int):
    """Pages holding positions [0, pos], clamped to the table."""
    return jnp.clip(pos // page_size + 1, 1, n_pages)


def _attend_page(q, k, v, carry, first_line, pos, scale: float,
                 masked: bool):
    """Fold one page of every kv head into the online softmax.  q: (K, G,
    Dh); k/v: (K, ps, Dh); carry: m, l (K, G, 1) and acc (K, G, Dh), all
    float32.  ``masked`` drops the page's lines past ``pos`` (a lane's
    last live page; the pages before it are full)."""
    m, l, acc = carry
    dt = jnp.promote_types(q.dtype, k.dtype)
    s = jax.lax.dot_general(
        q.astype(dt), k.astype(dt), (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale   # (K, G, ps)
    if masked:
        kv_pos = first_line + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kv_pos <= pos, s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)                            # masked lines: 0
    l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    acc = alpha * acc + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)            # (K, G, Dh)
    return m_new, l, acc


def _softmax_init(K: int, G: int, Dh: int):
    return (jnp.full((K, G, 1), _NEG_INF, jnp.float32),
            jnp.zeros((K, G, 1), jnp.float32),
            jnp.zeros((K, G, Dh), jnp.float32))


def _paged_decode_kernel(layer_ref, pt_ref, pos_ref, q_ref, k_hbm, v_hbm,
                         o_ref, k_buf, v_buf, sems, slot_ref, *,
                         page_size: int, n_pages: int, scale: float):
    b = pl.program_id(0)
    layer = layer_ref[0]

    def copies(lane, i, slot):
        page = pt_ref[lane * n_pages + i]
        return (pltpu.make_async_copy(k_hbm.at[layer, page], k_buf.at[slot],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, page], v_buf.at[slot],
                                      sems.at[1, slot]))

    @pl.when(b == 0)
    def _():
        slot_ref[0] = 0
        for c in copies(0, 0, 0):
            c.start()

    first = slot_ref[0]              # the buffer this lane's page 0 is in
    n = _live_pages(pos_ref[b], page_size, n_pages)
    q = q_ref[0]                                      # (K, G, Dh)

    def page(i, carry, last: bool):
        slot = (first + i) % 2
        if last:
            # the lane's final page: prefetch the next lane's first page
            @pl.when(b + 1 < pl.num_programs(0))
            def _():
                slot_ref[0] = 1 - slot
                for c in copies(b + 1, 0, 1 - slot):
                    c.start()
        else:
            for c in copies(b, i + 1, 1 - slot):
                c.start()
        for c in copies(b, i, slot):
            c.wait()
        return _attend_page(q, k_buf[slot], v_buf[slot], carry,
                            i * page_size, pos_ref[b], scale, masked=last)

    carry = jax.lax.fori_loop(0, n - 1,
                              lambda i, c: page(i, c, last=False),
                              _softmax_init(*q.shape))
    _, l, acc = page(n - 1, carry, last=True)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def _paged_decode_grid_kernel(layer_ref, pt_ref, pos_ref, q_ref, k_ref,
                              v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                              page_size: int, n_pages: int, scale: float):
    del layer_ref, pt_ref                             # used by index_maps
    b, i = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[b]
    n = _live_pages(pos, page_size, n_pages)

    @pl.when(i == 0)
    def _():
        m_ref[...], l_ref[...], acc_ref[...] = _softmax_init(*acc_ref.shape)

    @pl.when(i < n)
    def _():
        m_ref[...], l_ref[...], acc_ref[...] = _attend_page(
            q_ref[0], k_ref[0, 0], v_ref[0, 0],
            (m_ref[...], l_ref[...], acc_ref[...]), i * page_size, pos,
            scale, masked=True)

    @pl.when(i == n - 1)
    def _():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_decode_paged_bkgd(q, k, v, page_table, pos, layer, *,
                            interpret: bool = False):
    """Decode attention against one layer of a stacked paged pool.

    q: (B, K, G, Dh) grouped queries; k/v: (layers, num_pages, K,
    page_size, Dh) pools; page_table: (B, n_pages) int32 (logical page ->
    physical page, 0 = null page); pos: (B,) int32 — sequence b attends
    logical KV positions [0, pos_b]; layer: int32 scalar, the pool layer
    read.  Returns (B, K, G, Dh).

    One grid cell per sequence; it copies the ``cdiv(pos_b + 1,
    page_size)`` physical pages of ``layer`` that hold its live lines,
    all kv heads at once, and keeps the softmax running across them in
    float32.  The table's later pages are not read.  (A head dim off the
    128-lane tiling takes the (sequence, page) grid described above.)
    """
    B, K, G, Dh = q.shape
    page_size = k.shape[3]
    n_pages = page_table.shape[1]
    params = dict(page_size=page_size, n_pages=n_pages, scale=Dh ** -0.5)
    softmax_state = [pltpu.VMEM((K, G, 1), jnp.float32),
                     pltpu.VMEM((K, G, 1), jnp.float32),
                     pltpu.VMEM((K, G, Dh), jnp.float32)]
    if Dh % 128 == 0:
        kernel = functools.partial(_paged_decode_kernel, **params)
        grid = (B,)
        lane = pl.BlockSpec((1, K, G, Dh),
                            lambda b, lyr, pt, pos: (b, 0, 0, 0))
        pool = pl.BlockSpec(memory_space=pl.ANY)
        scratch = [pltpu.VMEM((2, K, page_size, Dh), k.dtype),
                   pltpu.VMEM((2, K, page_size, Dh), v.dtype),
                   pltpu.SemaphoreType.DMA((2, 2)),
                   pltpu.SMEM((1,), jnp.int32)]
    else:
        kernel = functools.partial(_paged_decode_grid_kernel, **params)
        grid = (B, n_pages)
        lane = pl.BlockSpec((1, K, G, Dh),
                            lambda b, i, lyr, pt, pos: (b, 0, 0, 0))

        def live_page(b, i, lyr, pt, pos):
            last = _live_pages(pos[b], page_size, n_pages) - 1
            return (lyr[0], pt[b * n_pages + jnp.minimum(i, last)], 0, 0, 0)

        pool = pl.BlockSpec((1, 1, K, page_size, Dh), live_page)
        scratch = softmax_state
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=grid, in_specs=[lane, pool, pool],
            out_specs=lane, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((B, K, G, Dh), q.dtype),
        # a lane's last page prefetches the next lane's first: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      page_table.astype(jnp.int32).reshape(-1), pos.astype(jnp.int32),
      q, k, v)
