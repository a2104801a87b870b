"""Pallas TPU grouped matmuls for the dropless expert layer.

The rows of ``x`` are (token, expert) pairs sorted by expert: expert ``g``
owns rows ``[start_g, end_g)`` (``sizes`` gives their counts; rows past the
last group belong to experts held elsewhere and are never computed).  Two
kernels run one expert layer's held experts over those rows:

  * ``moe_experts_up``: ``silu(x W1_g) * (x W3_g)`` (the SwiGLU hidden);
  * ``moe_experts_down``: ``h W2_g``, in float32 for the weighted combine.

The grid is (output column tiles, visits).  A visit is one (row tile,
expert) pair whose rows overlap: an expert's rows span one row tile or a
few, and a row tile holds the rows of one expert or several, so there are
at most ``row tiles + experts - 1`` visits.  Visits run in row order, so
each output tile stays resident in VMEM while the experts whose rows it
holds write their rows into it (masked by row), and each visit fetches its
expert's weight tile once.  So a decode pass, where every held expert has a
handful of rows in one tile, reads each held expert's weights once, and an
expert with no rows is never read.  Grid steps past the last visit repeat
its block indices (no fetch) and compute nothing.

Each ``pallas_call`` is named ``<name>_up`` / ``<name>_down``: the
operation's name in the device trace, where the benchmark's expert-layer
metrics find it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows per tile: the (8, 128)-aligned sublane extent of one MXU pass
ROW_TILE = 128


def visits(sizes: jax.Array, rows: int, tm: int = ROW_TILE):
    """Visit schedule of ``sizes`` ((G,) int32 rows per expert, in row
    order) over ``rows`` rows in tiles of ``tm``: (expert of each visit,
    row tile of each visit, group starts, group ends, (1,) visit count),
    each padded to the static ``rows // tm + G - 1`` visits by repeating
    the last one."""
    G = sizes.shape[0]
    n_tiles = rows // tm
    ends = jnp.cumsum(sizes).astype(jnp.int32)
    starts = ends - sizes
    first = starts // tm
    nv = jnp.where(sizes > 0, (jnp.maximum(ends, 1) - 1) // tm - first + 1, 0)
    cum = jnp.cumsum(nv)
    total = cum[-1]
    v = jnp.minimum(jnp.arange(n_tiles + G - 1), jnp.maximum(total - 1, 0))
    g = jnp.minimum(jnp.searchsorted(cum, v, side="right"), G - 1)
    tile = jnp.clip(first[g] + v - (cum[g] - nv[g]), 0, n_tiles - 1)
    return (g.astype(jnp.int32), tile.astype(jnp.int32), starts, ends,
            total.astype(jnp.int32)[None])


def _masked_store(o_ref, out, tile, start, end, tm: int):
    rows = tile * tm + jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
    keep = (rows >= start) & (rows < end)
    o_ref[...] = jnp.where(keep, out.astype(o_ref.dtype), o_ref[...])


def _up_kernel(gid, tid, start, end, total, x_ref, w1_ref, w3_ref, o_ref,
               *, tm: int):
    v = pl.program_id(1)

    @pl.when(v < total[0])
    def _():
        g = gid[v]
        x = x_ref[...]
        a = jnp.dot(x, w1_ref[0], preferred_element_type=jnp.float32)
        b = jnp.dot(x, w3_ref[0], preferred_element_type=jnp.float32)
        _masked_store(o_ref, a / (1.0 + jnp.exp(-a)) * b, tid[v], start[g],
                      end[g], tm)


def _down_kernel(gid, tid, start, end, total, h_ref, w2_ref, o_ref, *,
                 tm: int):
    v = pl.program_id(1)

    @pl.when(v < total[0])
    def _():
        g = gid[v]
        y = jnp.dot(h_ref[...], w2_ref[0], preferred_element_type=jnp.float32)
        _masked_store(o_ref, y, tid[v], start[g], end[g], tm)


def col_tile(n: int, block_n: int) -> int:
    """Output columns per tile: the widest multiple of 128 up to
    ``block_n`` that divides ``n`` (qwen2-moe's 1408 takes 128), or all
    of ``n`` where none does."""
    return next((t for t in range(block_n - block_n % 128, 0, -128)
                 if n % t == 0), n)


def _grouped(kernel, name: str, x, ws, sizes, out_dtype, block_n: int,
             interpret: bool):
    rows, k = x.shape
    n = ws[0].shape[2]
    tm = ROW_TILE
    tn = col_tile(n, block_n)
    assert rows % tm == 0, (rows, tm)
    meta = visits(sizes, rows, tm)
    n_visits = meta[0].shape[0]
    w_spec = pl.BlockSpec((1, k, tn),
                          lambda j, v, gid, tid, s, e, t: (gid[v], 0, j))
    return pl.pallas_call(
        functools.partial(kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // tn, n_visits),
            in_specs=[pl.BlockSpec((tm, k),
                                   lambda j, v, gid, tid, s, e, t:
                                   (tid[v], 0))] + [w_spec] * len(ws),
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, gid, tid, s, e, t:
                                   (tid[v], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), out_dtype),
        name=name,
        interpret=interpret,
    )(*meta, x, *ws)


@functools.partial(jax.jit, static_argnames=("block_n", "name",
                                             "interpret"))
def expert_swiglu(x, w1, w3, w2, sizes, *, block_n: int = 512,
                  name: str = "moe_experts", interpret: bool = False):
    """Each expert's SwiGLU over its own rows.  x: (rows, d) pairs sorted
    by expert (``rows`` a multiple of ``ROW_TILE``); w1/w3: (G, d, f),
    w2: (G, f, d); sizes: (G,) int32.  Returns (rows, d) float32, of
    which only the rows of some group are defined."""
    h = _grouped(_up_kernel, f"{name}_up", x, (w1, w3), sizes, x.dtype,
                 block_n, interpret)
    return _grouped(_down_kernel, f"{name}_down", h, (w2,), sizes,
                    jnp.float32, block_n, interpret)
