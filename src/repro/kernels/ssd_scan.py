"""Pallas TPU kernel for the Mamba-2 SSD chunked scan. [arXiv:2405.21060]

TPU adaptation: the CUDA Mamba kernel leans on warp-level scans; the TPU
version instead exploits the *state-space duality* directly — within a chunk
the quadratic "attention-like" form runs on the MXU ((Q,N)@(N,Q) and
(Q,Q)@(Q,P) matmuls), while the inter-chunk recurrence is carried in a VMEM
scratch state of shape (P, N) across the sequential innermost grid dimension.
That is the natural systolic mapping of the SSD algorithm: big dense matmuls
per chunk, O(1)-size carry between chunks.

Grid: ``(batch, heads, num_chunks)`` — chunks innermost/sequential; the
scratch ``state`` persists across the chunk dimension for one (b, h).

Layout contract (from ops.py):
  x  (B, H, S, P)   head inputs
  dt (B, H, S, 1)   post-softplus step sizes (fp32)
  a  (B, H, S, 1)   dt * A  (fp32, precomputed — avoids scalar refs)
  Bm (B, S, N)      input projection (shared across heads)
  Cm (B, S, N)      output projection
  y  (B, H, S, P)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *,
                chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)        # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)      # (Q, 1)
    dA = a_ref[0, 0].astype(jnp.float32)       # (Q, 1)  = dt * A  (<= 0)
    Bm = b_ref[0].astype(jnp.float32)          # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)          # (Q, N)

    causal = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive cumsum of dA as a lower-triangular matmul (Mosaic has no
    # cumsum), once as a column and once as a row so ``seg`` needs no
    # transpose; HIGHEST keeps the f32 sums from rounding through bf16
    tri = causal.astype(jnp.float32)           # tri[i, j] = j <= i
    hi = jax.lax.Precision.HIGHEST
    dA_cs = jax.lax.dot_general(
        tri, dA, (((1,), (0,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)    # (Q, 1)
    dA_cs_row = jax.lax.dot_general(
        dA, tri, (((0,), (1,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)    # (1, Q)
    xdt = x * dt                               # (Q, P)

    # ---- intra-chunk quadratic (MXU) ----
    seg = dA_cs - dA_cs_row                    # (Q, Q)
    L = jnp.where(causal, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)    # (Q, Q)
    y_diag = jax.lax.dot_general(
        scores * L, xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)    # (Q, P)

    # ---- contribution of the carried state ----
    state = state_ref[...]                     # (P, N)
    state_decay = jnp.exp(dA_cs)               # (Q, 1)
    y_off = jax.lax.dot_general(
        Cm, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * state_decay  # (Q, P)

    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    # ---- state update for the next chunk ----
    total = jnp.sum(dA)                        # scalar: dA_cs[-1]
    chunk_decay = jnp.exp(total)
    in_decay = jnp.exp(total - dA_cs)          # (Q, 1)
    new_state = jax.lax.dot_general(
        xdt * in_decay, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)    # (P, N)
    state_ref[...] = state * chunk_decay + new_state


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_bhsp(x, dt, a, Bm, Cm, *, chunk: int = 256,
                  interpret: bool = False):
    """x: (B,H,S,P); dt/a: (B,H,S,1); Bm/Cm: (B,S,N).  S % chunk == 0."""
    B, H, S, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    grid = (B, H, nc)
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((P, N), jnp.float32),   # carried SSD state
        ],
        interpret=interpret,
    )(x, dt, a, Bm, Cm)
