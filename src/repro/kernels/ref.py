"""Pure-jnp oracles for the Pallas kernels.

These intentionally re-derive the math independently of the model code's
blockwise implementations (``repro.models.attention.causal_attention`` is
itself chunked) so kernel tests compare against the most naive possible
formulation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def attention_ref(q, k, v, window=None):
    """Naive causal (+ sliding window) attention.

    q: (B,S,H,Dh); k/v: (B,S,K,Dh), H % K == 0.  fp32 softmax.
    """
    B, S, H, Dh = q.shape
    K = k.shape[2]
    G = H // K
    kk = jnp.repeat(k, G, axis=2)
    vv = jnp.repeat(v, G, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) * (Dh ** -0.5)
    pos = jnp.arange(S)
    mask = pos[None, :] <= pos[:, None]
    if window is not None:
        mask &= (pos[:, None] - pos[None, :]) < window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, vv.astype(jnp.float32))
    return o.astype(q.dtype)


def decode_attention_ref(q, k, v, pos):
    """Naive single-query decode attention (the flash-decode oracle).

    q: (B,1,H,Dh); k/v: (B,S,K,Dh) with H % K == 0 (slot i holds absolute
    position i); pos: (B,) int32 — sequence b attends slots [0, pos_b].
    fp32 softmax.
    """
    B, S, K, Dh = k.shape
    H = q.shape[2]
    G = H // K
    kk = jnp.repeat(k, G, axis=2)
    vv = jnp.repeat(v, G, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) * (Dh ** -0.5)
    valid = jnp.arange(S)[None, :] <= pos[:, None]           # (B, S)
    logits = jnp.where(valid[:, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, vv.astype(jnp.float32))
    return o.astype(q.dtype)


def paged_decode_attention_ref(q, k, v, page_table, pos):
    """Naive paged decode attention (the paged flash-decode oracle).

    q: (B,1,H,Dh); k/v: (num_pages, K, page_size, Dh) one layer of the
    shared pool; page_table: (B, n_pages) int32 (0 = null page); pos:
    (B,) int32.  Gathers the logical (B, n_pages*page_size, K, Dh) view
    through the page table, then defers to :func:`decode_attention_ref`.
    """
    B = q.shape[0]
    n_pages = page_table.shape[1]
    K, ps, Dh = k.shape[1], k.shape[2], k.shape[3]

    def logical(pool):
        return pool[page_table].transpose(0, 1, 3, 2, 4).reshape(
            B, n_pages * ps, K, Dh)

    return decode_attention_ref(q, logical(k), logical(v), pos)


def ssd_ref(x, dt, A, Bm, Cm):
    """Naive sequential SSD recurrence (token-by-token, exact).

    x: (B,S,H,P); dt: (B,S,H) post-softplus; A: (H,);
    Bm/Cm: (B,S,N).  Returns y: (B,S,H,P).
    """
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    f32 = jnp.float32

    def step(state, inp):
        xt, dtt, bt, ct = inp                    # (B,H,P), (B,H), (B,N), (B,N)
        decay = jnp.exp(dtt * A.astype(f32))     # (B,H)
        dBx = jnp.einsum("bh,bhp,bn->bhpn", dtt, xt, bt)
        state = state * decay[..., None, None] + dBx
        y = jnp.einsum("bhpn,bn->bhp", state, ct)
        return state, y

    s0 = jnp.zeros((Bsz, H, P, N), f32)
    xs = (x.astype(f32).transpose(1, 0, 2, 3), dt.astype(f32).transpose(1, 0, 2),
          Bm.astype(f32).transpose(1, 0, 2), Cm.astype(f32).transpose(1, 0, 2))
    _, ys = jax.lax.scan(step, s0, xs)
    return ys.transpose(1, 0, 2, 3).astype(x.dtype)
