"""jit'd public wrappers around the Pallas kernels.

Model code calls these with model-layout tensors; the wrappers transpose
activations and dense caches to kernel layout, pick hardware-aligned block
sizes, and run the kernel — ``interpret=True`` on the CPU backend, compiled
on a TPU.  The paged pool is stored in the layout its kernel reads, so
:func:`flash_decode_paged` hands it over untouched.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.flash_decode import flash_decode_bkgd, flash_decode_paged_bkgd
from repro.kernels.ssd_scan import ssd_scan_bhsp


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _pick_block(S: int, target: int) -> int:
    """Largest divisor of S that is <= target (prefers multiples of 128)."""
    b = min(target, S)
    while S % b:
        b -= 1
    return b


def flash_attention(q, k, v, window=None, block_q: int = 512,
                    block_k: int = 512, interpret=None):
    """Model-layout wrapper.  q: (B,S,H,Dh); k/v: (B,S,K,Dh) -> (B,S,H,Dh)."""
    if interpret is None:
        interpret = _on_cpu()
    S = q.shape[1]
    bq = _pick_block(S, block_q)
    bk = _pick_block(S, block_k)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = flash_attention_bhsd(qt, kt, vt, window=window, block_q=bq,
                             block_k=bk, interpret=interpret)
    return o.transpose(0, 2, 1, 3)


def flash_decode(q, k, v, pos, block_k: int = 512, interpret=None,
                 window=None):
    """Model-layout wrapper for single-query decode attention.

    q: (B, 1, H, Dh) roped query; k/v: (B, S, K, Dh) KV cache (slot i =
    absolute position i, H % K == 0); pos: (B,) int32 — attends slots
    [0, pos_b].  Returns (B, 1, H, Dh).

    ``window`` (static int) marks k/v as a sliding-window ring of S =
    min(cache_len, window) slots (slot = position mod S): sequence b
    attends only positions (pos_b - window, pos_b] through the wrapped
    slot map.
    """
    if interpret is None:
        interpret = _on_cpu()
    B, _, H, Dh = q.shape
    K = k.shape[2]
    G = H // K
    S = k.shape[1]
    bk = _pick_block(S, block_k)
    qg = q[:, 0].reshape(B, K, G, Dh)                # grouped like the model
    kt = k.transpose(0, 2, 1, 3)                     # (B, K, S, Dh)
    vt = v.transpose(0, 2, 1, 3)
    o = flash_decode_bkgd(qg, kt, vt, pos, block_k=bk, interpret=interpret,
                          window=window)
    return o.reshape(B, H, Dh)[:, None]


def flash_decode_paged(q, k, v, page_table, pos, layer, interpret=None):
    """Model-layout wrapper for paged single-query decode attention.

    q: (B, 1, H, Dh) roped query; k/v: (layers, num_pages, K, page_size,
    Dh) the model's stacked page pool, read in place (H % K == 0);
    page_table: (B, n_pages) int32 mapping logical to physical pages (0 =
    null page); pos: (B,) int32 — attends logical positions [0, pos_b];
    layer: int32 scalar, the pool layer attended.  Returns (B, 1, H, Dh).
    Each sequence reads only the ``cdiv(pos_b + 1, page_size)`` pages that
    hold its lines, all kv heads of a page at once.
    """
    if interpret is None:
        interpret = _on_cpu()
    B, _, H, Dh = q.shape
    K = k.shape[2]
    G = H // K
    qg = q[:, 0].reshape(B, K, G, Dh)                # grouped like the model
    o = flash_decode_paged_bkgd(qg, k, v, page_table, pos, layer,
                                interpret=interpret)
    return o.reshape(B, H, Dh)[:, None]


def ssd_scan(x, dt, A, Bm, Cm, chunk: int = 256, interpret=None):
    """Model-layout wrapper.  x: (B,S,H,P); dt: (B,S,H); A: (H,);
    Bm/Cm: (B,S,N) -> y: (B,S,H,P)."""
    if interpret is None:
        interpret = _on_cpu()
    B, S, H, P = x.shape
    Q = _pick_block(S, chunk)
    xt = x.transpose(0, 2, 1, 3)                     # (B,H,S,P)
    # dt/a ride as (B,H,S,1) columns: a (1, chunk) block of a (B,H,S)
    # array breaks Mosaic's (8, 128) tiling rule for the last two dims
    dtt = dt.astype(jnp.float32).transpose(0, 2, 1)[..., None]  # (B,H,S,1)
    a = dtt * A.astype(jnp.float32)[None, :, None, None]         # (B,H,S,1)
    y = ssd_scan_bhsp(xt, dtt, a, Bm, Cm, chunk=Q, interpret=interpret)
    return y.transpose(0, 2, 1, 3)
