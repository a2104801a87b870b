"""GQA attention: training/prefill (blockwise, memory-efficient) and decode.

The training/prefill path is q-block-chunked so the (S, S) logits tensor is
never materialized — required at 32k context where a naive einsum would need
terabytes of HBM.  This pure-JAX path doubles as the oracle for the Pallas
flash-attention kernel (``repro.kernels.flash_attention``); ``use_pallas``
switches the hot loop to the kernel (TPU / interpret mode).

Sliding-window attention (``cfg.sliding_window``) is a first-class variant:
it bounds the KV range per query and, at decode time, turns the KV cache into
a ring buffer of ``window`` slots — this is what makes ``long_500k`` decode
sub-quadratic-feasible for dense architectures (see DESIGN.md).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.actshard import constrain, maybe_psum, tp_will_reduce
from repro.models.mlp import rmsnorm

_NEG_INF = -1e30


# ---------------------------------------------------------------- rotary ----

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, Dh); positions: (..., S) or (S,)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                       # (Dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    cos = jnp.cos(angles)[..., None, :]                 # (..., S, 1, Dh/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pe(positions: jax.Array, d_model: int) -> jax.Array:
    """(S,) -> (S, d_model) classic transformer sinusoidal embedding."""
    half = d_model // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ----------------------------------------------------------- projections ----

def qkv_proj(p: dict, x: jax.Array, cfg: ModelConfig):
    dtype = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dtype)
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    if cfg.qk_norm:                   # RMSNorm over each head's Dh
        q = rmsnorm(q, p["q_norm"]["scale"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"]["scale"], cfg.norm_eps)
    return (constrain(q, "heads"), constrain(k, "kv"), constrain(v, "kv"))


def gate_heads(p: dict, x: jax.Array, o: jax.Array, cfg: ModelConfig):
    """o: (B, S, H, Dh) attention output of the sublayer input x; with
    ``cfg.attn_gate`` each head's lines are scaled by sigmoid(x Wg)."""
    if not cfg.attn_gate:
        return o
    g = jnp.einsum("bsd,dhk->bshk", x, p["wg"].astype(x.dtype),
                   preferred_element_type=jnp.float32)
    return constrain((o.astype(jnp.float32) * jax.nn.sigmoid(g))
                     .astype(o.dtype), "heads")


def out_proj(p: dict, o: jax.Array) -> jax.Array:
    # contracts the head dim — under serving TP (heads sharded over the
    # mesh inside shard_map) each shard holds a partial sum here, hence
    # the one cross-shard reduction per attention layer.  The partial
    # stays float32 through the psum: summing rounded bf16 partials can
    # flip near-tie logits vs the single-device contraction
    w = p["wo"].astype(o.dtype)
    if tp_will_reduce("attn_out"):
        part = jnp.einsum("bshk,hkd->bsd", o, w,
                          preferred_element_type=jnp.float32)
        return maybe_psum(part, "attn_out").astype(o.dtype)
    return jnp.einsum("bshk,hkd->bsd", o, w)


# ------------------------------------------------- blockwise causal core ----

def _attend_block(q, k, v, q_pos, kv_pos, window, scale, kv_valid=None):
    """q: (B,qb,K,G,Dh)  k/v: (B,S,K,Dh)  -> (B,qb,K,G,Dh).

    Computes softmax over the full kv range with causal (+ window) masking.
    fp32 logits/softmax for stability.  ``kv_valid`` ((S,) bool) excludes
    pad kv lines entirely (front-padded prefill); a query whose kv range
    masks out completely stays NaN-free because ``_NEG_INF`` is finite —
    its softmax is uniform and its (garbage) output is never consumed.
    """
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32) * scale
    mask = kv_pos[None, :] <= q_pos[:, None]                 # causal
    if window is not None:
        mask &= (q_pos[:, None] - kv_pos[None, :]) < window  # sliding window
    if kv_valid is not None:
        mask &= kv_valid[None, :]
    logits = jnp.where(mask[None, None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs, v)


def causal_attention(q, k, v, cfg: ModelConfig, q_block: int = 512,
                     positions: Optional[jax.Array] = None,
                     kv_valid: Optional[jax.Array] = None,
                     unroll: bool = False, one_block: bool = False):
    """q: (B,S,H,Dh), k/v: (B,S,K,Dh) -> (B,S,H,Dh).  Full/sliding causal.

    ``unroll`` replaces the q-block scan with straight-line HLO (dry-run
    cost probes only — XLA cost_analysis counts loop bodies once).

    ``one_block`` computes all q rows in one _attend_block call.  Used by
    sequence parallelism: the q-block SCAN's interleaved S-tiling cannot
    merge back into a contiguously S-sharded hidden (GSPMD inserted 24 GiB
    logit all-gathers to reshard — measured, see EXPERIMENTS.md §Perf-1);
    with one block the S shards flow through scores -> probs -> output
    untouched.  The (S_shard, S) logits transient is remat-bounded.

    ``kv_valid`` ((S,) bool, optional) excludes pad kv lines for every
    query (front-padded bucketed prefill, where ``positions`` carries the
    shifted coordinates).  It forces the one-block path: the q-block scan
    derives each block's q positions from ``start + arange``, which only
    holds for the identity position map.
    """
    B, S, H, Dh = q.shape
    K = k.shape[2]
    G = H // K
    scale = Dh ** -0.5
    window = cfg.sliding_window
    qg = q.reshape(B, S, K, G, Dh)
    kv_pos = jnp.arange(S) if positions is None else positions

    if one_block or S <= q_block or kv_valid is not None:
        o = _attend_block(qg, k, v, kv_pos, kv_pos, window, scale,
                          kv_valid=kv_valid)
        return o.reshape(B, S, H, Dh)

    nb = S // q_block
    assert S % q_block == 0, (S, q_block)
    q_blocks = qg.reshape(B, nb, q_block, K, G, Dh).transpose(1, 0, 2, 3, 4, 5)
    # under sequence parallelism the within-block q rows stay sharded over
    # `model`, so every model shard works on every scan iteration (the
    # OUTER nb dim is scanned sequentially — sharding it would idle chips)
    q_blocks = constrain(q_blocks, "q_blocks")

    # checkpoint the block body: otherwise the scan VJP stacks the softmax
    # residuals across blocks — the full (S, S) probs tensor, 6 GB/device in
    # f32 at 4k context (measured; see EXPERIMENTS.md §Perf).  Recomputing
    # scores in the backward is the flash-attention trade.
    @jax.checkpoint
    def body(_, inputs):
        qb, start = inputs
        q_pos = start + jnp.arange(q_block)
        o = _attend_block(qb, k, v, q_pos, kv_pos, window, scale)
        return None, o

    starts = jnp.arange(nb) * q_block
    if unroll:
        o_blocks = jnp.stack([body(None, (q_blocks[i], starts[i]))[1]
                              for i in range(nb)])
    else:
        _, o_blocks = jax.lax.scan(body, None, (q_blocks, starts))
    o = o_blocks.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, Dh)
    return o


# -------------------------------------------------------------- training ----

def attention_train(p: dict, x: jax.Array, cfg: ModelConfig,
                    use_pallas: bool = False,
                    unroll: bool = False,
                    one_block: bool = False) -> jax.Array:
    """Full-sequence attention (training / prefill without cache return)."""
    B, S, _ = x.shape
    q, k, v = qkv_proj(p, x, cfg)
    if cfg.pos_embedding == "rope":
        pos = jnp.arange(S)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    if use_pallas:
        from repro.kernels import ops as kops
        o = kops.flash_attention(q, k, v, window=cfg.sliding_window)
    else:
        o = causal_attention(q, k, v, cfg, unroll=unroll,
                             one_block=one_block)
    o = gate_heads(p, x, constrain(o, "heads"), cfg)
    return out_proj(p, o)


# ---------------------------------------------------------------- decode ----

def init_kv_cache(cfg: ModelConfig, batch: int, cache_len: int, n_groups: int,
                  dtype=jnp.bfloat16, abstract: bool = False, paging=None):
    """Stacked (over scan groups) KV cache for one attention sublayer slot.

    For sliding-window configs the cache has ``window`` slots (ring buffer);
    otherwise ``cache_len`` slots.

    ``paging`` (a :class:`repro.models.paging.PagedKVConfig`) switches the
    layout from dense per-slot lines ``(G, B, slots, K, Dh)`` to one shared
    page pool ``(G, num_pages, K, page_size, Dh)``: requests address it
    through a per-slot page table instead of a batch index, so the pool is
    sized to the HBM budget rather than ``batch * cache_len``.  Heads sit
    inside a page, so one (page_size, Dh) block is the tile the paged
    decode kernel reads straight from the pool.  Callers that indexed the
    cache by batch must go through the page table in this mode (see
    README "Paged KV cache" migration note).
    """
    if paging is not None:
        shape = (n_groups, paging.num_pages, cfg.num_kv_heads,
                 paging.page_size, cfg.head_dim)
    else:
        slots = min(cache_len, cfg.sliding_window or cache_len)
        shape = (n_groups, batch, slots, cfg.num_kv_heads, cfg.head_dim)
    if abstract:
        arr = jax.ShapeDtypeStruct(shape, dtype)
        return {"k": arr, "v": arr}
    # distinct buffers: the serving engine donates the cache pytree into
    # jitted steps, and XLA rejects donating one buffer twice
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attention_prefill(p: dict, x: jax.Array, cfg: ModelConfig,
                      cache_slots: int, use_pallas: bool = False,
                      unroll: bool = False,
                      positions: Optional[jax.Array] = None,
                      valid: Optional[jax.Array] = None,
                      roll: Optional[jax.Array] = None):
    """Prefill: full attention + return the populated KV cache slice.

    Returns (out (B,S,d), {"k","v"} (B, slots, K, Dh)).  When
    ``cache_slots < S`` (sliding window) the last ``slots`` positions are
    kept, laid out at ring indices ``pos % slots``.

    ``positions``/``valid``/``roll`` support front-padded bucketed
    prefill (hybrid/SSM configs whose siblings need the real tokens
    chunk-aligned): ``positions`` ((S,) int32, may be negative at the
    front pad) replaces ``arange(S)`` for RoPE and the causal mask,
    ``valid`` ((S,) bool) masks pad kv out of every query, and ``roll``
    (traced int32, the front-pad width) rotates the returned KV slice so
    real tokens land at cache lines ``[0, num_real)`` — exactly where an
    unpadded prefill writes them.  Garbage lines at/past ``num_real``
    stay masked at decode until overwritten, same as the tail-pad path.
    """
    B, S, _ = x.shape
    q, k, v = qkv_proj(p, x, cfg)
    if cfg.pos_embedding == "rope":
        pos = jnp.arange(S) if positions is None else positions
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    if valid is not None:
        assert not use_pallas, \
            "front-padded prefill has no Pallas path (flash kernel " \
            "lacks a kv-validity mask)"
        o = causal_attention(q, k, v, cfg, positions=positions,
                             kv_valid=valid)
    elif use_pallas:
        from repro.kernels import ops as kops
        o = kops.flash_attention(q, k, v, window=cfg.sliding_window)
    else:
        o = causal_attention(q, k, v, cfg)
    o = constrain(o, "heads")
    if cache_slots >= S:
        if roll is not None:
            k = jnp.roll(k, -roll, axis=1)
            v = jnp.roll(v, -roll, axis=1)
        pad = cache_slots - S
        ck = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cv = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        assert roll is None, \
            "front-padded prefill is gated off sliding-window ring caches"
        # last `slots` positions, placed at ring index pos % slots
        tail_k = k[:, S - cache_slots:]
        tail_v = v[:, S - cache_slots:]
        idx = (jnp.arange(S - cache_slots, S)) % cache_slots
        ck = jnp.zeros_like(tail_k).at[:, idx].set(tail_k)
        cv = jnp.zeros_like(tail_v).at[:, idx].set(tail_v)
    o = gate_heads(p, x, o, cfg)
    return out_proj(p, o), {"k": ck, "v": cv}


def paged_view(pool: jax.Array, layer, page_table: jax.Array):
    """One layer of a stacked pool ``(G, num_pages, K, ps, Dh)`` seen
    through ``page_table`` ((B, n) int32): the logical (B, n * ps, K, Dh)
    lines, line i of row b at position i — the dense cache's layout.
    One gather, so only the table's pages are read."""
    B, n = page_table.shape
    _, _, K, ps, Dh = pool.shape
    return pool[layer, page_table].transpose(0, 1, 3, 2, 4).reshape(
        B, n * ps, K, Dh)


def write_lines(pool: jax.Array, layer, pages, offs, lines):
    """Write KV ``lines`` (``pages.shape + (K, Dh)``) into a stacked pool
    ``(G, num_pages, K, ps, Dh)`` at per-line physical ``(layer, pages,
    offs)``; ``layer`` is a scalar or broadcasts against ``pages``.  A
    scatter of just these lines: on a carried or donated pool XLA
    updates it in place.  Each (line, head) is its own (Dh,) row, so the
    scatter's window is the pool's minor dim alone and leaves the pool in
    the row-major layout the paged kernel reads (a (K, Dh) window would
    have the compiler re-lay the pool with heads minor, and copy it back
    around every kernel call)."""
    idx = (jnp.asarray(layer)[..., None], pages[..., None],
           jnp.arange(pool.shape[2]), offs[..., None])
    return pool.at[idx].set(lines.astype(pool.dtype))


def write_pages(pool: jax.Array, page_ids, lines):
    """Write whole pages into every layer of a stacked pool ``(G,
    num_pages, K, ps, Dh)``: ``lines`` (G, n * ps, K, Dh) fill pages
    ``page_ids`` ((n,) int32) in order."""
    G, _, K, ps, Dh = pool.shape
    pages = lines.reshape(G, page_ids.shape[0], ps, K, Dh)
    return pool.at[:, page_ids].set(
        pages.transpose(0, 1, 3, 2, 4).astype(pool.dtype))


def attention_prefill_paged(p: dict, x: jax.Array, cache: dict,
                            layer, page_table: jax.Array, start: jax.Array,
                            cfg: ModelConfig):
    """Suffix prefill against a cached prefix in the paged KV pool
    (prefix-cache reuse: only the un-cached tail of the prompt runs).

    x: (B, S, d) hidden states of the *suffix* tokens, at absolute
    positions ``start + [0, S)``; cache k/v: (G, num_pages, K, page_size,
    Dh) — the shared stacked pool, of which ``layer`` (int32 scalar) is
    read; page_table: (B, n_prefix_pages) int32 rows
    whose first ``ceil(start / page_size)`` entries are the request's
    READ-ONLY shared prefix pages (any remaining entries null — callers
    may bucket the row width to the match depth so cost scales with the
    actual prefix); start: scalar int32 prefix length.  ``start`` need
    NOT be page-aligned: the prefix mask below works at LINE granularity
    (``arange(L) < start``), so a partially-filled last page contributes
    exactly its live lines — this is what lets chunked prefill
    (``models.model.prefill_chunk``) resume from any position.

    Suffix queries attend causally over [the prefix gathered through the
    page table (positions < start), the suffix itself].  The masking and
    einsum strings are exactly the dense prefill's, so with compute
    dtype == pool dtype the logits match a full-prompt prefill bit for
    bit.  The pool is **never written** — shared pages are read-only by
    construction; the suffix K/V slice is returned for the caller to
    scatter into privately-owned pages (the copy-on-write fork: writes
    only ever land past the shared region).

    Returns (out (B,S,d), {"k","v"} suffix slice (B, S, K, Dh)).
    """
    B, S, _ = x.shape
    q, k, v = qkv_proj(p, x, cfg)
    pos = start + jnp.arange(S)
    if cfg.pos_embedding == "rope":
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    H, Dh = q.shape[2], q.shape[3]
    K = k.shape[2]
    G = H // K
    kd = paged_view(cache["k"], layer, page_table).astype(q.dtype)
    vd = paged_view(cache["v"], layer, page_table).astype(q.dtype)
    L = kd.shape[1]                                  # logical prefix width
    scale = Dh ** -0.5
    qg = q.reshape(B, S, K, G, Dh)
    # prefix part: every suffix query sees every position < start (all
    # causal by construction); pool garbage past start is masked out
    lp = jnp.einsum("bqkgd,bskd->bkgqs", qg, kd).astype(jnp.float32) * scale
    pre_valid = jnp.arange(L) < start                # (L,)
    lp = jnp.where(pre_valid[None, None, None, None, :], lp, _NEG_INF)
    # suffix part: causal within the suffix (pad tails in a bucketed
    # suffix sit at higher positions, so real queries never see them)
    ls = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) * scale
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    ls = jnp.where(causal[None, None, None], ls, _NEG_INF)
    probs = jax.nn.softmax(jnp.concatenate([lp, ls], axis=-1),
                           axis=-1).astype(x.dtype)
    # combine the two regions with f32 partial sums and ONE final cast:
    # a bf16 round between the partials would double-round vs the dense
    # path's single accumulation and drift the suffix hidden states
    f32 = jnp.float32
    o = (jnp.einsum("bkgqs,bskd->bqkgd", probs[..., :L].astype(f32),
                    vd.astype(f32))
         + jnp.einsum("bkgqs,bskd->bqkgd", probs[..., L:].astype(f32),
                      v.astype(f32)))
    o = o.astype(x.dtype).reshape(B, S, H, Dh)
    o = gate_heads(p, x, constrain(o, "heads"), cfg)
    return out_proj(p, o), {"k": k, "v": v}


def attention_verify(p: dict, x: jax.Array, cache: dict, layer,
                     pos0: jax.Array, pages: jax.Array, offs: jax.Array,
                     page_table: jax.Array, cfg: ModelConfig):
    """Multi-position decode against a paged KV pool (speculative verify).

    x: (B, S, d) hidden states of S consecutive tokens per slot — the
    slot's last sampled token followed by S-1 drafted continuations, at
    absolute positions ``pos0 + [0, S)``; cache k/v: (G, num_pages, K,
    page_size, Dh) — the shared stacked pool, of which ``layer`` (int32
    scalar) is written and read; pages/offs: (B, S) int32
    physical scatter targets for each token's KV line (the null page for
    lines past a slot's allocation or for dead slots); page_table:
    (B, pages_per_seq) as in :func:`attention_decode_paged`.

    Query row j is EXACTLY the one-token decode at position ``pos0 + j``:
    all S lines scatter first, then each row gathers the pool through
    the page table and masks ``arange <= pos0 + j`` — the same valid
    mask, scale, einsum strings, and cast points as
    :func:`attention_decode_paged`, so row j's output is bit-identical
    to a sequential decode that had written lines ``pos0..pos0+j``.
    Rejected drafts' lines are dead on arrival: the accept mask
    truncates ``pos`` host-side, and the next round's scatter overwrites
    them before any query can attend past its own position.

    Returns (out (B,S,d), updated stacked cache).
    """
    assert cfg.sliding_window is None, "paged KV is full-attention only"
    B, S, _ = x.shape
    q, k, v = qkv_proj(p, x, cfg)                     # (B,S,H/K,Dh)
    posm = pos0[:, None].astype(jnp.int32) + jnp.arange(S)[None, :]
    if cfg.pos_embedding == "rope":
        q = apply_rope(q, posm, cfg.rope_theta)
        k = apply_rope(k, posm, cfg.rope_theta)
    # scatter all S lines; duplicate writes only ever target the null
    # page (dead slots / over-capacity lines), same as bucket pad lines
    ck = write_lines(cache["k"], layer, pages, offs, k)
    cv = write_lines(cache["v"], layer, pages, offs, v)

    H, Dh = q.shape[2], q.shape[3]
    K = ck.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, Dh)
    kd = paged_view(ck, layer, page_table)
    vd = paged_view(cv, layer, page_table)
    L = kd.shape[1]
    valid = jnp.arange(L)[None, None, :] <= posm[:, :, None]   # (B,S,L)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, kd).astype(jnp.float32)
    logits = logits * (Dh ** -0.5)
    logits = jnp.where(valid[:, None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", probs, vd).reshape(B, S, H, Dh)
    o = gate_heads(p, x, constrain(o, "heads"), cfg)
    return out_proj(p, o), {"k": ck, "v": cv}


def attention_decode(p: dict, x: jax.Array, cache: dict, pos: jax.Array,
                     cfg: ModelConfig, use_pallas: bool = False):
    """One-token decode.  x: (B,1,d); cache k/v: (B, slots, K, Dh);
    pos: scalar int32 OR (B,) int32 — absolute position of each new token
    (0-based).  Per-slot positions support continuous batching (each slot
    of the serving engine decodes at its own depth).

    ``use_pallas`` routes the attention through the split-KV flash-decode
    kernel (``repro.kernels.flash_decode``): full caches mask ``slot <=
    pos`` (slot i holds absolute position i), sliding-window ring caches
    pass ``window`` so the kernel masks through the wrapped
    slot-to-position map instead of falling back to the reference path.

    Returns (out (B,1,d), updated cache).
    """
    B = x.shape[0]
    slots = cache["k"].shape[1]
    q, k, v = qkv_proj(p, x, cfg)                     # (B,1,H/K,Dh)
    posv = jnp.broadcast_to(pos, (B,)).astype(jnp.int32)
    if cfg.pos_embedding == "rope":
        q = apply_rope(q, posv[:, None], cfg.rope_theta)
        k = apply_rope(k, posv[:, None], cfg.rope_theta)
    slot = posv % slots                               # (B,) ring index
    bidx = jnp.arange(B)
    ck = cache["k"].at[bidx, slot].set(k[:, 0].astype(cache["k"].dtype))
    cv = cache["v"].at[bidx, slot].set(v[:, 0].astype(cache["v"].dtype))

    if use_pallas:
        from repro.kernels import ops as kops
        o = kops.flash_decode(q, ck, cv, posv, window=cfg.sliding_window)
        o = gate_heads(p, x, constrain(o, "heads"), cfg)
        return out_proj(p, o), {"k": ck, "v": cv}

    H, Dh = q.shape[2], q.shape[3]
    K = ck.shape[2]
    G = H // K
    qg = q.reshape(B, 1, K, G, Dh)
    # absolute position held by each ring slot i:  p - ((p - i) mod slots)
    slot_ids = jnp.arange(slots)
    slot_pos = posv[:, None] - ((posv[:, None] - slot_ids[None, :]) % slots)
    valid = (slot_pos >= 0) & (slot_pos <= posv[:, None])  # (B, slots)
    if cfg.sliding_window is not None:
        valid &= (posv[:, None] - slot_pos) < cfg.sliding_window
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, ck).astype(jnp.float32)
    logits = logits * (Dh ** -0.5)
    logits = jnp.where(valid[:, None, None, None, :], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", probs, cv).reshape(B, 1, H, Dh)
    o = gate_heads(p, x, constrain(o, "heads"), cfg)
    return out_proj(p, o), {"k": ck, "v": cv}


def attention_decode_paged(p: dict, x: jax.Array, cache: dict, layer,
                           pos: jax.Array, page_table: jax.Array,
                           cfg: ModelConfig, use_pallas: bool = False):
    """One-token decode against a paged KV pool.

    x: (B,1,d); cache k/v: (G, num_pages, K, page_size, Dh) — the shared
    pool stacked over layer groups, NOT per-batch; layer: int32 scalar,
    the group written and read; page_table: (B, pages_per_seq) int32
    mapping each sequence's logical page j to a physical pool page
    (entry 0 = the null page for unallocated tails); pos: scalar or (B,)
    int32 absolute position of the new token.

    The new K/V line lands at physical ``(layer, page_table[b, pos//ps],
    :, pos % ps)``, a scatter of B lines into the stacked pool; nothing
    else of the pool is copied.  ``use_pallas`` hands the whole stacked
    pool and ``layer`` to the paged kernel, which reads the table's pages
    where they lie.  Otherwise attention gathers the layer through the
    page table into a (B, pages_per_seq*ps, K, Dh) logical view and runs
    exactly the dense full-cache math — masked logits are the same array
    the dense path produces, so greedy decode is bit-identical to the
    dense cache (pool garbage beyond ``pos`` is masked to the same
    ``_NEG_INF``).

    Full-attention only: the paged pool has no ring layout, so callers
    gate ``sliding_window`` configs to the dense path.

    Returns (out (B,1,d), updated stacked cache).
    """
    assert cfg.sliding_window is None, "paged KV is full-attention only"
    B = x.shape[0]
    ps = cache["k"].shape[3]
    q, k, v = qkv_proj(p, x, cfg)                     # (B,1,H/K,Dh)
    posv = jnp.broadcast_to(pos, (B,)).astype(jnp.int32)
    if cfg.pos_embedding == "rope":
        q = apply_rope(q, posv[:, None], cfg.rope_theta)
        k = apply_rope(k, posv[:, None], cfg.rope_theta)
    bidx = jnp.arange(B)
    page = page_table[bidx, posv // ps]               # (B,) physical page
    off = posv % ps
    # live slots own disjoint pages; dead/frozen slots all target the null
    # page, whose contents are never read unmasked
    ck = write_lines(cache["k"], layer, page, off, k[:, 0])
    cv = write_lines(cache["v"], layer, page, off, v[:, 0])

    if use_pallas:
        from repro.kernels import ops as kops
        o = kops.flash_decode_paged(q, ck, cv, page_table, posv, layer)
        o = gate_heads(p, x, constrain(o, "heads"), cfg)
        return out_proj(p, o), {"k": ck, "v": cv}

    H, Dh = q.shape[2], q.shape[3]
    K = ck.shape[2]
    G = H // K
    qg = q.reshape(B, 1, K, G, Dh)
    # gather the logical view: (B, n_pages*ps, K, Dh)
    kd = paged_view(ck, layer, page_table)
    vd = paged_view(cv, layer, page_table)
    valid = jnp.arange(kd.shape[1])[None, :] <= posv[:, None]
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, kd).astype(jnp.float32)
    logits = logits * (Dh ** -0.5)
    logits = jnp.where(valid[:, None, None, None, :], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", probs, vd).reshape(B, 1, H, Dh)
    o = gate_heads(p, x, constrain(o, "heads"), cfg)
    return out_proj(p, o), {"k": ck, "v": cv}
