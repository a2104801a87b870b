"""Model assembly: embedding/frontends -> scanned layer groups -> LM head.

Entry points (all pure functions of (params, batch/cache)):
  * ``forward_train(params, batch, cfg, run)``  -> (logits, aux)
  * ``loss_fn(params, batch, cfg, run)``        -> (loss, metrics)
  * ``prefill(params, batch, cfg, run)``        -> (logits, cache)
  * ``decode_step(params, cache, token, pos, cfg, run)`` -> (logits, cache)
  * ``decode_n(params, cache, token, pos, ...)`` -> N tokens per dispatch
    (``lax.scan`` over ``decode_step`` with fused sampling and device-side
    per-slot stop masking — the serving engine's device-resident fast path)

Layers run as a ``lax.scan`` over stacked layer groups (period P =
lcm(attn_every, moe.every)); compile time is flat in depth.  Remat policy
(``run.remat``) wraps the scan body with ``jax.checkpoint``.

Modality frontends (vision/audio) are stubs per the assignment: the batch
carries precomputed prefix/frame embeddings and this module consumes them.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.core.actshard import constrain
from repro.models import attention as A
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.mlp import mlp_apply, rmsnorm
from repro.models.spec import group_period, layer_schedule

AUX_KEYS = ("moe_balance_loss", "moe_z_loss")


# ------------------------------------------------------------ embeddings ----

def compute_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def embed_tokens(params, tokens, cfg: ModelConfig):
    tab = params["embed"]["tok"].astype(compute_dtype(cfg))
    h = jnp.take(tab, tokens, axis=0)
    if cfg.embed_scale:                               # muP: x sqrt(d)
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    return h


def build_hidden(params, batch: dict, cfg: ModelConfig):
    """Assemble the input hidden states from tokens and/or stub embeddings."""
    dtype = compute_dtype(cfg)
    parts = []
    if "prefix_embeddings" in batch:                 # vlm: ViT stub output
        parts.append(batch["prefix_embeddings"].astype(dtype))
    if "frame_embeddings" in batch:                  # audio: codec stub output
        parts.append(batch["frame_embeddings"].astype(dtype))
    if "tokens" in batch:
        parts.append(embed_tokens(params, batch["tokens"], cfg))
    h = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    if cfg.pos_embedding == "sinusoidal":
        S = h.shape[1]
        pe = A.sinusoidal_pe(jnp.arange(S), cfg.d_model).astype(dtype)
        h = h + pe[None]
    return constrain(h, "hidden")


def unembed(params, h, cfg: ModelConfig):
    w = params["embed"]["tok"] if cfg.tie_embeddings else params["lm_head"]["w"]
    return constrain(jnp.einsum("bsd,vd->bsv", h, w.astype(h.dtype)), "logits")


# ------------------------------------------------------------- sublayers ----

def _zeros_aux():
    return {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}


def layer_config(cfg: ModelConfig, i: int, paged: bool = False):
    """``cfg`` as the attention of layer slot ``i`` sees it: its own
    window and rotary (``ModelConfig.attn_kinds``).  ``paged`` drops every
    window: the paged pool holds no ring, and ``DecodeEngine`` pages a
    windowed configuration only where its cache cannot outgrow the window,
    so there the window changes nothing."""
    if cfg.layer_types is None and not (paged and cfg.sliding_window):
        return cfg
    window, rope = cfg.attn_kinds()[i]
    return dataclasses.replace(
        cfg, layer_types=None, sliding_window=None if paged else window,
        pos_embedding=cfg.pos_embedding if rope or cfg.pos_embedding != "rope"
        else "none")


def _post_norm(p, name: str, h, cfg: ModelConfig):
    """A sublayer's output, through its own RMSNorm under
    ``cfg.sandwich_norm``."""
    if not cfg.sandwich_norm:
        return h
    return rmsnorm(h, p[name]["scale"], cfg.norm_eps)


def sublayer_train(p, x, mixer: str, ffn: str, cfg: ModelConfig,
                   run: RunConfig, acfg: Optional[ModelConfig] = None):
    """One sublayer; ``acfg`` is its attention's ``layer_config``."""
    aux = _zeros_aux()
    h = rmsnorm(x, p["norm1"]["scale"], cfg.norm_eps)
    if mixer == "attn":
        h = A.attention_train(p["attn"], h, acfg or cfg,
                              use_pallas=run.use_pallas, unroll=run.unroll,
                              one_block=run.seq_parallel)
    else:
        h = SSM.ssm_train(p["ssm"], h, cfg, use_pallas=run.use_pallas)
    x = _attn_block(p, x, h, cfg)
    if ffn != "none":
        h = rmsnorm(x, p["norm2"]["scale"], cfg.norm_eps)
        if run.seq_parallel:
            # Megatron-SP: gather the bf16 post-norm hidden to full S for
            # the TP FFN; the output constraint below makes XLA emit a
            # reduce-scatter (not all-reduce) for the w2 partial sums.
            h = constrain(h, "hidden_full")
        if ffn == "moe":
            h, moe_aux = MOE.moe_apply(p["moe"], h, cfg)
            for k in AUX_KEYS:
                aux[k] = aux[k] + moe_aux[k]
        else:
            h = mlp_apply(p["mlp"], h, cfg.mlp_type)
        if run.seq_parallel:
            h = constrain(h, "hidden")
        x = constrain(x + _post_norm(p, "post_mlp_norm", h, cfg), "hidden")
    return x, aux


def _attn_block(p, x, hh, cfg: ModelConfig):
    """The attention half's residual: x + (normed) attention output."""
    return constrain(x + _post_norm(p, "post_attn_norm", hh, cfg), "hidden")


def _ffn_block(p, x, ffn: str, cfg: ModelConfig, use_pallas: bool = False,
               valid=None, name: str = "moe_experts"):
    """The sublayer's FFN half (norm, MLP or dropless MoE, residual) at
    inference.  Returns (x, the expert layer's ``MOE.MOE_STATS`` counts of
    the rows ``valid`` marks, or None); ``name`` names its kernels."""
    if ffn == "none":
        return x, None
    hh = rmsnorm(x, p["norm2"]["scale"], cfg.norm_eps)
    stats = None
    if ffn == "moe":
        hh, stats = MOE.moe_dropless(p["moe"], hh, cfg, use_pallas, valid,
                                     name)
    else:
        hh = mlp_apply(p["mlp"], hh, cfg.mlp_type)
    return constrain(x + _post_norm(p, "post_mlp_norm", hh, cfg),
                     "hidden"), stats


def _paged_layers(params, cache, x, attend, cfg: ModelConfig,
                  run: RunConfig, remat: bool = False, count=None,
                  name: str = "moe_experts"):
    """Run the layer groups against the stacked paged pools.

    ``attend(p_attn, h, pool, g, acfg) -> (h, pool, out)`` is one
    attention sublayer: ``pool`` is that sublayer's ``{"k", "v"}`` pool,
    each leaf ``(G, num_pages, K, page_size, Dh)``, ``g`` the int32 group
    index it reads and writes at, and ``acfg`` its ``layer_config``.  The
    pools ride whole in the loop's carry, never in ``xs``/``ys``: no
    layer's pool is sliced out and stacked back, so a layer's new lines
    are written in place and its pages are read where they lie.  A cache
    that carries ``"moe_stats"`` ((4,) int32, ``MOE.MOE_STATS``) carries
    it through; where ``count`` ((B, S) bool: the rows to count) is given,
    every expert layer's counts of those rows are added to it.  ``name``
    names the expert kernels (``MOE.moe_dropless``).  Returns (x,
    {"layers": [...]} pools (and the counts), ``out``s stacked over
    groups, a tuple per sublayer)."""
    sched = layer_schedule(cfg)[:group_period(cfg)]
    assert all(mixer == "attn" for mixer, _ in sched), \
        "the paged KV pool is full-attention only"
    counting = "moe_stats" in cache
    adding = counting and count is not None

    def step(carry, inp):
        x, pools = carry[:2]
        stats = carry[2] if counting else None
        group_params, g = inp
        pools, outs = list(pools), []
        for i, (_mixer, ffn) in enumerate(sched):
            p = group_params[i]
            hh = rmsnorm(x, p["norm1"]["scale"], cfg.norm_eps)
            hh, pools[i], out = attend(p["attn"], hh, pools[i], g,
                                       layer_config(cfg, i, paged=True))
            x, st = _ffn_block(p, _attn_block(p, x, hh, cfg), ffn, cfg,
                               run.use_pallas, count, name)
            if adding and st is not None:
                stats = stats + st
            outs.append(out)
        new = (x, tuple(pools)) + ((stats,) if counting else ())
        return new, tuple(outs)

    if remat:
        step = jax.checkpoint(step, prevent_cse=False)
    carry = (x, tuple(cache["layers"]))
    if counting:
        carry += (cache["moe_stats"],)
    n_groups = jax.tree.leaves(params["layers"])[0].shape[0]
    if run.unroll:
        per_group = []
        for g in range(n_groups):
            gp = jax.tree.map(lambda l: l[g], tuple(params["layers"]))
            carry, out = step(carry, (gp, g))
            per_group.append(out)
        outs = jax.tree.map(lambda *xs: jnp.stack(xs), *per_group)
    else:
        carry, outs = jax.lax.scan(
            step, carry,
            (tuple(params["layers"]), jnp.arange(n_groups, dtype=jnp.int32)))
    new_cache = {"layers": list(carry[1])}
    if counting:
        new_cache["moe_stats"] = carry[2]
    return carry[0], new_cache, outs


# --------------------------------------------------------------- forward ----

def backbone_train(params, h, cfg: ModelConfig, run: RunConfig):
    """Scan the layer groups; returns (h, aux-sums)."""
    P = group_period(cfg)
    sched = layer_schedule(cfg)[:P]

    def group_body(carry, group_params):
        x, acc = carry
        for i, (mixer, ffn) in enumerate(sched):
            x, aux = sublayer_train(group_params[i], x, mixer, ffn, cfg, run,
                                    layer_config(cfg, i))
            acc = {k: acc[k] + aux[k] for k in AUX_KEYS}
        return (x, acc), None

    if run.remat in ("layer", "full"):
        group_body = jax.checkpoint(group_body,
                                    prevent_cse=False)
    if run.unroll:
        carry = (h, _zeros_aux())
        n_groups = jax.tree.leaves(params["layers"])[0].shape[0]
        for g in range(n_groups):
            gp = jax.tree.map(lambda l: l[g], tuple(params["layers"]))
            carry, _ = group_body(carry, gp)
        return carry
    (h, acc), _ = jax.lax.scan(group_body, (h, _zeros_aux()),
                               tuple(params["layers"]))
    return h, acc


def forward_train(params, batch: dict, cfg: ModelConfig, run: RunConfig):
    h = build_hidden(params, batch, cfg)
    h, aux = backbone_train(params, h, cfg, run)
    h = rmsnorm(h, params["final_norm"]["scale"], cfg.norm_eps)
    return unembed(params, h, cfg), aux


def softmax_xent(logits, labels, mask):
    """Vocab-parallel-friendly cross entropy (one-hot formulation)."""
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    oh = jax.nn.one_hot(labels, lg.shape[-1], dtype=jnp.float32)
    ll = jnp.sum(oh * lg, axis=-1)
    per_tok = (lse - ll) * mask
    return jnp.sum(per_tok) / jnp.maximum(jnp.sum(mask), 1.0)


def loss_fn(params, batch: dict, cfg: ModelConfig, run: RunConfig):
    logits, aux = forward_train(params, batch, cfg, run)
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones(batch["labels"].shape, jnp.float32)
    xent = softmax_xent(logits, batch["labels"], mask)
    loss = xent
    if cfg.moe is not None:
        loss = (loss + cfg.moe.balance_coef * aux["moe_balance_loss"]
                + cfg.moe.router_z_coef * aux["moe_z_loss"])
    metrics = {"loss": loss, "xent": xent, **aux}
    return loss, metrics


# ----------------------------------------------------------------- cache ----

def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               abstract: bool = False, paging=None):
    """Decode cache pytree: one entry per sublayer slot, stacked over groups.

    ``paging`` (a :class:`repro.models.paging.PagedKVConfig`) makes every
    attention sublayer a shared page pool ``(G, num_pages, K, page_size,
    Dh)`` instead of ``batch`` dense rows ``(G, batch, slots, K, Dh)``;
    decode must then pass the matching ``page_table``.  Paged mode is
    full-attention only (SSM state is not line-addressable), so callers
    gate on ``cfg.ssm is None``.
    """
    P = group_period(cfg)
    n_groups = cfg.num_layers // P
    sched = layer_schedule(cfg)[:P]
    layers = []
    for i, (mixer, _) in enumerate(sched):
        if mixer == "attn":
            layers.append(A.init_kv_cache(layer_config(cfg, i), batch,
                                          cache_len, n_groups,
                                          abstract=abstract, paging=paging))
        else:
            assert paging is None, "paged KV cache is attention-only"
            layers.append(SSM.init_ssm_cache(cfg, batch, n_groups,
                                             abstract=abstract))
    return {"layers": layers}


def cache_logical_axes(cfg: ModelConfig, paging: bool = False):
    """Logical axes pytree matching ``init_cache`` (see core/sharding.py)."""
    P = group_period(cfg)
    sched = layer_schedule(cfg)[:P]
    layers = []
    for mixer, _ in sched:
        if mixer == "attn":
            # paged pools have no batch dim: pages replace (batch, seq),
            # and heads sit outside a page's lines
            ax = (("layers", None, "kv_heads", "cache_seq", "head_dim")
                  if paging else
                  ("layers", "batch", "cache_seq", "kv_heads", "head_dim"))
            layers.append({"k": ax, "v": ax})
        else:
            layers.append({
                "state": ("layers", "batch", "ssm_head", None, "ssm_state"),
                "conv": ("layers", "batch", "conv", None),
            })
    return {"layers": layers}


def cache_kv_head_dim(cfg: ModelConfig, paging: bool = False) -> int:
    """Index of the ``kv_heads`` axis in every attention KV-cache leaf of
    one layout.

    ``paging=True``: the paged pool ``(G, pages, K, ps, Dh)``, dim 2.
    ``paging=False``: dense rows ``(G, B, slots, K, Dh)`` and the
    one-request prefill/chunk slices ``(G, B, S, K, Dh)`` that are
    scattered into either cache, dim 3.  Within one layout every leaf
    agrees, so serving TP covers a cache pytree with a single
    PartitionSpec prefix per layout (``serving.tp.cache_pspec``).
    Derived from :func:`cache_logical_axes` so a future layout change
    breaks loudly here instead of silently mis-sharding."""
    for leaf in cache_logical_axes(cfg, paging=paging)["layers"]:
        for axes in leaf.values():
            if "kv_heads" in axes:
                return axes.index("kv_heads")
    raise ValueError(
        f"no attention KV leaf in the cache for cfg with ssm={cfg.ssm!r}")


# ---------------------------------------------------------------- prefill ----

def prefill(params, batch: dict, cfg: ModelConfig, run: RunConfig,
            cache_len: Optional[int] = None,
            last_pos: Optional[jax.Array] = None,
            front_pad: Optional[jax.Array] = None,
            num_real: Optional[jax.Array] = None):
    """Run the full prompt, return (last-position logits, populated cache).

    ``last_pos`` (traced scalar int32, optional) selects which position's
    logits to return instead of the literal last one.  Bucketed prefill
    pads prompts up to a power-of-two length L and passes ``P - 1`` here:
    causal masking guarantees positions < P never attend the pad tail, so
    the logits at P-1 are exactly the unpadded prompt's (the pad KV lines
    written past P-1 stay masked at decode time until overwritten).

    ``front_pad``/``num_real`` (traced int32, optional, both or neither)
    switch to FRONT-padded bucketing for SSM/hybrid configs, whose state
    scan cannot ride the causal-mask-only tail-pad argument: the real
    tokens sit at ``[front_pad, front_pad + num_real)``, pad positions
    are explicitly masked out of attention kv and of the SSD recurrence
    (``dt=0`` identity steps), RoPE/causal positions shift to
    ``arange(S) - front_pad``, and attention KV lines rotate back so
    real tokens land at cache lines ``[0, num_real)``.  Callers align
    ``front_pad`` to a multiple of ``cfg.ssm.chunk`` so the real tokens'
    chunk offsets — and the f32 scan — match the unpadded run bit for
    bit.  Pass ``last_pos = front_pad + num_real - 1``.  Requires
    ``cfg.pos_embedding != "sinusoidal"`` (that PE is added before the
    shift is known) and no sliding-window ring.
    """
    P = group_period(cfg)
    sched = layer_schedule(cfg)[:P]
    h = build_hidden(params, batch, cfg)
    S = h.shape[1]
    cache_len = cache_len or S
    positions = valid = None
    if front_pad is not None:
        assert num_real is not None
        assert cfg.pos_embedding != "sinusoidal", \
            "front-padded prefill: sinusoidal PE is applied in " \
            "build_hidden, before the position shift"
        idx = jnp.arange(S)
        valid = (idx >= front_pad) & (idx < front_pad + num_real)
        positions = idx - front_pad

    def group_body(x, group_params):
        new_caches = []
        for i, (mixer, ffn) in enumerate(sched):
            p = group_params[i]
            hh = rmsnorm(x, p["norm1"]["scale"], cfg.norm_eps)
            if mixer == "attn":
                acfg = layer_config(cfg, i)
                slots = min(cache_len, acfg.sliding_window or cache_len)
                hh, c = A.attention_prefill(p["attn"], hh, acfg, slots,
                                            use_pallas=run.use_pallas,
                                            unroll=run.unroll,
                                            positions=positions,
                                            valid=valid, roll=front_pad)
            else:
                hh, c = SSM.ssm_prefill(
                    p["ssm"], hh, cfg, use_pallas=run.use_pallas,
                    valid=valid,
                    conv_end=(None if front_pad is None
                              else front_pad + num_real))
            x, _ = _ffn_block(p, _attn_block(p, x, hh, cfg), ffn, cfg,
                              run.use_pallas, name="moe_experts_prefill")
            new_caches.append(c)
        return x, tuple(new_caches)

    if run.remat in ("layer", "full"):
        group_body = jax.checkpoint(group_body, prevent_cse=False)
    if run.unroll:
        n_groups = jax.tree.leaves(params["layers"])[0].shape[0]
        per_group = []
        for g in range(n_groups):
            gp = jax.tree.map(lambda l: l[g], tuple(params["layers"]))
            h, c = group_body(h, gp)
            per_group.append(c)
        caches = jax.tree.map(lambda *xs: jnp.stack(xs), *per_group)
    else:
        h, caches = jax.lax.scan(group_body, h, tuple(params["layers"]))
    h = rmsnorm(h, params["final_norm"]["scale"], cfg.norm_eps)
    if last_pos is None:
        h_last = h[:, -1:]
    else:
        h_last = jax.lax.dynamic_slice_in_dim(h, last_pos, 1, axis=1)
    logits = unembed(params, h_last, cfg)
    return logits, {"layers": list(caches)}


def prefill_suffix(params, batch: dict, cache, page_table, start,
                   cfg: ModelConfig, run: RunConfig,
                   last_pos: Optional[jax.Array] = None):
    """Prefill from a page-aligned offset against cached prefix pages
    (the prefix-cache reuse path: only the prompt's un-cached tail runs).

    ``batch["tokens"]``: (B, S) the *suffix* tokens, at absolute
    positions ``start + [0, S)``; ``cache``: the paged pool pytree
    (read-only here); ``page_table``: (B, n_prefix_pages) rows whose
    first ``ceil(start / page_size)`` entries are the request's prefix
    pages; ``start``: scalar int32 prefix length — page-aligned on the
    prefix-cache path, but ANY position works (the prefix mask is
    line-granular; see :func:`prefill_chunk`); ``last_pos``: like
    :func:`prefill` — bucketed suffixes pass the true last *local*
    index.

    Returns (logits (B,1,V), {"layers": [...]} suffix cache slices, each
    (G, B, S, K, Dh)) — the caller scatters the slices into its
    privately-owned pages; the shared pages are never written.  Each
    layer reads its pages through its group index in the stacked pool
    (:func:`_paged_layers`), so no layer's pool is sliced out.
    Full-attention configs only (paging already gates SSM/ring out).
    """
    h = embed_tokens(params, batch["tokens"], cfg)
    S = h.shape[1]
    if cfg.pos_embedding == "sinusoidal":
        pe = A.sinusoidal_pe(start + jnp.arange(S), cfg.d_model)
        h = h + pe[None].astype(h.dtype)
    h = constrain(h, "hidden")

    def attend(p, hh, pool, g, acfg):
        hh, c = A.attention_prefill_paged(p, hh, pool, g, page_table,
                                          start, acfg)
        return hh, pool, c

    h, new_cache, caches = _paged_layers(
        params, cache, h, attend, cfg, run,
        remat=run.remat in ("layer", "full"), name="moe_experts_prefill")
    h = rmsnorm(h, params["final_norm"]["scale"], cfg.norm_eps)
    if last_pos is None:
        h_last = h[:, -1:]
    else:
        h_last = jax.lax.dynamic_slice_in_dim(h, last_pos, 1, axis=1)
    return unembed(params, h_last, cfg), dict(new_cache,
                                              layers=list(caches))


def prefill_chunk(params, batch: dict, cache, page_table, start,
                  cfg: ModelConfig, run: RunConfig,
                  last_pos: Optional[jax.Array] = None):
    """One chunk of a chunked (continuous-batching) prefill.

    Chunked prefill IS suffix prefill applied repeatedly: chunk ``i``
    treats the ``start = pos_filled`` tokens already written to the
    request's pages as the "prefix" and its own ``S`` tokens as the
    "suffix", so this is :func:`prefill_suffix` with two relaxations the
    underlying attention already supports:

    * ``start`` is NOT page-aligned in general — a chunk boundary can
      land mid-page.  ``attention_prefill_paged`` masks the gathered
      prefix at line granularity (``arange(L) < start``), so the
      partially-filled last page contributes exactly its live lines.
      Only the caller's KV *scatter* needs mid-page placement (the
      engine's per-line chunk insert).
    * ``page_table`` rows cover the pages holding ``[0, start + S)`` —
      the engine grows the request's holdings to ceil((start+S)/page)
      pages before dispatching the chunk.

    Chunks pad to a small fixed bucket set (powers of two up to the
    token budget), so the serving step compiles O(chunk buckets) times;
    ``last_pos`` selects the last REAL token's logits, and the final
    chunk's logits seed decode exactly like a whole-prompt prefill's.
    Greedy decode after N chunks is bit-identical to one whole-prompt
    prefill: every chunk runs the same masked attention math over the
    same absolute positions, and the f32-partial-sum combine in
    ``attention_prefill_paged`` avoids the double-rounding a bf16
    prefix/suffix split would introduce.

    Returns (logits (B,1,V), {"layers": [...]} chunk KV slices, each
    (G, B, S, K, Dh)).
    """
    return prefill_suffix(params, batch, cache, page_table, start, cfg,
                          run, last_pos=last_pos)


# ----------------------------------------------------------------- decode ----

def decode_step(params, cache, token, pos, cfg: ModelConfig, run: RunConfig,
                page_table=None, done=None):
    """One decoding step.  token: (B, 1) int32; pos: scalar int32 OR (B,)
    int32 (0-based absolute position of each new token — vector form for
    continuous batching).  ``page_table`` ((B, n_pages) int32) routes
    attention through the paged KV pool instead of dense per-slot rows:
    the stacked pool is carried through the layer loop, each layer writes
    its B new lines in place and reads its pages where they lie
    (:func:`_paged_layers`); there the expert counters count the lanes
    not ``done`` ((B,) bool, default none).  Returns (logits (B,1,V),
    new cache)."""
    P = group_period(cfg)
    sched = layer_schedule(cfg)[:P]
    B = token.shape[0]
    h = embed_tokens(params, token, cfg)
    if cfg.pos_embedding == "sinusoidal":
        posv = jnp.broadcast_to(pos, (B,))
        pe = A.sinusoidal_pe(posv[:, None], cfg.d_model)   # (B,1,d)
        h = h + pe.astype(h.dtype)

    if page_table is not None:
        def attend(p, hh, pool, g, acfg):
            hh, pool = A.attention_decode_paged(
                p, hh, pool, g, pos, page_table, acfg,
                use_pallas=run.use_pallas)
            return hh, pool, None

        count = None
        if "moe_stats" in cache:
            count = (jnp.ones((B, 1), bool) if done is None
                     else jnp.logical_not(done).reshape(B, 1))
        h, new_cache, _ = _paged_layers(params, cache, h, attend, cfg, run,
                                        count=count)
        h = rmsnorm(h, params["final_norm"]["scale"], cfg.norm_eps)
        return unembed(params, h, cfg), new_cache

    def group_body(x, inp):
        group_params, group_cache = inp
        new_caches = []
        for i, (mixer, ffn) in enumerate(sched):
            p = group_params[i]
            hh = rmsnorm(x, p["norm1"]["scale"], cfg.norm_eps)
            if mixer == "attn":
                hh, c = A.attention_decode(p["attn"], hh, group_cache[i],
                                           pos, layer_config(cfg, i),
                                           use_pallas=run.use_pallas)
            else:
                hh, c = SSM.ssm_decode(p["ssm"], hh, group_cache[i], cfg)
            x, _ = _ffn_block(p, _attn_block(p, x, hh, cfg), ffn, cfg,
                              run.use_pallas)
            new_caches.append(c)
        return x, tuple(new_caches)

    if run.unroll:
        n_groups = jax.tree.leaves(params["layers"])[0].shape[0]
        per_group = []
        for g in range(n_groups):
            gp = jax.tree.map(lambda l: l[g], tuple(params["layers"]))
            gc = jax.tree.map(lambda l: l[g], tuple(cache["layers"]))
            h, c = group_body(h, (gp, gc))
            per_group.append(c)
        new_layers = jax.tree.map(lambda *xs: jnp.stack(xs), *per_group)
    else:
        h, new_layers = jax.lax.scan(
            group_body, h, (tuple(params["layers"]), tuple(cache["layers"])))
    h = rmsnorm(h, params["final_norm"]["scale"], cfg.norm_eps)
    logits = unembed(params, h, cfg)
    return logits, {"layers": list(new_layers)}


def verify_tokens(params, cache, tokens, pos0, pages, offs, page_table,
                  cfg: ModelConfig, run: RunConfig):
    """Score S = k+1 tokens per slot in ONE dispatch (speculative
    draft-and-verify).

    tokens: (B, S) int32 — ``tokens[:, 0]`` is the slot's last sampled
    token (its KV line is written now, exactly where the next decode
    step would have written it) and ``tokens[:, 1:]`` are the k drafted
    continuations; pos0: (B,) int32 absolute position of ``tokens[:,
    0]``; pages/offs: (B, S) per-line physical scatter targets (null
    page beyond a slot's allocation / for dead slots); page_table as in
    :func:`decode_step`.

    ``logits[:, j]`` predicts position ``pos0 + j + 1``, so comparing
    ``argmax(logits[:, j])`` against ``tokens[:, j + 1]`` decides
    acceptance of draft j: the longest agreeing run under greedy, or a
    rejection-sampling walk under temperature.  Each row's math is the
    paged decode step's bit for bit (see
    :func:`repro.models.attention.attention_verify`), so greedy
    speculation is bit-identical to sequential decode.

    Returns (logits (B, S, V), new cache).  The stacked pool rides in
    the layer loop's carry like :func:`decode_step`'s.  Full-attention
    configs only (paging already gates SSM/ring out).
    """
    h = embed_tokens(params, tokens, cfg)
    if cfg.pos_embedding == "sinusoidal":
        S = tokens.shape[1]
        posm = pos0[:, None] + jnp.arange(S)[None, :]
        pe = A.sinusoidal_pe(posm, cfg.d_model)            # (B,S,d)
        h = h + pe.astype(h.dtype)
    h = constrain(h, "hidden")

    def attend(p, hh, pool, g, acfg):
        hh, pool = A.attention_verify(p, hh, pool, g, pos0, pages, offs,
                                      page_table, acfg)
        return hh, pool, None

    h, new_cache, _ = _paged_layers(params, cache, h, attend, cfg, run,
                                    name="moe_experts_verify")
    h = rmsnorm(h, params["final_norm"]["scale"], cfg.norm_eps)
    return unembed(params, h, cfg), new_cache


# ------------------------------------------------- fused decode fast path ----

#: token emitted by finished slots inside a decode_n chunk (host drops them)
PAD_TOKEN_ID = 0


def sample_tokens(key, logits, temps):
    """Fused per-slot sampling on device.  logits: (B, V); temps: (B,)
    (0 => greedy).  Splits ``key`` exactly like the host sampler did
    (``categorical`` on ``logits / max(t, 1e-4)``), so host and fused
    paths are bit-identical given the same key stream."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = jnp.maximum(temps, 1e-4)[:, None]
    sampled = jax.random.categorical(
        key, logits.astype(jnp.float32) / t, axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


def decode_n(params, cache, token, pos, remaining, done, eos, temps, key,
             cfg: ModelConfig, run: RunConfig, num_tokens: int,
             cache_len: int, page_table=None, limit=None):
    """Generate up to ``num_tokens`` tokens per slot in ONE dispatch.

    A ``lax.scan`` over ``decode_step`` with sampling and stop handling
    fused on device, so the host syncs (and pays a dispatch) once per
    chunk instead of once per token:

      * sampling — per-slot temperature vector, PRNG key threaded through
        the scan (split once per generated token, matching the host path);
      * stop masking — a slot finishes on EOS, on ``remaining`` hitting 0,
        or at the cache boundary; finished slots emit ``PAD_TOKEN_ID``,
        stop advancing ``pos``/``remaining``, and re-feed their frozen
        final (token, pos) — deterministic, repeated writes confined to
        the finished slot's own cache row (replaced wholesale at the next
        admission), so live slots stay bit-stable.

    Args (all device arrays, B = num_slots):
      token (B,) int32   last sampled token per slot
      pos (B,) int32     absolute position of ``token`` (its KV write index)
      remaining (B,) int32  tokens the slot may still generate
      done (B,) bool     slot finished / empty (frozen for the whole chunk)
      eos (B,) int32     per-slot EOS id, -1 = none
      temps (B,) float32 per-slot sampling temperature, 0 = greedy
      key                PRNG key (consumed; the advanced key is returned)
      page_table (B, n_pages) int32, optional — paged-KV routing
      limit (B,) int32, optional — per-slot cache capacity (paged mode:
        ``allocated_pages * page_size``, so a slot freezes at its own
        allocation boundary instead of the global ``cache_len``; None
        keeps the dense scalar boundary, bit-identical to before)

    Returns ``(tokens (B, N), cache, token, pos, remaining, done, key)``;
    per slot the first ``new_pos - old_pos`` entries of ``tokens`` are
    real, the rest pad.
    """
    boundary = (cache_len - 1) if limit is None else (limit - 1)

    def body(carry, _):
        cache, tok, pos, rem, done, key = carry
        logits, cache = decode_step(params, cache, tok[:, None], pos, cfg,
                                    run, page_table=page_table,
                                    done=done)
        key, sub = jax.random.split(key)
        nxt = sample_tokens(sub, logits[:, 0], temps)
        live = jnp.logical_not(done)
        emit = jnp.where(live, nxt, PAD_TOKEN_ID)
        new_pos = jnp.where(live, pos + 1, pos)
        new_rem = jnp.where(live, rem - 1, rem)
        hit_eos = (eos >= 0) & (nxt == eos)
        new_done = done | (live & (hit_eos | (new_rem <= 0)
                                   | (new_pos >= boundary)))
        new_tok = jnp.where(live, nxt, tok)
        return (cache, new_tok, new_pos, new_rem, new_done, key), emit

    carry = (cache, token, pos, remaining, done, key)
    (cache, token, pos, remaining, done, key), toks = jax.lax.scan(
        body, carry, None, length=num_tokens)
    return toks.T, cache, token, pos, remaining, done, key
