"""Batched decode engine: slot-based continuous batching over a shared KV
cache (the TensorRT-role module from DESIGN.md's assumption log).

A fixed number of *slots* share one batched cache pytree.  Requests queue
behind a multi-tenant :class:`~repro.serving.admission.AdmissionController`;
when a slot frees, the next request is chosen by the same
``2^(-usage/shares)`` fair-share priority the batch scheduler uses, then
prefilled (its cache slice written into the batch cache at the slot index)
and joins the batched decode loop.  Finished sequences (EOS or
max_new_tokens) free their slot immediately — the engine never waits for
the whole batch, which is the throughput property continuous batching
exists for.

The decode hot loop is **device-resident** (the fast path): sampling and
stop handling run inside the jitted step (``models.model.decode_n``), and
one dispatch generates ``decode_chunk`` tokens per slot via ``lax.scan``.
The host syncs ``tokens/pos/remaining/done`` once per chunk, then does
admission / ledger / metrics work exactly as before — so QOS preemption
and fair-share picks happen at chunk boundaries.  ``fused=False`` keeps
the original one-token host loop (reference + benchmark baseline).

Prefill is **bucketed** when ``prefill_buckets`` is set (full-attention,
non-sliding-window configs): prompts pad to the next bucket length so the
jitted prefill compiles once per bucket instead of once per distinct
prompt length, and the cache slice lands in the batch cache through one
pre-jitted donated ``dynamic_update_slice`` insert.

Multi-tenancy rides entirely on the host side: admission picks, GrpTRES
slot caps, QOS preemption (a blocked high request evicts one scavenger
slot; the victim requeues with its partial output retained and resumes
exactly where it stopped), and per-chunk batched ledger charges are all
O(tenants) Python per chunk.

**Paged KV cache** (``kv_page_size > 0``, opt-in): instead of pinning
``cache_len`` dense lines per slot, all slots share one device page pool
(``models.paging``).  A request holds exactly ``ceil(tokens/page_size)``
pages, grows one page at a time at decode-time page boundaries (the host
pre-allocates each chunk's worth before dispatch), and frees everything
back to the pool on finish/evict — so the same HBM budget serves far
more concurrent short requests.  Admission turns page-budget-aware: a
request is only picked when its prefill fits the free pool, GrpTRES can
cap ``kv_pages`` per tenant, and the ledger bills ``kv_pages`` residency
(true HBM held) instead of dense ``kv_tokens``.  Pool exhaustion at
growth time triggers the same one-victim scavenger eviction QOS
preemption uses; if nothing is evictable the starved slot truncates at
its allocation boundary instead of corrupting neighbours.  Greedy fused
decode is bit-identical to the dense cache (the gathered logical view
feeds the exact same masked attention math).

**Prefix cache** (``prefix_cache=True``, needs paging): a radix index
(``serving.prefix``) maps complete prompt-token pages to physical pages.
On admission the engine looks up the longest cached prefix, maps those
pages READ-ONLY into the new request's page table (copy-on-write: refs,
not copies — decode writes only ever land past the shared region on
privately-owned pages) and prefills **only the suffix**
(``models.model.prefill_suffix``, riding the same buckets).  Finished
requests donate their complete prompt pages to the index; under
capacity pressure the index LRU-evicts unpinned prefixes back to the
free pool *before* the scavenger victim path fires.  Shared pages bill
``gres/kv_page`` residency once, amortized across current holders, so
``sshare --tres`` keeps reporting true HBM use, and greedy decode stays
bit-identical to the no-reuse path.

**Continuous batching with chunked prefill** (``max_batch_tokens``,
needs paging + fused): instead of running a whole prompt's prefill as
one blocking dispatch at admission — head-of-line blocking every
decoding slot for the duration — each engine iteration runs ONE fused
step over a token budget that mixes decode lanes (1 token each while
prefills are pending) and prefill *chunks* from a partial-prefill
queue.  Chunked prefill is suffix prefill applied repeatedly
(``models.model.prefill_chunk``): a partially-prefilled request holds
exactly ``ceil(pos_filled/page)`` pages, its next chunk attends the
already-written lines through its page table (line-granular masking, so
chunk boundaries need not be page-aligned), and the chunk's KV lines
scatter mid-page into its pages.  Chunks pad to power-of-two buckets
(compiles O(buckets)); the queue packs shortest-remaining-first within
QOS rank so short interactive prompts cannot queue behind long batch
ones.  Admission/billing integrate at chunk granularity:
``adjust_pages`` grows a partial's GrpTRES holdings chunk-by-chunk
(true holdings, not the worst-case reservation the classic paged path
takes), a mid-prefill request is preemptible at chunk boundaries via
the existing requeue path, and PREFILL trace spans carry
``chunks``/``pos_filled`` attrs.  Greedy output is bit-identical to
whole-prompt prefill; ``serve_stats`` counters feed ``sdiag``'s
serve-step utilization section.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as PSpec

from repro.configs.base import ModelConfig, RunConfig
from repro.models import init_cache, prefill
from repro.models.attention import write_lines, write_pages
from repro.models.moe import MOE_STATS
from repro.models.model import (
    decode_n, decode_step, prefill_chunk, prefill_suffix, verify_tokens,
)
from repro.models.paging import (
    NULL_PAGE, PageAllocator, PagedKVConfig, ShardedAllocatorView,
    TwoLevelPageTable, pages_for,
)
from repro.monitoring import MetricsRegistry, Tracer
from repro.monitoring.trace import phase, stamp
from repro.monitoring.metrics import (
    METRIC_SERVE_KV_PAGES_IN_USE, METRIC_SERVE_PREEMPTIONS,
    METRIC_SERVE_PREFIX_EVICTIONS, METRIC_SERVE_PREFIX_HITS,
    METRIC_SERVE_PREFIX_MISSES, METRIC_SERVE_PREFIX_REUSED_TOKENS,
    METRIC_SERVE_TENANT_ADMITTED, METRIC_SERVE_TENANT_TOKENS,
    METRIC_SPEC_ACCEPT_RATE, METRIC_SPEC_ACCEPTED, METRIC_SPEC_PROPOSED,
)
from repro.serving import tp as tp_mod
from repro.serving.admission import (
    SERVING_TRES_WEIGHTS, AdmissionController,
)
from repro.serving.prefix import PrefixCache
from repro.serving.spec import (
    ModelDraftSource, NgramDraftSource, draft_config, rejection_sample,
)


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (P,) int32
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    temperature: float = 0.0           # 0 => greedy
    tenant: str = "default"            # account in the shared ledger
    qos: str = "normal"                # service tier (see repro.policy.qos)
    user: str = ""                     # optional tenant/user leaf association
    # filled by the engine
    output: list = field(default_factory=list)
    done: bool = False
    preemptions: int = 0               # times evicted mid-decode
    # the wait for the first token: seconds queued before it (every
    # queue wait until then), and from the admission that produced it to
    # it; then the stamps they come from, one per instant
    # (monitoring.trace.stamp: the tracer's clock, else perf_counter)
    queue_s: float = 0.0
    prefill_s: Optional[float] = None
    submitted_at: Optional[float] = None
    queued_at: Optional[float] = None     # None while not queued
    admitted_at: Optional[float] = None   # the latest admission
    _seq: int = field(default=0, repr=False)   # admission arrival order
    _slot: int = field(default=-1, repr=False)  # current decode slot (-1 = none)
    _est_pages: int = field(default=0, repr=False)  # paged: worst-case pages
    # budgeted mode bills TRUE holdings, grown chunk-by-chunk, instead of
    # the classic worst-case reservation
    _held_pages: int = field(default=0, repr=False)
    # lifecycle tracing (populated only when the engine has a tracer)
    _trace: dict = field(default_factory=dict, repr=False)  # open spans
    _t_last: Optional[float] = field(default=None, repr=False)  # last token


@dataclass(eq=False)
class _PartialPrefill:
    """An admitted request whose prompt is not fully prefilled yet.  It
    owns a decode slot (so eviction/requeue ride the existing paths) but
    its decode lane stays frozen — the page-table row the decode dispatch
    sees is all-NULL until promotion — while ``pos_filled`` advances one
    chunk at a time across engine iterations.  Compared by identity: two
    partials are never the same entry of ``_partials`` by value."""
    req: Request
    toks: np.ndarray                    # full resume/prefill token sequence
    slot: int
    pos_filled: int = 0                 # prompt lines already in the pool
    pages: list = field(default_factory=list)   # pages covering pos_filled
    n_shared: int = 0                   # leading prefix-cache pages
    chunks: int = 0                     # chunks dispatched so far
    span: object = None                 # open PREFILL trace span
    admitted_iteration: int = 0         # serve_stats iteration admitting it


@dataclass
class _ChunkPlan:
    """One planned prefill chunk: bucketed tokens plus the per-line page
    scatter targets, ready for dispatch (fused with decode or standalone)."""
    part: _PartialPrefill
    bucket: int                         # padded chunk length (power of two)
    real: int                           # real tokens in the chunk
    start: int                          # == part.pos_filled at plan time
    tokens: np.ndarray                  # (bucket,) int32, zero-padded
    row: np.ndarray                     # (pages_per_seq,) page-table row
    pages: np.ndarray                   # (bucket,) per-line target page
    offs: np.ndarray                    # (bucket,) per-line offset in page


class DecodeEngine:
    def __init__(self, cfg: ModelConfig, params, num_slots: int = 8,
                 cache_len: int = 1024, run: Optional[RunConfig] = None,
                 metrics: Optional[MetricsRegistry] = None, seed: int = 0,
                 admission: Optional[AdmissionController] = None,
                 decode_chunk: int = 1, fused: bool = True,
                 prefill_buckets: Union[None, str, Sequence[int]] = None,
                 kv_page_size: int = 0,
                 kv_pages: Optional[int] = None,
                 prefix_cache: bool = False,
                 max_batch_tokens: Optional[int] = None,
                 tracer: Optional[Tracer] = None,
                 speculate: int = 0,
                 spec_source: str = "ngram",
                 draft_model: Optional[ModelConfig] = None,
                 index_generated: Optional[bool] = None,
                 mesh=None, device=None):
        assert mesh is None or device is None, \
            "a TP engine is placed by its mesh; pass mesh or device"
        self.cfg = cfg
        self.params = params
        self.run = run or RunConfig(remat="none")
        # ---- tensor parallelism (mesh=None -> single-shard, zero cost) ----
        # resolved up front: the paged pool view and every jitted builder
        # below depend on the plan
        self.tp = tp_mod.plan_tp(cfg, mesh)
        for note in self.tp.notices:
            print(f"[serve] tp: {note}")
        self._pp = tp_mod.param_pspecs(cfg, self.tp) if self.tp.active \
            else None
        self.num_slots = num_slots
        self.cache_len = cache_len
        self.metrics = metrics or MetricsRegistry()
        self.admission = admission if admission is not None \
            else AdmissionController()
        # request-lifecycle tracer (opt-in; None = zero overhead).  The
        # admission controller writes QUEUED spans / queue-wait series
        # into the same tracer unless it already has its own.
        self.tracer = tracer
        if tracer is not None and self.admission.tracer is None:
            self.admission.tracer = tracer
        self.decode_chunk = max(1, int(decode_chunk))
        self.fused = fused
        self.paging = self._resolve_paging(kv_page_size, kv_pages)
        if cfg.layer_types is not None and self.paging is None:
            raise ValueError(
                "cfg.layer_types: per-layer attention kinds serve on the "
                "paged path only (kv_page_size > 0); the classic cache "
                "sizes one ring for every layer")
        # the engine's cache (the paged pool, or dense rows) and the
        # one-request slices scattered into it carry kv heads at their
        # own dims, so each gets its own spec
        self._cc = tp_mod.cache_pspec(
            self.tp, cfg, paging=self.paging is not None) \
            if self.tp.active else None
        self._cs = tp_mod.cache_pspec(self.tp, cfg) if self.tp.active \
            else None
        if self.paging is not None:
            # one page bills like the lines it holds, keeping fair-share
            # comparable across page sizes and with dense engines on the
            # same ledger; setdefault so an operator-set weight wins
            w = self.admission.tree.tres_weights
            w.setdefault("gres/kv_page", self.paging.page_size *
                         w.get("gres/kv_token",
                               SERVING_TRES_WEIGHTS["gres/kv_token"]))
            self.allocator = PageAllocator(self.paging.num_pages)
            # one logical page id = one page slice per shard (TP shards
            # the pool along kv heads); admission consumes the per-shard
            # budget vectors, not the scalar
            self.pool_view = ShardedAllocatorView(
                self.allocator,
                shards=(self.tp.tp if self.tp.active and self.tp.shard_attn
                        else 1))
            # two-level (directory, leaf) page map: host memory scales
            # with pages actually mapped, not slots * pages_per_seq
            self._ptab = TwoLevelPageTable(num_slots,
                                           self.paging.pages_per_seq)
            #: dispatch-width bucket for the classic paged mode (grows
            #: monotonically in powers of two, so the decode programs
            #: recompile O(log pages_per_seq) times)
            self._table_width = 1
            self._slot_pages: list[list[int]] = [[] for _ in
                                                 range(num_slots)]
        self.prefix: Optional[PrefixCache] = None
        if prefix_cache:
            if self.paging is None:
                raise ValueError(
                    "prefix_cache=True needs the paged KV cache: pass "
                    "kv_page_size > 0 (CLI: --prefix-cache implies "
                    "--kv-paging)")
            self.prefix = PrefixCache(self.allocator, self.paging.page_size)
            # active-request holders per physical page, for amortized
            # residency billing (a page shared by h requests bills 1/h
            # to each, so the ledger charges true HBM once)
            self._page_holders: dict[int, int] = {}
            # radix tie-break: when fair-share priorities tie exactly,
            # admission prefers the head whose prompt hits the index
            # (its prefill is mostly cached).  A controller the caller
            # pre-wired keeps its own probe.
            if self.admission.radix_probe is None:
                self.admission.radix_probe = (
                    lambda r: bool(self.prefix.match(
                        self._resume_tokens(r))))
        self.cache = init_cache(cfg, num_slots, cache_len,
                                paging=self.paging)
        if cfg.moe is not None and self.paging is not None \
                and not self.tp.active:
            # the expert layers' counters ride in the cache pytree, which
            # every paged program carries and returns: they add up on the
            # device and are read only with ``serve_stats``
            self.cache["moe_stats"] = jnp.zeros(len(MOE_STATS), jnp.int32)
        if self.tp.active:
            # place params and the KV pool on the mesh: attention weights
            # and cache split along (kv) heads, MLP along d_ff, everything
            # else (embed/lm_head/norms) replicated so every shard holds
            # full logits and sampling needs no collective
            self.params = jax.device_put(
                self.params, tp_mod.param_shardings(cfg, self.tp))
            self.cache = jax.device_put(
                self.cache, tp_mod.cache_shardings(
                    self.cache, self.tp, cfg,
                    paging=self.paging is not None))
        elif device is not None:
            # one replica per device: committed params and pool pull every
            # jitted program of this engine onto ``device``
            self.params = jax.device_put(self.params, device)
            self.cache = jax.device_put(self.cache, device)
        self.slots: list[Optional[Request]] = [None] * num_slots
        self.pos = np.zeros(num_slots, np.int64)       # next position per slot
        self.last_tok = np.zeros(num_slots, np.int32)
        self.remaining = np.zeros(num_slots, np.int64)
        self._key = jax.random.PRNGKey(seed)
        self._buckets = self._resolve_buckets(prefill_buckets)
        self._step = self._build_step()
        self._decode_n = self._build_decode_n()
        self._insert = self._build_insert()
        self._prefill_fn = self._build_prefill()
        self._suffix_prefill_fn = (self._build_suffix_prefill()
                                   if self.prefix is not None else None)
        # ---- continuous batching (token-budgeted serve step) ----
        # always defined (empty in classic mode) so the shared eviction /
        # step paths need no mode guards
        self._partials: list[_PartialPrefill] = []
        self._prefill_slots: dict[int, _PartialPrefill] = {}
        #: per-iteration counters behind sdiag's serve-step utilization
        #: (``serve_stats``); ``iterations`` counts every ``step``,
        #: ``host_s`` its time outside its ``engine.sync`` phases
        self._serve_stats = {"iterations": 0, "decode_tokens": 0,
                             "prefill_tokens": 0, "prefill_chunks": 0,
                             "host_s": 0.0}
        self._synced = 0.0              # seconds in engine.sync so far
        self.max_batch_tokens: Optional[int] = None
        self._mixed_step = None
        if max_batch_tokens is not None:
            if self.paging is None:
                raise ValueError(
                    "max_batch_tokens: continuous batching with chunked "
                    "prefill needs the paged KV cache — a partial prefill "
                    "holds ceil(pos_filled/page) pages, which the dense "
                    "per-slot layout cannot express.  Pass kv_page_size "
                    "> 0 (CLI: --max-batch-tokens implies --kv-paging)")
            if not fused:
                raise ValueError(
                    "max_batch_tokens: the token-budgeted serve step "
                    "fuses decode and prefill chunks into one dispatch, "
                    "which needs fused=True (the host per-token loop has "
                    "no budgeted equivalent)")
            self.max_batch_tokens = int(max_batch_tokens)
            assert self.max_batch_tokens >= 1, max_batch_tokens
            b, buckets = 1, []
            while b <= self.max_batch_tokens:
                buckets.append(b)
                b *= 2
            # ascending: _plan_chunk picks the smallest bucket covering
            # the remaining prompt that still fits the budget
            self.chunk_buckets = tuple(buckets)
            # mixed iterations decode 1 token/lane; reuse the chunked
            # program when decode_chunk is already 1
            self._decode_n1 = (self._decode_n if self.decode_chunk == 1
                               else self._build_decode_n(1))
            self._chunk_fn = self._build_chunk_prefill()
            self._mixed_step = self._build_mixed_step()
        # ---- speculative decoding (draft-and-verify in the chunk) ----
        self.speculate = int(speculate)
        self.spec = None
        self._verify_fn = None
        self._mixed_verify = None
        #: per-round speculation counters behind sdiag's speculation
        #: section (rounds = verify dispatches; accepted counts draft
        #: tokens the target agreed with, excluding correction/bonus)
        self.spec_stats = {"rounds": 0, "proposed": 0, "accepted": 0,
                           "emitted": 0, "proposed_by": {}}
        self._spec_rng = np.random.default_rng(seed)
        if self.speculate:
            if self.paging is None:
                raise ValueError(
                    "speculate: draft-and-verify writes each proposal's "
                    "KV line through per-line (page, offset) scatter "
                    "targets and relies on rejected lines dying on the "
                    "null page — pass kv_page_size > 0 (CLI: --speculate "
                    "implies --kv-paging)")
            if not fused:
                raise ValueError(
                    "speculate: verification is one batched dispatch "
                    "over the fused decode lanes, which needs fused=True")
            if spec_source == "model":
                dcfg = draft_model if draft_model is not None \
                    else draft_config(cfg)
                self.spec = ModelDraftSource(dcfg, num_slots, cache_len,
                                             seed=seed, run=self.run)
            elif spec_source == "ngram":
                self.spec = NgramDraftSource()
            else:
                raise ValueError(f"unknown spec_source {spec_source!r} "
                                 "(expected 'ngram' or 'model')")
            self._verify_fn = self._build_verify()
            if self.max_batch_tokens is not None:
                self._mixed_verify = self._build_mixed_verify()
        #: index finished requests' complete generated-token pages into
        #: the radix trie (cross-request reuse of generated tokens).
        #: Defaults on exactly when speculation can mine them, so
        #: non-speculative engines keep pool accounting bit-identical.
        if index_generated is None:
            index_generated = bool(self.speculate) \
                and self.prefix is not None
        self.index_generated = bool(index_generated)

    @property
    def serve_stats(self) -> dict:
        """Additive counters: the serve step's, and on the paged path of a
        model with experts the expert layers' (``MOE_STATS``), which are
        read from the device here and only here."""
        if "moe_stats" in self.cache:
            self._serve_stats.update(zip(
                MOE_STATS, np.asarray(self.cache["moe_stats"]).tolist()))
        return self._serve_stats

    def _resolve_paging(self, kv_page_size: int,
                        kv_pages: Optional[int]) -> Optional[PagedKVConfig]:
        """Paged layout, or None (dense default).  Paging needs a cache
        without position-dependent physical layout: full attention (no
        SSM state to page) and no window shorter than the cache (a ring).
        ``kv_pages``
        overrides the pool size; the default matches the dense HBM
        budget (num_slots * cache_len lines) plus the null page, so
        dense and paged engines are HBM-comparable out of the box."""
        if not kv_page_size:
            return None
        # name the offending config field: "full-attention only" alone
        # sends operators hunting through the whole ModelConfig
        if self.cfg.ssm is not None:
            raise ValueError(
                "kv_page_size: paged KV cache needs a full-attention "
                f"config, but cfg.ssm={self.cfg.ssm!r} — SSM recurrent "
                "state is not line-addressable, so it cannot be paged")
        if self.cfg.attn_every != 1:
            raise ValueError(
                "kv_page_size: paged KV cache needs a full-attention "
                f"config, but cfg.attn_every={self.cfg.attn_every} "
                "interleaves non-attention sublayers whose state has no "
                "page layout")
        window = self.cfg.sliding_window
        if window is not None and self.cache_len > window:
            # a window no shorter than the cache changes nothing, so such
            # layers page as full attention (models.model.layer_config)
            raise ValueError(
                "kv_page_size: paged KV cache does not support "
                f"cfg.sliding_window={window} with cache_len="
                f"{self.cache_len}, where the window drops lines — "
                "windowed layers need rings of pages that free the pages "
                "behind the window (ROADMAP Reach item 1)")
        assert self.cache_len % kv_page_size == 0, \
            (self.cache_len, kv_page_size)
        if kv_pages is not None:
            assert kv_pages >= 2, "pool needs the null page + 1 usable page"
            return PagedKVConfig(page_size=kv_page_size, num_pages=kv_pages,
                                 pages_per_seq=self.cache_len // kv_page_size)
        return PagedKVConfig.for_budget(self.num_slots * self.cache_len,
                                        kv_page_size, self.cache_len)

    # -------------------------------------------------------- page table ----
    @property
    def page_tables(self) -> np.ndarray:
        """Dense (num_slots, pages_per_seq) logical->physical view of the
        two-level table (tests/diagnostics; dispatches use the bucketed
        :meth:`_dispatch_table`)."""
        return self._ptab.dense()

    def _dispatch_table(self) -> np.ndarray:
        """The page table a decode/verify dispatch sees.  Budgeted mode
        pins the full ``pages_per_seq`` width — its compile-count
        invariant (``chunk_compilations() <= 2 * buckets``) admits no
        per-width retraces.  Classic paged mode buckets the width to a
        monotonically-growing power of two covering every live mapping,
        so short requests dispatch small gathers and the programs
        recompile O(log pages_per_seq) times over the engine's life."""
        if self.max_batch_tokens is not None:
            return self._ptab.dense()
        w = max(self._ptab.max_width(), 1)
        while self._table_width < w:
            self._table_width *= 2
        self._table_width = min(self._table_width,
                                self.paging.pages_per_seq)
        return self._ptab.dense(self._table_width)

    # ------------------------------------------------------------ jitted ----
    # Each builder jits a function whose name is unique in the engine: a
    # profiler trace names the program after it (``jit_<name>``).
    def _tp_wrap(self, fn, in_kinds: str, out_kinds: str,
                 donate: tuple = ()):
        """``tp.wrap`` with per-argument specs named by kind: ``p`` the
        params pytree, ``c`` the engine's cache (the paged pool, kv heads
        at dim 2, or dense rows, at dim 3), ``s`` one-request cache
        slices (kv heads at dim 3), ``r`` replicated.  Inactive plans
        compile to a plain ``jax.jit`` with identical semantics."""
        if not self.tp.active:
            return tp_mod.wrap(self.tp, fn, (), (), donate)
        m = {"p": self._pp, "c": self._cc, "s": self._cs, "r": PSpec()}
        ins = tuple(m[k] for k in in_kinds)
        outs = (m[out_kinds] if len(out_kinds) == 1
                else tuple(m[k] for k in out_kinds))
        return tp_mod.wrap(self.tp, fn, ins, outs, donate)

    def _build_step(self):
        cfg, run = self.cfg, self.run

        if self.paging is not None:
            def step_paged(params, cache, token, pos, page_table):
                logits, cache = decode_step(params, cache, token, pos, cfg,
                                            run, page_table=page_table)
                return logits[:, 0], cache

            return self._tp_wrap(step_paged, "pcrrr", "rc")

        def step(params, cache, token, pos):
            # per-slot positions: (B,) — decode_step handles scalar or vector
            logits, cache = decode_step(params, cache, token, pos, cfg, run)
            return logits[:, 0], cache

        return self._tp_wrap(step, "pcrr", "rc")

    def _build_decode_n(self, chunk: Optional[int] = None):
        cfg, run = self.cfg, self.run
        cache_len = self.cache_len
        chunk = self.decode_chunk if chunk is None else chunk

        if self.paging is not None:
            def step_n_paged(params, cache, token, pos, remaining, done,
                             eos, temps, key, page_table, limit):
                return decode_n(params, cache, token, pos, remaining, done,
                                eos, temps, key, cfg, run, chunk, cache_len,
                                page_table=page_table, limit=limit)

            return self._tp_wrap(step_n_paged, "pc" + "r" * 9, "rcrrrrr",
                                 donate=(1,))

        def step_n(params, cache, token, pos, remaining, done, eos, temps,
                   key):
            return decode_n(params, cache, token, pos, remaining, done, eos,
                            temps, key, cfg, run, chunk, cache_len)

        return self._tp_wrap(step_n, "pc" + "r" * 7, "rcrrrrr",
                             donate=(1,))

    def _build_insert(self):
        if self.paging is not None:
            ps = self.paging.page_size

            def insert_paged(pool_cache, one_cache, page_ids):
                # scatter the request's prefilled lines into its pages;
                # pad-tail pages ride on the null page (id 0), whose
                # garbage is never read unmasked
                def put(pool_leaf, one_leaf):
                    length = one_leaf.shape[2]
                    n = page_ids.shape[0]
                    lines = one_leaf[:, 0]
                    if n * ps > length:
                        lines = jnp.pad(
                            lines, ((0, 0), (0, n * ps - length),
                                    (0, 0), (0, 0)))
                    return write_pages(pool_leaf, page_ids, lines)
                return DecodeEngine._merge(pool_cache, one_cache, put)

            return self._tp_wrap(insert_paged, "csr", "c", donate=(0,))

        def insert(batch_cache, one_cache, slot):
            def put(batch_leaf, one_leaf):
                return jax.lax.dynamic_update_slice_in_dim(
                    batch_leaf, one_leaf.astype(batch_leaf.dtype), slot,
                    axis=1)
            return jax.tree.map(put, batch_cache, one_cache)

        return self._tp_wrap(insert, "csr", "c", donate=(0,))

    def _build_prefill(self):
        cfg, run, cache_len = self.cfg, self.run, self.cache_len
        # paged mode: prefill only materializes the prompt's own lines
        # (cache_len=None -> S slots); the page scatter does the placement
        paged = self.paging is not None

        if getattr(self, "_front_pad", False):
            # SSM/hybrid bucketed prefill: real tokens sit at a traced
            # chunk-aligned front offset, so one program per bucket
            # serves every prompt length (front_pad/num_real are traced)
            def prefill_front_fn(params, tokens, front_pad, num_real,
                                 last_pos):
                return prefill(params, {"tokens": tokens}, cfg, run,
                               cache_len=cache_len, last_pos=last_pos,
                               front_pad=front_pad, num_real=num_real)

            return self._tp_wrap(prefill_front_fn, "prrrr", "rs")

        def prefill_fn(params, tokens, last_pos):
            return prefill(params, {"tokens": tokens}, cfg, run,
                           cache_len=None if paged else cache_len,
                           last_pos=last_pos)

        return self._tp_wrap(prefill_fn, "prr", "rs")

    def _build_suffix_prefill(self):
        """Jitted suffix prefill for prefix-cache hits: compiles once per
        (bucketed) suffix length; ``start`` and the page table are traced
        so any prefix depth reuses the same program."""
        cfg, run = self.cfg, self.run

        def suffix_fn(params, cache, tokens, page_table, start, last_pos):
            return prefill_suffix(params, {"tokens": tokens}, cache,
                                  page_table, start, cfg, run,
                                  last_pos=last_pos)

        return self._tp_wrap(suffix_fn, "pcrrrr", "rs")

    @staticmethod
    def _scatter_chunk(cache, slices, pages, offs):
        """Write a chunk's KV lines into the pool at per-line
        (page, offset) targets.  Unlike the whole-page admission insert,
        a chunk may start and end mid-page, so the write is
        line-granular; pad lines target the null page (harmless
        duplicate writes).  Traced inside the chunk/mixed programs, on
        the donated pool: a scatter of just these lines, in place."""
        def put(pool_leaf, one_leaf):
            layers = jnp.arange(pool_leaf.shape[0])[:, None]
            return write_lines(pool_leaf, layers, pages[None], offs[None],
                               one_leaf[:, 0])             # (G,C,K,Dh)
        return DecodeEngine._merge(cache, slices, put)

    @staticmethod
    def _merge(pool, slices, put):
        """``pool`` with ``put(pool leaf, slice leaf)`` over its layers,
        and the expert counters the slices' program ended with."""
        out = dict(pool, layers=jax.tree.map(put, pool["layers"],
                                             slices["layers"]))
        if "moe_stats" in slices:
            out["moe_stats"] = slices["moe_stats"]
        return out

    def _build_chunk_prefill(self):
        """Jitted standalone prefill chunk (budgeted mode): compute the
        chunk against the pool, scatter its lines back, return (logits,
        cache) — ONE dispatch per chunk.  Compiles once per chunk bucket:
        ``start``/``last_pos`` and the page-table row (always
        ``pages_per_seq`` wide) are traced, so every chunk of every
        request at every depth reuses the same O(buckets) programs."""
        cfg, run = self.cfg, self.run

        def chunk_fn(params, cache, tokens, page_table, start, last_pos,
                     pages, offs):
            logits, slices = prefill_chunk(
                params, {"tokens": tokens}, cache, page_table, start, cfg,
                run, last_pos=last_pos)
            return logits, DecodeEngine._scatter_chunk(
                cache, slices, pages, offs)

        return self._tp_wrap(chunk_fn, "pc" + "r" * 6, "rc", donate=(1,))

    def _build_mixed_step(self):
        """THE budgeted serve step: one dispatch running a prefill chunk
        (compute + line scatter) and a full ``decode_chunk``-token decode
        over every live lane — streaming a prefill must not drop decode
        lanes to 1 token/dispatch.  The chunk reads the pre-decode,
        pre-scatter pool state and its pages are disjoint from the
        lanes' write targets, so fusing changes no math — greedy output
        stays bit-identical to running the dispatches back-to-back."""
        cfg, run, cache_len = self.cfg, self.run, self.cache_len
        num_tokens = self.decode_chunk

        def mixed(params, cache, token, pos, remaining, done, eos, temps,
                  key, page_table, limit, c_tokens, c_row, c_start,
                  c_last, c_pages, c_offs):
            c_logits, c_slices = prefill_chunk(
                params, {"tokens": c_tokens}, cache, c_row, c_start, cfg,
                run, last_pos=c_last)
            cache = DecodeEngine._scatter_chunk(
                cache, c_slices, c_pages, c_offs)
            out = decode_n(params, cache, token, pos, remaining, done,
                           eos, temps, key, cfg, run, num_tokens,
                           cache_len, page_table=page_table, limit=limit)
            return out + (c_logits,)

        return self._tp_wrap(mixed, "pc" + "r" * 15, "rcrrrrrr",
                             donate=(1,))

    def _build_verify(self):
        """Jitted speculative verification: score ``last_tok`` plus up to
        ``speculate`` drafts per lane in ONE dispatch.  Row ``j``'s
        logits are bitwise-identical to a sequential ``decode_step`` at
        position ``pos0+j``, so the device argmax returned here IS the
        greedy token stream — accepting the longest agreeing run keeps
        greedy output bit-identical to non-speculative decode.  Raw
        logits ride along for temperature-mode rejection sampling."""
        cfg, run = self.cfg, self.run

        def verify(params, cache, tokens, pos0, pages, offs, page_table):
            logits, cache = verify_tokens(params, cache, tokens, pos0,
                                          pages, offs, page_table, cfg,
                                          run)
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                    logits, cache)

        return self._tp_wrap(verify, "pc" + "r" * 5, "rrc", donate=(1,))

    def _build_mixed_verify(self):
        """Budgeted serve step with speculation: one dispatch running a
        prefill chunk (compute + line scatter) and the speculative verify
        over every live lane — same fusion (and same disjoint-pages
        argument) as ``_build_mixed_step``, with verify in the decode
        role."""
        cfg, run = self.cfg, self.run

        def mixed_verify(params, cache, tokens, pos0, pages, offs,
                         page_table, c_tokens, c_row, c_start, c_last,
                         c_pages, c_offs):
            c_logits, c_slices = prefill_chunk(
                params, {"tokens": c_tokens}, cache, c_row, c_start, cfg,
                run, last_pos=c_last)
            cache = DecodeEngine._scatter_chunk(
                cache, c_slices, c_pages, c_offs)
            logits, cache = verify_tokens(params, cache, tokens, pos0,
                                          pages, offs, page_table, cfg,
                                          run)
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                    logits, cache, c_logits)

        return self._tp_wrap(mixed_verify, "pc" + "r" * 11, "rrcr",
                             donate=(1,))

    def _resolve_buckets(self, spec):
        """Power-of-two prompt-length buckets, or None (exact-length
        prefill).  Full-attention configs pad the prompt TAIL (causal
        masking keeps pads out of real state).  SSM/hybrid configs pad
        the FRONT instead (``models.model.prefill`` front-pad mode): the
        pad lands at a chunk-aligned offset whose masked positions are
        the SSD scan's identity, so the recurrent state stays
        bit-identical to the exact path.  Still refused — silently
        degrading to exact prefill — for sliding-window ring caches (the
        wrapped slot layout has no pad region), sinusoidal embeddings
        (added before the front shift is known), and Pallas prefill (the
        fused kernels take no validity mask)."""
        self._front_pad = False
        if not spec:
            return None
        if self.cfg.sliding_window is not None:
            return None
        attn_only = self.cfg.attn_every == 1 and self.cfg.ssm is None
        if not attn_only:
            if self.cfg.pos_embedding == "sinusoidal" or self.run.use_pallas:
                return None
            self._front_pad = True
        if spec == "auto":
            out, b = [], 32
            while b < self.cache_len:
                out.append(b)
                b *= 2
            out.append(self.cache_len)
            return tuple(out)
        out = tuple(sorted({int(b) for b in spec}))
        assert out and 0 < out[0] and out[-1] <= self.cache_len, out
        if out[-1] < self.cache_len:       # any resume prompt must fit
            out = out + (self.cache_len,)
        return out

    @property
    def prefill_buckets(self):
        return self._buckets

    def prefill_compilations(self) -> int:
        """Distinct prefill programs compiled so far — one per bucket on
        the bucketed path.  The exact-length path runs the eager
        (unjitted) prefill and never touches this cache, so it reports
        0 there."""
        return int(self._prefill_fn._cache_size())

    def chunk_compilations(self) -> int:
        """Distinct chunked-prefill programs compiled so far (budgeted
        mode): one per chunk bucket for the standalone dispatch plus one
        per bucket for the fused decode+chunk step — O(buckets), never
        O(prompt lengths x depths)."""
        if self.max_batch_tokens is None:
            return 0
        return (int(self._chunk_fn._cache_size())
                + int(self._mixed_step._cache_size()))

    def tp_stats(self) -> dict:
        """Tensor-parallel shard stats behind ``sdiag``'s TP section:
        the resolved plan, per-device page-pool occupancy, and the
        cross-shard reduction count one decode token pays."""
        plan = self.tp
        out = {"tp": plan.tp,
               "active": plan.active,
               "plan": plan.describe(self.cfg),
               "devices": [str(d) for d in plan.devices()],
               "notices": list(plan.notices),
               "psums_per_token": plan.psums_per_token(self.cfg)}
        if self.paging is not None:
            out["kv_pages_in_use"] = [
                int(n) for n in self.pool_view.in_use_vector()]
            out["kv_pages_total"] = self.paging.usable_pages
        return out

    def _update_pool_gauges(self):
        """Per-device ``serve_kv_pages_in_use`` gauges (one series per
        shard; single-shard engines report the default device)."""
        if self.paging is None:
            return
        g = self.metrics.gauge(
            METRIC_SERVE_KV_PAGES_IN_USE,
            "KV pages with >= 1 holder, per device")
        devs = self.tp.devices() or jax.devices()[:1]
        for k, n in enumerate(self.pool_view.in_use_vector()):
            dev = devs[k] if k < len(devs) else f"shard{k}"
            g.set(int(n), device=str(dev))

    # ----------------------------------------------------------- tracing ----
    @contextmanager
    def _sync(self):
        """A blocking device->host read: the ``engine.sync`` phase, timed
        so that ``step`` can leave it out of ``host_s``."""
        t0 = time.perf_counter()
        with phase("engine.sync", self.tracer):
            yield
        self._synced += time.perf_counter() - t0

    def _token_known(self, req: Request, resume: bool) -> float:
        """Stamp the instant a prefill's token is known; a first token
        (not a resume's) ends the request's prefill wait."""
        now = stamp(self.tracer)
        if not resume:
            req.prefill_s = now - req.admitted_at
        return now

    def _trace_root(self, req: Request):
        trace = getattr(req, "_trace", None)
        return trace.get("root") if trace else None

    def _trace_decode_end(self, req: Request, reason: Optional[str] = None):
        """Close the request's current DECODE span (finish, preemption,
        or page starvation); ``reason`` also lands as a root-span event,
        so PREEMPT/STARVED transitions are visible on the timeline."""
        tr = self.tracer
        if tr is None:
            return
        trace = getattr(req, "_trace", None)
        if not trace:
            return
        dec = trace.pop("decode", None)
        if dec is not None:
            tr.end(dec, tokens=len(req.output),
                   **({"stop": reason} if reason else {}))
        root = trace.get("root")
        if root is not None and reason:
            tr.event(reason, root)

    # ------------------------------------------------------------ public ----
    def submit(self, req: Request):
        # generation past the cache boundary truncates in _maybe_finish,
        # which also guarantees a preemption victim's resume prefill
        # (prompt + partial output) still fits the cache
        assert len(req.prompt) < self.cache_len, "prompt exceeds cache"
        tr = self.tracer
        req.submitted_at = stamp(tr)
        if tr is not None:
            root = tr.begin(
                f"request {req.rid}", cat="request",
                track=(f"serving:{req.tenant}", f"req {req.rid}"),
                ts=req.submitted_at, rid=req.rid, tenant=req.tenant,
                qos=req.qos, prompt_tokens=len(req.prompt),
                max_new_tokens=req.max_new_tokens)
            req._trace = {"root": root}
            tr.event("SUBMIT", root, ts=req.submitted_at)
        if self.paging is not None:
            # worst-case page footprint, for GrpTRES kv_pages caps
            req._est_pages = pages_for(
                min(len(req.prompt) + req.max_new_tokens + 1,
                    self.cache_len), self.paging.page_size)
            # a footprint the pool can never hold would queue forever
            # (page-budget admission keeps vetoing it): refuse loudly
            assert req._est_pages <= self.paging.usable_pages, \
                (f"request {req.rid}: needs {req._est_pages} pages, pool "
                 f"has {self.paging.usable_pages}")
        self.admission.submit(req)

    def active(self) -> int:
        """Requests currently holding a decode slot."""
        return sum(r is not None for r in self.slots)

    def _capacity(self, slot: int) -> int:
        """KV lines slot may write before growing (paged) / cache_len."""
        if self.paging is None:
            return self.cache_len
        return len(self._slot_pages[slot]) * self.paging.page_size

    def _resume_tokens(self, req) -> np.ndarray:
        """The token sequence a (possibly resumed) request prefills:
        prompt plus retained partial output, minus the last token (which
        re-decodes)."""
        if req.output:
            return np.concatenate(
                [req.prompt, np.asarray(req.output[:-1], np.int32)])
        return np.asarray(req.prompt, np.int32)

    def _fits_pages(self, req) -> bool:
        """Page-budget admission predicate: the resume/prefill pages must
        fit the free pool right now (decode growth is handled later).
        With the prefix cache, cached prefix pages cost nothing and
        LRU-evictable cached pages count as free — so a request whose
        prompt is mostly cached admits into a pool that looks full."""
        toks = self._resume_tokens(req)
        need = pages_for(len(toks), self.paging.page_size)
        # per-shard budget vector: a logical page is grantable only when
        # EVERY shard can hold its slice, so admission gates on the min
        budget = self.pool_view.min_available()
        if self.prefix is not None and need > budget:
            # matched pages cost nothing, and evictable cached pages
            # count as free — EXCLUDING the match itself: placement pins
            # it before evicting, so a page cannot serve as both shared
            # mapping and eviction fodder (counting it twice would admit
            # requests that then bounce off allocation forever)
            matched = len(self.prefix.match(toks))
            need -= matched
            budget += max(self.prefix.evictable_pages() - matched, 0)
        return need <= budget

    def pending(self) -> int:
        return self.admission.pending()

    def load(self) -> int:
        """Queue depth: slot holders plus queued requests — the router's
        spill signal and the autoscaler's emptiest-replica criterion."""
        return self.active() + self.pending()

    def radix_occupancy(self) -> dict:
        """Prefix-index occupancy for the router/sdiag surface: cached
        pages currently indexed and how many of them are evictable."""
        if self.prefix is None:
            return {"nodes": 0, "evictable_pages": 0}
        return {"nodes": self.prefix.nodes,
                "evictable_pages": self.prefix.evictable_pages()}

    def drain(self) -> list:
        """Evict everything and hand it back: each in-flight request
        leaves through the preemption path (pages released, slot hold
        returned, partial output retained — mid-prefill partials included,
        since partials hold their slot too), then every queued request is
        popped.  Returns all of them in arrival order, ready to resubmit
        elsewhere; greedy decode is batch-independent, so a drained
        request finishes bit-identical on whichever replica resumes it."""
        for req in [r for r in self.slots if r is not None]:
            self._evict(req)
        drained = []
        for t in self.admission.tenants.values():
            drained.extend(t.queue)
            t.queue.clear()
        drained.sort(key=lambda r: r._seq)
        return drained

    @property
    def queue(self) -> list:
        """Flattened view of all tenant queues (compat/diagnostics)."""
        return [r for t in self.admission.tenants.values() for r in t.queue]

    def _free_slots(self):
        return [i for i, r in enumerate(self.slots) if r is None]

    def _admit(self):
        """Fill free slots from the admission controller; then let blocked
        high-QOS requests preempt one preemptable slot each.  In paged
        mode the pick is additionally gated on the prefill fitting the
        free page pool (page-budget admission)."""
        eligible = self._fits_pages if self.paging is not None else None
        for slot in self._free_slots():
            req = self.admission.next_request(eligible=eligible)
            if req is None:
                return
            self._place(slot, req)
        # QOS preemption: each blocked preempting request evicts exactly
        # one victim slot (bounded per pass against cyclic QOS tables)
        for _ in range(self.num_slots):
            running = [r for r in self.slots if r is not None]
            pick = self.admission.next_preempting(running)
            if pick is None:
                return
            req, victim = pick
            slot = self._evict(victim)
            self._place(slot, req)

    def _place(self, slot: int, req: Request):
        """Route an admitted request: budgeted mode enqueues a partial
        prefill (chunked across iterations), classic mode prefills the
        whole prompt in one blocking dispatch."""
        if self.max_batch_tokens is not None:
            self._start_prefill(slot, req)
        else:
            self._prefill_into(slot, req)

    def _alloc_or_evict(self, need: int):
        """Allocate ``need`` pages, LRU-evicting unpinned cached prefixes
        to make room when the free pool is short (the capacity-pressure
        valve that fires BEFORE scavenger preemption)."""
        got = self.allocator.alloc(need)
        if got is None and self.prefix is not None:
            freed = self.prefix.evict(need - self.pool_view.min_available())
            if freed:
                self.metrics.counter(
                    METRIC_SERVE_PREFIX_EVICTIONS,
                    "cached prefix pages LRU-evicted").inc(freed)
                got = self.allocator.alloc(need)
        return got

    def _prefill_into(self, slot: int, req: Request):
        """Prefill a request into a free slot.  A preempted request
        resumes: its prompt *and* retained partial output are prefilled,
        so decode continues from exactly where the eviction stopped.

        Paged mode allocates exactly ``ceil(len(toks)/page_size)`` pages
        first (the bucketed pad tail allocates and charges NOTHING — it
        scatters onto the null page) and bails back to the queue if the
        pool cannot hold the prefill.  With the prefix cache, the longest
        cached prefix maps read-only (one allocator ref per page), only
        the suffix allocates/prefills, and the request's complete prompt
        pages join the radix index afterwards."""
        toks = self._resume_tokens(req)
        tr = self.tracer
        root = self._trace_root(req)
        resume = bool(req.output)
        psp = None
        if tr is not None:
            if root is not None:
                tr.event("ADMIT", root, slot=slot)
            psp = tr.begin("PREFILL", cat="prefill", parent=root,
                           ts=req.admitted_at, tokens=len(toks),
                           resume=resume)
        pages = shared = None
        if self.paging is not None:
            ps = self.paging.page_size
            if self.prefix is not None:
                # acquire BEFORE the private alloc: matched pages are
                # unpinned until then, and the eviction below must not
                # free what we are about to map
                shared = self.prefix.acquire(self.prefix.match(toks))
            n_shared = len(shared) if shared else 0
            priv = self._alloc_or_evict(
                pages_for(len(toks), ps) - n_shared)
            if priv is None and shared:
                # the shortfall may only be coverable by the matched
                # pages themselves: abandon the reuse (unpin, making the
                # match eviction fodder) and retry as a plain prefill —
                # correctness beats sharing
                self.allocator.free(shared)
                shared, n_shared = [], 0
                priv = self._alloc_or_evict(pages_for(len(toks), ps))
            if priv is None:
                # preemption admitted past the page gate but the pool
                # still can't hold the prefill: back to the queue
                if shared:
                    self.allocator.free(shared)      # unpin the match
                if psp is not None:
                    tr.end(psp, aborted=True)
                self.admission.release(req)
                self.admission.requeue(req)
                return
            pages = (shared or []) + priv
            if self.prefix is not None:
                # count hits/misses only for PLACED admissions, so a
                # requeue bounce cannot inflate the reuse figures
                if shared:
                    self.metrics.counter(
                        METRIC_SERVE_PREFIX_HITS,
                        "admissions reusing cached prefix pages").inc()
                    self.metrics.counter(
                        METRIC_SERVE_PREFIX_REUSED_TOKENS,
                        "prompt tokens served from cached pages").inc(
                        n_shared * ps)
                else:
                    self.metrics.counter(
                        METRIC_SERVE_PREFIX_MISSES,
                        "admissions with no cached prefix").inc()
        # the sync nests in the dispatch phase, which owns the rest
        with self.metrics.timer("serve_prefill_seconds", "prefill latency"), \
                phase("engine.dispatch", tr):
            if shared:
                # prefix hit: prefill ONLY the suffix, attending to the
                # shared pages through a prefix-only page-table row.  The
                # row width buckets to the next power of two >= the match
                # depth, so the gather/attention cost scales with the
                # ACTUAL prefix, not cache_len (compiles once per
                # (suffix bucket, prefix bucket) pair)
                start = n_shared * self.paging.page_size
                suffix = toks[start:]
                P = len(suffix)
                L = P if self._buckets is None else next(
                    b for b in self._buckets if b >= P)
                padded = np.zeros(L, np.int32)
                padded[:P] = suffix
                pb = 1
                while pb < n_shared:
                    pb *= 2
                row = np.full(pb, NULL_PAGE, np.int32)
                row[:n_shared] = shared
                logits, cache1 = self._suffix_prefill_fn(
                    self.params, self.cache, jnp.asarray(padded)[None],
                    jnp.asarray(row)[None], jnp.asarray(start, jnp.int32),
                    jnp.asarray(P - 1, jnp.int32))
            elif self._buckets is not None:
                P = len(toks)
                L = next(b for b in self._buckets if b >= P)
                padded = np.zeros(L, np.int32)
                if self._front_pad:
                    # SSM/hybrid: pad the FRONT, at a chunk-aligned
                    # offset so the real tokens' SSD chunk boundaries
                    # match the unpadded run bit-for-bit
                    Q = self.cfg.ssm.chunk if self.cfg.ssm else 1
                    f = ((L - P) // Q) * Q
                    padded[f:f + P] = toks
                    logits, cache1 = self._prefill_fn(
                        self.params, jnp.asarray(padded)[None],
                        jnp.asarray(f, jnp.int32),
                        jnp.asarray(P, jnp.int32),
                        jnp.asarray(f + P - 1, jnp.int32))
                else:
                    padded[:P] = toks
                    logits, cache1 = self._prefill_fn(
                        self.params, jnp.asarray(padded)[None],
                        jnp.asarray(P - 1, jnp.int32))
            else:
                L = len(toks)
                prompt = jnp.asarray(toks, jnp.int32)[None]
                logits, cache1 = prefill(
                    self.params, {"tokens": prompt}, self.cfg, self.run,
                    cache_len=None if self.paging is not None
                    else self.cache_len)
            # sync inside the timed region: dispatch is async, and the
            # very next consumer (argmax below) would absorb the device
            # wait — serve_prefill_seconds must report real latency
            with self._sync():
                jax.block_until_ready(logits)
        if self.paging is not None:
            # scatter the prefilled lines into the privately-owned pages
            # (suffix-only on a prefix hit — shared pages are READ-ONLY
            # and never written); the bucketed pad tail's pages are the
            # null page
            ps = self.paging.page_size
            page_ids = np.full(pages_for(L, ps), NULL_PAGE, np.int32)
            page_ids[:len(priv)] = priv
            with phase("engine.dispatch", tr):
                self.cache = self._insert(self.cache, cache1,
                                          jnp.asarray(page_ids))
            self._ptab.clear(slot)
            self._ptab.set_range(slot, 0, pages)
            self._slot_pages[slot] = pages
            if self.prefix is not None:
                # donate the complete prompt pages to the radix index
                # (the index takes its own refs) and register this
                # request as a holder of everything it maps
                self.prefix.insert(toks, pages)
                for p in pages:
                    self._page_holders[p] = \
                        self._page_holders.get(p, 0) + 1
            # GrpTRES holds the request's WORST-CASE footprint for its
            # whole residency (SLURM-style reservation): decode growth
            # then cannot push a tenant past its kv_pages cap
            self.admission.adjust_pages(req, req._est_pages)
        else:
            # write this request's cache slice into the batch cache through
            # the pre-jitted donated insert (one compile, zero retraces)
            with phase("engine.dispatch", tr):
                self.cache = self._insert(self.cache, cache1, slot)
        if req.output:
            tok = int(req.output[-1])      # resume: last token re-decodes
        else:
            with self._sync():
                tok = int(jnp.argmax(logits[0, -1]))
            req.output.append(tok)
        now = self._token_known(req, resume)
        self.slots[slot] = req
        req._slot = slot
        self.pos[slot] = len(toks)
        self.last_tok[slot] = tok
        self.remaining[slot] = req.max_new_tokens - len(req.output)
        if self.spec is not None:
            # full context incl. the pending last token (resume-safe:
            # toks is prompt+output[:-1], tok the re-decoding last)
            self.spec.begin(slot, np.append(toks, np.int32(tok)))
        # the prefilled KV residency the tenant pays for: dense lines, or
        # (paged) the pages actually pinned — amortized across holders
        # when the prefix cache shares them
        if self.paging is not None:
            self.admission.charge(req, kv_pages=self._billed_pages(slot))
        else:
            self.admission.charge(req, kv_tokens=len(toks))
        self.metrics.counter("serve_requests_admitted").inc()
        self.metrics.counter(
            METRIC_SERVE_TENANT_ADMITTED,
            "admissions per tenant").inc(tenant=req.tenant)
        if tr is not None:
            attrs = {"bucket": int(L), "chunks": 1, "iterations": 1}
            if self.paging is not None:
                n_sh = len(shared) if shared else 0
                attrs.update(prefix_pages=n_sh,
                             pages_allocated=len(priv))
            tr.end(psp, ts=now, **attrs)
            if resume:
                if root is not None:
                    tr.event("RESUME", root, slot=slot)
            else:
                # the first output token comes from the prefill argmax,
                # so TTFT = submit -> end of the prefill sync (resumes
                # already produced their first token pre-eviction)
                if root is not None:
                    tr.event("first_token", root)
                if req.submitted_at is not None:
                    tr.slo.ttft(now - req.submitted_at, req.tenant, req.qos)
            req._t_last = now
            req._trace["decode"] = tr.begin(
                "DECODE", cat="decode", parent=root, ts=now, slot=slot)
        self._maybe_finish(slot)

    def _hold_pages(self, req: Request, delta: int):
        """Budgeted mode: move the request's GrpTRES kv_pages hold by
        ``delta`` (chunk-by-chunk TRUE holdings, not the classic
        worst-case reservation).  No-op in classic mode, where
        ``_prefill_into`` reserves ``_est_pages`` up front."""
        if self.max_batch_tokens is None or delta == 0:
            return
        self.admission.adjust_pages(req, delta)
        req._held_pages += delta

    def _billed_pages(self, slot: int) -> float:
        """KV-page residency this slot bills per step: each page costs
        ``1 / holders``, so a prefix page shared by N live requests bills
        once across all of them (plain paged mode: every page has one
        holder and this is exactly the page count)."""
        if self.prefix is None:
            return len(self._slot_pages[slot])
        return sum(1.0 / self._page_holders[p]
                   for p in self._slot_pages[slot])

    def _release_pages(self, slot: int, req: Request):
        """Paged mode: drop the slot's page references (private pages
        return to the pool; shared prefix pages survive in the radix
        index — eviction-aware reclaim still sees freed pages
        immediately) and return the worst-case GrpTRES hold."""
        if self.paging is None:
            return
        pages = self._slot_pages[slot]
        if pages:
            self.allocator.free(pages)
            if self.prefix is not None:
                for p in pages:
                    h = self._page_holders.get(p, 0) - 1
                    if h > 0:
                        self._page_holders[p] = h
                    else:
                        self._page_holders.pop(p, None)
        if self.max_batch_tokens is not None:
            # budgeted mode holds true pages, grown chunk-by-chunk
            self.admission.adjust_pages(req, -req._held_pages)
            req._held_pages = 0
        else:
            self.admission.adjust_pages(req, -req._est_pages)
        self._slot_pages[slot] = []
        self._ptab.clear(slot)

    def _vacate(self, victim: Request) -> int:
        """Shared eviction bookkeeping: clear the slot, free its pages,
        return the slot/page holds, and requeue the request with partial
        output retained.  Returns the freed slot index (O(1) via the
        request's slot tag)."""
        slot = victim._slot
        assert slot >= 0 and self.slots[slot] is victim, (slot, victim.rid)
        if self.spec is not None:
            self.spec.release(slot)
        self.slots[slot] = None
        victim._slot = -1
        self._release_pages(slot, victim)
        self.admission.release(victim)
        self.admission.requeue(victim)
        return slot

    def _evict(self, victim: Request) -> int:
        """Evict a running request from its slot; it requeues at the head
        of its QOS class in its tenant queue with partial output retained.
        A mid-prefill partial (budgeted mode) is likewise preemptible —
        eviction lands at a chunk boundary, so its already-written pages
        simply free and the resume prefill replays the prompt."""
        victim.preemptions += 1
        self.metrics.counter(
            METRIC_SERVE_PREEMPTIONS, "evicted decode slots").inc()
        part = self._prefill_slots.get(victim._slot)
        if part is not None and part.req is victim:
            slot = victim._slot
            self._requeue_partial(part, "PREEMPT")
            return slot
        self._trace_decode_end(victim, "PREEMPT")
        return self._vacate(victim)

    def _finish(self, slot: int):
        req = self.slots[slot]
        req.done = True
        if self.spec is not None or self.index_generated:
            seq = np.concatenate([np.asarray(req.prompt, np.int32),
                                  np.asarray(req.output, np.int32)])
            if self.spec is not None:
                self.spec.release(slot)
                self.spec.observe(seq)
            if self.index_generated and self.prefix is not None:
                # KV lines exist for every token but the last (its line
                # would have been written by the next decode), so index
                # seq[:-1]; pages past the prompt carry generated tokens
                self.prefix.insert(seq[:-1], self._slot_pages[slot],
                                   generated_from=len(req.prompt))
        self.slots[slot] = None
        req._slot = -1
        self._release_pages(slot, req)
        self.admission.release(req)
        self.metrics.counter("serve_requests_completed").inc()
        tr = self.tracer
        if tr is not None:
            self._trace_decode_end(req)
            trace = getattr(req, "_trace", None)
            root = trace.pop("root", None) if trace else None
            if root is not None:
                tr.event("FINISH", root)
                tr.end(root, tokens=len(req.output))
            if req.submitted_at is not None:
                tr.slo.e2e(tr.clock() - req.submitted_at, req.tenant,
                           req.qos)

    def _maybe_finish(self, slot: int):
        req = self.slots[slot]
        if req is None:
            return
        if (req.eos_id is not None and req.output
                and req.output[-1] == req.eos_id) or self.remaining[slot] <= 0 \
                or self.pos[slot] >= self.cache_len - 1:
            self._finish(slot)

    def _sample(self, logits: jax.Array) -> np.ndarray:
        """Host-side per-slot sampling (fused=False path).
        logits: (num_slots, V)."""
        temps = np.array([
            (self.slots[i].temperature if self.slots[i] else 0.0)
            for i in range(self.num_slots)], np.float32)
        greedy = np.asarray(jnp.argmax(logits, axis=-1))
        # split unconditionally — one key per generated token, exactly the
        # stream decode_n consumes, so host and fused paths stay
        # interchangeable even when greedy and sampled slots mix
        self._key, sub = jax.random.split(self._key)
        if (temps <= 0).all():
            return greedy.astype(np.int32)
        t = jnp.maximum(jnp.asarray(temps), 1e-4)[:, None]
        sampled = np.asarray(
            jax.random.categorical(sub, logits.astype(jnp.float32) / t,
                                   axis=-1))
        return np.where(temps > 0, sampled, greedy).astype(np.int32)

    # ------------------------------------------------------- page growth ----
    def _reclaim_one_victim(self, requester: Request) -> bool:
        """Pool-exhaustion scavenger reclaim: evict ONE running request
        the requester's QOS may preempt (lowest QOS first, worst
        fair-share standing, most recent admission — the same victim rule
        admission preemption uses), freeing its pages.  Returns whether a
        victim was evicted."""
        qos = self.admission.qos_table.get(requester.qos)
        if qos is None:
            return False
        victims = [r for r in self.slots
                   if r is not None and r is not requester
                   and qos.can_preempt(r.qos)]
        if not victims:
            return False
        self._evict(self.admission.pick_victim(victims))
        return True

    def _requeue_starved(self, slot: int):
        """A slot the pool starved out goes back to its tenant queue with
        partial output retained (resume-exact, like a preemption victim);
        page-budget admission re-admits it once pages free up."""
        self._trace_decode_end(self.slots[slot], "STARVED")
        self._vacate(self.slots[slot])
        self.metrics.counter(
            "serve_page_starvations",
            "slots requeued on page-pool exhaustion").inc()

    def _ensure_pages(self, active: list, steps: Optional[int] = None):
        """Grow each live slot's allocation to cover the coming chunk
        (on-demand growth at decode-time page boundaries).  The +2
        headroom keeps the slot's freeze boundary strictly beyond the
        chunk, so a fully-grown paged slot freezes exactly where the
        dense cache would — bit-identical stopping.  On pool exhaustion,
        reclaim via one-victim scavenger eviction; a slot that still
        cannot cover even its current position requeues starved (its
        ``limit`` would otherwise let it write the null page)."""
        ps = self.paging.page_size
        steps_cap = self.decode_chunk if steps is None else steps
        for i in list(active):
            req = self.slots[i]
            if req is None:                    # evicted by a reclaim below
                active.remove(i)
                continue
            # a nearly-finished slot only needs pages for the tokens it
            # may still generate — don't pin headroom it can never use
            steps = min(steps_cap, max(int(self.remaining[i]), 1))
            target = min(int(self.pos[i]) + steps + 2, self.cache_len)
            need = pages_for(target, ps) - len(self._slot_pages[i])
            if need <= 0:
                continue
            # growth pressure relief, in escalation order: LRU-evict
            # unpinned cached prefixes first, scavenger preemption only
            # after the index has nothing left to give
            got = self._alloc_or_evict(need)
            if got is None and self._reclaim_one_victim(req):
                got = self._alloc_or_evict(need)
            if got is None:                    # partial growth: best effort
                got = self.allocator.alloc(
                    min(need, self.pool_view.min_available()))
            if got:
                if self.prefix is not None:
                    for p in got:
                        self._page_holders[p] = \
                            self._page_holders.get(p, 0) + 1
                # classic mode reserved the worst-case footprint at
                # admission (this is a no-op there); budgeted mode grows
                # the TRUE hold page-by-page
                self._hold_pages(req, len(got))
                n0 = len(self._slot_pages[i])
                self._slot_pages[i].extend(got)
                self._ptab.set_range(i, n0, got)
            if self._capacity(i) <= int(self.pos[i]):
                # starved: not even the current token's page
                self._requeue_starved(i)
                active.remove(i)

    # --------------------------------------- chunked prefill (budgeted) ----
    def _start_prefill(self, slot: int, req: Request):
        """Admit a request as a *partial prefill*: it takes the slot (so
        the existing eviction/requeue paths see it) but decodes nothing
        until ``_step_budgeted`` has streamed its whole prompt through
        chunk dispatches.  With the prefix cache, matched pages map
        read-only immediately and ``pos_filled`` starts past them — the
        chunks only ever cover the suffix."""
        toks = self._resume_tokens(req)
        tr = self.tracer
        root = self._trace_root(req)
        span = None
        if tr is not None:
            if root is not None:
                tr.event("ADMIT", root, slot=slot)
            span = tr.begin("PREFILL", cat="prefill", parent=root,
                            ts=req.admitted_at, tokens=len(toks),
                            resume=bool(req.output), chunked=True)
        shared = []
        if self.prefix is not None:
            shared = self.prefix.acquire(self.prefix.match(toks)) or []
            # reuse is decided (and counted) at admission: the pages are
            # pinned from here on, unlike the classic path there is no
            # later abandon-the-match fallback — chunks allocate one
            # bucket's worth at a time, so the all-or-nothing shortfall
            # that forces it cannot arise
            if shared:
                self.metrics.counter(
                    METRIC_SERVE_PREFIX_HITS,
                    "admissions reusing cached prefix pages").inc()
                self.metrics.counter(
                    METRIC_SERVE_PREFIX_REUSED_TOKENS,
                    "prompt tokens served from cached pages").inc(
                    len(shared) * self.paging.page_size)
                for p in shared:
                    self._page_holders[p] = \
                        self._page_holders.get(p, 0) + 1
            else:
                self.metrics.counter(
                    METRIC_SERVE_PREFIX_MISSES,
                    "admissions with no cached prefix").inc()
        self.slots[slot] = req
        req._slot = slot
        self.pos[slot] = 0
        self.last_tok[slot] = 0
        self.remaining[slot] = 0       # frozen until promotion
        part = _PartialPrefill(
            req=req, toks=toks, slot=slot,
            pos_filled=len(shared) * self.paging.page_size,
            pages=list(shared), n_shared=len(shared), span=span,
            admitted_iteration=self._serve_stats["iterations"])
        self._hold_pages(req, len(shared))
        self._partials.append(part)
        self._prefill_slots[slot] = part

    def _pack_order(self) -> list:
        """Chunk-packing order: QOS rank first, then SHORTEST REMAINING
        prefill, then arrival.  Shortest-first is what kills head-of-line
        blocking — a 10-token interactive prompt finishes in one chunk
        even when a 10k-token batch prompt arrived first."""
        qos_t = self.admission.qos_table

        def rank(part):
            q = qos_t.get(part.req.qos)
            prio = q.priority if q is not None else 0
            return (-prio, len(part.toks) - part.pos_filled,
                    part.req._seq)

        return sorted(self._partials, key=rank)

    def _plan_chunk(self, part: _PartialPrefill, budget: int,
                    min_bucket: int = 1) -> Optional[_ChunkPlan]:
        """Pick the next chunk's bucket, grow the partial's pages to
        cover it, and lay out the per-line scatter targets.  Returns None
        when no bucket fits the budget or the pool starves the partial
        back to its tenant queue (pages freed, holdings returned).

        ``min_bucket`` declines chunks that neither reach that size nor
        finish the prompt: the drain loop uses it so a long prompt's tail
        never dribbles out in tiny dispatches (a whole dispatch for a few
        tokens), while a short prompt — which such a chunk COMPLETES, and
        whose first token it unblocks — still packs at any size."""
        rem = len(part.toks) - part.pos_filled
        assert rem > 0, (part.req.rid, part.pos_filled)
        bucket = 0
        for b in self.chunk_buckets:
            if b > budget:
                break
            bucket = b
            if b >= rem:                # smallest bucket covering the rest
                break
        if bucket == 0 or (bucket < min_bucket and bucket < rem):
            return None
        real = min(rem, bucket)
        start = part.pos_filled
        ps = self.paging.page_size
        need = pages_for(start + real, ps) - len(part.pages)
        if need > 0:
            got = self._alloc_or_evict(need)
            if got is None and self._reclaim_one_victim(part.req):
                got = self._alloc_or_evict(need)
            if got is None:
                # pool exhausted mid-prefill: starve the partial back to
                # the queue (page-budget admission re-admits it later)
                self._requeue_partial(part, "STARVED")
                self.metrics.counter(
                    "serve_page_starvations",
                    "slots requeued on page-pool exhaustion").inc()
                return None
            if self.prefix is not None:
                for p in got:
                    self._page_holders[p] = \
                        self._page_holders.get(p, 0) + 1
            part.pages.extend(got)
            self._hold_pages(part.req, len(got))
        row = np.full(self.paging.pages_per_seq, NULL_PAGE, np.int32)
        row[:len(part.pages)] = part.pages
        tokens = np.zeros(bucket, np.int32)
        tokens[:real] = part.toks[start:start + real]
        pages = np.full(bucket, NULL_PAGE, np.int32)
        offs = np.zeros(bucket, np.int32)
        for j in range(real):           # pad lines write the null page
            pages[j] = part.pages[(start + j) // ps]
            offs[j] = (start + j) % ps
        return _ChunkPlan(part=part, bucket=bucket, real=real, start=start,
                          tokens=tokens, row=row, pages=pages, offs=offs)

    def _dispatch_chunk(self, plan: _ChunkPlan):
        """Standalone chunk dispatch (no decode lanes to fuse with).
        Deliberately NOT synced: a non-final chunk's outputs feed only
        the (async, in-program) line scatter, so the host keeps planning
        while the device works; the promotion argmax syncs the final
        chunk.  The prefill histogram therefore times submission here —
        device time shows up in the PREFILL trace span (admit ->
        promotion)."""
        with phase("engine.dispatch", self.tracer), self.metrics.timer(
                "serve_prefill_seconds", "prefill latency"):
            logits, self.cache = self._chunk_fn(
                self.params, self.cache, jnp.asarray(plan.tokens)[None],
                jnp.asarray(plan.row)[None],
                jnp.asarray(plan.start, jnp.int32),
                jnp.asarray(plan.real - 1, jnp.int32),
                jnp.asarray(plan.pages), jnp.asarray(plan.offs))
        return logits

    def _finish_chunk(self, plan: _ChunkPlan, logits):
        """Advance the partial past a dispatched chunk (the KV lines were
        scattered inside the chunk/mixed program); the final chunk
        promotes the request to a live decode lane (its first output
        token is this chunk's last-position argmax — exactly the
        whole-prompt prefill's)."""
        with phase("engine.commit", self.tracer):
            part = plan.part
            part.pos_filled = plan.start + plan.real
            part.chunks += 1
            self._serve_stats["prefill_tokens"] += plan.real
            self._serve_stats["prefill_chunks"] += 1
            if part.pos_filled >= len(part.toks):
                self._partials.remove(part)
                self._promote(part, logits)

    def _promote(self, part: _PartialPrefill, logits):
        """Last chunk done: unfreeze the slot into a decode lane.  The
        PREFILL span ends once the first token is known, ``iterations``
        engine iterations after admission (1: the admitting one)."""
        req, slot = part.req, part.slot
        tr = self.tracer
        root = self._trace_root(req)
        self._ptab.clear(slot)
        self._ptab.set_range(slot, 0, part.pages)
        self._slot_pages[slot] = part.pages
        if self.prefix is not None:
            # donate the complete prompt pages to the radix index;
            # holder refs were registered page-by-page as chunks grew
            self.prefix.insert(part.toks, part.pages)
        resume = bool(req.output)
        iterations = (self._serve_stats["iterations"]
                      - part.admitted_iteration + 1)
        if resume:
            tok = int(req.output[-1])      # resume: last token re-decodes
        else:
            with self._sync():
                tok = int(jnp.argmax(logits[0, -1]))
            req.output.append(tok)
        now = self._token_known(req, resume)
        self.pos[slot] = len(part.toks)
        self.last_tok[slot] = tok
        self.remaining[slot] = req.max_new_tokens - len(req.output)
        if self.spec is not None:
            self.spec.begin(slot, np.append(part.toks, np.int32(tok)))
        self.admission.charge(req, kv_pages=self._billed_pages(slot))
        self.metrics.counter("serve_requests_admitted").inc()
        self.metrics.counter(
            METRIC_SERVE_TENANT_ADMITTED,
            "admissions per tenant").inc(tenant=req.tenant)
        if tr is not None:
            if part.span is not None:
                tr.end(part.span, ts=now, chunks=part.chunks,
                       iterations=iterations, pos_filled=part.pos_filled,
                       prefix_pages=part.n_shared,
                       pages_allocated=len(part.pages) - part.n_shared)
            if resume:
                if root is not None:
                    tr.event("RESUME", root, slot=slot)
            else:
                # first output token came from the final chunk's argmax:
                # TTFT = submit -> that chunk's sync (resumes produced
                # their first token pre-eviction)
                if root is not None:
                    tr.event("first_token", root)
                if req.submitted_at is not None:
                    tr.slo.ttft(now - req.submitted_at, req.tenant, req.qos)
            req._t_last = now
            req._trace["decode"] = tr.begin(
                "DECODE", cat="decode", parent=root, ts=now, slot=slot)
        del self._prefill_slots[slot]
        self._maybe_finish(slot)

    def _requeue_partial(self, part: _PartialPrefill, reason: str):
        """Abort a mid-prefill partial at a chunk boundary (preemption or
        page starvation): free its pages, return its chunk-granular
        holdings, clear the slot, and requeue — the resume prefill
        replays prompt + retained output exactly like a decode victim."""
        req, slot = part.req, part.slot
        tr = self.tracer
        if part.span is not None:
            tr.end(part.span, aborted=True, chunks=part.chunks,
                   pos_filled=part.pos_filled)
        root = self._trace_root(req)
        if tr is not None and root is not None:
            tr.event(reason, root)
        if part.pages:
            self.allocator.free(part.pages)
            if self.prefix is not None:
                for p in part.pages:
                    h = self._page_holders.get(p, 0) - 1
                    if h > 0:
                        self._page_holders[p] = h
                    else:
                        self._page_holders.pop(p, None)
        self._hold_pages(req, -req._held_pages)
        self.slots[slot] = None
        req._slot = -1
        self._slot_pages[slot] = []
        self._ptab.clear(slot)
        self._partials.remove(part)
        del self._prefill_slots[slot]
        self.admission.release(req)
        self.admission.requeue(req)

    def _decode_active(self) -> list:
        """Slots with a LIVE decode lane (occupied, not mid-prefill)."""
        return [i for i, r in enumerate(self.slots)
                if r is not None and i not in self._prefill_slots]

    def _step_budgeted(self) -> int:
        """One token-budgeted iteration (continuous batching): decode
        lanes claim budget first (``decode_chunk`` tokens each, dropping
        to 1 only when that alone would blow the budget), then prefill
        chunks pack into the remainder — the head chunk FUSED into the
        same dispatch as decode, any leftover budget drained through
        standalone chunk dispatches."""
        st = self._serve_stats
        T = self.max_batch_tokens
        tr = self.tracer
        with phase("engine.plan", tr):
            decode_active = self._decode_active()
            d = self.decode_chunk
            # speculative lanes cost k+1 budget tokens each (worst case:
            # all drafts accepted plus the bonus); if that starves pending
            # prefills entirely, drop to the plain 1-token lane mix
            spec = self.speculate > 0
            lane = (self.speculate + 1) if spec else d
            if (self._partials and decode_active
                    and lane * len(decode_active) > T):
                d, spec, lane = 1, False, 1
            if decode_active:
                self._ensure_pages(decode_active, steps=lane)
                decode_active = self._decode_active()
            budget = T
            head_plan = None
            if self._partials and decode_active:
                budget -= lane * len(decode_active)
                for part in self._pack_order():
                    if budget < 1:
                        break
                    if self._prefill_slots.get(part.slot) is not part:
                        continue        # starved away by an earlier plan
                    head_plan = self._plan_chunk(part, budget)
                    if head_plan is not None:
                        budget -= head_plan.bucket
                        break
                # planning may have reclaim-evicted a decode slot
                decode_active = self._decode_active()
        if decode_active:
            if spec:
                total, chunk_out = self._step_spec(decode_active,
                                                   chunk_plan=head_plan)
                st["decode_tokens"] += total
                if head_plan is not None:
                    self._finish_chunk(head_plan, chunk_out)
            elif head_plan is not None and d == self.decode_chunk:
                total, chunk_out = self._step_fused(
                    decode_active, num_tokens=d, chunk_plan=head_plan)
                st["decode_tokens"] += total
                self._finish_chunk(head_plan, chunk_out)
            else:
                # budget too tight to fuse a full decode_chunk alongside
                # the chunk — dispatch the chunk standalone (async), let
                # the decode queue behind it, THEN finish the chunk:
                # promotion mid-iteration would un-freeze a lane the
                # in-flight decode already treats as done
                c_logits = (self._dispatch_chunk(head_plan)
                            if head_plan is not None else None)
                total, _ = self._step_fused(decode_active, num_tokens=d)
                st["decode_tokens"] += total
                if head_plan is not None:
                    self._finish_chunk(head_plan, c_logits)
        elif head_plan is not None:
            # the planned chunk's decode companions vanished (reclaimed):
            # run it standalone
            self._finish_chunk(head_plan, self._dispatch_chunk(head_plan))
        # drain the remaining budget with standalone chunk dispatches
        # (declining runt chunks that don't finish a prompt — each costs
        # a whole dispatch either way)
        min_bucket = max(1, self.chunk_buckets[-1] // 4)
        while budget >= 1 and self._partials:
            progressed = False
            with phase("engine.plan", tr):
                order = self._pack_order()
            for part in order:
                if budget < 1:
                    break
                if self._prefill_slots.get(part.slot) is not part:
                    continue
                with phase("engine.plan", tr):
                    plan = self._plan_chunk(part, budget,
                                            min_bucket=min_bucket)
                if plan is None:
                    continue
                self._finish_chunk(plan, self._dispatch_chunk(plan))
                budget -= plan.bucket
                progressed = True
            if not progressed:
                break
        return (len([r for r in self.slots if r is not None])
                + self.admission.pending())

    # -------------------------------------------------------------- step ----
    def step(self) -> int:
        """Admit + one batched decode dispatch (``decode_chunk`` tokens on
        the fused path, one on the host path).  Returns #active + #queued.

        Budgeted mode (``max_batch_tokens``) runs the token-budgeted
        continuous-batching iteration instead: decode lanes plus packed
        prefill chunks under one budget.

        Every host instant of a step lies in one ``engine.*`` phase
        (``monitoring.trace.phase``): admit, plan, dispatch, sync, commit.
        ``serve_stats["host_s"]`` keeps the step's time outside its syncs.
        """
        st = self._serve_stats
        t0, sync0 = time.perf_counter(), self._synced
        st["iterations"] += 1
        left = self._iterate()
        st["host_s"] += time.perf_counter() - t0 - (self._synced - sync0)
        return left

    def _iterate(self) -> int:
        tr = self.tracer
        with phase("engine.admit", tr):
            self._admit()
            self._update_pool_gauges()
        if self.max_batch_tokens is not None:
            return self._step_budgeted()
        with phase("engine.plan", tr):
            active = [i for i, r in enumerate(self.slots) if r is not None]
            if self.paging is not None and active:
                # speculative rounds may advance a lane by up to k+1 AND
                # fall back to the plain fused chunk when no lane has
                # drafts — pre-grow pages for whichever path runs
                self._ensure_pages(
                    active, steps=(max(self.decode_chunk, self.speculate + 1)
                                   if self.speculate else None))
                # growth may have evicted/requeued slots at ANY index (a
                # reclaim victim can precede its requester) — rebuild
                # rather than trust the in-place edits
                active = [i for i, r in enumerate(self.slots)
                          if r is not None]
        if not active:
            return self.admission.pending()
        if self.speculate:
            self._step_spec(active)
        elif self.fused:
            self._step_fused(active)
        else:
            self._step_host(active)
        return (len([r for r in self.slots if r is not None])
                + self.admission.pending())

    def _step_fused(self, active: list, num_tokens: Optional[int] = None,
                    chunk_plan: Optional[_ChunkPlan] = None):
        """Device-resident chunk: one dispatch, one host sync.

        Budgeted mode passes ``num_tokens`` (tokens per lane this
        iteration) and optionally ``chunk_plan`` — a prefill chunk fused
        into the SAME dispatch.  Mid-prefill slots count as done (their
        lanes freeze; capacity 0 routes their writes to the null page).
        Returns ``(generated_tokens, chunk_outputs_or_None)``."""
        tr = self.tracer
        with phase("engine.dispatch", tr):
            t0, out, chunk_out = self._dispatch_fused(num_tokens, chunk_plan)
        # ONE sync per chunk: everything after it is host-side numpy
        with self._sync():
            out = [np.asarray(x) for x in out]
        with phase("engine.commit", tr):
            total = self._commit_fused(active, *out, t0)
        return total, chunk_out

    def _dispatch_fused(self, num_tokens: Optional[int],
                        chunk_plan: Optional[_ChunkPlan]):
        """Stage the lanes' arguments and dispatch the decode program
        (fused with ``chunk_plan`` when given).  Returns the dispatch
        time, the lane outputs to sync (tokens, pos, last token,
        remaining, done) and the chunk's outputs."""
        done = np.array([self.slots[i] is None or i in self._prefill_slots
                         for i in range(self.num_slots)])
        eos = np.array([
            (self.slots[i].eos_id if self.slots[i] is not None
             and self.slots[i].eos_id is not None else -1)
            for i in range(self.num_slots)], np.int32)
        temps = np.array([
            (self.slots[i].temperature if self.slots[i] else 0.0)
            for i in range(self.num_slots)], np.float32)
        t0 = time.perf_counter()
        chunk_out = None
        if self.paging is not None:
            limit = np.array([
                self._capacity(i) if self.slots[i] is not None
                else self.cache_len
                for i in range(self.num_slots)], np.int32)
            fn = self._decode_n
            if num_tokens is not None and num_tokens != self.decode_chunk:
                fn = self._decode_n1   # budgeted mixed iterations: 1/lane
            if chunk_plan is not None:
                (toks, self.cache, token, pos, remaining, done_d,
                 self._key, chunk_out) = self._mixed_step(
                    self.params, self.cache, jnp.asarray(self.last_tok),
                    jnp.asarray(self.pos.astype(np.int32)),
                    jnp.asarray(self.remaining.astype(np.int32)),
                    jnp.asarray(done), jnp.asarray(eos),
                    jnp.asarray(temps), self._key,
                    jnp.asarray(self._dispatch_table()),
                    jnp.asarray(limit),
                    jnp.asarray(chunk_plan.tokens)[None],
                    jnp.asarray(chunk_plan.row)[None],
                    jnp.asarray(chunk_plan.start, jnp.int32),
                    jnp.asarray(chunk_plan.real - 1, jnp.int32),
                    jnp.asarray(chunk_plan.pages),
                    jnp.asarray(chunk_plan.offs))
            else:
                toks, self.cache, token, pos, remaining, done_d, \
                    self._key = fn(
                        self.params, self.cache,
                        jnp.asarray(self.last_tok),
                        jnp.asarray(self.pos.astype(np.int32)),
                        jnp.asarray(self.remaining.astype(np.int32)),
                        jnp.asarray(done), jnp.asarray(eos),
                        jnp.asarray(temps), self._key,
                        jnp.asarray(self._dispatch_table()),
                        jnp.asarray(limit))
        else:
            toks, self.cache, token, pos, remaining, done_d, self._key = \
                self._decode_n(
                    self.params, self.cache, jnp.asarray(self.last_tok),
                    jnp.asarray(self.pos.astype(np.int32)),
                    jnp.asarray(self.remaining.astype(np.int32)),
                    jnp.asarray(done), jnp.asarray(eos), jnp.asarray(temps),
                    self._key)
        return t0, (toks, pos, token, remaining, done_d), chunk_out

    def _commit_fused(self, active: list, toks, pos, token, remaining,
                      done_d, t0: float) -> int:
        """Per-lane outputs, billing, finish and requeue after a fused
        dispatch's sync.  Returns the tokens generated."""
        tr = self.tracer
        self.metrics.histogram("serve_decode_seconds",
                               "batched decode-step latency").observe(
            time.perf_counter() - t0)
        ts_sync = tr.clock() if tr is not None else 0.0
        charges = []
        tenant_tokens: dict[str, int] = {}
        total = 0
        for i in active:
            req = self.slots[i]
            n_gen = int(pos[i]) - int(self.pos[i])
            if n_gen:
                req.output.extend(int(t) for t in toks[i, :n_gen])
                if self.paging is not None:
                    # paged rent: pages actually pinned x steps — true HBM
                    # residency (shared prefix pages amortized across
                    # holders), so a short request stops paying for cache
                    # it never held
                    charges.append(
                        (req, n_gen, 0,
                         self._billed_pages(i) * n_gen))
                else:
                    # per-chunk charge: n tokens + KV-line rent summed over
                    # the chunk's steps (sum_{j=1..n} pos0+j), exactly the
                    # per-token path's total
                    kv = n_gen * int(self.pos[i]) + n_gen * (n_gen + 1) // 2
                    charges.append((req, n_gen, kv))
                tenant_tokens[req.tenant] = \
                    tenant_tokens.get(req.tenant, 0) + n_gen
                total += n_gen
                if tr is not None and req._t_last is not None:
                    # one host sync per chunk: spread the chunk's wall
                    # time evenly across its tokens (token-weighted)
                    tr.slo.itl((ts_sync - req._t_last) / n_gen,
                               req.tenant, req.qos, n=n_gen)
            if tr is not None:
                req._t_last = ts_sync
            self.pos[i] = pos[i]
            self.last_tok[i] = token[i]
            self.remaining[i] = remaining[i]
            if self.spec is not None and n_gen:
                # keep draft contexts in sync when this chunk ran as the
                # empty-draft fallback of the speculative step
                self.spec.advance(i, toks[i, :n_gen].astype(np.int32))
            if done_d[i]:
                hit_eos = (req.eos_id is not None and req.output
                           and req.output[-1] == req.eos_id)
                if (self.paging is not None and not hit_eos
                        and self.remaining[i] > 0
                        and self._capacity(i) < self.cache_len):
                    # froze at its allocation boundary, not a real stop:
                    # partial growth ran out of pages mid-chunk
                    self._requeue_starved(i)
                else:
                    self._finish(i)
        self.admission.charge_bulk(charges)
        self.metrics.counter("serve_tokens_generated").inc(total)
        tok_counter = self.metrics.counter(
            METRIC_SERVE_TENANT_TOKENS, "generated tokens per tenant")
        for tenant, n in tenant_tokens.items():
            tok_counter.inc(n, tenant=tenant)
        return total

    def _step_spec(self, active: list,
                   chunk_plan: Optional[_ChunkPlan] = None):
        """One speculative draft-and-verify round (paged + fused only).

        Per live lane the draft source proposes up to ``speculate``
        tokens; ONE batched verify dispatch scores ``last_tok`` plus all
        drafts (row ``j``'s logits bitwise-identical to a sequential
        decode at ``pos+j``), then the host accepts the longest agreeing
        run under greedy — or rejection-samples under temperature — and
        replays ``decode_n``'s exact stop walk over the emitted run
        (EOS / budget / allocation boundary), so stopping is
        bit-identical too.  Rejected proposals' KV lines are dead on
        arrival: masked until the next round's scatter overwrites them
        (pos only advances past ACCEPTED lines), the same null-page
        lifetime argument bucket pad lines ride on.

        When no lane has any draft, falls back to the plain fused chunk
        (classic mode) or a 1-token lane mix (budgeted mode) — identical
        output either way, but no S-row dispatch for 1-token progress.
        Returns ``(generated_tokens, chunk_outputs_or_None)`` like
        ``_step_fused``."""
        k = self.speculate
        tr = self.tracer
        with phase("engine.plan", tr):
            drafts: dict[int, np.ndarray] = {}
            kinds: dict[int, str] = {}
            for i in active:
                d = np.asarray(self.spec.draft(i, k), np.int32).ravel()[:k]
                kinds[i] = getattr(self.spec, "last_kind", self.spec.kind)
                # drop drafts the lane has no room to verify: every USED
                # verify row must read only lines inside the allocation
                room = self._capacity(i) - 1 - int(self.pos[i])
                if len(d) > room:
                    d = d[:max(room, 0)]
                drafts[i] = d
        if chunk_plan is None and all(len(drafts[i]) == 0 for i in active):
            if self.max_batch_tokens is not None:
                return self._step_fused(active, num_tokens=1)
            return self._step_fused(active)
        with phase("engine.dispatch", tr):
            t0, greedy, logits, chunk_out = self._dispatch_spec(
                active, drafts, chunk_plan)
        # ONE host sync per round; raw logits transfer only under
        # temperature (greedy needs just the device argmax)
        with self._sync():
            greedy = np.asarray(greedy)
            logits_np = (np.asarray(logits).astype(np.float32)
                         if any(self.slots[i].temperature > 0
                                for i in active) else None)
        with phase("engine.commit", tr):
            total = self._commit_spec(active, drafts, kinds, greedy,
                                      logits_np, t0)
        return total, chunk_out

    def _dispatch_spec(self, active: list, drafts: dict,
                       chunk_plan: Optional[_ChunkPlan]):
        """Stage every lane's last token and drafts with their KV-line
        targets, and dispatch the verify program (fused with
        ``chunk_plan`` when given).  Returns the dispatch time, the
        device argmax and logits, and the chunk's outputs."""
        S = self.speculate + 1
        ps = self.paging.page_size
        tokens = np.zeros((self.num_slots, S), np.int32)
        pos0 = np.zeros(self.num_slots, np.int32)
        pages = np.full((self.num_slots, S), NULL_PAGE, np.int32)
        offs = np.zeros((self.num_slots, S), np.int32)
        for i in active:
            p0 = int(self.pos[i])
            di = drafts[i]
            tokens[i, 0] = self.last_tok[i]
            tokens[i, 1:1 + len(di)] = di
            pos0[i] = p0
            sp = self._slot_pages[i]
            cap = len(sp) * ps
            for j in range(S):
                if p0 + j < cap:
                    pages[i, j] = sp[(p0 + j) // ps]
                    offs[i, j] = (p0 + j) % ps
            # columns past the allocation (and every column of frozen /
            # empty / mid-prefill lanes) scatter to the null page
        t0 = time.perf_counter()
        chunk_out = None
        args = (self.params, self.cache, jnp.asarray(tokens),
                jnp.asarray(pos0), jnp.asarray(pages), jnp.asarray(offs),
                jnp.asarray(self._dispatch_table()))
        if chunk_plan is not None:
            greedy, logits, self.cache, chunk_out = self._mixed_verify(
                *args,
                jnp.asarray(chunk_plan.tokens)[None],
                jnp.asarray(chunk_plan.row)[None],
                jnp.asarray(chunk_plan.start, jnp.int32),
                jnp.asarray(chunk_plan.real - 1, jnp.int32),
                jnp.asarray(chunk_plan.pages),
                jnp.asarray(chunk_plan.offs))
        else:
            greedy, logits, self.cache = self._verify_fn(*args)
        return t0, greedy, logits, chunk_out

    def _commit_spec(self, active: list, drafts: dict, kinds: dict,
                     greedy: np.ndarray, logits_np, t0: float) -> int:
        """Accept each lane's run after the verify sync, then bill,
        finish, requeue and count.  Returns the tokens emitted."""
        k = self.speculate
        st = self.spec_stats
        proposed = sum(len(drafts[i]) for i in active)
        tr = self.tracer
        self.metrics.histogram("serve_decode_seconds",
                               "batched decode-step latency").observe(
            time.perf_counter() - t0)
        ts_sync = tr.clock() if tr is not None else 0.0
        charges = []
        tenant_tokens: dict[str, int] = {}
        total = 0
        accepted_total = 0
        for i in active:
            req = self.slots[i]
            di = drafts[i]
            nd = len(di)
            if req.temperature > 0:
                t = max(req.temperature, 1e-4)
                rows = logits_np[i, :nd + 1] / t
                rows = rows - rows.max(axis=-1, keepdims=True)
                p = np.exp(rows)
                p /= p.sum(axis=-1, keepdims=True)
                cand = rejection_sample(self._spec_rng, p, di)
            else:
                tg = greedy[i]
                m = 0
                while m < nd and tg[m] == di[m]:
                    m += 1
                cand = tg[:m + 1]
            accepted_total += len(cand) - 1
            st["proposed_by"][kinds[i]] = \
                st["proposed_by"].get(kinds[i], 0) + nd
            # decode_n's stop walk, host-side: emit the token, then
            # freeze on EOS / remaining / allocation boundary
            boundary = self._capacity(i) - 1
            p0 = int(self.pos[i])
            rem = int(self.remaining[i])
            emitted = []
            stopped = False
            for tkn in cand:
                emitted.append(int(tkn))
                rem -= 1
                if ((req.eos_id is not None
                     and emitted[-1] == req.eos_id) or rem <= 0
                        or p0 + len(emitted) >= boundary):
                    stopped = True
                    break
            n_gen = len(emitted)
            req.output.extend(emitted)
            charges.append(
                (req, n_gen, 0, self._billed_pages(i) * n_gen))
            tenant_tokens[req.tenant] = \
                tenant_tokens.get(req.tenant, 0) + n_gen
            total += n_gen
            if tr is not None:
                if req._t_last is not None:
                    tr.slo.itl((ts_sync - req._t_last) / n_gen,
                               req.tenant, req.qos, n=n_gen)
                req._t_last = ts_sync
            self.pos[i] = p0 + n_gen
            self.last_tok[i] = emitted[-1]
            self.remaining[i] = rem
            self.spec.advance(i, np.asarray(emitted, np.int32))
            if n_gen == k + 1 and hasattr(self.spec, "set_pending"):
                # fully-accepted round: the model draft's k-step scan
                # never wrote draft k-1's own KV line — catch up later
                self.spec.set_pending(i, int(di[k - 1]))
            if stopped:
                hit_eos = (req.eos_id is not None and req.output
                           and req.output[-1] == req.eos_id)
                if (not hit_eos and self.remaining[i] > 0
                        and self._capacity(i) < self.cache_len):
                    # froze at its allocation boundary, not a real stop
                    self._requeue_starved(i)
                else:
                    self._finish(i)
        self.admission.charge_bulk(charges)
        self.metrics.counter("serve_tokens_generated").inc(total)
        tok_counter = self.metrics.counter(
            METRIC_SERVE_TENANT_TOKENS, "generated tokens per tenant")
        for tenant, n in tenant_tokens.items():
            tok_counter.inc(n, tenant=tenant)
        st["rounds"] += 1
        st["proposed"] += proposed
        st["accepted"] += accepted_total
        st["emitted"] += total
        self.metrics.counter(
            METRIC_SPEC_PROPOSED, "draft tokens proposed").inc(proposed)
        self.metrics.counter(
            METRIC_SPEC_ACCEPTED,
            "draft tokens accepted by the target").inc(accepted_total)
        if st["proposed"]:
            self.metrics.gauge(
                METRIC_SPEC_ACCEPT_RATE,
                "running draft acceptance rate").set(
                st["accepted"] / st["proposed"])
        return total

    def _step_host(self, active: list):
        """Original per-token host loop (baseline / reference path)."""
        tr = self.tracer
        with phase("engine.dispatch", tr):
            token = jnp.asarray(self.last_tok[:, None])
            pos = jnp.asarray(self.pos.astype(np.int32))
            t0 = time.perf_counter()
            if self.paging is not None:
                logits, self.cache = self._step(
                    self.params, self.cache, token, pos,
                    jnp.asarray(self._dispatch_table()))
            else:
                logits, self.cache = self._step(self.params, self.cache,
                                                token, pos)
            self.metrics.histogram("serve_decode_seconds",
                                   "batched decode-step latency").observe(
                time.perf_counter() - t0)
        with self._sync():
            nxt = self._sample(logits)
        with phase("engine.commit", tr):
            self._commit_host(active, nxt)

    def _commit_host(self, active: list, nxt: np.ndarray):
        """Append each lane's sampled token, then bill and finish."""
        tr = self.tracer
        ts_sync = tr.clock() if tr is not None else 0.0
        tenant_tokens: dict[str, int] = {}
        for i in active:
            req = self.slots[i]
            req.output.append(int(nxt[i]))
            if tr is not None:
                if req._t_last is not None:
                    tr.slo.itl(ts_sync - req._t_last, req.tenant, req.qos)
                req._t_last = ts_sync
            self.pos[i] += 1
            self.last_tok[i] = nxt[i]
            self.remaining[i] -= 1
            # one generated token + rent on the KV residency this slot
            # holds (dense lines, or the pages actually pinned)
            if self.paging is not None:
                self.admission.charge(req, tokens=1,
                                      kv_pages=self._billed_pages(i))
            else:
                self.admission.charge(req, tokens=1,
                                      kv_tokens=int(self.pos[i]))
            tenant_tokens[req.tenant] = tenant_tokens.get(req.tenant, 0) + 1
            self._maybe_finish(i)
        self.metrics.counter("serve_tokens_generated").inc(len(active))
        tok_counter = self.metrics.counter(
            METRIC_SERVE_TENANT_TOKENS, "generated tokens per tenant")
        for tenant, n in tenant_tokens.items():
            tok_counter.inc(n, tenant=tenant)

    def run_to_completion(self, max_steps: int = 10_000):
        for _ in range(max_steps):
            if self.step() == 0:
                break
