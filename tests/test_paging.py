"""Paged KV cache: allocator semantics, paged-vs-dense bit-identity
through the engine, the paged Pallas kernel vs its oracle, bucketed-prefill
x paging interaction (pad tails allocate and charge nothing), page-pool
exhaustion -> one-victim scavenger reclaim, kv_pages GrpTRES caps and
ledger residency, plus the sacctmgr modify satellite and the serve CLI's
device checks (--use-pallas off the TPU, --tp beyond the visible devices)
and its compile-cache directory."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import RunConfig, get_reduced_config
from repro.kernels import ops
from repro.kernels.ref import paged_decode_attention_ref
from repro.models.paging import (
    NULL_PAGE, PageAllocator, PagedKVConfig, page_visits, pages_for,
)
from repro.monitoring.metrics import METRIC_SERVE_PREEMPTIONS
from repro.policy import FairShareTree, QOS
from repro.serving import AdmissionController, DecodeEngine, Request

RNG = np.random.default_rng(7)


@pytest.fixture(scope="module")
def tiny_model():
    from repro.models import init_params
    cfg = get_reduced_config("stablelm-3b")
    return cfg, init_params(cfg, 0)


def _reqs(cfg, n=4, max_new=6, seed=3, plen=None, **kw):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        plen or (4 + 3 * i)).astype(np.int32),
                    max_new_tokens=max_new + (0 if plen else i), **kw)
            for i in range(n)]


def _run(cfg, params, reqs, num_slots=2, cache_len=64, **engine_kw):
    eng = DecodeEngine(cfg, params, num_slots=num_slots,
                       cache_len=cache_len, **engine_kw)
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    return eng


# -------------------------------------------------------------- allocator ----

def test_allocator_all_or_nothing_and_null_reserved():
    a = PageAllocator(6)                  # null + 5 usable
    assert a.available() == 5
    got = a.alloc(3)
    assert len(got) == 3 and NULL_PAGE not in got
    assert a.alloc(3) is None             # only 2 left: all-or-nothing
    assert a.available() == 2             # the failed alloc took nothing
    more = a.alloc(2)
    assert a.available() == 0 and a.in_use == 5 and a.high_water == 5
    a.free(got)
    assert a.available() == 3 and a.in_use == 2
    again = a.alloc(3)                    # freed pages are reusable
    assert sorted(again) == sorted(got)
    assert a.alloc(0) == []
    a.free(more + again)
    assert a.in_use == 0 and a.high_water == 5


def test_paged_config_budget_math():
    pc = PagedKVConfig.for_budget(4 * 128, 16, 128)
    assert pc.usable_pages == 32 and pc.num_pages == 33
    assert pc.pages_per_seq == 8 and pc.capacity_tokens == 512
    assert pages_for(0, 16) == 0
    assert pages_for(1, 16) == 1
    assert pages_for(16, 16) == 1
    assert pages_for(17, 16) == 2


# ---------------------------------------------------------- paged kernel ----

PAGED_CASES = [
    # (B, H, K, Dh, page_size, pool_pages, table_pages)
    (2, 4, 2, 64, 16, 12, 4),
    (1, 8, 8, 64, 32, 6, 2),      # MHA
    (3, 4, 1, 32, 8, 20, 8),      # MQA, many small pages
]


@pytest.mark.parametrize("B,H,K,Dh,ps,pool,npages", PAGED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_flash_decode_matches_oracle(B, H, K, Dh, ps, pool, npages,
                                           dtype):
    """The kernel reads layer ``l`` of a stacked (layers, pages, K, ps,
    Dh) pool in place; every layer matches the oracle on that layer."""
    layers = 3
    q = jnp.asarray(RNG.standard_normal((B, 1, H, Dh)), dtype)
    k = jnp.asarray(RNG.standard_normal((layers, pool, K, ps, Dh)), dtype)
    v = jnp.asarray(RNG.standard_normal((layers, pool, K, ps, Dh)), dtype)
    table = jnp.asarray(RNG.integers(1, pool, (B, npages)), jnp.int32)
    pos = jnp.asarray(RNG.integers(0, npages * ps, B), jnp.int32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    for layer in range(layers):
        out = ops.flash_decode_paged(q, k, v, table, pos,
                                     jnp.int32(layer), interpret=True)
        ref = paged_decode_attention_ref(q, k[layer], v[layer], table, pos)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=tol, rtol=tol)


@pytest.mark.parametrize("pos0", [6, 7, 8, 9, 23])
def test_paged_flash_decode_tails_and_page_boundaries(pos0):
    """Two lanes on disjoint pages of a stacked pool, each table row
    ending in null pages; ``pos`` on the last line of a page (7), on the
    first line of the next (8) and either side of them.  Every layer
    matches the oracle, and poisoning everything the lanes may not read
    (other layers, the null page, lines past ``pos``) changes nothing."""
    layers, pool, K, ps, Dh, H = 3, 9, 2, 8, 32, 4
    q = jnp.asarray(RNG.standard_normal((2, 1, H, Dh)), jnp.float32)
    k = RNG.standard_normal((layers, pool, K, ps, Dh)).astype(np.float32)
    v = RNG.standard_normal((layers, pool, K, ps, Dh)).astype(np.float32)
    # lane 0 owns pages 3, 5, 1; lane 1 owns 2, 8, 4; tails are null
    table = np.array([[3, 5, 1, 0, 0], [2, 8, 4, 0, 0]], np.int32)
    pos = np.array([pos0, min(pos0 + 1, 3 * ps - 1)], np.int32)
    for layer in range(layers):
        want = paged_decode_attention_ref(q, k[layer], v[layer], table, pos)
        got = ops.flash_decode_paged(q, jnp.asarray(k), jnp.asarray(v),
                                     table, pos, jnp.int32(layer),
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
        poisoned_k, poisoned_v = np.full_like(k, 1e4), np.full_like(v, 1e4)
        for b in range(2):
            for line in range(pos[b] + 1):
                page, off = table[b, line // ps], line % ps
                poisoned_k[layer, page, :, off] = k[layer, page, :, off]
                poisoned_v[layer, page, :, off] = v[layer, page, :, off]
        again = ops.flash_decode_paged(
            q, jnp.asarray(poisoned_k), jnp.asarray(poisoned_v), table, pos,
            jnp.int32(layer), interpret=True)
        np.testing.assert_allclose(np.asarray(again), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


LANE_WIDTHS = [
    # (K, G, Dh): starcoder2-3b's 2:24 under TP=2 and whole, trinity-mini's
    # 4:32, stablelm-3b's MHA heads of 80
    (1, 12, 128), (2, 12, 128), (4, 8, 128), (32, 1, 80),
]


@pytest.mark.parametrize("K,G,Dh", LANE_WIDTHS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("dead_pages", ["finite", "nan"])
def test_paged_flash_decode_reads_only_live_pages(K, G, Dh, dtype,
                                                  dead_pages):
    """One batch of lanes at the edges of the page loop: one live line,
    ``pos`` on the last line of a page and on the first of the next, a
    full table, and a finished lane whose table is all null pages.  Each
    lane's table runs on past its last live page into pages of its own;
    filled with NaN (K and V) they must change nothing, so the kernel
    does not read them."""
    ps, npages, layers, layer = 8, 4, 2, 1
    pos = np.array([0, ps - 1, ps, 2 * ps - 1, npages * ps - 1, 13],
                   np.int32)
    live = [p // ps + 1 for p in pos[:-1]]
    pool = 1 + npages * (len(pos) - 1)
    ids = iter(1 + RNG.permutation(pool - 1))
    table = np.zeros((len(pos), npages), np.int32)    # the last lane: null
    for b in range(len(pos) - 1):
        table[b] = [next(ids) for _ in range(npages)]
    dead = np.unique(np.concatenate(
        [table[b, n:] for b, n in enumerate(live)]))
    q = jnp.asarray(RNG.standard_normal((len(pos), 1, K * G, Dh)), dtype)
    k = RNG.standard_normal((layers, pool, K, ps, Dh)).astype(np.float32)
    v = RNG.standard_normal((layers, pool, K, ps, Dh)).astype(np.float32)
    want = paged_decode_attention_ref(
        q, jnp.asarray(k[layer], dtype), jnp.asarray(v[layer], dtype),
        table, pos)
    if dead_pages == "nan":
        k[:, dead], v[:, dead] = np.nan, np.nan
    got = ops.flash_decode_paged(q, jnp.asarray(k, dtype),
                                 jnp.asarray(v, dtype), table, pos,
                                 jnp.int32(layer), interpret=True)
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got, np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_page_visits_counts_each_tokens_live_pages():
    for start in range(0, 40):
        for n in range(0, 20):
            assert page_visits(start, n, 8) == sum(
                p // 8 + 1 for p in range(start, start + n))


def test_engine_counts_kv_pages_visited(tiny_model):
    """``kv_pages_visited`` / ``kv_pages_table`` against a hand count.
    A request of prompt P and N new tokens decodes N - 1 of them in
    decode dispatches, at positions P .. P+N-2 (the first comes from its
    prefill); the token at p reads p // 8 + 1 pages of 8 lines, and the
    budgeted engine dispatches the whole 8-page table.  In chunks of 4:
    A decodes positions 5, 6 and finishes mid-chunk; B decodes 6, 7, 8,
    9 (crossing into its second page mid-chunk) and then 10; C decodes
    17 alone."""
    cfg, params = tiny_model
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, plen)
                    .astype(np.int32), max_new_tokens=new)
            for i, (plen, new) in enumerate([(5, 3), (6, 6), (17, 2)])]
    eng = _run(cfg, params, reqs, num_slots=3, cache_len=64,
               kv_page_size=8, max_batch_tokens=32, decode_chunk=4)
    st = eng.serve_stats
    assert st["decode_tokens"] == 2 + 5 + 1
    assert st["kv_pages_visited"] == (1 + 1) + (1 + 1 + 2 + 2 + 2) + 3
    assert st["kv_pages_table"] == 8 * (2 + 5 + 1)


# ------------------------------------------------------ engine identity ----

def test_paged_greedy_bit_identical_to_dense(tiny_model):
    """Acceptance: greedy fused decode is bit-identical between the dense
    cache and the paged cache (both page sizes, chunk sizes that do and
    don't divide the generation lengths), and every page returns to the
    pool."""
    cfg, params = tiny_model
    ref = _reqs(cfg)
    _run(cfg, params, ref, decode_chunk=4)
    for page_size, chunk in ((8, 4), (16, 3)):
        got = _reqs(cfg)
        eng = _run(cfg, params, got, decode_chunk=chunk,
                   kv_page_size=page_size)
        assert [r.output for r in got] == [r.output for r in ref], page_size
        assert eng.allocator.in_use == 0
        assert (eng.page_tables == NULL_PAGE).all()


def _paged_program_args(eng, name, cache, C=8, S=3):
    """Arguments of one of the engine's paged programs, with ``cache`` in
    the pool's place (C: chunk tokens, S: verified tokens a lane)."""
    n, pps = eng.num_slots, eng.paging.pages_per_seq
    i32 = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
    table = i32(n, pps)
    lanes = [i32(n), i32(n), i32(n), np.ones(n, bool),
             np.full(n, -1, np.int32), np.zeros(n, np.float32), eng._key,
             table, np.full(n, eng.cache_len, np.int32)]
    chunk = [i32(1, C), i32(1, pps), np.int32(0), np.int32(C - 1),
             i32(C), i32(C)]
    return {"decode_n": [eng.params, cache] + lanes,
            "mixed_step": [eng.params, cache] + lanes + chunk,
            "chunk_fn": [eng.params, cache] + chunk,
            "verify_fn": [eng.params, cache, i32(n, S), i32(n), i32(n, S),
                          i32(n, S), table]}[name]


@pytest.mark.parametrize("program",
                         ["decode_n", "mixed_step", "chunk_fn", "verify_fn"])
def test_paged_programs_copy_no_layer_pool(program):
    """Guard against whole-pool copies in the paged programs: the layer
    loop carries the stacked pool, writes only the new lines and reads
    pages where they lie, so the compiled program's scratch stays below
    ONE layer's K+V pool although the pool is several times everything
    else.  Slicing a layer's pool out of the stack, stacking it back, or
    re-laying it for the kernel each need at least that much.

    float32 model and pool: the CPU backend widens a bfloat16 scatter to
    float32 over the whole operand (the TPU scatters bfloat16 in place,
    ``tests/test_tpu_compile.py`` holds that), which would hide the
    layout under test here."""
    import jax
    from repro.models import init_params
    cfg = dataclasses.replace(get_reduced_config("stablelm-3b"),
                              dtype="float32", param_dtype="float32")
    eng = DecodeEngine(cfg, init_params(cfg, 0), num_slots=4, cache_len=64,
                       kv_page_size=16, kv_pages=1025, decode_chunk=4,
                       max_batch_tokens=16, speculate=2)
    cache = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32), eng.cache)
    pool = cache["layers"][0]["k"]
    layer_kv = 2 * pool.dtype.itemsize * int(np.prod(pool.shape[1:]))
    params = sum(x.nbytes for x in jax.tree.leaves(eng.params))
    assert pool.shape[0] * layer_kv > 4 * params
    fn = getattr(eng, {"decode_n": "_decode_n"}.get(program, "_" + program))
    compiled = fn.lower(*_paged_program_args(eng, program, cache)).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_kv, (program, temp, layer_kv)


def test_paged_host_loop_matches_dense(tiny_model):
    cfg, params = tiny_model
    ref = _reqs(cfg, n=2)
    _run(cfg, params, ref, fused=False)
    got = _reqs(cfg, n=2)
    _run(cfg, params, got, fused=False, kv_page_size=8)
    assert [r.output for r in got] == [r.output for r in ref]


def test_paged_pallas_decode_matches_reference(tiny_model):
    """use_pallas routes paged decode through the paged split-KV kernel;
    greedy tokens must match the gathered-reference path."""
    cfg, params = tiny_model
    ref = _reqs(cfg, n=2, max_new=4)
    _run(cfg, params, ref, decode_chunk=4, kv_page_size=16)
    got = _reqs(cfg, n=2, max_new=4)
    _run(cfg, params, got, decode_chunk=4, kv_page_size=16,
         run=RunConfig(remat="none", use_pallas=True))
    assert [r.output for r in got] == [r.output for r in ref]


def test_paged_refused_for_ssm_and_ring_configs(tiny_model):
    from repro.models import init_params
    ssm_cfg = get_reduced_config("mamba2-780m")
    with pytest.raises(ValueError):
        DecodeEngine(ssm_cfg, init_params(ssm_cfg, 0), num_slots=1,
                     cache_len=32, kv_page_size=8)
    cfg, params = tiny_model
    win_cfg = dataclasses.replace(cfg, sliding_window=8)
    with pytest.raises(ValueError):
        DecodeEngine(win_cfg, params, num_slots=1, cache_len=32,
                     kv_page_size=8)


# ------------------------------------------------- bucketed x paged tails ----

def test_bucketed_pad_tail_allocates_and_charges_nothing(tiny_model):
    """Satellite acceptance: a 5-token prompt in a 32-bucket allocates
    ceil(5/16)=1 page — the 27 pad lines ride the null page and the
    ledger bills exactly one page."""
    cfg, params = tiny_model
    ctrl = AdmissionController()
    eng = DecodeEngine(cfg, params, num_slots=2, cache_len=64,
                       admission=ctrl, decode_chunk=4, kv_page_size=16,
                       prefill_buckets=(32, 64))
    eng.submit(Request(rid=0, prompt=np.arange(2, 7).astype(np.int32),
                       max_new_tokens=4, tenant="acct"))
    eng._admit()                           # prefill only, no decode growth
    slot = next(i for i, r in enumerate(eng.slots) if r is not None)
    assert len(eng._slot_pages[slot]) == 1
    assert eng.allocator.in_use == 1
    row = eng.page_tables[slot]
    assert row[0] != NULL_PAGE and (row[1:] == NULL_PAGE).all()
    assert ctrl.tree.tres_usage_of("acct")["gres/kv_page"] == 1.0
    eng.run_to_completion()
    assert eng.allocator.in_use == 0


def test_bucketed_paged_outputs_match_dense_bucketed(tiny_model):
    cfg, params = tiny_model
    ref = _reqs(cfg, n=3)
    _run(cfg, params, ref, decode_chunk=4, prefill_buckets=(16, 32, 64))
    got = _reqs(cfg, n=3)
    _run(cfg, params, got, decode_chunk=4, prefill_buckets=(16, 32, 64),
         kv_page_size=8)
    assert [r.output for r in got] == [r.output for r in ref]


# ------------------------------------------------- exhaustion / reclaim ----

def test_pool_exhaustion_evicts_one_scavenger_and_reclaims(tiny_model):
    """Satellite acceptance: when decode-time growth exhausts the pool, a
    normal-QOS slot reclaims by evicting exactly one scavenger victim;
    the victim requeues with output retained, resumes later, and both
    finish with every page back in the pool."""
    cfg, params = tiny_model
    ctrl = AdmissionController()
    # usable pages: 6 x 8 lines = 48 < 2 slots x 40-line demand
    eng = DecodeEngine(cfg, params, num_slots=2, cache_len=64,
                       admission=ctrl, decode_chunk=4, kv_page_size=8,
                       kv_pages=7)
    scav = Request(rid=0, prompt=np.arange(2, 10).astype(np.int32),
                   max_new_tokens=30, tenant="a", qos="scavenger")
    norm = Request(rid=1, prompt=np.arange(2, 10).astype(np.int32),
                   max_new_tokens=30, tenant="b", qos="normal")
    eng.submit(scav)
    eng.submit(norm)
    eng.run_to_completion()
    assert scav.done and norm.done
    assert scav.preemptions >= 1          # the reclaim victim
    assert eng.metrics.counter(METRIC_SERVE_PREEMPTIONS).value() >= 1
    assert len(scav.output) == 30 and len(norm.output) == 30
    assert eng.allocator.in_use == 0
    # resume correctness: the evicted run equals an undisturbed solo run
    solo = Request(rid=9, prompt=scav.prompt, max_new_tokens=30)
    _run(cfg, params, [solo], decode_chunk=4)
    assert scav.output == solo.output


def test_starved_slot_requeues_and_completes(tiny_model):
    """No evictable victim (all normal QOS) and a pool too small for both:
    the starved slot requeues (work retained, not truncated) and finishes
    once pages free up."""
    cfg, params = tiny_model
    eng = DecodeEngine(cfg, params, num_slots=2, cache_len=64,
                       decode_chunk=4, kv_page_size=8, kv_pages=7)
    reqs = _reqs(cfg, n=2, max_new=30, plen=8)
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    assert all(r.done and len(r.output) == 30 for r in reqs)
    assert eng.metrics.counter("serve_page_starvations").value() >= 1
    assert eng.allocator.in_use == 0


def test_reclaim_victim_below_requester_index_survives_dispatch(tiny_model):
    """Regression: a reclaim evicting a slot at a LOWER index than the
    growing slot must not leave a stale index in the step's active list
    (the readback loop would dereference the now-empty slot)."""
    cfg, params = tiny_model
    eng = DecodeEngine(cfg, params, num_slots=2, cache_len=64,
                       decode_chunk=4, kv_page_size=8, kv_pages=5)
    scav = Request(rid=0, prompt=np.arange(2, 10).astype(np.int32),
                   max_new_tokens=20, qos="scavenger")
    eng.submit(scav)
    eng.step()                             # scav runs in slot 0, grows
    assert scav._slot == 0 and not scav.done
    hi = Request(rid=1, prompt=np.arange(2, 10).astype(np.int32),
                 max_new_tokens=20, qos="high")
    eng.submit(hi)
    eng.run_to_completion()                # pre-fix: AttributeError here
    assert hi.done and scav.done
    assert eng.metrics.counter(METRIC_SERVE_PREEMPTIONS).value() >= 1
    assert eng.allocator.in_use == 0


def test_submit_refuses_footprint_larger_than_pool(tiny_model):
    """A request whose worst-case pages exceed the pool would be vetoed
    by page-budget admission forever — submit refuses it loudly."""
    cfg, params = tiny_model
    eng = DecodeEngine(cfg, params, num_slots=2, cache_len=64,
                       decode_chunk=4, kv_page_size=16, kv_pages=2)
    with pytest.raises(AssertionError):
        eng.submit(Request(rid=0, prompt=np.arange(2, 22).astype(np.int32),
                           max_new_tokens=8))


def test_kv_page_billing_scales_with_page_size(tiny_model):
    """One page bills like the lines it holds whatever the page size, so
    dense and paged tenants on one ledger stay fair-share comparable."""
    cfg, params = tiny_model
    for ps in (8, 32):
        ctrl = AdmissionController()
        DecodeEngine(cfg, params, num_slots=1, cache_len=64,
                     admission=ctrl, kv_page_size=ps)
        assert ctrl.tree.tres_weights["gres/kv_page"] == \
            pytest.approx(ps * ctrl.tree.tres_weights["gres/kv_token"])
    # an operator's explicit override survives
    tree = FairShareTree(tres_weights={"gres/kv_page": 1.0})
    ctrl = AdmissionController(tree=tree)
    DecodeEngine(cfg, params, num_slots=1, cache_len=64, admission=ctrl,
                 kv_page_size=8)
    assert tree.tres_weights["gres/kv_page"] == 1.0


def test_kv_pages_grp_tres_caps_tenant_residency(tiny_model):
    """GrpTRES {"kv_pages": N} bounds one tenant's concurrent HBM pages:
    with a 4-page cap and ~5-page requests (est: prompt+max_new), only
    one runs at a time even with slots to spare."""
    cfg, params = tiny_model
    qos_table = {"normal": QOS("normal", priority=500,
                               grp_tres={"kv_pages": 4})}
    ctrl = AdmissionController(qos_table=qos_table)
    eng = DecodeEngine(cfg, params, num_slots=4, cache_len=64,
                       admission=ctrl, decode_chunk=4, kv_page_size=8)
    reqs = _reqs(cfg, n=3, max_new=8, plen=16, tenant="capped")
    for r in reqs:
        eng.submit(r)
    assert all(r._est_pages == pages_for(16 + 8 + 1, 8) for r in reqs)
    peak = 0
    for _ in range(200):
        n = eng.step()
        peak = max(peak, eng.active())
        if n == 0:
            break
    assert all(r.done for r in reqs)
    assert peak == 1                       # cap serialized the tenant


# ------------------------------------------------------ sacctmgr modify ----

def _mini_cluster():
    from repro.cluster import Cluster, Node, Partition
    nodes = [Node(name=f"n{i:02d}", cpus=8, mem_mb=8192,
                  gres={"tpu": 4}, coord=(0, i)) for i in range(2)]
    parts = [Partition(name="gpu", nodes=tuple(n.name for n in nodes),
                       default=True)]
    c = Cluster(nodes, parts)
    c.fairshare.add_account("prod", shares=10)
    c.fairshare.add_account("research", shares=1)
    return c


def test_sacctmgr_modify_account_live_shares():
    from repro.cluster import commands
    c = _mini_cluster()
    before = c.fairshare.norm_shares("research")
    out = commands.sacctmgr_modify_account(c, "research", fairshare=30)
    assert "Fairshare=30" in out
    assert c.fairshare.norm_shares("research") > before
    # sshare reflects the edit on the next pass, no restart
    line = next(ln for ln in commands.sshare(c).splitlines()
                if "research" in ln)
    assert line.split()[1] == "30"


def test_sacctmgr_modify_account_validates():
    c = _mini_cluster()
    with pytest.raises(AssertionError):
        c.fairshare.modify_account("nope", shares=2)
    with pytest.raises(AssertionError):
        c.fairshare.modify_account("root", shares=2)
    c.fairshare.add_account("team", parent="prod")
    with pytest.raises(AssertionError):   # cycle: prod under its own child
        c.fairshare.modify_account("prod", parent="team")
    c.fairshare.modify_account("team", parent="research")
    assert c.fairshare.accounts["team"].parent == "research"


def test_sacctmgr_modify_qos_live():
    from repro.cluster import commands
    c = _mini_cluster()
    out = commands.sacctmgr_modify_qos(
        c, "scavenger", priority=42, grp_tres={"gres/tpu": 2})
    assert "priority=42" in out
    q = c.qos_table["scavenger"]
    assert q.priority == 42 and q.grp_tres == {"gres/tpu": 2}
    assert q.usage_factor == 0.25          # untouched fields survive
    assert "42" in commands.sacctmgr_show_qos(c)


def test_sshare_tres_column_reports_kv_pages():
    from repro.cluster import commands
    c = _mini_cluster()
    c.fairshare.charge_tres("research", {"gres/kv_page": 12.0})
    out = commands.sshare(c, tres=True)
    assert "TRESUsage" in out
    line = next(ln for ln in out.splitlines() if "research" in ln)
    assert "gres/kv_page=12" in line
    # default format unchanged (golden tests elsewhere)
    assert "TRESUsage" not in commands.sshare(c)


def test_tres_usage_decays_and_snapshots():
    t = FairShareTree(half_life_s=100.0)
    t.charge_tres("acct", {"gres/kv_page": 8.0, "tokens": 4.0})
    t.decay_to(100.0)                      # one half-life
    assert t.tres_usage_of("acct")["gres/kv_page"] == pytest.approx(4.0)
    restored = FairShareTree.restore(t.snapshot())
    assert restored.tres_usage_of("acct") == t.tres_usage_of("acct")


def test_tres_usage_reports_raw_consumption_not_billing_discount():
    """usage_factor is a billing break (scavenger pays 0.25x) but the
    per-key breakdown an auditor reads must show what was actually
    held."""
    t = FairShareTree(tres_weights={"gres/kv_page": 0.016})
    t.charge_tres("scav", {"gres/kv_page": 100.0}, usage_factor=0.25)
    assert t.usage["scav"] == pytest.approx(100.0 * 0.016 * 0.25)
    assert t.tres_usage_of("scav")["gres/kv_page"] == pytest.approx(100.0)


def test_kv_pages_cap_is_worst_case_reservation(tiny_model):
    """Decode-time growth cannot breach the GrpTRES cap: the hold
    reserves each request's worst-case footprint for its whole
    residency, so at cap 8 only two est-4 requests ever run at once —
    even while their actual allocations are still small."""
    cfg, params = tiny_model
    qos_table = {"normal": QOS("normal", priority=500,
                               grp_tres={"kv_pages": 8})}
    ctrl = AdmissionController(qos_table=qos_table)
    eng = DecodeEngine(cfg, params, num_slots=4, cache_len=64,
                       admission=ctrl, decode_chunk=2, kv_page_size=8)
    reqs = _reqs(cfg, n=3, max_new=14, plen=16, tenant="capped")  # est 4
    for r in reqs:
        eng.submit(r)
    peak_active = peak_hold = 0
    for _ in range(300):
        n = eng.step()
        peak_active = max(peak_active, eng.active())
        peak_hold = max(peak_hold, ctrl.tenants["capped"].pages_held)
        if n == 0:
            break
    assert all(r.done for r in reqs)
    assert peak_active == 2 and peak_hold <= 8


# ----------------------------------------------------------- serve CLI ----

def test_use_pallas_falls_back_on_cpu():
    """--use-pallas off the TPU is an error, not a quiet switch to the
    reference path."""
    from repro.launch.serve import resolve_use_pallas
    assert resolve_use_pallas(False, "cpu") is False
    assert resolve_use_pallas(False, "tpu") is False
    assert resolve_use_pallas(True, "tpu") is True
    with pytest.raises(ValueError, match="not TPU"):
        resolve_use_pallas(True, "cpu")


def test_tp_needs_enough_devices():
    """--tp N with fewer than N devices is an error, not a quiet tp=1."""
    from repro.launch.serve import resolve_tp
    assert resolve_tp(1, 1) == 1
    assert resolve_tp(2, 4) == 2
    with pytest.raises(ValueError, match="only 1 device"):
        resolve_tp(2, 1)


def test_compile_cache_dir(monkeypatch, tmp_path):
    """The persistent compile cache goes to $JAX_COMPILATION_CACHE_DIR
    when set, else to one fixed, git-ignored directory in the checkout."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch.compile_cache import (
        DEFAULT_CACHE_DIR, enable_compile_cache,
    )
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
    root = DEFAULT_CACHE_DIR.parent
    assert (root / "src" / "repro").is_dir()
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
