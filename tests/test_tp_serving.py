"""Tensor-parallel serving: sharding the model and the paged KV pool over
a mesh must be a pure *layout* change — greedy decode token-for-token
identical to TP=1 in every engine mode — with divisibility falling back
to replication (never crashing), the pool budget tracked per shard, the
ring flash-decode kernel matching the reference wrapped-slot mask, and
the sdiag TP section reporting the plan.

TP >= 2 needs real devices and this process pinned the platform to one
at import, so those tests subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=2`` (same recipe as
``test_parallelism.py``).  Host-side pieces — plan resolution, the
two-level page table, the sharded allocator view, the ring kernel and
the sdiag golden text — run in-process.
"""
import dataclasses
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.models.paging import (
    NULL_PAGE, PageAllocator, ShardedAllocatorView, TwoLevelPageTable,
)
from repro.serving.tp import TPPlan, cache_pspec, plan_tp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_tp2(code: str) -> str:
    """Run ``code`` in a subprocess with 2 forced host devices."""
    src = ("import os\n"
           "os.environ['XLA_FLAGS'] = "
           "'--xla_force_host_platform_device_count=2'\n" + code)
    r = subprocess.run([sys.executable, "-c", src], capture_output=True,
                       text=True, env=dict(os.environ, PYTHONPATH="src"),
                       cwd=REPO)
    assert r.returncode == 0 and "OK" in r.stdout, \
        (r.stdout[-2000:], r.stderr[-2000:])
    return r.stdout


_PREAMBLE = r'''
import dataclasses
import numpy as np, jax
assert len(jax.devices()) == 2, jax.devices()
from repro.configs.stablelm_3b import reduced
from repro.models import init_params
from repro.serving import DecodeEngine
from repro.serving.engine import Request
from repro.launch.mesh import make_mesh

# float32: the cross-TP bit-identity guarantee is for f32 models (TP
# reductions run in f32); bf16 logits quantize coarsely enough that a
# reassociated sum can flip an exact near-tie argmax
cfg = dataclasses.replace(reduced(), dtype="float32")
params = init_params(cfg, 0)
mesh = make_mesh(1, 2)

def serve(mesh, cfg=cfg, params=params, run=None, **kw):
    eng = DecodeEngine(cfg, params, num_slots=2, cache_len=64,
                       mesh=mesh, run=run, **kw)
    reqs = [Request(rid=0, prompt=np.arange(1, 9, dtype=np.int32),
                    max_new_tokens=6),
            Request(rid=1, prompt=np.arange(3, 17, dtype=np.int32),
                    max_new_tokens=5)]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    return [r.output for r in reqs], eng
'''


# --------------------------------------------- TP=2 bit-identity (2 devs) ----

def test_tp2_bit_identity_dense_paged_budgeted():
    """Classic dense, paged, and token-budgeted engines produce the SAME
    greedy tokens on a (1,2) mesh as on one device — and the sharded pool
    view drains to zero on every shard when the requests finish."""
    _run_tp2(_PREAMBLE + r'''
from repro.models.paging import NULL_PAGE

for kw in [dict(), dict(kv_page_size=8),
           dict(kv_page_size=8, max_batch_tokens=16)]:
    base, _ = serve(None, **kw)
    tpd, eng = serve(mesh, **kw)
    assert base == tpd, (kw, base, tpd)
    assert eng.tp.active and eng.tp.tp == 2, eng.tp
    assert eng.tp.shard_attn and eng.tp.shard_mlp, eng.tp
    if eng.paging is not None:
        vec = eng.pool_view.in_use_vector()
        assert vec.shape == (2,) and (vec == 0).all(), vec
        assert (eng.page_tables == NULL_PAGE).all()
        st = eng.tp_stats()
        assert st["kv_pages_in_use"] == [0, 0], st
        assert st["kv_pages_total"] == eng.paging.usable_pages
print("OK")
''')


def test_tp2_bit_identity_prefix_and_speculative():
    """Prefix-cache (COW page sharing) and speculative (draft-and-verify
    scatter) engines stay bit-identical under TP=2, and a starved pool
    requeues without leaking pages on either shard."""
    _run_tp2(_PREAMBLE + r'''
for kw in [dict(kv_page_size=8, prefix_cache=True),
           dict(kv_page_size=8, speculate=2)]:
    base, _ = serve(None, **kw)
    tpd, eng = serve(mesh, **kw)
    assert base == tpd, (kw, base, tpd)

# tiny pool: 3 usable pages, requests need 2+3 -> the second starves
# until the first finishes; the requeue must free pages on EVERY shard
kw = dict(kv_page_size=8, kv_pages=4)
base, _ = serve(None, **kw)
tpd, eng = serve(mesh, **kw)
assert base == tpd, (base, tpd)
assert (eng.pool_view.in_use_vector() == 0).all()
print("OK")
''')


def test_tp2_pallas_and_nondivisible_fallback():
    """The Pallas flash-decode kernel runs per-shard inside shard_map
    (each shard sees K/tp KV heads, grid unchanged); head counts that do
    not divide the mesh axis replicate attention with a notice while the
    MLP still shards — output unchanged either way."""
    _run_tp2(_PREAMBLE + r'''
import dataclasses
from repro.configs.base import RunConfig

rc = RunConfig(remat="none", use_pallas=True)
base, _ = serve(None, run=rc, kv_page_size=8)
tpd, eng = serve(mesh, run=rc, kv_page_size=8)
assert base == tpd, (base, tpd)

cfg3 = dataclasses.replace(cfg, num_kv_heads=3, num_heads=3)
params3 = init_params(cfg3, 0)
base, _ = serve(None, cfg=cfg3, params=params3, kv_page_size=8)
tpd, eng = serve(mesh, cfg=cfg3, params=params3, kv_page_size=8)
assert base == tpd, (base, tpd)
assert eng.tp.shard_attn is False and eng.tp.shard_mlp is True
assert any("not divisible" in n for n in eng.tp.notices), eng.tp.notices
print("OK")
''')


# --------------------------------------------------- plan resolution ----

class _FakeMesh:
    """Just enough Mesh surface for plan_tp (no devices needed)."""

    def __init__(self, tp):
        self.shape = {"data": 1, "model": tp}
        self.axis_names = ("data", "model")
        self.devices = np.empty((1, tp), object)


def test_plan_tp_divisibility_policy():
    cfg = get_reduced_config("stablelm-3b")
    plan = plan_tp(cfg, _FakeMesh(2))
    assert plan.shard_attn and plan.shard_mlp and plan.active
    assert plan.notices == []
    # non-divisible heads: attention replicates, MLP still shards
    cfg3 = dataclasses.replace(cfg, num_kv_heads=3, num_heads=3)
    plan = plan_tp(cfg3, _FakeMesh(2))
    assert not plan.shard_attn and plan.shard_mlp and plan.active
    assert any("not divisible" in n for n in plan.notices)
    # nothing divides: fully replicated, inactive (engine skips shard_map)
    plan = plan_tp(cfg, _FakeMesh(5))
    assert not plan.shard_attn and not plan.shard_mlp and not plan.active
    assert any("nothing shardable" in n for n in plan.notices)
    # no mesh / tp=1: inert plan
    assert not plan_tp(cfg, None).active
    assert not plan_tp(cfg, _FakeMesh(1)).active


def test_plan_tp_psums_and_describe():
    cfg = get_reduced_config("stablelm-3b")          # 2 attn+mlp layers
    plan = plan_tp(cfg, _FakeMesh(2))
    assert plan.psums_per_token(cfg) == {"attn_out": 2, "mlp_out": 2}
    assert "attn(heads 4->2/shard" in plan.describe(cfg)
    plan3 = plan_tp(dataclasses.replace(cfg, num_kv_heads=3, num_heads=3),
                    _FakeMesh(2))
    assert plan3.psums_per_token(cfg) == {"attn_out": 0, "mlp_out": 2}


def test_cache_pspec_targets_kv_head_dim():
    cfg = get_reduced_config("stablelm-3b")
    plan = TPPlan(mesh=None, tp=2, shard_attn=True)
    spec = cache_pspec(plan, cfg)
    assert tuple(spec) == (None, None, None, "model", None)
    # the paged pool (G, pages, K, ps, Dh) carries kv heads at dim 2
    assert tuple(cache_pspec(plan, cfg, paging=True)) == \
        (None, None, "model", None, None)
    assert cache_pspec(TPPlan(mesh=None, tp=2, shard_attn=False), cfg) \
        == cache_pspec(TPPlan(mesh=None), None)


# ---------------------------------------------------- pool primitives ----

def test_sharded_allocator_view_vectors():
    alloc = PageAllocator(num_pages=5)               # 4 usable
    view = ShardedAllocatorView(alloc, shards=2)
    assert list(view.available_vector()) == [4, 4]
    pages = alloc.alloc(3)
    assert list(view.in_use_vector()) == [3, 3]
    assert view.min_available() == 1
    alloc.free(pages)
    assert list(view.in_use_vector()) == [0, 0]
    assert view.min_available() == 4


def test_two_level_page_table_round_trip():
    t = TwoLevelPageTable(num_slots=2, pages_per_seq=128, leaf_size=32)
    # a mapping crossing a leaf boundary lands intact
    t.set_range(0, 30, [7, 8, 9, 10])
    row = t.row(0)
    assert list(row[30:34]) == [7, 8, 9, 10]
    assert (np.delete(row, range(30, 34)) == NULL_PAGE).all()
    assert t.max_width() == 34
    # dense() at a narrow width truncates, at full width covers all slots
    t.set_range(1, 0, [3])
    d = t.dense(4)
    assert d.shape == (2, 4) and d[1, 0] == 3
    assert t.dense().shape == (2, 128)
    # host memory scales with leaves touched, not slots*pages_per_seq
    assert t.directory_leaves == 3                   # slot0: 2, slot1: 1
    t.clear(0)
    assert (t.row(0) == NULL_PAGE).all() and t.max_width() == 1
    assert t.directory_leaves == 1


def test_two_level_page_table_leaf_clamp():
    # leaf wider than the table clamps so one leaf covers the whole row
    t = TwoLevelPageTable(num_slots=1, pages_per_seq=4, leaf_size=32)
    assert t.leaf_size == 4
    t.set_range(0, 0, [1, 2, 3, 4])
    assert list(t.row(0)) == [1, 2, 3, 4]
    with pytest.raises(AssertionError):
        t.set_range(0, 3, [5, 6])                    # past pages_per_seq


# ------------------------------------------------- ring flash-decode ----

def _ring_decode_ref(q, k, v, pos, window):
    """Numpy oracle: wrapped-slot mask + softmax, one head at a time."""
    B, _, H, Dh = q.shape
    S, K = k.shape[1], k.shape[2]
    G = H // K
    out = np.zeros((B, 1, H, Dh), np.float32)
    for b in range(B):
        slots = np.arange(S)
        slot_pos = pos[b] - ((pos[b] - slots) % S)
        mask = (slot_pos >= 0) & ((pos[b] - slot_pos) < window)
        for h in range(H):
            kh = h // G                              # grouped-query layout
            s = (q[b, 0, h] @ k[b, :, kh].T) * (Dh ** -0.5)
            s = np.where(mask, s, -1e30)
            p = np.exp(s - s.max())
            p = np.where(mask, p, 0.0)
            out[b, 0, h] = (p / p.sum()) @ v[b, :, kh]
    return out


def test_ring_flash_decode_matches_oracle():
    """``window`` turns the split-KV kernel's validity mask into the
    wrapped slot->position map; masking must match the reference ring
    math exactly (wrapped, partially-filled, and unwrapped positions)."""
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    B, H, K, Dh, window = 3, 4, 2, 16, 12
    S = window                                       # ring of min(len, win)
    q = rng.standard_normal((B, 1, H, Dh)).astype(np.float32)
    k = rng.standard_normal((B, S, K, Dh)).astype(np.float32)
    v = rng.standard_normal((B, S, K, Dh)).astype(np.float32)
    for pos in ([0, 5, 11], [13, 25, 31]):           # pre- and post-wrap
        pos = np.asarray(pos, np.int32)
        out = ops.flash_decode(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), jnp.asarray(pos),
                               block_k=4, interpret=True, window=window)
        ref = _ring_decode_ref(q, k, v, pos, window)
        np.testing.assert_allclose(np.asarray(out), ref,
                                   atol=2e-5, rtol=2e-5)


def test_windowed_engine_pallas_matches_reference():
    """End to end: a sliding-window config decodes through the ring
    kernel (cache wraps past ``window`` tokens) with the same greedy
    tokens as the jnp reference path."""
    from repro.configs.base import RunConfig
    from repro.models import init_params
    from repro.serving import DecodeEngine
    from repro.serving.engine import Request

    cfg = get_reduced_config("stablelm-3b").with_sliding_window(16)
    params = init_params(cfg, 0)

    def serve(use_pallas):
        eng = DecodeEngine(cfg, params, num_slots=2, cache_len=64,
                           run=RunConfig(remat="none",
                                         use_pallas=use_pallas))
        reqs = [Request(rid=0, prompt=np.arange(1, 13, dtype=np.int32),
                        max_new_tokens=10),          # crosses the wrap
                Request(rid=1, prompt=np.arange(3, 9, dtype=np.int32),
                        max_new_tokens=6)]
        for r in reqs:
            eng.submit(r)
        eng.run_to_completion()
        return [r.output for r in reqs]

    assert serve(False) == serve(True)


# ------------------------------------------------------ sdiag surface ----

def test_sdiag_tp_golden():
    from types import SimpleNamespace

    from repro.cluster import commands
    eng = SimpleNamespace(
        max_batch_tokens=None, speculate=0,
        tp=SimpleNamespace(tp=2),
        tp_stats=lambda: {
            "tp": 2, "active": True,
            "plan": "tp=2 attn(heads 4->2/shard, kv 4->2/shard), "
                    "mlp(ffn 512->256/shard)",
            "devices": ["TFRT_CPU_0", "TFRT_CPU_1"],
            "notices": ["d_ff=512 example notice"],
            "psums_per_token": {"attn_out": 2, "mlp_out": 2},
            "kv_pages_in_use": [4, 4], "kv_pages_total": 8})
    assert commands.sdiag(engine=eng) == "\n".join([
        "Tensor parallelism:",
        "\tPlan:             tp=2 attn(heads 4->2/shard, kv 4->2/shard), "
        "mlp(ffn 512->256/shard)",
        "\tDevices:          2 (TFRT_CPU_0, TFRT_CPU_1)",
        "\tPsums/token:      4 (attn_out 2, mlp_out 2)",
        "\tKV pool shard 0:  4/8 pages (50%)",
        "\tKV pool shard 1:  4/8 pages (50%)",
        "\tNotice:           d_ff=512 example notice",
    ])
    # tp=1 engines contribute no section
    off = SimpleNamespace(max_batch_tokens=None, speculate=0,
                          tp=SimpleNamespace(tp=1))
    assert commands.sdiag(engine=off) == "sdiag: nothing to report"
