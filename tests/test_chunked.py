"""Continuous batching with chunked prefill (``max_batch_tokens``):
chunk-boundary bit-identity against classic whole-prompt paged serving,
head-of-line regression (a short's first token lands while a long
prompt is still mid-prefill), mid-prefill preemption at a chunk
boundary round-tripping through the tenant queue, SSM front-padded
bucketed prefill, the sdiag serve-step utilization section, the
--compare disjoint-percentile-key warning, and the chunked serve-step
dry-run twin."""
import numpy as np
import pytest

from repro.configs import RunConfig, get_reduced_config
from repro.serving import AdmissionController, DecodeEngine, Request


@pytest.fixture(scope="module")
def tiny_model():
    from repro.models import init_params
    cfg = get_reduced_config("stablelm-3b")
    return cfg, init_params(cfg, 0)


def _reqs(cfg, n=4, seed=3, **kw):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        4 + 5 * i).astype(np.int32),
                    max_new_tokens=6 + i, **kw)
            for i in range(n)]


def _run(eng, reqs):
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    assert all(r.done for r in reqs), [(r.rid, r.done) for r in reqs]
    return {r.rid: list(r.output) for r in reqs}


# ------------------------------------------------------- bit-identity ----

@pytest.mark.parametrize("budget", [1, 8, 13])
def test_chunked_greedy_identical_to_whole_prompt(tiny_model, budget):
    """Chunked prefill is just prefill_suffix applied repeatedly: greedy
    outputs must be bit-identical to classic whole-prompt paged serving
    at ANY budget — 1 (degenerate single-token chunks), 8 (= page_size,
    page-aligned chunks), 13 (odd: packs mixed 8/4/1 buckets, so chunks
    start and end mid-page)."""
    cfg, params = tiny_model
    ref = _run(DecodeEngine(cfg, params, num_slots=2, cache_len=64,
                            kv_page_size=8), _reqs(cfg))
    eng = DecodeEngine(cfg, params, num_slots=2, cache_len=64,
                       kv_page_size=8, max_batch_tokens=budget)
    got = _run(eng, _reqs(cfg))
    assert got == ref
    st = eng.serve_stats
    assert st["prefill_tokens"] == sum(len(r.prompt)
                                       for r in _reqs(cfg))
    assert st["prefill_chunks"] >= 1 and st["iterations"] >= 1
    # O(buckets) programs: every chunk of every request at every depth
    # reuses the per-bucket chunk/mixed programs
    assert eng.chunk_compilations() <= 2 * len(eng.chunk_buckets)


def test_chunked_identical_with_prefix_cache(tiny_model):
    """Budgeted admission composes with the radix prefix cache: a
    partial starts with the shared pages already mapped (pos_filled
    jumps past them) and only the suffix streams through chunks."""
    cfg, params = tiny_model
    rng = np.random.default_rng(9)
    shared = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)

    def reqs():
        return [Request(rid=i,
                        prompt=np.concatenate(
                            [shared, rng2.integers(
                                0, cfg.vocab_size,
                                3 + 5 * i).astype(np.int32)]),
                        max_new_tokens=5)
                for i in range(3)]

    rng2 = np.random.default_rng(4)
    ref = _run(DecodeEngine(cfg, params, num_slots=2, cache_len=64,
                            kv_page_size=8), reqs())
    rng2 = np.random.default_rng(4)
    eng = DecodeEngine(cfg, params, num_slots=2, cache_len=64,
                       kv_page_size=8, prefix_cache=True,
                       max_batch_tokens=8)
    got = _run(eng, reqs())
    assert got == ref
    from repro.monitoring.metrics import METRIC_SERVE_PREFIX_HITS
    assert eng.metrics.counter(METRIC_SERVE_PREFIX_HITS).value() >= 1


# ------------------------------------------------- head-of-line removal ----

def test_short_first_token_lands_mid_long_prefill(tiny_model):
    """THE continuous-batching property: a short prompt's first token is
    produced while a long prompt sharing the engine is still mid-prefill
    — classic serving can't do this (admission prefills the whole prompt
    in one shot before any other work)."""
    cfg, params = tiny_model
    long = Request(rid=0, prompt=np.arange(48, dtype=np.int32) % 50,
                   max_new_tokens=4)
    short = Request(rid=1, prompt=np.arange(5, dtype=np.int32),
                    max_new_tokens=8)
    eng = DecodeEngine(cfg, params, num_slots=2, cache_len=64,
                       kv_page_size=8, max_batch_tokens=16)
    eng.submit(long)
    eng.submit(short)
    saw_hol_removal = False
    for _ in range(100):
        n = eng.step()
        if short.output and not long.done:
            part = next((p for p in eng._partials
                         if p.req is long), None)
            if part is not None and part.pos_filled < len(long.prompt):
                saw_hol_removal = True
        if n == 0:
            break
    assert saw_hol_removal, "short waited for the long's whole prefill"
    assert long.done and short.done
    # and the outputs still match an uncontended classic run
    ref_long = Request(rid=0, prompt=long.prompt.copy(), max_new_tokens=4)
    ref_short = Request(rid=1, prompt=short.prompt.copy(),
                        max_new_tokens=8)
    ref = _run(DecodeEngine(cfg, params, num_slots=2, cache_len=64,
                            kv_page_size=8), [ref_long, ref_short])
    assert ref == {0: list(long.output), 1: list(short.output)}


# ------------------------------------------- mid-prefill preemption ----

def test_preempt_mid_prefill_round_trips_at_chunk_boundary(tiny_model):
    """A scavenger request preempted MID-PREFILL (pool pressure from a
    high-QOS arrival) lands back in its tenant queue at a chunk
    boundary: pages free, holdings return to zero, and the resumed
    prefill replays the prompt to an identical greedy output."""
    cfg, params = tiny_model
    scav = Request(rid=0, prompt=(np.arange(56, dtype=np.int32) % 50),
                   max_new_tokens=4, qos="scavenger")
    hi = Request(rid=1, prompt=(np.arange(96, dtype=np.int32) % 50),
                 max_new_tokens=4, qos="high")
    eng = DecodeEngine(cfg, params, num_slots=2, cache_len=128,
                       kv_page_size=8, kv_pages=17,  # 16 usable pages
                       max_batch_tokens=16)
    eng.submit(scav)
    eng.step()
    eng.step()                      # scav mid-prefill (32/56 tokens)
    assert eng._partials and not scav.done
    eng.submit(hi)                  # higher QOS chunks first; its pages
    eng.run_to_completion()         # (13) + scav's can't coexist (16)
    assert scav.done and hi.done
    assert scav.preemptions >= 1
    assert eng.admission.stats["requeues"] >= 1
    ref = _run(DecodeEngine(cfg, params, num_slots=2, cache_len=128,
                            kv_page_size=8),
               [Request(rid=0, prompt=scav.prompt.copy(),
                        max_new_tokens=4),
                Request(rid=1, prompt=hi.prompt.copy(),
                        max_new_tokens=4)])
    assert ref == {0: list(scav.output), 1: list(hi.output)}


def test_preempting_a_partial_beside_a_listed_twin(tiny_model):
    """Two scavenger partials share one rid, so comparing them by value
    reaches their prompt arrays (of unequal length).  A high-QOS arrival
    evicts the later one mid-prefill: it leaves the partial list by
    identity, its twin keeps prefilling, and every output matches
    classic whole-prompt serving."""
    cfg, params = tiny_model
    scavs = [Request(rid=7, prompt=(np.arange(40 + 8 * i, dtype=np.int32)
                                    % 50), max_new_tokens=3,
                     qos="scavenger") for i in range(2)]
    hi = Request(rid=8, prompt=(np.arange(12, dtype=np.int32) % 50) + 1,
                 max_new_tokens=3, qos="high")
    eng = DecodeEngine(cfg, params, num_slots=2, cache_len=128,
                       kv_page_size=8, max_batch_tokens=8)
    for r in scavs:
        eng.submit(r)
    eng.step()
    assert [p.req for p in eng._partials] == scavs
    eng.submit(hi)
    eng.step()
    victim = scavs[1]
    assert victim.preemptions == 1 and scavs[0].preemptions == 0
    assert [p.req for p in eng._partials] == [scavs[0], hi]
    eng.run_to_completion()
    assert all(r.done for r in scavs + [hi])
    for r in scavs + [hi]:
        ref = Request(rid=0, prompt=r.prompt.copy(), max_new_tokens=3)
        _run(DecodeEngine(cfg, params, num_slots=1, cache_len=128,
                          kv_page_size=8), [ref])
        assert list(r.output) == list(ref.output)


# ---------------------------------------------------- SSM front-pad ----

def test_ssm_front_padded_buckets_identical(tiny_model):
    """SSM configs no longer auto-disable bucketed prefill: the prompt
    front-pads to the bucket at a chunk-aligned offset whose masked
    positions are the SSD scan's identity, so outputs stay bit-identical
    to exact-length prefill — at O(buckets) compiles."""
    from repro.models import init_params
    cfg = get_reduced_config("mamba2-780m")
    params = init_params(cfg, 0)

    def reqs():
        rng = np.random.default_rng(5)
        return [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            3 + 7 * i).astype(np.int32),
                        max_new_tokens=5)
                for i in range(3)]

    ref_eng = DecodeEngine(cfg, params, num_slots=2, cache_len=64)
    assert ref_eng.prefill_buckets is None
    ref = _run(ref_eng, reqs())
    eng = DecodeEngine(cfg, params, num_slots=2, cache_len=64,
                       prefill_buckets="auto")
    assert eng.prefill_buckets is not None and eng._front_pad
    assert _run(eng, reqs()) == ref
    assert eng.prefill_compilations() <= len(eng.prefill_buckets)


# -------------------------------------------------- guards & surfaces ----

def test_budgeted_mode_requires_paging_and_fused(tiny_model):
    cfg, params = tiny_model
    with pytest.raises(ValueError, match="kv_page_size"):
        DecodeEngine(cfg, params, num_slots=2, cache_len=64,
                     max_batch_tokens=32)
    with pytest.raises(ValueError, match="fused"):
        DecodeEngine(cfg, params, num_slots=2, cache_len=64,
                     kv_page_size=8, fused=False, max_batch_tokens=32)


def test_sdiag_serve_step_utilization_golden():
    """The serve-step section duck-types the engine: iterations, budget
    fill ratio, and the decode/prefill token split."""
    from types import SimpleNamespace

    from repro.cluster import commands
    eng = SimpleNamespace(
        max_batch_tokens=64,
        serve_stats={"iterations": 10, "decode_tokens": 400,
                     "prefill_tokens": 200, "prefill_chunks": 5})
    out = commands.sdiag(engine=eng)
    assert out == "\n".join([
        "Serve-step utilization (token budget):",
        "\tIterations:       10",
        "\tToken budget:     64/step",
        "\tBudget fill:      600/640 (94%)",
        "\tDecode tokens:    400 (67%)",
        "\tPrefill tokens:   200 (33%, 5 chunks)",
    ])
    # engines without a token budget contribute no section
    classic = SimpleNamespace(max_batch_tokens=None, serve_stats={})
    assert commands.sdiag(engine=classic) == "sdiag: nothing to report"


def test_compare_warns_on_disjoint_percentile_keys(tmp_path, capsys):
    """Renaming a percentile key silently un-gates the benchmark; the
    gate must say so — naming BOTH key sets — instead of skipping the
    percentile comparison without a trace."""
    from benchmarks.run import compare_against, write_results
    path = tmp_path / "base.json"
    write_results([("b1", 100.0, "x", {"ttft_p99_ms": 5.0})], str(path))
    rc = compare_against(
        [("b1", 100.0, "x", {"ttft_p99_ms_budgeted": 5.0})], str(path))
    err = capsys.readouterr().err
    assert rc == 0                       # reported, never fails the gate
    assert "WARNING b1: no shared percentile keys" in err
    assert "ttft_p99_ms" in err and "ttft_p99_ms_budgeted" in err
    # shared keys still gate: no warning, regression caught
    rc = compare_against(
        [("b1", 100.0, "x", {"ttft_p99_ms": 9.0})], str(path))
    err = capsys.readouterr().err
    assert rc == 1 and "WARNING" not in err


def test_chunked_serve_step_lowers(tiny_model):
    from repro.configs.base import InputShape
    from repro.launch.mesh import make_mesh
    from repro.serving import (
        chunked_serve_step_lowering_args, make_chunked_serve_step,
    )
    cfg, _ = tiny_model
    run = RunConfig(strategy="dp", remat="none")
    mesh = make_mesh(1, 1)
    shape = InputShape("decode_smoke", 64, 2, "decode")
    step = make_chunked_serve_step(cfg, run, mesh, 2, 64, page_size=8,
                                   num_tokens=4)
    args = chunked_serve_step_lowering_args(cfg, run, mesh, shape,
                                            chunk=16, page_size=8)
    lowered = step.lower(*args)
    assert "while" in lowered.as_text() or "scan" in lowered.as_text()
