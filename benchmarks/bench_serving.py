"""Serving benchmarks: device-resident fused decode vs the per-token host
loop (the fast-path claim), batched-decode throughput scaling with slot
count (the continuous-batching claim), bucketed-prefill compile counts,
paged-KV concurrent capacity at a fixed HBM budget (the PagedAttention
claim), radix prefix-cache prefill reduction for shared system prompts
(the SGLang-RadixAttention claim), speculative decoding throughput on
repeat-heavy single-stream workloads (the draft-and-verify claim),
tensor-parallel concurrent capacity at a fixed per-device HBM budget
(the sharded-KV-pool claim), and prefill latency vs prompt length."""
from __future__ import annotations

import time

import numpy as np

from repro.configs import get_reduced_config
from repro.models import init_params
from repro.serving import DecodeEngine, Request


def _throughput(cfg, params, slots: int, **engine_kw):
    """Returns (tokens/sec, wall seconds) for 8 requests x 16 new tokens."""
    rng = np.random.default_rng(0)
    eng = DecodeEngine(cfg, params, num_slots=slots, cache_len=128,
                       **engine_kw)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, 16).astype(
                        np.int32), max_new_tokens=16)
            for i in range(8)]
    for r in reqs:
        eng.submit(r)
    eng.step()                          # absorb compile time
    warm = int(eng.metrics.counter("serve_tokens_generated").value())
    t0 = time.perf_counter()
    eng.run_to_completion()
    dt = time.perf_counter() - t0
    # count only tokens generated inside the timed window (the fused
    # warm-up step emits a whole chunk, so including it would flatter
    # the fused numbers)
    toks = int(eng.metrics.counter("serve_tokens_generated").value()) - warm
    return toks / dt, dt


def bench_decode_throughput(results: list):
    """Host loop vs fused chunk at 1 and 4 slots.  Claims asserted:
    batching scales (4 slots > 1.3x 1 slot on the host path) and the
    device-resident fast path is >= 2x the per-token host loop at 4
    slots."""
    cfg = get_reduced_config("stablelm-3b")
    params = init_params(cfg, 0)
    host, fused = {}, {}
    for slots in (1, 4):
        host[slots], dt = _throughput(cfg, params, slots, fused=False)
        results.append((f"decode_throughput_slots{slots}", dt * 1e6,
                        f"{host[slots]:,.0f} tok/s host loop"))
    for slots in (1, 4):
        fused[slots], dt = _throughput(cfg, params, slots, decode_chunk=8,
                                       prefill_buckets="auto")
        results.append((f"decode_throughput_fused_slots{slots}", dt * 1e6,
                        f"{fused[slots]:,.0f} tok/s fused chunk=8 "
                        f"({fused[slots] / host[slots]:.1f}x host)"))
    # batching must help, and the fused path must beat per-token dispatch
    assert host[4] > host[1] * 1.3, (host, fused)
    assert fused[4] >= host[4] * 2.0, (host, fused)


def bench_prefill_bucketed(results: list):
    """Mixed prompt lengths through bucketed prefill: compilations are
    bounded by the bucket count, not the number of distinct lengths."""
    cfg = get_reduced_config("stablelm-3b")
    params = init_params(cfg, 0)
    rng = np.random.default_rng(2)
    eng = DecodeEngine(cfg, params, num_slots=4, cache_len=128,
                       decode_chunk=8, prefill_buckets="auto")
    lengths = [int(p) for p in rng.integers(4, 100, 20)]
    t0 = time.perf_counter()
    for i, plen in enumerate(lengths):
        eng.submit(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, plen).astype(
                np.int32), max_new_tokens=2))
    eng.run_to_completion()
    dt = time.perf_counter() - t0
    compiles = eng.prefill_compilations()
    buckets = eng.prefill_buckets
    results.append(("prefill_bucketed", dt * 1e6,
                    f"{compiles} prefill compiles for {len(lengths)} "
                    f"prompts ({len(set(lengths))} distinct lengths, "
                    f"{len(buckets)} buckets)"))
    assert compiles <= len(buckets), (compiles, buckets)


def bench_paged_capacity(results: list):
    """The paged-KV headline claim: at the SAME HBM budget, page tables
    serve >= 2x the concurrent short requests a dense per-slot cache can,
    because a short request holds ceil(tokens/page) pages instead of
    pinning cache_len lines.  Budget: 4 dense slots x 128 lines = 512
    lines = 32 usable 16-line pages."""
    cfg = get_reduced_config("stablelm-3b")
    params = init_params(cfg, 0)
    cache_len, page = 128, 16
    budget_lines = 4 * cache_len

    def peak_concurrency(**engine_kw):
        rng = np.random.default_rng(3)
        eng = DecodeEngine(cfg, params, cache_len=cache_len,
                           decode_chunk=4, prefill_buckets="auto",
                           **engine_kw)
        for i in range(24):                 # short: ~2 pages each
            eng.submit(Request(
                rid=i, prompt=rng.integers(0, cfg.vocab_size, 12).astype(
                    np.int32), max_new_tokens=12))
        peak, t0 = 0, time.perf_counter()
        for _ in range(2_000):
            n = eng.step()
            peak = max(peak, eng.active())
            if n == 0:
                break
        return peak, time.perf_counter() - t0, eng

    # dense: the budget caps the engine at 4 whole-cache slots
    dense_peak, dense_dt, _ = peak_concurrency(
        num_slots=budget_lines // cache_len)
    # paged: same budget in pages; slots bounded by the page pool instead
    paged_peak, paged_dt, eng = peak_concurrency(
        num_slots=16, kv_page_size=page,
        kv_pages=budget_lines // page + 1)
    results.append(("serving_paged_capacity", paged_dt * 1e6,
                    f"peak {paged_peak} concurrent vs {dense_peak} dense "
                    f"at equal budget ({paged_peak / dense_peak:.1f}x, "
                    f"high-water {eng.allocator.high_water}/"
                    f"{eng.paging.usable_pages} pages)"))
    assert paged_peak >= 2 * dense_peak, (paged_peak, dense_peak)


def bench_prefix_reuse(results: list):
    """The prefix-cache headline claim: 16 requests sharing a long system
    prompt (400 of 408 prompt tokens common) spend >= 2x less wall time
    in prefill when the radix index maps the shared pages read-only and
    only the per-request suffix runs — measured >= 3x target — at the
    same HBM budget, with greedy outputs bit-identical to the no-reuse
    path."""
    cfg = get_reduced_config("stablelm-3b")
    params = init_params(cfg, 0)
    rng = np.random.default_rng(5)
    cache_len, page = 512, 16
    system = rng.integers(2, cfg.vocab_size, 400).astype(np.int32)
    tails = [rng.integers(2, cfg.vocab_size, 8).astype(np.int32)
             for _ in range(16)]

    def serve(prefix_cache):
        eng = DecodeEngine(cfg, params, num_slots=8, cache_len=cache_len,
                           decode_chunk=4, prefill_buckets="auto",
                           kv_page_size=page, prefix_cache=prefix_cache)
        # warm-up: compiles the prefill programs (full + suffix buckets)
        # and, with reuse on, seeds the radix index — so the timed window
        # measures prefill math, not compilation
        for rid, tail in ((100, tails[0]), (101, tails[1])):
            eng.submit(Request(rid=rid,
                               prompt=np.concatenate([system, tail]),
                               max_new_tokens=2))
        eng.run_to_completion()
        hist = eng.metrics.histogram("serve_prefill_seconds")
        base = hist.sum()
        reqs = [Request(rid=i, prompt=np.concatenate([system, tail]),
                        max_new_tokens=8)
                for i, tail in enumerate(tails)]
        for r in reqs:
            eng.submit(r)
        eng.run_to_completion()
        return hist.sum() - base, [r.output for r in reqs], eng

    full_t, full_out, _ = serve(False)
    reuse_t, reuse_out, eng = serve(True)
    speedup = full_t / reuse_t
    reused = int(eng.metrics.counter("serve_prefix_reused_tokens").value())
    results.append(("serving_prefix_reuse", reuse_t * 1e6,
                    f"prefill {speedup:.1f}x faster with prefix reuse "
                    f"({full_t * 1e3:.0f} -> {reuse_t * 1e3:.0f} ms for 16 "
                    f"shared-prompt requests, {reused} tokens reused)"))
    # greedy decode must not notice the reuse — bit-identical outputs
    assert reuse_out == full_out, "prefix reuse changed greedy output"
    assert speedup >= 2.0, (full_t, reuse_t)


def bench_latency_slo(results: list):
    """Request-lifecycle tracing on a bursty two-tenant mixed-length
    workload: records TTFT/ITL percentiles from the tracer's derived SLO
    histograms into the bench JSON (so ``run.py --compare`` can gate on
    p99 TTFT regressions) and asserts the tracer costs < 5% tok/s vs
    tracing disabled.  The tracer attaches to an already-warm engine, so
    both measurements run the same compiled programs."""
    from repro.monitoring import Tracer
    from repro.monitoring.trace import METRIC_SERVE_ITL, METRIC_SERVE_TTFT
    from repro.serving import AdmissionController

    cfg = get_reduced_config("stablelm-3b")
    params = init_params(cfg, 0)
    rng = np.random.default_rng(7)
    admission = AdmissionController()
    admission.add_tenant("interactive", shares=4)
    admission.add_tenant("batch", shares=1)
    eng = DecodeEngine(cfg, params, num_slots=4, cache_len=128,
                       decode_chunk=8, prefill_buckets="auto",
                       admission=admission)

    def make_requests():
        reqs = []
        for i in range(12):
            short = i % 2 == 0
            reqs.append(Request(
                rid=i,
                prompt=rng.integers(0, cfg.vocab_size,
                                    8 if short else 48).astype(np.int32),
                max_new_tokens=16,
                tenant="interactive" if short else "batch",
                qos="high" if short else "normal"))
        return reqs

    def serve_burst():
        reqs = make_requests()
        warm = int(eng.metrics.counter("serve_tokens_generated").value())
        t0 = time.perf_counter()
        for w in range(2):                       # two bursts: real queueing
            for r in reqs[w * 6:(w + 1) * 6]:
                eng.submit(r)
            if w == 0:
                eng.step()
                eng.step()
        eng.run_to_completion()
        dt = time.perf_counter() - t0
        toks = int(
            eng.metrics.counter("serve_tokens_generated").value()) - warm
        return toks / dt, dt

    serve_burst()                                # absorb compile time
    base_tps = max(serve_burst()[0] for _ in range(2))
    tracer = Tracer()
    eng.tracer = tracer                          # attach post-warm-up
    eng.admission.tracer = tracer
    traced = [serve_burst() for _ in range(2)]
    traced_tps = max(t for t, _ in traced)
    ttft = tracer.metrics.histogram(METRIC_SERVE_TTFT)
    itl = tracer.metrics.histogram(METRIC_SERVE_ITL)
    labels = {"tenant": "interactive", "qos": "high"}
    assert ttft.count(**labels) > 0 and itl.count(**labels) > 0, \
        "tracer recorded no interactive-tier SLO samples"
    percentiles = {
        "ttft_p50_ms": round(ttft.quantile(0.5, **labels) * 1e3, 3),
        "ttft_p99_ms": round(ttft.quantile(0.99, **labels) * 1e3, 3),
        "itl_p50_ms": round(itl.quantile(0.5, **labels) * 1e3, 3),
        "itl_p99_ms": round(itl.quantile(0.99, **labels) * 1e3, 3),
    }
    results.append(("serving_latency_slo", traced[-1][1] * 1e6,
                    f"{traced_tps:,.0f} tok/s traced vs {base_tps:,.0f} "
                    f"untraced ({1 - traced_tps / base_tps:+.1%} overhead), "
                    f"interactive TTFT p99 {percentiles['ttft_p99_ms']:.1f}ms",
                    percentiles))
    assert traced_tps >= 0.95 * base_tps, (base_tps, traced_tps)


def bench_chunked_prefill_ttft(results: list):
    """The continuous-batching headline claim: under a bursty two-tenant
    mixed-length workload, token-budgeted serving (``max_batch_tokens``)
    improves short-request p99 TTFT >= 2x over classic paged serving —
    a long prompt's whole-prompt prefill no longer head-of-line blocks
    the wave's short prompts, because the budgeted engine admits it as a
    partial and packs its prefill chunk-by-chunk AFTER the shorts —
    while aggregate throughput stays within 10% and greedy outputs stay
    bit-identical.  The long prompt (700 tokens, 1024 cache) sits just
    past half its power-of-two prefill bucket, so classic serving also
    pays ~1.5x padding compute per prefill that the chunked path — which
    only ever computes real tokens — never does."""
    cfg = get_reduced_config("stablelm-3b")
    params = init_params(cfg, 0)
    cache_len, page = 1024, 16
    waves, shorts_per_wave = 4, 3

    def make_wave(w, rng):
        # the long submits FIRST so classic admission picks it first
        # (arrival order breaks the fair-share tie) and its whole-prompt
        # prefill blocks the wave's shorts — the HOL scenario
        reqs = [Request(rid=w * 10,
                        prompt=rng.integers(
                            2, cfg.vocab_size, 700).astype(np.int32),
                        max_new_tokens=4, tenant="batch")]
        for i in range(shorts_per_wave):
            reqs.append(Request(
                rid=w * 10 + 1 + i,
                prompt=rng.integers(2, cfg.vocab_size,
                                    8 + 2 * i).astype(np.int32),
                max_new_tokens=64, tenant="interactive"))
        return reqs

    def serve(max_batch_tokens):
        from repro.serving import AdmissionController
        admission = AdmissionController()
        admission.add_tenant("interactive", shares=4)
        admission.add_tenant("batch", shares=1)
        eng = DecodeEngine(cfg, params, num_slots=1 + shorts_per_wave,
                           cache_len=cache_len, decode_chunk=8,
                           prefill_buckets="auto", kv_page_size=page,
                           admission=admission,
                           max_batch_tokens=max_batch_tokens)
        rng = np.random.default_rng(11)
        for r in make_wave(9, rng):      # warm-up wave: absorb compiles
            eng.submit(r)
        eng.run_to_completion()
        warm = int(eng.metrics.counter("serve_tokens_generated").value())
        ttfts, outputs = [], {}
        t0 = time.perf_counter()
        for w in range(waves):
            wave = make_wave(w, rng)
            t_submit = time.perf_counter()
            for r in wave:
                eng.submit(r)
            pending = {r.rid: r for r in wave if r.tenant == "interactive"}
            while eng.step() > 0:
                now = time.perf_counter()
                for rid in [i for i, r in pending.items() if r.output]:
                    ttfts.append(now - t_submit)
                    del pending[rid]
            outputs.update((r.rid, list(r.output)) for r in wave)
        dt = time.perf_counter() - t0
        toks = int(
            eng.metrics.counter("serve_tokens_generated").value()) - warm
        return np.asarray(sorted(ttfts)), toks / dt, outputs

    ttft_base, tps_base, out_base = serve(None)
    ttft_chunk, tps_chunk, out_chunk = serve(128)
    p99_base = float(np.quantile(ttft_base, 0.99))
    p99_chunk = float(np.quantile(ttft_chunk, 0.99))
    speedup = p99_base / p99_chunk
    results.append((
        "serving_chunked_prefill", p99_chunk * 1e6,
        f"short-request p99 TTFT {speedup:.1f}x better with chunked "
        f"prefill ({p99_base * 1e3:.0f} -> {p99_chunk * 1e3:.0f} ms), "
        f"{tps_chunk:,.0f} vs {tps_base:,.0f} tok/s",
        {"ttft_p99_ms_budgeted": round(p99_chunk * 1e3, 3),
         "ttft_p99_ms_classic": round(p99_base * 1e3, 3)}))
    # greedy decode must not notice the chunking — bit-identical outputs
    assert out_chunk == out_base, "chunked prefill changed greedy output"
    assert speedup >= 2.0, (p99_base, p99_chunk)
    assert tps_chunk >= 0.9 * tps_base, (tps_base, tps_chunk)


def bench_speculative_tokps(results: list):
    """The speculative-decoding headline claim: on a repeat-heavy
    single-stream workload (the regime where batching cannot help —
    one request, lanes idle), prompt-lookup draft-and-verify with k=4
    lifts decode throughput >= 1.3x over the fused non-speculative
    engine (measured ~3x), with greedy output bit-identical: a verify
    round scores all drafts in ONE dispatch whose rows reproduce the
    sequential decode logits exactly, so wrong drafts cost speed, never
    tokens.  The acceptance rate lands in the bench JSON so ``run.py
    --compare`` can catch draft-quality regressions separately from
    raw tok/s."""
    cfg = get_reduced_config("stablelm-3b")
    params = init_params(cfg, 0)
    rng = np.random.default_rng(0)
    base = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    prompt = np.concatenate([base] * 6)      # looped phrase: drafts match

    def serve(speculate):
        eng = DecodeEngine(cfg, params, num_slots=1, cache_len=256,
                           decode_chunk=8, prefill_buckets="auto",
                           kv_page_size=16, speculate=speculate)
        # warm-up request absorbs compiles (and, with speculation on,
        # feeds the cross-request n-gram index like a steady state would)
        eng.submit(Request(rid=99, prompt=prompt.copy(),
                           max_new_tokens=96))
        eng.run_to_completion()
        warm = int(eng.metrics.counter("serve_tokens_generated").value())
        req = Request(rid=0, prompt=prompt.copy(), max_new_tokens=96)
        t0 = time.perf_counter()
        eng.submit(req)
        eng.run_to_completion()
        dt = time.perf_counter() - t0
        toks = int(eng.metrics.counter(
            "serve_tokens_generated").value()) - warm
        return toks / dt, dt, list(req.output), eng

    base_tps, _, base_out, _ = serve(0)
    spec_tps, spec_dt, spec_out, eng = serve(4)
    st = eng.spec_stats
    rate = st["accepted"] / st["proposed"] if st["proposed"] else 0.0
    speedup = spec_tps / base_tps
    results.append((
        "serving_speculative_tokps", spec_dt * 1e6,
        f"{spec_tps:,.0f} tok/s speculative (k=4, ngram) vs "
        f"{base_tps:,.0f} non-speculative ({speedup:.1f}x), "
        f"accepted {st['accepted']}/{st['proposed']} drafts ({rate:.0%})",
        # gated keys are lower-is-better (the gate fails on increases):
        # per-token latency catches throughput regressions, draft waste
        # (rejected fraction) catches draft-quality regressions even
        # when raw tok/s holds
        {"spec_tok_ms": round(1e3 / spec_tps, 3),
         "spec_draft_waste": round(1.0 - rate, 3)}))
    # speculation must never change greedy output — and must pay its way
    assert spec_out == base_out, "speculation changed greedy output"
    assert speedup >= 1.3, (base_tps, spec_tps)


def tp_capacity_report() -> dict:
    """Serve the TP=2 capacity and bit-identity workloads on the first two
    visible devices and return the numbers ``bench_tp_capacity`` checks."""
    import dataclasses

    import jax

    from repro.launch.mesh import make_mesh

    assert len(jax.devices()) >= 2, jax.devices()
    # float32: TP reductions run in f32, so greedy decode is bit-identical
    # across TP degrees for f32 models; bf16 activations quantize logits
    # to ~1e-2 ulps and a reassociated sum can flip an exact near-tie argmax
    cfg = dataclasses.replace(get_reduced_config("stablelm-3b"),
                              dtype="float32")
    params = init_params(cfg, 0)
    cache_len, page = 128, 16
    budget_lines = 512                  # per-DEVICE HBM budget in KV lines

    def serve(mesh, usable_pages, n_req, max_new):
        rng = np.random.default_rng(3)
        eng = DecodeEngine(cfg, params, num_slots=n_req,
                           cache_len=cache_len, decode_chunk=4,
                           prefill_buckets="auto", kv_page_size=page,
                           kv_pages=usable_pages + 1, mesh=mesh)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size, 12).astype(
                            np.int32), max_new_tokens=max_new)
                for i in range(n_req)]
        for r in reqs:
            eng.submit(r)
        peak, t0 = 0, time.perf_counter()
        for _ in range(5_000):
            n = eng.step()
            peak = max(peak, eng.active())
            if n == 0:
                break
        return (peak, time.perf_counter() - t0,
                [list(r.output) for r in reqs], eng)

    # bit-identity on a starvation-free workload (pool covers every
    # request on both sides): the guarantee is per-schedule — a starved
    # pool requeues, and the resume re-prefills the partial through a
    # different (bucketed) program whose f32 reassociation is not bitwise
    # the incremental decode, independent of TP
    _, _, base_out, _ = serve(None, 64, 8, 12)
    _, _, tp_out, _ = serve(make_mesh(1, 2), 64, 8, 12)

    # capacity at equal per-device HBM: one device's budget IS the pool;
    # two shards hold half a page each, so the same budget backs 2x pages
    base_peak, base_dt, _, _ = serve(None, budget_lines // page, 48, 24)
    tp_peak, tp_dt, _, eng = serve(make_mesh(1, 2),
                                   2 * (budget_lines // page), 48, 24)
    return {
        "base_peak": base_peak, "base_dt": base_dt,
        "tp_peak": tp_peak, "tp_dt": tp_dt,
        "identical": tp_out == base_out,
        "high_water": eng.allocator.high_water,
        "tp_pages": eng.paging.usable_pages,
        "plan": eng.tp_stats()["plan"],
    }


def bench_tp_capacity(results: list):
    """The tensor-parallel serving headline claim: sharding the paged KV
    pool along KV heads puts HALF of every page on each of 2 devices, so
    the SAME per-device HBM budget backs 2x the logical pages and
    >= 1.8x the concurrent short requests — with greedy outputs
    bit-identical to TP=1.

    With 2+ devices visible it runs in this process.  On a one-device
    CPU backend it runs in a child pinned to the CPU with 2 forced host
    devices (a device count is fixed once jax starts); this process
    parses the child's JSON report.  A single accelerator cannot be
    shared with a child, so there the claim is not measured."""
    import jax
    if len(jax.devices()) >= 2:
        rep = tp_capacity_report()
    elif jax.default_backend() == "cpu":
        import json
        import os
        import subprocess
        import sys
        from pathlib import Path
        root = Path(__file__).resolve().parents[1]
        env = dict(os.environ, PYTHONPATH=f"{root / 'src'}:{root}",
                   JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=2")
        code = ("import json; from benchmarks.bench_serving import "
                "tp_capacity_report; print(json.dumps(tp_capacity_report()))")
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, env=env, cwd=root)
        assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-2000:])
        rep = json.loads(r.stdout.splitlines()[-1])
    else:
        print(f"serving_tp_capacity: needs 2 devices, 1 "
              f"{jax.default_backend()} device visible — not measured")
        return
    ratio = rep["tp_peak"] / rep["base_peak"]
    results.append(("serving_tp_capacity", rep["tp_dt"] * 1e6,
                    f"peak {rep['tp_peak']} concurrent on 2 shards vs "
                    f"{rep['base_peak']} on one device at equal per-device "
                    f"HBM ({ratio:.1f}x, high-water {rep['high_water']}/"
                    f"{rep['tp_pages']} pages, {rep['plan']})"))
    # sharding must never change greedy output — and must buy capacity
    assert rep["identical"], "TP=2 changed greedy output"
    assert ratio >= 1.8, (rep["base_peak"], rep["tp_peak"])


def bench_router_scaling(results: list):
    """The elastic-serving claims.

    (a) Aggregate throughput: 2 replicas >= 1.8x 1 replica at equal
    per-replica HBM.  Replicas share nothing — a parallel deployment's
    wall clock is the *busiest* replica's compute time — so aggregate
    tok/s is tokens / max per-replica busy seconds, each replica's
    dispatches timed for real.  In-process the dispatches serialize, so
    this is also an honest router-balance gate: a skewed router piles
    the work (and the busy seconds) onto one replica and the ratio
    collapses to ~1x.  Greedy outputs must stay bit-identical to the
    single-replica run.

    (b) Affinity hit rate: prefix-affinity routing >= 1.5x round-robin's
    prefix-cache hit rate on 16 requests drawn from two 400-token
    system-prompt groups, with each replica's page pool sized to hold
    roughly ONE group's prefix.  Affinity pins each group to one
    replica's radix index (one cold miss per group); round-robin
    interleaves both groups onto both replicas and LRU-thrashes the
    pools.
    """
    from repro.monitoring import MetricsRegistry
    from repro.serving import Router
    from repro.serving.router import HashRing, affinity_key

    cfg = get_reduced_config("stablelm-3b")
    params = init_params(cfg, 0)

    # ------------------------------------------ (a) throughput scaling ----
    def serve(n_replicas):
        metrics = MetricsRegistry()

        def make_engine(admission):
            return DecodeEngine(cfg, params, num_slots=4, cache_len=128,
                                metrics=metrics, admission=admission,
                                decode_chunk=8, prefill_buckets="auto")

        # round-robin + a uniform workload = exact per-replica balance,
        # so part (a) measures scaling, not placement luck
        router = Router(make_engine, replicas=n_replicas, policy="rr",
                        metrics=metrics)
        rng = np.random.default_rng(0)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size, 16).astype(
                            np.int32), max_new_tokens=16)
                for i in range(16)]
        wrng = np.random.default_rng(1)
        warm = [Request(rid=100 + i,
                        prompt=wrng.integers(0, cfg.vocab_size, 16).astype(
                            np.int32), max_new_tokens=16)
                for i in range(2 * n_replicas)]
        for r in warm:                  # absorb per-engine compiles
            router.submit(r)
        router.run_to_completion()
        for rep in router.replicas.values():
            rep.busy_s = 0.0
        for r in reqs:
            router.submit(r)
        router.run_to_completion()
        toks = sum(len(r.output) for r in reqs)
        busy = max(router.busy_seconds().values())
        return toks / busy, busy, [list(r.output) for r in reqs]

    tps1, busy1, out1 = serve(1)
    tps2, busy2, out2 = serve(2)
    ratio = tps2 / tps1
    results.append(("serving_router_scaling", busy2 * 1e6,
                    f"{tps2:,.0f} agg tok/s on 2 replicas vs {tps1:,.0f} "
                    f"on 1 ({ratio:.1f}x, busiest-replica wall)"))
    assert out2 == out1, "2-replica routing changed greedy output"
    assert ratio >= 1.8, (tps1, tps2)

    # -------------------------------------- (b) affinity vs round-robin ----
    page, cache_len = 16, 512
    # two 400-token system prompts, seed-searched (deterministically) so
    # the ring maps them to DIFFERENT replicas — the bench measures
    # routing policy, not a hash collision
    ring = HashRing()
    ring.add(0)
    ring.add(1)
    grng = np.random.default_rng(17)
    groups = []
    while len(groups) < 2:
        g = grng.integers(2, cfg.vocab_size, 400).astype(np.int32)
        if ring.lookup(affinity_key(g, page)) == len(groups):
            groups.append(g)

    def hit_rate(policy):
        metrics = MetricsRegistry()

        def make_engine(admission):
            # 27 usable pages: ONE group's 25-page prefix + a working
            # margin, so holding both groups is impossible and the
            # interleaved (round-robin) arrival order must LRU-thrash
            return DecodeEngine(cfg, params, num_slots=2,
                                cache_len=cache_len, metrics=metrics,
                                admission=admission, decode_chunk=4,
                                prefill_buckets="auto", kv_page_size=page,
                                kv_pages=28, prefix_cache=True)

        router = Router(make_engine, replicas=2, policy=policy,
                        metrics=metrics)
        rng = np.random.default_rng(13)
        reqs = []
        for i in range(16):
            tail = rng.integers(2, cfg.vocab_size, 8).astype(np.int32)
            reqs.append(Request(
                rid=i, prompt=np.concatenate([groups[(i // 2) % 2], tail]),
                max_new_tokens=8))
        t0 = time.perf_counter()
        for r in reqs:
            router.submit(r)
        router.run_to_completion()
        dt = time.perf_counter() - t0
        hits = int(metrics.counter("serve_prefix_hits").value())
        misses = int(metrics.counter("serve_prefix_misses").value())
        reused = int(metrics.counter(
            "serve_prefix_reused_tokens").value())
        frac = reused / sum(len(r.prompt) for r in reqs)
        return hits / (hits + misses), frac, dt

    # no cross-policy output assert here: the round-robin run thrashes
    # the pool BY DESIGN, and a starvation requeue re-prefills through a
    # different bucketed program whose f32 reassociation is not bitwise
    # the incremental decode (same per-schedule caveat bench_tp_capacity
    # documents) — the bit-identity acceptance gate lives in part (a)
    # and tests/test_router.py on starvation-free workloads
    aff_rate, aff_frac, aff_dt = hit_rate("affinity")
    rr_rate, rr_frac, _ = hit_rate("rr")
    results.append(("serving_router_affinity", aff_dt * 1e6,
                    f"prefix hit rate {aff_rate:.0%} affinity vs "
                    f"{rr_rate:.0%} round-robin (reused prompt tokens "
                    f"{aff_frac:.0%} vs {rr_frac:.0%}; 2 replicas, two "
                    f"400-token system prompts)"))
    # >= 1.5x round-robin, and good in absolute terms (one cold miss
    # per group per replica is 14/16 = 88%)
    assert aff_rate >= max(1.5 * rr_rate, 0.5), (aff_rate, rr_rate)


def bench_prefill_latency(results: list):
    import jax.numpy as jnp
    from repro.configs import RunConfig
    from repro.models.model import prefill
    cfg = get_reduced_config("stablelm-3b")
    params = init_params(cfg, 0)
    run = RunConfig(remat="none")
    rng = np.random.default_rng(1)
    for plen in (32, 128, 512):
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, plen)),
                           jnp.int32)
        import jax
        f = jax.jit(lambda p, t: prefill(p, {"tokens": t}, cfg, run,
                                         cache_len=1024)[0])
        f(params, toks)                 # compile
        t0 = time.perf_counter()
        reps = 3
        for _ in range(reps):
            jax.block_until_ready(f(params, toks))
        dt = (time.perf_counter() - t0) / reps
        results.append((f"prefill_latency_p{plen}", dt * 1e6,
                        f"{plen / dt:,.0f} tok/s"))


def run(results: list):
    bench_decode_throughput(results)
    bench_prefill_bucketed(results)
    bench_paged_capacity(results)
    bench_prefix_reuse(results)
    bench_latency_slo(results)
    bench_chunked_prefill_ttft(results)
    bench_speculative_tokps(results)
    bench_tp_capacity(results)
    bench_router_scaling(results)
    bench_prefill_latency(results)
