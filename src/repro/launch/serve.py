"""`python -m repro.launch.serve` — batched serving entry point: spin up the
DecodeEngine on a (reduced) architecture and push a synthetic request load
through it, reporting throughput/latency metrics — per tenant when
``--tenants`` carves the engine into fair-share slices.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.configs import ARCH_IDS, get_config, get_reduced_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.monitoring import MetricsRegistry
from repro.monitoring.metrics import (
    METRIC_SERVE_PREFIX_EVICTIONS, METRIC_SERVE_PREFIX_HITS,
    METRIC_SERVE_PREFIX_MISSES, METRIC_SERVE_PREFIX_REUSED_TOKENS,
    METRIC_SERVE_TENANT_TOKENS,
)
from repro.serving import AdmissionController, DecodeEngine, Request

#: page size --prefix-cache falls back to when --kv-paging is absent
DEFAULT_PREFIX_PAGE_SIZE = 16


def resolve_prefix_paging(prefix_cache: bool, kv_paging: int) -> int:
    """--prefix-cache implies --kv-paging: with no explicit page size the
    default kicks in (and says so), since the radix index shares physical
    pages and cannot exist over the dense per-slot cache."""
    if prefix_cache and not kv_paging:
        print(f"[serve] --prefix-cache implies --kv-paging: using "
              f"{DEFAULT_PREFIX_PAGE_SIZE}-line pages")
        return DEFAULT_PREFIX_PAGE_SIZE
    return kv_paging


def resolve_chunked_paging(max_batch_tokens, kv_paging: int) -> int:
    """--max-batch-tokens implies --kv-paging: a partial prefill holds
    exactly ceil(pos_filled/page) pages, which the dense per-slot cache
    cannot express — so budgeted mode defaults the page size in (and
    says so) when paging wasn't requested explicitly."""
    if max_batch_tokens is not None and not kv_paging:
        print(f"[serve] --max-batch-tokens implies --kv-paging: using "
              f"{DEFAULT_PREFIX_PAGE_SIZE}-line pages")
        return DEFAULT_PREFIX_PAGE_SIZE
    return kv_paging


def resolve_spec_paging(speculate: int, kv_paging: int) -> int:
    """--speculate implies --kv-paging: the verify scatter routes draft
    KV lines through per-line page targets (rejected lines die on the
    null page), which the dense cache cannot express."""
    if speculate and not kv_paging:
        print(f"[serve] --speculate implies --kv-paging: using "
              f"{DEFAULT_PREFIX_PAGE_SIZE}-line pages")
        return DEFAULT_PREFIX_PAGE_SIZE
    return kv_paging


def parse_tenants(spec: str, shares: str = "") -> dict[str, int]:
    """``alice:8,bob:1`` (or ``--tenants alice,bob --shares 8,1``) ->
    {"alice": 8, "bob": 1}."""
    out: dict[str, int] = {}
    names = [p.strip() for p in spec.split(",") if p.strip()]
    extra = [s.strip() for s in shares.split(",") if s.strip()] if shares \
        else []
    for i, part in enumerate(names):
        name, _, inline = part.partition(":")
        if inline:
            share = int(inline)
        elif i < len(extra):
            share = int(extra[i])
        else:
            share = 1
        assert share >= 1, f"tenant {name!r}: shares must be >= 1"
        out[name] = share
    return out


def parse_buckets(spec: str):
    """``auto`` -> power-of-two buckets, ``off`` -> exact-length prefill,
    ``32,64,128`` -> explicit bucket lengths."""
    spec = spec.strip().lower()
    if spec in ("", "off", "none"):
        return None
    if spec == "auto":
        return "auto"
    return tuple(int(p) for p in spec.split(",") if p.strip())


def resolve_tp(requested: int, n_devices: int) -> int:
    """``--tp N`` needs N visible devices.  Fewer is an error, never a
    silent tp=1: a deployment sized for N shards would otherwise come up
    on one chip and report numbers for the wrong system."""
    if requested <= 1:
        return 1
    if n_devices < requested:
        raise ValueError(
            f"--tp {requested}: only {n_devices} device(s) visible (on "
            f"CPU, set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{requested} before jax is imported to fake devices)")
    return requested


def resolve_use_pallas(requested: bool, backend: str) -> bool:
    """``--use-pallas`` needs a TPU backend: the kernels are TPU-Pallas,
    and anywhere else they would either run interpreted or not lower at
    all.  A non-TPU backend is an error, never a silent switch to the
    reference path."""
    if requested and backend != "tpu":
        raise ValueError(
            f"--use-pallas: backend is {backend!r}, not TPU (the Pallas "
            "kernels run only on TPU; drop the flag to serve through the "
            "reference attention path)")
    return requested


def make_requests(args, cfg, names, qos_cycle) -> list:
    """The synthetic workload both serving paths (single-engine and
    routed) push: per-request prompt lengths/tenants/QOS are a pure
    function of ``--seed``, so replica counts never change the
    workload."""
    rng = np.random.default_rng(args.seed)
    assert args.shared_prefix < args.cache_len, "--shared-prefix too long"
    system = rng.integers(2, cfg.vocab_size,
                          args.shared_prefix).astype(np.int32)
    if args.speculate and args.shared_prefix >= 8:
        # tile a short phrase so prompt-lookup drafts have material
        phrase = system[:8]
        system = np.tile(phrase, -(-args.shared_prefix // 8))[
            :args.shared_prefix]
    requests = []
    for rid in range(args.requests):
        plen = int(rng.integers(4, args.cache_len // 4))
        prompt = rng.integers(2, cfg.vocab_size, plen).astype(np.int32)
        if args.shared_prefix:
            prompt = np.concatenate([system, prompt])[:args.cache_len - 1]
            if args.speculate and args.shared_prefix >= 8:
                # land the prompt tail back inside the tiled phrase so
                # n-gram lookups fire from the first decode step
                prompt = np.concatenate([prompt, system[:8]])[
                    :args.cache_len - 1]
        requests.append(Request(
            rid=rid,
            prompt=prompt,
            max_new_tokens=args.max_new,
            temperature=float(rid % 2) * 0.8,
            tenant=names[rid % len(names)],
            qos=qos_cycle[rid % len(qos_cycle)]))
    return requests


def _serve_elastic(args, cfg, params, metrics, tenants, use_pallas,
                   kv_paging, report) -> int:
    """--replicas/--autoscale: N engines behind the prefix-affinity
    router, optionally as scavenger jobs in a small simulated cluster
    with the autoscaler driving replica count.  Replicas take the visible
    devices round-robin, each holding its own params and KV pool."""
    import itertools

    import jax

    from repro.cluster.commands import sdiag
    from repro.configs.base import RunConfig
    from repro.serving import Autoscaler, Router

    if args.tp > 1 or args.speculate or args.trace:
        print("[serve] --replicas/--autoscale path ignores --tp, "
              "--speculate and --trace (single-engine features)")

    devices = jax.devices()
    built = itertools.count()

    def make_engine(admission):
        return DecodeEngine(
            cfg, params, num_slots=args.slots, cache_len=args.cache_len,
            metrics=metrics, admission=admission,
            run=RunConfig(remat="none", use_pallas=use_pallas),
            decode_chunk=args.decode_chunk, fused=not args.no_fused,
            prefill_buckets=parse_buckets(args.prefill_buckets),
            kv_page_size=kv_paging, kv_pages=args.kv_pages,
            prefix_cache=args.prefix_cache,
            max_batch_tokens=args.max_batch_tokens,
            device=devices[next(built) % len(devices)])

    router = Router(make_engine,
                    replicas=0 if args.autoscale else args.replicas,
                    policy="affinity" if args.affinity else "rr",
                    spill_factor=args.spill_factor, metrics=metrics)
    for name, share in tenants.items():
        router.add_tenant(name, shares=share)
    autoscaler = cluster = None
    if args.autoscale:
        from repro.cluster import (
            Cluster, Node, Partition, ResourceRequest,
        )
        n_nodes = max(args.replicas, 2)
        nodes = [Node(name=f"n{i:02d}", cpus=16, mem_mb=65536,
                      gres={"tpu": 4}, coord=(0, i))
                 for i in range(n_nodes)]
        cluster = Cluster(nodes, [Partition(
            name="serve", nodes=tuple(nd.name for nd in nodes),
            default=True)])
        autoscaler = Autoscaler(
            router, cluster,
            req=ResourceRequest(nodes=1, gres_per_node={"tpu": 4},
                                time_limit_s=36_000),
            min_replicas=1, max_replicas=max(args.replicas, 1))
        autoscaler.tick()
        print(f"[serve] autoscaler: probe saw "
              f"{autoscaler.stats['last_probe']} idle node(s), started "
              f"{len(router.replicas)} replica(s) as scavenger jobs")
    names = list(tenants)
    qos_cycle = [q.strip() for q in args.qos.split(",") if q.strip()] \
        or ["normal"]
    requests = make_requests(args, cfg, names, qos_cycle)
    bursts = max(args.bursts, 1)
    per_wave = -(-len(requests) // bursts)       # ceil division
    t0 = time.perf_counter()
    for w in range(bursts):
        for req in requests[w * per_wave:(w + 1) * per_wave]:
            router.submit(req)
        if w < bursts - 1:
            for _ in range(3):                    # let the wave decode a bit
                router.step()
        if autoscaler is not None and w == 0 and len(router.replicas) > 1:
            # mid-run batch pressure: a high-QOS job preempts one
            # scavenger replica job; the tick drains that replica and its
            # in-flight requests resume elsewhere (partial output kept)
            from repro.cluster import ResourceRequest
            cluster.submit("batch-train", ResourceRequest(
                nodes=1, gres_per_node={"tpu": 4}), qos="high",
                run_time_s=600.0)
            autoscaler.tick()
            print(f"[serve] batch pressure: drained down to "
                  f"{len(router.replicas)} replica(s), "
                  f"{router.stats['resubmitted']} request(s) re-routed")
    router.run_to_completion()
    wall = time.perf_counter() - t0
    report.update(router=router, requests=requests)
    total = int(metrics.counter("serve_tokens_generated").value())
    busy = max(router.busy_seconds().values())
    st = router.stats
    print(f"served {len(requests)} requests on {len(router.replicas)} "
          f"replica(s), {total} tokens in {wall:.1f}s "
          f"({total / wall:,.1f} tok/s wall, busiest replica "
          f"{busy:.1f}s busy)")
    print(f"router: policy {router.policy}, {st['routed']} routed, "
          f"{st['affinity_hits']} affinity hits, {st['spills']} spills, "
          f"{st['drains']} drains ({st['resubmitted']} re-routed)")
    if args.prefix_cache:
        hits = int(metrics.counter(METRIC_SERVE_PREFIX_HITS).value())
        misses = int(metrics.counter(METRIC_SERVE_PREFIX_MISSES).value())
        print(f"prefix cache (all replicas): {hits} hits / {misses} "
              f"misses, "
              f"{int(metrics.counter(METRIC_SERVE_PREFIX_REUSED_TOKENS).value())} "
              f"prompt tokens reused")
    print(sdiag(cluster=cluster, router=router, autoscaler=autoscaler))
    return _check_finished(requests)


def _check_finished(requests) -> int:
    """Exit status of a serve run: 0 only when every request finished."""
    left = [r.rid for r in requests if not r.done]
    if left:
        print(f"[serve] {len(left)} request(s) did not finish: {left}")
        return 1
    return 0


def main(argv=None, report: dict | None = None) -> int:
    """Serve the synthetic load; 0 when every request finished.

    ``report``, when given, receives the ``engine`` (or ``router``) and the
    ``requests`` it served, for callers that embed the server in-process
    and inspect it afterwards."""
    report = {} if report is None else report
    ap = argparse.ArgumentParser(prog="repro.launch.serve")
    ap.add_argument("--arch", default="stablelm-3b", choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--param-dtype", default=None,
                    choices=("float32", "bfloat16"),
                    help="weight storage dtype (default: the config's); "
                         "compute runs in the config's dtype either way")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--decode-chunk", type=int, default=8,
                    help="tokens generated per device dispatch (the fused "
                         "decode_n scan length); 1 = per-token chunks")
    ap.add_argument("--no-fused", action="store_true",
                    help="use the legacy per-token host sampling loop")
    ap.add_argument("--prefill-buckets", default="auto",
                    help="'auto' (power-of-two), 'off', or comma lengths "
                         "e.g. 32,64,128 — prompts pad to the next bucket "
                         "so prefill compiles once per bucket")
    ap.add_argument("--tp", type=int, default=1, metavar="N",
                    help="tensor-parallel serving over N devices: params, "
                         "decode dispatches and the paged KV pool shard "
                         "over a (1, N) mesh's 'model' axis; greedy output "
                         "stays bit-identical to --tp 1 (an error when "
                         "fewer devices are visible)")
    ap.add_argument("--use-pallas", action="store_true",
                    help="route attention through the Pallas flash "
                         "kernels (TPU only: an error on other backends)")
    ap.add_argument("--kv-paging", type=int, default=0, metavar="PAGE_SIZE",
                    help="paged KV cache with PAGE_SIZE-line pages "
                         "(0 = dense per-slot cache); short requests then "
                         "share HBM instead of pinning cache-len lines")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="page-pool size override (default: dense-budget "
                         "equivalent, slots*cache_len/page_size + null)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache: requests sharing a prompt "
                         "prefix map the same KV pages copy-on-write and "
                         "prefill only their suffix (implies --kv-paging "
                         f"{DEFAULT_PREFIX_PAGE_SIZE})")
    ap.add_argument("--max-batch-tokens", type=int, nargs="?", const=512,
                    default=None, metavar="T",
                    help="continuous batching: each iteration runs ONE "
                         "fused step over a T-token budget mixing decode "
                         "lanes and prefill chunks, so long prompts stop "
                         "head-of-line blocking short ones (bare flag: "
                         "T=512; implies --kv-paging "
                         f"{DEFAULT_PREFIX_PAGE_SIZE})")
    ap.add_argument("--speculate", type=int, nargs="?", const=4,
                    default=0, metavar="K",
                    help="speculative decoding: draft up to K tokens per "
                         "lane and verify them in ONE batched target "
                         "dispatch — greedy output stays bit-identical, "
                         "temperature uses rejection sampling (bare "
                         "flag: K=4; implies --kv-paging "
                         f"{DEFAULT_PREFIX_PAGE_SIZE})")
    ap.add_argument("--spec-source", default="ngram",
                    choices=("ngram", "model"),
                    help="draft source: 'ngram' prompt-lookup (free, fed "
                         "by finished requests) or 'model' (a tiny draft "
                         "model with its own dense KV cache)")
    ap.add_argument("--draft-model", default=None, choices=ARCH_IDS,
                    metavar="ARCH",
                    help="with --spec-source model: architecture to draft "
                         "with (always reduced; must share the target's "
                         "vocabulary). Default: a 1-layer shrink of the "
                         "target config")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="prepend the same N-token system prompt to every "
                         "synthetic request (exercises --prefix-cache)")
    ap.add_argument("--tenants", default="",
                    help="tenant:shares list, e.g. alice:8,bob:1 "
                         "(empty: single default tenant)")
    ap.add_argument("--shares", default="",
                    help="shares for --tenants given as bare names, "
                         "e.g. --tenants alice,bob --shares 8,1")
    ap.add_argument("--qos", default="",
                    help="comma list of QOS tiers cycled across requests "
                         "(e.g. high,scavenger); empty = all 'normal'")
    ap.add_argument("--bursts", type=int, default=1,
                    help="submit the workload in N bursts with a few "
                         "decode steps between waves (exercises queueing "
                         "and the queue-wait/TTFT series)")
    ap.add_argument("--replicas", type=int, default=1, metavar="N",
                    help="elastic serving: N decode-engine replicas "
                         "behind the router; replicas share one "
                         "fair-share ledger and one GrpTRES scope")
    ap.add_argument("--affinity", action="store_true",
                    help="prefix-affinity routing (consistent hash on "
                         "the first prompt page, spill to least-loaded); "
                         "default with --replicas is round-robin")
    ap.add_argument("--spill-factor", type=float, default=2.0,
                    help="with --affinity: shed to the least-loaded "
                         "replica once the affine one's queue runs this "
                         "many num_slots deeper (default 2.0)")
    ap.add_argument("--autoscale", action="store_true",
                    help="run replicas as scavenger jobs in a small "
                         "simulated cluster: the autoscaler grows to "
                         "--replicas while idle nodes exist and drains "
                         "replicas when a batch job preempts them")
    ap.add_argument("--trace", default="", metavar="OUT_JSON",
                    help="record request-lifecycle spans and write a "
                         "Chrome trace-event JSON (load in Perfetto or "
                         "chrome://tracing); also prints the SLO report")
    args = ap.parse_args(argv)

    import dataclasses

    import jax

    from repro.configs.base import RunConfig

    enable_compile_cache()
    cfg = (get_reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    if args.param_dtype is not None:
        cfg = dataclasses.replace(cfg, param_dtype=args.param_dtype)
    params = init_params(cfg, args.seed)
    metrics = MetricsRegistry()
    tenants = parse_tenants(args.tenants, args.shares) if args.tenants \
        else {"default": 1}
    tracer = None
    if args.trace:
        from repro.monitoring import Tracer
        tracer = Tracer(metrics=metrics)
    admission = AdmissionController(tracer=tracer)
    for name, share in tenants.items():
        admission.add_tenant(name, shares=share)
    use_pallas = resolve_use_pallas(args.use_pallas, jax.default_backend())
    tp = resolve_tp(args.tp, len(jax.devices()))
    mesh = None
    if tp > 1:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(1, tp)
    kv_paging = resolve_prefix_paging(args.prefix_cache, args.kv_paging)
    kv_paging = resolve_chunked_paging(args.max_batch_tokens, kv_paging)
    kv_paging = resolve_spec_paging(args.speculate, kv_paging)
    draft_cfg = None
    if args.draft_model is not None:
        draft_cfg = get_reduced_config(args.draft_model)
        assert draft_cfg.vocab_size == cfg.vocab_size, \
            "--draft-model must share the target's vocabulary"
    if args.replicas > 1 or args.autoscale:
        return _serve_elastic(args, cfg, params, metrics, tenants,
                              use_pallas, kv_paging, report)
    engine = DecodeEngine(cfg, params, num_slots=args.slots,
                          cache_len=args.cache_len, metrics=metrics,
                          admission=admission,
                          run=RunConfig(remat="none", use_pallas=use_pallas),
                          decode_chunk=args.decode_chunk,
                          fused=not args.no_fused,
                          prefill_buckets=parse_buckets(args.prefill_buckets),
                          kv_page_size=kv_paging,
                          kv_pages=args.kv_pages,
                          prefix_cache=args.prefix_cache,
                          max_batch_tokens=args.max_batch_tokens,
                          tracer=tracer,
                          speculate=args.speculate,
                          spec_source=args.spec_source,
                          draft_model=draft_cfg,
                          mesh=mesh)
    names = list(tenants)
    qos_cycle = [q.strip() for q in args.qos.split(",") if q.strip()] \
        or ["normal"]
    requests = make_requests(args, cfg, names, qos_cycle)
    bursts = max(args.bursts, 1)
    per_wave = -(-len(requests) // bursts)       # ceil division
    t0 = time.perf_counter()
    for w in range(bursts):
        for req in requests[w * per_wave:(w + 1) * per_wave]:
            engine.submit(req)
        if w < bursts - 1:
            for _ in range(3):                    # let the wave decode a bit
                engine.step()
    engine.run_to_completion()
    wall = time.perf_counter() - t0
    report.update(engine=engine, requests=requests)
    total = int(metrics.counter("serve_tokens_generated").value())
    mode = ("host loop" if args.no_fused
            else f"fused chunk={args.decode_chunk}")
    print(f"served {args.requests} requests, {total} tokens in {wall:.1f}s "
          f"({total / wall:,.1f} tok/s, {args.slots} slots, {mode})")
    if engine.prefill_buckets:
        print(f"prefill buckets {engine.prefill_buckets}: "
              f"{engine.prefill_compilations()} compilations")
    if engine.paging is not None:
        print(f"paged KV: {engine.paging.page_size}-line pages, pool "
              f"{engine.paging.usable_pages} pages "
              f"(high-water {engine.allocator.high_water}, "
              f"{int(metrics.counter('serve_page_starvations').value())} "
              f"starvation requeues)")
    if engine.tp.tp > 1:
        ps = engine.tp.psums_per_token(cfg)
        print(f"tensor parallel: {engine.tp.describe(cfg)} on "
              f"{len(engine.tp.devices())} devices, "
              f"{sum(ps.values())} psums/token "
              f"(attn {ps['attn_out']}, mlp {ps['mlp_out']})")
    if engine.max_batch_tokens is not None:
        st = engine.serve_stats
        spent = st["decode_tokens"] + st["prefill_tokens"]
        cap = st["iterations"] * engine.max_batch_tokens
        print(f"continuous batching: budget {engine.max_batch_tokens} "
              f"tok/step, {st['iterations']} iterations, "
              f"fill {spent}/{cap} ({spent / cap if cap else 0:.0%}), "
              f"{st['prefill_chunks']} prefill chunks "
              f"({engine.chunk_compilations()} chunk compilations)")
    if engine.speculate:
        st = engine.spec_stats
        rate = st["accepted"] / st["proposed"] if st["proposed"] else 0.0
        per_round = st["emitted"] / st["rounds"] if st["rounds"] else 0.0
        print(f"speculative decoding: k={engine.speculate} "
              f"({args.spec_source}), {st['rounds']} verify rounds, "
              f"accepted {st['accepted']}/{st['proposed']} drafts "
              f"({rate:.0%}), {per_round:.2f} tokens/round")
    if engine.prefix is not None:
        hits = int(metrics.counter(METRIC_SERVE_PREFIX_HITS).value())
        misses = int(metrics.counter(METRIC_SERVE_PREFIX_MISSES).value())
        print(f"prefix cache: {hits} hits / {misses} misses, "
              f"{int(metrics.counter(METRIC_SERVE_PREFIX_REUSED_TOKENS).value())} "
              f"prompt tokens reused, "
              f"{int(metrics.counter(METRIC_SERVE_PREFIX_EVICTIONS).value())} "
              f"pages evicted, {engine.prefix.nodes} pages cached")
    if len(names) > 1 and total:
        tok = metrics.counter(METRIC_SERVE_TENANT_TOKENS)
        parts = []
        for name in names:
            n = int(tok.value(tenant=name))
            parts.append(f"{name}[{tenants[name]}sh]={n} "
                         f"({n / total:.0%})")
        print("per-tenant tokens: " + "  ".join(parts))
    print(f"decode p50 "
          f"{metrics.histogram('serve_decode_seconds').quantile(0.5)*1e3:.1f}"
          f"ms  p99 "
          f"{metrics.histogram('serve_decode_seconds').quantile(0.99)*1e3:.1f}"
          f"ms")
    if tracer is not None:
        data = tracer.export_chrome(args.trace)
        from repro.cluster.commands import sdiag
        print(f"trace: {len(data['traceEvents'])} events -> {args.trace} "
              f"(load in ui.perfetto.dev)")
        print(sdiag(admission=admission, tracer=tracer, engine=engine))
    return _check_finished(requests)


if __name__ == "__main__":
    raise SystemExit(main())
