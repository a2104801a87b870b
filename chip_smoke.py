#!/usr/bin/env python3
"""Bring-up check of the serving path on TPU, through the normal entry point.

    python chip_smoke.py               # one chip (what CI-style runs use)
    python chip_smoke.py --four-chips  # TP=4 and 4 router replicas, 4 chips

One chip: serves full-width stablelm-3b (random weights from a seed) through
``repro.launch.serve.main`` with the paged KV pool, the prefix cache,
token-budgeted batching and the Pallas kernels; checks that every request
finished, that the compiled decode step holds Mosaic kernels
(``tpu_custom_call``) and that the compiled kernels agree with
``repro.kernels.ref`` at stablelm-3b widths.

Four chips: serves the same model at ``--tp 4`` and at TP=1 on one of the
chips and holds both prefills against float32 logits of the same weights,
then serves ``--replicas 4`` with one replica per chip and compares its
greedy outputs with the single engine's.

Everything runs in this one process (a chip belongs to one process).  Earlier
lines report set-up facts — compile seconds, tokens, device memory, compile
cache use; they are not speed measurements.  The last line is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.  Anything
else — no TPU backend, a failed phase — exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Weights are stored in bfloat16 (compute is bfloat16 either way).  With the
# config's float32 storage the decode program needs 21.5 GiB of a v5e's
# 15.75 GiB (TPU compiler's memory analysis of decode_n at 4 slots x 1024
# lines), so float32 does not fit one chip.
PARAM_DTYPE = "bfloat16"

# One chip: every serving feature the engine composes on one device.
ONE_CHIP_SERVE = [
    "--arch", "stablelm-3b", "--param-dtype", PARAM_DTYPE,
    "--requests", "8", "--slots", "4", "--cache-len", "1024",
    "--max-new", "16", "--kv-paging", "16", "--prefix-cache",
    "--shared-prefix", "64", "--max-batch-tokens", "256", "--use-pallas",
]
# Four chips: the dense cache and one prefill bucket, so that a request's
# computation depends on nothing but its own prompt (no prefix hits, no
# chunk plans, no page-table widths) and each replica compiles 3 programs.
FOUR_CHIP_SERVE = [
    "--arch", "stablelm-3b", "--param-dtype", PARAM_DTYPE,
    "--requests", "8", "--slots", "4", "--cache-len", "1024",
    "--max-new", "16", "--prefill-buckets", "256", "--use-pallas",
]

# Compiled kernel vs float32 reference on bf16 inputs: the kernel output is
# rounded to bf16 (relative step 2^-8 = 3.9e-3) and the MXU multiplies in
# bf16 passes; outputs are convex combinations of N(0,1) values, so 2e-2 is
# about 5 bf16 steps at |out| ~ 1.
KERNEL_ATOL = KERNEL_RTOL = 2e-2
# TP=4 and TP=1 prefill logits both carry bf16 rounding: the psums only
# reassociate float32 partial sums, but a one-step change in a bf16
# activation (3.9e-3 relative) propagates through 32 residual layers of
# random weights, so how far TP=4 lands from TP=1 measures the model's
# sensitivity more than TP.  Both are held against the same weights computed
# in float32 (reference attention, HIGHEST matmuls): two bf16 evaluations
# that differ only in summation order have the same expected error, and
# TP=4 may be at most 1.5x further from the float32 logits (relative L2)
# than TP=1 is.
TP_ERR_RATIO = 1.5


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    raise SystemExit(1)


def device_check(count: int):
    """The TPU backend with at least ``count`` chips, or exit non-zero."""
    import jax
    backend = jax.default_backend()
    devices = jax.devices()
    print(f"backend {backend}: {devices}")
    if backend != "tpu":
        fail(f"JAX backend is {backend!r}, not 'tpu' — no chip, no result")
    if len(devices) < count:
        fail(f"{len(devices)} TPU device(s) visible, {count} needed")
    print(f"device_kind {devices[0].device_kind!r}, {len(devices)} device(s)")
    return devices


class CompileLog:
    """Backend compile seconds per program and persistent-cache hits, from
    JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = collections.defaultdict(list)
        self.events = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds[kw.get("fun_name", "?")].append(secs)

    def _event(self, event, **kw):
        if event.startswith("/jax/compilation_cache/"):
            self.events[event.rsplit("/", 1)[1]] += 1

    def report(self, top: int = 10) -> None:
        total = sum(sum(v) for v in self.seconds.values())
        print(f"compile: {sum(len(v) for v in self.seconds.values())} "
              f"backend compiles, {total:.1f}s in all")
        ranked = sorted(self.seconds.items(), key=lambda kv: -sum(kv[1]))
        for name, secs in ranked[:top]:
            print(f"  {name}: {len(secs)} x, "
                  + ", ".join(f"{s:.1f}s" for s in secs))
        rest = ranked[top:]
        if rest:
            print(f"  {len(rest)} other programs: "
                  f"{sum(sum(v) for _, v in rest):.1f}s")
        print(f"compile cache: {self.events['compile_requests_use_cache']} "
              f"lookups, {self.events['cache_hits']} hits, "
              f"{self.events['cache_misses']} misses")


def memory_report(devices) -> None:
    for d in devices:
        st = d.memory_stats() or {}
        print(f"{d}: peak_bytes_in_use {st.get('peak_bytes_in_use')} "
              f"bytes_in_use {st.get('bytes_in_use')} "
              f"bytes_limit {st.get('bytes_limit')}")


def serve(argv: list) -> dict:
    """``repro.launch.serve.main`` in this process; the engine (or router)
    and the requests it served, after checking every request finished."""
    from repro.launch import serve as serve_mod
    report: dict = {}
    print(f"serve: {' '.join(argv)}")
    t0 = time.perf_counter()
    rc = serve_mod.main(argv, report=report)
    print(f"serve: exit {rc} after {time.perf_counter() - t0:.1f}s "
          "(compiles included)")
    if rc != 0:
        fail(f"serve exited {rc}")
    reqs = report["requests"]
    short = [r.rid for r in reqs
             if not r.done or len(r.output) != r.max_new_tokens]
    if short:
        fail(f"requests {short} did not finish all their tokens")
    print(f"serve: {len(reqs)} requests finished, "
          f"{sum(len(r.output) for r in reqs)} tokens generated")
    return report


def greedy_outputs(reqs) -> dict:
    return {r.rid: list(r.output) for r in reqs if r.temperature == 0.0}


def check_kernels(S: int = 2048, Sp: int = 1024, seed: int = 0) -> None:
    """Compiled flash_decode (dense over S lines in 512-line blocks, and
    paged) and flash_attention over Sp tokens against the float32
    references, at stablelm-3b widths (32 heads of 80, MHA)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    H = K = 32
    Dh = 80
    B, ps = 4, 16
    pages = B * S // ps + 1             # every sequence owns its pages
    rng = np.random.default_rng(seed)

    def bf16(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    def compare(name, got, want):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = np.abs(got - want)
        bound = KERNEL_ATOL + KERNEL_RTOL * np.abs(want)
        print(f"kernel {name}: max |err| {err.max():.3e}, "
              f"max err/bound {(err / bound).max():.3f}")
        if not np.all(np.isfinite(got)) or np.any(err > bound):
            fail(f"kernel {name} disagrees with the reference")

    def f32(x):
        return x.astype(jnp.float32)

    pos = jnp.asarray(rng.integers(S // 2, S, B), jnp.int32)
    q, k, v = bf16(B, 1, H, Dh), bf16(B, S, K, Dh), bf16(B, S, K, Dh)
    dense = jax.jit(ops.flash_decode)
    text = dense.lower(q, k, v, pos).compile().as_text()
    if "tpu_custom_call" not in text:
        fail("dense flash_decode did not compile to a Mosaic kernel")
    with jax.default_matmul_precision("highest"):
        want = ref.decode_attention_ref(f32(q), f32(k), f32(v), pos)
    compare(f"flash_decode dense S={S} ({-(-S // 512)} kv blocks)",
            dense(q, k, v, pos), want)

    n_pages = S // ps
    table = jnp.asarray(
        1 + rng.permutation(pages - 1)[:B * n_pages].reshape(B, n_pages),
        jnp.int32)
    # layer 1 of a two-layer stacked pool, read in place
    kp, vp = bf16(2, pages, K, ps, Dh), bf16(2, pages, K, ps, Dh)
    layer = jnp.int32(1)
    paged = jax.jit(ops.flash_decode_paged)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_decode_attention_ref(f32(q), f32(kp[1]),
                                              f32(vp[1]), table, pos)
    compare(f"flash_decode paged {n_pages} pages of {ps}",
            paged(q, kp, vp, table, pos, layer), want)

    qa, ka, va = bf16(1, Sp, H, Dh), bf16(1, Sp, K, Dh), bf16(1, Sp, K, Dh)
    attn = jax.jit(ops.flash_attention)
    with jax.default_matmul_precision("highest"):
        want = ref.attention_ref(f32(qa), f32(ka), f32(va))
    compare(f"flash_attention S={Sp}", attn(qa, ka, va), want)


def decode_program_text(engine) -> str:
    """The engine's own compiled decode program, lowered with its state."""
    import jax.numpy as jnp
    import numpy as np
    n = engine.num_slots
    args = [engine.params, engine.cache, np.zeros(n, np.int32),
            np.zeros(n, np.int32), np.zeros(n, np.int32), np.ones(n, bool),
            np.full(n, -1, np.int32), np.zeros(n, np.float32), engine._key]
    if engine.paging is not None:
        args += [jnp.asarray(engine._dispatch_table()),
                 np.full(n, engine.cache_len, np.int32)]
    return engine._decode_n.lower(*args).compile().as_text()


def one_chip() -> None:
    import jax
    log = CompileLog()
    check_kernels()
    print(f"weights stored as {PARAM_DTYPE}: float32 storage does not fit "
          "the decode program in one v5e's HBM (see PARAM_DTYPE)")
    report = serve(ONE_CHIP_SERVE)
    engine = report["engine"]
    n_kernels = decode_program_text(engine).count("tpu_custom_call")
    print(f"decode program: {n_kernels} tpu_custom_call site(s)")
    if not n_kernels:
        fail("the compiled decode step holds no Mosaic kernel")
    memory_report(jax.devices()[:1])
    log.report()


def _prefill_args(engine, prompt):
    """``prompt`` padded to the engine's first prefill bucket, and the
    position whose logits a prefill returns."""
    import jax.numpy as jnp
    import numpy as np
    padded = np.zeros((1, engine.prefill_buckets[0]), np.int32)
    padded[0, :len(prompt)] = prompt
    return jnp.asarray(padded), jnp.asarray(len(prompt) - 1, jnp.int32)


def prefill_logits(engine, prompt):
    """Last-position logits of the engine's own prefill program."""
    import numpy as np
    logits, _ = engine._prefill_fn(engine.params,
                                   *_prefill_args(engine, prompt))
    return np.asarray(logits, np.float32).reshape(-1)


def float32_logits(engine, prompt, device):
    """The same last-position logits from the engine's weights computed in
    float32 on ``device``: reference attention, HIGHEST-precision matmuls."""
    import dataclasses

    import jax
    import numpy as np

    from repro.configs.base import RunConfig
    from repro.models import prefill
    cfg = dataclasses.replace(engine.cfg, dtype="float32")
    fn = jax.jit(lambda p, t, last: prefill(
        p, {"tokens": t}, cfg, RunConfig(remat="none"), last_pos=last)[0])
    params = jax.device_put(engine.params, device)
    with jax.default_matmul_precision("highest"):
        logits = fn(params, *_prefill_args(engine, prompt))
    return np.asarray(logits, np.float32).reshape(-1)


def rel_l2(a, b) -> float:
    import numpy as np
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def four_chips() -> None:
    import jax
    import numpy as np
    devices = jax.devices()[:4]
    log = CompileLog()

    # --- TP=1 on chip 0: the reference for both comparisons ---
    report = serve(FOUR_CHIP_SERVE + ["--tp", "1"])
    base = greedy_outputs(report["requests"])
    prompt = report["requests"][0].prompt
    base_logits = prefill_logits(report["engine"], prompt)
    exact = float32_logits(report["engine"], prompt, devices[1])
    report.clear()              # frees the TP=1 weights and cache on chip 0
    gc.collect()
    memory_report(devices[:1])

    # --- TP=4 over the four chips ---
    report = serve(FOUR_CHIP_SERVE + ["--tp", "4"])
    engine = report["engine"]
    placed = {d for leaf in jax.tree.leaves(engine.params)
              for d in leaf.devices()}
    print(f"tp=4 params span {len(placed)} device(s)")
    if placed != set(devices):
        fail(f"TP=4 params are on {placed}, not the four chips")
    tp_logits = prefill_logits(engine, prompt)
    err1, err4 = rel_l2(base_logits, exact), rel_l2(tp_logits, exact)
    print(f"prefill logits vs float32: tp=1 rel L2 {err1:.3e}, tp=4 "
          f"{err4:.3e} (limit {TP_ERR_RATIO} x tp=1); tp=4 vs tp=1 rel L2 "
          f"{rel_l2(tp_logits, base_logits):.3e}, max |diff| "
          f"{np.abs(tp_logits - base_logits).max():.3e}; argmax tp=4 "
          f"{int(tp_logits.argmax())}, tp=1 {int(base_logits.argmax())}, "
          f"float32 {int(exact.argmax())}")
    tp_out = greedy_outputs(report["requests"])
    same = sum(a == b for rid in base for a, b in zip(base[rid], tp_out[rid]))
    total = sum(len(v) for v in base.values())
    print(f"tp=4 vs tp=1 greedy tokens: {same}/{total} match "
          f"({same / total:.1%})")
    report.clear()
    engine = None
    gc.collect()
    if not err4 <= TP_ERR_RATIO * err1:
        fail("TP=4 prefill logits are further from float32 than TP=1 allows")

    # --- four router replicas, one per chip ---
    report = serve(FOUR_CHIP_SERVE + ["--replicas", "4"])
    router = report["router"]
    homes = []
    for rid, rep in sorted(router.replicas.items()):
        eng = rep.engine
        on = {d for leaf in jax.tree.leaves((eng.params, eng.cache))
              for d in leaf.devices()}
        print(f"replica {rid}: params and KV cache on {sorted(map(str, on))}")
        if len(on) != 1:
            fail(f"replica {rid} spans {on}")
        homes.append(on.pop())
    if sorted(homes, key=str) != sorted(devices, key=str):
        fail(f"replicas sit on {homes}, not one per chip")
    routed = greedy_outputs(report["requests"])
    diff = [rid for rid in base if routed[rid] != base[rid]]
    print(f"router greedy outputs vs single engine: "
          f"{len(base) - len(diff)}/{len(base)} requests bit-identical")
    if diff:
        fail(f"router changed the greedy output of requests {diff}")
    memory_report(devices)
    log.report()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chip_smoke.py")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip paths (TP=4, 4 router "
                         "replicas) and what they are compared with")
    args = ap.parse_args(argv)
    import jax
    device_check(4 if args.four_chips else 1)   # before the program loads
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = Path(enable_compile_cache())
    n_cached = len(list(cache_dir.glob("*"))) if cache_dir.is_dir() else 0
    print(f"compile cache {cache_dir}: {n_cached} entries at start")
    four_chips() if args.four_chips else one_chip()
    print(f"compile cache {cache_dir}: "
          f"{len(list(cache_dir.glob('*')))} entries at the end")
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
