"""Request-lifecycle tracing & SLO observability.

Acceptance properties:

* spans nest (explicit parents, track inheritance), retention is a ring
  buffer, and the injectable clock fully determines timestamps;
* the Chrome trace export is valid JSON with monotonically ordered
  timestamps and resolvable parent/child links (what Perfetto loads);
* Prometheus label-value escaping survives hostile tenant names;
* histogram quantiles interpolate within the terminal bucket;
* SLO attainment counters split met/violated exactly at the tier target;
* a preempted-then-resumed serving request yields ONE request trace with
  TWO decode spans (residency segments) plus PREEMPT/RESUME markers;
* cluster jobs emit PENDING/RUNNING/PREEMPTED state spans on the virtual
  clock, and ``sdiag`` reports scheduler/admission/SLO statistics.
"""
import json

import numpy as np
import pytest

from repro.monitoring import (
    DEFAULT_SLO_TARGETS, LATENCY_BUCKETS, MetricsRegistry, SLOTarget, Tracer,
)
from repro.monitoring.metrics import _labels_text
from repro.monitoring.trace import (
    ENGINE_TRACK, METRIC_SERVE_ITL, METRIC_SERVE_QUEUE_WAIT,
    METRIC_SERVE_TTFT, METRIC_SLO_ITL_MET, METRIC_SLO_ITL_VIOLATIONS,
    METRIC_SLO_TTFT_MET, METRIC_SLO_TTFT_VIOLATIONS, phase,
)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


# ------------------------------------------------------------ span core ----

def test_spans_nest_and_inherit_track():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    root = tr.begin("request 0", cat="request", track=("serving:a", "req 0"))
    clk.advance(1.0)
    child = tr.begin("PREFILL", cat="prefill", parent=root)
    assert child.track == root.track           # inherited
    assert child.parent == root.sid
    clk.advance(0.5)
    tr.end(child)
    tr.end(root)
    assert child.start == 1.0 and child.end == 1.5
    assert root.duration == 1.5
    # double-end is a no-op
    tr.end(child)
    assert child.end == 1.5
    assert [s.name for s in tr.spans(cat="prefill")] == ["PREFILL"]


def test_explicit_ts_overrides_clock():
    tr = Tracer(clock=FakeClock(100.0))
    sp = tr.begin("job 1", ts=5.0)
    tr.event("SUBMIT", sp, ts=5.0)
    tr.end(sp, ts=9.0)
    assert sp.start == 5.0 and sp.end == 9.0
    assert sp.events[0].ts == 5.0


def test_ring_buffer_bounds_retention():
    tr = Tracer(clock=FakeClock(), max_spans=4)
    for i in range(10):
        tr.end(tr.begin(f"s{i}"))
    done = tr.spans()
    assert len(done) == 4
    assert [s.name for s in done] == ["s6", "s7", "s8", "s9"]


def test_span_contextmanager_and_open_spans():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("work", meta=1) as sp:
        assert sp in tr.open_spans()
        clk.advance(2.0)
    assert sp.end == 2.0 and not tr.open_spans()


# --------------------------------------------------------- chrome export ----

def test_chrome_export_golden(tmp_path):
    clk = FakeClock()
    tr = Tracer(clock=clk)
    root = tr.begin("request 0", cat="request", track=("serving:a", "req 0"))
    tr.event("SUBMIT", root)
    clk.advance(0.001)
    child = tr.begin("PREFILL", parent=root)
    clk.advance(0.002)
    tr.end(child)
    tr.end(root)
    path = tmp_path / "trace.json"
    data = tr.export_chrome(str(path))
    on_disk = json.loads(path.read_text())     # valid JSON round-trip
    assert on_disk == json.loads(json.dumps(data))
    evs = [e for e in data["traceEvents"] if e["ph"] != "M"]
    meta = [e for e in data["traceEvents"] if e["ph"] == "M"]
    # process/thread named for the track
    assert {m["name"] for m in meta} == {"process_name", "thread_name"}
    # monotonically ordered timestamps
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)
    # parent/child: child's X event links to root's sid, same lane, and
    # the child interval is contained in the parent interval
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    rx, cx = xs["request 0"], xs["PREFILL"]
    assert cx["args"]["parent_sid"] == rx["args"]["sid"]
    assert (rx["pid"], rx["tid"]) == (cx["pid"], cx["tid"])
    assert rx["ts"] <= cx["ts"]
    assert cx["ts"] + cx["dur"] <= rx["ts"] + rx["dur"]
    # the instant event rides on the root span
    (inst,) = [e for e in evs if e["ph"] == "i"]
    assert inst["name"] == "SUBMIT"
    assert inst["args"]["span_sid"] == rx["args"]["sid"]


def test_chrome_export_includes_open_spans():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    tr.begin("unfinished")
    clk.advance(1.0)
    data = tr.export_chrome()
    (ev,) = [e for e in data["traceEvents"] if e["ph"] == "X"]
    assert ev["args"]["incomplete"] is True and ev["dur"] == 1e6
    assert not tr.export_chrome(include_open=False)["traceEvents"]


def test_validate_trace_script(tmp_path):
    import sys
    sys.path.insert(0, "scripts")
    try:
        import validate_trace
    finally:
        sys.path.pop(0)
    tr = Tracer(clock=FakeClock())
    tr.end(tr.begin("ok"))
    good = tmp_path / "good.json"
    tr.export_chrome(str(good))
    assert validate_trace.validate(str(good)) == []
    bad = tmp_path / "bad.json"
    data = tr.export_chrome()
    data["traceEvents"][-1]["args"]["parent_sid"] = 999
    bad.write_text(json.dumps(data))
    assert validate_trace.validate(str(bad))


# ----------------------------------------------------- metrics satellites ----

def test_label_value_escaping_round_trip():
    text = _labels_text({"tenant": 'acme "prod"\\team\nx'})
    assert text == '{tenant="acme \\"prod\\"\\\\team\\nx"}'
    # the exposition line must not contain a raw newline or bare quote
    assert "\n" not in text


def test_escaped_labels_expose_parses():
    reg = MetricsRegistry()
    reg.counter("c", "h").inc(tenant='a"b\\c')
    lines = reg.expose().splitlines()
    (sample,) = [ln for ln in lines if ln.startswith("c{")]
    assert sample == 'c{tenant="a\\"b\\\\c"} 1.0'


def test_quantile_interpolates_within_bucket():
    reg = MetricsRegistry()
    h = reg.histogram("h")
    for _ in range(100):
        h.observe(0.3)                  # lands in the (0.1, 0.5] bucket
    # interpolation reports a value inside the bucket, not the 0.5 bound
    assert 0.1 < h.quantile(0.5) < 0.5
    assert h.quantile(0.99) < 0.5
    # +Inf terminal bucket: report the last finite boundary
    h2 = reg.histogram("h2")
    h2.observe(1e6)
    assert h2.quantile(0.5) == h2.buckets[-2]


def test_registry_timer_records_into_histogram():
    reg = MetricsRegistry()
    with reg.timer("op_seconds", "op latency", stage="x"):
        pass
    h = reg.histogram("op_seconds")
    assert h.count(stage="x") == 1
    assert 0.0 <= h.sum(stage="x") < 1.0


def test_dashboard_renders_histogram_rows():
    reg = MetricsRegistry()
    reg.gauge("cluster_util").set(0.5)
    h = reg.histogram("lat_seconds")
    for v in (0.2, 0.3, 0.4):
        h.observe(v, tenant="a")
    out = reg.dashboard()
    row = [ln for ln in out.splitlines() if "lat_seconds" in ln]
    assert len(row) == 1
    assert "n=3" in row[0] and "p50=" in row[0] and "p99=" in row[0]
    assert 'tenant="a"' in row[0]


# ------------------------------------------------------------ SLO series ----

def test_slo_counters_split_at_target():
    tr = Tracer(clock=FakeClock(),
                slo_targets={"high": SLOTarget(ttft_s=1.0, itl_s=0.2),
                             "scavenger": SLOTarget()})
    for s in (0.5, 1.0, 1.5):          # met, met (boundary), violated
        tr.slo.ttft(s, "a", "high")
    met = tr.metrics.counter(METRIC_SLO_TTFT_MET)
    viol = tr.metrics.counter(METRIC_SLO_TTFT_VIOLATIONS)
    assert met.value(tenant="a", qos="high") == 2
    assert viol.value(tenant="a", qos="high") == 1
    # best-effort tier: series recorded, no attainment counters
    tr.slo.ttft(99.0, "b", "scavenger")
    hist = tr.metrics.histogram(METRIC_SERVE_TTFT)
    assert hist.count(tenant="b", qos="scavenger") == 1
    assert viol.value(tenant="b", qos="scavenger") == 0


def test_slo_itl_is_token_weighted():
    tr = Tracer(clock=FakeClock())
    tr.slo.itl(0.01, "a", "normal", n=8)       # one fused chunk, 8 tokens
    hist = tr.metrics.histogram(METRIC_SERVE_ITL)
    assert hist.count(tenant="a", qos="normal") == 8
    assert hist.sum(tenant="a", qos="normal") == pytest.approx(0.08)


@pytest.mark.parametrize("seconds,n,qos", [
    (0.01, 8, "normal"), (0.3, 8, "high"), (0.9, 1, "normal"),
    (0.05, 5, "scavenger")])
def test_weighted_itl_matches_per_token_observations(seconds, n, qos):
    """One observation of weight n: the same bucket counts and the same
    attainment counters as n observations of the same latency."""
    tr = Tracer(clock=FakeClock())
    tr.slo.itl(seconds, "a", qos, n=n)
    ref = MetricsRegistry()
    hist = ref.histogram(METRIC_SERVE_ITL, buckets=LATENCY_BUCKETS)
    target = DEFAULT_SLO_TARGETS[qos].itl_s
    for _ in range(n):
        hist.observe(seconds, tenant="a", qos=qos)
        if target is not None:
            ref.counter(METRIC_SLO_ITL_MET if seconds <= target
                        else METRIC_SLO_ITL_VIOLATIONS).inc(
                tenant="a", qos=qos)

    def buckets(h):
        return [ln for ln in h.expose() if "_bucket" in ln]

    got = tr.metrics.histogram(METRIC_SERVE_ITL)
    assert buckets(got) == buckets(hist)
    assert got.count(tenant="a", qos=qos) == n
    assert got.sum(tenant="a", qos=qos) == pytest.approx(
        hist.sum(tenant="a", qos=qos))
    for name in (METRIC_SLO_ITL_MET, METRIC_SLO_ITL_VIOLATIONS):
        assert tr.metrics.counter(name).value(tenant="a", qos=qos) \
            == ref.counter(name).value(tenant="a", qos=qos)


def test_phase_without_a_tracer_is_one_profiler_annotation():
    from jax.profiler import TraceAnnotation
    assert type(phase("engine.sync")) is TraceAnnotation
    with phase("engine.sync", None):
        pass


def test_phase_with_a_tracer_is_also_an_engine_span():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with phase("engine.plan", tr):
        clk.advance(0.25)
    (sp,) = tr.spans(name="engine.plan")
    assert sp.track == ENGINE_TRACK and sp.cat == "engine"
    assert (sp.start, sp.end) == (0.0, 0.25)


def test_slo_report_lists_tenants():
    tr = Tracer(clock=FakeClock())
    tr.slo.ttft(0.1, "alice", "high")
    tr.slo.itl(0.01, "alice", "high", n=4)
    report = tr.slo.format_report()
    assert "alice" in report and "high" in report and "TTFT" in report


# ----------------------------------------------------- engine integration ----

@pytest.fixture(scope="module")
def tiny_model():
    from repro.configs import get_reduced_config
    from repro.models import init_params
    cfg = get_reduced_config("stablelm-3b")
    return cfg, init_params(cfg, 0)


def test_preempted_request_traces_two_decode_segments(tiny_model, tmp_path):
    """Preempt -> resume shows up as ONE request trace with TWO decode
    spans plus PREEMPT/RESUME markers, and the SLO series populate."""
    from repro.serving import AdmissionController, DecodeEngine, Request

    cfg, params = tiny_model
    rng = np.random.default_rng(7)
    tracer = Tracer()
    ctrl = AdmissionController(tracer=tracer)
    ctrl.add_tenant("research", shares=1)
    ctrl.add_tenant("prod", shares=10)
    eng = DecodeEngine(cfg, params, num_slots=2, cache_len=64,
                       admission=ctrl, tracer=tracer)
    scavs = [Request(rid=i,
                     prompt=rng.integers(0, cfg.vocab_size, 8).astype(
                         np.int32),
                     max_new_tokens=16, tenant="research", qos="scavenger")
             for i in range(2)]
    for r in scavs:
        eng.submit(r)
    for _ in range(4):
        eng.step()
    hi = Request(rid=2,
                 prompt=rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                 max_new_tokens=4, tenant="prod", qos="high")
    eng.submit(hi)
    eng.run_to_completion()
    (victim,) = [r for r in scavs if r.preemptions == 1]

    track = ("serving:research", f"req {victim.rid}")
    (root,) = tracer.spans(name=f"request {victim.rid}")
    assert root.track == track and root.attrs["qos"] == "scavenger"
    decodes = tracer.spans(name="DECODE", track=track)
    assert len(decodes) == 2                   # two residency segments
    assert all(d.parent == root.sid for d in decodes)
    assert decodes[0].attrs["stop"] == "PREEMPT"
    marks = [e.name for e in root.events]
    assert marks.count("PREEMPT") == 1 and marks.count("RESUME") == 1
    assert marks[0] == "SUBMIT" and marks[-1] == "FINISH"
    # two queue waits (initial + requeue), ONE ttft (resume is not a
    # first token)
    qw = tracer.metrics.histogram(METRIC_SERVE_QUEUE_WAIT)
    assert qw.count(tenant="research", qos="scavenger") >= 3   # 2 + victim
    ttft = tracer.metrics.histogram(METRIC_SERVE_TTFT)
    assert ttft.count(tenant="research", qos="scavenger") == 2
    assert ttft.count(tenant="prod", qos="high") == 1
    itl = tracer.metrics.histogram(METRIC_SERVE_ITL)
    assert itl.count(tenant="research", qos="scavenger") > 0
    # every lifecycle state reached the trace, and the export is valid
    names = {s.name for s in tracer.spans()}
    assert {"QUEUED", "PREFILL", "DECODE", "engine.dispatch",
            "engine.sync"} <= names
    path = tmp_path / "serve_trace.json"
    data = tracer.export_chrome(str(path))
    ts = [e["ts"] for e in data["traceEvents"] if e["ph"] != "M"]
    assert ts == sorted(ts) and len(ts) > 10


def _host_events(logdir) -> list:
    """(name, start_ns, end_ns) of every ``engine.*`` host event in the
    profiler trace under ``logdir``."""
    import glob

    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    return sorted((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                  for plane in ProfileData.from_file(path).planes
                  if plane.name.startswith("/host:")
                  for ln in plane.lines for ev in ln.events
                  if ev.name.startswith("engine."))


def test_engine_phases_tile_each_step_under_the_profiler(tiny_model,
                                                         tmp_path):
    """Every engine.* phase of a budgeted engine lies inside an
    engine.step; the outermost phases of a step do not overlap and
    cover at least 95% of it."""
    import jax

    from repro.serving import DecodeEngine, Request

    cfg, params = tiny_model
    rng = np.random.default_rng(11)
    eng = DecodeEngine(cfg, params, num_slots=2, cache_len=64,
                       kv_page_size=8, max_batch_tokens=16, decode_chunk=4)
    for i in range(4):
        eng.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, 10 + 9 * i).astype(np.int32),
            max_new_tokens=6))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    left = 1
    while left:
        with jax.profiler.TraceAnnotation("engine.step"):
            left = eng.step()
    jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    steps = [(s, e) for n, s, e in events if n == "engine.step"]
    phases = [(s, e, n) for n, s, e in events if n != "engine.step"]
    assert len(steps) >= 5
    assert {n for _, _, n in phases} == {
        "engine.admit", "engine.plan", "engine.dispatch", "engine.sync",
        "engine.commit"}
    inside = 0
    for s0, e0 in steps:
        mine = sorted((s, e) for s, e, _ in phases if s0 <= s and e <= e0)
        inside += len(mine)
        outer = [(s, e) for s, e in mine
                 if not any(a <= s and e <= b and (a, b) != (s, e)
                            for a, b in mine)]
        assert all(a[1] <= b[0] for a, b in zip(outer, outer[1:]))
        assert sum(e - s for s, e in outer) >= 0.95 * (e0 - s0)
    assert inside == len(phases)


def test_engine_programs_have_unique_names(tiny_model):
    """A profiler trace names each jitted program after its function
    (``jit_<name>``): every builder's is its own."""
    from repro.serving import DecodeEngine

    cfg, params = tiny_model
    eng = DecodeEngine(cfg, params, num_slots=2, cache_len=64,
                       kv_page_size=8, max_batch_tokens=16, decode_chunk=4,
                       prefix_cache=True, speculate=2)
    names = {attr: getattr(eng, attr).__name__ for attr in (
        "_step", "_decode_n", "_insert", "_prefill_fn", "_suffix_prefill_fn",
        "_chunk_fn", "_mixed_step", "_verify_fn", "_mixed_verify")}
    assert names == {
        "_step": "step_paged", "_decode_n": "step_n_paged",
        "_insert": "insert_paged", "_prefill_fn": "prefill_fn",
        "_suffix_prefill_fn": "suffix_fn", "_chunk_fn": "chunk_fn",
        "_mixed_step": "mixed", "_verify_fn": "verify",
        "_mixed_verify": "mixed_verify"}


def test_untraced_engine_has_no_trace_state(tiny_model):
    """tracer=None pays nothing: no span dicts, no SLO series."""
    from repro.serving import DecodeEngine, Request

    cfg, params = tiny_model
    eng = DecodeEngine(cfg, params, num_slots=1, cache_len=64)
    r = Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                max_new_tokens=4)
    eng.submit(r)
    eng.run_to_completion()
    assert r._trace == {} and r._t_last is None
    assert eng.metrics.histogram(METRIC_SERVE_TTFT).count() == 0


# ---------------------------------------------------- cluster integration ----

def _small_cluster(n_nodes=4):
    from repro.cluster import Cluster, Node, Partition
    nodes = [Node(name=f"n{i:02d}", cpus=16, mem_mb=65536,
                  gres={"tpu": 4}, coord=(0, i)) for i in range(n_nodes)]
    parts = [Partition(name="gpu", nodes=tuple(n.name for n in nodes),
                       default=True)]
    return Cluster(nodes, parts)


def test_cluster_jobs_emit_state_spans_on_virtual_clock():
    from repro.cluster import ResourceRequest

    c = _small_cluster()
    tracer = Tracer()
    c.tracer = tracer
    req = ResourceRequest(nodes=4, gres_per_node={"tpu": 4},
                          cpus_per_node=1, mem_mb_per_node=1024,
                          time_limit_s=36_000)
    (sc,) = c.submit("scav", req, user="bob", qos="scavenger",
                     run_time_s=1000)
    c.clock = 250.0
    (hi,) = c.submit("prod", req, user="alice", qos="high", run_time_s=50)
    c.run()

    track = ("cluster:root", f"job {sc}")
    (root,) = tracer.spans(name=f"job {sc}")
    assert root.track == track and root.attrs["state"] == "COMPLETED"
    states = [(s.name, s.start, s.end)
              for s in tracer.spans(cat="state", track=track)]
    names = [n for n, _, _ in states]
    # PENDING -> RUNNING -> PREEMPTED -> PENDING(requeued) -> RUNNING
    assert names == ["PENDING", "RUNNING", "PREEMPTED", "PENDING",
                     "RUNNING"]
    # virtual-clock timestamps: first RUNNING segment spans [0, 250)
    assert states[1][1] == 0.0 and states[1][2] == 250.0
    assert states[2] == ("PREEMPTED", 250.0, 250.0)   # zero-length marker
    # the high job's trace closes COMPLETED with a RUNNING segment
    (hroot,) = tracer.spans(name=f"job {hi}")
    assert hroot.attrs["state"] == "COMPLETED"
    # scheduler passes were traced and timed
    assert tracer.spans(name="schedule_pass")
    assert c.sched_stats["passes"] > 0
    assert c.sched_stats["total_us"] >= c.sched_stats["max_us"] > 0


def test_sdiag_reports_all_sections(tiny_model):
    from repro.cluster import ResourceRequest, commands
    from repro.serving import AdmissionController, DecodeEngine, Request

    c = _small_cluster(n_nodes=1)
    tracer = Tracer()
    c.tracer = tracer
    c.submit("j", ResourceRequest(nodes=1, gres_per_node={"tpu": 4},
                                  cpus_per_node=1, mem_mb_per_node=1024,
                                  time_limit_s=3600), run_time_s=10)
    c.run()
    cfg, params = tiny_model
    ctrl = AdmissionController(tracer=tracer)
    eng = DecodeEngine(cfg, params, num_slots=1, cache_len=64,
                       admission=ctrl, tracer=tracer)
    eng.submit(Request(rid=0, prompt=np.arange(6, dtype=np.int32),
                       max_new_tokens=4, qos="high"))
    eng.run_to_completion()
    out = commands.sdiag(cluster=c, tracer=tracer, admission=ctrl)
    assert "Main schedule statistics" in out
    assert "Total cycles:" in out and "Jobs started:     1" in out
    assert "Admission controller statistics" in out
    assert "Picks:            1" in out
    assert "Serving SLO" in out and "default" in out and "high" in out
    assert commands.sdiag() == "sdiag: nothing to report"
