"""Fleet-wide distributed tracing (ISSUE 14): cross-process trace
propagation (`X-Trace-Id`/`X-Parent-Span`), merged fleet export +
per-request critical-path attribution, tail-based sampling, and the
post-mortem flight recorder.

Correctness anchors:
  * one trace id end to end — a hedged unary request and a
    killed/failed-over stream each carry a SINGLE trace id across
    every leg (primary, hedge, resume), and a merged buffer has zero
    orphan spans;
  * the wire pair degrades, never 400s — a malformed parent span id
    parses to 0 (root of a remote track), a missing trace id to None;
  * bounded buffers — the span ring evicts (counted), the JSONL event
    log rotates (counted) and its flush accounting stays CUMULATIVE
    across rotations;
  * tail sampling keeps only interesting requests (slow / failed /
    shed / hedged / resumed) and physically discards the rest's
    buffered spans;
  * the flight recorder dumps on its trigger table — rollback,
    quarantine, failover, shed storm, divergence, faulted flush —
    rate-limited per trigger, WITHOUT any trace exporter configured.

Cost control: everything but the last two tests runs on scriptable
stubs and hand-built buffers (no compiled programs).  One test kills
a real engine of a two-engine in-process fleet mid-stream; the one
real worker subprocess test is `@pytest.mark.slow`."""

import glob
import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

from singa_tpu import obs
from singa_tpu.obs import collect
from singa_tpu.obs.flightrec import FlightRecorder
from singa_tpu.obs.log import EventLog
from singa_tpu.obs.metrics import MetricsRegistry
from singa_tpu.obs.trace import Tracer
from singa_tpu.serve import Router, RouterSpec, qos
from singa_tpu.serve.router import (HttpEngineHandle, RequestLog,
                                    RouterStats)
from singa_tpu.serve.stats import ServeStats
from singa_tpu.utils.faults import FaultSchedule, inject

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_leaked_session():
    obs.disable()
    yield
    obs.disable()


# -- the wire pair: serialize / parse ----------------------------------------

def test_trace_headers_roundtrip():
    assert qos.trace_to_headers(None) == {}
    assert qos.trace_to_headers(("", 0)) == {}
    h = qos.trace_to_headers(("abc123", 42))
    assert h == {qos.TRACE_HEADER: "abc123",
                 qos.PARENT_SPAN_HEADER: "42"}
    assert qos.trace_from_headers("abc123", "42") == ("abc123", 42)
    # a trace id without a parent span: root of a remote track
    h = qos.trace_to_headers(("abc123", 0))
    assert h == {qos.TRACE_HEADER: "abc123"}
    assert qos.trace_from_headers("abc123", None) == ("abc123", 0)


def test_trace_headers_degrade_never_reject():
    """A malformed span id parses to 0 and a missing trace id to None
    — telemetry that rides along on a request must never 400 it."""
    assert qos.trace_from_headers("abc123", "not-a-number") == \
        ("abc123", 0)
    assert qos.trace_from_headers(None, "42") is None
    assert qos.trace_from_headers("   ", "42") is None


def test_explicit_anchor_joins_remote_trace():
    """The receive side of a hop: `span(..., trace=..., parent=...)`
    lands the local span in the SENDER's trace under its span."""
    with obs.session(obs.ObsSpec()) as o:
        with obs.span("frontend") as fsp:
            ctx = obs.trace_context()
            assert ctx == (fsp.trace, fsp.span_id)
            wire = qos.trace_to_headers(ctx)
        # "other process": parse the pair back and re-anchor
        rx = qos.trace_from_headers(wire.get(qos.TRACE_HEADER),
                                    wire.get(qos.PARENT_SPAN_HEADER))
        with obs.span("worker", trace=rx[0], parent=rx[1]) as wsp:
            assert wsp.trace == fsp.trace
            assert wsp.parent_id == fsp.span_id
        evs = {e["name"]: e for e in o.tracer.events()}
    assert evs["worker"]["args"]["trace"] == \
        evs["frontend"]["args"]["trace"]
    assert evs["worker"]["args"]["parent_id"] == \
        evs["frontend"]["args"]["span_id"]


# -- bounded span buffer (satellite: ring mode) ------------------------------

def test_trace_ring_keeps_most_recent_and_counts_evictions():
    t = Tracer(ring=4, process="w0")
    t0 = time.perf_counter()
    for i in range(10):
        t.add_span(f"s{i}", t0, 0.001)
    evs = t.events()
    assert [e["name"] for e in evs] == ["s6", "s7", "s8", "s9"]
    assert t.evicted == 6 and t.dropped == 0
    d = t.trace_dict()
    assert d["process"] == "w0" and "wall_origin_s" in d


def test_discard_trace_counts_sampled_out():
    t = Tracer()
    t0 = time.perf_counter()
    t.add_span("keep", t0, 0.001, trace="t-keep")
    t.add_span("drop1", t0, 0.001, trace="t-drop")
    t.add_span("drop2", t0, 0.001, trace="t-drop")
    assert t.discard_trace("t-drop") == 2
    assert [e["name"] for e in t.events()] == ["keep"]
    assert t.sampled_out == 2
    assert t.discard_trace("") == 0


# -- tail-based sampling policy ----------------------------------------------

def test_tail_sampler_policy_matrix():
    s = obs.TailSampler(obs.ObsSpec(sample="tail", sample_slow_ms=50))
    assert not s.keep(0.010)                  # fast + boring: dropped
    assert s.keep(0.100)                      # slow against the bar
    assert s.keep(0.001, failed=True)
    assert s.keep(0.001, shed=True)
    assert s.keep(0.001, hedged=True)
    assert s.keep(0.001, resumed=True)
    snap = s.snapshot()
    assert snap == {"policy": "tail", "kept": 5, "sampled_out": 1}
    # no explicit bar: the caller's windowed p95 decides
    s = obs.TailSampler(obs.ObsSpec(sample="tail"))
    assert s.keep(0.200, p95_s=0.1)
    assert not s.keep(0.050, p95_s=0.1)
    assert not s.keep(0.050, p95_s=None)      # no signal: count+drop
    # sample=all keeps everything, sampler is pure bookkeeping
    s = obs.TailSampler(obs.ObsSpec(sample="all"))
    assert s.keep(0.0001) and s.snapshot()["sampled_out"] == 0


def test_sample_trace_discards_buffered_spans():
    spec = obs.ObsSpec(sample="tail", sample_slow_ms=1000)
    with obs.session(spec) as o:
        with obs.span("boring") as sp:
            tid = sp.trace
        assert len(o.tracer.events()) == 1
        assert obs.sample_trace(tid, 0.001) is False
        assert o.tracer.events() == []        # physically discarded
        assert o.tracer.sampled_out == 1
        # an interesting request at the same latency is kept
        with obs.span("hedged") as sp:
            tid2 = sp.trace
        assert obs.sample_trace(tid2, 0.001, hedged=True) is True
        assert [e["name"] for e in o.tracer.events()] == ["hedged"]


def test_obs_spec_grammar_new_keys():
    s = obs.ObsSpec.parse("sample=tail,sample_slow_ms=250,"
                          "trace_ring=128,max_events_mb=1.5,"
                          "process=w0,flightrec=/tmp/fr,"
                          "flightrec_ring=64")
    assert s.sample == "tail" and s.sample_slow_ms == 250.0
    assert s.trace_ring == 128 and s.max_events_mb == 1.5
    assert s.process == "w0" and s.flightrec == "/tmp/fr"
    assert s.flightrec_ring == 64
    with pytest.raises(ValueError):
        obs.ObsSpec.parse("sample=sometimes")


# -- merged export: dedup, re-anchor, orphans, critical path -----------------

def _buf(process, pid, wall_origin_s, spans):
    evs = [{"ph": "M", "pid": pid, "tid": 0, "name": "process_name",
            "args": {"name": process}}]
    for name, sid, parent, ts, dur, extra in spans:
        args = {"span_id": sid, "trace": "t1"}
        if parent:
            args["parent_id"] = parent
        args.update(extra)
        evs.append({"ph": "X", "cat": "obs", "name": name,
                    "pid": pid, "tid": 1, "ts": ts, "dur": dur,
                    "args": args})
    return {"traceEvents": evs, "displayTimeUnit": "ms",
            "process": process, "pid": pid,
            "wall_origin_s": wall_origin_s}


def test_merge_dedupes_and_reanchors_onto_earliest_origin():
    router = _buf("router", 1, 100.0,
                  [("router.dispatch", 1, 0, 0.0, 1000.0, {})])
    worker = _buf("worker-0", 2, 100.0005,
                  [("serve.request", 2, 1, 0.0, 400.0,
                    {"engine": "e0"})])
    # the worker buffer pulled twice (overlapping /trace windows):
    # dedup on (pid, span_id) keeps one copy
    m = collect.merge([router, worker, worker])
    spans = [e for e in m["traceEvents"] if e["ph"] == "X"]
    assert len(spans) == 2
    assert collect.trace_ids(m) == ["t1"]
    # worker ts re-anchored by the 500us origin skew
    by_name = {e["name"]: e for e in spans}
    assert by_name["serve.request"]["ts"] == pytest.approx(500.0)
    assert by_name["router.dispatch"]["ts"] == pytest.approx(0.0)
    # metadata first, both process names survive
    assert m["traceEvents"][0]["ph"] == "M"
    assert m["processes"] == {1: "router", 2: "worker-0"}
    # every parent resolves across the process boundary
    assert collect.orphans(m, "t1") == []


def test_merge_orphans_and_critical_path():
    router = _buf("router", 1, 100.0,
                  [("router.dispatch", 1, 0, 0.0, 1000.0, {}),
                   ("lost", 3, 999, 10.0, 5.0, {})])
    worker = _buf("worker-0", 2, 100.0,
                  [("serve.request", 2, 1, 100.0, 400.0,
                    {"engine": "e0"})])
    m = collect.merge([router, worker])
    orphans = collect.orphans(m, "t1")
    assert [e["name"] for e in orphans] == ["lost"]
    # self time = duration minus child overlap: the dispatch span
    # mostly WAITED on the worker, so the worker leads the path
    rows = collect.critical_path(m, "t1")
    self_us = {r["name"]: r["self_us"] for r in rows}
    # the orphan's missing parent discounts nothing: 1000 - 400
    assert self_us["router.dispatch"] == pytest.approx(600.0)
    assert self_us["serve.request"] == pytest.approx(400.0)
    assert rows[0]["name"] == "router.dispatch"
    assert rows[0]["process"] == "router"
    assert {r["name"] for r in rows} == \
        {"router.dispatch", "serve.request", "lost"}


# -- event-log rotation: counters stay cumulative (satellite a) --------------

def test_eventlog_rotation_never_resets_counters(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    log = EventLog(path, max_bytes=256)
    for i in range(30):
        assert log.emit("tick", i=i, pad="x" * 40)
    assert log.rotations >= 1
    assert os.path.exists(path + ".1")
    assert log.written == 30 and log.dropped == 0
    log.close()
    # the live file holds only the post-rotation suffix, yet the
    # counter covered every generation
    with open(path) as f:
        live = [json.loads(ln) for ln in f if ln.strip()]
    assert 0 < len(live) < 30


def test_flush_accounting_survives_rotation(tmp_path):
    """The obs.flush record's `events_written` must keep adding up no
    matter how many times the JSONL file rolled underneath it."""
    path = str(tmp_path / "ev.jsonl")
    spec = obs.ObsSpec(events=path, max_events_mb=0.0002)  # ~200 B
    with obs.session(spec):
        for i in range(12):
            obs.emit_event("tick", i=i, pad="y" * 40)
    recs = []
    for p in (path, path + ".1"):
        if os.path.exists(p):
            with open(p) as f:
                recs += [json.loads(ln) for ln in f if ln.strip()]
    flush = [r for r in recs if r["kind"] == "obs.flush"]
    assert len(flush) == 1
    assert flush[0]["events_written"] >= 12
    assert flush[0]["events_rotations"] >= 1
    assert flush[0]["events_dropped"] == 0


# -- per-request lifecycle records (GET /debug/requests) ---------------------

def test_request_log_bounds_and_slowest():
    rl = RequestLog(keep=4, slowest=2)
    for i in range(10):
        rl.record(corr=f"req-{i}", latency_ms=float(i))
    snap = rl.snapshot()
    assert snap["recorded"] == 10
    assert [r["corr"] for r in snap["recent"]] == \
        ["req-6", "req-7", "req-8", "req-9"]
    assert [r["latency_ms"] for r in snap["slowest"]] == [9.0, 8.0]
    assert all("ts" in r for r in snap["recent"])


# -- real Prometheus histograms (satellite b) --------------------------------

def test_router_stats_histograms_render():
    reg = MetricsRegistry()
    rs = RouterStats()
    rs.register_into(reg)
    rs.observe_latency(0.05)
    rs.observe_stage("admit", 0.01)
    rs.observe_stage("decode", 0.04)
    text = reg.render_prometheus()
    for name in ("singa_fleet_request_latency_seconds",
                 "singa_request_stage_seconds_admit",
                 "singa_request_stage_seconds_decode"):
        assert f"{name}_bucket{{le=" in text, name
        assert f"{name}_sum" in text and f"{name}_count" in text
    # no registry attached: observe_stage is a no-op, not a crash
    RouterStats().observe_stage("admit", 0.01)


def test_serve_stats_histograms_render():
    reg = MetricsRegistry()
    ss = ServeStats()
    ss.register_into(reg)
    ss.observe_latency(0.02)
    ss.observe_request(queue_wait_s=0.005, service_s=0.015,
                       ntokens=8)
    text = reg.render_prometheus()
    for name in ("singa_serve_request_latency_seconds",
                 "singa_serve_queue_wait_seconds",
                 "singa_serve_service_seconds"):
        assert f"{name}_bucket{{le=" in text, name
        assert f"{name}_sum" in text and f"{name}_count" in text
    # unregistered stats keep working without histograms
    ServeStats().observe_latency(0.01)


# -- scriptable stream stubs (the test_failover.py mold) ---------------------

def _tok(step, j):
    return (int(step) * 7 + j * 3) % 101


class StreamStubHandle:
    """Engine-handle double speaking the indexed stream protocol,
    scriptable to die at an absolute token index (fires once)."""

    def __init__(self, name, step=1):
        self.name = name
        self.step = step
        self.die_at = None
        self.calls = []

    def probe(self):
        return {"ok": True, "status": "ok", "step": self.step,
                "queue_depth": 0}

    def stats_snapshot(self):
        return {"completed": 0, "failed": 0, "expired": 0,
                "p95_latency_ms": None}

    def request(self, mode, tokens, timeout=None):
        return {"tokens": [1], "step": self.step}

    def request_stream(self, tokens, timeout=None, max_new=None,
                       deadline=None, priority="interactive",
                       cancel_event=None, resume_from=0):
        self.calls.append((int(resume_from), len(tokens)))

        def gen():
            for j in range(int(resume_from), int(max_new)):
                if self.die_at == j:
                    self.die_at = None
                    raise RuntimeError(f"{self.name} exploded at {j}")
                yield {"token": _tok(self.step, j), "i": j}
            yield {"done": True, "finish": "length",
                   "step": self.step,
                   "tokens": [_tok(self.step, j) for j in
                              range(int(resume_from), int(max_new))]}
        return gen()


class SlowUnaryStubHandle(StreamStubHandle):
    """Unary requests take `delay` seconds — long enough for the
    router's forced 10ms hedge delay to fire a second leg."""

    def __init__(self, name, step=1, delay=0.15):
        super().__init__(name, step=step)
        self.delay = delay

    def request(self, mode, tokens, timeout=None):
        time.sleep(self.delay)
        return {"tokens": [1], "step": self.step}


def _router(handles, **spec_kw):
    spec_kw.setdefault("probe_period_s", 60.0)
    spec_kw.setdefault("quarantine_after", 10)
    spec_kw.setdefault("request_timeout_s", 10.0)
    spec_kw.setdefault("hedge", "off")
    r = Router(handles, spec=RouterSpec(**spec_kw),
               log_fn=lambda s: None)
    r.probe_all()
    return r


def _consume(stream):
    toks, done = [], None
    for ev in stream:
        if ev.get("done"):
            done = ev
            break
        toks.append(ev)
    return toks, done


# -- satellite c: one trace id across primary + hedge + resumed legs ---------

def test_one_trace_id_spans_failover_legs():
    """A mid-stream engine death must not fork the trace: the resume
    leg (and the post-hoc stage spans) anchor under the originating
    `router.stream` span, same trace id, same corr."""
    e0, e1 = StreamStubHandle("e0"), StreamStubHandle("e1")
    e0.die_at = 3
    r = _router([e0, e1])
    try:
        with obs.session(obs.ObsSpec()):
            toks, done = _consume(r.route_stream([5, 6], max_new=8))
            evs = [e for e in obs.trace_dump()["traceEvents"]
                   if e["ph"] == "X"]
            merged = collect.merge([obs.trace_dump()])
        assert done["spliced"] is True and done["resumes"] == 1
        assert len(toks) == 8
        by_name = {}
        for e in evs:
            by_name.setdefault(e["name"], []).append(e)
        for needed in ("router.stream", "router.attempt",
                       "router.resume", "stream.first_token",
                       "stream.decode"):
            assert needed in by_name, (needed, sorted(by_name))
        root = by_name["router.stream"][0]
        tid = root["args"]["trace"]
        corr = root["args"]["corr"]
        # every leg — dispatch attempt, failover resume, post-hoc
        # stage spans — carries the ONE trace id and originating corr
        legs = (by_name["router.attempt"] + by_name["router.resume"]
                + by_name["stream.first_token"]
                + by_name["stream.decode"])
        assert {e["args"]["trace"] for e in legs} == {tid}
        assert {e["args"].get("corr") for e in legs} == {corr}
        # the resume leg is anchored under the stream root and names
        # both engines of the splice
        rsp = by_name["router.resume"][0]["args"]
        assert rsp["parent_id"] == root["args"]["span_id"]
        assert rsp["from_engine"] == "e0" and rsp["engine"] == "e1"
        assert collect.orphans(merged, tid) == []
        # the lifecycle record indexes the same trace
        row = r.requests.snapshot()["recent"][-1]
        assert row["trace"] == tid and row["corr"] == corr
        assert row["outcome"] == "spliced" and row["resumes"] == 1
    finally:
        r.stop()


def test_one_trace_id_spans_hedge_legs():
    """Both legs of a hedged unary request carry the originating
    corr/trace — the regression was each hedge run() thread minting a
    fresh root, making hedges invisible in any trace."""
    e0 = SlowUnaryStubHandle("e0")
    e1 = SlowUnaryStubHandle("e1")
    r = _router([e0, e1], hedge="on",
                hedge_min_s=0.01, hedge_max_s=0.01)
    try:
        with obs.session(obs.ObsSpec()):
            out = r.route("generate", [5, 6])
            # the losing leg closes its span AFTER the winner returns
            # (its thread is still in the stub's sleep): wait for it
            stop = time.monotonic() + 5.0
            while time.monotonic() < stop:
                evs = [e for e in obs.trace_dump()["traceEvents"]
                       if e["ph"] == "X"]
                if sum(1 for e in evs
                       if e["name"] == "router.attempt") >= 2:
                    break
                time.sleep(0.01)
        assert out["engine"] in ("e0", "e1")
        disp = [e for e in evs if e["name"] == "router.dispatch"]
        attempts = [e for e in evs if e["name"] == "router.attempt"]
        assert len(disp) == 1 and len(attempts) >= 2
        tid = disp[0]["args"]["trace"]
        corr = disp[0]["args"]["corr"]
        assert {e["args"]["trace"] for e in attempts} == {tid}
        assert {e["args"]["corr"] for e in attempts} == {corr}
        hedge_flags = {e["args"]["hedge"] for e in attempts}
        assert hedge_flags == {True, False}
        assert all(e["args"]["parent_id"] ==
                   disp[0]["args"]["span_id"] for e in attempts)
        row = r.requests.snapshot()["recent"][-1]
        assert row["hedged"] is True and row["trace"] == tid
    finally:
        r.stop()


def test_stage_partition_sums_to_latency():
    """admit/first_token/decode share one clock and its boundary
    stamps, so the recorded stages sum to the recorded latency."""
    r = _router([StreamStubHandle("e0")])
    try:
        with obs.session(obs.ObsSpec()):
            _consume(r.route_stream([5], max_new=4))
        row = r.requests.snapshot()["recent"][-1]
        assert set(row["stages_ms"]) == \
            {"admit", "first_token", "decode"}
        assert sum(row["stages_ms"].values()) == \
            pytest.approx(row["latency_ms"], abs=0.005)
    finally:
        r.stop()


# -- flight recorder ---------------------------------------------------------

def test_flightrec_trigger_table(tmp_path):
    fr = FlightRecorder(str(tmp_path), ring=32, cooldown_s=0.05)
    path = fr.observe("fleet.rollback", {"target": 7})
    assert path and "flightrec-rollback-" in os.path.basename(path)
    with open(path) as f:
        dump = json.load(f)
    assert dump["trigger"] == "rollback"
    assert dump["events"][-1]["kind"] == "fleet.rollback"
    assert dump["events"][-1]["target"] == 7
    # rate limit: a second rollback inside the cooldown is absorbed
    assert fr.observe("fleet.rollback", {}) is None
    time.sleep(0.06)
    assert fr.observe("fleet.rollback", {}) is not None
    # the rest of the trigger table
    p = fr.observe("fleet.quarantine", {"engine": "e0"})
    assert p and "flightrec-quarantine-" in os.path.basename(p)
    p = fr.observe("stream.resume", {"sid": "s1"})
    assert p and "flightrec-failover-" in os.path.basename(p)
    p = fr.observe("health.verdict", {"verdict": "DIVERGED"})
    assert p and "flightrec-divergence-" in os.path.basename(p)
    assert fr.observe("health.verdict", {"verdict": "HEALTHY"}) is None
    assert fr.dumps == 5 and fr.dump_failures == 0


def test_flightrec_shed_storm(tmp_path):
    fr = FlightRecorder(str(tmp_path), cooldown_s=0.0)
    paths = [fr.observe("serve.shed", {"priority": "best_effort"})
             for _ in range(16)]
    # one shed is load; the 16th inside the window is an incident
    assert all(p is None for p in paths[:15])
    assert paths[15] and "shed_storm" in os.path.basename(paths[15])
    assert fr.sheds_seen == 16


def test_flightrec_dump_carries_tracer_tail(tmp_path):
    t = Tracer(process="w0")
    t.add_span("serve.request", time.perf_counter(), 0.001,
               corr="req-1")
    fr = FlightRecorder(str(tmp_path))
    path = fr.trigger("quarantine", tracer=t, engine="e0", strikes=3)
    with open(path) as f:
        dump = json.load(f)
    assert dump["process"] == "w0"
    assert dump["context"] == {"engine": "e0", "strikes": 3}
    assert [s["name"] for s in dump["spans"]] == ["serve.request"]


def test_flightrec_fires_without_trace_export(tmp_path):
    """The 3am story: nobody configured trace/events exporters, only
    `flightrec=...` — a failover event must still leave a dump."""
    fr_dir = str(tmp_path / "fr")
    with obs.session(obs.ObsSpec(flightrec=fr_dir)):
        obs.emit_event("stream.resume", sid="s1", from_engine="e0",
                       engine="e1", at=3)
    dumps = glob.glob(os.path.join(fr_dir, "flightrec-failover-*.json"))
    assert len(dumps) == 1
    with open(dumps[0]) as f:
        dump = json.load(f)
    assert any(ev["kind"] == "stream.resume" for ev in dump["events"])


def test_obs_flush_fault_triggers_flightrec(tmp_path):
    """A faulted telemetry teardown is itself a trigger — the one
    loss the recorder exists to survive."""
    fr_dir = str(tmp_path / "fr")
    sched = FaultSchedule.parse("obs.flush@0")
    with obs.session(obs.ObsSpec(flightrec=fr_dir)):
        with obs.span("work"):
            pass
        with inject(sched):
            obs.disable()                      # flush under fault
    assert [f.site for f in sched.fired] == ["obs.flush"]
    dumps = glob.glob(os.path.join(fr_dir, "flightrec-*.json"))
    assert len(dumps) == 1
    with open(dumps[0]) as f:
        dump = json.load(f)
    assert dump["trigger"] == "obs.flush_fault"
    assert [s["name"] for s in dump["spans"]] == ["work"]


# -- satellite d: real two-process propagation -------------------------------

@pytest.mark.slow
def test_worker_spans_carry_router_trace_two_process(tmp_path):
    """Spawn a real pinned worker subprocess with `--obs on`, route
    one request through a local Router under a router-side session,
    pull the worker's `/trace` ring, and prove the merged file holds
    ONE trace spanning both pids with zero orphans."""
    port = 18517
    url = f"http://127.0.0.1:{port}"
    spec = ("buckets=2x128,max_new_tokens=8,batch_window_s=0.005,"
            "cb=on,cb_slots=2,cb_block_len=16")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "singa_tpu.main", "serve",
         "-model_conf", "examples/transformer/lm.conf", "--pinned",
         "--port", str(port), "--serve_spec", spec,
         "--workspace", str(tmp_path), "--obs", "on",
         "--obs_spec", "trace_ring=4096,process=worker-0"],
        cwd=REPO, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    r = None
    try:
        deadline = time.monotonic() + 300.0
        while True:
            if proc.poll() is not None:
                pytest.fail("worker exited before serving /healthz")
            try:
                with urllib.request.urlopen(url + "/healthz",
                                            timeout=2.0) as resp:
                    if resp.status == 200:
                        break
            except Exception:
                pass
            if time.monotonic() > deadline:
                pytest.fail("worker never became healthy")
            time.sleep(0.5)
        with obs.session(obs.ObsSpec(process="router",
                                     trace_ring=65536)):
            r = Router([HttpEngineHandle("w0", url)],
                       spec=RouterSpec(probe_period_s=60.0,
                                       quarantine_after=5,
                                       request_timeout_s=120.0,
                                       hedge="off"),
                       log_fn=lambda s: None)
            r.probe_all()
            out = r.route("generate", [5, 7, 9, 11], timeout=120.0)
            assert out["tokens"]
            row = r.requests.snapshot()["recent"][-1]
            tid = row["trace"]
            assert tid
            worker_buf = collect.fetch_trace(url)
            merged = collect.merge([obs.trace_dump(), worker_buf])
        spans = collect.spans_of(merged, tid)
        names = {e["name"] for e in spans}
        assert "router.dispatch" in names and "serve.request" in names
        # the trace crossed the process boundary: both pids, both
        # process names, and every remote parent resolves
        assert len({e["pid"] for e in spans}) >= 2
        assert {"router", "worker-0"} <= set(merged["processes"].values())
        assert collect.orphans(merged, tid) == []
    finally:
        if r is not None:
            r.stop()
        proc.kill()
        proc.wait(30)


# -- one trace id across REAL engines (two compiled engines, one fleet) ------

def test_one_trace_id_spans_real_engines_across_a_failover():
    """The stub choreography above over real compiled engines: a
    stream whose engine is killed mid-decode keeps ONE trace id from
    the router's root span through both engines' scheduler spans, the
    merged buffer has no orphan span, and the lifecycle row indexes
    the same trace."""
    from singa_tpu.serve import EngineFleet, ServeSpec
    from test_fleet import SEQ, _net_and_params

    net, params = _net_and_params()
    max_new = 24
    spec = ServeSpec(buckets=((2, SEQ),), max_new_tokens=max_new,
                     temperature=0.0, batch_window_s=0.002,
                     request_timeout_s=60.0,
                     cb="on", cb_slots=2, cb_block_len=4)
    rspec = RouterSpec(probe_period_s=0.1, quarantine_after=5,
                       hedge="off", request_timeout_s=60.0)
    with obs.session(obs.ObsSpec(process="router", trace_ring=65536)):
        fleet = EngineFleet.local(net, spec, 2, params=params,
                                  router_spec=rspec,
                                  log_fn=lambda s: None)
        fleet.start()
        try:
            for n in fleet.router.names():
                fleet.router.handle_for(n).engine.set_stall(0.01)
            ntok, done = 0, None
            for ev in fleet.generate_stream([3, 1, 4, 1],
                                            max_new=max_new,
                                            timeout=60.0):
                if ev.get("done"):
                    done = ev
                    break
                ntok += 1
                if ntok == 6:
                    fleet.router.handle_for(
                        fleet.router.sessions.snapshot()[
                            "sessions"][0]["engine"]).kill()
            row = fleet.router.requests.snapshot()["recent"][-1]
        finally:
            fleet.stop()
        # dumped once the schedulers and the prober have stopped: a
        # span still open on another thread is not in the buffer yet,
        # and its closed children would read as orphans
        merged = collect.merge([obs.trace_dump()])
    assert done is not None and done["spliced"] and ntok == max_new
    assert row["outcome"] == "spliced" and row["resumes"] >= 1
    tagged = [e["args"] for e in merged["traceEvents"]
              if e.get("ph") == "X"
              and e["args"].get("corr") == row["corr"]]
    assert {a.get("trace") for a in tagged} == {row["trace"]}
    spans = collect.spans_of(merged, row["trace"])
    names = {e["name"] for e in spans}
    assert {"router.stream", "router.resume", "stream.decode"} <= names
    # the engines' own spans joined the router's trace: one prefill
    # for the first leg, one for the resumed leg
    assert sum(e["name"] == "scheduler.prefill" for e in spans) >= 2
    engines = {e["args"].get("engine") for e in spans
               if e["args"].get("engine")}
    assert len(engines) >= 2, engines
    assert collect.orphans(merged, row["trace"]) == []
