"""Unified telemetry tests (ISSUE 6): span tracer + Chrome trace
export, correlation-id flow, ObsSpec grammar, metrics registry +
Prometheus exposition, /metrics-vs-/stats consistency on a live
server, obs.emit fault degradation (dropped telemetry, work
unaffected), the ServeStats.gauge typo regression, and the windowed
QPS / uptime satellites.

Cost control: the one compiled-engine test module-scopes a 1-layer
single-bucket LM server; everything else is pure-host."""

import json
import threading
import urllib.request

import numpy as np
import pytest

from singa_tpu import obs
from singa_tpu.config.schema import model_config_from_dict
from singa_tpu.core.trainer import Trainer
from singa_tpu.data.synthetic import synthetic_image_batches
from singa_tpu.obs.log import EventLog
from singa_tpu.obs.metrics import (MetricsRegistry, Sample,
                                   parse_prometheus)
from singa_tpu.obs.trace import NULL_HANDLE, NULL_SPAN
from singa_tpu.serve.stats import ServeStats
from singa_tpu.utils.faults import FaultSchedule, inject

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _no_leaked_session():
    obs.disable()
    yield
    obs.disable()


# -- tracer / spans ----------------------------------------------------------

def test_span_is_null_when_off():
    assert obs.active() is None
    with NULL_SPAN as sp:
        assert sp is NULL_HANDLE
    with obs.span("anything", corr="x") as sp:
        assert sp is NULL_HANDLE         # no handle, no id, no lock
        sp.set(k=1)                      # no-op, no error
    assert obs.current_corr() is None
    obs.emit_event("nothing", a=1)       # no-op, no error


def test_trace_export_nested_parented_corr(tmp_path):
    trace_path = tmp_path / "trace.json"
    spec = obs.ObsSpec(trace=str(trace_path))
    with obs.session(spec):
        with obs.span("outer", corr="attempt-1", step=4) as outer:
            assert obs.current_corr() == "attempt-1"
            with obs.span("inner") as inner:
                # same-thread spans inherit parent + corr
                assert inner.parent_id == outer.span_id
                assert inner.corr == "attempt-1"
            with obs.span("override", corr="req-9") as ov:
                assert ov.corr == "req-9"
    # session exit exported the trace
    d = json.loads(trace_path.read_text())
    assert d["displayTimeUnit"] == "ms"
    evs = {e["name"]: e for e in d["traceEvents"] if e["ph"] == "X"}
    assert set(evs) == {"outer", "inner", "override"}
    for e in evs.values():
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert e["cat"] == "obs"
    assert evs["inner"]["args"]["parent_id"] == \
        evs["outer"]["args"]["span_id"]
    assert evs["inner"]["args"]["corr"] == "attempt-1"
    assert evs["override"]["args"]["corr"] == "req-9"
    assert evs["outer"]["args"]["step"] == 4
    assert "parent_id" not in evs["outer"]["args"]
    # thread-name metadata rides along for Perfetto track naming
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               for e in d["traceEvents"])


def test_cross_thread_corr_handoff():
    with obs.session(obs.ObsSpec()) as o:
        with obs.span("consumer", corr="attempt-3"):
            corr = obs.current_corr()    # explicit hand-off value

            def producer():
                # thread-local stacks do NOT cross threads: without the
                # explicit corr the producer span would be rootless
                assert obs.current_corr() is None
                with obs.span("producer", corr=corr):
                    pass

            t = threading.Thread(target=producer)
            t.start()
            t.join()
        evs = {e["name"]: e for e in o.tracer.events()}
        assert evs["producer"]["args"]["corr"] == "attempt-3"
        assert "parent_id" not in evs["producer"]["args"]


def test_span_records_error_and_propagates():
    with obs.session(obs.ObsSpec()) as o:
        with pytest.raises(RuntimeError, match="boom"):
            with obs.span("failing"):
                raise RuntimeError("boom")
        (ev,) = o.tracer.events()
        assert ev["args"]["error"] == "RuntimeError"


# -- ObsSpec grammar ---------------------------------------------------------

def test_obsspec_parse_grammar():
    spec = obs.ObsSpec.parse("trace=/tmp/t.json;events=/tmp/e.jsonl,"
                             "metrics_period_s=2.5,max_spans=100")
    assert spec.trace == "/tmp/t.json"
    assert spec.events == "/tmp/e.jsonl"
    assert spec.metrics_period_s == 2.5 and spec.max_spans == 100
    assert obs.ObsSpec.parse(None) == obs.ObsSpec()
    assert obs.ObsSpec.parse("") == obs.ObsSpec()
    with pytest.raises(ValueError, match="bad obs spec entry"):
        obs.ObsSpec.parse("bogus=1")
    with pytest.raises(ValueError, match="bad obs spec"):
        obs.ObsSpec.parse("max_spans")           # no '='
    with pytest.raises(ValueError, match="bad obs spec value"):
        obs.ObsSpec.parse("max_spans=lots")


# -- metrics registry --------------------------------------------------------

def test_registry_prometheus_render_roundtrip():
    reg = MetricsRegistry()
    c = reg.counter("singa_test_steps_total", "steps")
    c.inc()
    c.inc(2)
    assert reg.counter("singa_test_steps_total") is c  # idempotent
    reg.gauge("singa_test_depth").set(7)
    h = reg.histogram("singa_test_latency_seconds",
                      buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    reg.register_collector(lambda: [
        Sample("singa_test_collected", "gauge", "from a surface", 3.5)])
    text = reg.render_prometheus()
    assert "# TYPE singa_test_steps_total counter" in text
    assert "# TYPE singa_test_latency_seconds histogram" in text
    parsed = parse_prometheus(text)
    assert parsed["singa_test_steps_total"] == 3
    assert parsed["singa_test_depth"] == 7
    assert parsed["singa_test_collected"] == 3.5
    # cumulative le-buckets + sum/count
    assert parsed['singa_test_latency_seconds_bucket{le="0.1"}'] == 1
    assert parsed['singa_test_latency_seconds_bucket{le="1"}'] == 2
    assert parsed['singa_test_latency_seconds_bucket{le="+Inf"}'] == 3
    assert parsed["singa_test_latency_seconds_count"] == 3
    assert abs(parsed["singa_test_latency_seconds_sum"] - 5.55) < 1e-9
    # flat snapshot mirrors the same data
    snap = reg.snapshot()
    assert snap["singa_test_steps_total"] == 3
    assert snap["singa_test_latency_seconds_count"] == 3
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("singa_test_steps_total")
    with pytest.raises(ValueError, match="bad metric name"):
        parse_prometheus("this is not prometheus\n")
    with pytest.raises(ValueError, match="bad exposition line"):
        parse_prometheus("lonely_name\n")


def test_registry_broken_collector_is_skipped():
    reg = MetricsRegistry()
    reg.counter("singa_ok_total").inc()
    reg.register_collector(lambda: 1 / 0)
    parsed = parse_prometheus(reg.render_prometheus())
    assert parsed["singa_ok_total"] == 1
    assert reg.collector_errors >= 1


# -- live server: /metrics vs /stats ----------------------------------------

@pytest.fixture(scope="module")
def http_server():
    import jax

    from singa_tpu.core.net import build_net
    from singa_tpu.models.transformer import transformer_lm
    from singa_tpu.serve import (InferenceEngine, InferenceServer,
                                 ServeSpec)
    cfg = transformer_lm(vocab_size=64, num_layers=1, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=16,
                         batchsize=2)
    net = build_net(cfg, "kTest",
                    {"data": {"input": (16,), "target": (16,)}})
    params = net.init_params(jax.random.PRNGKey(0))
    spec = ServeSpec(buckets=((2, 6),), max_new_tokens=3,
                     batch_window_s=0.005, request_timeout_s=20.0)
    engine = InferenceEngine(net, spec, params=params,
                             log_fn=lambda s: None)
    server = InferenceServer(engine, port=0, http=True,
                             log_fn=lambda s: None)
    server.start()
    yield server
    server.stop()


def _get(server, path):
    host, port = server.address
    with urllib.request.urlopen(f"http://{host}:{port}{path}",
                                timeout=30) as r:
        return r.headers.get("Content-Type", ""), r.read().decode()


def test_metrics_endpoint_agrees_with_stats(http_server):
    server = http_server
    for plen in (2, 5, 3):
        server.generate(np.arange(1, 1 + plen, dtype=np.int32))
    ctype, text = _get(server, "/metrics")
    assert ctype.startswith("text/plain")
    parsed = parse_prometheus(text)          # valid exposition format
    _, stats_body = _get(server, "/stats")
    stats = json.loads(stats_body)
    for k in ("submitted", "completed", "failed", "shed", "batches",
              "compiles", "reloads"):
        assert parsed[f"singa_serve_{k}_total"] == stats[k], k
    assert parsed["singa_serve_queue_depth"] == stats["queue_depth"]
    assert parsed["singa_serve_uptime_s"] >= 0
    assert parsed["singa_serve_p95_latency_ms"] == \
        stats["p95_latency_ms"]


def test_obs_emit_fault_request_still_served(http_server):
    server = http_server
    sched = FaultSchedule.parse("obs.emit@0")
    with obs.session(obs.ObsSpec()) as o:
        with inject(sched):
            out = server.generate(np.array([5, 6], np.int32))
    assert len(out["tokens"]) == 3           # request completed
    assert [f.site for f in sched.fired] == ["obs.emit"]
    assert o.tracer.dropped >= 1             # telemetry degraded


# -- obs.emit fault on the training side -------------------------------------

def _tiny_mlp_cfg(train_steps=4):
    return model_config_from_dict({
        "name": "obs-mlp", "train_steps": train_steps,
        "updater": {"type": "kSGD", "base_learning_rate": 0.01,
                    "learning_rate_change_method": "kFixed"},
        "neuralnet": {"layer": [
            {"name": "data", "type": "kShardData",
             "data_param": {"batchsize": 8}},
            {"name": "mnist", "type": "kMnistImage",
             "srclayers": "data", "mnist_param": {"norm_a": 255.0}},
            {"name": "label", "type": "kLabel", "srclayers": "data"},
            {"name": "ip", "type": "kInnerProduct", "srclayers": "mnist",
             "inner_product_param": {"num_output": 10},
             "param": [{"name": "w", "init_method": "kUniformSqrtFanIn"},
                       {"name": "b"}]},
            {"name": "loss", "type": "kSoftmaxLoss",
             "srclayers": ["ip", "label"]}]}})


def test_obs_emit_fault_training_step_completes(tmp_path):
    shapes = {"data": {"pixel": (28, 28), "label": ()}}
    tr = Trainer(_tiny_mlp_cfg(), shapes, log_fn=lambda s: None,
                 donate=False)
    p, o = tr.init(seed=0)
    spec = obs.ObsSpec(trace=str(tmp_path / "t.json"),
                       events=str(tmp_path / "e.jsonl"))
    sched = FaultSchedule.parse("obs.emit@0,obs.emit@1")
    with obs.session(spec) as sess:
        with inject(sched):
            p, o, hist = tr.run(p, o, synthetic_image_batches(
                8, seed=3, stream_seed=104), seed=0)
        dropped = sess.tracer.dropped + \
            (sess.events.dropped if sess.events else 0)
    assert len(sched.fired) == 2             # both faults consumed
    assert dropped >= 1                      # into drop counters...
    for k in p:                              # ...not into the step
        assert np.all(np.isfinite(np.asarray(p[k]))), k


# -- event log + logger ------------------------------------------------------

def test_event_log_writes_jsonl(tmp_path):
    path = tmp_path / "events.jsonl"
    ev = EventLog(str(path))
    assert ev.emit("supervisor.restart", attempt=1, fail_kind="preempt")
    assert ev.emit("health.verdict", step=3, status="SPIKE")
    ev.close()
    assert not ev.emit("late")               # closed -> dropped
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    assert [r["kind"] for r in recs] == ["supervisor.restart",
                                         "health.verdict"]
    assert recs[0]["attempt"] == 1 and "ts" in recs[0]
    assert ev.written == 2 and ev.dropped == 1


def test_logger_prefix_levels_and_event_mirror(tmp_path):
    lines = []
    log = obs.get_logger("trainer", sink=lines.append)
    log("step-5: loss : 0.3")
    log("warning: something soft failed")
    assert lines == ["[trainer] step-5: loss : 0.3",
                     "[trainer] warning: something soft failed"]
    # warning+ records mirror into the ACTIVE session's event log,
    # resolved per call — the logger predates the session
    spec = obs.ObsSpec(events=str(tmp_path / "e.jsonl"))
    with obs.session(spec):
        log("warning: now mirrored")
        log("plain info, not mirrored")
    recs = [json.loads(l) for l in
            (tmp_path / "e.jsonl").read_text().splitlines()]
    logged = [r for r in recs if r["kind"] == "log"]
    assert len(logged) == 1
    assert logged[0]["component"] == "trainer"
    assert logged[0]["level"] == "warning"
    assert "now mirrored" in logged[0]["msg"]


# -- ServeStats satellites ---------------------------------------------------

def test_gauge_typo_raises_attribute_error():
    st = ServeStats()
    st.gauge("queue_depth", 5)
    assert st.queue_depth == 5
    with pytest.raises(AttributeError):
        st.gauge("queue_dpeth", 5)           # the regression: silent
    assert not hasattr(st, "queue_dpeth")    # attribute creation


def test_qps_recent_and_uptime():
    st = ServeStats(qps_window_s=10.0)
    assert st.qps_recent() == 0.0            # idle from birth
    for _ in range(5):
        st.observe_latency(0.01)
    assert st.qps_recent() > 0.0
    assert st.uptime_s() >= 0.0
    snap = st.snapshot()
    assert snap["completed"] == 5
    assert snap["qps_recent"] > 0.0
    assert snap["uptime_s"] >= 0.0
    # lifetime qps also positive here; the two only diverge when
    # traffic stops (qps decays, qps_recent zeroes out of the window)
    assert snap["qps"] > 0.0
