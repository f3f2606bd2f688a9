"""`obs.span` on the profiler's clock (ISSUE 24): while a profiler
runs every span is a `jax.profiler.TraceAnnotation`, so a
`jax.profiler` trace holds the program's span names and scalar
attributes with a session on AND off; the per-token path (scheduler
step, engine call) is spanned from inside; the two cb programs carry
names that reach a trace; `/metrics` has TTFT, queue wait at admission
and the admission counters.

Cost control: one module-scoped cb engine over the tiny 2-layer LM;
the profiler traces are CPU traces of a few spans each."""

import glob
import os
import threading
import time

import jax
import numpy as np
import pytest

from singa_tpu import obs
from singa_tpu.core.net import build_net
from singa_tpu.models.transformer import transformer_lm
from singa_tpu.obs.metrics import MetricsRegistry
from singa_tpu.obs.trace import NULL_HANDLE, SpanHandle
from singa_tpu.serve import InferenceEngine, InferenceServer, ServeSpec
from singa_tpu.serve.scheduler import StreamTicket
from singa_tpu.serve.stats import ServeStats
from singa_tpu.utils.faults import FaultSchedule, inject

pytestmark = pytest.mark.obs

VOCAB, SEQ = 64, 16
SHAPES = {"data": {"input": (SEQ,), "target": (SEQ,)}}


@pytest.fixture(autouse=True)
def _no_leaked_session():
    obs.disable()
    yield
    obs.disable()


# -- the bridge ---------------------------------------------------------------

def _profiled(tmp_path, body):
    """Run `body` under a `jax.profiler` trace; return the host plane's
    events as {name: [stats dict, ...]}."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(dict(ev.stats))
    return out


@pytest.mark.parametrize("session", [False, True], ids=["off", "on"])
def test_profiler_trace_holds_spans_and_scalar_attrs(tmp_path, session):
    seen = {}

    def body():
        with obs.span("devclock.outer", corr="req-7", plen=12,
                      queue_ms=3.5, tenant="a,b#c", blob=[1, 2]) as sp:
            seen["outer"] = sp
            with obs.span("devclock.inner"):
                pass

    if session:
        with obs.session(obs.ObsSpec()) as o:
            events = _profiled(tmp_path, body)
            recorded = {e["name"]: e for e in o.tracer.events()}
        # the tracer recorded as before: ids, parent, corr, attrs
        assert isinstance(seen["outer"], SpanHandle)
        assert recorded["devclock.inner"]["args"]["parent_id"] == \
            recorded["devclock.outer"]["args"]["span_id"]
        assert recorded["devclock.outer"]["args"]["corr"] == "req-7"
        assert recorded["devclock.outer"]["args"]["plen"] == 12
    else:
        events = _profiled(tmp_path, body)
        assert seen["outer"] is NULL_HANDLE
    assert len(events["devclock.inner"]) == 1
    (stats,) = events["devclock.outer"]
    assert stats["plen"] == 12 and stats["queue_ms"] == 3.5
    assert stats["corr"] == "req-7"
    # the profiler's own delimiters are kept out of a string value,
    # and what is no scalar stays out of the annotation
    assert stats["tenant"] == "a;b_c" and "blob" not in stats


def test_off_path_is_a_flag_read_and_the_null_span(tmp_path):
    from jax.profiler import TraceAnnotation
    from singa_tpu.obs.trace import NULL_SPAN
    assert obs.active() is None and not obs.tracing()
    # no session, no profiler: nothing is built
    assert obs.span("devclock.off", corr="x", plen=3) is NULL_SPAN
    with obs.span("devclock.off") as sp:
        assert sp is NULL_HANDLE and sp.trace == "" and sp.corr is None
        sp.set(k=1)                          # no-op, no error
    assert obs.current_corr() is None and obs.trace_context() is None
    # a running profiler is the only switch there is
    seen = {}

    def body():
        seen["tracing"] = obs.tracing()
        ctx = obs.span("devclock.profiled", plen=3)
        seen["ctx"] = ctx
        with ctx as sp:
            seen["handle"] = sp

    events = _profiled(tmp_path, body)
    assert seen["tracing"] and isinstance(seen["ctx"], TraceAnnotation)
    assert seen["handle"] is NULL_HANDLE
    assert events["devclock.profiled"] == [{"plen": 3}]
    assert not obs.tracing()
    with obs.session(obs.ObsSpec()):
        assert obs.tracing()                 # a session records too


@pytest.mark.parametrize("session", [False, True], ids=["off", "on"])
def test_exceptions_propagate_and_the_span_closes(tmp_path, session):
    def body():
        with pytest.raises(KeyError):
            with obs.span("devclock.raises", step=1):
                raise KeyError("boom")
        with obs.span("devclock.after"):     # the stack is intact
            pass

    if session:
        with obs.session(obs.ObsSpec()) as o:
            events = _profiled(tmp_path, body)
            recorded = {e["name"]: e for e in o.tracer.events()}
        assert recorded["devclock.raises"]["args"]["error"] == "KeyError"
        assert "parent_id" not in recorded["devclock.after"]["args"]
    else:
        events = _profiled(tmp_path, body)
    assert len(events["devclock.raises"]) == 1
    assert len(events["devclock.after"]) == 1


def test_emit_fault_drops_the_record_not_the_work(tmp_path):
    done = []

    def body():
        with inject(FaultSchedule.parse("obs.emit@0")):
            with obs.span("devclock.faulted"):
                done.append(1)

    with obs.session(obs.ObsSpec()) as o:
        events = _profiled(tmp_path, body)
        assert o.tracer.dropped == 1 and not o.tracer.events()
    assert done == [1]
    assert len(events["devclock.faulted"]) == 1   # the profiler has it


def test_add_span_stays_tracer_only(tmp_path):
    with obs.session(obs.ObsSpec()) as o:
        events = _profiled(tmp_path, lambda: o.tracer.add_span(
            "devclock.posthoc", 0.0, 0.001))
        assert [e["name"] for e in o.tracer.events()] == ["devclock.posthoc"]
    assert "devclock.posthoc" not in events


# -- the per-token path -------------------------------------------------------

@pytest.fixture(scope="module")
def cb_served():
    cfg = transformer_lm(vocab_size=VOCAB, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=SEQ,
                         batchsize=2)
    net = build_net(cfg, "kTest", SHAPES)
    params = net.init_params(jax.random.PRNGKey(0))
    spec = ServeSpec(buckets=((2, SEQ),), max_new_tokens=6,
                     temperature=0.0, request_timeout_s=30.0,
                     cb="on", cb_slots=2, cb_block_len=4)
    engine = InferenceEngine(net, spec, params=params,
                             log_fn=lambda s: None)
    server = InferenceServer(engine, http=False, log_fn=lambda s: None)
    server.start()
    yield engine, server
    server.stop()


PROMPT = np.array([3, 1, 4, 1, 5], np.int32)


def _children(events):
    by_parent = {}
    for e in events:
        by_parent.setdefault(e["args"].get("parent_id"), []).append(e)
    return by_parent


def test_one_step_is_spanned_from_inside(cb_served):
    engine, server = cb_served
    with obs.session(obs.ObsSpec()) as o:
        with obs.span("client.request", corr="req-1") as root:
            out = server.generate(PROMPT)
        # the last token is out before its step's span closes
        deadline = time.monotonic() + 10
        while (sum(e["name"] == "scheduler.step"
                   for e in o.tracer.events()) < 5
               and time.monotonic() < deadline):
            time.sleep(0.01)
        events = o.tracer.events()
    assert len(out["tokens"]) == 6
    kids = _children(events)
    names = lambda e: sorted(k["name"] for k in       # noqa: E731
                             kids.get(e["args"]["span_id"], []))
    steps = [e for e in events if e["name"] == "scheduler.step"]
    admitting = [s for s in steps if "scheduler.admit_pending" in names(s)]
    assert len(admitting) == 1 and len(steps) >= 5
    step = admitting[0]
    # the stall account's running values, and nothing no metric reads
    assert {k: step["args"][k] for k in step["args"]
            if k not in ("trace", "span_id", "parent_id", "corr")} == {
        "stalls": 0, "stall_ms": 0, "stall_wait_ms": 0}
    assert names(step) == ["scheduler.admit_pending", "scheduler.decode"]
    one = lambda e, n: [k for k in kids[e["args"]["span_id"]]  # noqa: E731
                        if k["name"] == n][0]
    admit = one(step, "scheduler.admit_pending")
    assert "admitted" not in admit["args"]     # nothing read it: gone
    # the prefill is anchored in the REQUEST's trace (its link), so it
    # is the request's child, and lies inside the admission by time
    prefill = [e for e in events if e["name"] == "scheduler.prefill"][0]
    assert prefill["args"]["trace"] == root.trace
    assert prefill["args"]["corr"] == "req-1"
    assert prefill["args"]["queue_ms"] >= 0
    assert prefill["args"]["plen"] == 5 and prefill["args"]["slot"] == 0
    assert admit["ts"] <= prefill["ts"] and \
        prefill["ts"] + prefill["dur"] <= admit["ts"] + admit["dur"] + 1
    # the hand-over, then the wait for the first token
    assert names(prefill) == ["engine.cb_prefill", "engine.cb_prefill_fetch"]
    assert one(prefill, "engine.cb_prefill")["args"]["width"] == SEQ
    decode = one(step, "scheduler.decode")
    assert decode["args"]["active"] == 1 and decode["args"]["ahead"] == 0
    assert names(decode) == ["engine.cb_decode", "scheduler.emit"]
    assert one(decode, "scheduler.emit")["args"]["slots"] == 1
    assert names(one(decode, "engine.cb_decode")) == [
        "engine.dispatch", "engine.fetch", "engine.upload"]
    # a step that admits nothing opens no admission span
    assert all(names(s) == ["scheduler.decode"]
               for s in steps if s is not step)
    # every span of the per-token path has a reader (PERF.md 3)
    assert {e["name"] for e in events if e["name"].startswith(
        ("scheduler.", "engine."))} == {
        "scheduler.admit", "scheduler.queue", "scheduler.step",
        "scheduler.admit_pending", "scheduler.prefill", "scheduler.decode",
        "scheduler.emit", "engine.cb_prefill", "engine.cb_prefill_fetch",
        "engine.cb_decode", "engine.upload", "engine.dispatch",
        "engine.fetch"}
    # the request's wait, recorded when it ended, in the request's trace
    queue = [e for e in events if e["name"] == "scheduler.queue"]
    assert len(queue) == 1 and queue[0]["args"]["trace"] == root.trace
    assert queue[0]["args"]["plen"] == 5 and queue[0]["dur"] >= 0
    assert abs(queue[0]["dur"] / 1e3 - prefill["args"]["queue_ms"]) < 0.01
    # names the benchmark's reducer keeps for its own wrappers
    assert not {e["name"] for e in events} & {
        "host.fetch", "sched.admit", "engine.prefill", "engine.decode"}


def test_tokens_identical_with_spans_on_off_and_profiled(cb_served,
                                                         tmp_path):
    engine, server = cb_served
    off = server.generate(PROMPT)["tokens"]
    with obs.session(obs.ObsSpec()):
        on = server.generate(PROMPT)["tokens"]
    got = {}
    events = _profiled(tmp_path, lambda: got.update(
        server.generate(PROMPT)))
    assert off == on == got["tokens"]
    # with no session the spans are in the profiler's trace all the same
    assert events["scheduler.prefill"][0]["plen"] == 5
    assert events["scheduler.prefill"][0]["queue_ms"] >= 0
    assert len(events["engine.cb_decode"]) == 5
    assert events["scheduler.step"][0] == {
        "stalls": 0, "stall_ms": 0, "stall_wait_ms": 0}
    assert [e["active"] for e in events["scheduler.decode"]] == [1] * 5
    assert [e["ahead"] for e in events["scheduler.decode"]] == [0] * 5
    assert [e["slots"] for e in events["scheduler.emit"]] == [1] * 5
    assert events["engine.cb_prefill"] == [{"width": SEQ}]
    for name in ("scheduler.admit_pending", "engine.upload",
                 "engine.dispatch", "engine.fetch",
                 "engine.cb_prefill_fetch"):
        assert name in events, name


def test_seams_the_benchmark_wraps_are_kept(cb_served):
    """`benchmark/runners/serve_cb.py` patches `StreamTicket._emit` on
    the class and the engine's two calls on the instance."""
    import inspect
    engine, server = cb_served
    assert list(inspect.signature(StreamTicket._emit).parameters) == [
        "self", "token"]
    assert list(inspect.signature(engine.run_cb_prefill).parameters) == [
        "params", "pools", "tokens", "plen", "row"]
    assert list(inspect.signature(engine.run_cb_decode).parameters) == [
        "params", "pools", "tokens", "ntoks", "tables"]
    calls, stamped = [], []
    dec, emit = engine.run_cb_decode, StreamTicket._emit

    def wrapped(params, pools, tokens, ntoks, tables):
        calls.append(int(np.sum(ntoks)))
        return dec(params, pools, tokens, ntoks, tables)

    def stamping(ticket, token):
        stamped.append(token)
        emit(ticket, token)

    engine.run_cb_decode, StreamTicket._emit = wrapped, stamping
    try:
        out = server.generate(PROMPT)
    finally:
        del engine.run_cb_decode
        StreamTicket._emit = emit
    assert len(calls) == 5 and stamped == out["tokens"]


@pytest.mark.parametrize("which", ["prefill", "decode"])
def test_cb_programs_are_named(cb_served, which):
    """A program's name in a trace is `jit_<function>`."""
    engine, _ = cb_served
    assert f"HloModule jit_cb_{which}" in \
        engine._compile_cb(which).as_text()


def test_step_attributes_cost_nothing_while_nothing_records(cb_served,
                                                            monkeypatch):
    """The stall account's values on `scheduler.step` are read and
    scaled only under a session or a running profiler."""
    engine, server = cb_served
    seen = []
    real = obs.span

    def spy(name, **kw):
        seen.append((name, kw))
        return real(name, **kw)

    monkeypatch.setattr(obs, "span", spy)
    server.generate(PROMPT)
    steps = [kw for name, kw in seen if name == "scheduler.step"]
    assert steps and all(kw == {} for kw in steps)
    seen.clear()
    with obs.session(obs.ObsSpec()):
        server.generate(PROMPT)
    steps = [kw for name, kw in seen if name == "scheduler.step"]
    assert steps and all(set(kw) == {"stalls", "stall_ms", "stall_wait_ms"}
                         for kw in steps)


# -- what an operator lacks at /metrics ---------------------------------------

def test_ttft_and_admission_observed_once_a_request(cb_served):
    engine, server = cb_served
    reg = MetricsRegistry()
    engine.stats.register_into(reg)
    before = server.snapshot()
    threads = [threading.Thread(target=server.generate, args=(PROMPT,))
               for _ in range(3)]             # 3 requests, 2 slots
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = server.snapshot()
    assert snap["cb_prefills"] - before["cb_prefills"] == 3
    assert 1 <= snap["cb_admit_steps"] - before["cb_admit_steps"] <= 3
    assert snap["p50_ttft_ms"] > 0 and \
        snap["p95_ttft_ms"] >= snap["p50_ttft_ms"]
    assert snap["p50_ttft_ms"] >= snap["p50_queue_wait_ms"]
    assert set(before) <= set(snap)            # /stats keys are additive
    text = reg.render_prometheus()
    assert "singa_serve_ttft_seconds_count 3" in text
    assert "singa_serve_queue_wait_seconds_count 3" in text
    for name in ("singa_serve_cb_prefills_total",
                 "singa_serve_cb_admit_steps_total",
                 "singa_serve_p95_ttft_ms"):
        assert name in text, name


def test_a_failed_prefill_is_no_counted_prefill(cb_served):
    engine, server = cb_served
    before = server.snapshot()

    def failing(params, pools, tokens, plen, row):
        raise RuntimeError("boom")

    engine.run_cb_prefill = failing
    try:
        with pytest.raises(Exception, match="prefill failed"):
            server.generate(PROMPT)
    finally:
        del engine.run_cb_prefill
    snap = server.snapshot()
    assert snap["cb_prefills"] == before["cb_prefills"]
    assert snap["cb_admit_steps"] == before["cb_admit_steps"]
    assert len(server.generate(PROMPT)["tokens"]) == 6    # still serving
    assert server.snapshot()["cb_prefills"] == before["cb_prefills"] + 1


def test_queue_wait_is_observed_at_admission_not_completion():
    """A backlog shows while it grows: the wait of a request that has a
    slot but has not finished is already in the histogram."""
    stats, reg = ServeStats(), MetricsRegistry()
    stats.register_into(reg)
    stats.observe_admission(0.25)
    assert "singa_serve_queue_wait_seconds_count 1" in \
        reg.render_prometheus()
    assert stats.snapshot()["p50_queue_wait_ms"] == 250.0
    assert stats.snapshot()["completed"] == 0
    # completion of a cb request adds no second sample; the static
    # batcher's completion still brings its own
    stats.observe_request(None, 0.5, 4)
    assert "singa_serve_queue_wait_seconds_count 1" in \
        reg.render_prometheus()
    stats.observe_request(0.1, 0.5, 4)
    assert "singa_serve_queue_wait_seconds_count 2" in \
        reg.render_prometheus()
    stats.observe_ttft(0.3)
    assert stats.snapshot()["p50_ttft_ms"] == 300.0
