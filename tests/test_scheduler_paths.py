"""The serving step accounts for itself on every path (ISSUE 37): the
step ahead, the drain, the emit loop and the admission's engine calls
lie under the program's own spans, `/metrics` counts how a step went
out, and the loop thread's stall account says in which lap of a step a
long pause fell.  Held here on the tiny 2-layer LM: the span tree of
each path, tokens unchanged by a session, the counters, the stall
account at the `engine.stall` fault site and at a wait made long, the
step's period in the cost account, and the catalogue of
docs/OBSERVABILITY.md against the names the code emits."""

import json
import os
import re
import time

import jax
import numpy as np
import pytest

from singa_tpu import obs
from singa_tpu.core.net import build_net
from singa_tpu.models.transformer import transformer_lm
from singa_tpu.obs import perf
from singa_tpu.obs.metrics import MetricsRegistry
from singa_tpu.serve import InferenceEngine, ServeSpec
from singa_tpu.serve import scheduler as S
from singa_tpu.serve.scheduler import ContinuousScheduler
from singa_tpu.utils.faults import FaultSchedule, inject

pytestmark = pytest.mark.obs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, SEQ, NEW = 64, 16, 8
SHAPES = {"data": {"input": (SEQ,), "target": (SEQ,)}}


@pytest.fixture(autouse=True)
def _no_leaked_session():
    obs.disable()
    yield
    obs.disable()


@pytest.fixture(scope="module")
def lm():
    cfg = transformer_lm(vocab_size=VOCAB, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=SEQ, batchsize=2)
    net = build_net(cfg, "kTest", SHAPES)
    return net, net.init_params(jax.random.PRNGKey(0))


def _engine(lm, slots, **spec):
    net, params = lm
    spec = ServeSpec(buckets=((1, SEQ),), max_new_tokens=NEW,
                     temperature=0.0, eos_id=None, request_timeout_s=120.0,
                     cb="on", cb_slots=slots, cb_block_len=4, **spec)
    engine = InferenceEngine(net, spec, params=params,
                             log_fn=lambda s: None)
    engine.warmup()
    return engine


def _prompts(seed, plens):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, p).astype(np.int32) for p in plens]


def _serve(engine, prompts, news, drained=False):
    """Every request queued before the loop starts: as many as slots
    make a full house from the first step.  `drained`: the loop is
    stopped only once it has read the step it still has in flight (a
    full house's last step, one too many, goes out before the requests
    resolve; whether the loop's next turn reads it or `stop()` gets
    there first is a race the machine's load decides)."""
    sched = ContinuousScheduler(engine, log_fn=lambda s: None)
    try:
        tickets = [sched.submit(p, max_new=n) for p, n in zip(prompts, news)]
        sched.start()
        tokens = [t.wait(timeout=300)["tokens"] for t in tickets]
        give_up = time.monotonic() + 60.0
        while drained and sched._flying is not None \
                and time.monotonic() < give_up:
            time.sleep(0.001)
        return tokens
    finally:
        sched.stop()


def _traced(engine, prompts, news):
    """Serve under a session; (tokens, the loop thread's spans, each
    with its children in the order they began)."""
    with obs.session(obs.ObsSpec()) as o:
        tokens = _serve(engine, prompts, news)
        events = o.tracer.events()
    kids = {}
    for e in sorted(events, key=lambda e: e["ts"]):
        kids.setdefault(e["args"].get("parent_id"), []).append(e)
    for e in events:
        e["kids"] = kids.get(e["args"]["span_id"], [])
    return tokens, events


def _named(events, name, **args):
    return [e for e in events if e["name"] == name
            and all(e["args"].get(k) == v for k, v in args.items())]


def _kids(e):
    return [k["name"] for k in e["kids"]]


# -- spans where the work happens ---------------------------------------------

def test_a_step_ahead_holds_its_hand_over_then_the_read_of_the_one_before(lm):
    engine = _engine(lm, 2)
    _, events = _traced(engine, _prompts(1, (4, 6)), [NEW, NEW])
    ahead = _named(events, "scheduler.decode", ahead=1)
    # NEW steps went out (the last one too many): all but the first ahead
    assert len(ahead) == NEW - 1
    for decode in ahead:
        assert decode["args"]["active"] == 2
        assert _kids(decode) == ["engine.cb_decode", "scheduler.collect"]
        hand, collect = decode["kids"]
        assert _kids(hand) == ["engine.upload", "engine.dispatch"]
        assert collect["args"]["why"] == S.COLLECT_BEHIND
        assert _kids(collect) == ["engine.fetch", "scheduler.emit"]
        assert collect["kids"][1]["args"]["slots"] == 2
    # the house's first step finds nothing in flight: handed over, not
    # ahead of anything, and read by the step after it
    first, = [d for d in _named(events, "scheduler.decode", ahead=0)
              if d["args"]["active"] == 2]
    assert _kids(first) == ["engine.cb_decode"]
    assert engine.stats.cb_steps_ahead == NEW - 1


def test_a_retirement_drains_the_step_in_flight(lm):
    """Two slots, two requests, one shorter: when it retires nothing
    takes its slot, so the step in flight is read with none behind it
    (why 1), and the house goes on by round trips."""
    engine = _engine(lm, 2)
    _, events = _traced(engine, _prompts(2, (4, 6)), [4, NEW])
    drains = _named(events, "scheduler.collect", why=S.COLLECT_DRAIN)
    assert len(drains) == 1
    drain, = drains
    assert _kids(drain) == ["engine.fetch", "scheduler.emit"]
    step, = [e for e in _named(events, "scheduler.step") if drain in e["kids"]]
    assert _kids(step) == ["scheduler.collect", "scheduler.decode"]
    after = step["kids"][1]
    assert after["args"]["ahead"] == 0 and after["args"]["active"] == 1
    assert _kids(after) == ["engine.cb_decode", "scheduler.emit"]
    assert _kids(after["kids"][0]) == ["engine.upload", "engine.dispatch",
                                       "engine.fetch"]
    assert engine.stats.cb_collects_drained == 1
    assert not _named(events, "scheduler.collect", why=S.COLLECT_ADMIT)


def test_an_admission_behind_a_step_in_flight(lm):
    """Three requests, two slots: the third's prefill is handed over
    behind the step in flight, that step is read while it runs, then
    its first token."""
    engine = _engine(lm, 2)
    _, events = _traced(engine, _prompts(3, (4, 6, 5)), [4, NEW, 3])
    behind = [p for p in _named(events, "scheduler.prefill")
              if "scheduler.collect" in _kids(p)]
    assert len(behind) == 1
    prefill, = behind
    assert _kids(prefill) == ["engine.cb_prefill", "scheduler.collect",
                              "engine.cb_prefill_fetch"]
    assert prefill["kids"][0]["args"]["width"] == SEQ
    collect = prefill["kids"][1]
    assert collect["args"]["why"] == S.COLLECT_ADMIT
    assert _kids(collect) == ["engine.fetch", "scheduler.emit"]
    # the first two were admitted into an empty house: no step to read
    alone = [p for p in _named(events, "scheduler.prefill")
             if p is not prefill]
    assert len(alone) == 2 and all(
        _kids(p) == ["engine.cb_prefill", "engine.cb_prefill_fetch"]
        for p in alone)
    assert engine.stats.cb_collects_drained == \
        len(_named(events, "scheduler.collect")) - \
        len(_named(events, "scheduler.collect", why=S.COLLECT_BEHIND))


def test_a_house_that_is_not_full_goes_by_round_trips(lm):
    engine = _engine(lm, 4)
    _, events = _traced(engine, _prompts(4, (4, 6, 2)), [NEW, 5, 6])
    decodes = _named(events, "scheduler.decode")
    assert len(decodes) == NEW - 1
    assert all(d["args"]["ahead"] == 0 for d in decodes)
    assert all(_kids(d) == ["engine.cb_decode", "scheduler.emit"]
               for d in decodes)
    assert [d["kids"][1]["args"]["slots"] for d in decodes] == \
        [d["args"]["active"] for d in decodes]
    assert not _named(events, "scheduler.collect")
    assert engine.stats.cb_steps_ahead == 0
    assert engine.stats.cb_collects_drained == 0


@pytest.mark.parametrize("slots", [2, 4], ids=["full", "not_full"])
def test_tokens_are_the_same_with_a_session_on_and_off(lm, slots):
    prompts, news = _prompts(5, (4, 6, 5)), [4, NEW, 3]
    off = _serve(_engine(lm, slots), prompts, news)
    on, _ = _traced(_engine(lm, slots), prompts, news)
    assert on == off


def test_the_idle_loop_is_the_profilers_alone(lm, tmp_path):
    """`scheduler.wait` lies in a profiler's trace and never in a
    session's tracer (an idle server would fill it)."""
    import glob
    engine = _engine(lm, 2)
    with obs.session(obs.ObsSpec()) as o:
        sched = ContinuousScheduler(engine, log_fn=lambda s: None).start()
        jax.profiler.start_trace(str(tmp_path))
        try:
            time.sleep(0.2)
            sched.submit(_prompts(6, (4,))[0], max_new=2).wait(timeout=60)
        finally:
            jax.profiler.stop_trace()
            sched.stop()
        recorded = {e["name"] for e in o.tracer.events()}
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    seen = {ev.name for plane in jax.profiler.ProfileData.from_file(
        path).planes for line in plane.lines for ev in line.events}
    assert "scheduler.wait" in seen and "scheduler.wait" not in recorded
    # (the step's own span may still be open when the last token is out)
    assert "scheduler.prefill" in seen and "scheduler.prefill" in recorded


# -- counters, always on -------------------------------------------------------

def test_step_paths_are_counted_on_metrics(lm):
    engine = _engine(lm, 2)
    reg = MetricsRegistry()
    engine.stats.register_into(reg)
    _serve(engine, _prompts(3, (4, 6, 5)), [4, NEW, 3])
    snap = engine.stats.snapshot()
    assert snap["cb_steps_ahead"] > 0 and snap["cb_collects_drained"] > 0
    assert snap["cb_steps_ahead"] + snap["cb_collects_drained"] <= \
        snap["cb_steps"]
    text = reg.render_prometheus()
    for name in ("cb_steps_ahead", "cb_collects_drained", "cb_stalls",
                 "cb_stall_seconds", "cb_stall_wait_seconds"):
        line, = re.findall(rf"^singa_serve_{name}_total (\S+)$", text, re.M)
        assert float(line) == float(snap[name])


def _stall_events(path):
    with open(path) as f:
        return [e for e in map(json.loads, f) if e["kind"] == "serve.cb_stall"]


@pytest.mark.parametrize("stall_s, stalls", [(0.6, 1), (0.1, 0)])
def test_a_stalled_hand_over_is_counted_and_told(lm, tmp_path, stall_s,
                                                 stalls):
    """The `engine.stall` fault site fires at the request's one decode
    step: the host sleeps before the compiled call, in the hand-over."""
    engine = _engine(lm, 2, stall_fault_s=stall_s)
    log = str(tmp_path / "events.jsonl")
    with obs.session(obs.ObsSpec(events=log)):
        # visit 0 is the prefill's hand-over, visit 1 the decode step's
        with inject(FaultSchedule.parse("engine.stall@1:stall")):
            _serve(engine, _prompts(7, (5,)), [2])
    st = engine.stats
    assert st.cb_stalls == stalls
    assert st.cb_stall_wait_seconds == 0
    told = _stall_events(log)
    assert len(told) == stalls
    if not stalls:
        assert st.cb_stall_seconds == 0
        return
    assert stall_s <= st.cb_stall_seconds < stall_s + 0.4
    event, = told
    assert event["lap"] == "handover"
    assert event["seconds"] == pytest.approx(st.cb_stall_seconds, abs=1e-3)
    assert event["laps"]["handover"] == event["seconds"]
    # the step admitted the request too: its prefill's two laps
    assert set(event["laps"]) == {"rest", "prefill", "first_token",
                                  "handover", "wait", "emit"}
    assert all(v < 0.4 for k, v in event["laps"].items() if k != "handover")
    assert event["active"] == 0 and event["pending"] == 0   # it retired


def test_a_long_wait_on_the_device_is_counted_as_a_wait(lm, tmp_path,
                                                        monkeypatch):
    """Full house, one fetch made long: the seconds count as a stall
    AND as waited on the device or the runtime."""
    engine = _engine(lm, 2)
    real_tokens, calls = engine._cb_tokens, []

    def slow_tokens(nxt):
        calls.append(1)
        if len(calls) == 3:
            time.sleep(0.6)
        return real_tokens(nxt)

    monkeypatch.setattr(engine, "_cb_tokens", slow_tokens)
    log = str(tmp_path / "events.jsonl")
    with obs.session(obs.ObsSpec(events=log)) as o:
        _serve(engine, _prompts(8, (4, 6)), [NEW, NEW])
        steps = _named(o.tracer.events(), "scheduler.step")
    st = engine.stats
    assert st.cb_stalls == 1
    assert 0.6 <= st.cb_stall_seconds < 1.0
    assert st.cb_stall_wait_seconds == st.cb_stall_seconds
    event, = _stall_events(log)
    assert event["lap"] == "wait" and event["active"] == 2
    # the steps after it carry the account's running values
    last = max(steps, key=lambda e: e["ts"])["args"]
    assert last["stalls"] == 1
    assert last["stall_ms"] == last["stall_wait_ms"] == \
        int(1e3 * st.cb_stall_seconds)


def test_a_call_that_does_not_stamp_its_wait_is_one_wait_lap(lm):
    """A stand-in for an engine call (a test's, a bench's) leaves
    `cb_wait` as it was: the lap is the whole call's, and no old stamp
    is read as a pause."""
    engine = _engine(lm, 4)
    real = engine.run_cb_decode

    def stand_in(*args):
        out = real(*args)
        engine.cb_wait = (0.0, 0.0)
        return out

    engine.run_cb_decode = stand_in
    _serve(engine, _prompts(9, (4,)), [4])
    assert engine.stats.cb_stalls == 0


# -- the step account the MFU on /metrics is derived from ----------------------

def test_a_full_houses_step_period_reaches_the_cost_account(lm):
    """`run_cb_decode` was the only caller of `perf.observe_step`: in a
    full house the step seconds stood at the last round trip's."""
    engine = _engine(lm, 2)
    seen = []
    real = perf.observe_step

    def spy(program, seconds):
        seen.append((program, seconds))
        real(program, seconds)

    perf.observe_step = spy
    try:
        t0 = time.perf_counter()
        _serve(engine, _prompts(1, (4, 6)), [NEW, NEW], drained=True)
        wall = time.perf_counter() - t0
    finally:
        perf.observe_step = real
    steps = [s for p, s in seen if p == "cb_decode"]
    # every step of the full house is observed, each once, as a period:
    # together they cannot exceed the run
    assert len(steps) == NEW == engine.stats.cb_decode_steps
    assert all(s > 0 for s in steps) and sum(steps) < wall
    assert perf.snapshot()["cost"]["cb_decode"]["step_seconds"] == steps[-1]


# -- the catalogue -------------------------------------------------------------

PER_TOKEN = {"scheduler.step", "scheduler.admit_pending", "scheduler.queue",
             "scheduler.prefill", "scheduler.decode", "scheduler.collect",
             "scheduler.emit", "scheduler.wait", "engine.cb_prefill",
             "engine.cb_prefill_fetch", "engine.cb_decode", "engine.upload",
             "engine.dispatch", "engine.fetch"}


def test_the_catalogue_names_every_span_of_the_per_token_path(lm):
    """docs/OBSERVABILITY.md's table of the per-token path against the
    names in the code and the names a served request emits."""
    with open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")) as f:
        doc = f.read()
    table = doc[doc.index("Continuous batching (`cb=on`"):
                doc.index("Closed-loop pipeline")]
    rows = [ln.split("|")[1] for ln in table.splitlines()
            if ln.startswith("| `")]
    catalogued = {n for row in rows for n in re.findall(r"`([a-z_.]+)`", row)}
    catalogued = {n if "." in n else "engine." + n for n in catalogued}
    assert catalogued == PER_TOKEN | {"scheduler.admit"}
    # the names the two modules can emit on the loop thread
    src = ""
    for mod in ("scheduler.py", "engine.py"):
        with open(os.path.join(ROOT, "singa_tpu", "serve", mod)) as f:
            src += f.read()
    in_code = set(re.findall(
        r'(?:obs\.span|obs\.device_span|add_span)\(\s*"([a-z_.]+)"', src))
    assert in_code - {"scheduler.admit", "engine.run_batch",
                      "engine.compile", "engine.reload"} == PER_TOKEN
    # and what three requests through two slots emit under a session
    _, events = _traced(_engine(lm, 2), _prompts(3, (4, 6, 5)), [4, NEW, 3])
    emitted = {e["name"] for e in events
               if e["name"].startswith(("scheduler.", "engine."))}
    assert emitted == (PER_TOKEN | {"scheduler.admit"}) - {"scheduler.wait"}
    # the catalogue's "Metric names" and "Event-log schema" know the account
    for name in ("cb_steps_ahead", "cb_collects_drained", "cb_stalls",
                 "cb_stall_seconds", "cb_stall_wait_seconds",
                 "serve.cb_stall"):
        assert f"`{name}`" in doc, name
