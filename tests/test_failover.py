"""Mid-stream failover (serve/session.py + the Router's durable
stream path): a decode stream survives the death of the engine
serving it by re-admitting (prompt ‖ emitted prefix) on a
same-fingerprint sibling and splicing the legs by absolute sequence
number.

Correctness anchors:
  * exactly-once: across a mid-stream kill every index reaches the
    client once — no duplicates, no gaps — and the spliced terminal
    carries the FULL journaled token list;
  * honesty under impossibility: no same-fingerprint sibling ->
    `finish="failover_stale"` with the journaled prefix (never a
    cross-checkpoint splice); resume off / faulted / legacy handle ->
    the pre-failover terminal error (never a hang, never a replay
    from index 0);
  * the idle watchdog converts a SILENT stall into the same failover
    a transport break gets, and a drain-timeout kick fails a live —
    even already-resumed — stream over instead of truncating it;
  * the scheduler treats an inadmissible `resume_from` (past
    max_new, past EOS, past the provided prefix, negative) as a fast
    400: counted `rejected`, zero engine steps;
  * `qos.transport_budget` clamps the per-hop socket slack to the
    remaining end-to-end deadline (the flat `+30s` leak).

Cost control: the failover choreography runs on scriptable stub
handles (the test_autoscale.py mold — no compiled programs); one
module-scoped compiled engine backs the scheduler-level resume
admission tests, and one parametrised test loses a real engine of a
two-engine fleet mid-stream (killed, or silently stalled)."""

import threading
import time

import jax
import numpy as np
import pytest

from singa_tpu.core.net import build_net
from singa_tpu.models.transformer import transformer_lm
from singa_tpu.serve import (InferenceEngine, InferenceServer,
                             Router, RouterSpec, ServeSpec, qos)
from singa_tpu.utils.faults import FaultSchedule, inject

pytestmark = pytest.mark.failover


# -- satellite: transport budget clamps to the deadline ----------------------

def test_transport_budget_clamps_slack_to_deadline():
    """A 2s client deadline must bound the socket budget: the old
    flat `+ 30.0` held the connection (and the engine slot behind it)
    half a minute after the client gave up."""
    now = time.monotonic()
    b = qos.transport_budget(now + 2.0, None, 30.0)
    assert b < 4.2, f"slack leaked past the deadline: {b}"
    assert b > 2.0                       # still covers the remaining
    # nearly-dead request: floor at 0.1s base + 0.1s slack, never <= 0
    b = qos.transport_budget(now - 5.0, None, 30.0)
    assert 0.15 <= b <= 0.25
    # a deadline far beyond the slack keeps the full 30s slack
    b = qos.transport_budget(now + 300.0, None, 30.0)
    assert 325.0 < b < 335.0
    # no deadline: the old generous behavior stands
    assert qos.transport_budget(None, 5.0, 30.0) == pytest.approx(35.0)
    assert qos.transport_budget(None, None, 7.0) == pytest.approx(37.0)


# -- scriptable stream stubs (no compiled programs) --------------------------

def _tok(step, j):
    """The determinism stand-in: token at absolute index j depends
    only on (fingerprint step, j) — any same-step sibling re-derives
    the identical continuation, exactly like greedy decode."""
    return (int(step) * 7 + j * 3) % 101


class StreamStubHandle:
    """Engine-handle double whose `request_stream` speaks the indexed
    protocol and can be scripted to die, stall silently, or block at
    an absolute token index (each trigger fires once)."""

    def __init__(self, name, step=1):
        self.name = name
        self.step = step
        self.die_at = None       # raise before emitting this index
        self.stall_at = None     # block silently before this index
        self.calls = []          # (resume_from, len(tokens)) per admit

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
                if self.stall_at == j:
                    self.stall_at = None
                    if cancel_event is not None:
                        cancel_event.wait(10.0)
                    return           # ends without a terminal event
                yield {"token": _tok(self.step, j), "i": j}
            yield {"done": True, "finish": "length", "step": self.step,
                   "tokens": [_tok(self.step, j) for j in
                              range(int(resume_from), int(max_new))]}
        return gen()


class LegacyStreamStubHandle(StreamStubHandle):
    """A pre-failover handle: no `resume_from` parameter, no `i`
    field — what every engine looked like before this PR."""

    def request_stream(self, tokens, timeout=None, max_new=None,
                       deadline=None, priority="interactive",
                       cancel_event=None):
        self.calls.append((0, len(tokens)))

        def gen():
            for j in range(int(max_new)):
                if self.die_at == j:
                    self.die_at = None
                    raise RuntimeError(f"{self.name} exploded at {j}")
                yield {"token": _tok(self.step, j)}
            yield {"done": True, "finish": "length", "step": self.step,
                   "tokens": [_tok(self.step, j)
                              for j in range(int(max_new))]}
        return gen()


def _router(handles, **spec_kw):
    spec_kw.setdefault("probe_period_s", 60.0)
    spec_kw.setdefault("quarantine_after", 10)
    spec_kw.setdefault("request_timeout_s", 10.0)
    spec_kw.setdefault("hedge", "off")
    r = Router(handles, spec=RouterSpec(**spec_kw),
               log_fn=lambda s: None)
    r.probe_all()
    return r


def _consume(stream, on_event=None):
    """Drain a stream into (token events, terminal event)."""
    toks, done = [], None
    for ev in stream:
        if ev.get("done"):
            done = ev
            break
        toks.append(ev)
        if on_event is not None:
            on_event(ev)
    return toks, done


# -- the tentpole: exactly-once failover on stubs ----------------------------

def test_stream_failover_exactly_once():
    e0, e1 = StreamStubHandle("e0"), StreamStubHandle("e1")
    e0.die_at = 3                       # dies owing index 3
    r = _router([e0, e1])
    toks, done = _consume(r.route_stream([5, 6], max_new=8))
    # every index exactly once, every token the deterministic one —
    # and each event carries BOTH keys, so a pre-PR client that only
    # reads `token` sees an unchanged stream
    assert [ev["i"] for ev in toks] == list(range(8))
    assert [ev["token"] for ev in toks] == [_tok(1, j) for j in range(8)]
    assert all("token" in ev and "i" in ev for ev in toks)
    # the spliced terminal: full journal, honest provenance
    assert done["tokens"] == [_tok(1, j) for j in range(8)]
    assert done["spliced"] is True and done["resumes"] == 1
    assert done["engine"] == "e1" and done["finish"] == "length"
    # the resume re-admitted (prompt ‖ 3-token prefix) from index 3
    assert e1.calls == [(3, 5)]
    snap = r.sessions.snapshot()
    assert snap["failovers"] == 1 and snap["resumed"] == 1
    assert snap["spliced"] == 1 and snap["done"] == 1
    assert snap["dup_tokens"] == 0 and snap["gap_events"] == 0
    assert r.snapshot()["streams"]["opened"] == 1


def test_failover_stale_fingerprint_is_honest():
    """No same-step sibling left: the stream ends with the journaled
    prefix and `finish="failover_stale"` — never a splice across
    checkpoints, never an exception-shaped lie."""
    e0, e1 = StreamStubHandle("e0", step=1), StreamStubHandle("e1", step=2)
    e0.die_at = 2
    r = _router([e0, e1])
    toks, done = _consume(r.route_stream([5], max_new=8))
    assert [ev["i"] for ev in toks] == [0, 1]
    assert done["finish"] == "failover_stale"
    assert done["tokens"] == [_tok(1, 0), _tok(1, 1)]
    assert done["resumes"] == 1 and "error" in done
    snap = r.sessions.snapshot()
    assert snap["failover_stale"] == 1 and snap["resumed"] == 0
    assert e1.calls == []               # the stale sibling never touched


def test_resume_fault_degrades_to_terminal_error():
    """An injected `serve.resume` fault abandons the resume and the
    client sees the PRE-failover terminal error — degraded, not hung,
    not duplicated."""
    e0, e1 = StreamStubHandle("e0"), StreamStubHandle("e1")
    e0.die_at = 2
    r = _router([e0, e1])
    stream = r.route_stream([5], max_new=8)
    got = []
    with inject(FaultSchedule.parse("serve.resume@0:error")):
        with pytest.raises(RuntimeError, match="e0 exploded at 2"):
            for ev in stream:
                got.append(ev)
    assert [ev["i"] for ev in got] == [0, 1]   # prefix delivered once
    snap = r.sessions.snapshot()
    assert snap["resume_faults"] == 1 and snap["resumed"] == 0
    assert snap["failed"] == 1
    assert e1.calls == []


def test_resume_off_restores_pre_pr_behavior():
    e0, e1 = StreamStubHandle("e0"), StreamStubHandle("e1")
    e0.die_at = 2
    r = _router([e0, e1], resume="off")
    with pytest.raises(RuntimeError, match="e0 exploded at 2"):
        list(r.route_stream([5], max_new=8))
    snap = r.sessions.snapshot()
    assert snap["failovers"] == 1 and snap["resumed"] == 0
    assert e1.calls == []


def test_idle_watchdog_resumes_silent_stall():
    """A stall emits no bytes and no error — only the per-stream idle
    watchdog can tell the client is starving.  It must trigger the
    same exactly-once failover a transport break gets."""
    e0, e1 = StreamStubHandle("e0"), StreamStubHandle("e1")
    e0.stall_at = 2
    r = _router([e0, e1], stream_idle_s=0.2)
    toks, done = _consume(r.route_stream([5], max_new=8))
    assert [ev["i"] for ev in toks] == list(range(8))
    assert [ev["token"] for ev in toks] == [_tok(1, j) for j in range(8)]
    assert done["spliced"] is True
    snap = r.sessions.snapshot()
    assert snap["idle_timeouts"] >= 1 and snap["resumed"] == 1
    assert e1.calls == [(2, 3)]


# -- satellite: drain-timeout kicks a RESUMED stream onwards -----------------

def test_drain_kick_fails_over_a_resumed_stream():
    """Scale-down during an already-failed-over stream: the victim of
    `remove_engine(drain=True)` holds a RESUMED leg; the drain-timeout
    kick must fail it over AGAIN and the client still gets every
    token exactly once."""
    e0 = StreamStubHandle("e0")
    e1 = StreamStubHandle("e1")
    e2 = StreamStubHandle("e2")
    e0.die_at = 2                       # first hop: e0 -> e1
    e1.stall_at = 5                     # e1 blocks so the kick lands
                                        # while its leg is live
    r = _router([e0, e1, e2])
    kicked_at = []

    def on_event(ev):
        if ev["i"] == 3 and not kicked_at:
            kicked_at.append(ev["i"])
            assert not r.remove_engine("e1", drain=True,
                                       timeout_s=0.05)
    toks, done = _consume(r.route_stream([5], max_new=8),
                          on_event=on_event)
    assert [ev["i"] for ev in toks] == list(range(8))
    assert [ev["token"] for ev in toks] == [_tok(1, j) for j in range(8)]
    assert done["spliced"] is True and done["resumes"] == 2
    assert done["tokens"] == [_tok(1, j) for j in range(8)]
    snap = r.sessions.snapshot()
    assert snap["kicked"] == 1 and snap["resumed"] == 2
    assert snap["failovers"] == 2 and snap["done"] == 1
    assert "e1" not in r.names()        # the retire itself completed
    assert e2.calls == [(5, 6)]         # second hop resumed at index 5


# -- satellite: protocol compatibility with pre-PR engines -------------------

def test_legacy_handle_fresh_stream_still_works():
    """A handle that predates the `i` field serves a fresh stream
    unchanged: indices are inferred sequentially, the terminal is not
    marked spliced."""
    r = _router([LegacyStreamStubHandle("e0")])
    toks, done = _consume(r.route_stream([5], max_new=6))
    assert [ev["token"] for ev in toks] == [_tok(1, j) for j in range(6)]
    assert done["tokens"] == [_tok(1, j) for j in range(6)]
    assert "spliced" not in done
    snap = r.sessions.snapshot()
    assert snap["done"] == 1 and snap["failovers"] == 0


def test_legacy_handle_death_degrades_not_replays():
    """A sibling whose `request_stream` would silently DROP
    `resume_from` must not be spliced to — it would replay from index
    0 and duplicate the prefix.  The stream degrades to the original
    terminal error instead."""
    e0 = LegacyStreamStubHandle("e0")
    e1 = LegacyStreamStubHandle("e1")
    e0.die_at = 2
    r = _router([e0, e1])
    with pytest.raises(RuntimeError, match="e0 exploded at 2"):
        list(r.route_stream([5], max_new=8))
    snap = r.sessions.snapshot()
    assert snap["resume_denied"] >= 1 and snap["resumed"] == 0
    assert len(e1.calls) == 0           # never even admitted


# -- scheduler-level resume admission (one compiled engine) ------------------

VOCAB, SEQ, EOS = 64, 16, 63
SHAPES = {"data": {"input": (SEQ,), "target": (SEQ,)}}


@pytest.fixture(scope="module")
def fo_lm():
    cfg = transformer_lm(vocab_size=VOCAB, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=SEQ,
                         batchsize=2)
    net = build_net(cfg, "kTest", SHAPES)
    return net, net.init_params(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def fo_served(fo_lm):
    net, params = fo_lm
    spec = ServeSpec(buckets=((2, SEQ),), max_new_tokens=32,
                     temperature=0.0, request_timeout_s=30.0,
                     cb="on", cb_slots=4, cb_block_len=4, eos_id=EOS)
    engine = InferenceEngine(net, spec, params=params,
                             log_fn=lambda s: None)
    server = InferenceServer(engine, http=False, log_fn=lambda s: None)
    server.start()
    yield engine, server
    server.stop()


def test_inadmissible_resume_is_fast_400(fo_served):
    """Every inadmissible `resume_from` is refused before any queue
    or engine work: counted `rejected`, zero scheduler steps."""
    engine, server = fo_served
    prompt = [3, 1, 4, 1]
    rejected0 = engine.stats.rejected
    steps0 = engine.stats.cb_steps
    with pytest.raises(ValueError, match="past max_new"):
        server.generate_stream(prompt, resume_from=64)
    with pytest.raises(ValueError, match=">= 0"):
        server.generate_stream(prompt, resume_from=-1)
    with pytest.raises(ValueError, match="exceeds"):
        server.generate_stream(prompt, resume_from=10)
    with pytest.raises(ValueError, match="eos"):
        # the provided prefix already contains EOS: the original
        # stream finished, there is nothing to resume
        server.generate_stream(prompt + [EOS], resume_from=1)
    assert engine.stats.rejected == rejected0 + 4
    assert engine.stats.cb_steps == steps0, \
        "an inadmissible resume reached the engine"


def test_resume_readmission_bit_identical(fo_served):
    """The determinism contract the whole failover rests on, on a
    REAL compiled scheduler: re-admitting (prompt ‖ prefix) with
    `resume_from=k` re-derives exactly the suffix the uninterrupted
    stream produced, numbered from absolute index k."""
    engine, server = fo_served
    prompt = [3, 1, 4, 1]
    ref = server.generate_stream(prompt).wait(60.0)["tokens"]
    assert len(ref) >= 2
    # resume before any EOS in the reference (an EOS-bearing prefix
    # is inadmissible by design)
    limit = ref.index(EOS) if EOS in ref else len(ref)
    k = max(1, min(limit - 1, (SEQ - len(prompt)) // 2, 4))
    resumed0 = engine.stats.resumed
    ticket = server.generate_stream(prompt + ref[:k], resume_from=k)
    assert ticket.first_index == k
    events = []
    for kind, payload in ticket.events():
        if kind == "tok":
            events.append(payload)
    out = ticket.wait(60.0)
    assert out["tokens"] == ref[k:], \
        f"resume at {k} diverged: {out['tokens']} vs {ref[k:]}"
    assert events == ref[k:]
    assert engine.stats.resumed == resumed0 + 1


# -- a real engine lost mid-stream (two compiled engines, one fleet) ---------

@pytest.mark.parametrize("loss", ["kill", "silent-stall"])
def test_real_engine_lost_mid_stream_splices_exactly_once(fo_lm, loss):
    """The choreography above over REAL compiled engines: the engine
    decoding a live stream is killed (a transport break) or goes
    silent (caught by the `stream_idle_s` watchdog) after the client
    holds 6 tokens; the sibling re-admits (prompt || prefix) and the
    client sees every index once, bit-identical to an uninterrupted
    decode of the same prompt."""
    from singa_tpu.serve import EngineFleet

    net, params = fo_lm
    max_new = 24
    spec = ServeSpec(buckets=((2, SEQ),), max_new_tokens=max_new,
                     temperature=0.0, batch_window_s=0.002,
                     request_timeout_s=60.0,
                     cb="on", cb_slots=2, cb_block_len=4)
    rspec = RouterSpec(
        probe_period_s=0.1, quarantine_after=5, hedge="off",
        request_timeout_s=60.0,
        stream_idle_s=0.3 if loss == "silent-stall" else 0.0)
    fleet = EngineFleet.local(net, spec, 2, params=params,
                              router_spec=rspec, log_fn=lambda s: None)
    fleet.start()
    try:
        prompt = [3, 1, 4, 1]
        ref = [ev for ev in fleet.generate_stream(prompt,
                                                  max_new=max_new)
               if ev.get("done")][0]["tokens"]
        assert len(ref) == max_new
        # a step slow enough that the stream is still decoding when
        # its engine is lost
        for n in fleet.router.names():
            fleet.router.handle_for(n).engine.set_stall(0.01)
        seen, done, victim = [], None, None
        for ev in fleet.generate_stream(prompt, max_new=max_new,
                                        timeout=60.0):
            if ev.get("done"):
                done = ev
                break
            seen.append((ev["i"], ev["token"]))
            if len(seen) == 6:
                victim = fleet.router.sessions.snapshot()[
                    "sessions"][0]["engine"]
                h = fleet.router.handle_for(victim)
                if loss == "kill":
                    h.kill()
                else:
                    h.engine.set_stall(1.5)  # alive, probing ok, mute
        assert done is not None and "error" not in done, done
        assert [i for i, _ in seen] == list(range(max_new))
        assert [t for _, t in seen] == ref
        assert done["tokens"] == ref
        assert done["spliced"] is True and done["resumes"] >= 1
        snap = fleet.router.sessions.stats.snapshot()
        assert snap["resumed"] >= 1 and snap["failed"] == 0
        if loss == "silent-stall":
            assert snap["idle_timeouts"] >= 1
    finally:
        fleet.stop()
