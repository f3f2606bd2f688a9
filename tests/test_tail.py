"""Tail-tolerant serving (singa_tpu/serve/qos.py + router hedging +
priority brownout): end-to-end deadlines, hedged dispatch under a
global retry budget, and priority-aware admission.

Correctness anchors:
  * a deadline is ONE absolute budget — dead-on-arrival requests are
    counted `expired_on_arrival` and never reach an engine, a retry
    never outlives the client's deadline, and an engine-reported
    DeadlineExpired is TERMINAL (no strike, no retry-elsewhere);
  * the hedge fires after the windowed-p95-derived delay, the first
    result wins, the loser is cancelled (`cancelled`, never `failed`),
    and every hedge token comes from the global `RetryBudget` —
    exhaustion degrades to single-shot, never to shed;
  * brownout sheds lowest class first with an honest per-class
    Retry-After that escalates over consecutive sheds and resets after
    a healthy dispatch (the regression this file pins).

Cost control: router paths run on scriptable stubs; the two real-cb
tests share one module-scoped tiny engine."""

import threading
import time

import jax
import numpy as np
import pytest

from singa_tpu.core.net import build_net
from singa_tpu.models.transformer import transformer_lm
from singa_tpu.serve import (Cancelled, DeadlineExpired,
                             InferenceEngine, InferenceServer,
                             Overloaded, Router, RouterSpec, ServeSpec,
                             qos)
from singa_tpu.serve.router import RouterStats
from singa_tpu.serve.stats import ServeStats
from singa_tpu.serve.traffic import Phase, TrafficGen, steady
from singa_tpu.utils.faults import FaultSchedule, inject

pytestmark = pytest.mark.tail

VOCAB, SEQ = 64, 16
SHAPES = {"data": {"input": (SEQ,), "target": (SEQ,)}}


# -- qos primitives ----------------------------------------------------------

def test_check_priority_normalizes_and_rejects():
    assert qos.check_priority(None) == "interactive"
    assert qos.check_priority(" Batch ") == "batch"
    assert qos.check_priority("BEST_EFFORT") == "best_effort"
    with pytest.raises(ValueError, match="unknown priority"):
        qos.check_priority("urgent")


def test_resolve_deadline_precedence():
    now = time.monotonic()
    # explicit deadline wins over any timeout
    assert qos.resolve_deadline(5.0, now + 1.0, 30.0) == now + 1.0
    # timeout-derived otherwise; default when timeout is None
    d = qos.resolve_deadline(2.0, None, 30.0)
    assert 1.5 < qos.remaining_s(d) <= 2.0
    d = qos.resolve_deadline(None, None, 30.0)
    assert 29.0 < qos.remaining_s(d) <= 30.0
    # a non-positive timeout means no deadline at all
    assert qos.resolve_deadline(0.0, None, 30.0) is None
    assert qos.remaining_s(None) is None


def test_deadline_header_roundtrip_reanchors():
    d = time.monotonic() + 1.0
    hdr = qos.deadline_to_header(d)
    assert hdr is not None and 0 < int(hdr) <= 1000
    back = qos.deadline_from_header(hdr)
    assert 0 < qos.remaining_s(back) <= 1.0
    # a DEAD deadline propagates as dead (0ms), never as no-deadline
    assert qos.deadline_to_header(time.monotonic() - 5.0) == "0"
    assert qos.remaining_s(qos.deadline_from_header("0")) <= 0
    assert qos.deadline_to_header(None) is None
    assert qos.deadline_from_header(None) is None
    assert qos.deadline_from_header("") is None


def test_retry_budget_caps_amplification():
    b = qos.RetryBudget(ratio=0.25, burst=2.0)
    assert b.spend() and b.spend()        # burst drains
    assert not b.spend()                  # then denied
    for _ in range(4):                    # 4 primaries earn 1 token
        b.earn()
    assert b.spend() and not b.spend()
    b.refund()                            # never-dispatched spend
    assert b.spend()
    for _ in range(1000):                 # earning caps at burst
        b.earn()
    assert b.tokens() == pytest.approx(2.0)


def test_class_backoffs_escalate_per_class_and_reset():
    cb = qos.ClassBackoffs(base=0.05, cap=2.0, seed=0)
    d_int = cb.shed_delay("interactive")
    d_be1 = cb.shed_delay("best_effort")
    # lower classes are told to stay away longer (factor 4x)
    assert d_be1 > d_int
    d_be2 = cb.shed_delay("best_effort")
    assert d_be2 > d_be1                  # ITS streak escalates...
    assert cb.streak("interactive") == 1  # ...without touching others
    cb.reset("best_effort")
    assert cb.streak("best_effort") == 0
    assert cb.shed_delay("best_effort") <= d_be2  # streak restarted


# -- scriptable router stubs -------------------------------------------------

class TailStub:
    """Engine-handle double with a QoS-aware `request`: scriptable
    latency and failure, records the kwargs each dispatch carried."""

    def __init__(self, name, delay_s=0.0, exc=None):
        self.name = name
        self.delay_s = delay_s
        self.exc = exc
        self.step = 1
        self.queue_depth = 0
        self.served = 0
        self.calls = []

    def probe(self):
        return {"ok": True, "status": "ok", "step": self.step,
                "queue_depth": self.queue_depth}

    def stats_snapshot(self):
        return {"completed": self.served}

    def request(self, mode, tokens, timeout=None, deadline=None,
                priority="interactive", cancel_event=None):
        self.calls.append({"deadline": deadline, "priority": priority,
                           "cancel_event": cancel_event})
        if self.delay_s:
            time.sleep(self.delay_s)
        if self.exc is not None:
            raise self.exc
        self.served += 1
        return {"tokens": [1, 2], "step": self.step}


def _router(stubs, **spec_kw):
    spec_kw.setdefault("request_timeout_s", 5.0)
    spec_kw.setdefault("hedge_max_s", 0.05)
    r = Router(stubs, spec=RouterSpec(**spec_kw),
               log_fn=lambda s: None)
    r.probe_all()
    return r


# -- deadlines through the router --------------------------------------------

def test_router_dead_on_arrival_never_reaches_an_engine():
    stubs = [TailStub("e0"), TailStub("e1")]
    r = _router(stubs)
    with pytest.raises(DeadlineExpired, match="dead on arrival"):
        r.route("generate", [1, 2], deadline=time.monotonic() - 0.1)
    assert r.stats.expired_on_arrival == 1
    assert r.stats.routed == 0            # never counted as traffic
    assert all(not s.calls for s in stubs)


def test_engine_deadline_is_terminal_not_a_strike():
    # satellite: an engine-reported DeadlineExpired must count
    # deadline_terminal — NOT failed, NOT a strike toward quarantine,
    # and never a retry on a sibling (that only blows the budget more)
    stubs = [TailStub("e0", exc=DeadlineExpired("expired in queue")),
             TailStub("e1")]
    r = _router(stubs, hedge="off", quarantine_after=1)
    with pytest.raises(DeadlineExpired):
        r.route("generate", [1, 2])
    assert r.stats.deadline_terminal == 1
    assert r.stats.failed == 0 and r.stats.retried == 0
    m = {m["name"]: m for m in r.members()}["e0"]
    assert m["strikes"] == 0 and not m["quarantined"]
    assert not stubs[1].calls             # no retry elsewhere


def test_retry_never_outlives_the_client_deadline():
    stubs = [TailStub("e0", delay_s=0.08, exc=RuntimeError("boom")),
             TailStub("e1", delay_s=0.08, exc=RuntimeError("boom"))]
    r = _router(stubs, hedge="off", quarantine_after=10)
    with pytest.raises(DeadlineExpired, match="deadline exhausted"):
        r.route("generate", [1, 2],
                deadline=time.monotonic() + 0.04)
    # the first attempt ate the budget; the retry was refused
    assert r.stats.deadline_terminal == 1
    assert len(stubs[0].calls) + len(stubs[1].calls) == 1


def test_deadline_and_priority_propagate_to_the_handle():
    stubs = [TailStub("e0")]
    r = _router(stubs)
    d = time.monotonic() + 3.0
    r.route("generate", [1, 2], deadline=d, priority="batch")
    call = stubs[0].calls[0]
    assert call["deadline"] == d and call["priority"] == "batch"


# -- hedged dispatch ---------------------------------------------------------

def test_hedge_beats_a_straggler_and_cancels_the_loser():
    slow = TailStub("e0", delay_s=0.6)
    fast = TailStub("e1")
    r = _router([slow, fast], hedge_min_s=0.01, hedge_max_s=0.05)
    t0 = time.monotonic()
    out = r.route("generate", [1, 2])
    dt = time.monotonic() - t0
    assert out["engine"] == "e1"          # the hedge won
    assert dt < 0.5                       # without waiting out e0
    assert r.stats.hedges == 1 and r.stats.hedge_wins == 1
    assert r.stats.completed == 1 and r.stats.failed == 0
    # the loser's cancel_event was set so it can stop mid-decode
    deadline = time.monotonic() + 2.0
    while not slow.calls[0]["cancel_event"].is_set():
        assert time.monotonic() < deadline
        time.sleep(0.005)


def test_hedge_budget_exhaustion_degrades_to_single_shot():
    slow = TailStub("e0", delay_s=0.15)
    fast = TailStub("e1")
    r = _router([slow, fast], hedge_min_s=0.01, hedge_max_s=0.03,
                retry_budget_ratio=0.0, retry_budget_burst=0.0)
    out = r.route("generate", [1, 2])
    assert out["engine"] == "e0"          # served, slowly, by the
    assert r.stats.hedges == 0            # primary: never shed because
    assert r.stats.budget_denied >= 1     # the budget ran dry
    assert r.stats.completed == 1 and r.stats.shed == 0


def test_serve_hedge_fault_abandons_only_the_hedge():
    slow = TailStub("e0", delay_s=0.15)
    fast = TailStub("e1")
    r = _router([slow, fast], hedge_min_s=0.01, hedge_max_s=0.03)
    with inject(FaultSchedule.parse("serve.hedge@0:error")):
        out = r.route("generate", [1, 2])
    assert out["engine"] == "e0"          # primary untouched
    assert r.stats.hedges == 0 and not fast.calls
    # the spent token was refunded: no dispatch ever happened
    assert r.retry_budget.tokens() == pytest.approx(
        r.retry_budget.burst)


def test_hedge_delay_tracks_windowed_p95():
    r = _router([TailStub("e0"), TailStub("e1")],
                hedge_min_s=0.05, hedge_max_s=1.0)
    assert r._hedge_delay() == pytest.approx(1.0)   # no history yet
    for _ in range(20):
        r.stats.observe_latency(0.2)
    r._hedge_cache_t = 0.0                # expire the 0.5s cache
    assert r._hedge_delay() == pytest.approx(0.2, abs=0.01)
    for _ in range(400):                  # p95 now in the fast mass
        r.stats.observe_latency(0.001)
    r._hedge_cache_t = 0.0
    assert r._hedge_delay() == pytest.approx(0.05)  # clamped at min


# -- priority brownout -------------------------------------------------------

def _pressurize(r, rate=1.0, tenant="default"):
    """Pin the router's cached capacity-shed pressure reading for one
    tenant (brownout reads the requesting tenant's own rate)."""
    r._pressure_by_tenant[tenant] = rate
    r._pressure_t = time.monotonic() + 60.0   # cache never refreshes


def test_brownout_sheds_lowest_class_first():
    r = _router([TailStub("e0"), TailStub("e1")],
                brownout_shed_rate=0.1)
    _pressurize(r, 0.15)                  # over thr, under 3x thr
    with pytest.raises(Overloaded, match="brownout"):
        r.route("generate", [1, 2], priority="best_effort")
    r.route("generate", [1, 2], priority="batch")       # still admits
    r.route("generate", [1, 2], priority="interactive")
    _pressurize(r, 0.5)                   # over 3x thr: batch too
    with pytest.raises(Overloaded):
        r.route("generate", [1, 2], priority="batch")
    r.route("generate", [1, 2], priority="interactive")  # always
    assert r.stats.shed_best_effort == 1
    assert r.stats.shed_batch == 1 and r.stats.shed_interactive == 0
    assert r.stats.brownout_sheds == 2
    assert r.stats.completed == 3


def test_brownout_sheds_do_not_feed_the_pressure_signal():
    rs = RouterStats(window_s=30.0)
    for _ in range(10):
        rs.count("routed")
    rs.observe_shed("interactive", brownout=False)      # capacity
    rs.observe_shed("best_effort", brownout=True, n=5)  # brownout
    w = rs.windowed(5.0)
    assert w["shed_rate"] == pytest.approx(6 / 10)
    # only the capacity shed engages brownout — its own sheds feeding
    # back would latch it on forever
    assert w["capacity_shed_rate"] == pytest.approx(1 / 10)


def test_shed_retry_after_escalates_then_resets_after_dispatch():
    # the regression this PR pins: consecutive router sheds escalate
    # the honest Retry-After, and ONE healthy dispatch resets it
    r = _router([TailStub("e0")], brownout_shed_rate=0.1)
    _pressurize(r, 1.0)
    delays = []
    for _ in range(3):
        with pytest.raises(Overloaded) as ei:
            r.route("generate", [1, 2], priority="best_effort")
        delays.append(ei.value.retry_after)
    assert delays[0] < delays[1] < delays[2]  # escalating streak
    assert r._shed_backoffs.streak("best_effort") == 3
    _pressurize(r, 0.0)                   # pressure clears
    r.route("generate", [1, 2], priority="best_effort")
    assert r._shed_backoffs.streak("best_effort") == 0
    _pressurize(r, 1.0)
    with pytest.raises(Overloaded) as ei:
        r.route("generate", [1, 2], priority="best_effort")
    assert ei.value.retry_after <= delays[1]  # back near base


# -- stats: p99 + per-class views (satellite) --------------------------------

def test_router_stats_p99_and_class_views():
    rs = RouterStats(window_s=30.0)
    for ms in range(1, 101):
        rs.observe_latency(ms / 1e3,
                           "interactive" if ms <= 90 else "batch")
    w = rs.windowed(30.0)
    assert w["p99_latency_ms"] == pytest.approx(100.0, abs=0.01)
    assert w["p95_by_class"]["interactive"] < \
        w["p95_by_class"]["batch"]
    assert w["completed_by_class"] == {"interactive": 90, "batch": 10,
                                       "best_effort": 0}
    snap = rs.snapshot()
    assert snap["p99_latency_ms"] == pytest.approx(100.0, abs=0.01)
    assert snap["p99_latency_recent_ms"] == pytest.approx(100.0,
                                                          abs=0.01)


def test_serve_stats_p99_nearest_rank():
    ss = ServeStats()
    for ms in range(1, 101):
        ss.observe_latency(ms / 1e3)
    assert ss.snapshot()["p99_latency_ms"] == pytest.approx(100.0,
                                                            abs=0.01)
    assert ss.windowed(30.0)["p99_latency_ms"] == pytest.approx(
        100.0, abs=0.01)


# -- traffic harness priority mixes ------------------------------------------

def test_traffic_priority_mix_reports_per_class():
    seen = []

    def req(tokens, priority="interactive"):
        seen.append(priority)
        if priority == "best_effort":
            raise Overloaded("browned out", retry_after=0.01)

    gen = TrafficGen(req, seed=11, log_fn=lambda s: None)
    rep = gen.run([steady("mix", duration_s=0.4, rate_rps=60.0,
                          priorities=("interactive", "best_effort"),
                          priority_weights=(1.0, 1.0))],
                  drain_timeout_s=5.0)
    by = rep["totals"]["by_class"]
    assert set(seen) == {"interactive", "best_effort"}
    assert by["interactive"]["completed"] >= 1
    assert by["best_effort"]["shed"] >= 1
    assert by["best_effort"]["completed"] == 0
    with pytest.raises(ValueError, match="unknown priority"):
        Phase(name="bad", duration_s=1.0, rate_rps=1.0,
              priorities=("vip",))


def test_traffic_default_phase_keeps_bare_request_fn():
    # back-compat: a plain `lambda tokens:` target must keep working
    gen = TrafficGen(lambda tokens: None, seed=1,
                     log_fn=lambda s: None)
    rep = gen.run([steady("plain", duration_s=0.2, rate_rps=30.0)],
                  drain_timeout_s=5.0)
    assert rep["totals"]["failed"] == 0
    assert rep["totals"]["completed"] == rep["totals"]["offered"]


# -- real continuous-batching engine (shared; expensive) ---------------------

@pytest.fixture(scope="module")
def tail_served():
    cfg = transformer_lm(vocab_size=VOCAB, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=SEQ,
                         batchsize=2)
    net = build_net(cfg, "kTest", SHAPES)
    params = net.init_params(jax.random.PRNGKey(0))
    spec = ServeSpec(buckets=((2, SEQ),), max_new_tokens=16,
                     temperature=0.0, request_timeout_s=30.0,
                     cb="on", cb_slots=2, cb_block_len=4)
    engine = InferenceEngine(net, spec, params=params,
                             log_fn=lambda s: None)
    server = InferenceServer(engine, http=False, log_fn=lambda s: None)
    server.start()
    yield engine, server
    server.stop()


def test_dead_on_arrival_burns_zero_engine_steps(tail_served):
    engine, server = tail_served
    prompt = np.arange(1, 5, dtype=np.int32)
    server.generate(prompt)               # warm: the engine works
    steps_before = engine.stats.cb_steps
    doa_before = engine.stats.expired_on_arrival
    with pytest.raises(DeadlineExpired, match="dead on arrival"):
        server.generate(prompt, deadline=time.monotonic() - 0.5)
    assert engine.stats.expired_on_arrival == doa_before + 1
    assert engine.stats.cb_steps == steps_before  # no prefill, no step
    server.generate(prompt)               # the engine is unharmed


def test_cancelled_request_is_dropped_not_failed(tail_served):
    engine, server = tail_served
    prompt = np.arange(1, 5, dtype=np.int32)
    ev = threading.Event()
    ev.set()                              # cancelled before admission
    ticket = server.scheduler.submit(prompt, timeout=5.0,
                                     cancel_event=ev)
    with pytest.raises(Cancelled):
        for _ in ticket.events():
            pass
    assert engine.stats.cancelled >= 1
    server.generate(prompt)               # slot bookkeeping intact
