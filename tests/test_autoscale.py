"""SLO-driven autoscaler + open-loop traffic harness
(singa_tpu/serve/autoscale.py + traffic.py) and the elastic-membership
paths they lean on (Router.add_engine/remove_engine, canary abort).

Correctness anchors:
  * drain semantics — a draining member stops admitting under the same
    lock that admits, in-flight work finishes before retirement, and a
    deliberately retired engine leaves its strike record behind;
  * removing the CANARY mid-rollout ABORTS the canary (back to
    OBSERVE, checkpoint unjudged, re-canaries on a survivor) — it
    never counts as a rollback and never condemns the fingerprint;
  * the control law scales up on any pressure signal, scales down only
    after a consecutive-quiet-tick streak, and a faulted `scale.decide`
    tick takes NO membership action;
  * the traffic generator is open-loop: arrivals never wait on
    completions.

Cost control: everything but the last test runs on stub handles and
fabricated signals — no compiled programs; the last one rides a real
one-engine fleet through a flash crowd and back."""

import tempfile
import threading
import time

import numpy as np
import pytest

from singa_tpu.serve import (Overloaded, RolloutController, RolloutSpec,
                             Router, RouterSpec)
from singa_tpu.serve.autoscale import AutoScaler, AutoScaleSpec
from singa_tpu.serve.router import RouterStats
from singa_tpu.serve.stats import ServeStats
from singa_tpu.serve.traffic import (Phase, TrafficGen, diurnal,
                                     flash_crowd, ramp, steady)
from singa_tpu.utils.checkpoint import CheckpointManager
from singa_tpu.utils.faults import FaultSchedule, inject

pytestmark = pytest.mark.traffic


class StubHandle:
    """Scriptable engine-handle double (the test_fleet.py mold): no
    threads, no compiled programs."""

    def __init__(self, name, step=1, queue_depth=0):
        self.name = name
        self.step = step
        self.queue_depth = queue_depth
        self.fail_probe = False
        self.occupancy = None
        self.served = 0
        self.reloads = []

    def probe(self):
        if self.fail_probe:
            from singa_tpu.serve import EngineUnavailable
            raise EngineUnavailable(f"{self.name} is down")
        return {"ok": True, "status": "ok", "step": self.step,
                "queue_depth": self.queue_depth}

    def stats_snapshot(self):
        snap = {"completed": self.served, "failed": 0, "expired": 0,
                "p95_latency_ms": None}
        if self.occupancy is not None:
            snap["cb_slot_occupancy"] = self.occupancy
        return snap

    def request(self, mode, tokens, timeout=None):
        self.served += 1
        return {"tokens": [1, 2], "step": self.step}

    def reload(self, step=None):
        self.reloads.append(step)
        if step is not None and step != self.step:
            self.step = step
            return {"outcome": "reloaded", "step": step}
        return {"outcome": "unchanged", "step": self.step}


def _router(n=2, **spec_kw):
    spec_kw.setdefault("quarantine_after", 2)
    spec_kw.setdefault("readmit_base_s", 0.01)
    spec_kw.setdefault("readmit_cap_s", 0.02)
    stubs = [StubHandle(f"e{i}") for i in range(n)]
    r = Router(stubs, spec=RouterSpec(**spec_kw), log_fn=lambda s: None)
    r.probe_all()
    return r, stubs


class StubFleet:
    """Fleet double over a real Router: `grow`/`retire` go through the
    real membership paths, so the AutoScaler under test exercises the
    same add/drain semantics as a local fleet."""

    def __init__(self, n=1):
        self.router, self.stubs = _router(n)
        self.rollout = None
        self.grow_error = None
        self._next = n

    def grow(self):
        if self.grow_error is not None:
            raise RuntimeError(self.grow_error)
        h = StubHandle(f"e{self._next}")
        self._next += 1
        self.stubs.append(h)
        self.router.add_engine(h)
        return h.name

    def retire(self, name, drain=True, timeout_s=30.0):
        return self.router.remove_engine(name, drain=drain,
                                         timeout_s=timeout_s)


def _scaler(n=1, **spec_kw):
    spec_kw.setdefault("cooldown_s", 0.0)
    spec_kw.setdefault("window_s", 5.0)
    spec_kw.setdefault("tick_s", 0.01)
    spec_kw.setdefault("quiet_ticks", 2)
    spec_kw.setdefault("max_engines", 3)
    fleet = StubFleet(n)
    sc = AutoScaler(fleet, spec=AutoScaleSpec(**spec_kw),
                    log_fn=lambda s: None)
    return sc, fleet


def _join_action(sc, timeout=5.0):
    t = sc._action_thread
    if t is not None:
        t.join(timeout)
    deadline = time.monotonic() + timeout
    while sc._busy and time.monotonic() < deadline:
        time.sleep(0.002)
    assert not sc._busy


# -- spec grammar ------------------------------------------------------------

def test_autoscale_spec_parse_grammar():
    s = AutoScaleSpec.parse("slo_p95_ms=150,max_engines=8;"
                            "cooldown_s=1.5,quiet_ticks=5")
    assert s.slo_p95_ms == 150.0 and s.max_engines == 8
    assert s.cooldown_s == 1.5 and s.quiet_ticks == 5
    assert AutoScaleSpec.parse(None) == AutoScaleSpec()
    assert AutoScaleSpec.parse("") == AutoScaleSpec()
    with pytest.raises(ValueError, match="unknown key"):
        AutoScaleSpec.parse("bogus=1")
    with pytest.raises(ValueError):
        AutoScaleSpec.parse("min_engines=0")
    with pytest.raises(ValueError):
        AutoScaleSpec.parse("min_engines=3,max_engines=2")
    with pytest.raises(ValueError):
        AutoScaleSpec.parse("down_margin=1")


# -- windowed stats (satellite: recent-rate views) ---------------------------

def test_router_stats_windowed_rates():
    rs = RouterStats(window_s=5.0)
    for _ in range(8):
        rs.count("routed")
    rs.count("shed", 2)
    for ms in (10, 20, 30, 40):
        rs.observe_latency(ms / 1000.0)
    w = rs.windowed(5.0)
    assert w["routed"] == 8 and w["shed"] == 2 and w["completed"] == 4
    assert w["shed_rate"] == pytest.approx(2 / 8, abs=1e-3)
    assert w["p50_latency_ms"] == pytest.approx(30.0, abs=0.01)
    assert w["p95_latency_ms"] == pytest.approx(40.0, abs=0.01)
    assert w["qps"] > 0
    snap = rs.snapshot()
    assert snap["shed_rate_recent"] == pytest.approx(2 / 8, abs=1e-3)
    assert snap["p95_latency_recent_ms"] == pytest.approx(40.0,
                                                          abs=0.01)


def test_router_stats_window_excludes_old_samples():
    rs = RouterStats(window_s=1.0)
    now = time.monotonic()
    rs._t0 = now - 100.0          # fake uptime so the cap won't bite
    rs._routed_t.append((now - 50.0, "default"))            # ancient
    rs._done_t.append((now - 50.0, 9.9, "interactive", "default"))
    rs.count("routed")
    rs.observe_latency(0.005)
    w = rs.windowed(1.0)
    assert w["routed"] == 1 and w["completed"] == 1
    assert w["p95_latency_ms"] == pytest.approx(5.0, abs=0.01)


def test_serve_stats_windowed_rates():
    ss = ServeStats()
    ss.count("shed")
    ss.observe_latency(0.02)
    ss.observe_latency(0.04)
    w = ss.windowed(10.0)
    assert w["shed"] == 1 and w["completed"] == 2
    assert w["shed_rate"] == pytest.approx(1 / 3, abs=1e-3)
    assert w["p95_latency_ms"] == pytest.approx(40.0, abs=0.01)
    snap = ss.snapshot()
    assert snap["shed_rate_recent"] == pytest.approx(1 / 3, abs=1e-3)
    assert snap["p95_latency_recent_ms"] == pytest.approx(40.0,
                                                          abs=0.01)


# -- elastic membership: add_engine / remove_engine --------------------------

def test_add_engine_joins_and_serves():
    r, stubs = _router(1)
    r.add_engine(StubHandle("e9", queue_depth=0))
    assert sorted(r.names()) == ["e0", "e9"]
    assert r.stats.joins == 1
    stubs[0].queue_depth = 9
    r.probe_all()
    out = r.route("generate", [1, 2])
    assert out["engine"] == "e9"          # new member eats traffic
    with pytest.raises(ValueError, match="duplicate engine name"):
        r.add_engine(StubHandle("e9"))


def test_remove_engine_drains_in_flight_before_retiring():
    r, stubs = _router(2)
    name = r._pick(set())                 # hold one in-flight slot
    assert name is not None
    done = {}

    def retire():
        done["drained"] = r.remove_engine(name, drain=True,
                                          timeout_s=5.0)

    t = threading.Thread(target=retire)
    t.start()
    deadline = time.monotonic() + 2.0
    while time.monotonic() < deadline:   # admissions stop immediately
        m = {m["name"]: m for m in r.members()}
        if name in m and m[name]["draining"]:
            break
        time.sleep(0.002)
    assert r._pick(set()) != name        # draining excluded from _pick
    assert name in r.names()             # but not yet retired
    r._release(name)                     # in-flight work completes
    t.join(5.0)
    assert done["drained"] is True
    assert name not in r.names()
    assert r.stats.retires == 1


def test_remove_engine_drain_timeout_still_retires():
    r, stubs = _router(2)
    name = r._pick(set())                # never released
    drained = r.remove_engine(name, drain=True, timeout_s=0.05)
    assert drained is False              # timed out...
    assert name not in r.names()         # ...but retirement completes


def test_retire_forgets_strikes():
    r, stubs = _router(2, quarantine_after=1)
    stubs[0].fail_probe = True
    r.probe_all()
    assert {m["name"]: m for m in r.members()}["e0"]["quarantined"]
    assert r.remove_engine("e0", drain=True, timeout_s=1.0)
    # deliberate retirement: the strike record leaves with the member
    stubs[0].fail_probe = False
    r.add_engine(stubs[0])
    m = {m["name"]: m for m in r.members()}["e0"]
    assert m["strikes"] == 0 and not m["quarantined"] and m["healthy"]


# -- canary removed mid-rollout: abort, not rollback -------------------------

def _controller(ws, n=3, **ro_kw):
    ro_kw.setdefault("window_s", 0.01)
    r, stubs = _router(n, quarantine_after=1)
    ctrl = RolloutController(r, ws, spec=RolloutSpec(**ro_kw),
                             log_fn=lambda s: None)
    ctrl.pinned_step = 1
    ctrl._fp = ctrl.mgr.fingerprint()
    return ctrl, r, stubs


def test_canary_removed_mid_canary_aborts_without_rollback():
    params = {"w": np.ones((2,), np.float32)}
    with tempfile.TemporaryDirectory() as ws:
        mgr = CheckpointManager(ws, log_fn=lambda s: None)
        mgr.save(1, params, {"t": np.zeros(())},
                 health={"verdict": "ok"})
        ctrl, r, stubs = _controller(ws)
        mgr.save(2, params, {"t": np.zeros(())},
                 health={"verdict": "ok"})
        ctrl.tick()
        assert ctrl.state == "CANARY"
        victim = ctrl.canary
        assert r.remove_engine(victim, drain=True, timeout_s=1.0)
        ctrl.tick()
        # abort: back to OBSERVE, no rollback counted, checkpoint NOT
        # condemned
        assert ctrl.state == "OBSERVE"
        assert ctrl.canary_aborts == 1 and ctrl.rollbacks == 0
        assert ctrl._rejected_fp is None
        assert ctrl.pinned_step == 1
        # the unjudged step re-canaries on a survivor
        ctrl.tick()
        assert ctrl.state == "CANARY" and ctrl.canaries == 2
        assert ctrl.canary != victim and ctrl.canary in r.names()


def test_non_canary_removal_leaves_rollout_untouched():
    params = {"w": np.ones((2,), np.float32)}
    with tempfile.TemporaryDirectory() as ws:
        mgr = CheckpointManager(ws, log_fn=lambda s: None)
        mgr.save(1, params, {"t": np.zeros(())},
                 health={"verdict": "ok"})
        ctrl, r, stubs = _controller(ws)
        mgr.save(2, params, {"t": np.zeros(())},
                 health={"verdict": "ok"})
        ctrl.tick()
        assert ctrl.state == "CANARY"
        bystander = next(n for n in r.names() if n != ctrl.canary)
        assert r.remove_engine(bystander, drain=True, timeout_s=1.0)
        time.sleep(0.02)                  # window_s elapsed
        ctrl.tick()
        assert ctrl.promotions == 1 and ctrl.pinned_step == 2
        assert ctrl.canary_aborts == 0 and ctrl.rollbacks == 0


# -- control law on fabricated signals ---------------------------------------

def _sig(**kw):
    base = {"n": 1, "healthy": 1, "queue_depth": 0, "shed_rate": 0.0,
            "qps": 0.0, "p95_ms": None, "occupancy": None,
            "lag_steps": 0}
    base.update(kw)
    return base


def test_decide_up_on_each_pressure_signal():
    sc, _ = _scaler(1)
    assert sc.decide(_sig(shed_rate=0.5))["dir"] == "up"
    assert sc.decide(_sig(p95_ms=10_000.0))["dir"] == "up"
    assert sc.decide(_sig(queue_depth=99))["dir"] == "up"
    assert sc.decide(_sig(occupancy=0.99))["dir"] == "up"
    # pressure at max_engines holds instead
    assert sc.decide(_sig(n=3, shed_rate=0.5))["dir"] == "hold"


def test_decide_down_needs_consecutive_quiet_streak():
    sc, _ = _scaler(2, quiet_ticks=3, min_engines=1)
    quiet = _sig(n=2)
    assert sc.decide(quiet)["dir"] == "hold"     # streak 1
    assert sc.decide(quiet)["dir"] == "hold"     # streak 2
    assert sc.decide(_sig(n=2, shed_rate=0.5))["dir"] == "up"  # reset
    assert sc.decide(quiet)["dir"] == "hold"     # streak restarts
    assert sc.decide(quiet)["dir"] == "hold"
    assert sc.decide(quiet)["dir"] == "down"     # streak 3
    # quiet at min_engines never goes below the floor
    sc2, _ = _scaler(1, quiet_ticks=1, min_engines=1)
    assert sc2.decide(_sig(n=1))["dir"] == "hold"
    # pipeline lag is NOT quiet: a busy fleet is not a shrinkable one
    sc3, _ = _scaler(2, quiet_ticks=1)
    assert sc3.decide(_sig(n=2, lag_steps=3))["dir"] == "hold"


def test_tick_scales_up_on_shed_pressure():
    sc, fleet = _scaler(1)
    fleet.router.stats.count("routed", 10)
    fleet.router.stats.count("shed", 5)
    assert sc.tick() == "up"
    assert sc.scale_ups == 1
    assert len(fleet.router.names()) == 2
    # the joined member is live in dispatch
    assert sorted(fleet.router.healthy_names()) == ["e0", "e1"]


def test_tick_cooldown_vetoes_backtoback_actions():
    sc, fleet = _scaler(1, cooldown_s=30.0)
    fleet.router.stats.count("routed", 10)
    fleet.router.stats.count("shed", 5)
    assert sc.tick() == "up"
    fleet.router.stats.count("shed", 5)          # still under pressure
    assert sc.tick() == "hold"                   # cooldown veto
    assert sc.holds == 1 and len(fleet.router.names()) == 2
    assert "cooldown" in sc.last_why


def test_tick_scales_down_after_quiet_and_drains():
    sc, fleet = _scaler(2, quiet_ticks=2, min_engines=1)
    assert sc.tick() == "hold"                   # quiet streak 1
    assert sc.tick() == "down"                   # streak 2: retire one
    _join_action(sc)
    assert sc.scale_downs == 1 and sc.drained_clean == 1
    assert len(fleet.router.names()) == 1
    # at the floor now: quiet ticks keep holding
    assert sc.tick() == "hold"
    assert len(fleet.router.names()) == 1


def test_scale_down_never_picks_the_canary():
    sc, fleet = _scaler(2, quiet_ticks=1, min_engines=1)

    class _Rollout:
        canary = "e0"
    fleet.rollout = _Rollout()
    assert sc.tick() == "down"
    _join_action(sc)
    assert fleet.router.names() == ["e0"]        # bystander retired


def test_grow_failure_aborts_without_membership_change():
    sc, fleet = _scaler(1)
    fleet.grow_error = "no spawn config"
    fleet.router.stats.count("routed", 10)
    fleet.router.stats.count("shed", 5)
    assert sc.tick() == "abort"
    assert sc.grow_failures == 1 and sc.aborts == 1
    assert len(fleet.router.names()) == 1


def test_scale_decide_fault_skips_decision():
    sc, fleet = _scaler(2, quiet_ticks=1, min_engines=1)
    with inject(FaultSchedule.parse("scale.decide@0:error")):
        assert sc.tick() == "abort"              # faulted: no action
    assert sc.decide_faults == 1 and sc.aborts == 1
    assert len(fleet.router.names()) == 2        # nothing retired
    assert sc.scale_downs == 0 and sc.scale_ups == 0
    assert sc.tick() == "down"                   # next tick recovers
    _join_action(sc)
    assert len(fleet.router.names()) == 1


def test_autoscaler_snapshot_and_metrics():
    from singa_tpu.obs.metrics import MetricsRegistry
    sc, fleet = _scaler(1)
    sc.tick()
    snap = sc.snapshot()
    assert snap["ticks"] == 1 and snap["engines"] == 1
    reg = MetricsRegistry()
    sc.register_into(reg)
    text = reg.render_prometheus()
    assert "singa_autoscale_ticks_total" in text
    assert "singa_autoscale_engines" in text


# -- open-loop traffic harness -----------------------------------------------

def test_phase_validation_and_builders():
    with pytest.raises(ValueError):
        Phase(name="bad", duration_s=0, rate_rps=1.0)
    with pytest.raises(ValueError):
        Phase(name="bad", duration_s=1.0, rate_rps=-1.0)
    p = ramp("r", 2.0, 1.0, 5.0)
    assert p.rate_at(0.0) == pytest.approx(1.0)
    assert p.rate_at(1.0) == pytest.approx(5.0)
    fc = flash_crowd("f", 1.0, 2.0, k=5.0)
    assert fc.rate_rps == pytest.approx(10.0)
    day = diurnal(base_rps=1.0, peak_rps=4.0, rise_s=1.0,
                  plateau_s=1.0, fall_s=1.0)
    assert [p.name for p in day] == ["diurnal-rise", "diurnal-plateau",
                                     "diurnal-fall"]
    assert day[1].rate_rps == pytest.approx(4.0)


def test_traffic_is_open_loop_arrivals_do_not_wait():
    def slow_request(tokens):
        time.sleep(0.3)                  # far slower than the gap

    gen = TrafficGen(slow_request, seed=7, log_fn=lambda s: None)
    rep = gen.run([steady("burst", duration_s=0.4, rate_rps=40.0)],
                  drain_timeout_s=5.0)
    tot = rep["totals"]
    # closed-loop would manage ~1 arrival in 0.4s; open-loop offers
    # ~16 (Poisson) regardless of completion latency
    assert tot["offered"] >= 6
    assert tot["completed"] == tot["offered"]
    assert tot["failed"] == 0 and tot["dropped_harness"] == 0


def test_traffic_accounts_shed_and_failures():
    calls = {"n": 0}

    def flaky(tokens):
        calls["n"] += 1
        if calls["n"] % 3 == 1:
            raise Overloaded("full", retry_after=0.01)
        if calls["n"] % 3 == 2:
            raise ValueError("boom")

    gen = TrafficGen(flaky, seed=3, log_fn=lambda s: None)
    rep = gen.run([steady("p", duration_s=0.3, rate_rps=30.0)],
                  drain_timeout_s=5.0)
    tot = rep["totals"]
    assert tot["offered"] == (tot["completed"] + tot["shed"]
                              + tot["failed"])
    assert tot["shed"] >= 1 and tot["failed"] >= 1
    assert tot["shed_rate"] > 0
    assert any("ValueError" in e for e in tot["errors"])
    row = rep["phases"][0]
    for key in ("offered", "completed", "shed", "failed",
                "dropped_harness", "qps_offered", "p95_ms"):
        assert key in row


def test_traffic_max_outstanding_counts_drops():
    release = threading.Event()

    def stuck(tokens):
        release.wait(5.0)

    gen = TrafficGen(stuck, seed=1, max_outstanding=2,
                     log_fn=lambda s: None)
    try:
        rep = gen.run([steady("p", duration_s=0.3, rate_rps=50.0)],
                      drain_timeout_s=0.1)
    finally:
        release.set()
    tot = rep["totals"]
    assert tot["dropped_harness"] >= 1   # counted, never silent
    # only spawned arrivals count as offered; the cap held
    assert tot["offered"] <= 2


def test_traffic_streams_with_slow_reader():
    events = {"n": 0}

    def req(tokens):
        pass

    def stream(tokens, max_new=4):
        for i in range(int(max_new)):
            events["n"] += 1
            yield {"token": i}
        yield {"done": True}

    gen = TrafficGen(req, stream_fn=stream, seed=5,
                     log_fn=lambda s: None)
    rep = gen.run([steady("s", duration_s=0.25, rate_rps=20.0,
                          stream_p=1.0, slow_reader_s=0.001,
                          max_new=(3,))],
                  drain_timeout_s=5.0)
    tot = rep["totals"]
    assert tot["completed"] == tot["offered"] and tot["failed"] == 0
    assert events["n"] == 3 * tot["completed"]


# -- the real fleet: capacity follows the load, a drain drops nothing --------

def test_real_fleet_grows_under_flash_shrinks_when_quiet_and_drains():
    """One real engine (2 slots, a 4-deep queue, a throttled step)
    under an open-loop flash crowd: the autoscaler answers the sheds
    with a second compiled engine, retires one again once the window
    is quiet, and nothing but `Overloaded` ever reaches a client.
    Then an engine holding a live slow-reader stream is retired with
    drain=True: every token and the done event arrive before the
    member leaves."""
    from singa_tpu.serve import EngineFleet, ServeSpec
    from test_fleet import SEQ, _net_and_params

    net, params = _net_and_params()
    spec = ServeSpec(buckets=((2, SEQ),), max_new_tokens=8,
                     batch_window_s=0.002, request_timeout_s=30.0,
                     queue_capacity=4, cb="on", cb_slots=2,
                     cb_block_len=4)
    fleet = EngineFleet.local(
        net, spec, 1, params=params,
        router_spec=RouterSpec(probe_period_s=0.05,
                               quarantine_after=3),
        log_fn=lambda s: None)
    fleet.start()
    # 8 steps x 10 ms a request, 2 slots: ~25 req/s an engine, well
    # under the flash's 120
    fleet.router.handle_for("engine-0").engine.set_stall(0.01)
    scaler = AutoScaler(
        fleet, spec=AutoScaleSpec(
            slo_p95_ms=5000.0, max_shed_rate=0.02, min_engines=1,
            max_engines=2, cooldown_s=0.2, window_s=1.0, tick_s=0.05,
            quiet_ticks=5, drain_timeout_s=20.0),
        log_fn=lambda s: None)
    scaler.start()
    try:
        gen = TrafficGen(lambda toks: fleet.generate(toks.tolist()),
                         vocab=64, seed=0, max_outstanding=512,
                         log_fn=lambda s: None)
        rep = gen.run([steady("flash", 1.5, 120.0, prompt_lens=(4,)),
                       steady("quiet", 1.0, 2.0, prompt_lens=(4,))],
                      drain_timeout_s=30.0)
        tot = rep["totals"]
        assert tot["shed"] >= 1              # the flash did overflow
        assert tot["failed"] == 0, tot["errors"][:3]
        assert tot["dropped_harness"] == 0
        stop = time.monotonic() + 30.0       # a grow compiles 2 programs
        while scaler.scale_ups < 1 and time.monotonic() < stop:
            time.sleep(0.05)
        assert scaler.scale_ups >= 1
        stop = time.monotonic() + 30.0
        while scaler.scale_downs < 1 and time.monotonic() < stop:
            time.sleep(0.05)
        assert scaler.scale_downs >= 1
        _join_action(scaler, timeout=25.0)
        assert len(fleet.router.names()) == 1
    finally:
        scaler.stop()
    try:
        # -- drain: retire the engine that holds a live stream --------
        fleet.grow()
        for n in fleet.router.names():
            fleet.router.handle_for(n).engine.set_stall(0.0)
        events, errors = [], []
        started = threading.Event()

        def slow_reader():
            try:
                for ev in fleet.generate_stream([1, 2, 3, 4],
                                                max_new=6):
                    events.append(ev)
                    started.set()
                    if "token" in ev:
                        time.sleep(0.05)     # slower than the decode
            except Exception as e:  # noqa: BLE001 — asserted below
                errors.append(repr(e))
                started.set()

        reader = threading.Thread(target=slow_reader)
        reader.start()
        assert started.wait(10.0)
        victim, stop = None, time.monotonic() + 5.0
        while victim is None and time.monotonic() < stop:
            victim = next((m["name"] for m in fleet.router.members()
                           if m["in_flight"] > 0), None)
        assert victim is not None
        assert fleet.retire(victim, drain=True, timeout_s=20.0)
        reader.join(30.0)
        assert not reader.is_alive() and not errors, errors
        assert sum("token" in ev for ev in events) == 6
        assert events[-1].get("done")
        assert victim not in fleet.router.names()
    finally:
        fleet.stop()
