"""Crash-safe control plane (serve/sessionlog.py + the Router's
recovery surface): durable session WAL, epoch fencing, restart and
handoff with exactly-once stream resume.

Correctness anchors:
  * WAL replay is torn-tail-tolerant and idempotent: a SIGKILL
    mid-write truncates the journal, it never poisons it; a duplicate
    token append after a crash-between-fsync-and-ack folds to a no-op
    by absolute index;
  * a finished stream replays as a pure journal read — no engine ever
    re-decodes it; a live stream re-enters the durable-session resume
    path pinned to its journaled fingerprint and a reconnecting client
    splices exactly-once, bit-identical to the uninterrupted decode;
  * epochs fence: a newer claim over the shared journal directory
    makes the old epoch's writes counted refusals — a replaced
    primary can never corrupt the successor's recovery source;
  * quarantine strikes/benches and per-(tenant, class) shed streaks
    survive restart (control-state snapshot), so a crash cannot
    launder a strike streak or a Retry-After escalation.

Cost control: WAL/replay/fencing logic runs on plain files and stub
handles; three tests build real engines over one module-scoped net:
restart + handoff in a single fleet sequence, a faulted recovery, and
the handoff as a client sees it over HTTP.  The subprocess SIGKILL
restart over HTTP is the `slow` test at the end."""

import json
import os
import tempfile
import threading
import time

import numpy as np
import pytest

from singa_tpu.serve import qos
from singa_tpu.serve.router import (EngineUnavailable, LameDuck,
                                    Overloaded, Router, RouterSpec,
                                    UnknownSession)
from singa_tpu.serve.session import SessionManager
from singa_tpu.serve.sessionlog import (ControlStateStore, SessionWal,
                                        WalStats, claim_epoch,
                                        latest_wal_before, read_epoch,
                                        reduce_sessions, replay_wal,
                                        wal_path, walcheck)
from singa_tpu.utils.faults import FaultSchedule, inject

pytestmark = pytest.mark.wal


def _wal(dir_, epoch=1, **kw):
    kw.setdefault("group_tokens", 4)
    kw.setdefault("group_ms", 5.0)
    kw.setdefault("log_fn", lambda s: None)
    return SessionWal(dir_, epoch, **kw)


# -- WAL append / replay ------------------------------------------------------

def test_wal_roundtrip_and_walcheck(tmp_path):
    d = str(tmp_path)
    w = _wal(d, epoch=1)
    w.append_open("s1-1", [5, 6], 8, "interactive", "acme", None, 3,
                  12.5)
    for i, t in enumerate([10, 11, 12]):
        w.append_tok("s1-1", i, t)
    w.append_resume("s1-1", "engine-1", 3)
    w.append_open("s1-2", [7], 4, "batch", "default", None, 3, None)
    w.append_tok("s1-2", 0, 42)
    w.append_close("s1-2", "done")
    w.close()

    header, records, torn = replay_wal(wal_path(d, 1))
    assert not torn
    assert header["epoch"] == 1 and header["ver"] == 1
    red = reduce_sessions(records)
    assert set(red) == {"s1-1", "s1-2"}
    live = red["s1-1"]
    assert live["terminal"] is None
    assert live["prompt"] == [5, 6] and live["emitted"] == [10, 11, 12]
    assert live["step"] == 3 and live["tenant"] == "acme"
    assert live["resumes"] == 1 and live["engine"] == "engine-1"
    closed = red["s1-2"]
    assert closed["terminal"] == "done" and closed["emitted"] == [42]

    chk = walcheck(wal_path(d, 1))
    assert chk["epoch"] == 1 and not chk["torn_tail"]
    assert chk["sessions"] == 2 and chk["live_sessions"] == 1
    assert chk["journaled_tokens"] == 4
    assert chk["live"][0]["sid"] == "s1-1"


def test_wal_coalesces_contiguous_tokens(tmp_path):
    """Consecutive same-sid tokens become ONE journal record — the
    group commit stays compact at streaming rates."""
    d = str(tmp_path)
    w = _wal(d, epoch=1, group_tokens=1000, group_ms=1000.0)
    w.append_open("s", [1], 8, "interactive", "default", None, 1, None)
    for i in range(6):
        w.append_tok("s", i, 100 + i)
    w.close()
    _, records, _ = replay_wal(wal_path(d, 1))
    toks = [r for r in records if r["k"] == "tok"]
    assert len(toks) == 1
    assert toks[0]["i"] == 0 and toks[0]["t"] == [100 + i
                                                  for i in range(6)]


def test_wal_torn_tail_truncates_never_poisons(tmp_path):
    d = str(tmp_path)
    w = _wal(d, epoch=1)
    w.append_open("s", [1], 8, "interactive", "default", None, 1, None)
    w.append_tok("s", 0, 7)
    w.close()
    # a SIGKILL mid-write: half a record at the tail, then (as if a
    # later writer raced) a VALID-looking record after the tear —
    # replay must stop at the tear, trusting only the prefix
    good = {"k": "tok", "sid": "s", "i": 1, "t": [9]}
    import zlib
    line = json.dumps({"c": zlib.crc32(json.dumps(
        good, sort_keys=True,
        separators=(",", ":")).encode()) & 0xFFFFFFFF, "r": good})
    with open(wal_path(d, 1), "ab") as f:
        f.write(b'{"c": 123, "r": {"k": "tok", "sid')   # torn line
        f.write(b"\n" + line.encode() + b"\n")
    _, records, torn = replay_wal(wal_path(d, 1))
    assert torn
    red = reduce_sessions(records)
    assert red["s"]["emitted"] == [7]     # nothing after the tear


def test_reduce_folds_duplicate_appends_and_gaps():
    records = [
        {"k": "open", "sid": "s", "prompt": [1], "max_new": 8,
         "priority": "interactive", "tenant": "default",
         "family": None, "step": 1, "deadline_rem_s": None},
        {"k": "tok", "sid": "s", "i": 0, "t": [10, 11]},
        # duplicate flush after a crash-between-fsync-and-ack:
        # same indices again plus one new token
        {"k": "tok", "sid": "s", "i": 0, "t": [10, 11, 12]},
        # a gap (index 5 with only 3 journaled) keeps the prefix
        {"k": "tok", "sid": "s", "i": 5, "t": [99]},
        # tok for a sid never opened: ignored
        {"k": "tok", "sid": "ghost", "i": 0, "t": [1]},
    ]
    red = reduce_sessions(records)
    assert red["s"]["emitted"] == [10, 11, 12]
    assert "ghost" not in red


def test_epoch_claim_monotonic_and_latest_wal(tmp_path):
    d = str(tmp_path)
    assert read_epoch(d) == 0
    assert claim_epoch(d) == 1
    assert claim_epoch(d) == 2
    assert claim_epoch(d) == 3
    _wal(d, epoch=1).close()
    _wal(d, epoch=2).close()
    # the successor of epoch 3 replays the HIGHEST journal below it
    assert latest_wal_before(d, 3) == wal_path(d, 2)
    assert latest_wal_before(d, 2) == wal_path(d, 1)
    assert latest_wal_before(d, 1) is None


def test_fenced_epoch_refuses_writes(tmp_path):
    d = str(tmp_path)
    stats = WalStats()
    w = _wal(d, epoch=claim_epoch(d), stats=stats)
    w.append_open("s", [1], 8, "interactive", "default", None, 1, None)
    w.flush()
    size_before = os.path.getsize(w.path)
    # a successor claims over us (restart or handoff): the next group
    # commit self-fences instead of writing
    claim_epoch(d)
    w.append_tok("s", 0, 7)
    w.flush()
    assert w.fenced
    assert os.path.getsize(w.path) == size_before
    assert stats.snapshot()["fenced_writes"] >= 1
    # and every append after the fence is a counted refusal
    assert w.append_tok("s", 1, 8) is False
    w.close()


def test_explicit_fence_flushes_pending_first(tmp_path):
    """Handoff ordering: fence() writes what is pending BEFORE
    refusing — the successor's recovery source is complete up to the
    fence."""
    d = str(tmp_path)
    w = _wal(d, epoch=1, group_tokens=1000, group_ms=1000.0)
    w.append_open("s", [1], 8, "interactive", "default", None, 1, None)
    w.append_tok("s", 0, 7)
    w.fence()
    assert w.append_tok("s", 1, 8) is False
    w.close()
    _, records, _ = replay_wal(wal_path(d, 1))
    assert reduce_sessions(records)["s"]["emitted"] == [7]


def test_wal_fault_degrades_to_counted_loss(tmp_path):
    """An injected `router.wal` fault (disk error stand-in) drops the
    batch as counted lost durability — append/flush never raise, the
    stream's tokens never block."""
    d = str(tmp_path)
    stats = WalStats()
    w = _wal(d, epoch=1, stats=stats)
    with inject(FaultSchedule.parse("router.wal@0:error")):
        w.append_open("s", [1], 8, "interactive", "default", None, 1,
                      None)
        w.flush()                        # faulted commit: dropped
        assert stats.snapshot()["wal_lost"] >= 1
        w.append_tok("s", 0, 7)
        w.flush()                        # next commit succeeds
    w.close()
    _, records, _ = replay_wal(wal_path(d, 1))
    red = reduce_sessions(records)
    # the open record was in the dropped batch; the tok survives but
    # has no open to attach to — replay degrades, never corrupts
    assert "s" not in red
    assert stats.snapshot()["wal_appends"] == 2


def test_control_state_store_roundtrip_and_torn(tmp_path):
    d = str(tmp_path)
    store = ControlStateStore(d)
    assert store.load() is None          # missing: clean start
    assert store.save({"epoch": 2, "router": {"members": {}}})
    assert store.load()["epoch"] == 2
    with open(store.path, "w") as f:
        f.write('{"epoch": 2, "rou')     # torn snapshot
    assert store.load() is None          # degrades to clean start


# -- replay-only terminal sessions (no engine re-decode) ---------------------

def test_register_terminal_replays_without_engine():
    mgr = SessionManager()
    rec = {"sid": "s1-9", "prompt": [1, 2], "max_new": 4,
           "priority": "interactive", "tenant": "default",
           "family": None, "step": 3, "emitted": [10, 11, 12],
           "resumes": 0, "terminal": "done"}
    s = mgr.register_terminal(rec)
    assert mgr.get("s1-9") is s and s.attachable
    evs = list(s.attach(resume_from=0))
    toks = [(e["i"], e["token"]) for e in evs if "token" in e]
    assert toks == [(0, 10), (1, 11), (2, 12)]
    done = evs[-1]
    assert done["done"] and done["replayed"]
    assert done["tokens"] == [10, 11, 12] and done["finish"] == "length"
    # reconnect-with-prefix: indices below resume_from are skipped
    evs2 = list(s.attach(resume_from=2))
    assert [(e["i"], e["token"]) for e in evs2
            if "token" in e] == [(2, 12)]


def test_session_manager_bounds_terminal_retention():
    mgr = SessionManager()
    mgr.configure(ttl_s=60.0, cap=3)
    for i in range(6):
        mgr.register_terminal(
            {"sid": f"t{i}", "prompt": [1], "emitted": [i],
             "terminal": "done"})
        mgr._evict()
    snap = mgr.snapshot()
    assert snap["terminal_retained"] <= 3
    assert snap["sessions_evicted"] >= 3
    assert mgr.get("t0") is None and mgr.get("t5") is not None
    # TTL: an expired entry goes on the next sweep
    mgr2 = SessionManager()
    mgr2.configure(ttl_s=0.0, cap=100)
    mgr2.register_terminal({"sid": "x", "prompt": [1], "emitted": [],
                            "terminal": "done"})
    time.sleep(0.01)
    mgr2._evict()
    assert mgr2.get("x") is None
    assert mgr2.stats.snapshot()["sessions_evicted"] == 1


# -- stub-router surface: lame duck, attach errors, state restore ------------

class StubHandle:
    def __init__(self, name, step=1):
        self.name = name
        self.step = step
        self.fail_probe = False

    def probe(self):
        if self.fail_probe:
            raise EngineUnavailable(f"{self.name} is down")
        return {"ok": True, "status": "ok", "step": self.step,
                "queue_depth": 0}

    def stats_snapshot(self):
        return {"completed": 0, "failed": 0, "expired": 0,
                "p95_latency_ms": None}

    def request(self, mode, tokens, timeout=None):
        return {"tokens": [1], "step": self.step}


def _router(n=2, **kw):
    kw.setdefault("quarantine_after", 2)
    kw.setdefault("probe_period_s", 60.0)
    kw.setdefault("readmit_base_s", 30.0)   # benches outlast the test
    stubs = [StubHandle(f"e{i}") for i in range(n)]
    r = Router(stubs, spec=RouterSpec(**kw), log_fn=lambda s: None)
    r.probe_all()
    return r, stubs


def test_lame_duck_refuses_with_successor_hint():
    r, _ = _router(2)
    assert r.route("generate", [1])["step"] == 1
    r.enter_lame_duck(successor="http://next:8000", retry_after=0.25)
    with pytest.raises(LameDuck) as ei:
        r.route("generate", [1])
    assert ei.value.successor == "http://next:8000"
    assert ei.value.retry_after == 0.25
    with pytest.raises(LameDuck):
        r.route_stream([1], max_new=4)
    assert r.stats.lame_duck_refusals == 2
    assert r.snapshot()["lame_duck"] is True


def test_attach_unknown_session_raises_gone():
    r, _ = _router(1)
    with pytest.raises(UnknownSession):
        r.attach_stream("never-journaled")


def test_quarantine_and_shed_streaks_survive_restart():
    """The control-state snapshot closes the restart laundering hole:
    a quarantined engine stays benched for its REMAINING time, and a
    tenant's Retry-After streak keeps escalating where it left off."""
    r1, stubs = _router(2, quarantine_after=2)
    stubs[0].fail_probe = True
    r1.probe_all()
    r1.probe_all()                    # 2 strikes -> quarantined
    assert {m["name"]: m["quarantined"]
            for m in r1.members()}["e0"]
    # build a shed streak for one (tenant, class)
    r1._shed_backoffs.shed_delay("interactive", tenant="acme")
    r1._shed_backoffs.shed_delay("interactive", tenant="acme")
    state = r1.export_control_state()
    assert state["members"]["e0"]["quarantined"]
    assert state["members"]["e0"]["bench_remaining_s"] > 0
    assert state["shed_streaks"] == {"acme\tinteractive": 2}

    # "restart": a fresh router over the same membership
    r2, stubs2 = _router(2, quarantine_after=2)
    assert not any(m["quarantined"] for m in r2.members())
    r2.restore_control_state(state)
    m = {m["name"]: m for m in r2.members()}
    assert m["e0"]["quarantined"] and not m["e1"]["quarantined"]
    assert r2.healthy_names() == ["e1"]
    # the restored bench holds: a probe round does NOT readmit early
    r2.probe_all()
    assert {m["name"]: m["quarantined"]
            for m in r2.members()}["e0"]
    assert r2._shed_backoffs.export_streaks() == {
        "acme\tinteractive": 2}


def test_shed_streak_export_restore_grammar():
    b = qos.ClassBackoffs(seed=0)
    b.shed_delay("batch", tenant="a")
    b.shed_delay("batch", tenant="a")
    b.shed_delay("interactive", tenant="b")
    b.reset("interactive", tenant="b")   # streak resets -> not exported
    out = b.export_streaks()
    assert out == {"a\tbatch": 2}
    b2 = qos.ClassBackoffs(seed=0)
    b2.restore_streaks(out)
    assert b2.export_streaks() == {"a\tbatch": 2}
    # garbage keys degrade to ignored, never raise
    b2.restore_streaks({"no-tab": 3, "x\ty": "bad"})


# -- satellite: supervised reload poll (silent-death fix) --------------------

def test_reload_poll_death_is_counted_and_survived():
    """An unexpected exception in the reload poll used to kill the
    daemon thread silently — stale params behind a healthy /healthz
    forever.  Now each death is counted, the loop restarts after a
    Backoff delay, and health degrades once the streak crosses
    `degraded_after`."""
    import jax

    from singa_tpu.core.net import build_net
    from singa_tpu.models.transformer import transformer_lm
    from singa_tpu.serve import InferenceEngine, InferenceServer, \
        ServeSpec

    cfg = transformer_lm(vocab_size=64, num_layers=1, embed_dim=16,
                         num_heads=2, head_dim=8, seq_len=8,
                         batchsize=2)
    net = build_net(cfg, "kTest",
                    {"data": {"input": (8,), "target": (8,)}})
    params = net.init_params(jax.random.PRNGKey(0))
    spec = ServeSpec(buckets=((2, 8),), max_new_tokens=2,
                     reload_poll_s=0.01, degraded_after=2)
    eng = InferenceEngine(net, spec, params=params,
                          log_fn=lambda s: None)

    def boom():
        raise RuntimeError("poll exploded")

    eng.poll_reload = boom
    srv = InferenceServer(eng, http=False, warmup_modes=(),
                          log_fn=lambda s: None)
    srv.start()
    try:
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and \
                srv.stats.snapshot()["reload_poll_deaths"] < 2:
            time.sleep(0.01)
        snap = srv.stats.snapshot()
        assert snap["reload_poll_deaths"] >= 2
        assert srv._poll_thread.is_alive()   # supervised, not dead
        h = eng.health()
        assert not h["ok"]
        assert any("reload poll died" in s for s in h["reasons"])
        # recovery clears the degradation
        eng.note_poll_ok()
        assert eng.health()["ok"]
    finally:
        srv.stop()


# -- satellite: HttpEngineHandle connection hygiene (fd-flat) ----------------

def test_http_handle_fds_flat_under_churn():
    """500 churned calls — successes, HTTP errors, and streams closed
    early — must not grow this process's open-fd count: every error
    body and every stream response is closed deterministically, not
    left to GC (PR 15's singa_process_open_fds watches the same
    signal in production)."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from singa_tpu.serve.router import HttpEngineHandle

    class H(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):
            pass

        def _json(self, code, obj):
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path == "/healthz":
                self._json(200, {"ok": True, "status": "ok",
                                 "step": 1})
            else:
                self._json(500, {"error": "boom"})

        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            req = json.loads(self.rfile.read(n) or b"{}")
            if req.get("stream"):
                self.send_response(200)
                self.send_header("Content-Type",
                                 "application/x-ndjson")
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()
                for i in range(4):
                    line = json.dumps({"token": i, "i": i}).encode() \
                        + b"\n"
                    self.wfile.write(f"{len(line):X}\r\n".encode()
                                     + line + b"\r\n")
                self.wfile.write(
                    b"0\r\n\r\n")
            else:
                self._json(500, {"error": "boom"})

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
    httpd.daemon_threads = True
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    h = HttpEngineHandle(
        "e0", f"http://127.0.0.1:{httpd.server_address[1]}")

    def nfds():
        return len(os.listdir("/proc/self/fd"))

    try:
        for _ in range(10):              # settle urllib/socket caches
            h.probe()
        base = nfds()
        for k in range(500):
            if k % 3 == 0:
                h.probe()                # 200 + a 500 /stats inside
            elif k % 3 == 1:
                with pytest.raises(EngineUnavailable):
                    h.request("generate", [1, 2])   # 500 error body
            else:
                gen = h.request_stream([1], max_new=4)
                next(gen)
                gen.close()              # client walks away mid-body
        assert nfds() <= base + 8, \
            f"fd leak under churn: {base} -> {nfds()}"
    finally:
        httpd.shutdown()
        httpd.server_close()


# -- the tentpole over real engines: restart + handoff -----------------------

@pytest.fixture(scope="module")
def tiny_lm():
    import jax

    from singa_tpu.core.net import build_net
    from singa_tpu.models.transformer import transformer_lm

    seq = 16
    cfg = transformer_lm(vocab_size=64, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=seq,
                         batchsize=2)
    net = build_net(cfg, "kTest",
                    {"data": {"input": (seq,), "target": (seq,)}})
    return net, net.init_params(jax.random.PRNGKey(0)), seq


def _make_fleet(tiny_lm, ws, standby=False, log=lambda s: None):
    from singa_tpu.serve import EngineFleet, ServeSpec

    net, params, seq = tiny_lm
    spec = ServeSpec(buckets=((2, seq),), max_new_tokens=8,
                     batch_window_s=0.002, request_timeout_s=60.0,
                     cb="on", cb_slots=3, cb_block_len=4)
    rspec = RouterSpec(probe_period_s=0.1, hedge="off",
                       request_timeout_s=60.0, wal_group_tokens=4,
                       wal_group_ms=5.0, state_snapshot_s=0.1)
    return EngineFleet.local(net, spec, 1, workspace=ws,
                             params=params, router_spec=rspec,
                             standby=standby, log_fn=log)


def test_router_restart_resumes_stream_exactly_once(tiny_lm):
    """The tentpole, in-process: a stream is cut mid-decode by a
    router 'crash' (the fleet object is abandoned, never stopped —
    exactly what SIGKILL leaves behind: a WAL with no close record);
    a successor fleet over the same workspace claims the next epoch,
    replays the journal, re-admits the stream pinned to the journaled
    fingerprint, and the reconnecting client's spliced stream is
    BIT-IDENTICAL to an uninterrupted reference — with the old
    epoch's journal fenced against late writes."""
    import numpy as _np

    from singa_tpu.utils.checkpoint import CheckpointManager

    net, params, seq = tiny_lm
    with tempfile.TemporaryDirectory() as ws:
        mgr = CheckpointManager(ws, log_fn=lambda s: None)
        mgr.save(1, params, {"t": _np.zeros(())},
                 health={"verdict": "ok"})
        prompt = _np.arange(1, 5, dtype=_np.int32)

        # reference: uninterrupted greedy decode (also proves a
        # finished stream's journal replays as terminal later)
        f0 = _make_fleet(tiny_lm, ws)
        f0.start()
        assert f0.epoch == 1
        ref = [ev["token"]
               for ev in f0.generate_stream(prompt, max_new=8)
               if "token" in ev]
        assert len(ref) == 8
        f0.stop()

        # the victim: consume 3 tokens, then CRASH (abandon, no stop;
        # keep the generator referenced so GC cannot close it and
        # journal a close record a real SIGKILL would never write)
        f1 = _make_fleet(tiny_lm, ws)
        f1.start()
        assert f1.epoch == 2
        stream = f1.generate_stream(prompt, max_new=8)
        seen, sid, epoch_seen = [], None, None
        for ev in stream:
            if sid is None and "sid" in ev:
                sid, epoch_seen = ev["sid"], ev.get("epoch")
            if "token" in ev:
                seen.append(ev["token"])
            if len(seen) >= 3:
                break
        assert sid is not None and epoch_seen == 2
        assert sid.startswith("s2-")   # epoch-namespaced: no collision
        f1.wal.flush()                 # the group commit a crash races

        # the successor: claims epoch 3, replays epoch 2's journal
        f2 = _make_fleet(tiny_lm, ws)
        f2.start()
        assert f2.epoch == 3
        out = list(f2.router.attach_stream(sid,
                                           resume_from=len(seen)))
        toks = [ev["token"] for ev in out if "token" in ev]
        done = [ev for ev in out if ev.get("done")][0]
        assert seen + toks == ref      # exactly-once, bit-identical
        assert done["tokens"] == ref and done["spliced"]
        assert done["finish"] == "length"
        snap = f2.wal_stats.snapshot()
        assert snap["recovered_streams"] == 1
        assert snap["replayed_sessions"] >= 1
        assert f2.router.sessions.stats.snapshot()["attached"] == 1
        # second reconnect: the finished session replays from the
        # retained journal — no engine re-decodes it
        again = list(f2.router.attach_stream(sid, resume_from=0))
        assert [e["token"] for e in again if "token" in e] == ref

        # the fenced predecessor cannot corrupt the successor's
        # journal: its next group commit is a counted refusal
        f1.wal.append_close(sid, "done")
        f1.wal.flush()
        assert f1.wal.fenced
        assert f1.wal_stats.snapshot()["fenced_writes"] >= 1

        # handoff leg: lame-duck f2 toward a standby, promote it
        f3 = _make_fleet(tiny_lm, ws, standby=True)
        f3.start()
        assert f3.standby and f3.epoch == 0 and f3.wal is None
        got = f2.handoff(successor="http://standby:9")
        assert got["lame_duck"] and f2.wal.fenced
        with pytest.raises(LameDuck) as ei:
            f2.generate(prompt)
        assert ei.value.successor == "http://standby:9"
        promoted = f3.promote_standby()
        assert f3.epoch == 4 and not f3.standby
        # f2 had no live streams at handoff; its terminal sessions
        # replay on the promoted standby
        assert promoted["terminal"] >= 1
        assert [e["token"]
                for e in f3.router.attach_stream(sid, resume_from=0)
                if "token" in e] == ref
        # fresh admissions flow on the new primary
        assert f3.generate(prompt)["step"] == 1
        f3.stop()
        f2.stop()
        stream.close()                 # release f1's abandoned leg
        f1.stop()


def test_recovery_fault_degrades_to_serving_without_replay(tiny_lm):
    """An injected `router.recover` fault (corrupt journal stand-in)
    must not stop the successor from serving NEW traffic — recovery
    is an add-on, not a startup gate."""
    import numpy as _np

    from singa_tpu.utils.checkpoint import CheckpointManager

    net, params, seq = tiny_lm
    with tempfile.TemporaryDirectory() as ws:
        mgr = CheckpointManager(ws, log_fn=lambda s: None)
        mgr.save(1, params, {"t": _np.zeros(())},
                 health={"verdict": "ok"})
        f0 = _make_fleet(tiny_lm, ws)
        f0.start()
        list(f0.generate_stream(_np.arange(1, 5, dtype=_np.int32),
                                max_new=4))
        f0.stop()
        with inject(FaultSchedule.parse("router.recover@0:error")):
            f1 = _make_fleet(tiny_lm, ws)
            f1.start()
        assert f1.wal_stats.snapshot()["recovered_streams"] == 0
        out = f1.generate(_np.arange(1, 5, dtype=_np.int32))
        assert out["step"] == 1
        f1.stop()


def test_handoff_over_http_refuses_with_409_at_the_successor(tiny_lm):
    """The handoff as a client sees it: POST /admin/handoff lame-ducks
    the primary mid-stream, a fresh admission is refused with 409 whose
    body and Retry-After point at the successor, the in-flight stream
    still finishes on the lame duck, and after POST /admin/promote the
    standby serves the same prompt bit-identically."""
    import urllib.error
    import urllib.request

    import numpy as _np

    from singa_tpu.serve import FleetServer
    from singa_tpu.utils.checkpoint import CheckpointManager

    net, params, seq = tiny_lm
    prompt = [1, 2, 3, 4]

    def post(url, body):
        req = urllib.request.Request(
            url, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        return urllib.request.urlopen(req, timeout=60.0)

    def stream(url, out):
        with post(url + "/generate", {"tokens": prompt, "stream": True,
                                      "max_new": 8}) as r:
            for line in r:
                ev = json.loads(line)
                if "token" in ev:
                    out.append(int(ev["token"]))

    with tempfile.TemporaryDirectory() as ws:
        CheckpointManager(ws, log_fn=lambda s: None).save(
            1, params, {"t": _np.zeros(())}, health={"verdict": "ok"})
        primary = _make_fleet(tiny_lm, ws)
        standby = _make_fleet(tiny_lm, ws, standby=True)
        primary.start()
        standby.start()
        front1 = FleetServer(primary, log_fn=lambda s: None).start()
        front2 = FleetServer(standby, log_fn=lambda s: None).start()
        url1, url2 = (f"http://{h}:{p}"
                      for h, p in (front1.address, front2.address))
        try:
            ref = []
            stream(url1, ref)
            assert len(ref) == 8
            # a step slow enough that the stream is still in flight
            # when the primary is lame-ducked
            primary.router.handle_for("engine-0").engine.set_stall(0.05)
            inflight = []
            t = threading.Thread(target=stream, args=(url1, inflight))
            t.start()
            stop = time.monotonic() + 10.0
            while not inflight and time.monotonic() < stop:
                time.sleep(0.005)
            with post(url1 + "/admin/handoff",
                      {"successor": url2, "retry_after": 0.2}) as r:
                assert r.status == 200 and json.loads(r.read())[
                    "lame_duck"]
            with pytest.raises(urllib.error.HTTPError) as ei:
                post(url1 + "/generate", {"tokens": prompt})
            assert ei.value.code == 409
            assert float(ei.value.headers["Retry-After"]) == \
                pytest.approx(0.2)
            body = json.loads(ei.value.read())
            assert body["successor"] == url2
            with post(url2 + "/admin/promote", {}) as r:
                assert r.status == 200
                assert int(json.loads(r.read())["epoch"]) >= 2
            t.join(60.0)
            assert not t.is_alive()
            assert inflight == ref       # finished on the lame duck
            after = []
            stream(url2, after)
            assert after == ref          # the successor, bit-identical
        finally:
            front1.stop()
            front2.stop()
            standby.stop()
            primary.stop()


# -- the real thing: SIGKILL a fleet-router subprocess, restart it -----------

@pytest.mark.slow
def test_subprocess_sigkill_restart_resumes_over_http(tmp_path):
    """The whole crash story with a REAL process death: a fleet
    router subprocess is SIGKILLed mid-stream (no atexit, no close
    record — the journal tail is whatever the last group commit made
    durable), restarted on the same port over the same workspace, and
    the reconnecting HTTP client (X-Session-Id + resume_from) splices
    to the bit-identical uninterrupted sequence."""
    import signal
    import subprocess
    import sys
    import urllib.request

    import jax

    from singa_tpu.config import load_model_config
    from singa_tpu.core.trainer import Trainer
    from singa_tpu.data import discover_input_shapes
    from singa_tpu.utils.checkpoint import CheckpointManager

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    conf = os.path.join(repo, "examples/transformer/lm_tiny.conf")
    ws = str(tmp_path)
    # a blessed checkpoint so every incarnation serves the SAME
    # fingerprint (greedy decode is bit-deterministic given it)
    model = load_model_config(conf)
    shapes = discover_input_shapes(model, force_synthetic=True)
    trainer = Trainer(model, shapes, log_fn=lambda s: None)
    net = trainer.test_net or trainer.train_net
    params = net.init_params(jax.random.PRNGKey(0))
    CheckpointManager(ws, log_fn=lambda s: None).save(
        1, params, {"t": np.zeros(())}, health={"verdict": "ok"})

    port = 18533
    url = f"http://127.0.0.1:{port}"
    cmd = [sys.executable, "-m", "singa_tpu.main", "serve",
           "-model_conf", conf, "--workspace", ws,
           "--fleet", "1", "--port", str(port),
           "--serve_spec",
           "buckets=2x16,max_new_tokens=8,batch_window_s=0.005,"
           "cb=on,cb_slots=2,cb_block_len=4",
           "--fleet_spec",
           "probe_period_s=0.2,hedge=off,wal_group_tokens=2,"
           "wal_group_ms=5,state_snapshot_s=0.2"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def launch():
        return subprocess.Popen(cmd, cwd=repo, env=env,
                                stdout=subprocess.DEVNULL,
                                stderr=subprocess.DEVNULL)

    def wait_healthy(proc, secs=300.0):
        deadline = time.monotonic() + secs
        while True:
            if proc.poll() is not None:
                pytest.fail("router exited before /healthz")
            try:
                with urllib.request.urlopen(url + "/healthz",
                                            timeout=2.0) as r:
                    if r.status == 200:
                        return
            except Exception:
                pass
            if time.monotonic() > deadline:
                pytest.fail("router never became healthy")
            time.sleep(0.25)

    def stream(body):
        req = urllib.request.Request(
            url + "/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        return urllib.request.urlopen(req, timeout=120.0)

    prompt = [3, 5, 7, 11]
    proc = launch()
    try:
        wait_healthy(proc)
        # reference: one uninterrupted stream
        ref = []
        with stream({"tokens": prompt, "stream": True,
                     "max_new": 8}) as r:
            for line in r:
                ev = json.loads(line)
                if "token" in ev:
                    ref.append(ev["token"])
        assert len(ref) == 8

        # the victim stream: read 3 tokens, then SIGKILL the router
        r = stream({"tokens": prompt, "stream": True, "max_new": 8})
        sid, seen = None, []
        for line in r:
            ev = json.loads(line)
            if sid is None and "sid" in ev:
                sid = ev["sid"]
            if "token" in ev:
                seen.append(ev["token"])
            if len(seen) >= 3:
                break
        assert sid
        # let the group commit (2 tokens / 5 ms) reach the disk, then
        # kill -9: no close record, no flush-on-exit
        time.sleep(0.3)
        proc.send_signal(signal.SIGKILL)
        proc.wait(30)
        try:
            r.close()
        except Exception:
            pass

        # restart on the same port over the same workspace
        proc = launch()
        wait_healthy(proc)
        with stream({"stream": True, "session": sid,
                     "resume_from": len(seen)}) as r2:
            got = [json.loads(line) for line in r2]
        toks = [ev["token"] for ev in got if "token" in ev]
        done = [ev for ev in got if ev.get("done")][0]
        assert seen + toks == ref          # exactly-once, bit-identical
        assert done["tokens"] == ref
        assert done.get("finish") == "length"
        # the journal directory holds both epochs' WALs + state
        rdir = os.path.join(ws, "router")
        assert sorted(f for f in os.listdir(rdir)
                      if f.startswith("wal-"))[:2] == \
            ["wal-00000001.ndjson", "wal-00000002.ndjson"]
    finally:
        proc.kill()
        proc.wait(30)
