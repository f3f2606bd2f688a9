"""Zero-copy binary transport (serve/wire.py): frame-codec roundtrip
and fuzz hardening, TokenRing semantics, live binary server + handle
bit-identity against HTTP, transport negotiation with automatic HTTP
fallback, mixed binary+HTTP fleets with cross-boundary failover, the
`wire.frame` fault site, and the HttpEngineHandle keep-alive
regression.  Select with `-m wire`."""

import json
import socket
import tempfile
import threading
import time

import numpy as np
import pytest

from singa_tpu.serve import wire
from singa_tpu.serve.wire import (
    BinaryEngineHandle, BinaryTransportServer, FrameReader,
    LineCoalescer, NegotiatingEngineHandle, TokenRing, WireError,
    WireStats, K_DONE, K_ERR, K_HELLO, K_REQ,
    K_RESULT, K_TOKENS, K_CANCEL, MAGIC, VERSION, OP_GENERATE,
    OP_STREAM, decode_error, decode_qos_header, decode_request,
    decode_tokens, encode_error, encode_qos_header, encode_request,
    frame_parts, send_frame, token_frame_parts)

pytestmark = pytest.mark.wire


# -- codec roundtrip (property-style, every frame kind) ----------------------

def _loop_frame(kind, req_id, header=b"", payload_parts=(),
                stats=None):
    """Encode a frame through a real socketpair and decode it back."""
    a, b = socket.socketpair()
    try:
        st = stats or WireStats()
        send_frame(a, threading.Lock(), kind, req_id, header,
                   payload_parts, stats=st)
        a.close()
        return FrameReader(b, stats=st).read_frame()
    finally:
        b.close()


def test_qos_header_roundtrip_all_fields():
    deadline = time.monotonic() + 12.0
    h = encode_qos_header(deadline=deadline, priority="batch",
                          tenant="acme", trace=("tr-77", 12345),
                          sid="s3-9", resume_from=41)
    d = decode_qos_header(h)
    assert d["priority"] == "batch"
    assert d["tenant"] == "acme"
    assert d["trace"] == ("tr-77", 12345)
    assert d["sid"] == "s3-9"
    assert d["resume_from"] == 41
    # remaining-ms re-anchoring: same clock here, so within ~1s
    assert abs(d["deadline"] - deadline) < 1.0


def test_qos_header_roundtrip_empty():
    d = decode_qos_header(encode_qos_header())
    assert d["deadline"] is None and d["priority"] is None
    assert d["trace"] is None and d["sid"] is None
    assert d["resume_from"] == 0
    assert d["tenant"] == "default"      # check_tenant folds None


def test_request_payload_roundtrip():
    rng = np.random.default_rng(7)
    for _ in range(50):
        toks = rng.integers(0, 1 << 30,
                            int(rng.integers(0, 64))).astype(np.int32)
        p = encode_request(OP_STREAM, toks,
                           timeout=float(rng.random() * 10),
                           max_new=int(rng.integers(1, 100)))
        d = decode_request(p)
        assert d["mode"] == "stream"
        np.testing.assert_array_equal(d["tokens"], toks)
    d = decode_request(encode_request(OP_GENERATE, None))
    assert d["timeout"] is None and d["max_new"] is None
    assert d["step"] is None and d["tokens"].size == 0


def test_every_frame_kind_roundtrips_over_a_socket():
    rng = np.random.default_rng(13)
    cases = [
        (K_HELLO, b"", []),
        (K_REQ, encode_qos_header(priority="interactive", sid="s1-1"),
         [encode_request(OP_GENERATE, [1, 2, 3], timeout=2.0)]),
        (K_RESULT, b"", [json.dumps({"tokens": [4, 5]}).encode()]),
        (K_TOKENS, b"",
         token_frame_parts(9,
                           rng.integers(0, 99, 17).astype(np.int32))),
        (K_DONE, b"", [json.dumps({"done": True}).encode()]),
        (K_ERR, b"", [encode_error(wire.E_OVERLOADED, "busy", 0.5)]),
        (K_CANCEL, b"", []),
    ]
    for kind, header, parts in cases:
        got = _loop_frame(kind, 42, header, parts)
        assert got is not None
        gk, _flags, req_id, ghdr, gpayload = got
        assert gk == kind and req_id == 42
        assert ghdr == bytes(header)
        assert gpayload == b"".join(bytes(p) for p in parts)
    # the TOKENS payload decodes back to the identical int32 array
    first_i, arr = decode_tokens(
        b"".join(bytes(p) for p in
                 token_frame_parts(3, np.arange(8, dtype=np.int32))))
    assert first_i == 3
    np.testing.assert_array_equal(arr, np.arange(8, dtype=np.int32))


def test_error_payload_roundtrip():
    code, ra, msg = decode_error(
        encode_error(wire.E_DEADLINE, "too late", 2.25))
    assert code == wire.E_DEADLINE and ra == 2.25 and msg == "too late"


# -- fuzz hardening: malformed input is a counted close, never a hang --------

def _whole_request_frame() -> bytes:
    return b"".join(bytes(p) for p in frame_parts(
        K_REQ, 7, encode_qos_header(tenant="t"),
        [encode_request(OP_GENERATE, [1, 2, 3])]))


def _read_with_stats(raw: bytes):
    """Feed raw bytes to a FrameReader over a socketpair; return
    (result_or_exception, stats)."""
    a, b = socket.socketpair()
    st = WireStats()
    try:
        a.sendall(raw)
        a.close()
        b.settimeout(5.0)               # a hang fails the test, fast
        r = FrameReader(b, stats=st)
        try:
            return r.read_frame(), st
        except WireError as e:
            return e, st
    finally:
        b.close()


def test_garbage_magic_is_counted_malformed():
    out, st = _read_with_stats(b"XX" + b"\x00" * 14)
    assert isinstance(out, WireError)
    assert st.snapshot()["malformed"] == 1


def test_version_skew_is_counted_malformed():
    pre = wire._PREAMBLE.pack(MAGIC, VERSION + 1, K_HELLO, 0, 0, 1,
                              0, 0)
    out, st = _read_with_stats(pre)
    assert isinstance(out, WireError) and "version skew" in str(out)
    assert st.snapshot()["malformed"] == 1


def test_oversized_length_prefix_is_rejected_not_allocated():
    # a hostile payload_len must be rejected from the PREFIX — the
    # reader must not try to read (or allocate) 64 MiB+
    pre = wire._PREAMBLE.pack(MAGIC, VERSION, K_REQ, 0, 0, 1, 0,
                              wire.MAX_PAYLOAD_LEN + 1)
    out, st = _read_with_stats(pre)
    assert isinstance(out, WireError) and "oversized" in str(out)
    assert st.snapshot()["malformed"] == 1
    pre = wire._PREAMBLE.pack(MAGIC, VERSION, K_REQ, 0, 0, 1,
                              wire.MAX_HEADER_LEN + 1, 0)
    out, _ = _read_with_stats(pre)
    assert isinstance(out, WireError)


def test_truncated_frames_every_cut_point():
    """EOF at any offset inside a frame is a counted malformed close —
    never a hang, never a crash.  (EOF exactly at a frame boundary is
    the one clean shutdown.)"""
    whole = _whole_request_frame()
    clean, st = _read_with_stats(b"")
    assert clean is None and st.snapshot()["malformed"] == 0
    for cut in range(1, len(whole)):
        out, st = _read_with_stats(whole[:cut])
        assert isinstance(out, WireError), f"cut at {cut}: {out!r}"
        assert st.snapshot()["malformed"] == 1


def test_random_garbage_never_hangs_or_crashes():
    rng = np.random.default_rng(99)
    for _ in range(200):
        raw = rng.integers(0, 256,
                           int(rng.integers(1, 64))).astype(np.uint8)
        out, _ = _read_with_stats(raw.tobytes())
        assert out is None or isinstance(out, WireError)


def test_unknown_frame_kind_is_malformed():
    pre = wire._PREAMBLE.pack(MAGIC, VERSION, 200, 0, 0, 1, 0, 0)
    out, st = _read_with_stats(pre)
    assert isinstance(out, WireError)
    assert st.snapshot()["malformed"] == 1


# -- TokenRing ---------------------------------------------------------------

def test_token_ring_push_peek_consume_wraparound():
    ring = TokenRing(capacity=8)
    out = []
    ring.push_many([1, 2, 3, 4, 5])
    kind, start, view = ring.peek_batch(64)
    assert kind == "toks" and start == 0
    out.extend(int(t) for t in view)
    ring.consume(len(view))
    # wrap: head at 5, push 6 more — peek returns the CONTIGUOUS run
    # to the buffer end first, then the wrapped remainder
    ring.push_many([6, 7, 8, 9, 10, 11])
    while len(ring):
        _k, _s, view = ring.peek_batch(64)
        out.extend(int(t) for t in view)
        ring.consume(len(view))
    assert out == list(range(1, 12))


def test_token_ring_blocks_producer_until_consumed():
    ring = TokenRing(capacity=4)
    ring.push_many([1, 2, 3, 4])
    with pytest.raises(TimeoutError):
        ring.push_many([5], timeout=0.05)
    done = []

    def producer():
        ring.push_many([5, 6], timeout=5.0)
        done.append(True)

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    _k, _s, view = ring.peek_batch(2)
    ring.consume(len(view))
    t.join(5.0)
    assert done == [True]


def test_token_ring_terminal_and_error():
    ring = TokenRing(capacity=4)
    ring.push_many([7])
    ring.finish({"finish": "eos"})
    k, _s, view = ring.peek_batch(8)
    assert k == "toks" and list(view) == [7]
    ring.consume(1)
    assert ring.peek_batch(8) == ("done", {"finish": "eos"})
    with pytest.raises(RuntimeError):
        ring.push_many([8])
    ring2 = TokenRing(capacity=4)
    ring2.fail(RuntimeError("slot died"))
    with pytest.raises(RuntimeError, match="slot died"):
        ring2.peek_batch(8)
    with pytest.raises(TimeoutError):
        TokenRing(capacity=4).peek_batch(8, timeout=0.05)


# -- LineCoalescer -----------------------------------------------------------

def test_line_coalescer_first_line_flushes_alone():
    writes = []
    co = LineCoalescer(writes.append, flush_tokens=4, flush_ms=1e4,
                       stats=WireStats())
    co.add(b"a\n")
    assert writes == [b"a\n"]           # first line: immediate
    co.add(b"b\n")
    co.add(b"c\n")
    assert writes == [b"a\n"]           # batching engaged
    co.add(b"d\n")
    co.add(b"e\n")
    assert writes == [b"a\n", b"b\nc\nd\ne\n"]  # count flush at 4
    co.add(b"f\n")
    co.add(b"g\n", urgent=True)         # terminal: flush now
    assert writes[-1] == b"f\ng\n"


# -- live engine fixtures ----------------------------------------------------

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


def _make_server(tiny_lm, wire_on=True):
    from singa_tpu.serve import (InferenceEngine, InferenceServer,
                                 ServeSpec)

    net, params, seq = tiny_lm
    spec = ServeSpec(buckets=((2, seq),), max_new_tokens=8,
                     batch_window_s=0.002, request_timeout_s=60.0,
                     cb="on", cb_slots=3, cb_block_len=4)
    eng = InferenceEngine(net, spec, params=params,
                          log_fn=lambda s: None)
    srv = InferenceServer(eng, port=0, wire_on=wire_on,
                          log_fn=lambda s: None)
    srv.start()
    return srv


@pytest.fixture(scope="module")
def wire_server(tiny_lm):
    """One shared live server (cb=on, wire on) for the tests that
    leave it intact; tests that stop listeners build their own."""
    srv = _make_server(tiny_lm, wire_on=True)
    yield srv
    srv.stop()


# -- binary server + handle over a real engine -------------------------------

def test_binary_stream_bit_identical_to_http(wire_server):
    from singa_tpu.serve import HttpEngineHandle

    host, port = wire_server.address
    prompt = np.arange(1, 5, dtype=np.int32)
    hh = HttpEngineHandle("e0", f"http://{host}:{port}")
    bh = BinaryEngineHandle("e0", wire_server.wire_address)
    try:
        u1 = hh.request("generate", prompt, timeout=30)
        u2 = bh.request("generate", prompt, timeout=30)
        assert u1["tokens"] == u2["tokens"]
        s1 = list(hh.request_stream(prompt, timeout=30, max_new=8))
        s2 = list(bh.request_stream(prompt, timeout=30, max_new=8))
        t1 = [ev["token"] for ev in s1 if "done" not in ev]
        t2 = [ev["token"] for ev in s2 if "done" not in ev]
        assert t1 == t2 == u1["tokens"]
        assert [ev["i"] for ev in s2 if "done" not in ev] == \
            list(range(8))
        assert s1[-1]["done"] and s2[-1]["done"]
        assert s1[-1]["finish"] == s2[-1]["finish"]
    finally:
        hh.close()
        bh.close()


def test_binary_multiplexes_streams_on_one_connection(wire_server):
    """Two concurrent streams ride ONE persistent socket (req_id
    demux) — and an early-closed stream cancels server-side without
    killing its neighbor."""
    bh = BinaryEngineHandle("e0", wire_server.wire_address)
    prompt = np.arange(1, 5, dtype=np.int32)
    try:
        g1 = bh.request_stream(prompt, timeout=30, max_new=8)
        g2 = bh.request_stream(prompt, timeout=30, max_new=8)
        first1 = next(g1)
        first2 = next(g2)
        assert first1["token"] == first2["token"]
        g1.close()                       # hedge-loser path: CANCEL
        rest = list(g2)
        assert rest[-1]["done"]
        assert bh._conn is not None and bh._conn.alive
    finally:
        bh.close()


def test_binary_error_mapping_admission(wire_server):
    bh = BinaryEngineHandle("e0", wire_server.wire_address)
    try:
        with pytest.raises(ValueError):
            bh.request("generate",
                       np.arange(100, dtype=np.int32), timeout=5)
        gen = bh.request_stream(np.arange(100, dtype=np.int32),
                                timeout=5)
        with pytest.raises(ValueError):
            next(gen)
    finally:
        bh.close()


def _live_fuzz(case):
    """The byte strings of one class of wrong framing."""
    if case == "truncated-frame":
        whole = _whole_request_frame()
        return [whole[:cut] for cut in range(1, len(whole), 7)]
    if case == "random-bytes":
        rng = np.random.default_rng(11)
        return [rng.integers(0, 256, int(rng.integers(1, 48)))
                .astype(np.uint8).tobytes() for _ in range(25)]
    return [{
        "http-request": b"GET / HTTP/1.1\r\n\r\n",
        "garbage-magic": b"XX" + b"\x00" * 14,
        "version-skew": wire._PREAMBLE.pack(
            MAGIC, VERSION + 1, K_HELLO, 0, 0, 1, 0, 0),
        "oversized-prefix": wire._PREAMBLE.pack(
            MAGIC, VERSION, K_REQ, 0, 0, 1, 0, wire.MAX_PAYLOAD_LEN + 1),
    }[case]]


#: the classes whose bytes stop inside a frame; the others hold a whole
#: preamble, and the server closes on it while the client's side of the
#: socket stays open
_CUT_SHORT = ("truncated-frame", "random-bytes")


@pytest.mark.parametrize("case", [
    "http-request", "garbage-magic", "version-skew", "oversized-prefix",
    "truncated-frame", "random-bytes"])
def test_malformed_bytes_close_a_live_server_connection(wire_server,
                                                        case):
    """A client that frames wrong — another protocol, a bad magic, a
    skewed version, a hostile length prefix, a frame cut short, noise
    — gets its connection closed within the timeout (counted, never a
    hang) and the listener keeps serving other connections."""
    raws = _live_fuzz(case)
    before = wire.STATS.snapshot()["malformed"]
    for raw in raws:
        s = socket.create_connection(wire_server.wire_address,
                                     timeout=5.0)
        try:
            s.sendall(raw)
            s.settimeout(5.0)
            if case in _CUT_SHORT:
                # an incomplete frame is only known to be one when
                # no more bytes can follow
                s.shutdown(socket.SHUT_WR)
            assert s.recv(64) == b""          # closed, not hung
        finally:
            s.close()
    assert wire.STATS.snapshot()["malformed"] - before == len(raws)
    # the listener survives: a well-formed client still works
    h = BinaryEngineHandle("e0", wire_server.wire_address)
    try:
        assert h.probe()["ok"]
    finally:
        h.close()


def test_binary_handle_reconnects_after_listener_restart(tiny_lm):
    from singa_tpu.serve.router import EngineUnavailable

    srv = _make_server(tiny_lm, wire_on=True)
    bh = BinaryEngineHandle("e0", srv.wire_address)
    try:
        assert bh.probe()["ok"]
        before = wire.STATS.snapshot()["reconnects"]
        srv._wire.stop()
        with pytest.raises(EngineUnavailable):
            bh.probe()
        srv._wire = BinaryTransportServer(
            srv, log_fn=lambda s: None).start()
        bh.address = srv.wire_address
        assert bh.probe()["ok"]
        assert wire.STATS.snapshot()["reconnects"] > before
    finally:
        bh.close()
        srv.stop()


# -- transport negotiation + fallback ----------------------------------------

def test_negotiation_upgrades_and_falls_back(tiny_lm):
    srv = _make_server(tiny_lm, wire_on=True)
    host, port = srv.address
    nh = NegotiatingEngineHandle("e0", f"http://{host}:{port}",
                                 log_fn=lambda s: None)
    prompt = np.arange(1, 5, dtype=np.int32)
    try:
        assert nh.transport == "http"    # before any probe
        h = nh.probe()
        assert h["transport"] == "binary" and h["wire_port"]
        ref = nh.request("generate", prompt, timeout=30)["tokens"]

        # kill ONLY the wire listener: the next binary attempt falls
        # back to HTTP in the SAME call — zero client-visible failures
        srv._wire.stop()
        srv._wire = None
        before = wire.STATS.snapshot()["fallbacks"]
        out = nh.request("generate", prompt, timeout=30)
        assert out["tokens"] == ref
        assert nh.transport == "http"
        assert wire.STATS.snapshot()["fallbacks"] == before + 1
        # ... and the stream path re-admits over HTTP the same way
        toks = [ev["token"]
                for ev in nh.request_stream(prompt, timeout=30,
                                            max_new=8)
                if "done" not in ev]
        assert toks == ref

        # the next probe is the re-discovery point
        srv._wire = BinaryTransportServer(
            srv, log_fn=lambda s: None).start()
        nh.probe()
        assert nh.transport == "binary"
        assert nh.request("generate", prompt,
                          timeout=30)["tokens"] == ref
    finally:
        nh.close()
        srv.stop()


def test_healthz_advertises_wire_port_only_when_listening(
        wire_server, tiny_lm):
    import urllib.request

    host, port = wire_server.address
    h = json.loads(urllib.request.urlopen(
        f"http://{host}:{port}/healthz", timeout=5).read())
    wa = wire_server.wire_address
    assert wa is not None and h["wire_port"] == wa[1]
    srv2 = _make_server(tiny_lm, wire_on=False)
    try:
        h2, p2 = srv2.address
        got = json.loads(urllib.request.urlopen(
            f"http://{h2}:{p2}/healthz", timeout=5).read())
        assert "wire_port" not in got
    finally:
        srv2.stop()


# -- mixed fleet: route / failover across the transport boundary -------------

def _adopted_fleet(urls, ws):
    from singa_tpu.serve import EngineFleet, RouterSpec

    rspec = RouterSpec(probe_period_s=0.1, hedge="off",
                       request_timeout_s=60.0, wal_group_tokens=4,
                       wal_group_ms=5.0, state_snapshot_s=0.1)
    return EngineFleet.adopt(urls, workspace=ws, router_spec=rspec,
                             log_fn=lambda s: None)


def _wait_transport(fleet, name, want, budget=10.0):
    deadline = time.monotonic() + budget
    h = fleet.router.handle_for(name)
    while time.monotonic() < deadline and h.transport != want:
        time.sleep(0.05)
    return h.transport


def test_mixed_fleet_failover_crosses_transport_boundary(tiny_lm):
    """A fleet mixing a binary-capable engine and an HTTP-only engine
    routes across the boundary, and a mid-stream kill of the binary
    engine splices the stream exactly-once onto the HTTP-only sibling
    via the session machinery — the final token sequence is
    BIT-IDENTICAL to an uninterrupted reference."""
    from singa_tpu.utils.checkpoint import CheckpointManager

    net, params, seq = tiny_lm
    a = _make_server(tiny_lm, wire_on=True)     # binary-capable
    b = _make_server(tiny_lm, wire_on=False)    # HTTP-only
    with tempfile.TemporaryDirectory() as ws:
        CheckpointManager(ws, log_fn=lambda s: None).save(
            1, params, {"t": np.zeros(())}, health={"verdict": "ok"})
        urls = [f"http://{h}:{p}" for h, p in (a.address, b.address)]
        fleet = _adopted_fleet(urls, ws)
        try:
            fleet.start()
            assert _wait_transport(fleet, "engine-0",
                                   "binary") == "binary"
            assert fleet.router.handle_for("engine-1").transport == \
                "http"

            prompt = np.arange(1, 5, dtype=np.int32)
            ref = [ev["token"]
                   for ev in fleet.generate_stream(prompt, max_new=8)
                   if "token" in ev]
            assert len(ref) == 8

            # unary traffic crosses the boundary freely: concurrent
            # requests spread over BOTH transports (sequential calls
            # would all land on the least-loaded tie winner)
            outs = []

            def one():
                outs.append(fleet.generate(prompt)["engine"])

            threads = [threading.Thread(target=one)
                       for _ in range(12)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30.0)
            assert len(outs) == 12       # every request succeeded
            assert set(outs) <= {"engine-0", "engine-1"}

            # mid-stream kill of the binary worker: the session layer
            # must splice the remainder from the HTTP-only sibling
            stream = fleet.generate_stream(prompt, max_new=8)
            seen, killed = [], False
            for ev in stream:
                if "token" in ev:
                    seen.append(ev["token"])
                if len(seen) == 3 and not killed:
                    killed = True
                    a.stop()             # the whole binary worker
            assert seen == ref           # exactly once, bit-identical
        finally:
            fleet.stop()
            b.stop()
            try:
                a.stop()
            except Exception:  # noqa: BLE001 — may already be down
                pass


def test_wire_listener_death_does_not_lose_inflight_stream(tiny_lm):
    """The binary listener of an engine dies mid-stream (the worker
    and its HTTP surface stay up): the stream's wire break feeds the
    router's failover machinery, the transport degrades to HTTP, and
    the client sees every token exactly once."""
    from singa_tpu.utils.checkpoint import CheckpointManager

    net, params, seq = tiny_lm
    a = _make_server(tiny_lm, wire_on=True)
    b = _make_server(tiny_lm, wire_on=False)
    with tempfile.TemporaryDirectory() as ws:
        CheckpointManager(ws, log_fn=lambda s: None).save(
            1, params, {"t": np.zeros(())}, health={"verdict": "ok"})
        urls = [f"http://{h}:{p}" for h, p in (a.address, b.address)]
        fleet = _adopted_fleet(urls, ws)
        try:
            fleet.start()
            assert _wait_transport(fleet, "engine-0",
                                   "binary") == "binary"
            prompt = np.arange(1, 5, dtype=np.int32)
            ref = [ev["token"]
                   for ev in fleet.generate_stream(prompt, max_new=8)
                   if "token" in ev]

            h0 = fleet.router.handle_for("engine-0")
            # a step slow enough that the stream is still in flight
            # when the listener dies (8 steps take a few ms otherwise,
            # and whether any were left was a matter of timing)
            a.engine.set_stall(0.02)
            stream = fleet.generate_stream(prompt, max_new=8)
            seen, killed = [], False
            for ev in stream:
                if "token" in ev:
                    seen.append(ev["token"])
                if len(seen) == 2 and not killed:
                    killed = True
                    a._wire.stop()       # ONLY the binary listener
                    a._wire = None
            assert seen == ref           # exactly once, no loss
            # engine-0's data plane degraded to HTTP (its worker and
            # debug surface never went away)
            assert h0.transport == "http"
        finally:
            fleet.stop()
            b.stop()
            a.stop()


# -- wire.frame fault site ---------------------------------------------------

def test_wire_frame_fault_degrades_to_http_not_failure(tiny_lm):
    """An injected frame drop / corruption / tear on the binary path
    is a counted transport failure the negotiating handle absorbs by
    falling back to HTTP — never a client-visible error, never a
    hang."""
    from singa_tpu.utils.faults import FaultSchedule, inject

    srv = _make_server(tiny_lm, wire_on=True)
    host, port = srv.address
    prompt = np.arange(1, 5, dtype=np.int32)
    try:
        for kind in ("error", "corrupt", "torn"):
            nh = NegotiatingEngineHandle(
                "e0", f"http://{host}:{port}", connect_timeout_s=3.0,
                log_fn=lambda s: None)
            try:
                nh.probe()
                assert nh.transport == "binary"
                before = wire.STATS.snapshot()["faulted_frames"]
                with inject(
                        FaultSchedule.parse(f"wire.frame@0:{kind}")):
                    out = nh.request("generate", prompt, timeout=30)
                assert len(out["tokens"]) == 8, kind
                assert wire.STATS.snapshot()["faulted_frames"] > \
                    before, kind
            finally:
                nh.close()
    finally:
        srv.stop()


def test_wire_frame_corrupt_counts_malformed_at_receiver(tiny_lm):
    """A corrupted outbound frame (flipped magic) must be counted
    `wire_malformed_total` by the RECEIVER and close that connection
    — the honest-error contract of the fuzz satellite, on a live
    server."""
    from singa_tpu.utils.faults import FaultSchedule, inject

    srv = _make_server(tiny_lm, wire_on=True)
    before = wire.STATS.snapshot()["malformed"]
    try:
        with inject(FaultSchedule.parse("wire.frame@0:corrupt")):
            with pytest.raises(Exception):
                # HELLO goes out corrupted -> server counts + closes
                # -> handshake fails
                BinaryEngineHandle("e0", srv.wire_address,
                                   connect_timeout_s=3.0).probe()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and \
                wire.STATS.snapshot()["malformed"] <= before:
            time.sleep(0.02)
        assert wire.STATS.snapshot()["malformed"] > before
    finally:
        srv.stop()


# -- satellite: HttpEngineHandle keep-alive reuse ----------------------------

def _stub_http(handler_cls, server_cls=None):
    from http.server import ThreadingHTTPServer

    cls = server_cls or ThreadingHTTPServer
    httpd = cls(("127.0.0.1", 0), handler_cls)
    httpd.daemon_threads = True
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd


def test_http_handle_keepalive_reuses_one_socket():
    """N sequential unary calls and probes ride ONE TCP connection —
    per-request connection setup is off the hot path.  The stub
    server counts accepted connections; an error reply must NOT
    poison the pooled socket (the body is drained, keep-alive
    holds)."""
    from http.server import (BaseHTTPRequestHandler,
                             ThreadingHTTPServer)

    from singa_tpu.serve.batcher import Overloaded
    from singa_tpu.serve.router import HttpEngineHandle

    conns = []

    class CountingServer(ThreadingHTTPServer):
        def process_request(self, request, client_address):
            conns.append(client_address)
            super().process_request(request, client_address)

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
            self._json(200, {"ok": True, "status": "ok", "step": 1,
                             "queue_depth": 0})

        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            self.rfile.read(n)
            self._json(503, {"error": "overloaded",
                             "retry_after": 0.1})

    httpd = _stub_http(H, CountingServer)
    h = HttpEngineHandle(
        "e0", f"http://127.0.0.1:{httpd.server_address[1]}")
    try:
        for _ in range(10):
            h.probe()                    # 2 GETs each
        for _ in range(10):
            with pytest.raises(Overloaded):
                h.request("generate", [1, 2])   # 503 + drained body
        for _ in range(10):
            h.stats_snapshot()
        assert len(conns) == 1, \
            f"expected ONE reused connection, server saw {len(conns)}"
    finally:
        h.close()
        httpd.shutdown()
        httpd.server_close()


def test_http_handle_keepalive_survives_peer_close():
    """A peer that closes after every reply (Connection: close) must
    not poison the pool or surface errors — the handle detects the
    non-reusable exchange and never pools that socket."""
    from http.server import BaseHTTPRequestHandler

    from singa_tpu.serve.router import HttpEngineHandle

    class H(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):
            pass

        def do_GET(self):
            body = json.dumps({"ok": True, "status": "ok",
                               "step": 1, "queue_depth": 0}).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.send_header("Connection", "close")
            self.end_headers()
            self.wfile.write(body)
            self.close_connection = True

    httpd = _stub_http(H)
    h = HttpEngineHandle(
        "e0", f"http://127.0.0.1:{httpd.server_address[1]}")
    try:
        for _ in range(5):
            assert h.stats_snapshot()["ok"]
        assert len(h._pool) == 0         # close-announced: not pooled
    finally:
        h.close()
        httpd.shutdown()
        httpd.server_close()


def test_http_handle_pool_is_bounded():
    """Pooled sockets are capped at POOL_CAP — a concurrent burst
    must not grow an unbounded fd set."""
    from http.server import BaseHTTPRequestHandler

    from singa_tpu.serve.router import HttpEngineHandle

    class H(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):
            pass

        def do_GET(self):
            body = b'{"ok": true}'
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = _stub_http(H)
    h = HttpEngineHandle(
        "e0", f"http://127.0.0.1:{httpd.server_address[1]}")
    try:
        threads = [threading.Thread(
            target=lambda: h._call("GET", "/healthz"))
            for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10.0)
        assert len(h._pool) <= h.POOL_CAP
    finally:
        h.close()
        httpd.shutdown()
        httpd.server_close()
