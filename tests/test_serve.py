"""Inference serving tier (singa_tpu/serve/): bucket selection and
padding, deadline expiry + shedding, hot-reload atomicity under
`serve.reload` faults, unhealthy-checkpoint reload refusal.

Correctness anchor: a request served through a padded bucket must
decode the EXACT tokens `generate()` produces unpadded — left-padding
plus the per-key kmask preserves every RoPE-relative (query, key)
distance, so the serving tier adds batching without changing the
model's output.

Cost control: compiled-program tests share one module-scoped engine
over the tiny 2-layer test LM; reload/refusal tests verify params
values and steps directly (no compiled programs needed)."""

import tempfile
import threading
import time

import jax
import numpy as np
import pytest

from singa_tpu.core.net import build_net
from singa_tpu.models.generate import generate
from singa_tpu.models.transformer import transformer_lm
from singa_tpu.serve import (DeadlineExpired, InferenceEngine,
                             InferenceServer, MicroBatcher, Overloaded,
                             ServeSpec, ServeStats)
from singa_tpu.utils.checkpoint import CheckpointManager
from singa_tpu.utils.faults import FaultError, FaultSchedule, inject

pytestmark = pytest.mark.serve

VOCAB, SEQ = 64, 16
SHAPES = {"data": {"input": (SEQ,), "target": (SEQ,)}}


def _net_and_params(seed=0):
    cfg = transformer_lm(vocab_size=VOCAB, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=SEQ,
                         batchsize=2)
    net = build_net(cfg, "kTest", SHAPES)
    return net, net.init_params(jax.random.PRNGKey(seed))


# -- ServeSpec ---------------------------------------------------------------

def test_spec_parse_grammar():
    spec = ServeSpec.parse("buckets=1x8/4x16,max_new_tokens=4,"
                           "eos_id=2;temperature=0.5,queue_capacity=9")
    assert spec.buckets == ((1, 8), (4, 16))
    assert spec.max_new_tokens == 4 and spec.eos_id == 2
    assert spec.temperature == 0.5 and spec.queue_capacity == 9
    assert ServeSpec.parse("eos_id=none").eos_id is None
    with pytest.raises(ValueError, match="unknown key"):
        ServeSpec.parse("bogus=1")
    with pytest.raises(ValueError):
        ServeSpec.parse("max_new_tokens=0")


def test_spec_bucket_selection_smallest_admissible():
    spec = ServeSpec(buckets=((1, 8), (4, 8), (2, 16), (8, 32)))
    # smallest batch that fits, shortest prompt padding
    assert spec.bucket_for(1, 5) == (1, 8)
    assert spec.bucket_for(3, 8) == (4, 8)
    assert spec.bucket_for(2, 9) == (2, 16)
    # overflow: no bucket holds 6 at plen<=8 -> widest admissible
    assert spec.bucket_for(6, 8) == (8, 32)
    assert spec.bucket_for(9, 30) == (8, 32)
    with pytest.raises(ValueError, match="exceeds every bucket"):
        spec.bucket_for(1, 33)


# -- shared compiled engine (expensive: built once) --------------------------

@pytest.fixture(scope="module")
def served():
    net, params = _net_and_params()
    spec = ServeSpec(buckets=((2, 6), (4, 12)), max_new_tokens=5,
                     batch_window_s=0.01, request_timeout_s=20.0)
    engine = InferenceEngine(net, spec, params=params,
                             log_fn=lambda s: None)
    server = InferenceServer(engine, http=False, log_fn=lambda s: None)
    server.start()
    yield net, params, engine, server
    server.stop()


def test_padded_bucket_matches_unpadded_generate(served):
    net, params, engine, server = served
    rng = np.random.default_rng(0)
    for plen in (1, 4, 9, 12):
        prompt = rng.integers(1, VOCAB, plen).astype(np.int32)
        ref = np.asarray(generate(net, params, prompt[None], 5))[0]
        out = server.generate(prompt)
        assert out["tokens"] == ref.tolist(), \
            f"plen={plen}: padded {out['tokens']} != {ref.tolist()}"


def test_concurrent_mixed_lengths_zero_recompiles(served):
    net, params, engine, server = served
    warm = engine.stats.compiles
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, VOCAB, rng.integers(1, 13)).astype(
        np.int32) for _ in range(16)]
    errs, outs = [], []

    def client(p):
        try:
            outs.append(server.generate(p))
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=client, args=(p,))
               for p in prompts]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs and len(outs) == 16
    assert engine.stats.compiles == warm, "recompiled after warmup"
    occ = engine.stats.occupancy()
    assert occ is not None and 0 < occ <= 1.0


def test_predict_mode_logprobs(served):
    net, params, engine, server = served
    out = server.predict(np.array([3, 1, 4], np.int32))
    lp = np.asarray(out["logprobs"])
    assert lp.shape == (VOCAB,)
    assert abs(float(np.exp(lp).sum()) - 1.0) < 1e-4


def test_http_frontend_roundtrip(served):
    import json
    import urllib.request

    net, params, engine, _ = served
    srv = InferenceServer(engine, port=0, log_fn=lambda s: None)
    srv.start()
    try:
        host, port = srv.address
        req = urllib.request.Request(
            f"http://{host}:{port}/generate",
            data=json.dumps({"tokens": [5, 9, 3]}).encode())
        with urllib.request.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
        assert len(out["tokens"]) == 5
        with urllib.request.urlopen(
                f"http://{host}:{port}/stats", timeout=10) as r:
            snap = json.loads(r.read())
        assert snap["completed"] >= 1 and "p50_latency_ms" in snap
    finally:
        srv.stop()


# -- admission control / deadlines (no compiled programs needed) -------------

class _StallEngine:
    """Engine stand-in whose run_batch blocks on an event — lets the
    queue fill / deadlines pass deterministically."""

    def __init__(self, spec):
        self.spec = spec
        self.stats = ServeStats()
        self.params = {"w": np.zeros(1)}
        self.params_step = 0
        self.release = threading.Event()
        self.calls = []

    def run_batch(self, mode, tokens, plens, params=None):
        self.calls.append((mode, tokens.shape, tuple(plens.tolist())))
        self.release.wait(20.0)
        if mode == "predict":
            return np.zeros((tokens.shape[0], VOCAB), np.float32)
        return np.zeros((tokens.shape[0], self.spec.max_new_tokens),
                        np.int32)


def test_queue_full_sheds_with_backoff_hint():
    spec = ServeSpec(buckets=((1, 8),), queue_capacity=2,
                     batch_window_s=0.01)
    eng = _StallEngine(spec)
    mb = MicroBatcher(eng, log_fn=lambda s: None)
    mb.start()
    try:
        first = mb.submit([1, 2])
        for _ in range(200):          # wait until it's IN FLIGHT (off
            if eng.calls:             # the queue, stalled in run_batch)
                break
            time.sleep(0.01)
        assert eng.calls, "dispatch loop never picked up the request"
        tickets = [first] + [mb.submit([1, 2]) for _ in range(2)]
        delays = []
        for _ in range(3):
            with pytest.raises(Overloaded) as ei:
                mb.submit([1, 2])
            delays.append(ei.value.retry_after)
        assert eng.stats.shed == 3
        # consecutive sheds escalate the Backoff hint
        assert delays[0] < delays[-1]
        eng.release.set()
        for t in tickets:
            t.wait(20.0)
        assert eng.stats.completed == 3
    finally:
        eng.release.set()
        mb.stop()


def test_admit_fault_sheds_request():
    spec = ServeSpec(buckets=((1, 8),))
    eng = _StallEngine(spec)
    eng.release.set()
    mb = MicroBatcher(eng, log_fn=lambda s: None)
    mb.start()
    try:
        with inject(FaultSchedule.parse("serve.admit@0:error")):
            with pytest.raises(Overloaded, match="admission fault"):
                mb.submit([1, 2])
        assert eng.stats.shed == 1 and eng.stats.submitted == 0
        mb.submit([1, 2]).wait(20.0)   # next request admitted fine
    finally:
        mb.stop()


def test_deadline_expires_in_queue():
    spec = ServeSpec(buckets=((1, 8),), batch_window_s=0.0)
    eng = _StallEngine(spec)
    mb = MicroBatcher(eng, log_fn=lambda s: None)
    mb.start()
    try:
        blocker = mb.submit([1, 2], timeout=30.0)   # occupies dispatch
        time.sleep(0.05)
        doomed = mb.submit([3, 4], timeout=0.05)    # expires queued
        time.sleep(0.2)
        eng.release.set()
        blocker.wait(20.0)
        with pytest.raises(DeadlineExpired):
            doomed.wait(20.0)
        assert eng.stats.expired == 1
    finally:
        eng.release.set()
        mb.stop()


def test_batch_fault_fails_batch_but_server_stays_up():
    spec = ServeSpec(buckets=((1, 8),))
    eng = _StallEngine(spec)
    eng.release.set()
    mb = MicroBatcher(eng, log_fn=lambda s: None)
    mb.start()
    try:
        with inject(FaultSchedule.parse("serve.batch@0:error")):
            t1 = mb.submit([1, 2])
            with pytest.raises(FaultError):
                t1.wait(20.0)
            assert eng.stats.failed == 1
            # the dispatch loop survives: the next batch serves
            mb.submit([1, 2]).wait(20.0)
        assert eng.stats.completed == 1
    finally:
        mb.stop()


def test_unservable_prompt_rejected():
    spec = ServeSpec(buckets=((2, 8),))
    eng = _StallEngine(spec)
    mb = MicroBatcher(eng, log_fn=lambda s: None)
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        mb.submit(np.arange(9))
    with pytest.raises(ValueError, match="empty"):
        mb.submit([])


# -- hot reload (real CheckpointManager, no compiled programs) ---------------

def _save(mgr, step, params, verdict="ok"):
    mgr.save(step, params, {"t": np.zeros(())},
             health={"verdict": verdict})


def test_engine_loads_latest_healthy_checkpoint():
    net, params = _net_and_params()
    p2 = jax.tree_util.tree_map(lambda a: a * 2.0, params)
    with tempfile.TemporaryDirectory() as ws:
        mgr = CheckpointManager(ws, max_to_keep=10,
                                log_fn=lambda s: None)
        _save(mgr, 1, params)
        _save(mgr, 2, p2)
        _save(mgr, 3, params, verdict="diverged")   # latest is bad
        eng = InferenceEngine(net, ServeSpec(), workspace=ws,
                              log_fn=lambda s: None)
        assert eng.load() == 2     # walked back past the unhealthy one
        k = next(iter(eng.params))
        np.testing.assert_array_equal(np.asarray(eng.params[k]),
                                      np.asarray(p2[k]))


def test_reload_swaps_refuses_and_degrades():
    net, params = _net_and_params()
    p2 = jax.tree_util.tree_map(lambda a: a * 1.5, params)
    p3 = jax.tree_util.tree_map(lambda a: a + 1.0, params)
    with tempfile.TemporaryDirectory() as ws:
        mgr = CheckpointManager(ws, max_to_keep=10,
                                log_fn=lambda s: None)
        _save(mgr, 1, params)
        eng = InferenceEngine(net, ServeSpec(), workspace=ws,
                              log_fn=lambda s: None)
        assert eng.load() == 1
        assert eng.poll_reload() == "unchanged"

        # new healthy snapshot -> swap
        _save(mgr, 2, p2)
        assert eng.poll_reload() == "reloaded"
        assert eng.params_step == 2 and eng.stats.reloads == 1

        # new UNHEALTHY snapshot -> refused, old params keep serving,
        # and the refusal is not re-attempted every poll
        _save(mgr, 3, p3, verdict="nonfinite")
        assert eng.poll_reload() == "refused"
        assert eng.params_step == 2
        assert eng.stats.reloads_refused == 1
        assert eng.poll_reload() == "unchanged"

        # injected reload fault -> degrade (counted), params unmoved...
        _save(mgr, 4, p3)
        with inject(FaultSchedule.parse("serve.reload@0:error")):
            assert eng.poll_reload() == "failed"
        assert eng.params_step == 2
        assert eng.stats.reload_failures == 1
        # ...and the very next clean poll retries and lands
        assert eng.poll_reload() == "reloaded"
        assert eng.params_step == 4
        k = next(iter(eng.params))
        np.testing.assert_array_equal(np.asarray(eng.params[k]),
                                      np.asarray(p3[k]))


def test_reload_atomicity_inflight_batch_keeps_old_params():
    """The dispatcher reads engine.params once per batch: a swap that
    lands mid-batch must not change what that batch computes with."""
    net, params = _net_and_params()
    p2 = jax.tree_util.tree_map(lambda a: a * 3.0, params)
    with tempfile.TemporaryDirectory() as ws:
        mgr = CheckpointManager(ws, max_to_keep=10,
                                log_fn=lambda s: None)
        _save(mgr, 1, params)
        eng = InferenceEngine(net, ServeSpec(), workspace=ws,
                              log_fn=lambda s: None)
        eng.load()
        captured = eng.params          # the batch's one read
        k = next(iter(captured))
        before = np.asarray(captured[k]).copy()
        _save(mgr, 2, p2)
        assert eng.poll_reload() == "reloaded"      # swap mid-flight
        # the captured tree is untouched; only the live pointer moved
        np.testing.assert_array_equal(np.asarray(captured[k]), before)
        np.testing.assert_array_equal(np.asarray(eng.params[k]),
                                      np.asarray(p2[k]))


def test_reload_rejects_mismatched_geometry():
    """A checkpoint whose params disagree in shape with the serving
    model must degrade (old params keep serving), not swap garbage in
    front of compiled programs."""
    net, params = _net_and_params()
    bad = dict(params)
    k = next(iter(bad))
    bad[k] = np.zeros(np.asarray(bad[k]).shape + (2,), np.float32)
    with tempfile.TemporaryDirectory() as ws:
        mgr = CheckpointManager(ws, max_to_keep=10,
                                log_fn=lambda s: None)
        _save(mgr, 1, params)
        eng = InferenceEngine(net, ServeSpec(), workspace=ws,
                              log_fn=lambda s: None)
        eng.load()
        _save(mgr, 2, bad)
        assert eng.poll_reload() == "failed"
        assert eng.params_step == 1
        assert eng.stats.reload_failures == 1


def test_stats_snapshot_fields():
    st = ServeStats()
    st.count("submitted", 3)
    st.observe_batch(3, 4)
    for ms in (1.0, 2.0, 100.0):
        st.observe_latency(ms / 1e3)
    snap = st.snapshot()
    assert snap["completed"] == 3
    assert snap["batch_occupancy"] == 0.75
    assert snap["p50_latency_ms"] == 2.0
    assert snap["p95_latency_ms"] == 100.0
    assert snap["qps"] > 0


# What /stats and /metrics showed before `ServeStats` read its names from
# one table (singa_tpu/serve/stats.py TALLIES), taken from that tree: the
# table is held to both, order included.
_SNAPSHOT_KEYS = (
    "submitted", "completed", "failed", "expired", "expired_on_arrival",
    "cancelled", "shed", "shed_interactive", "shed_batch",
    "shed_best_effort", "rejected", "resumed", "queue_depth",
    "generated_tokens", "batches", "batched_requests", "batch_slots",
    "cb_steps", "cb_prefills", "cb_flash_prefills", "cb_prefill_rows",
    "cb_prefill_width_rows", "cb_chunked_prompts", "cb_prefill_chunks",
    "cb_chunk_tokens", "cb_prefix_rows", "cb_steps_between_chunks",
    "cb_grouped_rows", "cb_grouped_row_slots", "cb_grouped_tile_rows",
    "cb_admit_steps", "cb_steps_ahead", "cb_collects_drained",
    "cb_stalls", "cb_stall_seconds", "cb_stall_wait_seconds",
    "cb_blocks_in_use", "cb_blocks_total", "cb_slot_state_bytes",
    "cb_block_bytes", "cb_window_block_bytes", "cb_block_copy_bytes",
    "cb_window_block_copy_bytes", "cb_ring_blocks", "cb_extent_blocks",
    "cb_live_block_steps", "cb_block_copies", "cb_window_block_steps",
    "cb_routed_layer_steps", "cb_routed_assignments",
    "cb_routed_experts_touched", "cb_routed_max_load",
    "cb_emit_slot_steps", "cb_tokens_emitted", "cb_drafts_made",
    "cb_drafts_accepted", "consecutive_batch_failures", "compiles",
    "reloads", "reload_failures", "reloads_refused", "torn_polls",
    "reload_poll_deaths", "qps", "qps_recent", "shed_rate_recent",
    "p95_latency_recent_ms", "p99_latency_recent_ms", "uptime_s",
    "p50_latency_ms", "p95_latency_ms", "p99_latency_ms",
    "p50_queue_wait_ms", "p95_queue_wait_ms", "p50_service_ms",
    "p95_service_ms", "p50_ttft_ms", "p95_ttft_ms", "p50_tokens_per_s",
    "p95_tokens_per_s", "batch_occupancy", "cb_slot_occupancy",
    "cb_slot_occupancy_recent", "cb_block_utilization",
    "cb_live_block_share", "cb_prefill_fill_share",
    "cb_window_block_share", "by_tenant")
_METRIC_COUNTERS = (
    "submitted", "completed", "failed", "expired", "expired_on_arrival",
    "cancelled", "shed", "shed_interactive", "shed_batch",
    "shed_best_effort", "rejected", "resumed", "generated_tokens",
    "batches", "batched_requests", "batch_slots", "cb_steps",
    "cb_prefills", "cb_flash_prefills", "cb_prefill_rows",
    "cb_prefill_width_rows", "cb_chunked_prompts", "cb_prefill_chunks",
    "cb_chunk_tokens", "cb_prefix_rows", "cb_steps_between_chunks",
    "cb_grouped_rows", "cb_grouped_row_slots", "cb_grouped_tile_rows",
    "cb_admit_steps", "cb_steps_ahead", "cb_collects_drained",
    "cb_stalls", "cb_stall_seconds", "cb_stall_wait_seconds",
    "cb_live_block_steps", "cb_block_copies", "cb_window_block_steps",
    "cb_routed_layer_steps", "cb_routed_assignments",
    "cb_routed_experts_touched", "cb_routed_max_load",
    "cb_emit_slot_steps", "cb_tokens_emitted", "cb_drafts_made",
    "cb_drafts_accepted", "compiles", "reloads", "reload_failures",
    "reloads_refused", "torn_polls", "reload_poll_deaths")
_METRIC_GAUGES = (
    "queue_depth", "consecutive_batch_failures", "qps", "qps_recent",
    "uptime_s", "p50_latency_ms", "p95_latency_ms", "p99_latency_ms",
    "shed_rate_recent", "p95_latency_recent_ms",
    "p99_latency_recent_ms", "p50_queue_wait_ms", "p95_queue_wait_ms",
    "p50_service_ms", "p95_service_ms", "p50_ttft_ms", "p95_ttft_ms",
    "p50_tokens_per_s", "p95_tokens_per_s", "batch_occupancy",
    "cb_slot_occupancy", "cb_slot_occupancy_recent",
    "cb_block_utilization", "cb_live_block_share",
    "cb_prefill_fill_share", "cb_blocks_in_use", "cb_blocks_total",
    "cb_slot_state_bytes", "cb_block_bytes", "cb_window_block_bytes",
    "cb_ring_blocks", "cb_extent_blocks", "cb_block_copy_bytes",
    "cb_window_block_copy_bytes", "cb_window_block_share")
_METRIC_SAMPLES = (
    [("singa_serve_request_latency_seconds", "histogram",
      "end-to-end request latency on this engine"),
     ("singa_serve_queue_wait_seconds", "histogram",
      "time queued before dispatch/admission"),
     ("singa_serve_service_seconds", "histogram",
      "time being served after dispatch"),
     ("singa_serve_ttft_seconds", "histogram",
      "submit to first token (continuous batching)")]
    + [(f"singa_serve_{k}_total", "counter", f"serving counter {k!r}")
       for k in _METRIC_COUNTERS]
    + [(f"singa_serve_{k}", "gauge", f"serving gauge {k!r}")
       for k in _METRIC_GAUGES]
    + [(f"singa_tenant_{k}_total", "counter", f"per-tenant counter {k!r}")
       for k in ("submitted", "completed", "shed")])


def _driven_stats():
    """A `ServeStats` every gauge of which has a value (a gauge that is
    None is left out of /metrics), and the registry it registered into."""
    from singa_tpu.obs import MetricsRegistry
    st, registry = ServeStats(), MetricsRegistry()
    st.register_into(registry)
    for field, value in (("cb_slot_capacity", 4), ("cb_table_blocks", 32),
                         ("cb_blocks_total", 64), ("cb_ring_blocks", 3)):
        st.gauge(field, value)
    st.count("shed")
    st.observe_batch(3, 4)
    st.observe_latency(0.25)
    st.observe_request(0.125, 0.375, 12)
    st.observe_ttft(0.03125)
    st.observe_cb_prefill(100, 256, flash=True)
    st.observe_cb_step(3, 7, live_blocks=11, window_blocks=5, copies=6)
    st.tenants.count("submitted", "acme")
    return st, registry


def _metric_heads(registry):
    """(name, type, help) of every /metrics sample, as the endpoint
    lists them."""
    heads, helps = [], {}
    for line in registry.render_prometheus().splitlines():
        if line.startswith("# HELP "):
            name, text = line[len("# HELP "):].split(" ", 1)
            helps[name] = text
        elif line.startswith("# TYPE "):
            name, kind = line[len("# TYPE "):].split(" ", 1)
            heads.append((name, kind, helps.get(name, "")))
    return heads


def test_stats_surfaces_are_what_they_were():
    assert tuple(ServeStats().snapshot()) == _SNAPSHOT_KEYS
    st, registry = _driven_stats()
    assert tuple(st.snapshot()) == _SNAPSHOT_KEYS
    assert _metric_heads(registry) == _METRIC_SAMPLES


@pytest.mark.parametrize("kind", ["counter", "gauge", "internal", "derived"])
def test_stats_table_says_where_each_name_shows(kind):
    """Every name of the table is where its kind says, and nothing is
    there beside the table: an attribute `__init__` zeroes, a key of
    `snapshot()`, a /metrics sample of that type."""
    from singa_tpu.serve.stats import TALLIES
    declared = [t.name for t in TALLIES if t.kind == kind]
    assert len(set(declared)) == len(declared)
    assert all(t.meaning for t in TALLIES)
    attrs = {k for k, v in vars(ServeStats()).items()
             if not k.startswith("_") and isinstance(v, (int, float))
             } - {"qps_window_s"}
    st, registry = _driven_stats()
    keys = set(st.snapshot()) - {"by_tenant"}
    types = {name: mtype for name, mtype, _ in _metric_heads(registry)
             if name.startswith("singa_serve_") and mtype != "histogram"}
    counters = {k for k in attrs | keys
                if types.get(f"singa_serve_{k}_total") == "counter"}
    gauges = {k for k in attrs | keys
              if types.get(f"singa_serve_{k}") == "gauge"}
    assert len(counters) + len(gauges) == len(types)
    found = {"counter": counters & attrs & keys,
             "gauge": gauges & attrs & keys,
             "internal": attrs - keys - counters - gauges,
             "derived": (keys - attrs) & gauges}[kind]
    assert found == set(declared)
    assert attrs | keys == {t.name for t in TALLIES}
