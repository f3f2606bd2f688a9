"""Performance observatory tests (ISSUE 15): CompileWatch counting
and hit/miss labeling, the recompile-anomaly event + flight-recorder
trigger (with cooldown), MemoryWatch's analytic fallback arithmetic
against a known KV-pool geometry, CostWatch's no-recompile property,
readiness-timer latch monotonicity, the process-level collector, and
the labeled-Sample exposition round trip.

Cost control: everything here is host-side except one tiny jit (one
add) proving `compiled_flops` still accepts a jit-wrapped callable,
and the last test, which reads the observatory off one real cb engine
through its own /metrics."""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu import obs
from singa_tpu.core.net import build_net
from singa_tpu.models.transformer import transformer_lm
from singa_tpu.obs import perf
from singa_tpu.obs.metrics import MetricsRegistry, parse_prometheus
from singa_tpu.serve.kvcache import init_pools, pool_bytes
from singa_tpu.utils.flops import compiled_flops, cost_metrics

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _fresh_watch():
    """Each test gets its own PerfWatch (the module API is a
    process-global singleton) and no leaked obs session."""
    obs.disable()
    perf.reset()
    yield
    obs.disable()
    perf.reset()


# -- CompileWatch ------------------------------------------------------------

def test_compile_counts_and_cache_labels():
    with perf.compile_span("progA", geometry="b2_p16"):
        pass
    with perf.compile_span("progA"):
        pass
    with perf.compile_span("progB"):
        pass
    perf.lookup_hit("progA")
    perf.lookup_hit("progA")
    snap = perf.snapshot()
    assert snap["compiles"] == {"progA": 2, "progB": 1}
    assert snap["compiles_total"] == 3
    assert snap["cache"]["progA:hit"] == 2
    assert snap["cache"]["progA:miss"] == 2
    assert snap["cache"]["progB:miss"] == 1
    assert snap["compile_count"] == 3
    # the labeled series fan out per program in the exposition
    reg = MetricsRegistry()
    perf.register_into(reg)
    got = parse_prometheus(reg.render_prometheus())
    assert got['singa_compiles_total{program="progA"}'] == 2
    assert got['singa_compiles_total{program="progB"}'] == 1
    assert got['singa_compile_cache_total{program="progA",'
               'result="hit"}'] == 2
    assert got["singa_compile_seconds_count"] == 3


def test_register_into_survives_reset():
    reg = MetricsRegistry()
    perf.register_into(reg)
    perf.reset()                      # swaps the singleton
    with perf.compile_span("after_reset"):
        pass
    got = parse_prometheus(reg.render_prometheus())
    assert got['singa_compiles_total{program="after_reset"}'] == 1


def test_warm_scope_anomaly_accounting():
    perf.mark_warm("eng1", "generate")
    # other family / other scope: lazy compiles, not violations
    with perf.compile_span("predict", scope="eng1", family="predict"):
        pass
    with perf.compile_span("generate", scope="eng2",
                           family="generate"):
        pass
    assert perf.snapshot()["anomalies"] == 0
    # same (scope, family): PR 8's invariant is broken
    with perf.compile_span("generate", scope="eng1",
                           family="generate"):
        pass
    snap = perf.snapshot()
    assert snap["anomalies"] == 1
    assert [r for r in snap["records"] if r["anomaly"]] \
        == [{"program": "generate", "geometry": "", "scope": "eng1",
             "seconds": snap["records"][-1]["seconds"],
             "anomaly": True}]


def test_recompile_anomaly_event_and_flightrec_trigger(tmp_path):
    events = tmp_path / "events.jsonl"
    rec_dir = tmp_path / "rec"
    spec = obs.ObsSpec(events=str(events), flightrec=str(rec_dir))
    with obs.session(spec) as o:
        o.flightrec.cooldown_s = 3600.0   # suppress the second dump
        perf.mark_warm("eng", "generate")
        with perf.compile_span("generate", scope="eng",
                               family="generate"):
            pass
        with perf.compile_span("generate", scope="eng",
                               family="generate"):
            pass
        assert perf.snapshot()["anomalies"] == 2
        dumps = glob.glob(str(rec_dir / "flightrec-recompile-*.json"))
        assert len(dumps) == 1            # cooldown rate-limited
        with open(dumps[0]) as f:
            dump = json.load(f)
        assert dump["trigger"] == "recompile"
        # the perf context rides along with the evidence
        assert dump["perf"]["anomalies"] >= 1
        assert "hbm_watermark_bytes" in dump["perf"]
        # cooldown over -> the next anomaly dumps again
        o.flightrec.cooldown_s = 0.0
        with perf.compile_span("generate", scope="eng",
                               family="generate"):
            pass
        assert len(glob.glob(
            str(rec_dir / "flightrec-recompile-*.json"))) == 2
    kinds = [json.loads(line)["kind"]
             for line in events.read_text().splitlines()]
    assert kinds.count("perf.recompile_anomaly") == 3


# -- readiness latches -------------------------------------------------------

def test_readiness_latch_first_call_wins():
    assert perf.snapshot()["serving_ready_s"] is None
    a = perf.mark_serving_ready()
    b = perf.mark_serving_ready()
    assert a == b > 0
    t1 = perf.mark_training_ready()
    t2 = perf.mark_training_ready()
    assert t1 == t2 > 0
    snap = perf.snapshot()
    assert snap["serving_ready_s"] == a
    assert snap["training_ready_s"] == t1
    reg = MetricsRegistry()
    perf.register_into(reg)
    got = parse_prometheus(reg.render_prometheus())
    assert got["singa_restart_to_serving_seconds"] == pytest.approx(a)
    assert got["singa_restart_to_training_seconds"] == pytest.approx(t1)


# -- MemoryWatch -------------------------------------------------------------

def test_analytic_pool_bytes_matches_real_pools():
    cfg = transformer_lm(vocab_size=32, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=16,
                         batchsize=2)
    net = build_net(cfg, "kTest",
                    {"data": {"input": (16,), "target": (16,)}})
    num_blocks, block_len = 9, 4
    pools = init_pools(net, num_blocks, block_len)
    real = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for layer in pools.values() for a in layer.values())
    analytic = pool_bytes(net, num_blocks, block_len)
    # 2 layers x {k,v} x (9, 4 kv_heads, 4, 8) x float32
    assert analytic == real == 2 * 2 * 9 * 4 * 4 * 8 * 4


def test_memory_components_and_watermark():
    perf.set_memory("kv_pool", 1000, scope="eng1")
    perf.set_memory("kv_pool", 500, scope="eng2")
    perf.set_memory_tree("params", {"w": np.zeros((10, 10),
                                                  np.float32)})
    snap = perf.snapshot()
    assert snap["memory_components"] == {"kv_pool": 1500,
                                         "params": 400}
    assert snap["hbm_watermark_bytes"] == 1900
    # shrinking a component never lowers the watermark
    perf.set_memory("kv_pool", 100, scope="eng1")
    snap = perf.snapshot()
    assert snap["memory_components"]["kv_pool"] == 600
    assert snap["hbm_watermark_bytes"] == 1900
    reg = MetricsRegistry()
    perf.register_into(reg)
    got = parse_prometheus(reg.render_prometheus())
    assert got['singa_hbm_analytic_bytes{component="kv_pool"}'] == 600
    assert got["singa_hbm_analytic_total_bytes"] == 1000
    assert got["singa_hbm_watermark_bytes"] == 1900


# -- CostWatch ---------------------------------------------------------------

class _CompiledGuard:
    """Stands in for a jit(...).lower(...).compile() result; any
    attempt to re-lower (i.e. recompile) trips the test."""

    def cost_analysis(self):
        return {"flops": 123.0, "bytes accessed": 456.0,
                "not_numeric": "x"}

    def lower(self, *a, **k):       # pragma: no cover — the property
        raise AssertionError("CostWatch triggered a recompile")


def test_costwatch_never_recompiles():
    guard = _CompiledGuard()
    assert cost_metrics(guard) == {"flops": 123.0,
                                   "bytes accessed": 456.0}
    assert compiled_flops(guard) == 123.0
    entry = perf.harvest("prog", guard)
    assert entry == {"flops": 123.0, "bytes": 456.0}
    perf.observe_step("prog", 0.5)
    reg = MetricsRegistry()
    perf.register_into(reg)
    got = parse_prometheus(reg.render_prometheus())
    assert got['singa_program_flops{program="prog"}'] == 123.0
    assert got['singa_program_bytes{program="prog"}'] == 456.0
    assert got['singa_program_arith_intensity{program="prog"}'] == \
        pytest.approx(123.0 / 456.0)


def test_compiled_flops_still_accepts_jitted_callable():
    jitted = jax.jit(lambda x: x @ x)
    got = compiled_flops(jitted, jnp.ones((4, 4), jnp.float32))
    assert got is None or got > 0   # backend cost model may omit flops


# -- process collector + exposition ------------------------------------------

def test_process_collector_on_registry():
    reg = MetricsRegistry()
    perf.register_process_into(reg)
    got = parse_prometheus(reg.render_prometheus())
    assert got["singa_process_threads"] >= 1
    assert got["singa_process_uptime_seconds"] > 0
    if os.path.exists("/proc/self/statm"):
        assert got["singa_process_rss_bytes"] > 0
        assert got["singa_process_open_fds"] > 0


def test_labeled_samples_render_one_header_per_name():
    with perf.compile_span("a"):
        pass
    with perf.compile_span("b"):
        pass
    reg = MetricsRegistry()
    perf.register_into(reg)
    text = reg.render_prometheus()
    assert text.count("# TYPE singa_compiles_total counter") == 1
    got = parse_prometheus(text)
    assert got['singa_compiles_total{program="a"}'] == 1
    assert got['singa_compiles_total{program="b"}'] == 1


# -- the observatory on a real engine, as an operator scrapes it -------------

def test_real_engine_metrics_carry_the_observatory_and_harvest_is_free():
    """One tiny cb engine behind its HTTP server: warmup compiles its
    cb programs once, requests compile nothing (no anomaly), a
    CostWatch sweep over the compiled programs moves no compile
    counter, and /metrics exports readiness, the HBM watermark, the
    process collector and the decode program's FLOPs."""
    import urllib.request

    from singa_tpu.serve import (InferenceEngine, InferenceServer,
                                 ServeSpec)
    from test_fleet import SEQ, _net_and_params

    net, params = _net_and_params()
    spec = ServeSpec(buckets=((2, SEQ),), max_new_tokens=8,
                     temperature=0.0, request_timeout_s=60.0,
                     reload_poll_s=100.0,
                     cb="on", cb_slots=2, cb_block_len=4)
    engine = InferenceEngine(net, spec, params=params,
                             log_fn=lambda s: None)
    server = InferenceServer(engine, port=0, log_fn=lambda s: None)
    server.start()                 # load + warmup
    try:
        warm = engine.stats.compiles
        assert warm == len(spec.cb_prefill_widths) + 1
        for plen, max_new in ((3, 8), (7, 2), (12, 4)):
            out = server.generate(np.arange(1, plen + 1, dtype=np.int32),
                                  max_new=max_new)
            assert len(out["tokens"]) == max_new
        assert engine.stats.compiles == warm
        assert engine.harvest_costs() >= 2
        assert engine.stats.compiles == warm
        host, port = server.address
        with urllib.request.urlopen(f"http://{host}:{port}/metrics",
                                    timeout=10) as r:
            got = parse_prometheus(r.read().decode())
    finally:
        server.stop()
    snap = perf.snapshot()
    assert snap["anomalies"] == 0
    assert snap["cost"]["cb_decode"]["flops"] > 0
    assert got["singa_restart_to_serving_seconds"] > 0
    assert got["singa_hbm_watermark_bytes"] > 0
    assert got["singa_process_rss_bytes"] > 0
    assert got["singa_recompile_anomalies_total"] == 0


def test_real_trainer_run_latches_restart_to_training():
    """A tiny MLP through `Trainer.run`'s fused scan: the first
    completed dispatch latches restart-to-training, and nothing it
    compiles counts as an anomaly."""
    from singa_tpu.config.schema import model_config_from_dict
    from singa_tpu.core.trainer import Trainer
    from singa_tpu.data.synthetic import synthetic_image_batches

    cfg = model_config_from_dict({
        "name": "perf_mlp", "train_steps": 8, "display_frequency": 0,
        "updater": {"type": "kSGD", "base_learning_rate": 0.1,
                    "learning_rate_change_method": "kFixed"},
        "neuralnet": {"layer": [
            {"name": "data", "type": "kShardData",
             "data_param": {"batchsize": 8}},
            {"name": "mnist", "type": "kMnistImage",
             "srclayers": "data"},
            {"name": "label", "type": "kLabel", "srclayers": "data"},
            {"name": "ip", "type": "kInnerProduct",
             "srclayers": "mnist",
             "inner_product_param": {"num_output": 10},
             "param": [{"name": "weight"}, {"name": "bias"}]},
            {"name": "loss", "type": "kSoftmaxLoss",
             "srclayers": ["ip", "label"]}]}})
    trainer = Trainer(cfg, {"data": {"pixel": (28, 28), "label": ()}},
                      donate=False, log_fn=lambda s: None)
    params, opt_state = trainer.init(0)
    assert perf.snapshot()["training_ready_s"] is None
    trainer.run(params, opt_state,
                synthetic_image_batches(8, seed=1, stream_seed=7),
                seed=0, scan_chunk=4)
    snap = perf.snapshot()
    assert snap["training_ready_s"] > 0
    assert snap["anomalies"] == 0
