"""Continuous batching (serve/kvcache.py, serve/scheduler.py): paged
KV cache bookkeeping, paged-vs-contiguous greedy parity, slot
join/retire under mid-batch EOS, deadline expiry (queued and
mid-stream), block-pool exhaustion -> admission shed, hot-reload
mid-stream, zero recompiles after warmup, and the head-of-line p95
gate against the static bucket path.

Correctness anchor: a request decoded through the paged cache must
produce the EXACT greedy tokens `generate()` produces on a contiguous
cache — token position p of slot s lives at
pool[table[s, p // block_len], :, p % block_len], the paged attention
kernel walks the slot's blocks in absolute-position order, and masked
scores underflow to exact zeros, so paging changes memory layout and
the order of the f32 sums and nothing else.

Cost control: compiled-program tests share two module-scoped engines
(one cb, one static for the p95 gate) over the tiny 2-layer test LM;
the deadline/exhaustion engine self-calibrates its timeout from a
measured full run instead of guessing CPU step latency."""

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
                             InferenceServer, Overloaded,
                             PagedKVCache, ServeSpec)
from singa_tpu.serve.kvcache import NULL_BLOCK
from singa_tpu.utils.checkpoint import CheckpointManager

pytestmark = pytest.mark.serve

VOCAB, SEQ = 64, 16
SHAPES = {"data": {"input": (SEQ,), "target": (SEQ,)}}


def _net_and_params(seed=0):
    cfg = transformer_lm(vocab_size=VOCAB, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=SEQ,
                         batchsize=2)
    net = build_net(cfg, "kTest", SHAPES)
    return net, net.init_params(jax.random.PRNGKey(seed))


# -- spec grammar ------------------------------------------------------------

def test_spec_parse_cb_grammar():
    spec = ServeSpec.parse("buckets=4x16,max_new_tokens=8,cb=on,"
                           "cb_slots=4,cb_block_len=4")
    assert spec.cb_on and spec.cb_slots == 4 and spec.cb_block_len == 4
    assert spec.cb_prefill_len == 16          # already a block multiple
    assert spec.cb_blocks_per_slot == 6       # ceil((16 + 8) / 4)
    assert spec.cb_pool_blocks == 25          # 4 * 6 + null block
    assert not ServeSpec.parse("buckets=4x16").cb_on  # default off
    # a prompt cap below the bucket keeps its own prefill geometry
    capped = ServeSpec.parse("buckets=4x16,max_new_tokens=8,cb=on,"
                             "cb_block_len=4,cb_prompt_cap=6")
    assert capped.cb_max_prompt_len == 6
    assert capped.cb_prefill_len == 8         # 6 rounded up to blocks
    with pytest.raises(ValueError):
        ServeSpec.parse("cb=maybe")
    with pytest.raises(ValueError):
        ServeSpec.parse("cb=on,cb_slots=0")
    with pytest.raises(ValueError):
        ServeSpec.parse("cb_block_len=0")


@pytest.mark.parametrize("cap,block_len,want", [
    (16, 4, (16,)),                   # every tiny test spec: as it was
    (200, 16, (208,)),                # under the floor, rounded to blocks
    (256, 16, (256,)),                # at the floor
    (512, 16, (256, 512)),
    (1024, 16, (256, 512, 1024)),     # the benchmark's serving cells
    (768, 16, (256, 512, 768)),       # a cap that is no power of two
    (300, 16, (256, 304)),
    (1000, 48, (288, 576, 1008)),     # a block that does not divide 256
])
def test_prefill_ladder_from_the_geometry(cap, block_len, want):
    spec = ServeSpec(buckets=((1, cap),), cb="on", cb_block_len=block_len)
    widths = spec.cb_prefill_widths
    assert widths == want
    assert all(w % block_len == 0 for w in widths)
    assert list(widths) == sorted(set(widths))
    assert widths[-1] == spec.cb_prefill_len
    # the narrowest rung that holds the prompt
    for rung, below in zip(widths, (0,) + widths):
        assert spec.cb_prefill_width(below + 1) == rung
        assert spec.cb_prefill_width(min(rung, cap)) == rung
    with pytest.raises(ValueError):
        spec.cb_prefill_width(widths[-1] + 1)


# -- paged cache bookkeeping (no compiled programs) --------------------------

def test_kvcache_alloc_free_refcounts():
    net, _ = _net_and_params()
    kv = PagedKVCache(net, num_slots=2, max_blocks_per_slot=3,
                      num_blocks=7, block_len=4, dtype=np.float32)
    assert kv.usable_blocks == 6 and kv.free_blocks == 6
    assert kv.blocks_for(1) == 1 and kv.blocks_for(4) == 1
    assert kv.blocks_for(5) == 2
    row = kv.alloc(0, 2)
    assert row.shape == (3,) and NULL_BLOCK not in row[:2]
    assert row[2] == NULL_BLOCK               # tail beyond reservation
    assert kv.free_blocks == 4 and kv.blocks_in_use == 2
    row2 = kv.alloc(1, 3)
    assert kv.free_blocks == 1
    assert not kv.can_admit(2) and kv.can_admit(1)
    kv.free(0)
    assert kv.free_blocks == 3
    # freed blocks are reusable; the null block never enters the pool
    row3 = kv.alloc(0, 3)
    assert NULL_BLOCK not in row3
    assert set(map(int, row3)) & set(map(int, row))
    assert not set(map(int, row3)) & set(map(int, row2[:3]))
    kv.free_all()
    assert kv.free_blocks == 6 and kv.blocks_in_use == 0
    with pytest.raises(ValueError):
        PagedKVCache(net, num_slots=1, max_blocks_per_slot=1,
                     num_blocks=1, block_len=4, dtype=np.float32)


# -- shared cb engine (expensive: built once) --------------------------------

@pytest.fixture(scope="module")
def cb_served():
    net, params = _net_and_params()
    spec = ServeSpec(buckets=((2, SEQ),), max_new_tokens=32,
                     temperature=0.0, request_timeout_s=30.0,
                     cb="on", cb_slots=4, cb_block_len=4)
    engine = InferenceEngine(net, spec, params=params,
                             log_fn=lambda s: None)
    server = InferenceServer(engine, http=False, log_fn=lambda s: None)
    server.start()
    yield net, params, engine, server
    server.stop()


def test_paged_matches_contiguous_greedy(cb_served):
    """The acceptance anchor: every prompt length, admitted
    concurrently so they share decode steps, decodes bit-identically
    to the contiguous-cache generate()."""
    net, params, engine, server = cb_served
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, VOCAB, plen).astype(np.int32)
               for plen in (1, 5, 9, SEQ)]
    refs = [np.asarray(generate(net, params, p[None], 32))[0].tolist()
            for p in prompts]
    tickets = [server.generate_stream(p) for p in prompts]
    outs = [t.wait(60.0) for t in tickets]
    for p, ref, out in zip(prompts, refs, outs):
        assert out["tokens"] == ref, \
            f"plen={p.size}: paged {out['tokens']} != {ref}"
        assert out["finish"] == "length"


def test_short_joins_and_finishes_while_long_decodes(cb_served):
    """The continuous-batching point: a short request admitted while
    a long generation is mid-decode completes first — no head-of-line
    blocking."""
    net, params, engine, server = cb_served
    long_t = server.generate_stream(np.array([3, 1, 4], np.int32))
    # wait until the long request is actually decoding
    first = next(long_t.tokens(timeout=30.0))
    assert isinstance(first, int)
    short = server.generate(np.array([7, 7], np.int32), max_new=2)
    assert len(short["tokens"]) == 2 and short["finish"] == "length"
    assert not long_t.done(), \
        "short finished only after the long generation — head-of-line"
    out = long_t.wait(60.0)
    assert len(out["tokens"]) == 32 and out["finish"] == "length"


def test_zero_recompiles_after_warmup_mixed_load(cb_served):
    net, params, engine, server = cb_served
    warm = engine.stats.compiles
    assert warm >= 2                  # one prefill + one decode program
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, VOCAB, rng.integers(1, SEQ + 1)).astype(
        np.int32) for _ in range(12)]
    errs, outs = [], []

    def client(p, mn):
        try:
            outs.append(server.generate(p, max_new=mn))
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=client,
                                args=(p, int(rng.integers(1, 33))))
               for p in prompts]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs and len(outs) == 12
    assert engine.stats.compiles == warm, "recompiled after warmup"


def test_stats_split_histograms_and_prometheus(cb_served):
    from singa_tpu.obs.metrics import MetricsRegistry

    net, params, engine, server = cb_served
    server.generate(np.array([5, 9], np.int32), max_new=3)
    snap = server.snapshot()
    assert snap["generated_tokens"] > 0
    for k in ("p50_queue_wait_ms", "p95_queue_wait_ms",
              "p50_service_ms", "p95_service_ms", "p50_tokens_per_s"):
        assert snap[k] is not None and snap[k] >= 0, k
    assert 0 < snap["cb_slot_occupancy"] <= 1.0
    assert 0 < snap["cb_block_utilization"] <= 1.0
    assert snap["cb"]["slots"] == 4
    reg = MetricsRegistry()
    engine.stats.register_into(reg)
    text = reg.render_prometheus()
    for name in ("singa_serve_generated_tokens_total",
                 "singa_serve_cb_steps_total",
                 "singa_serve_p95_queue_wait_ms",
                 "singa_serve_p95_service_ms",
                 "singa_serve_cb_slot_occupancy",
                 "singa_serve_cb_block_utilization"):
        assert name in text, name


def test_overlong_prompt_fast_reject_both_paths(cb_served):
    net, params, engine, server = cb_served
    before = engine.stats.rejected
    too_long = np.arange(SEQ + 1, dtype=np.int32) % VOCAB + 1
    with pytest.raises(ValueError, match="not servable"):
        server.scheduler.submit(too_long)
    with pytest.raises(ValueError, match="not servable"):
        server.batcher.submit(too_long, mode="generate")
    with pytest.raises(ValueError, match="empty prompt"):
        server.scheduler.submit(np.zeros((0,), np.int32))
    assert engine.stats.rejected == before + 3


# -- EOS retire + slot reuse -------------------------------------------------

# -- the prefill ladder (floor patched down to the tiny net's sizes) ---------

LADDER_PLENS = (3, 4, 5, 7, 8, 9, 15, 16)     # rung-1, rung, rung+1


@pytest.fixture(scope="module")
def ladder_run():
    """One engine whose ladder is (4, 8, 16), warmed, then a load on
    both sides of every rung boundary; what the tests below check is
    recorded here, and the floor is put back before any of them runs."""
    from singa_tpu import obs
    from singa_tpu.serve import engine as engine_mod
    from singa_tpu.serve.scheduler import ContinuousScheduler

    net, params = _net_and_params()
    spec = ServeSpec(buckets=((1, SEQ),), max_new_tokens=6,
                     temperature=0.0, request_timeout_s=60.0,
                     cb="on", cb_slots=4, cb_block_len=4)
    got = {"net": net, "params": params, "spec": spec}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_mod, "CB_PREFILL_FLOOR", 4)
        got["widths"] = spec.cb_prefill_widths
        engine = InferenceEngine(net, spec, params=params,
                                 log_fn=lambda s: None)
        got["warm_compiles"] = engine.warmup()
        got["programs"] = sorted(k[0] for k in engine._compiled)
        rng = np.random.default_rng(3)
        got["prompts"] = [rng.integers(1, VOCAB, p).astype(np.int32)
                          for p in LADDER_PLENS]
        sched = ContinuousScheduler(engine, log_fn=lambda s: None).start()
        try:
            with obs.session(obs.ObsSpec()) as o:
                tickets = [sched.submit(p) for p in got["prompts"]]
                got["served"] = [t.wait(timeout=120)["tokens"]
                                 for t in tickets]
                got["prefills"] = {
                    e["args"]["plen"]: e["args"]["width"]
                    for e in o.tracer.events()
                    if e["name"] == "scheduler.prefill"}
            got["compiles_after"] = engine.stats.compiles
            got["snapshot"] = engine.stats.snapshot()
            for width in (12, 32):     # a block multiple, and no rung
                with pytest.raises(ValueError, match="rungs"):
                    engine.dispatch_cb_prefill(
                        engine.params, sched.kv.pools,
                        np.zeros((1, width), np.int32), 1,
                        np.zeros((width // 4,), np.int32))
        finally:
            sched.stop()
        got["engine"] = engine
        got["cap_is_default"] = engine._compile_cb("prefill") is \
            engine._compile_cb("prefill", spec.cb_prefill_len)
    return got


@pytest.mark.parametrize("i", range(len(LADDER_PLENS)),
                         ids=[f"plen{p}" for p in LADDER_PLENS])
def test_ladder_tokens_equal_generates_around_every_rung(ladder_run, i):
    prompt = ladder_run["prompts"][i]
    want = np.asarray(generate(ladder_run["net"], ladder_run["params"],
                               prompt[None], 6))[0].tolist()
    assert ladder_run["served"][i] == want, f"plen={prompt.size}"


def test_warmup_compiles_every_rung_and_a_mixed_load_none(ladder_run):
    assert ladder_run["widths"] == (4, 8, 16)
    assert ladder_run["warm_compiles"] == len(ladder_run["widths"]) + 1
    assert ladder_run["programs"] == ["cb_decode", "cb_prefill",
                                      "cb_prefill_4", "cb_prefill_8"]
    assert ladder_run["compiles_after"] == ladder_run["warm_compiles"]
    assert ladder_run["cap_is_default"]


def test_fill_share_and_span_width_by_hand(ladder_run):
    # each prompt at the narrowest rung that holds it
    assert ladder_run["prefills"] == {3: 4, 4: 4, 5: 8, 7: 8, 8: 8,
                                      9: 16, 15: 16, 16: 16}
    snap = ladder_run["snapshot"]
    assert snap["cb_prefills"] == 8
    assert snap["cb_flash_prefills"] == 0      # rungs under a lane tile
    assert ladder_run["engine"].cb_flash_widths == set()
    assert snap["cb_prefill_rows"] == sum(LADDER_PLENS) == 67
    assert snap["cb_prefill_width_rows"] == 4 + 4 + 8 + 8 + 8 + 16 * 3
    assert snap["cb_prefill_fill_share"] == round(67 / 80, 4)
    from singa_tpu.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    ladder_run["engine"].stats.register_into(reg)
    text = reg.render_prometheus()
    assert "singa_serve_cb_prefill_fill_share 0.8375" in text
    assert "singa_serve_cb_prefill_rows_total 67" in text


def test_build_cb_prefill_without_a_width_is_the_caps_program(ladder_run):
    """`tests/benchmark/test_bench_preflight.py` compiles the prefill
    it gets from `_build_cb_prefill()` at the cap's shapes."""
    import jax.numpy as jnp
    from singa_tpu.serve.kvcache import init_pools
    engine, spec = ladder_run["engine"], ladder_run["spec"]
    pools = jax.eval_shape(lambda: init_pools(
        ladder_run["net"], spec.cb_pool_blocks, spec.cb_block_len,
        jnp.float32))

    def lowered(fn, width):
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
        return jax.jit(fn).lower(
            ladder_run["params"], pools, i32(1, width), i32(),
            i32(width // spec.cb_block_len),
            jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text()

    cap = spec.cb_prefill_len
    assert lowered(engine._build_cb_prefill(), cap) == \
        lowered(engine._build_cb_prefill(cap), cap)
    assert "jit_cb_prefill" in lowered(engine._build_cb_prefill(8), 8)
    assert ServeSpec().cb_prefill_widths == (ServeSpec().cb_prefill_len,)


# -- the prefill's attention through the flash forward kernel ------------------
# Heads of 128, as every serving configuration has; rungs (64, 128, 256):
# `attend_cache` takes the kernel at whole lane tiles of rows (128 and
# 256) and the dense scores at 64.  A window of 160 is dropped at the
# 128 rung (it holds every key) and masks at the 256 rung.
FLASH_PLENS = (40, 100, 128, 200, 255)
FLASH_NEW = 5


def _flash_model(kind):
    from singa_tpu.data import discover_input_shapes
    from singa_tpu.models.transformer import hybrid_lm
    if kind == "gqa":
        cfg = transformer_lm(vocab_size=VOCAB, num_layers=2, embed_dim=64,
                             num_heads=4, head_dim=128, num_kv_heads=1,
                             seq_len=256, batchsize=1)
    else:
        att = {"num_heads": 2, "num_kv_heads": 1, "head_dim": 128,
               "qk_norm": True, "gate": True, "norm_epsilon": 1e-5,
               "rope_theta": 1e4}
        cfg = hybrid_lm(
            vocab_size=VOCAB, embed_dim=64, seq_len=256, post_norm=True,
            mixers=[{"attention": {**att, "rope": True, "window": 160}},
                    {"attention": {**att, "rope": False}}],
            ffns=[{"dense": {"hidden_dim": 128, "activation": "silu"}}] * 2)
    net = build_net(cfg, "kTest",
                    discover_input_shapes(cfg, force_synthetic=True))
    return net, net.init_params(jax.random.PRNGKey(2))


@pytest.fixture(scope="module", params=["windowed_gated_normed", "gqa"])
def flash_run(request):
    from singa_tpu.serve import engine as engine_mod
    from singa_tpu.serve.scheduler import ContinuousScheduler
    net, params = _flash_model(request.param)
    spec = ServeSpec(buckets=((1, 256),), max_new_tokens=FLASH_NEW,
                     temperature=0.0, eos_id=None, request_timeout_s=600.0,
                     cb="on", cb_slots=2, cb_block_len=16, cb_prompt_cap=256)
    got = {"net": net, "params": params}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_mod, "CB_PREFILL_FLOOR", 64)
        got["widths"] = spec.cb_prefill_widths
        engine = InferenceEngine(net, spec, params=params,
                                 log_fn=lambda s: None)
        engine.warmup()
        rng = np.random.default_rng(1)
        got["prompts"] = [rng.integers(0, VOCAB, n).astype(np.int32)
                          for n in FLASH_PLENS]
        sched = ContinuousScheduler(engine, log_fn=lambda s: None).start()
        try:
            tickets = [sched.submit(p, max_new=FLASH_NEW)
                       for p in got["prompts"]]
            got["served"] = [list(t.wait(timeout=600)["tokens"])
                             for t in tickets]
        finally:
            sched.stop()
    got["engine"] = engine
    return got


@pytest.mark.parametrize("i", range(len(FLASH_PLENS)),
                         ids=[f"plen{p}" for p in FLASH_PLENS])
def test_flash_prefill_tokens_equal_generates(flash_run, i):
    """`generate()` prefills into a cache longer than its prompt: the
    dense scores, whatever the length."""
    prompt = flash_run["prompts"][i]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(generate(flash_run["net"], flash_run["params"],
                                   prompt[None], FLASH_NEW))[0].tolist()
    assert flash_run["served"][i] == want, f"plen={prompt.size}"


def test_flash_prefills_are_counted_by_rung_and_exported(flash_run):
    from singa_tpu.obs.metrics import MetricsRegistry
    engine = flash_run["engine"]
    assert flash_run["widths"] == (64, 128, 256)
    assert engine.cb_flash_widths == {128, 256}
    snap = engine.stats.snapshot()
    assert snap["cb_prefills"] == len(FLASH_PLENS)
    assert snap["cb_flash_prefills"] == sum(p > 64 for p in FLASH_PLENS)
    reg = MetricsRegistry()
    engine.stats.register_into(reg)
    text = reg.render_prometheus()
    assert "singa_serve_cb_flash_prefills_total 4" in text
    assert "singa_serve_cb_prefills_total 5" in text


@pytest.mark.parametrize("on_device", [False, True])
def test_decode_args_stay_on_the_host_until_the_call(cb_served, on_device):
    """The step's small inputs reach the compiled call as int32 host
    arrays (its own transfer moves them: no `jnp.asarray` on the
    per-token path); tokens the step before left on the device go in as
    they are."""
    import jax.numpy as jnp
    _, _, engine, _ = cb_served
    spec = engine.spec
    toks = np.arange(spec.cb_slots, dtype=np.int64)
    given = jnp.asarray(toks, jnp.int32) if on_device else toks
    tokens, ntoks, tables, key = engine._cb_decode_args(
        given, np.ones(spec.cb_slots, np.int64),
        np.zeros((spec.cb_slots, spec.cb_blocks_per_slot), np.int64))
    assert (tokens is given) if on_device else \
        (isinstance(tokens, np.ndarray) and tokens.dtype == np.int32)
    assert np.array_equal(np.asarray(tokens), toks)
    for arr in (ntoks, tables):
        assert isinstance(arr, np.ndarray) and arr.dtype == np.int32
    assert isinstance(key, np.ndarray) and key.dtype == np.uint32


def test_eos_retires_slot_mid_batch_and_slot_is_reused():
    net, params = _net_and_params()
    probe = np.array([3, 1, 4], np.int32)
    ref = np.asarray(generate(net, params, probe[None], 8))[0].tolist()
    eos = ref[3]        # greedy hits this mid-decode -> EOS retire
    expected = ref[:ref.index(eos) + 1]   # first occurrence may be <4
    spec = ServeSpec(buckets=((2, SEQ),), max_new_tokens=8,
                     temperature=0.0, eos_id=eos,
                     request_timeout_s=30.0,
                     cb="on", cb_slots=2, cb_block_len=4)
    engine = InferenceEngine(net, spec, params=params,
                             log_fn=lambda s: None)
    server = InferenceServer(engine, http=False, log_fn=lambda s: None)
    server.start()
    try:
        other = np.array([9, 2, 5, 11], np.int32)
        oref = np.asarray(generate(net, params, other[None], 8,
                                   eos_id=eos))[0].tolist()
        if eos in oref:
            oref = oref[:oref.index(eos) + 1]
        t1 = server.generate_stream(probe)
        t2 = server.generate_stream(other)
        out1, out2 = t1.wait(30.0), t2.wait(30.0)
        assert out1["finish"] == "eos"
        assert out1["tokens"] == expected and out1["tokens"][-1] == eos
        assert out2["tokens"] == oref
        # the freed slot admits the next request (retire released it)
        out3 = server.generate(probe)
        assert out3["tokens"] == expected and out3["finish"] == "eos"
    finally:
        server.stop()


# -- deadlines + pool exhaustion (one small engine, self-calibrated) ---------

@pytest.fixture(scope="module")
def cb_small():
    net, params = _net_and_params()
    # pool of 40 blocks: one worst-case request (36 blocks) fits, two
    # cannot coexist -> exhaustion is reachable with two requests
    spec = ServeSpec(buckets=((2, SEQ),), max_new_tokens=128,
                     temperature=0.0, queue_capacity=2,
                     request_timeout_s=30.0,
                     cb="on", cb_slots=2, cb_block_len=4, cb_blocks=40)
    engine = InferenceEngine(net, spec, params=params,
                             log_fn=lambda s: None)
    server = InferenceServer(engine, http=False, log_fn=lambda s: None)
    server.start()
    # calibrate: one full worst-case generation, wall-clock
    t0 = time.monotonic()
    out = server.generate(np.array([1, 2, 3], np.int32))
    full_s = time.monotonic() - t0
    assert len(out["tokens"]) == 128
    yield net, params, engine, server, full_s
    server.stop()


def test_deadline_mid_stream_retires_with_partial_result(cb_small):
    net, params, engine, server, full_s = cb_small
    # a deadline a third of the measured full run: at least the
    # prefill token lands, the 128-token decode cannot finish
    budget = max(full_s / 3.0, 0.02)
    out = server.generate(np.array([4, 5], np.int32), timeout=budget)
    assert out["finish"] == "deadline"
    assert 1 <= len(out["tokens"]) < 128


def test_deadline_expires_in_queue_when_pool_is_held(cb_small):
    net, params, engine, server, full_s = cb_small
    hog = server.generate_stream(np.array([6, 7, 8], np.int32))
    next(hog.tokens(timeout=30.0))    # hog now holds 33 of 39 blocks
    # worst-case reservation (33 blocks) cannot be admitted while the
    # hog runs; a tiny deadline expires it in the queue
    with pytest.raises(DeadlineExpired):
        server.generate(np.array([9, 9, 9], np.int32), timeout=0.05)
    assert engine.stats.expired >= 1
    out = hog.wait(60.0)              # the hog itself is unharmed
    assert len(out["tokens"]) == 128


def test_pool_exhaustion_sheds_at_admission_no_deadlock(cb_small):
    net, params, engine, server, full_s = cb_small
    before_shed = engine.stats.shed
    hog = server.generate_stream(np.array([1, 1, 1], np.int32))
    next(hog.tokens(timeout=30.0))
    # a small reservation still fits alongside the hog (6 free blocks)
    small = server.generate(np.array([5], np.int32), max_new=2)
    assert len(small["tokens"]) == 2
    # two more worst-case requests fill the pending queue (capacity 2)
    q1 = server.generate_stream(np.array([2, 2, 2], np.int32))
    q2 = server.generate_stream(np.array([3, 3, 3], np.int32))
    # the third is shed with a retry hint -- not queued, not deadlocked
    with pytest.raises(Overloaded) as ei:
        server.generate_stream(np.array([4, 4, 4], np.int32))
    assert ei.value.retry_after > 0
    assert engine.stats.shed == before_shed + 1
    # everything admitted completes: FIFO drain, no deadlock
    for t in (hog, q1, q2):
        assert len(t.wait(120.0)["tokens"]) == 128


# -- hot reload mid-stream ---------------------------------------------------

def test_hot_reload_mid_stream_no_tear():
    net, params = _net_and_params()
    p2 = jax.tree_util.tree_map(lambda a: a * 2.0, params)
    with tempfile.TemporaryDirectory() as ws:
        mgr = CheckpointManager(ws, max_to_keep=10,
                                log_fn=lambda s: None)
        mgr.save(1, params, {"t": np.zeros(())},
                 health={"verdict": "ok"})
        # reload_poll_s far out: the test drives poll_reload itself
        spec = ServeSpec(buckets=((2, SEQ),), max_new_tokens=256,
                         temperature=0.0, request_timeout_s=60.0,
                         reload_poll_s=60.0,
                         cb="on", cb_slots=2, cb_block_len=4)
        engine = InferenceEngine(net, spec, workspace=ws,
                                 log_fn=lambda s: None)
        assert engine.load() == 1
        server = InferenceServer(engine, http=False,
                                 log_fn=lambda s: None)
        server.start()
        try:
            t = server.generate_stream(np.array([3, 1, 4], np.int32))
            next(t.tokens(timeout=30.0))
            mgr.save(2, p2, {"t": np.zeros(())},
                     health={"verdict": "ok"})
            assert engine.poll_reload() == "reloaded"
            assert engine.params_step == 2
            assert not t.done(), "stream ended before the reload " \
                "landed; mid-stream swap was not exercised"
            out = t.wait(120.0)
            # no tear: the stream finished cleanly on the new params
            # (each step is internally consistent; the result's step
            # is the one serving at retire time)
            assert len(out["tokens"]) == 256
            assert out["finish"] == "length" and out["step"] == 2
            assert all(0 <= tok < VOCAB for tok in out["tokens"])
        finally:
            server.stop()


# -- the head-of-line gate: cb p95 vs static p95 -----------------------------

def test_cb_p95_beats_static_under_mixed_load():
    """23 shorts + 1 long through both paths: the static bucket
    decodes every batch to full max_new_tokens, so shorts queue behind
    longs; cb retires shorts as they finish.  The acceptance gate is
    cb p95 <= 0.5x static p95.  Both engines use a 256-token decode
    horizon — the regime
    where the static path's pay-for-max pathology is the device time,
    not per-call overhead."""
    net, params = _net_and_params()
    st_spec = ServeSpec(buckets=((2, SEQ),), max_new_tokens=256,
                        temperature=0.0, batch_window_s=0.005,
                        request_timeout_s=60.0)
    cb_spec = ServeSpec(buckets=((2, SEQ),), max_new_tokens=256,
                        temperature=0.0, request_timeout_s=60.0,
                        cb="on", cb_slots=8, cb_block_len=4)
    st_engine = InferenceEngine(net, st_spec, params=params,
                                log_fn=lambda s: None)
    st_server = InferenceServer(st_engine, http=False,
                                log_fn=lambda s: None)
    st_server.start()
    cb_engine = InferenceEngine(net, cb_spec, params=params,
                                log_fn=lambda s: None)
    cb_server = InferenceServer(cb_engine, http=False,
                                log_fn=lambda s: None)
    cb_server.start()
    try:
        rng = np.random.default_rng(7)
        prompts = [rng.integers(1, VOCAB, 3).astype(np.int32)
                   for _ in range(24)]
        max_news = [2] * 23 + [256]   # p95 rank 22 lands on a short

        def run(server):
            lats = [None] * len(prompts)

            def client(i):
                t0 = time.monotonic()
                out = server.generate(prompts[i],
                                      max_new=max_news[i])
                lats[i] = time.monotonic() - t0
                assert len(out["tokens"]) == max_news[i]

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert all(v is not None for v in lats)
            return float(np.sort(lats)[int(0.95 * len(lats))])

        static_p95 = run(st_server)
        cb_p95 = run(cb_server)
        assert cb_p95 <= 0.5 * static_p95, \
            (f"continuous batching did not beat the static path: "
             f"cb p95 {cb_p95 * 1e3:.1f}ms vs static p95 "
             f"{static_p95 * 1e3:.1f}ms")
    finally:
        st_server.stop()
        cb_server.stop()
