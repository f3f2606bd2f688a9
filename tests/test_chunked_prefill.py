"""A prompt past the widest compiled prefill, served in chunks
(serve/engine.py, serve/scheduler.py, serve/kvcache.py; ISSUE 43), on
the Solar-Open2 configuration's tiny size, float32, on the CPU: the
spec's geometry, the scheduler's fixed rule (one chunk, then one decode
step of the running slots; one prompt in prefill at a time; a slot in
prefill is not busy and a decode step leaves its state bit for bit), a
cancel between chunks, the refusals at submit with their reasons, the
tokens against `generate()`, and the spans and counters of the path."""

import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, solar_weights  # noqa: E402
from benchmark.runners import serve_kimi, serve_solar  # noqa: E402
from singa_tpu import obs  # noqa: E402
from singa_tpu.core.net import build_net  # noqa: E402
from singa_tpu.data import discover_input_shapes  # noqa: E402
from singa_tpu.models.generate import generate  # noqa: E402
from singa_tpu.obs.metrics import MetricsRegistry  # noqa: E402
from singa_tpu.serve import engine as engine_mod  # noqa: E402
from singa_tpu.serve.batcher import Cancelled  # noqa: E402
from singa_tpu.serve.engine import InferenceEngine, ServeSpec  # noqa: E402
from singa_tpu.serve.kvcache import NULL_BLOCK, state_bytes  # noqa: E402
from singa_tpu.serve.scheduler import ContinuousScheduler  # noqa: E402

pytestmark = pytest.mark.serve

CFG = harness._tiny(harness.read_json(
    ROOT, "benchmark", "configs", "solar-open2-serve-l4-ep8.json"))
CAP, RUNG, BL, NEW = 64, 16, 4, 6


@pytest.fixture(autouse=True)
def _no_leaked_session():
    obs.disable()
    yield
    obs.disable()


@pytest.fixture(scope="module")
def lm():
    model = serve_solar.model_config(CFG, CAP)
    net = build_net(model, "kTrain",
                    discover_input_shapes(model, force_synthetic=True))
    made = solar_weights.tree(CFG, 11, jnp.float32)
    return net, {solar_weights.program_name(k): v for k, v in made.items()}


def _spec(slots=3, **kw):
    kw = {"cb_prompt_cap": CAP, "cb_prefill_rung": RUNG, **kw}
    return ServeSpec(buckets=((1, CAP),), max_new_tokens=NEW,
                     temperature=0.0, eos_id=None, request_timeout_s=300.0,
                     cb="on", cb_slots=slots, cb_block_len=BL, **kw)


@pytest.fixture(scope="module")
def engine(lm):
    """Rungs (8, 16) under a cap of 64, every program compiled once."""
    net, params = lm
    floor, engine_mod.CB_PREFILL_FLOOR = engine_mod.CB_PREFILL_FLOOR, 8
    try:
        spec = _spec()
        assert spec.cb_prefill_widths == (8, 16)
        eng = InferenceEngine(net, spec, params=params,
                              log_fn=lambda *a: None)
        # ONE ladder, the chunk programs, and the decode step: an engine
        # that chunks sends every prompt through them
        assert eng.warmup() == 3
        assert sorted(k[0] for k in eng._compiled) == [
            "cb_chunk_16", "cb_chunk_8", "cb_decode"]
        yield eng
    finally:
        engine_mod.CB_PREFILL_FLOOR = floor


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(
        1, CFG["vocab_size"], n).astype(np.int32)


def _greedy(lm, prompt, n):
    net, params = lm
    with jax.default_matmul_precision("highest"):
        return list(np.asarray(generate(net, params, prompt[None], n))[0])


# -- the geometry --------------------------------------------------------------

def test_the_cap_may_lie_past_the_widest_rung():
    spec = ServeSpec(buckets=((1, 32768),), max_new_tokens=1024, cb="on",
                     cb_block_len=16, cb_prompt_cap=32768,
                     cb_prefill_rung=2048)
    assert spec.cb_prefill_widths == (256, 512, 1024, 2048)
    assert (spec.cb_prefill_len, spec.cb_max_prompt_len) == (2048, 32768)
    assert spec.cb_chunked and spec.cb_blocks_per_slot == 2112
    assert spec.cb_chunks(2049) == ((0, 2048, 2048), (2048, 1, 256))
    assert spec.cb_chunks(4096) == ((0, 2048, 2048), (2048, 2048, 2048))
    assert spec.cb_chunks(9000)[-1] == (8192, 808, 1024)
    assert len(spec.cb_chunks(32768)) == 16
    with pytest.raises(ValueError, match="widest prefill program .2048"):
        spec.cb_prefill_width(2049)
    # a cap that is no whole number of rungs: the table holds the last
    # chunk's pads
    odd = ServeSpec(buckets=((1, 50),), max_new_tokens=6, cb="on",
                    cb_block_len=4, cb_prompt_cap=50, cb_prefill_rung=16)
    assert odd.cb_blocks_per_slot == (64 + 6 + 3) // 4
    # without the field, or with one at or past the cap, nothing changed
    for rung in (0, 1024, 4096):
        plain = ServeSpec(buckets=((1, 1024),), max_new_tokens=1024,
                          cb="on", cb_block_len=16, cb_prompt_cap=1024,
                          cb_prefill_rung=rung)
        assert not plain.cb_chunked and plain.cb_prefill_len == 1024
        assert plain.cb_blocks_per_slot == 128
    assert ServeSpec.parse("cb=on,cb_prefill_rung=32").cb_prefill_rung == 32
    with pytest.raises(ValueError):
        ServeSpec(cb="on", cb_prefill_rung=-1)


def test_state_bytes_count_what_a_slot_in_prefill_holds(lm):
    """A slot in prefill holds what a running one does, its chunks work
    straight on the pools: three KDA states and tails, and K and V a
    row of its blocks in the one attention layer."""
    net, _ = lm
    per = state_bytes(net, BL, jnp.float32)
    h, d, kv = 4, 16, 2
    assert per["slot"] == 3 * (h * d * d * 4 + 3 * 3 * h * d * 4)
    assert per["block"] == BL * 2 * kv * d * 4
    assert set(per) == {"slot", "block", "window_block", "block_copy",
                        "window_block_copy"}


# -- tokens ---------------------------------------------------------------------

@pytest.mark.parametrize("plen", [5, 16, 17, 33, 40, 64])
def test_chunked_tokens_equal_generates(lm, engine, plen):
    """One prompt a run: at the widest rung one last chunk, past it
    chunks of 16 and a last one at the narrowest rung that holds the
    rest."""
    prompt = _prompt(plen, plen)
    sched = ContinuousScheduler(engine, log_fn=lambda *a: None).start()
    try:
        got = sched.submit(prompt, max_new=NEW).wait(timeout=300)["tokens"]
    finally:
        sched.stop()
    assert got == _greedy(lm, prompt, NEW)


# -- (f): the scheduler's rule ---------------------------------------------------

class _Watch:
    """The engine's calls in the order the loop made them, and the
    pools' state of one slot before and after each decode step."""

    def __init__(self, engine, sched, slot=None):
        self.calls, self.engine, self.sched = [], engine, sched
        self.slot, self.kept = slot, []
        self._real = {n: getattr(engine, n) for n in (
            "dispatch_cb_chunk", "run_cb_decode", "dispatch_cb_prefill")}
        engine.dispatch_cb_chunk = self._chunk
        engine.run_cb_decode = self._decode
        engine.dispatch_cb_prefill = self._prefill

    def _state(self, pools, tables):
        rows = np.asarray(self.sched.kv.tables[self.slot])
        rows = rows[rows != NULL_BLOCK]
        return {n: {k: np.asarray(v[rows] if k == "kv" else v[self.slot])
                    for k, v in e.items() if k != "routed"}
                for n, e in pools.items()}

    def _chunk(self, params, pools, tokens, rows, start, last, row):
        self.calls.append(("chunk", int(start), int(rows), int(row[-1])))
        return self._real["dispatch_cb_chunk"](params, pools, tokens, rows,
                                               start, last, row)

    def _prefill(self, params, pools, tokens, plen, row):
        self.calls.append(("prefill", int(plen)))
        return self._real["dispatch_cb_prefill"](params, pools, tokens, plen,
                                                 row)

    def _decode(self, params, pools, tokens, ntoks, tables):
        busy = tuple(np.flatnonzero(ntoks))
        self.calls.append(("decode", busy))
        pf = self.sched._prefilling
        before = None
        if pf is not None and self.slot == pf.slot:
            assert not np.any(tables[pf.slot])        # its row is hidden
            before = self._state(pools, tables)
        out, pools = self._real["run_cb_decode"](params, pools, tokens,
                                                 ntoks, tables)
        if before is not None:
            self.kept.append((before, self._state(pools, tables)))
        return out, pools

    def close(self):
        for n, f in self._real.items():
            setattr(self.engine, n, f)


def test_decode_steps_go_out_between_chunks_and_leave_the_slot_alone(
        lm, engine):
    """A short request runs; a prompt of four chunks is admitted beside
    it: chunk, decode step, chunk, decode step ...; the slot in prefill
    is in no step's busy set, its table row is hidden from the step,
    and every array of its state (S, tails, K/V blocks) is bit for bit
    what it was after the step.  A third request waits behind the
    prompt in prefill and is prefilled only after its last chunk."""
    sched = ContinuousScheduler(engine, log_fn=lambda *a: None)
    short, long_, third = _prompt(1, 5), _prompt(2, 58), _prompt(3, 7)
    watch = _Watch(engine, sched, slot=1)
    try:
        t_short = sched.submit(short, max_new=NEW)
        t_long = sched.submit(long_, max_new=NEW)
        t_third = sched.submit(third, max_new=3)
        sched.start()
        got = [t.wait(timeout=300)["tokens"]
               for t in (t_short, t_long, t_third)]
    finally:
        sched.stop()
        watch.close()
    assert got == [_greedy(lm, short, NEW), _greedy(lm, long_, NEW),
                   _greedy(lm, third, 3)]
    kinds = [c[0] for c in watch.calls]
    # every prompt by the chunk programs, a short one as one last chunk
    assert "prefill" not in kinds
    assert watch.calls[0] == ("chunk", 0, 5, 0)
    first = kinds.index("chunk", 1)
    chunks = [c for c in watch.calls if c[0] == "chunk"]
    assert chunks[1:5] == [("chunk", 0, 16, 1), ("chunk", 16, 16, 1),
                           ("chunk", 32, 16, 1), ("chunk", 48, 10, 1)]
    assert chunks[5][1:3] == (0, 7) and len(chunks) == 6
    # one chunk, then one decode step of the running slot, and so on
    assert kinds[first:first + 7] == ["chunk", "decode"] * 3 + ["chunk"]
    for call in watch.calls[first:first + 7]:
        if call[0] == "decode":
            assert call[1] == (0,)                    # slot 1 is not busy
    # nothing was admitted while the prompt was in prefill
    assert watch.calls.index(chunks[5]) > first + 6
    # and the three steps between its chunks left its state bit for bit
    assert len(watch.kept) == 3
    for before, after in watch.kept:
        for name, entry in before.items():
            for key, value in entry.items():
                np.testing.assert_array_equal(after[name][key], value,
                                              err_msg=f"{name}/{key}")
    c = engine.stats
    assert c.cb_steps_between_chunks >= 3


def test_a_cancel_between_chunks_frees_the_blocks(lm, engine):
    sched = ContinuousScheduler(engine, log_fn=lambda *a: None)
    cancel = threading.Event()
    real = engine.dispatch_cb_chunk
    seen = []

    def chunk(params, pools, tokens, rows, start, last, row):
        seen.append(int(start))
        if len(seen) == 2:
            cancel.set()              # read before the third goes out
        return real(params, pools, tokens, rows, start, last, row)

    engine.dispatch_cb_chunk = chunk
    before = engine.stats.snapshot()["cancelled"]
    try:
        ticket = sched.submit(_prompt(4, 60), max_new=NEW,
                              cancel_event=cancel)
        after_it = sched.submit(_prompt(5, 20), max_new=2)
        sched.start()
        with pytest.raises(Cancelled, match="2 of 4 prefill chunks"):
            ticket.wait(timeout=300)
        assert after_it.wait(timeout=300)["tokens"] == _greedy(
            lm, _prompt(5, 20), 2)
        deadline = time.monotonic() + 30
        while sched.kv.blocks_in_use and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sched.kv.blocks_in_use == 0 and sched._prefilling is None
        assert not sched.kv.tables.any()
    finally:
        engine.dispatch_cb_chunk = real
        sched.stop()
    assert seen[:2] == [0, 16] and 32 not in seen[:3]
    assert engine.stats.snapshot()["cancelled"] == before + 1


def test_admission_reserves_the_prompts_blocks_before_its_first_chunk(
        lm, engine):
    """A pool that holds one long request: the second waits for the
    first's blocks, whole, and both are served."""
    net, params = lm
    spec = _spec(slots=2, cb_blocks=(CAP + NEW) // BL + 3)
    small = InferenceEngine(net, spec, params=params,
                            log_fn=lambda *a: None)
    small._compiled = dict(engine._compiled) if (
        spec.cb_blocks_per_slot == engine.spec.cb_blocks_per_slot
        and spec.cb_slots == engine.spec.cb_slots) else {}
    sched = ContinuousScheduler(small, log_fn=lambda *a: None)
    a, b = _prompt(6, 50), _prompt(7, 45)
    try:
        ta, tb = sched.submit(a, max_new=4), sched.submit(b, max_new=4)
        sched.start()
        assert ta.wait(timeout=300)["tokens"] == _greedy(lm, a, 4)
        assert tb.wait(timeout=300)["tokens"] == _greedy(lm, b, 4)
    finally:
        sched.stop()
    assert small.stats.cb_chunked_prompts == 2


def _kimi_engine():
    cfg = harness._tiny(harness.read_json(
        ROOT, "benchmark", "configs", "kimilinear-serve-l17-ep8.json"))
    model = serve_kimi.model_config(cfg, 64)
    net = build_net(model, "kTrain",
                    discover_input_shapes(model, force_synthetic=True))
    return InferenceEngine(net, _spec(), params=net.init_params(
        jax.random.PRNGKey(0)), log_fn=lambda *a: None)


def test_refusals_at_submit_say_why(engine):
    sched = ContinuousScheduler(engine, log_fn=lambda *a: None)
    with pytest.raises(ValueError, match=r"exceeds the cb prompt cap .64"):
        sched.submit(_prompt(8, CAP + 1))
    assert engine.chunks_prompts and engine.cb_prompt_limit == CAP
    # a latent cache cannot carry a chunk on: the cap is the widest rung
    latent = _kimi_engine()
    assert latent.cb_unchunked == ("kMLA",) and not latent.chunks_prompts
    assert latent.cb_prompt_limit == RUNG
    sched = ContinuousScheduler(latent, log_fn=lambda *a: None)
    with pytest.raises(ValueError, match=r"widest prefill program .16 "
                                         r"rows.*kMLA cannot"):
        sched.submit(_prompt(9, RUNG + 1))
    sched.submit(_prompt(9, RUNG))               # at the rung: queued
    # no ladder of chunks: the whole-prompt rungs and the decode step
    assert latent.warmup() == len(latent.spec.cb_prefill_widths) + 1


@pytest.mark.parametrize("kind,spec,word", [
    ("ring", {"window": 8}, "kAttention with a window"),
    ("plain", {}, "")])
def test_a_ring_keeps_a_model_to_one_chunk_and_plain_attention_does_not(
        kind, spec, word):
    from singa_tpu.models.generate import unchunked_layers
    from singa_tpu.models.transformer import hybrid_lm
    attn = {"num_heads": 2, "num_kv_heads": 1, "head_dim": 8, **spec}
    model = hybrid_lm(vocab_size=32, embed_dim=16,
                      mixers=[{"attention": attn}],
                      ffns=[{"dense": {"hidden_dim": 32}}], seq_len=16)
    net = build_net(model, "kTrain",
                    discover_input_shapes(model, force_synthetic=True))
    got = unchunked_layers(net)
    assert got == ((word + " (a ring of blocks)",) if word else ())


# -- spans and counters ----------------------------------------------------------

def test_the_paths_spans_and_counters(lm, engine):
    """`engine.cb_prefill` carries `start` and `width`,
    `scheduler.prefill` one span a chunk with the prompt's `corr`,
    `chunk` and `of`; the seven counters move by what three prompts (one
    of a single chunk, two of three) make them, on `/metrics` too."""
    names = ("cb_chunked_prompts", "cb_prefill_chunks", "cb_chunk_tokens",
             "cb_prefix_rows", "cb_steps_between_chunks", "cb_grouped_rows",
             "cb_grouped_row_slots", "cb_prefills", "cb_prefill_rows")
    c0 = {k: getattr(engine.stats, k) for k in names}
    sched = ContinuousScheduler(engine, log_fn=lambda *a: None)
    with obs.session(obs.ObsSpec()) as o:
        try:
            tickets = [sched.submit(_prompt(10 + n, n), max_new=3)
                       for n in (12, 40, 33)]
            sched.start()
            for t in tickets:
                t.wait(timeout=300)
        finally:
            sched.stop()
        events = o.tracer.events()
    d = {k: getattr(engine.stats, k) - c0[k] for k in names}
    assert d["cb_chunked_prompts"] == 3 and d["cb_prefills"] == 3
    assert d["cb_prefill_chunks"] == 1 + 3 + 3      # 12 | 16 16 8 | 16 16 1
    assert d["cb_chunk_tokens"] == d["cb_prefill_rows"] == 12 + 40 + 33
    assert d["cb_prefix_rows"] == 2 * (0 + 16 + 32)
    # the first request decodes while the second is in prefill
    assert d["cb_steps_between_chunks"] >= 2
    # tiny chunks stay under ROW_BLOCK: the dense walk, nothing grouped
    assert d["cb_grouped_rows"] == d["cb_grouped_row_slots"] == 0
    per_chunk = [e["args"] for e in events if e["name"] == "scheduler.prefill"
                 and "chunk" in e["args"]]
    assert [(a["chunk"], a["of"]) for a in per_chunk] == [(0, 1)] + [
        (0, 3), (1, 3), (2, 3)] * 2
    assert len({a["corr"] for a in per_chunk}) == 3
    assert [a["start"] for a in per_chunk] == [0] + [0, 16, 32] * 2
    assert "queue_ms" in per_chunk[1] and "queue_ms" not in per_chunk[2]
    handed = [e["args"] for e in events if e["name"] == "engine.cb_prefill"]
    assert {"start", "width", "rows"} <= set(handed[-1])
    assert [a["width"] for a in handed if "start" in a] == [
        16, 16, 16, 8, 16, 16, 8]
    registry = MetricsRegistry()
    engine.stats.register_into(registry)
    text = registry.render_prometheus()
    for k in names[:7]:
        assert f"singa_serve_{k}_total {getattr(engine.stats, k)}" in text, k


def test_grouped_rows_are_counted_where_a_chunk_takes_the_grouped_form(
        lm, engine, monkeypatch):
    """The engine's rule is the op's: at ROW_BLOCK 4 a 16-row chunk of
    this model (4 held, top 2) is grouped, and the counters say how
    many expert products that saved."""
    from singa_tpu.ops import moe as moe_ops
    assert engine.grouped_row_slots(16, 10) == 0
    monkeypatch.setattr(moe_ops, "ROW_BLOCK", 4)
    assert engine.grouped_row_slots(16, 10) == 10 * 4 * 4   # 4 layers x 4
    assert engine.grouped_row_slots(4, 3) == 0


def test_the_grouped_chunks_tiles_are_counted(lm, monkeypatch):
    """An engine whose 16-row chunks go grouped (ROW_BLOCK 4): a chunk's
    program hands back the rows of the tiles its products visited behind
    its routing counts, `cb_grouped_tile_rows` sums them, the pools keep
    their three counts a layer, and the tokens are the dense walk's."""
    from singa_tpu.ops import moe as moe_ops
    net, params = lm
    monkeypatch.setattr(moe_ops, "ROW_BLOCK", 4)
    eng = InferenceEngine(net, _spec(slots=2), params=params,
                          log_fn=lambda *a: None)
    assert eng.spec.cb_prefill_widths == (8, 16)
    read, seen = eng.fetch_cb_chunk, []

    def fetch(flying):
        seen.append(np.asarray(flying[0]))
        return read(flying)

    eng.fetch_cb_chunk = fetch
    prompt = _prompt(5, 40)
    sched = ContinuousScheduler(eng, log_fn=lambda *a: None)
    try:
        ticket = sched.submit(prompt, max_new=3)
        sched.start()
        toks = ticket.wait(timeout=300)["tokens"]
        pools = sched.kv.pools
    finally:
        sched.stop()
    assert toks == _greedy(lm, prompt, 3)
    # [token, assignments, experts touched, busiest, tiles' rows]
    assert [a.shape for a in seen] == [(5,)] * 3
    assert all(entry["routed"].shape == (3,)
               for entry in pools.values() if "routed" in entry)
    d = eng.stats.snapshot()
    assert d["cb_grouped_row_slots"] == 40 * 4 * 4
    assert d["cb_grouped_rows"] == sum(int(a[1]) for a in seen) > 0
    assert d["cb_grouped_tile_rows"] == sum(int(a[4]) for a in seen)
    for a, width in zip(seen, (16, 16, 8)):
        # 2 x width assignments a layer, `width` of them handed at a
        # time: one tile of the rows handed, a visit an expert touched
        # (two where its rows lie across the middle), three products
        assert 3 * width * a[2] <= a[4] <= 3 * width * (a[2] + 4)
        assert a[4] % (3 * width) == 0 and 3 * a[1] <= a[4]
