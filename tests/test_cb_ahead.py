"""The full house (serve/scheduler.py `_decode_ahead`): while every
slot is busy nothing can be admitted, so a decode step is handed to the
device before the step before it is read, its tokens going in as they
lie on the device.  Held here: the tokens stay `generate()`'s, a slot
that retires drops the token it made too many, a prefill goes behind
the step in flight, and a house that is not full takes the ordinary
path, call for call."""

import threading

import jax
import numpy as np
import pytest

from singa_tpu.core.net import build_net
from singa_tpu.models.generate import generate
from singa_tpu.models.transformer import transformer_lm
from singa_tpu.serve import Cancelled, InferenceEngine, ServeSpec
from singa_tpu.serve.scheduler import ContinuousScheduler

pytestmark = pytest.mark.serve

VOCAB, SEQ, NEW = 64, 16, 12
SHAPES = {"data": {"input": (SEQ,), "target": (SEQ,)}}
CALLS = ("run_cb_prefill", "dispatch_cb_prefill", "fetch_cb_prefill",
         "run_cb_decode", "dispatch_cb_decode", "fetch_cb_decode")


@pytest.fixture(scope="module")
def lm():
    cfg = transformer_lm(vocab_size=VOCAB, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=SEQ, batchsize=2)
    net = build_net(cfg, "kTest", SHAPES)
    return net, net.init_params(jax.random.PRNGKey(0))


def _engine(lm, slots, eos=None):
    net, params = lm
    spec = ServeSpec(buckets=((1, SEQ),), max_new_tokens=NEW,
                     temperature=0.0, eos_id=eos, request_timeout_s=120.0,
                     cb="on", cb_slots=slots, cb_block_len=4)
    return InferenceEngine(net, spec, params=params, log_fn=lambda s: None)


def _logged(engine):
    """Every call of the engine's six cb entry points, in order, with
    its arguments (the scheduler looks them up on the instance)."""
    log = []
    for name in CALLS:
        def wrapped(*args, _name=name, _real=getattr(engine, name)):
            log.append((_name, args))
            return _real(*args)
        setattr(engine, name, wrapped)
    return log


def _prompts(seed, plens):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, p).astype(np.int32) for p in plens]


def _serve(engine, prompts, news, hold=False):
    """All requests queued before the loop starts when `hold`: the
    house is full from the first step."""
    sched = ContinuousScheduler(engine, log_fn=lambda s: None)
    if not hold:
        sched.start()
    try:
        tickets = [sched.submit(p, max_new=n) for p, n in zip(prompts, news)]
        sched.start()
        return [t.wait(timeout=300) for t in tickets]
    finally:
        sched.stop()


def _ref(lm, prompt, new, eos=None):
    net, params = lm
    out = np.asarray(generate(net, params, prompt[None], new))[0].tolist()
    return out[:out.index(eos) + 1] if eos in out else out


@pytest.mark.parametrize("slots", [1, 2, 3])
def test_a_full_house_serves_generates_tokens(lm, slots):
    prompts = _prompts(slots, (1, 9, SEQ, 5, 3, 12, 7))
    news = [NEW, 3, 7, NEW, 2, 9, 5]
    engine = _engine(lm, slots)
    log = _logged(engine)
    outs = _serve(engine, prompts, news, hold=True)
    for p, n, out in zip(prompts, news, outs):
        assert out["tokens"] == _ref(lm, p, n) and out["finish"] == "length"
    ahead = [a for name, a in log if name == "dispatch_cb_decode"]
    assert ahead, "the house was full and no step went ahead"
    # a step's tokens went in as they lay on the device
    assert any(isinstance(a[2], jax.Array) for a in ahead)
    assert engine.stats.cb_prefills == len(prompts)


def test_a_step_is_handed_over_before_the_one_before_is_read(lm):
    engine = _engine(lm, 2)
    log = _logged(engine)
    _serve(engine, _prompts(5, (4, 6)), [NEW, NEW], hold=True)
    decodes = [n for n, _ in log if n.endswith("_cb_decode")]
    # two prefills fill the house; from then on: one step ahead
    assert decodes[:5] == ["dispatch_cb_decode", "dispatch_cb_decode",
                           "fetch_cb_decode", "dispatch_cb_decode",
                           "fetch_cb_decode"]
    assert "run_cb_decode" not in decodes
    # both retire in one step: one step was made too many, and is
    # read all the same (its counts are the stats')
    assert decodes.count("dispatch_cb_decode") == NEW
    assert decodes.count("fetch_cb_decode") == NEW
    assert engine.stats.cb_decode_steps == NEW


def test_a_house_that_is_not_full_takes_the_ordinary_path(lm):
    engine = _engine(lm, 4)
    log = _logged(engine)
    prompts = _prompts(6, (4, 6, 2))
    outs = _serve(engine, prompts, [NEW, 5, 8], hold=True)
    for p, n, out in zip(prompts, [NEW, 5, 8], outs):
        assert out["tokens"] == _ref(lm, p, n)
    names = [n for n, _ in log]
    # `run_*` is dispatch + fetch: the scheduler itself called neither
    assert names.count("run_cb_prefill") == 3
    assert names.count("dispatch_cb_prefill") == 3
    assert names.count("run_cb_decode") == NEW - 1
    assert "dispatch_cb_decode" not in names
    assert "fetch_cb_decode" not in names


def test_a_prefill_goes_behind_the_step_in_flight(lm):
    """Three requests, two slots: the third is admitted while a step is
    in flight.  Its prefill is handed over first, the step's tokens are
    read while it runs, then its first token."""
    engine = _engine(lm, 2)
    log = _logged(engine)
    prompts = _prompts(7, (3, 8, 5))
    news = [4, NEW, 6]
    outs = _serve(engine, prompts, news, hold=True)
    for p, n, out in zip(prompts, news, outs):
        assert out["tokens"] == _ref(lm, p, n)
    names = [n for n, _ in log]
    assert names.count("run_cb_prefill") == 2
    i = max(k for k, n in enumerate(names) if n == "dispatch_cb_prefill")
    assert names[i - 1] != "run_cb_prefill"
    assert names[i:i + 3] == ["dispatch_cb_prefill", "fetch_cb_decode",
                              "fetch_cb_prefill"]


@pytest.mark.parametrize("rung,plens", [(4, (3, 4, 5)), (8, (7, 8, 9)),
                                        (16, (14, 15, 16))])
def test_a_prefill_behind_a_step_takes_its_prompts_rung(lm, monkeypatch,
                                                        rung, plens):
    """The ladder (serve/engine.py `cb_prefill_widths`; here 4, 8, 16)
    through the full house: two requests fill it, and the three
    admitted behind steps in flight lie on both sides of a rung."""
    from singa_tpu.serve import engine as engine_mod
    monkeypatch.setattr(engine_mod, "CB_PREFILL_FLOOR", 4)
    engine = _engine(lm, 2)
    assert engine.spec.cb_prefill_widths == (4, 8, 16)
    log = _logged(engine)
    prompts = _prompts(10 + rung, (6, 2) + plens)
    news = [3, 5, 4, NEW, 6]
    outs = _serve(engine, prompts, news, hold=True)
    for p, n, out in zip(prompts, news, outs):
        assert out["tokens"] == _ref(lm, p, n), f"plen={p.size}"
    names = [n for n, _ in log]
    assert names.count("run_cb_prefill") == 2      # the two that filled it
    behind = [(a[2].shape[1], a[3]) for k, (n, a) in enumerate(log)
              if n == "dispatch_cb_prefill"
              and names[k - 1] != "run_cb_prefill"]
    assert behind == [(engine.spec.cb_prefill_width(p), p) for p in plens]
    assert {w for w, _ in behind} == ({rung, 2 * rung} if rung < 16
                                      else {16})
    assert engine.stats.cb_prefill_width_rows == 8 + 4 + sum(
        w for w, _ in behind)


@pytest.mark.parametrize("slots", [1, 2])
def test_an_eos_nobody_foresaw_drops_the_token_made_too_many(lm, slots):
    prompts = _prompts(8, (5, 2, 9, 4))
    eos = _ref(lm, prompts[0], NEW)[3]
    engine = _engine(lm, slots, eos=eos)
    outs = _serve(engine, prompts, [NEW] * 4, hold=True)
    finishes = set()
    for p, out in zip(prompts, outs):
        want = _ref(lm, p, NEW, eos)
        assert out["tokens"] == want
        finishes.add(out["finish"])
        assert out["finish"] == ("eos" if want[-1] == eos else "length")
    assert "eos" in finishes


def test_a_cancelled_request_leaves_a_full_house_and_the_rest_is_served(lm):
    engine = _engine(lm, 2)
    sched = ContinuousScheduler(engine, log_fn=lambda s: None)
    prompts = _prompts(9, (4, 7, 3))
    cancel = threading.Event()
    first = sched.submit(prompts[0], max_new=NEW, cancel_event=cancel)
    rest = [sched.submit(p, max_new=NEW) for p in prompts[1:]]
    emit = first._emit

    def cancelling(tok):
        emit(tok)
        cancel.set()              # after its first token: mid-decode

    first._emit = cancelling
    sched.start()
    try:
        with pytest.raises(Cancelled):
            first.wait(timeout=300)
        outs = [t.wait(timeout=300) for t in rest]
    finally:
        sched.stop()
    for p, out in zip(prompts[1:], outs):
        assert out["tokens"] == _ref(lm, p, NEW)
    assert sched._flying is None and not sched._active.any()
