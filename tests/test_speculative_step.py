"""The verify-and-draft decode step (a model with a multi-token
prediction module drafts with it; serve/engine.py, serve/scheduler.py):
at temperature 0 the tokens are those of the same model stepped without
its module, on the round-trip path and on the step ahead; at
temperature 1 the accept-or-resample rule draws from the main model's
distribution and accepts as often as sum min(p, q); what the engine
reports of its tokens and drafts is the reference's; a request that
retires on its first of two tokens drops the second; a model without a
module steps as it did."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, pangu_weights  # noqa: E402
from benchmark.reference import pangu  # noqa: E402
from benchmark.runners import serve_pangu  # noqa: E402
from singa_tpu.core.net import build_net  # noqa: E402
from singa_tpu.data import discover_input_shapes  # noqa: E402
from singa_tpu.models.generate import generate, verify_draft  # noqa: E402
from singa_tpu.models.transformer import hybrid_lm  # noqa: E402
from singa_tpu.serve.engine import (InferenceEngine, ServeSpec,  # noqa: E402
                                    StepTokens)
from singa_tpu.serve.scheduler import ContinuousScheduler  # noqa: E402

pytestmark = pytest.mark.serve

V, E, CAP = 48, 32, 16
MLA = {"num_heads": 2, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
       "v_head_dim": 8, "kv_lora_rank": 16, "q_lora_rank": 12,
       "rope_theta": 10000.0}
MOE = {"num_routed": 8, "experts_per_token": 2, "num_held": 8,
       "expert_hidden": 16, "shared_hidden": 16, "renormalize": True,
       "routed_scale": 2.5}


def _net(mtp: bool):
    model = hybrid_lm(
        V, E, [{"mla": MLA}] * 2, [{"dense": {"hidden_dim": 48}},
                                   {"moe": MOE}], seq_len=CAP,
        post_norm=True,
        mtp={"mixer": {"mla": MLA}, "ffn": {"moe": MOE}} if mtp else None)
    return build_net(model, "kTrain",
                     discover_input_shapes(model, force_synthetic=True))


@pytest.fixture(scope="module")
def pair():
    """A model whose module drafts WELL, and the same model without it.
    The blocks add little to the stream (their post norms' scales are
    small), so the next token mostly follows from the last one's
    embedding, which the module's entry lets through (W_eh = [I ; 0])
    past a block that adds nothing: most drafts are the main model's
    first choice, some are not."""
    net, plain = _net(True), _net(False)
    params = dict(net.init_params(jax.random.PRNGKey(5)))
    for name in params:
        if name.startswith("pn") and name.endswith("/scale"):
            params[name] = params[name] * (0.0 if name[2] == "2" else 0.12)
    eye = jnp.concatenate([jnp.eye(E), jnp.zeros((E, E))], 0)
    params["mtp/w_eh"] = eye.astype(params["mtp/w_eh"].dtype)
    mine = {k: v for k, v in params.items()
            if k in plain.init_params(jax.random.PRNGKey(0))}
    return net, params, plain, mine


def _serve(net, params, prompts, max_new, temperature=0.0, slots=2, seed=0):
    spec = ServeSpec(buckets=((1, CAP),), max_new_tokens=max(max_new),
                     temperature=temperature, cb="on", cb_slots=slots,
                     cb_block_len=4, cb_prompt_cap=CAP, seed=seed,
                     request_timeout_s=600.0)
    quiet = lambda *a, **k: None                             # noqa: E731
    engine = InferenceEngine(net, spec, params=params, log_fn=quiet)
    engine.load()
    engine.warmup()
    sched = ContinuousScheduler(engine, log_fn=quiet).start()
    tickets = [sched.submit(p, max_new=n) for p, n in zip(prompts, max_new)]
    out = [t.wait(timeout=600) for t in tickets]
    sched.stop()
    return out, engine


def _prompts(n, seed=3, vocab=V):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, int(rng.integers(2, CAP))).astype(np.int32)
            for _ in range(n)]


# -- temperature 0: the plain model's tokens -------------------------------------

@pytest.mark.parametrize("slots,ahead", [(4, False), (2, True)])
def test_greedy_tokens_are_the_plain_models_on_both_paths(pair, slots, ahead):
    """Four slots for three requests: every step is a round trip
    (`_decode_step`).  Two slots for six: the house is full and steps go
    out ahead (`_decode_ahead`), how far a slot advanced riding on the
    device.  Odd and even lengths: a request's last step may yield a
    second token it has no room for."""
    net, params, plain, mine = pair
    n = 6 if ahead else 3
    prompts, max_new = _prompts(n), [11, 12, 7, 9, 12, 10][:n]
    got, engine = _serve(net, params, prompts, max_new, slots=slots)
    want, other = _serve(plain, mine, prompts, max_new, slots=slots)
    st = engine.stats
    assert engine.drafts and not other.drafts
    assert (st.cb_steps_ahead > 0) == ahead
    # drafts were accepted and drafts were rejected
    assert 0 < st.cb_drafts_accepted < st.cb_drafts_made
    assert st.cb_tokens_emitted > st.cb_emit_slot_steps
    assert other.stats.cb_tokens_emitted == other.stats.cb_emit_slot_steps
    assert other.stats.cb_drafts_made == 0
    for g, w, m in zip(got, want, max_new):
        assert g["tokens"] == w["tokens"] and len(g["tokens"]) == m
        assert len(g["logprobs"]) == m and "logprobs" not in w
        assert all(lp <= 0 for lp in g["logprobs"])
    # and both are `generate`'s
    ref = np.asarray(generate(plain, mine, prompts[0][None], max_new[0]))[0]
    assert got[0]["tokens"] == list(ref)


def test_a_request_that_retires_on_its_first_of_two_drops_the_second(pair):
    """Every draft right (the blocks add nothing at all): each step
    yields two tokens, and a request of an odd length ends on a first."""
    net, params, plain, mine = pair
    params = {k: (v * 0 if k.startswith("pn") and k.endswith("/scale") else v)
              for k, v in params.items()}
    mine = {k: params[k] for k in mine}
    prompts = _prompts(2, seed=9)
    got, engine = _serve(net, params, prompts, [7, 8], slots=2)
    want, _ = _serve(plain, mine, prompts, [7, 8], slots=2)
    st = engine.stats
    assert st.cb_drafts_accepted == st.cb_drafts_made > 0
    assert [g["tokens"] for g in got] == [w["tokens"] for w in want]
    assert [len(g["tokens"]) for g in got] == [7, 8]
    # 1 from the prefill, then pairs: the seventh token was a step's first
    assert st.cb_tokens_emitted == 6 + 7


def test_the_emit_span_carries_the_tokens_it_handed_out(pair):
    """Under a session every `scheduler.emit` says how many tokens it
    handed out; they add up to the counter, and the spans of a step are
    the ones a model without a module has (tests/test_scheduler_paths.py)."""
    from singa_tpu import obs
    net, params, _, _ = pair
    with obs.session(obs.ObsSpec()) as o:
        got, engine = _serve(net, params, _prompts(3), [9, 8, 7], slots=2)
        events = o.tracer.events()
    obs.disable()
    emits = [e for e in events if e["name"] == "scheduler.emit"]
    st = engine.stats
    assert sum(e["args"]["tokens"] for e in emits) == st.cb_tokens_emitted
    assert any(e["args"]["tokens"] > e["args"]["slots"] for e in emits)
    # the first tokens came from the prefills, not from an emit loop
    assert st.cb_tokens_emitted == 9 + 8 + 7 - 3
    names = {e["name"] for e in events}
    assert {"engine.cb_decode", "engine.upload", "engine.dispatch",
            "engine.fetch", "scheduler.collect"} <= names


# -- temperature 1: the rule is lossless ---------------------------------------------

def test_the_rule_draws_from_the_main_models_distribution():
    """A vocabulary of 8, two fixed distributions, 40,000 slots under
    fixed keys: the emitted token is distributed as p whatever q is, the
    bonus as the second row's, and a draft is accepted as often as
    sum min(p, q)."""
    rng = np.random.default_rng(0)
    n, v = 40000, 8
    logits = jnp.asarray(rng.standard_normal((2, v)) * 1.5, jnp.float32)
    qlogits = jnp.asarray(rng.standard_normal(v) * 1.5, jnp.float32)
    p, p2 = (np.asarray(jax.nn.softmax(row)) for row in logits)
    q = jax.nn.softmax(qlogits)
    draft = jax.random.categorical(jax.random.PRNGKey(1), qlogits, shape=(n,))
    first, bonus, accepted, lp1, lp2 = verify_draft(
        jnp.broadcast_to(logits, (n, 2, v)), draft.astype(jnp.int32),
        jnp.broadcast_to(q, (n, v)), jax.random.PRNGKey(2), 1.0, 0, 0.0)
    freq = lambda t: np.bincount(np.asarray(t), minlength=v) / n  # noqa: E731
    # 3.5 standard errors of a share of 40,000 draws is under 0.009
    np.testing.assert_allclose(freq(first), p, atol=0.009)
    np.testing.assert_allclose(freq(bonus), p2, atol=0.009)
    overlap = float(np.minimum(p, np.asarray(q)).sum())
    assert 0.3 < overlap < 0.9
    assert abs(float(np.mean(np.asarray(accepted))) - overlap) < 0.009
    np.testing.assert_allclose(lp1, np.log(p)[np.asarray(first)], atol=1e-5)
    np.testing.assert_allclose(lp2, np.log(p2)[np.asarray(bonus)], atol=1e-5)
    # q == p: every draft is accepted, nothing is left to draw from
    same = verify_draft(jnp.broadcast_to(logits, (n, 2, v)),
                        draft.astype(jnp.int32),
                        jnp.broadcast_to(jnp.asarray(p), (n, v)),
                        jax.random.PRNGKey(3), 1.0, 0, 0.0)
    assert bool(np.asarray(same[2]).all())
    # temperature 0: exact match, q is not read
    hard = verify_draft(jnp.broadcast_to(logits, (n, 2, v)),
                        draft.astype(jnp.int32), None, None, 0.0, 0, 0.0)
    best = int(np.argmax(p))
    assert (np.asarray(hard[0]) == best).all()
    np.testing.assert_array_equal(np.asarray(hard[2]),
                                  np.asarray(draft) == best)


# -- what the engine reports is the reference's ------------------------------------------

CFG = harness._tiny(harness.read_json(
    ROOT, "benchmark", "configs", "openpangu-ultra-moe-serve-l5-ep32.json"))


def test_served_logprobs_and_drafts_are_the_references():
    """The configuration's tiny size sampled at temperature 1 in a full
    house: every emitted token's log-probability and every draft's,
    teacher-forced through `benchmark/reference/pangu.py` over the
    emitted sequence.  A rejected draft's row that was attended, a row
    not overwritten, a draft misplaced by one, would each show here."""
    model = serve_pangu.model_config(CFG, CAP)
    net = build_net(model, "kTrain",
                    discover_input_shapes(model, force_synthetic=True))
    made = pangu_weights.tree(CFG, 17, jnp.float32)
    params = {pangu_weights.program_name(k): v for k, v in made.items()}
    prompts = _prompts(5, seed=21, vocab=CFG["vocab_size"])
    max_new = [14, 9, 12, 16, 11]
    with jax.default_matmul_precision("highest"):
        got, engine = _serve(net, params, prompts, max_new, temperature=1.0,
                             slots=2, seed=4)
        st = engine.stats
        assert st.cb_steps_ahead > 0
        assert 0 < st.cb_drafts_accepted < st.cb_drafts_made
        width = CAP + 16
        for out, prompt, m in zip(got, prompts, max_new):
            seq = np.concatenate([prompt, out["tokens"]]).astype(np.int32)
            plen = len(prompt)
            toks, nxt, drafts = (np.zeros((1, width), np.int32)
                                 for _ in range(3))
            toks[0, :len(seq)] = seq
            nxt[0, :len(seq) - 1] = seq[1:]
            rows = {plen + k - 2: (tok, lq) for k, tok, lq in out["drafts"]
                    if k < m}
            for at, (tok, _) in rows.items():
                drafts[0, at] = tok
            ref = pangu.served_logprobs(
                toks, nxt, drafts, lambda n: made[n], CFG, 1.0)
            np.testing.assert_allclose(out["logprobs"],
                                       ref.logp[0, plen - 1:len(seq) - 1],
                                       atol=2e-3)
            assert len(rows) >= 3
            np.testing.assert_allclose([lq for _, lq in rows.values()],
                                       ref.logq[0, list(rows)], atol=2e-3)
            # what sampling has to give on average, from the same pass
            main, module = pangu.logits(toks, lambda n: made[n], CFG)
            p, q = (np.asarray(jax.nn.softmax(x[0], -1))
                    for x in (main, module))
            at = sorted(rows)
            np.testing.assert_allclose(
                ref.accept[0, at],
                [np.minimum(p[i + 1], q[i]).sum() for i in at], atol=1e-4)
            np.testing.assert_allclose(
                ref.entropy[0, at], -(p[at] * np.log(p[at])).sum(-1),
                atol=1e-4)
            np.testing.assert_allclose(
                ref.spread[0, at], (p[at] * np.log(p[at]) ** 2).sum(-1)
                - ref.entropy[0, at] ** 2, atol=1e-3)


# -- a model without a module steps as it did ---------------------------------------------

def test_a_model_without_a_module_steps_as_it_did(pair):
    _, _, plain, mine = pair
    prompts = _prompts(3, seed=12)
    got, engine = _serve(plain, mine, prompts, [6, 6, 6], slots=2)
    assert not engine.drafts
    for g, p in zip(got, prompts):
        assert set(g) == {"tokens", "step", "finish", "slots"}
        assert g["tokens"] == list(np.asarray(generate(plain, mine, p[None],
                                                       6))[0])
    # its decode step takes and gives what it took and gave
    compiled = engine._compile_cb("decode")
    assert len(compiled.in_avals[0]) == 2 + 4      # params, pools + 4 small
    step = engine.fetch_cb_decode(np.arange(2 + engine._cb_tail,
                                            dtype=np.int32))
    assert isinstance(step, np.ndarray) and not isinstance(step, StepTokens)
    assert list(step) == [0, 1]
