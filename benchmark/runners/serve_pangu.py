"""Runner: open-loop traffic through the continuous-batching engine, for
the openPangu-Ultra-MoE (`model_type: pangu_ultra_moe`) configurations
(rotated latent attention with a low-rank query, sandwich norms, sparse
experts of which this chip holds a share, and a multi-token prediction
module the engine drafts with: a decode step yields one or two SAMPLED
tokens a slot).

The same path as the other serving runners: `hybrid_lm(..., mtp=...)`
-> `NeuralNet` -> `InferenceEngine(net, spec, params=<the seed's
tree>)` -> `ContinuousScheduler(engine).start()`.  The run itself IS
`serve_kimi.run`, bound to this configuration's names for the length of
the call as `serve_trinity` binds it.  What differs is the comparison:
the tokens are sampled (temperature 1), so there is no first choice to
hold them to; `check_sample` here holds the LOG-PROBABILITIES the
program reports (`logprobs`: the main model's of every token it
emitted, the module's of every draft it made) to the reference's at the
same positions, teacher-forced over the emitted sequence
(`benchmark/reference/pangu.py`), and the TOKENS themselves to what
sampling from the main model has to give: the share of drafts that were
accepted (the token served two rows on IS the draft: a rejected one is
never drawn again) to the reference's mean of sum min(p, q), and the
reference's mean log-probability of the emitted tokens to minus its
mean entropy, each in standard errors of its own sample (`accept_z`,
`sampled_logprob_z`: a step that accepts what it should not, or draws
from another distribution than p, reports the right log p(token) all
the same).  This module's `run` adds those rows to the comparison
`serve_kimi.run` made.

Controls (`benchmark/probe.py seeds --control ...`), each through
`Compared`, so a control comes out as not correct: `fp8` and `no_rope`
read the same gaps for the reference computed with every matmul's
operands rounded to e4m3, or without the rotation (rows
`<control>.<name>`); `accept_all` runs the PROGRAM with a faulty rule
(every draft accepted, the right log-probability reported), which only
the two rows of the tokens can see; `greedy` runs the program at
temperature 0 and reads `served_gaps` as the greedy cells do (the
emitted tokens are the plain model's first choices; reported as the
control's reading, held to no limit yet).

A name only a program that drafts has is imported first, at the top: a
program that lacks it fails there, before anything is put on the
device.
"""

from __future__ import annotations

from singa_tpu.models.generate import mtp_module  # noqa: I001, F401 — first

import copy
import functools
from contextlib import contextmanager
from typing import Dict, Optional

import numpy as np

from singa_tpu.models.transformer import hybrid_lm

from benchmark import pangu_weights, weights
from benchmark.reference import pangu
from benchmark.runners import serve_kimi
from benchmark.runners.serve_cb import _dtype

COUNTERS = ("cb_steps", "cb_active_slot_steps", "cb_decode_steps",
            "cb_steps_ahead", "cb_prefills", "cb_prefill_width_rows",
            "cb_live_block_steps", "cb_routed_max_load",
            "cb_emit_slot_steps", "cb_tokens_emitted", "cb_drafts_made",
            "cb_drafts_accepted", "cb_stalls", "cb_stall_seconds",
            "cb_stall_wait_seconds") + serve_kimi.ROUTING_COUNTERS

# the rows of the comparison and their limits in float32; a cell's file
# carries the chip's (`limits`).  The last two are in standard errors of
# the run's own sample, so they hold at any size
DEFAULT_LIMITS = {"logprob_gap": 1e-2, "logprob_gap_mean": 1e-3,
                  "draft_logprob_gap_mean": 1e-3, "accept_z": 6.0,
                  "sampled_logprob_z": 6.0}


def model_config(cfg: Dict, seq_len: int):
    """The program's ModelConfig for the benchmark's configuration."""
    mla = {"num_heads": cfg["num_attention_heads"],
           "qk_nope_head_dim": cfg["qk_nope_head_dim"],
           "qk_rope_head_dim": cfg["qk_rope_head_dim"],
           "v_head_dim": cfg["v_head_dim"],
           "kv_lora_rank": cfg["kv_lora_rank"],
           "q_lora_rank": cfg["q_lora_rank"],
           "rope_theta": float(cfg["rope_theta"]),
           "epsilon": cfg["rms_norm_eps"]}
    moe = {"num_routed": cfg["router_width"],
           "experts_per_token": cfg["num_experts_per_tok"],
           "num_held": cfg["n_routed_experts"],
           "first_held": cfg["first_held_expert"],
           "expert_hidden": cfg["moe_intermediate_size"],
           "shared_hidden": (cfg["moe_intermediate_size"]
                             * cfg["n_shared_experts"]),
           "renormalize": cfg["norm_topk_prob"],
           "routed_scale": cfg["routed_scaling_factor"]}
    dense = {"hidden_dim": cfg["intermediate_size"],
             "activation": cfg["hidden_act"]}
    ffn = lambda f: {f: dense if f == "dense" else moe}      # noqa: E731
    kinds = pangu.layer_kinds(cfg)
    module = pangu.module_kinds(cfg)
    return hybrid_lm(
        vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
        mixers=[{"mla": mla}] * len(kinds), ffns=[ffn(f) for f in kinds],
        seq_len=seq_len, epsilon=cfg["rms_norm_eps"],
        post_norm=cfg["sandwich_norm"],
        mtp={"mixer": {"mla": mla}, "ffn": ffn(module[0])} if module
        else None)


def resident_bytes(cfg: Dict) -> Dict[str, int]:
    """What the chip holds before a request arrives, reckoned from the
    configuration: the weights, and one pool of latent rows (padded to
    whole lane tiles) for each main layer and for the module."""
    sv = cfg["serve"]
    item = 2 if sv["dtype"] == "bfloat16" else 4
    bl, slots = sv["cb_block_len"], sv["cb_slots"]
    row = -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) // 128) * 128
    table = -(-(sv["cb_prompt_cap"] + sv["max_new_tokens"]) // bl)
    layers = cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]
    return {"params": pangu_weights.param_count(cfg),
            "weights": pangu_weights.param_count(cfg) * item,
            "latent_pools": layers * (slots * table + 1) * bl * row * item,
            "draft_state": slots * cfg["vocab_size"] * 4}


def _counters(engine) -> Dict[str, int]:
    return {k: getattr(engine.stats, k) for k in COUNTERS}


class _Spans(serve_kimi._Spans):
    """`serve_kimi._Spans`, and on every `engine.decode` row, behind
    the step's assignments, the drafts it accepted."""

    def __init__(self, engine):
        super().__init__(engine)
        run, fetch = engine.run_cb_decode, engine.fetch_cb_decode

        def accepted(step):      # on the row the call has just appended
            self.rows[-1] += (int(step.count.sum())
                              - int(np.count_nonzero(step.count)),)
            return step

        def decode(params, pools, tokens, ntoks, tables):
            step, pools = run(params, pools, tokens, ntoks, tables)
            return accepted(step), pools

        engine.run_cb_decode = decode
        engine.fetch_cb_decode = lambda flying: accepted(fetch(flying))


def _sample(sent, count: int, seed: int):
    """The longest finished request and `count` - 1 others, as the
    other runners pick them."""
    done = [s for s in sent if s.served is not None]
    if not done:
        return []
    size = lambda s: len(s.req.tokens) + len(s.served)       # noqa: E731
    longest = max(done, key=size)
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng(int(seed) ^ 0x5EED)
    return [longest] + [rest[i] for i in rng.permutation(len(rest))
                        [:max(count - 1, 0)]]


def check_sample(read: Dict, cell, seed: int, sent, count: int,
                 control: Optional[str]):
    """Teacher-forced reference over a seeded sample of the finished
    requests, the longest among them.  Sampled tokens: the rows of
    `DEFAULT_LIMITS` into `read` (for `run`, which adds them to the
    comparison), and under a control of the reference its own into
    `read["control"]`; nothing for `serve_kimi.run`'s greedy rows.
    Greedy (temperature 0): `served_gaps` as `serve_kimi.check_sample`
    reads them, handed back as the control's reading."""
    cfg, sv = cell.config, cell.config["serve"]
    pick = _sample(sent, count, seed)
    if not pick:
        return None, 0, None
    # one width a run, whole lane tiles of it: the reference compiles
    # once, and is not run over padding a shorter sample does not need
    unit = min(1024, sv["cb_prompt_cap"])
    longest = len(pick[0].req.tokens) + len(pick[0].served)
    width = -(-longest // unit) * unit
    shape = (len(pick), width)
    toks, nxt, drafts = (np.zeros(shape, np.int32) for _ in range(3))
    mask, dmask = np.zeros(shape, bool), np.zeros(shape, bool)
    lp, lq = np.zeros(shape, np.float32), np.zeros(shape, np.float32)
    for r, s in enumerate(pick):
        seq = np.concatenate([s.req.tokens, np.asarray(s.served, np.int32)])
        plen = len(s.req.tokens)
        toks[r, :len(seq)] = seq
        nxt[r, :len(seq) - 1] = seq[1:]
        mask[r, plen - 1:len(seq) - 1] = True    # positions that were served
        got = s.ticket.wait(0)
        if "logprobs" in got:
            lp[r, plen - 1:len(seq) - 1] = got["logprobs"]
            # a draft for produced[k] was made from the row two before it
            for k, tok, logq in got["drafts"]:
                at = plen + k - 2
                if k < len(s.served):
                    drafts[r, at], lq[r, at], dmask[r, at] = tok, logq, True
    key = weights.seed_key(seed)
    table = {n: (s, d) for n, s, d in pangu_weights.leaf_table(cfg)}
    dtype = _dtype(sv["dtype"])

    def get_leaf(name):
        shape, draw = table[name]
        return pangu_weights.leaf(key, name, tuple(shape), draw, dtype)

    if float(sv["temperature"]) == 0.0:
        gap = pangu.served_gaps(toks, nxt, get_leaf, cfg)
        return None, int(mask.sum()), {"widest": float(np.max(gap[mask])),
                                       "mean": float(np.mean(gap[mask]))}
    ref = pangu.served_logprobs(toks, nxt, drafts, get_leaf, cfg,
                                sv["temperature"])
    # the token served two rows on from a draft's row IS the draft where
    # it was accepted: what is drawn after a rejection has p > q, the
    # draft p < q
    hit = np.zeros(shape, bool)
    hit[:, :-1] = nxt[:, 1:] == drafts[:, :-1]

    def z(found, mean, variance):
        """|found - mean| in standard errors of a sample of that size."""
        return (float(abs(found - mean.mean()))
                / max(float(np.sqrt(variance.sum())) / mean.size, 1e-12))

    def rows(logp, logq, of):
        """The rows of log-probabilities (the program's, or a control's
        of the reference) against the reference's, and of the served
        tokens against what `of` says sampling has to give."""
        main, draft = np.abs(logp - ref.logp)[mask], np.abs(
            logq - ref.logq)[dmask]
        out = {"logprob_gap": float(main.max()),
               "logprob_gap_mean": float(main.mean()),
               "sampled_logprob_z": z(of.logp[mask].mean(),
                                      -of.entropy[mask], of.spread[mask])}
        if draft.size:
            a = of.accept[dmask]
            out.update(draft_logprob_gap_mean=float(draft.mean()),
                       accept_z=z(hit[dmask].mean(), a, a * (1.0 - a)))
        return out

    read.update(rows(lp, lq, ref), drafts_compared=int(dmask.sum()))
    print(f"drafts accepted of those compared: {int(hit[dmask].sum())} of "
          f"{int(dmask.sum())}, the reference's mean of sum min(p, q): "
          f"{float(ref.accept[dmask].mean()) if dmask.any() else None!r}; "
          f"the reference's mean log p of the served tokens: "
          f"{float(ref.logp[mask].mean())!r}, minus its mean entropy: "
          f"{float(-ref.entropy[mask].mean())!r}", flush=True)
    if control in pangu.CONTROLS and control is not None:
        ctl = pangu.served_logprobs(toks, nxt, drafts, get_leaf, cfg,
                                    sv["temperature"], control=control)
        read["control"] = rows(ctl.logp, ctl.logq, ctl)
        # under the names `serve_kimi.run` prints a control's gaps by
        return None, int(mask.sum()), {
            "widest": read["control"]["logprob_gap"],
            "mean": read["control"]["logprob_gap_mean"], **read["control"]}
    return None, int(mask.sum()), None


@contextmanager
def _bound(read: Optional[Dict] = None):
    """`serve_kimi`'s build and run under this configuration's weights,
    builder, counters, spans and comparison (its readings into `read`)."""
    mine = {"kimi_weights": pangu_weights, "model_config": model_config,
            "program_name": pangu_weights.program_name,
            "_counters": _counters, "_Spans": _Spans,
            "check_sample": functools.partial(
                check_sample, {} if read is None else read)}
    theirs = {k: getattr(serve_kimi, k) for k in mine}
    for k, v in mine.items():
        setattr(serve_kimi, k, v)
    try:
        yield
    finally:
        for k, v in theirs.items():
            setattr(serve_kimi, k, v)


def build(cell, seed: int):
    """The engine and scheduler over the seed's weights, warmed."""
    print(f"resident: {resident_bytes(cell.config)}", flush=True)
    with _bound():
        return serve_kimi.build(cell, seed)


def _accept_all(rule):
    """The engine's accept-or-resample rule with a fault a log-
    probability cannot show: every draft accepted, the main model's
    log-probability of it reported."""
    import jax
    import jax.numpy as jnp

    def faulty(logits, draft, q, key, temperature, *filters):
        _, bonus, _, _, lp2 = rule(logits, draft, q, key, temperature,
                                   *filters)
        logp = jax.nn.log_softmax(logits[:, 0] / temperature, axis=-1)
        return (draft, bonus, jnp.ones(draft.shape, bool),
                jnp.take_along_axis(logp, draft[:, None], -1)[:, 0], lp2)

    return faulty


def run(cell, control: Optional[str] = None, **kw) -> Dict:
    from singa_tpu.serve import engine as program
    print(f"resident: {resident_bytes(cell.config)}", flush=True)
    rule = program.verify_draft
    if control == "greedy":        # the program at temperature 0
        cell = copy.copy(cell)
        cell.config = copy.deepcopy(cell.config)
        cell.config["serve"]["temperature"] = 0.0
    elif control == "accept_all":  # the program under a faulty rule
        program.verify_draft = _accept_all(rule)
    read: Dict = {}
    try:
        with _bound(read):
            out = serve_kimi.run(cell, control=control, **kw)
    finally:
        program.verify_draft = rule
    if not read:
        return out
    from benchmark import harness
    limits = {**DEFAULT_LIMITS, **cell.spec.get("limits", {})}
    cmp_ = harness.Compared()
    n = read["drafts_compared"]
    cmp_.add("drafts_compared", n, 1, ok=n >= 1)
    for name in DEFAULT_LIMITS:
        if name in read:
            cmp_.add(name, read[name], limits[name])
        if name in read.get("control", {}):
            cmp_.add(f"{control}.{name}", read["control"][name], limits[name])
    out["compared"] = out["compared"] + cmp_.rows
    out["correct"] = bool(out["correct"] and cmp_.ok)
    out["counts"]["drafts_compared"] = n
    return out
