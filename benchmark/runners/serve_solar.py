"""Runner: open-loop traffic of LONG prompts through the
continuous-batching engine, for the Solar-Open2 (`model_type:
solar_open2`) configurations (gated NoPE grouped-query attention in one
layer of four, Kimi Delta Attention with negative eigenvalues in the
other three, sparse experts of which this chip holds a share behind
every mixer).

The same path as the other serving runners: `hybrid_lm(...)` ->
`NeuralNet` -> `InferenceEngine(net, spec, params=<the seed's tree>)`
-> `ContinuousScheduler(engine).start()`.  The run itself IS
`serve_kimi.run` (the pre-roll onto a busy house, the window, the
counters, the result), bound to this configuration's names for the
length of the call as `serve_trinity` binds it.  Three of those names
are this module's own, because what the cell exists for is in them:

* `build`: the spec carries `cb_prefill_rung` (the cap lies past the
  widest compiled prefill: a prompt goes in chunks) and a pool smaller
  than every slot's worst case (`cb_pool_tokens`), and a long prompt is
  among the warm requests;
* `_Spans`: a row a chunk handed to the device, ("engine.chunk", t0,
  t1, real rows, start, width, last, assignments on held experts), t1
  when it was read back;
* `check_sample`: the reference at full widths over sequences of up to
  33,792 positions runs a sequence at a time (`solar_open2.served_gaps`),
  and of the requests compared at least two have prompts of four chunks
  or more, the first token behind the last chunk among the tokens
  compared.

Controls (`benchmark/probe.py seeds --control ...`), each through
`Compared` (rows `<control>.<name>`), so a control comes out as not
correct: `fp8` (the reference with every matmul's operands rounded to
e4m3), `cold_chunk` (the reference started at the prompt's last chunk
boundary: state, tails and prefix dropped there), `pos_eig` (the
reference with beta not doubled).

A name the chunked prefill brought to the program is imported first, at
the top: a program that lacks it fails there, before anything is put on
the device.
"""

from __future__ import annotations

from singa_tpu.models.generate import forward_chunk  # noqa: I001, F401 — first

import time
from contextlib import contextmanager
from typing import Dict, Optional

import numpy as np

from singa_tpu.models.transformer import hybrid_lm

from benchmark import harness, solar_weights, weights
from benchmark.reference import solar_open2
from benchmark.runners import serve_kimi
from benchmark.runners.serve_cb import DEFAULT_LIMITS, _dtype

COUNTERS = ("cb_steps", "cb_active_slot_steps", "cb_decode_steps",
            "cb_live_block_steps", "cb_routed_max_load", "cb_prefills",
            "cb_prefill_rows", "cb_chunked_prompts", "cb_prefill_chunks",
            "cb_chunk_tokens", "cb_prefix_rows", "cb_steps_between_chunks",
            "cb_grouped_rows", "cb_grouped_row_slots", "cb_tokens_emitted"
            ) + serve_kimi.ROUTING_COUNTERS
# prompts of this many chunks or more, at least this many compared
LONG_CHUNKS, LONG_COMPARED = 4, 2


def model_config(cfg: Dict, seq_len: int):
    """The program's ModelConfig for the benchmark's configuration."""
    lin = cfg["linear_attn_config"]
    kda = {"num_heads": lin["num_heads"], "head_dim": lin["head_dim"],
           "conv_kernel": lin["short_conv_kernel_size"],
           "epsilon": cfg["rms_norm_eps"],
           "neg_eigval": cfg["kda_allow_neg_eigval"]}
    gqa = {"num_heads": cfg["num_attention_heads"],
           "num_kv_heads": cfg["num_key_value_heads"],
           "head_dim": cfg["head_dim"], "rope": cfg["use_rope"],
           "gate": cfg["use_gqa_gate"]}
    moe = {"num_routed": cfg["router_width"],
           "experts_per_token": cfg["num_experts_per_tok"],
           "num_held": cfg["n_routed_experts"],
           "first_held": cfg["first_held_expert"],
           "expert_hidden": cfg["moe_intermediate_size"],
           "shared_hidden": (cfg["moe_intermediate_size"]
                             * cfg["n_shared_experts"]),
           "renormalize": cfg["norm_topk_prob"],
           "routed_scale": cfg["routed_scaling_factor"]}
    kinds = solar_open2.layer_kinds(cfg)
    return hybrid_lm(
        vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
        mixers=[{"attention": gqa} if m == "gqa" else {"kda": kda}
                for m in kinds],
        ffns=[{"moe": moe} for _ in kinds],
        seq_len=seq_len, epsilon=cfg["rms_norm_eps"])


def pool_blocks(cfg: Dict) -> int:
    """The pool, null block and all, that holds `cb_pool_tokens`."""
    sv = cfg["serve"]
    return -(-sv["cb_pool_tokens"] // sv["cb_block_len"]) + 1


def resident_bytes(cfg: Dict) -> Dict[str, int]:
    """What the chip holds before a request arrives, reckoned from the
    configuration: the weights, every slot's KDA state and tails, the
    K/V pool of the attention layers."""
    from benchmark import solar_opcount
    sv = cfg["serve"]
    item = 2 if sv["dtype"] == "bfloat16" else 4
    return {"params": solar_weights.param_count(cfg),
            "weights": solar_weights.param_count(cfg) * item,
            "slot_states": sv["cb_slots"] * solar_opcount.slot_state_bytes(
                cfg, item),
            "kv_pool": pool_blocks(cfg) * sv["cb_block_len"]
            * solar_opcount.kv_bytes_per_token(cfg, item)}


def _counters(engine) -> Dict[str, int]:
    return {k: getattr(engine.stats, k) for k in COUNTERS}


class _Spans(serve_kimi._Spans):
    """`serve_kimi._Spans`, and a row a chunk of a prompt that is
    prefilled in several: ("engine.chunk", t0 its hand-over, t1 when it
    was read back (the last chunk: fetched first token; another: the
    next chunk's turn, so a decode step may lie inside), real rows,
    start, width, last, assignments on held experts).  The annotation
    `engine.prefill` lies around the wait for a LAST chunk, as around a
    whole prompt's."""

    def __init__(self, engine):
        import jax
        super().__init__(engine)
        give, read = engine.dispatch_cb_chunk, engine.fetch_cb_chunk
        handed: Dict[int, tuple] = {}

        def dispatch_chunk(params, pools, tokens, rows, start, last, row):
            t0 = time.perf_counter()
            flying, pools = give(params, pools, tokens, rows, start, last,
                                 row)
            handed[id(flying)] = (t0, int(rows), int(start),
                                  int(tokens.shape[1]), bool(last))
            return flying, pools

        def fetch_chunk(flying):
            t0, rows, start, width, last = handed.pop(id(flying))
            if last:
                with jax.profiler.TraceAnnotation("engine.prefill"):
                    out = read(flying)
            else:
                out = read(flying)
            self.rows.append(("engine.chunk", t0, time.perf_counter(), rows,
                              start, width, last, out[1]))
            return out

        engine.dispatch_cb_chunk, engine.fetch_cb_chunk = \
            dispatch_chunk, fetch_chunk


def build(cell: harness.Cell, seed: int):
    """The engine and scheduler over the seed's weights, warmed: as
    `serve_kimi.build`, with the spec's widest rung and pool, and a
    prompt of three chunks among the warm requests."""
    import jax
    from singa_tpu.core.net import build_net
    from singa_tpu.data import discover_input_shapes
    from singa_tpu.serve.engine import InferenceEngine, ServeSpec
    from singa_tpu.serve.scheduler import ContinuousScheduler

    laps = harness.Laps()
    cfg, sv = cell.config, cell.config["serve"]
    print(f"resident: {resident_bytes(cfg)}", flush=True)
    model = model_config(cfg, sv["cb_prompt_cap"])
    net = build_net(model, "kTrain",
                    discover_input_shapes(model, force_synthetic=True))
    laps.lap("net")
    made = solar_weights.tree(cfg, seed, _dtype(sv["dtype"]))
    params = {solar_weights.program_name(k): v for k, v in made.items()}
    del made
    jax.block_until_ready(params)
    laps.lap("weights")
    spec = ServeSpec(
        buckets=((1, sv["cb_prompt_cap"]),),
        max_new_tokens=sv["max_new_tokens"],
        temperature=sv["temperature"], eos_id=None,
        queue_capacity=sv["queue_capacity"],
        request_timeout_s=sv["request_timeout_s"], cb=sv["cb"],
        cb_slots=sv["cb_slots"], cb_block_len=sv["cb_block_len"],
        cb_blocks=pool_blocks(cfg), cb_prompt_cap=sv["cb_prompt_cap"],
        cb_prefill_rung=sv["cb_prefill_rung"])
    quiet = lambda *a, **k: None                      # noqa: E731
    engine = InferenceEngine(net, spec, params=params, log_fn=quiet)
    del params
    engine.load()
    engine.warmup()      # the chunk ladder and the decode step, no others
    laps.lap("programs")
    sched = ContinuousScheduler(engine, log_fn=quiet).start()
    # every kind of program once on the device before the window opens
    rng = np.random.default_rng(0)
    long = 2 * spec.cb_prefill_len + spec.cb_prefill_widths[0] // 2
    for t in [sched.submit(rng.integers(0, cfg["vocab_size"], n), max_new=3)
              for n in (8, long, 8)]:
        t.wait(timeout=600)
    jax.block_until_ready(sched.kv.pools)
    laps.lap("warm_requests")
    return engine, sched


def _pick(done, count: int, seed: int, chunk: int):
    """`count` of the finished requests, drawn by the seed: those of
    `LONG_CHUNKS` chunks or more first, until `LONG_COMPARED` are in,
    then any.  (Not the longest finished, as the other cells pick: at
    the published widths the reference of ONE request of 33,792
    positions takes a minute of the chip.)"""
    is_long = lambda s: -(-len(s.req.tokens) // chunk) >= LONG_CHUNKS  # noqa: E731
    rng = np.random.default_rng(int(seed) ^ 0x5EED)
    drawn = [done[i] for i in rng.permutation(len(done))]
    pick = [s for s in drawn if is_long(s)][:min(LONG_COMPARED, count)]
    pick += [s for s in drawn if s not in pick][:count - len(pick)]
    return pick, sum(map(is_long, pick))


def check_sample(read: Dict, cell, seed: int, sent, count: int,
                 control: Optional[str]):
    """Teacher-forced reference over a seeded sample of the finished
    requests (`_pick`), a sequence at a time; how many of them were
    long goes into `read` for this module's `run`."""
    cfg, sv = cell.config, cell.config["serve"]
    done = [s for s in sent if s.served is not None]
    if not done:
        return None, 0, None
    pick, read["long"] = _pick(done, count, seed, sv["cb_prefill_rung"])
    read["prompts"] = [len(s.req.tokens) for s in pick]
    key = weights.seed_key(seed)
    table = {n: (s, d) for n, s, d in solar_weights.leaf_table(cfg)}
    dtype = _dtype(sv["dtype"])

    def get_leaf(name):
        shape, draw = table[name]
        return solar_weights.leaf(key, name, tuple(shape), draw, dtype)

    out = solar_open2.served_gaps(
        [np.concatenate([s.req.tokens, np.asarray(s.served, np.int32)])
         for s in pick], read["prompts"], get_leaf, cfg, control=control,
        chunk=sv["cb_prefill_rung"],
        length_step=min(solar_open2.LENGTH_STEP,
                        sv["cb_prompt_cap"] + sv["max_new_tokens"]))
    gaps, ctls = (out, None) if control is None else out
    both = lambda xs: {"widest": float(np.max(np.concatenate(xs))),  # noqa: E731
                       "mean": float(np.mean(np.concatenate(xs)))}
    return (both(gaps), int(sum(len(g) for g in gaps)),
            None if ctls is None else both(ctls))


@contextmanager
def _bound(read: Dict):
    """`serve_kimi.run` under this configuration's build, counters,
    spans and comparison (what it picked goes into `read`)."""
    import functools
    mine = {"build": build, "_counters": _counters, "_Spans": _Spans,
            "check_sample": functools.partial(check_sample, read)}
    theirs = {k: getattr(serve_kimi, k) for k in mine}
    for k, v in mine.items():
        setattr(serve_kimi, k, v)
    try:
        yield
    finally:
        for k, v in theirs.items():
            setattr(serve_kimi, k, v)


def run(cell, control: Optional[str] = None, **kw) -> Dict:
    read: Dict = {}
    with _bound(read):
        out = serve_kimi.run(cell, control=control, **kw)
    cmp_ = harness.Compared()
    if "long" in read:
        print(f"compared prompts: {read['prompts']}", flush=True)
        cmp_.add("long_prompts_compared", read["long"], LONG_COMPARED,
                 ok=read["long"] >= LONG_COMPARED)
    if out.get("control"):
        limits = {**DEFAULT_LIMITS, **cell.spec.get("limits", {})}
        cmp_.add(f"{control}.served_gap", out["control"]["widest"],
                 limits["served_gap"])
        cmp_.add(f"{control}.served_gap_mean", out["control"]["mean"],
                 limits["served_gap_mean"])
    out["compared"] = out["compared"] + cmp_.rows
    out["correct"] = bool(out["correct"] and (cmp_.ok or not cmp_.rows))
    return out
