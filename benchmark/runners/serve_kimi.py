"""Runner: open-loop traffic through the continuous-batching engine, for
the Kimi-Linear configurations (KDA + MLA mixers, sparse experts of
which this chip holds a share).

The same path as `serve_cb`: `hybrid_lm(...)` -> `NeuralNet` ->
`InferenceEngine(net, spec, params=<the seed's tree>)` ->
`ContinuousScheduler(engine).start()`; requests enter through
`scheduler.submit` and stream through `StreamTicket`.  The traffic's
driving, the draining and the token clock are `serve_cb`'s own; the
weights (`benchmark/kimi_weights.py`), the reference
(`benchmark/reference/kimi_linear.py`) and the counters read back (the
routing counts and the state's bytes of `ServeStats`) are this
configuration's.

The program's builder is imported first, at the top: a program that
lacks these layers fails there, before anything is put on the device.
"""

from __future__ import annotations

from singa_tpu.models.transformer import hybrid_lm     # noqa: I001 — first

import gc
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

import numpy as np

from benchmark import harness, kimi_weights, stats, weights
from benchmark.reference import kimi_linear
from benchmark.runners.serve_cb import (DEFAULT_LIMITS, _dtype, _Sent,
                                        _TokenClock, finish)
from benchmark.trace import capture

PROGRAM_NAMES = {"embed": "embed/embedding", "final_norm": "ln_f/scale",
                 "head": "loss/w", "mix_norm": "ln{i}a/scale",
                 "ffn_norm": "ln{i}b/scale"}
ROUTING_COUNTERS = ("cb_routed_layer_steps", "cb_routed_assignments",
                    "cb_routed_experts_touched")


def program_name(leaf: str) -> str:
    """The program's name for one of the benchmark's leaves:
    `L3.kda.wq` is `kda3/wq`, `L3.moe.router` `moe3/router`."""
    if not leaf.startswith("L"):
        return PROGRAM_NAMES[leaf]
    i, part = leaf[1:].split(".", 1)
    if part in PROGRAM_NAMES:
        return PROGRAM_NAMES[part].format(i=i)
    kind, name = part.split(".", 1)
    if kind == "ffn":
        name = {"w_gate": "w1", "w_up": "w3", "w_down": "w2"}[name]
    return f"{kind}{i}/{name}"


def model_config(cfg: Dict, seq_len: int):
    """The program's ModelConfig for the benchmark's configuration."""
    lin = cfg["linear_attn_config"]
    kda = {"num_heads": lin["num_heads"], "head_dim": lin["head_dim"],
           "conv_kernel": lin["short_conv_kernel_size"],
           "epsilon": cfg["rms_norm_eps"]}
    mla = {"num_heads": cfg["num_attention_heads"],
           "qk_nope_head_dim": cfg["qk_nope_head_dim"],
           "qk_rope_head_dim": cfg["qk_rope_head_dim"],
           "v_head_dim": cfg["v_head_dim"],
           "kv_lora_rank": cfg["kv_lora_rank"],
           "epsilon": cfg["rms_norm_eps"]}
    moe = {"num_routed": cfg["n_routed_experts"],
           "experts_per_token": cfg["num_experts_per_token"],
           "num_held": cfg["num_experts"],
           "first_held": cfg["first_held_expert"],
           "expert_hidden": cfg["moe_intermediate_size"],
           "shared_hidden": (cfg["moe_intermediate_size"]
                             * cfg["num_shared_experts"]),
           "renormalize": cfg["moe_renormalize"],
           "routed_scale": cfg["routed_scaling_factor"]}
    dense = {"hidden_dim": cfg["intermediate_size"],
             "activation": cfg["hidden_act"]}
    kinds = kimi_linear.layer_kinds(cfg)
    return hybrid_lm(
        vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
        mixers=[{m: kda if m == "kda" else mla} for m, _ in kinds],
        ffns=[{f: dense if f == "dense" else moe} for _, f in kinds],
        seq_len=seq_len, epsilon=cfg["rms_norm_eps"])


class _Spans:
    """Host spans around the engine's calls, in a traced run (as
    `serve_cb._Spans`; a decode row also carries the step's busy slots
    and routing counts).  While every slot is busy the scheduler hands a
    step over before it reads the one before (`dispatch_cb_decode`,
    then `fetch_cb_decode` of the older step): there a decode row runs
    from the return of the fetch before it (or the step's own hand-over,
    if later) to the return of its own, the step's period, and the
    annotation lies around the fetch, where the host waits while the
    device runs the step.  A prefill row runs from hand-over to fetched
    first token on either path."""

    def __init__(self, engine):
        import jax
        # (name, t0, t1, live tokens[, busy slots, held experts touched,
        # assignments on held experts])
        self.rows: List[tuple] = []
        st = engine.stats
        routed = lambda: (st.cb_routed_experts_touched,      # noqa: E731
                          st.cb_routed_assignments)
        dec = engine.run_cb_decode
        pre_d, pre_f = engine.dispatch_cb_prefill, engine.fetch_cb_prefill
        dec_d, dec_f = engine.dispatch_cb_decode, engine.fetch_cb_decode
        prefills, steps = deque(), deque()
        read = [0.0]                     # when the last fetch returned

        def dispatch_prefill(params, pools, tokens, plen, row):
            prefills.append((time.perf_counter(), int(plen)))
            return pre_d(params, pools, tokens, plen, row)

        def fetch_prefill(flying):
            with jax.profiler.TraceAnnotation("engine.prefill"):
                out = pre_f(flying)
            t0, plen = prefills.popleft()
            self.rows.append(("engine.prefill", t0, time.perf_counter(),
                              plen))
            return out

        def decode(params, pools, tokens, ntoks, tables):
            live = int(np.sum(ntoks))    # inactive slots hold 0
            busy = int(np.count_nonzero(ntoks))
            was = routed()
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("engine.decode"):
                out = dec(params, pools, tokens, ntoks, tables)
            # the step's own routing counts: the scheduler's thread is
            # the only one that moves them
            now = routed()
            self.rows.append(("engine.decode", t0, time.perf_counter(),
                              live, busy, now[0] - was[0], now[1] - was[1]))
            return out

        def dispatch_decode(params, pools, tokens, ntoks, tables):
            steps.append((time.perf_counter(), int(np.sum(ntoks)),
                          int(np.count_nonzero(ntoks))))
            return dec_d(params, pools, tokens, ntoks, tables)

        def fetch_decode(flying):
            was = routed()
            with jax.profiler.TraceAnnotation("engine.decode"):
                out = dec_f(flying)
            t0, live, busy = steps.popleft()
            now, t1 = routed(), time.perf_counter()
            self.rows.append(("engine.decode", max(t0, read[0]), t1, live,
                              busy, now[0] - was[0], now[1] - was[1]))
            read[0] = t1
            return out

        engine.run_cb_decode = decode
        engine.dispatch_cb_prefill, engine.fetch_cb_prefill = \
            dispatch_prefill, fetch_prefill
        engine.dispatch_cb_decode, engine.fetch_cb_decode = \
            dispatch_decode, fetch_decode


def build(cell: harness.Cell, seed: int):
    """The engine and scheduler over the seed's weights, warmed."""
    import jax
    from singa_tpu.core.net import build_net
    from singa_tpu.data import discover_input_shapes
    from singa_tpu.serve.engine import InferenceEngine, ServeSpec
    from singa_tpu.serve.scheduler import ContinuousScheduler

    laps = harness.Laps()
    cfg, sv = cell.config, cell.config["serve"]
    model = model_config(cfg, sv["cb_prompt_cap"])
    net = build_net(model, "kTrain",
                    discover_input_shapes(model, force_synthetic=True))
    laps.lap("net")
    made = kimi_weights.tree(cfg, seed, _dtype(sv["dtype"]))
    params = {program_name(k): v for k, v in made.items()}
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
        cb_prompt_cap=sv["cb_prompt_cap"])
    quiet = lambda *a, **k: None                      # noqa: E731
    engine = InferenceEngine(net, spec, params=params, log_fn=quiet)
    del params
    engine.load()
    engine.warmup()                  # cb_prefill and cb_decode, no others
    laps.lap("programs")
    sched = ContinuousScheduler(engine, log_fn=quiet).start()
    # run both programs once on the device before the window opens
    rng = np.random.default_rng(0)
    for t in [sched.submit(rng.integers(0, cfg["vocab_size"], 8), max_new=3)
              for _ in range(2)]:
        t.wait(timeout=600)
    jax.block_until_ready(sched.kv.pools)
    laps.lap("warm_requests")
    return engine, sched


def drive(sched, requests, seconds: float, preroll: float,
          opened: Callable[[], None]) -> tuple:
    """As `serve_cb.drive`, with the window opening `preroll` seconds
    into the schedule: the requests due before then are sent when they
    are due and fill the slots, so that the window opens on the system
    as it runs all day and not on an empty one.  `opened()` is called
    as the window opens.  Returns (sent, t0), t0 the window's start."""
    sent: List[_Sent] = []
    start = time.perf_counter()
    t0 = start + preroll
    shut = True

    def sleep_to(t):
        wait = t - time.perf_counter()
        if wait > 0:
            time.sleep(wait)

    for req in requests:
        due = start + req.due_s
        if shut and due >= t0:
            sleep_to(t0)
            opened()
            shut = False
        sleep_to(due)
        cancel = threading.Event()
        now = time.perf_counter()
        ticket = sched.submit(req.tokens, max_new=req.max_new,
                              cancel_event=cancel)
        sent.append(_Sent(req, due, now, ticket, cancel))
    if shut:
        sleep_to(t0)
        opened()
    sleep_to(t0 + seconds)
    return sent, t0


def check_sample(cell, seed: int, sent, count: int, control: Optional[str]):
    """Teacher-forced reference over a seeded sample of the finished
    requests, the longest among them (as `serve_cb.check_sample`, with
    this configuration's leaves and reference)."""
    cfg, sv = cell.config, cell.config["serve"]
    done = [s for s in sent if s.served is not None]
    if not done:
        return None, 0, None
    size = lambda s: len(s.req.tokens) + len(s.served)     # noqa: E731
    longest = max(done, key=size)
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng(int(seed) ^ 0x5EED)
    pick = [longest] + [rest[i] for i in rng.permutation(len(rest))
                        [:max(count - 1, 0)]]
    # one width whatever was picked: the reference compiles once
    width = sv["cb_prompt_cap"] + sv["max_new_tokens"]
    toks = np.zeros((len(pick), width), np.int32)
    nxt = np.zeros((len(pick), width), np.int32)
    mask = np.zeros((len(pick), width), bool)
    for r, s in enumerate(pick):
        seq = np.concatenate([s.req.tokens, np.asarray(s.served, np.int32)])
        plen = len(s.req.tokens)
        toks[r, :len(seq)] = seq
        nxt[r, :len(seq) - 1] = seq[1:]
        mask[r, plen - 1:len(seq) - 1] = True    # positions that were served
    key = weights.seed_key(seed)
    table = {n: (s, d) for n, s, d in kimi_weights.leaf_table(cfg)}
    dtype = _dtype(sv["dtype"])

    def get_leaf(name):
        shape, draw = table[name]
        return kimi_weights.leaf(key, name, tuple(shape), draw, dtype)

    out = kimi_linear.served_gaps(toks, nxt, get_leaf, cfg, control=control)
    gap, ctl = (out, None) if control is None else out
    got = {"widest": float(np.max(gap[mask])), "mean": float(np.mean(gap[mask]))}
    got_ctl = None if ctl is None else {
        "widest": float(np.max(ctl[mask])), "mean": float(np.mean(ctl[mask]))}
    return got, int(mask.sum()), got_ctl


def _counters(engine) -> Dict[str, int]:
    st = engine.stats
    return {k: getattr(st, k) for k in
            ("cb_steps", "cb_active_slot_steps", "cb_decode_steps")
            + ROUTING_COUNTERS}


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        t_process: float, compile_log, control: Optional[str] = None,
        broken: bool = False) -> Dict:
    from benchmark.traffic import open_loop

    clock = _TokenClock()
    engine, sched = build(cell, seed)
    spans = _Spans(engine) if trace else None
    # the window opens that share of its own length into ONE schedule
    # of the mix
    preroll = float(cell.spec.get("preroll_of_window", 0.0)) * seconds
    requests = open_loop.generate(cell.traffic, seed, preroll + seconds,
                                  cell.config["vocab_size"])
    if broken:
        _alter_tokens(engine, cell.config["vocab_size"])
    tr = capture.Capture(cell, trace, preroll + seconds, span_s=4.0)
    # what the harness has built stays: the collector does not walk it
    # again in the middle of a step
    gc.collect()
    gc.freeze()
    at_open: Dict = {}

    def opened():
        at_open.update(before=compile_log.snapshot(), c0=_counters(engine),
                       setup_s=time.perf_counter() - t_process)

    # ---- the pre-roll (set-up) and the window ------------------------
    tr.arm()
    sent, t0 = drive(sched, requests, seconds, preroll, opened)
    t1 = t0 + seconds
    c1 = _counters(engine)           # the window's, not the drain's
    tr.stop()
    finish(sent, cell.spec["at_window_end"])
    # ------------------------------------------------------------------
    before, c0, setup_s = at_open["before"], at_open["c0"], at_open["setup_s"]
    gc.unfreeze()
    after = compile_log.snapshot()
    gauges = {k: getattr(engine.stats, k)
              for k in ("cb_slot_state_bytes", "cb_block_bytes")}
    sched.stop()
    clock.close()
    memory_peak = harness.device_record(cell.chips)["memory_peak_bytes"]

    drain = cell.spec["at_window_end"] == "drain"
    first = [s for s in sent if s.times]
    if drain:
        attempted = sent
    else:        # given a slot inside the window
        attempted = [s for s in first if s.times[0] <= t1]
    wrong = [s for s in attempted
             if (s.error is not None and (drain or s.error != "Cancelled"))
             or (s.served is not None and len(s.served) != s.req.max_new)]
    ttft = [(s.times[0] - s.due) * 1e3 for s in first
            if drain or s.times[0] <= t1]
    itl = [(b - a) * 1e3 for s in first
           for a, b in zip(s.times, s.times[1:]) if b <= t1]
    emitted = sum(1 for s in first for t in s.times if t0 <= t <= t1)
    late = [(s.sent - s.due) * 1e3 for s in sent]

    # free the program's state, then the reference
    engine._params = engine._init_params = None
    sched.kv.pools = None
    gc.collect()
    limits = {**DEFAULT_LIMITS, **cell.spec.get("limits", {})}
    t_ref = time.perf_counter()
    gap, n_cmp, gap_ctl = check_sample(
        cell, seed, sent, int(cell.spec.get("check_requests", 4)), control)
    ref_s = time.perf_counter() - t_ref

    cmp_ = harness.Compared()
    cmp_.add("compiles_in_window", after["compiles"] - before["compiles"], 0)
    cmp_.add("requests_failed", len(wrong), 0)
    cmp_.add("served_tokens_compared", n_cmp, 1, ok=n_cmp >= 1)
    if gap is not None:
        cmp_.add("served_gap", gap["widest"], limits["served_gap"])
        cmp_.add("served_gap_mean", gap["mean"], limits["served_gap_mean"])
    if gap_ctl is not None:
        print(f"control {control} served_gap: {gap_ctl['widest']!r}\n"
              f"control {control} served_gap_mean: {gap_ctl['mean']!r}",
              flush=True)
    print(f"reference_seconds: {ref_s:.3f}", flush=True)

    for name, xs in (("ttft_ms", ttft), ("itl_ms", itl)):
        if xs:
            print(f"{name}: n {len(xs)} p50 {stats.percentile(xs, 50):.2f} "
                  f"p90 {stats.percentile(xs, 90):.2f} "
                  f"p95 {stats.percentile(xs, 95):.2f} "
                  f"p99 {stats.percentile(xs, 99):.2f} max {max(xs):.2f}",
                  flush=True)
    e2e = {"setup_s": setup_s, "out_tok_s": emitted / seconds}
    if ttft:
        e2e["ttft_p50_ms"] = stats.median(ttft)
    if itl:
        e2e["itl_p95_ms"] = stats.percentile(itl, 95)
    counters = {k: c1[k] - c0[k] for k in c1}
    counters.update(gauges, cb_slots=cell.config["serve"]["cb_slots"])
    print(f"counters: {counters}", flush=True)
    facts = {
        "cell": cell.name, "config": cell.config, "traffic": cell.traffic,
        "peaks": harness.peaks(cell), "chips": cell.chips,
        "window_s": seconds, "end_to_end": e2e,
        "samples": {"ttft_ms": ttft, "itl_ms": itl, "late_ms": late},
        "compile": {**after, "setup_s": setup_s},
        "counters": counters,
        "spans": spans.rows if spans else [],
        "trace_span": tr.host_span,
        "itemsize": np.dtype(_dtype(cell.config["serve"]["dtype"])).itemsize,
        "trace": tr.reduce()}
    return {"correct": cmp_.ok, "attempted": len(attempted),
            "failed": len(wrong), "end_to_end": e2e, "facts": facts,
            "memory_peak_bytes": memory_peak, "compared": cmp_.rows,
            "control": gap_ctl,
            "counts": {"requests": len(sent), "finished":
                       sum(1 for s in sent if s.served is not None),
                       "tokens_in_window": emitted,
                       "served_tokens_compared": n_cmp}}


def _alter_tokens(engine, vocab: int) -> None:
    """For the harness's own test: every decode step's tokens shifted
    by one where they are produced."""
    dec, fetch = engine.run_cb_decode, engine.fetch_cb_decode

    def broken(params, pools, tokens, ntoks, tables):
        nxt, pools = dec(params, pools, tokens, ntoks, tables)
        return (nxt + 1) % vocab, pools

    engine.run_cb_decode = broken
    engine.fetch_cb_decode = lambda flying: (fetch(flying) + 1) % vocab
