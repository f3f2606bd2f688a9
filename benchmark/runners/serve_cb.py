"""Runner: open-loop traffic through the continuous-batching engine.

Builds what `singa_tpu.main serve` builds behind HTTP, in this process:
`transformer_lm(...)` -> `NeuralNet` -> `InferenceEngine(net, spec,
params=<the seed's tree>)` -> `ContinuousScheduler(engine).start()`.
Requests enter through `scheduler.submit` and stream through
`StreamTicket`; there is no HTTP hop.

From the program: the engine, the scheduler, `ServeStats`' counters.
Everything else (traffic, weights, clocks, the comparison) is the
benchmark's.  Two seams are put on the program from here, because it
has no hook yet (PERF.md lists both for the tracing issue):
`StreamTicket._emit` is wrapped to stamp each token's time where it is
produced, before any flush batching; and in a traced run the engine
instance's `run_cb_prefill` / `run_cb_decode` are wrapped in spans.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from benchmark import harness, stats, weights
from benchmark.reference import dense_lm
from benchmark.trace import capture

# Per served token: by how much its reference logit lies below the
# reference's best.  Greedy tokens served from bf16 arithmetic differ
# from the float32 reference's first choice only where two logits lie
# closer than bf16's noise.
#   served_gap       the widest such gap.  It swings by its nature and
#                    fp8 does not move it past a sound run's; held at 3 x
#                    the sound runs' largest against gross faults (an
#                    altered token reads about 4).
#   served_gap_mean  the mean over the compared tokens: the number the
#                    lower precision has to fail (fp8's first choices lie
#                    7.5 x further below than any sound run's).
# The cell's file carries the numbers (`limits`), set from readings on
# the chip (PERF.md section 2).  These defaults hold for the float32 CPU
# rehearsal only, where served tokens ARE the reference's first choice.
DEFAULT_LIMITS = {"served_gap": 1e-3, "served_gap_mean": 1e-5}

PROGRAM_NAMES = {"embed": "embed/embedding", "final_norm": "ln_f/scale",
                 "head": "loss/w", "attn_norm": "ln{i}a/scale",
                 "ffn_norm": "ln{i}b/scale", "wq": "attn{i}/wq",
                 "wk": "attn{i}/wk", "wv": "attn{i}/wv", "wo": "attn{i}/wo",
                 "w_gate": "ffn{i}/w1", "w_up": "ffn{i}/w3",
                 "w_down": "ffn{i}/w2"}


def program_name(leaf: str) -> str:
    """The program's name for one of the benchmark's leaves."""
    if leaf.startswith("L"):
        i, part = leaf[1:].split(".", 1)
        return PROGRAM_NAMES[part].format(i=i)
    return PROGRAM_NAMES[leaf]


def model_config(cfg: Dict, seq_len: int, batch: int, precision: str):
    """The program's ModelConfig for the benchmark's configuration."""
    from singa_tpu.config.schema import RMSNormConfig
    from singa_tpu.models.transformer import transformer_lm
    model = transformer_lm(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        embed_dim=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        head_dim=cfg["head_dim"], num_kv_heads=cfg["num_key_value_heads"],
        ffn_hidden=cfg["intermediate_size"], seq_len=seq_len,
        batchsize=batch, precision=precision,
        tie_embeddings=cfg["tie_word_embeddings"], fused_head=True)
    for layer in model.neuralnet.layer:
        if layer.type == "kAttention":
            layer.attention_param.rope_theta = float(cfg["rope_theta"])
        elif layer.type == "kRMSNorm":
            layer.rmsnorm_param = RMSNormConfig(
                epsilon=float(cfg["rms_norm_eps"]))
    return model


def _dtype(name: str):
    import jax.numpy as jnp
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


class _TokenClock:
    """Stamps each token's time at `StreamTicket._emit`."""

    def __init__(self):
        from singa_tpu.serve.scheduler import StreamTicket
        self.cls, self.orig = StreamTicket, StreamTicket._emit
        orig = self.orig

        def _emit(ticket, token):
            ticket.__dict__.setdefault("bench_times", []).append(
                time.perf_counter())
            orig(ticket, token)

        StreamTicket._emit = _emit

    def close(self):
        self.cls._emit = self.orig


class _Spans:
    """Host spans around the engine's two calls, in a traced run: each
    is also a `TraceAnnotation`, so the trace's idle gaps can be named
    by what the host was doing."""

    def __init__(self, engine):
        import jax
        self.rows: List[tuple] = []      # (name, t0, t1, live_tokens)
        pre, dec = engine.run_cb_prefill, engine.run_cb_decode

        def prefill(params, pools, tokens, plen, row):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("engine.prefill"):
                out = pre(params, pools, tokens, plen, row)
            self.rows.append(("engine.prefill", t0, time.perf_counter(),
                              int(plen)))
            return out

        def decode(params, pools, tokens, ntoks, tables):
            live = int(np.sum(ntoks))    # inactive slots hold 0
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("engine.decode"):
                out = dec(params, pools, tokens, ntoks, tables)
            self.rows.append(("engine.decode", t0, time.perf_counter(),
                              live))
            return out

        engine.run_cb_prefill, engine.run_cb_decode = prefill, decode


def build(cell: harness.Cell, seed: int):
    """The engine and scheduler over the seed's weights, warmed."""
    import jax
    from singa_tpu.core.net import build_net
    from singa_tpu.data import discover_input_shapes
    from singa_tpu.serve.engine import InferenceEngine, ServeSpec
    from singa_tpu.serve.scheduler import ContinuousScheduler

    laps = harness.Laps()
    cfg, sv = cell.config, cell.config["serve"]
    model = model_config(cfg, sv["cb_prompt_cap"], 1, "float32")
    net = build_net(model, "kTrain",
                    discover_input_shapes(model, force_synthetic=True))
    laps.lap("net")
    made = weights.tree(cfg, seed, _dtype(sv["dtype"]))
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


class _Sent:
    """One request as sent, and (after `finish`) how it ended: `served`
    its tokens if it ran to its end, `error` the exception's name if it
    failed ("Cancelled" for one the window's close cancelled)."""
    __slots__ = ("req", "due", "sent", "ticket", "cancel", "served", "error")

    def __init__(self, req, due, sent, ticket, cancel):
        self.req, self.due, self.sent = req, due, sent
        self.ticket, self.cancel = ticket, cancel
        self.served: Optional[List[int]] = None
        self.error: Optional[str] = None

    @property
    def times(self) -> List[float]:
        return self.ticket.__dict__.get("bench_times", [])


def drive(sched, requests, seconds: float) -> tuple:
    """Send each request when it is due, whether or not earlier ones
    have finished, and wait for the window to close.  Returns
    (sent, t0)."""
    sent: List[_Sent] = []
    t0 = time.perf_counter()
    for req in requests:
        due = t0 + req.due_s
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        cancel = threading.Event()
        now = time.perf_counter()
        ticket = sched.submit(req.tokens, max_new=req.max_new,
                              cancel_event=cancel)
        sent.append(_Sent(req, due, now, ticket, cancel))
    left = t0 + seconds - time.perf_counter()
    if left > 0:
        time.sleep(left)
    return sent, t0


def finish(sent: List[_Sent], at_end: str, timeout: float = 120.0) -> None:
    """Drain what is in flight, or cancel it, and note how each request
    ended."""
    if at_end == "cancel":
        for s in sent:
            s.cancel.set()
    deadline = time.perf_counter() + timeout
    for s in sent:
        try:
            s.served = list(s.ticket.wait(
                max(deadline - time.perf_counter(), 0.0))["tokens"])
        except Exception as e:  # noqa: BLE001 — the outcome is the datum
            s.error = type(e).__name__


def check_sample(cell, seed: int, sent: List[_Sent], count: int,
                 control: Optional[str]):
    """Teacher-forced reference over a seeded sample of the finished
    requests, the longest among them.  Returns (widest and mean gap of
    the served tokens, number of served tokens compared, the same two
    for the control's first choices or None)."""
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
    table = {n: (s, std) for n, s, std in weights.leaf_table(cfg)}
    dtype = _dtype(sv["dtype"])

    def get_leaf(name):
        shape, std = table[name]
        return weights.leaf(key, name, tuple(shape), std, dtype)

    out = dense_lm.served_gaps(toks, nxt, get_leaf, cfg, control=control)
    gap, ctl = (out, None) if control is None else out
    got = {"widest": float(np.max(gap[mask])), "mean": float(np.mean(gap[mask]))}
    got_ctl = None if ctl is None else {
        "widest": float(np.max(ctl[mask])), "mean": float(np.mean(ctl[mask]))}
    return got, int(mask.sum()), got_ctl


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        t_process: float, compile_log, control: Optional[str] = None,
        broken: bool = False) -> Dict:
    import jax
    from benchmark.traffic import open_loop

    clock = _TokenClock()
    engine, sched = build(cell, seed)
    spans = _Spans(engine) if trace else None
    requests = open_loop.generate(cell.traffic, seed, seconds,
                                  cell.config["vocab_size"])
    if broken:
        _alter_tokens(engine, cell.config["vocab_size"])
    before = compile_log.snapshot()
    steps0 = (engine.stats.cb_steps, engine.stats.cb_active_slot_steps)
    tr = capture.Capture(cell, trace, seconds, span_s=4.0)
    setup_s = time.perf_counter() - t_process

    # ---- the window --------------------------------------------------
    tr.arm()
    sent, t0 = drive(sched, requests, seconds)
    t1 = t0 + seconds
    tr.stop()
    finish(sent, cell.spec["at_window_end"])
    # ------------------------------------------------------------------
    after = compile_log.snapshot()
    steps1 = (engine.stats.cb_steps, engine.stats.cb_active_slot_steps)
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
    facts = {
        "cell": cell.name, "config": cell.config, "traffic": cell.traffic,
        "peaks": harness.peaks(cell), "chips": cell.chips,
        "window_s": seconds, "end_to_end": e2e,
        "samples": {"ttft_ms": ttft, "itl_ms": itl, "late_ms": late},
        "compile": {**after, "setup_s": setup_s},
        "counters": {
            "cb_steps": steps1[0] - steps0[0],
            "cb_active_slot_steps": steps1[1] - steps0[1],
            "cb_slots": cell.config["serve"]["cb_slots"]},
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
    """For the harness's own test: the timed path broken underneath, a
    token altered where it is produced (every decode step's tokens
    shifted by one)."""
    dec = engine.run_cb_decode

    def broken(params, pools, tokens, ntoks, tables):
        nxt, pools = dec(params, pools, tokens, ntoks, tables)
        return (nxt + 1) % vocab, pools

    engine.run_cb_decode = broken
