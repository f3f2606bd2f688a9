"""Benchmark driver.  Prints ONE JSON line on stdout.

The stdout metric is the north-star gate 2 (BASELINE.md): CIFAR-10
AlexNet MFU on the 5-conv `alexnet_cifar10_full` stack, measured on
the available accelerator at the throughput-optimal batch size.
`vs_baseline` is value / 0.50 — the fraction of the >=50%-MFU gate —
because the reference publishes no numbers of its own (README.md:1-5;
BASELINE.md records its harness only).

Secondary metrics go to stderr so the driver contract stays a single
stdout line:
  * mnist_lenet_train_throughput — img/s/chip for the reference's own
    examples/mnist/conv.conf (batch enlarged to fill the chip), with
    vs_baseline grounded against REFERENCE_CPU_IMG_SEC: the SAME
    conv.conf workload measured through this framework's CPU backend
    on this host (single process, matching the reference's
    single-node CPU worker; measured 2026-07-30, best window
    4.7 ms/step at batch 64 => ~13.6k img/s).  Re-measure with
    `JAX_PLATFORMS=cpu python bench.py --cpu-baseline`.
  * cifar10_quick_mfu — the 3-conv caffe 'quick' net (its 32-channel
    convs cap the 128-lane MXU well below the gate regardless of
    software quality).
  * transformer_lm_mfu — the transformer LM stack.
  * mnist time-to-99%: produced by singa_tpu/tools/convergence_run.py
    (a full training run, too slow for every bench invocation) into
    CONVERGENCE.json; it is a record of its own and is not folded into
    this line.

Timing: ALL steps of a measurement run as ONE compiled lax.scan
program (trainer.train_steps) — device-only inner loop, one dispatch —
ended by `hard_sync` (jax.block_until_ready).  Each metric reports the
best of several scan windows.

Processes and the chip: a chip belongs to one process.  The default
run and every in-process smoke are one process.  `--perf-smoke` shells
out once to tools/bench_report.py, which never imports JAX.
`--router-smoke` starts a JAX child (`serve --fleet 1`) beside this
JAX parent, so it is a CPU functional gate and refuses any other
backend.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# Measured on this host — see module docstring and --cpu-baseline.
REFERENCE_CPU_IMG_SEC = 13600.0

GATE_MFU = 0.50


def _best_window(trainer, params, opt_state, batch, key, iters, reps):
    from singa_tpu.utils.profiler import hard_sync
    params, opt_state, _ = trainer.train_steps(
        params, opt_state, batch, 0, key, iters)
    hard_sync(params)
    best = float("inf")
    for r in range(reps):
        t0 = time.perf_counter()
        params, opt_state, _ = trainer.train_steps(
            params, opt_state, batch, iters, key, iters)
        hard_sync(params)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def _lenet_trainer(batch_size):
    import jax

    from singa_tpu.config import load_model_config
    from singa_tpu.core.trainer import Trainer

    cfg = load_model_config(os.path.join(REPO, "examples/mnist/conv.conf"))
    for layer in cfg.neuralnet.layer:
        if layer.data_param:
            layer.data_param.batchsize = batch_size
    trainer = Trainer(cfg, {"data": {"pixel": (28, 28), "label": ()}},
                      log_fn=lambda s: None)
    params, opt_state = trainer.init(seed=0)
    rng = np.random.default_rng(0)
    batch = {"data": {
        "pixel": jax.device_put(
            rng.integers(0, 256, (batch_size, 28, 28)).astype(np.uint8)),
        "label": jax.device_put(
            rng.integers(0, 10, (batch_size,)).astype(np.int32)),
    }}
    return trainer, params, opt_state, batch


def _cifar_mfu(cfg, batch_size, iters, reps, precision):
    """Shared CIFAR measurement: build trainer, synthetic batch, best
    scan window, analytic train MFU."""
    import jax

    from singa_tpu.core.trainer import Trainer
    from singa_tpu.utils.flops import mfu, net_train_flops

    cfg.precision = precision
    trainer = Trainer(cfg, {"data": {"pixel": (3, 32, 32), "label": ()}},
                      log_fn=lambda s: None)
    params, opt_state = trainer.init(seed=0)
    rng = np.random.default_rng(0)
    batch = {"data": {
        "pixel": jax.device_put(
            rng.standard_normal((batch_size, 3, 32, 32)).astype(np.float32)),
        "label": jax.device_put(
            rng.integers(0, 10, (batch_size,)).astype(np.int32)),
    }}
    step_s = _best_window(trainer, params, opt_state, batch,
                          jax.random.PRNGKey(0), iters, reps)
    flops = net_train_flops(trainer.train_net)
    return mfu(flops, step_s), step_s, flops


def bench_alexnet_mfu(batch_size=8192, iters=50, reps=4,
                      precision="bfloat16"):
    """North-star gate 2 (the judged stdout metric).

    iters=50: one dispatch per 50-step compiled window, the shape
    steady-state training runs (Trainer.run scan_chunk)."""
    from singa_tpu.models.vision import alexnet_cifar10_full

    util, step_s, flops = _cifar_mfu(alexnet_cifar10_full(
        batchsize=batch_size), batch_size, iters, reps, precision)
    return {
        "metric": "alexnet_cifar10_mfu",
        "value": round(util, 4) if util is not None else None,
        "unit": "fraction_of_peak",
        "vs_baseline": (round(util / GATE_MFU, 4)
                        if util is not None else None),
        "img_sec": round(batch_size / step_s, 1),
        "step_ms": round(step_s * 1e3, 3),
        "batch": batch_size,
        "model_tflops_per_step": round(flops / 1e12, 4),
        "precision": precision,
    }


def bench_lenet(batch_size=512, iters=50, reps=3):
    import jax

    trainer, params, opt_state, batch = _lenet_trainer(batch_size)
    step_s = _best_window(trainer, params, opt_state, batch,
                          jax.random.PRNGKey(0), iters, reps)
    img_sec = batch_size / step_s
    return {
        "metric": "mnist_lenet_train_throughput",
        "value": round(img_sec, 1),
        "unit": "img/sec/chip",
        "vs_baseline": round(img_sec / REFERENCE_CPU_IMG_SEC, 2),
        "baseline_img_sec_cpu": REFERENCE_CPU_IMG_SEC,
    }


def bench_cpu_baseline(iters=20, reps=5):
    """Measure REFERENCE_CPU_IMG_SEC on this host: the reference's own
    conv.conf (batch 64) through the CPU backend, single process.
    Run with JAX_PLATFORMS=cpu; refuses to record an accelerator
    number as a CPU baseline."""
    import jax

    if jax.default_backend() != "cpu":
        raise SystemExit("--cpu-baseline must run on the CPU backend: "
                         "JAX_PLATFORMS=cpu python bench.py --cpu-baseline "
                         f"(got {jax.default_backend()!r})")
    trainer, params, opt_state, batch = _lenet_trainer(64)
    step_s = _best_window(trainer, params, opt_state, batch,
                          jax.random.PRNGKey(0), iters, reps)
    print(json.dumps({"metric": "lenet_cpu_baseline",
                      "value": round(64 / step_s, 1),
                      "unit": "img/sec", "step_ms":
                          round(step_s * 1e3, 3)}))


def bench_quick_mfu(batch_size=2048, iters=50, reps=3,
                    precision="bfloat16"):
    from singa_tpu.models.vision import alexnet_cifar10

    util, step_s, _ = _cifar_mfu(alexnet_cifar10(batchsize=batch_size),
                                 batch_size, iters, reps, precision)
    return {"metric": "cifar10_quick_mfu",
            "value": round(util, 4) if util is not None else None,
            "unit": "fraction_of_peak",
            "img_sec": round(batch_size / step_s, 1)}


def bench_transformer_mfu(batch_size=32, seq_len=1024, iters=30,
                          precision="bfloat16", head_dim=64):
    import jax

    from singa_tpu.core.trainer import Trainer
    from singa_tpu.models.transformer import (synthetic_token_batches,
                                              transformer_lm)
    from singa_tpu.utils.flops import mfu, net_train_flops

    cfg = transformer_lm(vocab_size=32768, num_layers=12, embed_dim=768,
                         num_heads=768 // head_dim, head_dim=head_dim,
                         seq_len=seq_len,
                         batchsize=batch_size)
    cfg.precision = precision
    trainer = Trainer(cfg, {"data": {"input": (seq_len,),
                                     "target": (seq_len,)}},
                      log_fn=lambda s: None)
    params, opt_state = trainer.init(seed=0)
    batch = next(synthetic_token_batches(batch_size, seq_len, 32768))
    batch = jax.tree_util.tree_map(jax.device_put, batch)
    key = jax.random.PRNGKey(0)
    step_s = _best_window(trainer, params, opt_state, batch, key, iters, 3)
    # analytic model flops: XLA's cost analysis cannot see inside the
    # Pallas flash custom calls, so compiled_flops under-counts the
    # attention terms (~30% of this stack)
    flops = net_train_flops(trainer.train_net)
    util = mfu(flops, step_s)
    return {"metric": "transformer_lm_mfu",
            "value": round(util, 4) if util is not None else None,
            "unit": "fraction_of_peak",
            "tok_sec": round(batch_size * seq_len / step_s, 1),
            "step_ms": round(step_s * 1e3, 3),
            "model_tflops_per_step": round(flops / 1e12, 4)}


def bench_decode(batch_size=8, prompt_len=128, new_tokens=256,
                 reps=3, precision="bfloat16"):
    """KV-cache decode throughput: tokens/sec across the batch for the
    bench transformer (12L 768E 32k vocab), greedy sampling, one
    compiled prefill+scan program (models/generate.py).  vs_baseline is
    null — there is no reference decode path to compare against (the
    reference is train/test only); the row exists to make inference
    regressions visible round over round (BASELINE.md "Decode
    path")."""
    import jax

    from singa_tpu.core.trainer import Trainer
    from singa_tpu.models.generate import generate
    from singa_tpu.models.transformer import transformer_lm
    from singa_tpu.utils.profiler import hard_sync

    seq = prompt_len + new_tokens
    cfg = transformer_lm(vocab_size=32768, num_layers=12, embed_dim=768,
                         num_heads=12, head_dim=64, seq_len=seq,
                         batchsize=batch_size)
    cfg.precision = precision
    trainer = Trainer(cfg, {"data": {"input": (seq,), "target": (seq,)}},
                      log_fn=lambda s: None)
    net = trainer.test_net or trainer.train_net
    params, _ = trainer.init(seed=0)
    if precision == "bfloat16":
        import jax.numpy as jnp
        params = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
    rng = np.random.default_rng(0)
    prompt = jax.device_put(rng.integers(
        0, 32768, (batch_size, prompt_len)).astype(np.int32))

    def timed(n_new):
        # max_len pins the cache geometry to the full run's, so the
        # 1-new-token prefill probe runs the IDENTICAL prefill program
        # (same cache allocation, same masked-dense score width) and
        # the subtraction isolates exactly the decode steps
        out = generate(net, params, prompt, n_new,
                       max_len=seq)                  # compile + warm
        hard_sync(out)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = generate(net, params, prompt, n_new, max_len=seq)
            hard_sync(out)
            best = min(best, time.perf_counter() - t0)
        return best

    # prefill isolated via a 1-new-token run so the per-decode-step
    # number tracks the decode path only (a prefill-only speedup must
    # not move the decode regression anchor)
    t_full, t_prefill = timed(new_tokens), timed(1)
    decode_s = max(t_full - t_prefill, 1e-9) / (new_tokens - 1)
    tok_sec = batch_size / decode_s
    return {"metric": "decode_tok_sec",
            "value": round(tok_sec, 1),
            "unit": "tokens/sec/chip",
            "vs_baseline": None,
            "batch": batch_size, "prompt_len": prompt_len,
            "new_tokens": new_tokens,
            "ms_per_decode_step": round(decode_s * 1e3, 3),
            "prefill_ms": round(t_prefill * 1e3, 3),
            "end_to_end_tok_sec": round(
                batch_size * new_tokens / t_full, 1),
            "precision": precision}


def bench_feed_smoke(batch_size=64, steps=60, scan_chunk=10,
                     out=None):
    """Feed-pipeline A/B (ISSUE 2 acceptance): the LeNet train loop
    through Trainer.run with the DeviceFeeder ON vs OFF at the same
    scan_chunk, pulling the synthetic source DIRECTLY (no Prefetcher:
    that is a separate batch-granular stage — this smoke isolates the
    feed stage, so the off leg pays generation + stacking inline
    exactly where a prefetch-less loop would).  Reports steps/sec and
    the HOST-WAIT FRACTION of loop wall time: (wait + inline stage)
    for the synchronous leg vs consumer-side wait alone for the
    overlapped leg, whose staging runs on the producer thread.  `out`
    writes the JSON line to a file as well (scripts/perf_smoke.sh ->
    BENCH_pr2.json).

    batch 64 (not the throughput-optimal 512): this container's CPU is
    a single core, so the A/B must keep the compute share small enough
    that the data path is measurable at all; the fraction, not the
    absolute throughput, is the recorded metric."""
    import jax

    from singa_tpu.data.synthetic import synthetic_image_batches

    trainer, _, _, _ = _lenet_trainer(batch_size)
    trainer.cfg.train_steps = steps
    trainer.cfg.display_frequency = 0
    trainer.cfg.test_frequency = 0

    def one(feeder):
        params, opt_state = trainer.init(seed=0)
        it = synthetic_image_batches(batch_size, seed=1, stream_seed=7)
        trainer.timer.reset()
        t0 = time.perf_counter()
        trainer.run(params, opt_state, it, seed=0,
                    scan_chunk=scan_chunk, feeder=feeder)
        wall = time.perf_counter() - t0
        tm = dict(trainer.timer.times)
        host_wait = tm.get("wait", 0.0) + (0.0 if feeder
                                           else tm.get("stage", 0.0))
        return {"wall_s": round(wall, 4),
                "steps_per_sec": round(steps / wall, 2),
                "img_per_sec": round(steps * batch_size / wall, 1),
                "wait_s": round(tm.get("wait", 0.0), 4),
                "stage_s": round(tm.get("stage", 0.0), 4),
                "train_s": round(tm.get("train", 0.0), 4),
                "host_wait_fraction": round(host_wait / wall, 4)}

    one(False)   # warm the compile caches so both A/B legs are steady
    off, on = one(False), one(True)
    result = {
        "metric": "lenet_feed_pipeline",
        "value": round(off["host_wait_fraction"]
                       - on["host_wait_fraction"], 4),
        "unit": "host_wait_fraction_drop",
        "feeder_on": on, "feeder_off": off,
        "batch": batch_size, "steps": steps, "scan_chunk": scan_chunk,
        "backend": jax.default_backend(),
        "cpu_count": os.cpu_count(),
    }
    line = json.dumps(result)
    if out:
        with open(out, "w") as f:
            f.write(line + "\n")
    return result


def bench_serve_smoke(n_clients=6, reqs_per_client=5, out=None):
    """Serving-tier smoke (ISSUE 5 acceptance): N concurrent clients
    sustain traffic against the HTTP frontend on CPU, and the run
    FAILS (raises) unless:
      * zero program compiles after warmup (the compiled-bucket
        contract — every request padded into an AOT executable);
      * a mid-run checkpoint hot-reload lands with zero dropped or
        failed in-flight requests;
      * an injected `serve.reload` fault degrades to serving the OLD
        params (counted in ServeStats.reload_failures, params_step
        unmoved, process up) and the next clean poll recovers.
    Records p50/p95 latency, occupancy, and QPS; `out` writes the JSON
    line to a file as well (scripts/serve_smoke.sh -> BENCH_pr5.json).
    The model is bench-tiny (2L 32E vocab 64): the subject under test
    is the serving machinery, not the matmuls."""
    import json as _json
    import tempfile
    import threading
    import urllib.request

    import jax

    from singa_tpu.core.net import build_net
    from singa_tpu.models.transformer import transformer_lm
    from singa_tpu.serve import InferenceEngine, InferenceServer, ServeSpec
    from singa_tpu.utils.checkpoint import CheckpointManager
    from singa_tpu.utils.faults import FaultSchedule, inject

    vocab, seq = 64, 16
    cfg = transformer_lm(vocab_size=vocab, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=seq,
                         batchsize=2)
    net = build_net(cfg, "kTest",
                    {"data": {"input": (seq,), "target": (seq,)}})
    params = net.init_params(jax.random.PRNGKey(0))
    opt = {"t": np.zeros(())}

    ws = tempfile.mkdtemp(prefix="serve_smoke_")
    mgr = CheckpointManager(ws, max_to_keep=10, log_fn=lambda s: None)
    mgr.save(1, params, opt, health={"verdict": "ok"})

    spec = ServeSpec(buckets=((2, 8), (4, 8), (4, 16)),
                     max_new_tokens=8, batch_window_s=0.01,
                     request_timeout_s=30.0, reload_poll_s=100.0)
    engine = InferenceEngine(net, spec, workspace=ws,
                             log_fn=lambda s: None)
    engine.load()
    warm = engine.warmup()

    server = InferenceServer(engine, port=0, log_fn=lambda s: None)
    server.start()
    host, port = server.address
    url = f"http://{host}:{port}"

    def post(path, payload):
        req = urllib.request.Request(
            f"{url}{path}", data=_json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            return _json.loads(r.read())

    errors, results = [], []
    rng = np.random.default_rng(0)
    prompts = [[rng.integers(1, vocab, rng.integers(1, 13)).tolist()
                for _ in range(reqs_per_client)]
               for _ in range(n_clients)]

    def client(i):
        try:
            for p in prompts[i]:
                results.append(post("/generate", {"tokens": p}))
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(repr(e))

    # mid-run hot reload: clients in flight while the params swap
    p2 = jax.tree_util.tree_map(lambda a: a * 1.01, params)
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_clients)]
    for t in threads:
        t.start()
    mgr.save(2, p2, opt, health={"verdict": "ok"})
    r1 = engine.poll_reload()
    # injected reload fault mid-traffic: must degrade, not crash
    mgr.save(3, params, opt, health={"verdict": "ok"})
    with inject(FaultSchedule.parse("serve.reload@0:error")):
        r2 = engine.poll_reload()
    step_after_fault = engine.params_step
    r3 = engine.poll_reload()   # clean poll recovers
    for t in threads:
        t.join()

    # read the final stats through the HTTP endpoint — the same surface
    # an operator scrapes
    with urllib.request.urlopen(f"{url}/stats", timeout=10) as r:
        snap = _json.loads(r.read())
    server.stop()

    n_total = n_clients * reqs_per_client
    failures = []
    if errors:
        failures.append(f"client errors: {errors}")
    if len(results) != n_total or snap["completed"] < n_total:
        failures.append(f"dropped requests: {len(results)}/{n_total} "
                        f"responses, {snap['completed']} completed")
    if snap["failed"] or snap["expired"]:
        failures.append(f"failed={snap['failed']} "
                        f"expired={snap['expired']}")
    if snap["compiles"] != warm:
        failures.append(f"recompiled after warmup: {snap['compiles']} "
                        f"!= {warm}")
    if r1 != "reloaded":
        failures.append(f"mid-run hot reload did not land: {r1}")
    if r2 != "failed" or step_after_fault != 2:
        failures.append(f"reload fault did not degrade to old params: "
                        f"{r2}, step {step_after_fault}")
    if snap["reload_failures"] != 1:
        failures.append(f"reload failure not counted: "
                        f"{snap['reload_failures']}")
    if r3 != "reloaded" or snap["params_step"] != 3:
        failures.append(f"post-fault recovery failed: {r3}, "
                        f"step {snap['params_step']}")
    if failures:
        raise RuntimeError("serve smoke FAILED: " + "; ".join(failures))

    result = {
        "metric": "serve_smoke_p50_latency",
        "value": snap["p50_latency_ms"],
        "unit": "ms",
        "p95_latency_ms": snap["p95_latency_ms"],
        "batch_occupancy": snap["batch_occupancy"],
        "qps": snap["qps"],
        "requests": n_total,
        "clients": n_clients,
        "batches": snap["batches"],
        "compiles_warmup": warm,
        "compiles_total": snap["compiles"],
        "reloads": snap["reloads"],
        "reload_failures": snap["reload_failures"],
        "served_step": snap["params_step"],
        "buckets": [list(b) for b in spec.buckets],
        "backend": __import__("jax").default_backend(),
    }
    line = json.dumps(result)
    if out:
        with open(out, "w") as f:
            f.write(line + "\n")
    return result


def bench_fleet_smoke(n_clients=6, reqs_per_client=6, out=None):
    """Fleet smoke (ISSUE 7 acceptance): a 3-engine fleet behind the
    router + FleetServer sustains concurrent HTTP traffic on CPU, and
    the run FAILS (raises) unless:
      * killing 1 of 3 engines mid-load costs ZERO client-visible
        failures — every request either retries onto a healthy sibling
        or sheds with 503 + Retry-After (clients honor it); never a
        500, never a hang.  The dead engine is quarantined and, once
        revived, readmitted (kill->readmission time is recorded);
      * a DIVERGED checkpoint save is canaried on exactly one engine
        and auto-rolled back — at no point do >=2 engines serve the
        bad fingerprint, and the fleet ends on the old step;
      * a healthy save afterwards promotes fleet-wide (every engine on
        the new step).
    Records fleet p50/p95, kill-recovery time, and rollout outcome
    counts; `out` writes the JSON line to a file as well
    (scripts/fleet_smoke.sh -> BENCH_pr7.json)."""
    import json as _json
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    import jax

    from singa_tpu.core.net import build_net
    from singa_tpu.models.transformer import transformer_lm
    from singa_tpu.serve import (EngineFleet, FleetServer, RolloutSpec,
                                 RouterSpec, ServeSpec)
    from singa_tpu.utils.checkpoint import CheckpointManager

    vocab, seq = 64, 16
    cfg = transformer_lm(vocab_size=vocab, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=seq,
                         batchsize=2)
    net = build_net(cfg, "kTest",
                    {"data": {"input": (seq,), "target": (seq,)}})
    params = net.init_params(jax.random.PRNGKey(0))
    opt = {"t": np.zeros(())}

    ws = tempfile.mkdtemp(prefix="fleet_smoke_")
    mgr = CheckpointManager(ws, max_to_keep=10, log_fn=lambda s: None)
    mgr.save(1, params, opt, health={"verdict": "ok"})

    spec = ServeSpec(buckets=((2, 8), (4, 16)), max_new_tokens=6,
                     batch_window_s=0.005, request_timeout_s=30.0)
    fleet = EngineFleet.local(
        net, spec, 3, workspace=ws, params=params,
        router_spec=RouterSpec(probe_period_s=0.05,
                               quarantine_after=1,
                               readmit_base_s=0.05, readmit_cap_s=0.5),
        rollout_spec=RolloutSpec(poll_s=0.05, window_s=0.2),
        log_fn=lambda s: None)
    fleet.start()
    front = FleetServer(fleet, port=0, log_fn=lambda s: None)
    front.start()
    host, port = front.address
    url = f"http://{host}:{port}"

    errors, results = [], []
    sheds = [0]
    stop_traffic = threading.Event()

    def post_with_retry(payload):
        # the well-behaved client: honor 503 + Retry-After, treat any
        # other 5xx (or a hang) as a real failure
        for _ in range(50):
            req = urllib.request.Request(
                f"{url}/generate", data=_json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=60) as r:
                    return _json.loads(r.read())
            except urllib.error.HTTPError as e:
                if e.code == 503:
                    sheds[0] += 1
                    time.sleep(float(
                        e.headers.get("Retry-After", 0.05)) or 0.05)
                    continue
                raise
        raise RuntimeError("request still shed after 50 retries")

    rng = np.random.default_rng(0)
    prompts = [[rng.integers(1, vocab, rng.integers(1, 13)).tolist()
                for _ in range(reqs_per_client)]
               for _ in range(n_clients)]

    def client(i):
        try:
            for p in prompts[i]:
                results.append(post_with_retry({"tokens": p}))
                if stop_traffic.is_set():
                    return
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(repr(e))

    # -- phase 1: kill one engine under load, measure recovery --------
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_clients)]
    for t in threads:
        t.start()
    time.sleep(0.1)                  # let traffic land on every engine
    victim = fleet.router.healthy_names()[0]
    handle = fleet.router.handle_for(victim)
    t_kill = time.perf_counter()
    handle.kill()
    time.sleep(0.3)
    handle.revive()
    deadline = time.time() + 15
    while time.time() < deadline and \
            fleet.router.stats.readmissions == 0:
        time.sleep(0.02)
    kill_recovery_s = time.perf_counter() - t_kill
    for t in threads:
        t.join()

    # -- phase 2: diverged canary -> rollback, healthy -> promote -----
    def engine_steps():
        return [fleet.router.handle_for(n).engine.params_step
                for n in fleet.router.names()]

    probe = np.arange(1, 6, dtype=np.int32).tolist()
    max_on_bad = [0]
    mgr.save(2, params, opt, health={"verdict": "diverged"})
    deadline = time.time() + 20
    while time.time() < deadline and fleet.rollout.rollbacks == 0:
        max_on_bad[0] = max(max_on_bad[0],
                            sum(1 for s in engine_steps() if s == 2))
        post_with_retry({"tokens": probe})
    steps_after_rollback = engine_steps()
    mgr.save(3, params, opt, health={"verdict": "ok"})
    deadline = time.time() + 20
    while time.time() < deadline and fleet.rollout.promotions == 0:
        post_with_retry({"tokens": probe})
    time.sleep(0.1)
    steps_after_promote = engine_steps()

    with urllib.request.urlopen(f"{url}/stats", timeout=10) as r:
        snap = _json.loads(r.read())
    ro = fleet.rollout.snapshot()
    front.stop()
    fleet.stop()

    n_total = n_clients * reqs_per_client
    failures = []
    if errors:
        failures.append(f"client-visible failures: {errors}")
    if len(results) != n_total:
        failures.append(f"dropped requests: {len(results)}/{n_total}")
    if snap["quarantines"] < 1:
        failures.append("killed engine was never quarantined")
    if snap["readmissions"] < 1:
        failures.append("revived engine was never readmitted")
    if ro["rollbacks"] != 1 or max_on_bad[0] > 1:
        failures.append(f"diverged rollout not contained: rollbacks="
                        f"{ro['rollbacks']}, engines on bad "
                        f"fingerprint={max_on_bad[0]}")
    if steps_after_rollback != [1, 1, 1]:
        failures.append(f"fleet not restored to pinned step after "
                        f"rollback: {steps_after_rollback}")
    if ro["promotions"] != 1 or steps_after_promote != [3, 3, 3]:
        failures.append(f"healthy rollout did not promote fleet-wide: "
                        f"promotions={ro['promotions']}, steps "
                        f"{steps_after_promote}")
    if failures:
        raise RuntimeError("fleet smoke FAILED: " + "; ".join(failures))

    result = {
        "metric": "fleet_smoke_p50_latency",
        "value": snap["p50_latency_ms"],
        "unit": "ms",
        "p95_latency_ms": snap["p95_latency_ms"],
        "kill_recovery_s": round(kill_recovery_s, 3),
        "engines": 3,
        "clients": n_clients,
        "requests": n_total,
        "routed": snap["routed"],
        "completed": snap["completed"],
        "retried": snap["retried"],
        "shed_http_503": sheds[0],
        "quarantines": snap["quarantines"],
        "readmissions": snap["readmissions"],
        "canaries": ro["canaries"],
        "promotions": ro["promotions"],
        "rollbacks": ro["rollbacks"],
        "refusals": ro["refusals"],
        "final_steps": steps_after_promote,
        "backend": jax.default_backend(),
    }
    line = json.dumps(result)
    if out:
        with open(out, "w") as f:
            f.write(line + "\n")
    return result


def bench_pipeline_smoke(out=None):
    """ISSUE 10 acceptance: the closed train-and-serve loop on CPU,
    twice over one tiny LM — the run FAILS (raises) unless:

    Clean phase: a throttled supervised trainer publishes 4 blessed
    checkpoints (steps 6/12/18/24); EVERY one of them is canaried and
    promoted, in order, with zero rollbacks, and the blessed-to-served
    lag stays single-digit seconds.

    Faulted phase (fresh workspace): under seeded injection — a
    trainer preemption (kill), a torn checkpoint save (corrupt), and a
    NaN'd gradient window (diverge) — zero client requests fail, no
    response ever comes from below the promoted step or from a
    non-blessed step, the torn save is refused at the canary, and the
    loop still drains (served == last blessed) by the end.

    Records both phases' counters; `out` writes the JSON line to a
    file as well (scripts/pipeline_smoke.sh -> BENCH_pr10.json)."""
    import tempfile
    import threading

    import jax

    from singa_tpu.core.pipeline import PipelineController, PipelineSpec
    from singa_tpu.core.supervisor import Supervisor
    from singa_tpu.core.trainer import Trainer
    from singa_tpu.models.transformer import (synthetic_token_batches,
                                              transformer_lm)
    from singa_tpu.serve import EngineFleet, RolloutSpec, ServeSpec
    from singa_tpu.utils.faults import FaultSchedule, inject
    from singa_tpu.utils.health import HealthMonitor, HealthSpec

    vocab, seq = 64, 16
    shapes = {"data": {"input": (seq,), "target": (seq,)}}
    blessed_cadence = (6, 12, 18, 24)

    def run_loop(schedule):
        """One closed-loop run; returns (controller, supervisor,
        fleet, responses[(pinned_before, step)], failures,
        pinned_transitions)."""
        cfg = transformer_lm(vocab_size=vocab, num_layers=2,
                             embed_dim=32, num_heads=4, head_dim=8,
                             seq_len=seq, batchsize=4, train_steps=24)
        cfg.checkpoint_frequency = 6
        ws = tempfile.mkdtemp(prefix="pipeline_smoke_")
        tr = Trainer(cfg, shapes, log_fn=lambda s: None, donate=False,
                     health=HealthMonitor(HealthSpec(),
                                          log_fn=lambda s: None))
        sup = Supervisor(tr, ws, max_restarts=3, log=lambda s: None)
        net = tr.test_net or tr.train_net
        fleet = EngineFleet.local(
            net, ServeSpec(buckets=((2, 8),), max_new_tokens=4,
                           batch_window_s=0.002),
            2, workspace=ws,
            params=net.init_params(jax.random.PRNGKey(0)),
            rollout_spec=RolloutSpec(poll_s=0.05, window_s=0.2,
                                     min_requests=1),
            log_fn=lambda s: None)
        ctl = PipelineController(sup, fleet, ws,
                                 spec=PipelineSpec(lag_alarm_s=30),
                                 log_fn=lambda s: None)
        # pace training (~0.2 s/step) so the rollout can promote every
        # cadence save before the next one lands — the clean phase
        # gates on promote-per-publish, not newest-wins catch-up
        throttle = [lambda s, m: time.sleep(0.2)]
        rng = np.random.default_rng(0)
        responses, transitions, failures = [], [], [0]
        with inject(schedule):
            ctl.start(lambda: synthetic_token_batches(4, seq, vocab,
                                                      seed=5),
                      seed=0, hooks=throttle)
            try:
                deadline = time.monotonic() + 300.0
                while time.monotonic() < deadline:
                    done = not ctl.train_running()
                    lag = ctl.lag()
                    pinned = fleet.rollout.pinned_step
                    if not transitions or transitions[-1] != pinned:
                        transitions.append(pinned)
                    plen = int(rng.integers(1, 7))
                    prompt = rng.integers(1, vocab,
                                          plen).astype(np.int32)
                    try:
                        got = ctl.generate(prompt)
                        responses.append((pinned, got["step"]))
                    except Exception:  # noqa: BLE001 — gated below
                        failures[0] += 1
                    if done and lag["lag_steps"] == 0 and \
                            lag["blessed_step"] >= 0:
                        break
                if not ctl.wait(timeout=60.0):
                    raise RuntimeError("pipeline training never "
                                       "finished")
            finally:
                ctl.stop()
        return ctl, sup, fleet, responses, failures[0], transitions

    failures = []

    # -- clean phase: every blessed checkpoint promotes, in order -----
    ctl, sup, fleet, responses, client_failures, transitions = \
        run_loop(None)
    clean_lag = ctl.lag()
    promoted = [p for p in transitions if p >= 0]
    if ctl.train_error is not None or sup.failures:
        failures.append(f"clean run not clean: {ctl.train_error!r}, "
                        f"{sup.failures}")
    if client_failures:
        failures.append(f"clean run client failures: "
                        f"{client_failures}")
    if promoted != list(blessed_cadence):
        failures.append(f"clean run did not promote every blessed "
                        f"checkpoint in order: {promoted} != "
                        f"{list(blessed_cadence)}")
    if fleet.rollout.rollbacks != 0:
        failures.append(f"clean run rolled back "
                        f"{fleet.rollout.rollbacks}x")
    clean_promote_lag = (max(ctl.promote_lags_s)
                         if ctl.promote_lags_s else None)
    if clean_promote_lag is None or clean_promote_lag >= 10.0:
        failures.append(f"blessed-to-served lag not single-digit "
                        f"seconds: {clean_promote_lag}")
    clean = {
        "published": ctl.published,
        "promotions": fleet.rollout.promotions,
        "rollbacks": fleet.rollout.rollbacks,
        "canary_restarts": fleet.rollout.canary_restarts,
        "promoted_sequence": promoted,
        "promote_lag_max_s": (round(clean_promote_lag, 3)
                              if clean_promote_lag else None),
        "requests": len(responses),
        "client_failures": client_failures,
        "served_step": clean_lag["served_step"],
    }

    # -- faulted phase: kill + corrupt + diverge, traffic never blinks
    sched = FaultSchedule.parse(
        "step.train@8:preempt,ckpt.save@2:torn,step.grad@14:nan",
        seed=0)
    ctl, sup, fleet, responses, client_failures, transitions = \
        run_loop(sched)
    fault_lag = ctl.lag()
    blessed_ok = set(blessed_cadence) | {-1}
    below_pinned = [(p, s) for p, s in responses if s < p]
    off_blessed = sorted({s for _, s in responses}) if any(
        s not in blessed_ok for _, s in responses) else []
    if client_failures:
        failures.append(f"faulted run client failures: "
                        f"{client_failures}")
    if ctl.train_error is not None:
        failures.append(f"faulted run training failed: "
                        f"{ctl.train_error!r}")
    kinds = sorted(f.kind for f in sup.failures)
    if kinds != ["divergence", "preemption"]:
        failures.append(f"expected one preemption + one divergence "
                        f"rescue, got {kinds}")
    if {f.site for f in sched.fired} != \
            {"step.train", "ckpt.save", "step.grad"}:
        failures.append(f"injected faults did not all fire: "
                        f"{sched.fired}")
    if below_pinned:
        failures.append(f"responses served from below the promoted "
                        f"step: {below_pinned[:5]}")
    if off_blessed:
        failures.append(f"responses served from non-blessed steps: "
                        f"{off_blessed}")
    if fleet.rollout.refusals < 1:
        failures.append("torn checkpoint was never refused at the "
                        "canary")
    if fault_lag["lag_steps"] != 0 or \
            fault_lag["served_step"] != blessed_cadence[-1]:
        failures.append(f"faulted loop did not drain: {fault_lag}")
    faulted = {
        "published": ctl.published,
        "promotions": fleet.rollout.promotions,
        "rollbacks": fleet.rollout.rollbacks,
        "refusals": fleet.rollout.refusals,
        "torn_polls": fleet.rollout.mgr.torn_polls,
        "supervisor_failures": kinds,
        "requests": len(responses),
        "client_failures": client_failures,
        "served_step": fault_lag["served_step"],
        "blessed_step": fault_lag["blessed_step"],
    }

    if failures:
        raise RuntimeError("pipeline smoke FAILED: "
                           + "; ".join(failures))

    result = {
        "metric": "pipeline_smoke_promote_lag",
        "value": clean["promote_lag_max_s"],
        "unit": "s",
        "clean": clean,
        "faulted": faulted,
        "backend": jax.default_backend(),
    }
    line = json.dumps(result)
    if out:
        with open(out, "w") as f:
            f.write(line + "\n")
    return result


def bench_cb_smoke(n_requests=64, n_long=3, out=None):
    """ISSUE 8 acceptance: continuous batching vs the static bucket
    path under the same mixed load, over real HTTP.  61 shorts
    (max_new=2) + 3 longs (max_new=256) hit each server; the run
    FAILS (raises) unless:
      * on the cb leg, at least one short request that was submitted
        AFTER a long generation produced its first streamed token
        completes BEFORE that long generation finishes (no
        head-of-line blocking);
      * cb p95 <= 0.5x static p95 (shorts no longer pay for the
        batch-mate's full 256-token decode);
      * both legs compile O(1) programs at warmup and ZERO after
        (static: one bucket program; cb: one prefill + one decode).
    Records p50/p95/p99, decode tok/s, slot occupancy, block-pool
    utilization, and compile counts for both paths; `out` writes the
    JSON line as well (scripts/serve_smoke.sh -> BENCH_pr8.json).
    The model is bench-tiny: the subject is the scheduler, not the
    matmuls."""
    import json as _json
    import queue as _queue
    import threading
    import urllib.request

    import jax

    from singa_tpu.core.net import build_net
    from singa_tpu.models.transformer import transformer_lm
    from singa_tpu.serve import InferenceEngine, InferenceServer, ServeSpec

    vocab, seq = 64, 16
    cfg = transformer_lm(vocab_size=vocab, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=seq,
                         batchsize=2)
    net = build_net(cfg, "kTest",
                    {"data": {"input": (seq,), "target": (seq,)}})
    params = net.init_params(jax.random.PRNGKey(0))

    n_short = n_requests - n_long
    # a 1024-token horizon puts the static path's pay-for-max cost in
    # real decode compute (a 2-token request still rides a 1024-step
    # scan), not per-call overhead — the regime the gate is about
    max_new_long = 1024
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, vocab, int(rng.integers(1, seq + 1)))
               .tolist() for _ in range(n_requests)]

    def run_leg(spec, streaming):
        engine = InferenceEngine(net, spec, params=params,
                                 log_fn=lambda s: None)
        warm = engine.warmup()
        server = InferenceServer(engine, port=0, log_fn=lambda s: None)
        server.start()
        host, port = server.address
        url = f"http://{host}:{port}"

        def post(payload, timeout=120):
            req = urllib.request.Request(
                f"{url}/generate", data=_json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return _json.loads(r.read())

        errors, lat = [], [None] * n_requests
        long_first_tok = [None] * n_long   # monotonic, per long
        long_done = [None] * n_long
        short_span = [None] * n_short      # (t_submit, t_done)
        t_base = time.monotonic()

        def long_client(j):
            try:
                body = {"tokens": prompts[j], "timeout": 120,
                        "max_new": max_new_long}
                t0 = time.monotonic()
                if streaming:
                    body["stream"] = True
                    req = urllib.request.Request(
                        f"{url}/generate",
                        data=_json.dumps(body).encode(),
                        headers={"Content-Type": "application/json"})
                    ntok = 0
                    with urllib.request.urlopen(req, timeout=120) as r:
                        for ln in r:
                            if not ln.strip():
                                continue
                            ev = _json.loads(ln)
                            if "error" in ev and "done" not in ev:
                                raise RuntimeError(ev["error"])
                            if "token" in ev:
                                ntok += 1
                                if long_first_tok[j] is None:
                                    long_first_tok[j] = time.monotonic()
                            if ev.get("done"):
                                assert len(ev["tokens"]) == ntok
                else:
                    outp = post(body)
                    assert len(outp["tokens"]) == max_new_long
                    long_first_tok[j] = t0   # no stream: submit time
                long_done[j] = time.monotonic()
                lat[j] = long_done[j] - t0
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(f"long[{j}]: {e!r}")

        work: "_queue.Queue" = _queue.Queue()
        for i in range(n_short):
            work.put(i)

        def short_worker():
            while True:
                try:
                    i = work.get_nowait()
                except _queue.Empty:
                    return
                try:
                    t0 = time.monotonic()
                    outp = post({"tokens": prompts[n_long + i],
                                 "timeout": 120, "max_new": 2})
                    t1 = time.monotonic()
                    assert len(outp["tokens"]) == 2
                    lat[n_long + i] = t1 - t0
                    short_span[i] = (t0, t1)
                except Exception as e:  # noqa: BLE001
                    errors.append(f"short[{i}]: {e!r}")

        longs = [threading.Thread(target=long_client, args=(j,))
                 for j in range(n_long)]
        for t in longs:
            t.start()
        # shorts join a load that is already decoding the longs; 8
        # closed-loop workers keep the static queue several batches
        # deep without turning the cb leg's own admission drain into
        # the bottleneck
        time.sleep(0.05)
        workers = [threading.Thread(target=short_worker)
                   for _ in range(8)]
        for t in workers:
            t.start()
        for t in workers + longs:
            t.join()
        wall = time.monotonic() - t_base

        with urllib.request.urlopen(f"{url}/stats", timeout=10) as r:
            snap = _json.loads(r.read())
        server.stop()
        return {"errors": errors, "lat": lat, "snap": snap,
                "warm": warm, "wall": wall,
                "long_first_tok": long_first_tok,
                "long_done": long_done, "short_span": short_span}

    st_spec = ServeSpec(buckets=((2, seq),), max_new_tokens=max_new_long,
                        temperature=0.0, batch_window_s=0.005,
                        request_timeout_s=120.0, reload_poll_s=100.0)
    cb_spec = ServeSpec(buckets=((2, seq),), max_new_tokens=max_new_long,
                        temperature=0.0, request_timeout_s=120.0,
                        reload_poll_s=100.0,
                        cb="on", cb_slots=8, cb_block_len=4)
    st = run_leg(st_spec, streaming=False)
    cb = run_leg(cb_spec, streaming=True)

    def quantiles(lat):
        a = np.sort(np.asarray([v for v in lat if v is not None]))
        return {q: float(a[min(int(q / 100 * a.size), a.size - 1)])
                for q in (50, 95, 99)}

    failures = []
    for leg, name in ((st, "static"), (cb, "cb")):
        if leg["errors"]:
            failures.append(f"{name} client errors: {leg['errors']}")
        if any(v is None for v in leg["lat"]):
            failures.append(f"{name}: dropped requests")
        if leg["snap"]["compiles"] != leg["warm"]:
            failures.append(
                f"{name} recompiled after warmup: "
                f"{leg['snap']['compiles']} != {leg['warm']}")
    # the tentpole behavior: a short admitted after a long's first
    # streamed token finishes while that long is still decoding
    overlapped = any(
        ft is not None and dn is not None and sp is not None
        and sp[0] > ft and sp[1] < dn
        for ft, dn in zip(cb["long_first_tok"], cb["long_done"])
        for sp in cb["short_span"])
    if not overlapped:
        failures.append("no short request completed while a long "
                        "generation was still decoding")
    stq, cbq = quantiles(st["lat"]), quantiles(cb["lat"])
    if not failures and cbq[95] > 0.5 * stq[95]:
        failures.append(f"cb p95 {cbq[95] * 1e3:.1f}ms > 0.5x static "
                        f"p95 {stq[95] * 1e3:.1f}ms")
    if failures:
        raise RuntimeError("cb smoke FAILED: " + "; ".join(failures))

    result = {
        "metric": "cb_smoke_p95_ratio",
        "value": round(cbq[95] / stq[95], 4),
        "unit": "cb_p95_over_static_p95",
        "gate": 0.5,
        "requests": n_requests,
        "long_requests": n_long,
        "max_new_long": max_new_long,
        "short_completed_while_long_decoding": overlapped,
        "static": {
            "p50_ms": round(stq[50] * 1e3, 3),
            "p95_ms": round(stq[95] * 1e3, 3),
            "p99_ms": round(stq[99] * 1e3, 3),
            "wall_s": round(st["wall"], 3),
            "tokens_per_s_p50": st["snap"]["p50_tokens_per_s"],
            "generated_tokens": st["snap"]["generated_tokens"],
            "batch_occupancy": st["snap"]["batch_occupancy"],
            "compiles_warmup": st["warm"],
            "compiles_total": st["snap"]["compiles"],
        },
        "cb": {
            "p50_ms": round(cbq[50] * 1e3, 3),
            "p95_ms": round(cbq[95] * 1e3, 3),
            "p99_ms": round(cbq[99] * 1e3, 3),
            "wall_s": round(cb["wall"], 3),
            "tokens_per_s_p50": cb["snap"]["p50_tokens_per_s"],
            "generated_tokens": cb["snap"]["generated_tokens"],
            "slot_occupancy": cb["snap"]["cb_slot_occupancy"],
            "block_utilization": cb["snap"]["cb_block_utilization"],
            "decode_steps": cb["snap"]["cb_steps"],
            "slots": cb_spec.cb_slots,
            "block_len": cb_spec.cb_block_len,
            "pool_blocks": cb_spec.cb_pool_blocks,
            "compiles_warmup": cb["warm"],
            "compiles_total": cb["snap"]["compiles"],
        },
        "backend": jax.default_backend(),
    }
    line = json.dumps(result)
    if out:
        with open(out, "w") as f:
            f.write(line + "\n")
    return result


def bench_obs_overhead(batch_size=64, steps=96, scan_chunk=8,
                       reps=3, out=None):
    """ISSUE 6 acceptance: `--obs on` must cost < 3% wall time on the
    chunked LeNet training loop (the span-per-chunk hot path: one
    trainer.chunk + feeder.stage span pair per dispatch, plus the
    trace buffer append).  A/B of identical runs — obs off vs obs on
    with trace + event log under a temp dir — best-of-`reps` each leg
    to shave scheduler noise.  `value` is the overhead fraction
    (on/off - 1); `out` writes the JSON line as well
    (scripts/obs_smoke.sh -> BENCH_pr6.json)."""
    import tempfile

    import jax

    from singa_tpu import obs
    from singa_tpu.data.synthetic import synthetic_image_batches

    trainer, _, _, _ = _lenet_trainer(batch_size)
    trainer.cfg.train_steps = steps
    trainer.cfg.display_frequency = 0
    trainer.cfg.test_frequency = 0
    trainer.cfg.checkpoint_frequency = 0

    def one():
        params, opt_state = trainer.init(seed=0)
        it = synthetic_image_batches(batch_size, seed=1, stream_seed=7)
        t0 = time.perf_counter()
        trainer.run(params, opt_state, it, seed=0,
                    scan_chunk=scan_chunk)
        return time.perf_counter() - t0

    one()   # warm the compile caches so both legs are steady-state
    tmp = tempfile.mkdtemp(prefix="obs_bench_")
    spec = obs.ObsSpec(trace=os.path.join(tmp, "trace.json"),
                       events=os.path.join(tmp, "events.jsonl"))

    # interleaved A/B reps: host drift (thermal, allocator state)
    # hits both legs equally instead of biasing whichever ran last
    off = on = float("inf")
    for _ in range(reps):
        off = min(off, one())
        with obs.session(spec):
            on = min(on, one())
    overhead = on / off - 1.0
    result = {
        "metric": "obs_overhead",
        "value": round(overhead, 4),
        "unit": "wall_time_fraction",
        "gate": 0.03,
        "passed": overhead < 0.03,
        "wall_obs_off_s": round(off, 4),
        "wall_obs_on_s": round(on, 4),
        "batch": batch_size, "steps": steps, "scan_chunk": scan_chunk,
        "reps": reps,
        "backend": jax.default_backend(),
        "cpu_count": os.cpu_count(),
    }
    line = json.dumps(result)
    if out:
        with open(out, "w") as f:
            f.write(line + "\n")
    return result


def bench_perf_smoke(n_requests=12, n_long=2, out=None):
    """ISSUE 15 acceptance: the performance observatory measured end
    to end.  One training leg (tiny MLP through the fused scan —
    readiness timer, train_scan compile accounting, analytic memory
    components) and one cb serving leg under mixed load (exactly 2
    warmup compiles, 0 after; readiness + HBM watermark exported in
    /metrics; CostWatch harvest adds 0 compiles), then the interleaved
    obs-overhead A/B (observatory collectors ride every session
    registry, so the PR 6 ≤3% bar re-certifies with perf on) and a
    `bench_report.py --trajectory` render over the existing
    artifacts.  Writes BENCH_pr15.json."""
    import json as _json
    import subprocess
    import threading
    import urllib.request

    import jax

    from singa_tpu.config.schema import model_config_from_dict
    from singa_tpu.core.net import build_net
    from singa_tpu.core.trainer import Trainer
    from singa_tpu.data.synthetic import synthetic_image_batches
    from singa_tpu.models.transformer import transformer_lm
    from singa_tpu.obs import perf
    from singa_tpu.obs.metrics import parse_prometheus
    from singa_tpu.serve import (InferenceEngine, InferenceServer,
                                 ServeSpec)

    perf.reset()

    # -- training leg: readiness latch + CompileWatch on the scan ----------
    tcfg = model_config_from_dict({
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
    trainer = Trainer(tcfg, {"data": {"pixel": (28, 28), "label": ()}},
                      donate=False, log_fn=lambda s: None)
    tp, to = trainer.init(0)
    it = synthetic_image_batches(8, seed=1, stream_seed=7)
    chunk = [next(it) for _ in range(4)]
    stacked = jax.tree_util.tree_map(
        lambda *xs: np.stack(xs), *chunk)
    # the convergence tool's pre-compile path: CompileWatch times it,
    # CostWatch harvests it, and trainer.run below reuses the warm
    # executable
    trainer.compiled_scan(tp, to, stacked, 0, jax.random.PRNGKey(0),
                          4, True)
    trainer.run(tp, to, synthetic_image_batches(8, seed=1,
                                                stream_seed=7),
                seed=0, scan_chunk=4)
    tsnap = perf.snapshot()
    restart_training = tsnap["training_ready_s"] or 0.0
    train_compiles = tsnap["compiles"].get("train_scan", 0)

    # -- serving leg: tiny cb engine under mixed long/short load ----------
    vocab, seq = 64, 16
    cfg = transformer_lm(vocab_size=vocab, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=seq,
                         batchsize=2)
    net = build_net(cfg, "kTest",
                    {"data": {"input": (seq,), "target": (seq,)}})
    params = net.init_params(jax.random.PRNGKey(0))
    max_new_long = 64
    spec = ServeSpec(buckets=((2, seq),), max_new_tokens=max_new_long,
                     temperature=0.0, request_timeout_s=120.0,
                     reload_poll_s=100.0,
                     cb="on", cb_slots=8, cb_block_len=4)
    engine = InferenceEngine(net, spec, params=params,
                             log_fn=lambda s: None)
    server = InferenceServer(engine, port=0, log_fn=lambda s: None)
    server.start()                 # load + warmup (2 cb programs)
    warmup_compiles = engine.stats.compiles
    host, port = server.address
    url = f"http://{host}:{port}"
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, vocab, int(rng.integers(1, 13)))
               .tolist() for _ in range(n_requests)]
    errors, lat = [], []

    def post(tokens, max_new):
        t0 = time.monotonic()
        req = urllib.request.Request(
            f"{url}/generate",
            data=_json.dumps({"tokens": tokens, "timeout": 120,
                              "max_new": max_new}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            outp = _json.loads(r.read())
        assert len(outp["tokens"]) == max_new
        lat.append(time.monotonic() - t0)

    def client(i):
        try:
            post(prompts[i], max_new_long if i < n_long else 2)
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(f"req[{i}]: {e!r}")

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    post_warmup = engine.stats.compiles - warmup_compiles

    # CostWatch no-recompile property: a full harvest sweep over the
    # compiled programs must not move the compile counter
    before = engine.stats.compiles
    harvested = engine.harvest_costs()
    costwatch_compiles = engine.stats.compiles - before

    with urllib.request.urlopen(f"{url}/metrics", timeout=10) as r:
        metrics = parse_prometheus(r.read().decode())
    server.stop()

    snap = perf.snapshot()
    restart_serving = metrics.get("singa_restart_to_serving_seconds",
                                  0.0)
    hbm_watermark = metrics.get("singa_hbm_watermark_bytes", 0.0)
    rss = metrics.get("singa_process_rss_bytes", 0.0)
    cb_flops = snap["cost"].get("cb_decode", {}).get("flops", 0.0)
    mfu = metrics.get('singa_program_mfu{program="cb_decode"}')

    # -- overhead A/B: the observatory's collectors are registered on
    # every obs session registry, so the PR 6 bar re-certifies here
    over = bench_obs_overhead(batch_size=16, steps=32, scan_chunk=8,
                              reps=2)

    # -- trajectory render over the existing artifacts (run before
    # this bench's own artifact lands, so a previously-green tree
    # stays the reference) --
    traj = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "bench_report.py"),
         "--trajectory", REPO],
        capture_output=True, text=True)

    def gate(value, bound, op):
        ok = {"==": value == bound, "<=": value <= bound,
              ">": value > bound}[op]
        return {"value": value, "bound": bound, "op": op, "pass": ok}

    gates = {
        "warmup_cb_compiles": gate(warmup_compiles, 2, "=="),
        "post_warmup_compiles": gate(post_warmup, 0, "=="),
        "recompile_anomalies": gate(snap["anomalies"], 0, "=="),
        "restart_to_serving": gate(round(restart_serving, 4), 0, ">"),
        "restart_to_training": gate(round(restart_training, 4), 0,
                                    ">"),
        "hbm_watermark": gate(hbm_watermark, 0, ">"),
        "costwatch_compiles": gate(costwatch_compiles, 0, "=="),
        "obs_overhead": gate(over["value"], 0.03, "<="),
        "trajectory_renders": gate(traj.returncode, 0, "=="),
    }
    failures = [f"gate {k}: {g['value']} not {g['op']} {g['bound']}"
                for k, g in gates.items() if not g["pass"]]
    if errors:
        failures.append(f"client errors: {errors}")
    if rss <= 0:
        failures.append("process collector missing from /metrics")
    if harvested < 2 or cb_flops <= 0:
        failures.append(f"CostWatch harvested nothing "
                        f"({harvested} programs, flops {cb_flops})")
    if traj.returncode != 0:
        failures.append(f"trajectory: {traj.stderr.strip()[-500:]}")
    if failures:
        raise RuntimeError("perf smoke FAILED: " + "; ".join(failures))

    a = np.sort(np.asarray(lat))
    result = {
        "metric": "perf_smoke_post_warmup_compiles",
        "value": post_warmup,
        "unit": "compiles",
        "restart_to_serving_s": round(restart_serving, 4),
        "restart_to_training_s": round(restart_training, 4),
        "hbm_watermark_bytes": int(hbm_watermark),
        "memory_components": snap["memory_components"],
        "obs_overhead": over["value"],
        "compile_seconds_sum": snap["compile_seconds_sum"],
        "compiles": snap["compiles"],
        "train_scan_compiles": train_compiles,
        "cost_programs": sorted(snap["cost"]),
        "cb_decode_flops": cb_flops,
        "mfu_cb_decode": mfu,       # None on CPU (peak table has no
                                    # entry); populated on TPU
        "short_p95_ms": round(float(
            a[min(int(0.95 * a.size), a.size - 1)]) * 1e3, 3),
        "requests": n_requests,
        "gates": gates,
        "backend": jax.default_backend(),
    }
    line = json.dumps(result)
    if out:
        with open(out, "w") as f:
            f.write(line + "\n")
    return result


def bench_traffic_smoke(out=None):
    """ISSUE 11 acceptance: the SLO-driven autoscaler under adversarial
    open-loop traffic on CPU — a 1-engine fleet rides a ramp -> flash
    crowd -> decay -> quiet schedule and the run FAILS (raises) unless:
      * the fleet GREW under the flash crowd (scale_ups >= 1, peak
        engine count above the starting size) and SHRANK back once
        quiet (scale_downs >= 1, final count below peak) — capacity
        followed the workload in both directions;
      * p95 stayed inside the SLO outside the spike (gated on the
        quiet phase: the steady state the autoscaler converged to);
      * zero non-shed failures and zero harness drops — every offered
        request completed or was shed with Overloaded, nothing else;
      * retiring the engine that holds a live slow-reader stream with
        drain=True delivers EVERY token and the done event before the
        member leaves — scale-down never drops an in-flight stream.
    Records per-phase offered/completed/shed and percentiles, the
    autoscaler outcome counters, and the engine-count trajectory;
    `out` writes the JSON line to a file as well
    (scripts/traffic_smoke.sh -> BENCH_pr11.json)."""
    import tempfile
    import threading

    import jax

    from singa_tpu.core.net import build_net
    from singa_tpu.models.transformer import transformer_lm
    from singa_tpu.serve import (EngineFleet, RolloutSpec, RouterSpec,
                                 ServeSpec)
    from singa_tpu.serve.autoscale import AutoScaler, AutoScaleSpec
    from singa_tpu.serve.traffic import (TrafficGen, flash_crowd, ramp,
                                         steady)
    from singa_tpu.utils.checkpoint import CheckpointManager

    vocab, seq = 64, 16
    cfg = transformer_lm(vocab_size=vocab, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=seq,
                         batchsize=2)
    net = build_net(cfg, "kTest",
                    {"data": {"input": (seq,), "target": (seq,)}})
    params = net.init_params(jax.random.PRNGKey(0))

    ws = tempfile.mkdtemp(prefix="traffic_smoke_")
    mgr = CheckpointManager(ws, log_fn=lambda s: None)
    mgr.save(1, params, {"t": np.zeros(())}, health={"verdict": "ok"})

    # 2 slots + a 4-deep queue caps one engine well under the flash
    # rate: the ramp fits, the flash does not — the spike has to be
    # answered with capacity, not absorbed
    spec = ServeSpec(buckets=((2, 16),), max_new_tokens=48,
                     batch_window_s=0.002, request_timeout_s=30.0,
                     queue_capacity=4, cb="on", cb_slots=2,
                     cb_block_len=8)
    ascale = AutoScaleSpec(slo_p95_ms=1000.0, max_shed_rate=0.02,
                           min_engines=1, max_engines=3,
                           cooldown_s=1.0, window_s=1.5, tick_s=0.1,
                           quiet_ticks=10, queue_high=4.0,
                           occ_high=0.9, drain_timeout_s=20.0)
    fleet = EngineFleet.local(
        net, spec, 1, workspace=ws, params=params,
        router_spec=RouterSpec(probe_period_s=0.05,
                               quarantine_after=3),
        rollout_spec=RolloutSpec(poll_s=0.2, window_s=0.5),
        log_fn=lambda s: None)
    fleet.start()
    scaler = AutoScaler(fleet, spec=ascale, log_fn=lambda s: None)
    scaler.start()

    # engine-count trajectory, sampled while traffic runs
    sizes = []
    stop_sampling = threading.Event()

    def sample():
        while not stop_sampling.wait(0.05):
            sizes.append(len([m for m in fleet.router.members()
                              if not m.get("draining")]))

    sampler = threading.Thread(target=sample, daemon=True)
    sampler.start()

    gen = TrafficGen(
        lambda toks: fleet.generate(toks.tolist()),
        stream_fn=lambda toks, max_new=None: fleet.generate_stream(
            toks.tolist(), max_new=max_new),
        vocab=vocab, seed=0, log_fn=lambda s: None)
    phases = [ramp("ramp", 4.0, 2.0, 6.0, prompt_lens=(4, 8)),
              flash_crowd("flash", 5.0, 6.0, k=20.0,
                          prompt_lens=(4, 8)),
              ramp("decay", 4.0, 6.0, 2.0, prompt_lens=(4, 8)),
              steady("quiet", 6.0, 1.0, prompt_lens=(4,))]
    rep = gen.run(phases, drain_timeout_s=30.0)

    # idle tail: give the quiet-streak hysteresis room to scale down
    deadline = time.time() + 20
    while time.time() < deadline and scaler.scale_downs == 0:
        time.sleep(0.1)
    time.sleep(0.3)                      # let a draining member leave
    stop_sampling.set()
    sampler.join(2.0)
    scaler.stop()

    # -- drain sub-test: retire the engine holding a live stream ------
    while len(fleet.router.names()) < 2:
        fleet.grow()
    probe = np.arange(1, 5, dtype=np.int32).tolist()
    stream_events, stream_errors = [], []
    started = threading.Event()

    def slow_reader():
        try:
            for ev in fleet.generate_stream(probe, max_new=6):
                started.set()
                stream_events.append(ev)
                if "token" in ev:
                    time.sleep(0.05)     # slower than the decode loop
        except Exception as e:  # noqa: BLE001 — surfaced in gates
            stream_errors.append(repr(e))
            started.set()

    reader = threading.Thread(target=slow_reader)
    reader.start()
    started.wait(10.0)
    victim = None
    deadline = time.time() + 5
    while time.time() < deadline and victim is None:
        for m in fleet.router.members():
            if m["in_flight"] > 0:
                victim = m["name"]
                break
        if victim is None:
            time.sleep(0.01)
    stream_drained = (fleet.retire(victim, drain=True, timeout_s=20.0)
                      if victim is not None else False)
    reader.join(30.0)
    fleet.stop()

    sc = scaler.snapshot()
    tot = rep["totals"]
    quiet_row = next(r for r in rep["phases"] if r["name"] == "quiet")
    peak = max(sizes) if sizes else 1
    final = sizes[-1] if sizes else 1
    got_done = any(ev.get("done") for ev in stream_events)
    n_tokens = sum(1 for ev in stream_events if "token" in ev)

    failures = []
    if sc["scale_ups"] < 1 or peak <= 1:
        failures.append(f"fleet never grew under the flash crowd "
                        f"(scale_ups={sc['scale_ups']}, peak={peak})")
    if sc["scale_downs"] < 1 or final >= peak:
        failures.append(f"fleet never shrank after the spike "
                        f"(scale_downs={sc['scale_downs']}, "
                        f"peak={peak}, final={final})")
    if quiet_row["p95_ms"] is not None and \
            quiet_row["p95_ms"] > ascale.slo_p95_ms:
        failures.append(f"quiet-phase p95 {quiet_row['p95_ms']}ms "
                        f"blew the {ascale.slo_p95_ms}ms SLO")
    if tot["failed"] != 0:
        failures.append(f"non-shed failures: {tot['failed']} "
                        f"({tot['errors'][:3]})")
    if tot["dropped_harness"] != 0:
        failures.append(f"harness dropped {tot['dropped_harness']} "
                        f"arrivals (raise max_outstanding)")
    if victim is None:
        failures.append("drain sub-test never saw the stream's "
                        "in-flight slot")
    if stream_errors or not got_done or not stream_drained:
        failures.append(f"scale-down dropped an in-flight stream: "
                        f"errors={stream_errors}, done={got_done}, "
                        f"drained={stream_drained}, "
                        f"tokens={n_tokens}")
    if failures:
        raise RuntimeError("traffic smoke FAILED: "
                           + "; ".join(failures))

    result = {
        "metric": "traffic_smoke_quiet_p95_latency",
        "value": quiet_row["p95_ms"],
        "unit": "ms",
        "slo_p95_ms": ascale.slo_p95_ms,
        "offered": tot["offered"],
        "completed": tot["completed"],
        "shed": tot["shed"],
        "failed": tot["failed"],
        "shed_rate": tot["shed_rate"],
        "p50_ms": tot["p50_ms"],
        "p95_ms": tot["p95_ms"],
        "p99_ms": tot["p99_ms"],
        "phases": [{k: r[k] for k in ("name", "offered", "completed",
                                      "shed", "p95_ms")}
                   for r in rep["phases"]],
        "engines_start": 1,
        "engines_peak": peak,
        "engines_final": final,
        "scale_ups": sc["scale_ups"],
        "scale_downs": sc["scale_downs"],
        "holds": sc["holds"],
        "aborts": sc["aborts"],
        "drained_clean": sc["drained_clean"],
        "drain_timeouts": sc["drain_timeouts"],
        "stream_drain_tokens": n_tokens,
        "stream_drained": stream_drained,
        "backend": jax.default_backend(),
    }
    line = json.dumps(result)
    if out:
        with open(out, "w") as f:
            f.write(line + "\n")
    return result


def bench_tail_smoke(out=None):
    """ISSUE 12 acceptance: tail-tolerant serving on CPU, three legs —
    the run FAILS (raises) unless every gate holds:
      * HEDGE leg: two identical 3-engine fleets, one engine in each
        turned into a straggler (`set_stall`); identical closed-loop
        traffic.  Gates: hedged p99 <= 0.5x unhedged p99 (hedging cut
        the tail at least 2x) with hedges <= 10% of routed (the
        retry-budget bound, observed not just promised);
      * BROWNOUT leg: a 2-engine fleet under open-loop overload with a
        1:1:1 interactive/batch/best_effort mix.  Gates: retry
        amplification (attempts/routed) <= 1.2x, interactive p95
        holds the SLO while best_effort sheds (brownout engaged);
      * DOA leg: requests arriving with an already-expired deadline
        are counted `expired_on_arrival` and burn ZERO engine steps.
    Records both p99s, the hedge rate, amplification, per-class
    sheds/latency, and the DOA accounting; `out` writes the JSON line
    to a file as well (scripts/tail_smoke.sh -> BENCH_pr12.json)."""
    import tempfile
    import threading

    import jax

    from singa_tpu.core.net import build_net
    from singa_tpu.models.transformer import transformer_lm
    from singa_tpu.serve import (DeadlineExpired, EngineFleet,
                                 RouterSpec, ServeSpec)
    from singa_tpu.serve.traffic import TrafficGen, stall_chaos, steady
    from singa_tpu.utils.checkpoint import CheckpointManager

    vocab, seq = 64, 16
    cfg = transformer_lm(vocab_size=vocab, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=seq,
                         batchsize=2)
    net = build_net(cfg, "kTest",
                    {"data": {"input": (seq,), "target": (seq,)}})
    params = net.init_params(jax.random.PRNGKey(0))

    def make_fleet(size, router_spec, queue_capacity=8):
        ws = tempfile.mkdtemp(prefix="tail_smoke_")
        mgr = CheckpointManager(ws, log_fn=lambda s: None)
        mgr.save(1, params, {"t": np.zeros(())},
                 health={"verdict": "ok"})
        spec = ServeSpec(buckets=((2, seq),), max_new_tokens=4,
                         batch_window_s=0.002, request_timeout_s=30.0,
                         queue_capacity=queue_capacity, cb="on",
                         cb_slots=2, cb_block_len=4)
        fleet = EngineFleet.local(net, spec, size, workspace=ws,
                                  params=params,
                                  router_spec=router_spec,
                                  log_fn=lambda s: None)
        fleet.start()
        return fleet

    # -- leg 1: hedged vs unhedged tail under one straggler -----------
    def hedge_leg(hedge):
        rspec = RouterSpec(probe_period_s=0.05, quarantine_after=10,
                           request_timeout_s=30.0, hedge=hedge,
                           hedge_min_s=0.1, hedge_max_s=0.25)
        fleet = make_fleet(3, rspec)
        stall_chaos(fleet, stall_s=0.25)()   # latch the straggler
        lats, errors = [], []
        lock = threading.Lock()

        def worker(i):
            rng = np.random.default_rng(100 + i)
            for _ in range(30):
                toks = rng.integers(1, vocab, size=4).tolist()
                t0 = time.monotonic()
                try:
                    fleet.generate(toks)
                except Exception as e:  # noqa: BLE001 — gated below
                    with lock:
                        errors.append(repr(e))
                    continue
                with lock:
                    lats.append(time.monotonic() - t0)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120.0)
        snap = fleet.router.stats.snapshot()
        cancelled = sum(fleet.router.handle_for(n).engine
                        .stats.cancelled
                        for n in fleet.router.names())
        fleet.stop()
        lats.sort()
        p99 = (lats[min(int(0.99 * len(lats)), len(lats) - 1)] * 1e3
               if lats else None)
        return {"p99_ms": round(p99, 3) if p99 else None,
                "completed": len(lats), "errors": errors,
                "routed": snap["routed"], "hedges": snap["hedges"],
                "hedge_wins": snap["hedge_wins"],
                "cancelled": cancelled}

    unhedged = hedge_leg("off")
    hedged = hedge_leg("on")
    hedge_rate = hedged["hedges"] / max(hedged["routed"], 1)
    tail_ratio = (hedged["p99_ms"] / unhedged["p99_ms"]
                  if hedged["p99_ms"] and unhedged["p99_ms"]
                  else None)

    # -- leg 2: brownout under an open-loop overload with a QoS mix ---
    slo_p95_ms = 2000.0
    rspec = RouterSpec(probe_period_s=0.05, quarantine_after=10,
                       request_timeout_s=30.0, hedge="off",
                       brownout_shed_rate=0.05)
    fleet = make_fleet(2, rspec, queue_capacity=4)
    for n in fleet.router.names():     # throttle so the offered load
        fleet.router.handle_for(n).engine.set_stall(0.02)  # saturates
    gen = TrafficGen(
        lambda toks, priority="interactive": fleet.generate(
            toks.tolist(), priority=priority),
        vocab=vocab, seed=0, max_outstanding=512,
        log_fn=lambda s: None)
    rep = gen.run([steady("overload", duration_s=4.0, rate_rps=150.0,
                          prompt_lens=(4,), max_new=(4,),
                          priorities=("interactive", "batch",
                                      "best_effort"),
                          priority_weights=(1.0, 1.0, 1.0))],
                  drain_timeout_s=60.0)
    rsnap = fleet.router.stats.snapshot()
    amplification = rsnap["attempts"] / max(rsnap["routed"], 1)
    by_class = rep["totals"]["by_class"]
    inter_p95 = (by_class.get("interactive") or {}).get("p95_ms")
    be_sheds = (rsnap["shed_best_effort"]
                + sum(fleet.router.handle_for(n).engine
                      .stats.shed_best_effort
                      for n in fleet.router.names()))

    # -- leg 3: dead on arrival burns zero engine steps ---------------
    idle_deadline = time.time() + 30
    while time.time() < idle_deadline and any(
            m["in_flight"] > 0 for m in fleet.router.members()):
        time.sleep(0.05)
    time.sleep(0.3)                      # let the decode loops drain
    doa_before = rsnap["expired_on_arrival"]

    def engine_steps():
        return sum(fleet.router.handle_for(n).engine.stats.cb_steps
                   for n in fleet.router.names())

    steps_before = engine_steps()
    doa_n = 5
    doa_refused = 0
    dead = time.monotonic() - 1.0
    for _ in range(doa_n):
        try:
            fleet.generate([1, 2, 3], deadline=dead)
        except DeadlineExpired:
            doa_refused += 1
    time.sleep(0.2)
    steps_after = engine_steps()
    expired = (fleet.router.stats.snapshot()["expired_on_arrival"]
               - doa_before)
    doa_steps_burned = steps_after - steps_before
    fleet.stop()

    gates = {
        "tail_ratio": {"value": tail_ratio, "bound": 0.5,
                       "op": "<=",
                       "pass": bool(tail_ratio is not None
                                    and tail_ratio <= 0.5)},
        "hedge_rate": {"value": round(hedge_rate, 4), "bound": 0.10,
                       "op": "<=", "pass": bool(hedge_rate <= 0.10)},
        "retry_amplification": {
            "value": round(amplification, 4), "bound": 1.2,
            "op": "<=", "pass": bool(amplification <= 1.2)},
        "interactive_p95": {
            "value": inter_p95, "bound": slo_p95_ms, "op": "<=",
            "pass": bool(inter_p95 is not None
                         and inter_p95 <= slo_p95_ms)},
        "best_effort_sheds": {"value": be_sheds, "bound": 1,
                              "op": ">=",
                              "pass": bool(be_sheds >= 1)},
        "expired_on_arrival": {"value": expired, "bound": doa_n,
                               "op": "==",
                               "pass": bool(expired == doa_n
                                            and doa_refused == doa_n)},
        "doa_zero_steps": {"value": doa_steps_burned, "bound": 0,
                           "op": "==",
                           "pass": bool(doa_steps_burned == 0)},
    }
    failures = [f"{k}: {g['value']} not {g['op']} {g['bound']}"
                for k, g in gates.items() if not g["pass"]]
    if unhedged["errors"] or hedged["errors"]:
        failures.append(f"hedge legs saw non-shed failures: "
                        f"{(unhedged['errors'] + hedged['errors'])[:3]}")
    if rep["totals"]["failed"] != 0:
        failures.append(f"brownout leg non-shed failures: "
                        f"{rep['totals']['errors'][:3]}")
    if failures:
        raise RuntimeError("tail smoke FAILED: " + "; ".join(failures))

    result = {
        "metric": "tail_smoke_p99_ratio",
        "value": round(tail_ratio, 4),
        "unit": "x",
        "hedged_p99_ms": hedged["p99_ms"],
        "unhedged_p99_ms": unhedged["p99_ms"],
        "hedge_rate": round(hedge_rate, 4),
        "hedges": hedged["hedges"],
        "hedge_wins": hedged["hedge_wins"],
        "cancelled": hedged["cancelled"],
        "retry_amplification": round(amplification, 4),
        "interactive_p95_ms": inter_p95,
        "slo_p95_ms": slo_p95_ms,
        "best_effort_sheds": be_sheds,
        "brownout_sheds": rsnap["brownout_sheds"],
        "shed_by_class": {
            "interactive": rsnap["shed_interactive"],
            "batch": rsnap["shed_batch"],
            "best_effort": rsnap["shed_best_effort"]},
        "offered": rep["totals"]["offered"],
        "completed": rep["totals"]["completed"],
        "shed": rep["totals"]["shed"],
        "expired_on_arrival": expired,
        "doa_steps_burned": doa_steps_burned,
        "gates": gates,
        "backend": jax.default_backend(),
    }
    line = json.dumps(result)
    if out:
        with open(out, "w") as f:
            f.write(line + "\n")
    return result


def bench_failover_smoke(out=None):
    """Mid-stream failover proof (PR13, docs/SERVING.md): durable
    decode sessions survive engine death.  Three legs on local
    fleets pinned to one checkpoint fingerprint:

      * KILL leg: 3 concurrent 1024-token streams over 2 engines;
        the engine holding the most live streams is killed once every
        client has tokens in hand.  Gates: zero client-visible stream
        failures, zero duplicate and zero missing sequence numbers
        across all clients (exactly-once), >= 1 spliced terminal, and
        every spliced stream BIT-IDENTICAL to an uninterrupted
        reference decode of the same prompt (greedy determinism);
      * RESUME-FAULT leg: same crash with `serve.resume@0:error`
        injected — the resume attempt is abandoned and the stream
        degrades to the pre-failover terminal error (never a hang,
        never a duplicate token);
      * WATCHDOG leg: the serving engine goes silent mid-stream
        (`set_stall`, the engine.stall shape: alive, probing ok,
        producing nothing) — the per-stream idle watchdog
        (`stream_idle_s`) fails the stream over and it still finishes
        bit-identical.
    `out` writes the JSON line to a file as well
    (scripts/failover_smoke.sh -> BENCH_pr13.json)."""
    import tempfile
    import threading

    import jax

    from singa_tpu.core.net import build_net
    from singa_tpu.models.transformer import transformer_lm
    from singa_tpu.serve import EngineFleet, RouterSpec, ServeSpec
    from singa_tpu.utils.checkpoint import CheckpointManager
    from singa_tpu.utils.faults import FaultSchedule, inject

    vocab, plen, max_new = 64, 4, 1024
    seq = 1040                       # net horizon >= plen + max_new
    cfg = transformer_lm(vocab_size=vocab, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=seq,
                         batchsize=2)
    net = build_net(cfg, "kTest",
                    {"data": {"input": (seq,), "target": (seq,)}})
    params = net.init_params(jax.random.PRNGKey(0))

    def make_fleet(size, stream_idle_s=0.0):
        ws = tempfile.mkdtemp(prefix="failover_smoke_")
        mgr = CheckpointManager(ws, log_fn=lambda s: None)
        mgr.save(1, params, {"t": np.zeros(())},
                 health={"verdict": "ok"})
        spec = ServeSpec(buckets=((2, seq),), max_new_tokens=max_new,
                         batch_window_s=0.002,
                         request_timeout_s=120.0, cb="on",
                         cb_slots=3, cb_block_len=64)
        rspec = RouterSpec(probe_period_s=0.1, quarantine_after=5,
                           request_timeout_s=120.0, hedge="off",
                           stream_idle_s=stream_idle_s)
        fleet = EngineFleet.local(net, spec, size, workspace=ws,
                                  params=params, router_spec=rspec,
                                  log_fn=lambda s: None)
        fleet.start()
        return fleet

    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, vocab, size=plen).tolist()
               for _ in range(3)]

    # -- reference: uninterrupted greedy decode per prompt ------------
    fleet = make_fleet(1)
    reference = []
    for p in prompts:
        done = None
        for ev in fleet.generate_stream(p, max_new=max_new,
                                        timeout=300.0):
            if ev.get("done"):
                done = ev
        reference.append(done["tokens"])
    fleet.stop()

    def run_streams(fleet, n, mnew, kill_after=None, chaos=None):
        """n concurrent streams of `mnew` tokens; once EVERY stream
        has >= kill_after tokens in hand, `chaos(victim)` hits the
        engine holding the most live streams.  Returns (per-client
        audits, victim)."""
        results = [None] * n
        counts = [0] * n
        lock = threading.Lock()
        hit = {"victim": None}

        def strike_when_ready():
            while True:
                with lock:
                    if all(c >= kill_after for c in counts):
                        break
                    if all(r is not None for r in results):
                        return       # finished before chaos armed
                time.sleep(0.002)
            by_eng = {}
            for s in fleet.router.sessions.snapshot()["sessions"]:
                by_eng[s["engine"]] = by_eng.get(s["engine"], 0) + 1
            if not by_eng:
                return
            victim = max(sorted(by_eng), key=by_eng.get)
            hit["victim"] = victim
            chaos(victim)

        def client(k):
            seen, toks, done, err = [], [], None, None
            try:
                for ev in fleet.generate_stream(prompts[k],
                                                max_new=mnew,
                                                timeout=300.0):
                    if ev.get("done"):
                        done = ev
                        continue
                    seen.append(int(ev["i"]))
                    toks.append(int(ev["token"]))
                    with lock:
                        counts[k] += 1
            except Exception as e:  # noqa: BLE001 — gated below
                err = f"{type(e).__name__}: {e}"
            with lock:
                results[k] = {"seen": seen, "toks": toks,
                              "done": done, "err": err}

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(n)]
        for t in threads:
            t.start()
        if chaos is not None:
            threading.Thread(target=strike_when_ready,
                             daemon=True).start()
        for t in threads:
            t.join(600.0)
        if any(r is None for r in results):
            raise RuntimeError("failover smoke: a client HUNG "
                               "(stream neither finished nor failed)")
        return results, hit["victim"]

    def audit(results, mnew):
        failures = sum(1 for a in results
                       if a["err"] or a["done"] is None)
        dup = sum(len(a["seen"]) - len(set(a["seen"]))
                  for a in results)
        missing = sum(len(set(range(mnew)) - set(a["seen"]))
                      for a in results)
        return failures, dup, missing

    # -- leg 1: kill the engine holding live 1024-token streams -------
    fleet = make_fleet(2)
    res, victim = run_streams(
        fleet, 3, max_new, kill_after=64,
        chaos=lambda v: fleet.router.handle_for(v).kill())
    kill_snap = fleet.router.sessions.stats.snapshot()
    fleet.stop()
    k_fail, k_dup, k_missing = audit(res, max_new)
    k_spliced = sum(1 for a in res
                    if (a["done"] or {}).get("spliced"))
    k_parity = sum(
        1 for a, ref in zip(res, reference)
        if a["toks"] != ref or (a["done"] or {}).get("tokens") != ref)

    # -- leg 2: injected serve.resume fault degrades, never hangs -----
    fleet = make_fleet(2)
    with inject(FaultSchedule.parse("serve.resume@0:error")):
        res_f, _ = run_streams(
            fleet, 1, 256, kill_after=32,
            chaos=lambda v: fleet.router.handle_for(v).kill())
    fault_snap = fleet.router.sessions.stats.snapshot()
    fleet.stop()
    f_terminal = int(res_f[0]["err"] is not None
                     and res_f[0]["done"] is None)
    _, f_dup, _ = audit(res_f, 256)

    # -- leg 3: silent stall -> idle watchdog -> resume ---------------
    fleet = make_fleet(2, stream_idle_s=0.5)
    res_w, _ = run_streams(
        fleet, 1, 256, kill_after=32,
        chaos=lambda v: fleet.router.handle_for(v)
        .engine.set_stall(10.0))
    watch_snap = fleet.router.sessions.stats.snapshot()
    fleet.stop()
    w_fail, w_dup, w_missing = audit(res_w, 256)
    w_parity = int(res_w[0]["toks"] != reference[0][:256])
    w_resumed = int(watch_snap["idle_timeouts"] >= 1
                    and watch_snap["resumed"] >= 1 and not w_fail)

    gates = {
        "failover_stream_failures": {
            "value": k_fail, "bound": 0, "op": "==",
            "pass": bool(k_fail == 0)},
        "failover_dup_tokens": {
            "value": k_dup, "bound": 0, "op": "==",
            "pass": bool(k_dup == 0)},
        "failover_missing_tokens": {
            "value": k_missing, "bound": 0, "op": "==",
            "pass": bool(k_missing == 0)},
        "failover_spliced_streams": {
            "value": k_spliced, "bound": 1, "op": ">=",
            "pass": bool(k_spliced >= 1)},
        "failover_parity_mismatch": {
            "value": k_parity, "bound": 0, "op": "==",
            "pass": bool(k_parity == 0)},
        "resume_fault_terminal": {
            "value": f_terminal, "bound": 1, "op": "==",
            "pass": bool(f_terminal == 1
                         and fault_snap["resume_faults"] >= 1)},
        "resume_fault_dup_tokens": {
            "value": f_dup, "bound": 0, "op": "==",
            "pass": bool(f_dup == 0)},
        "idle_watchdog_resumed": {
            "value": w_resumed, "bound": 1, "op": "==",
            "pass": bool(w_resumed == 1 and w_dup == 0
                         and w_missing == 0 and w_parity == 0)},
    }
    failures = [f"{k}: {g['value']} not {g['op']} {g['bound']}"
                for k, g in gates.items() if not g["pass"]]
    if failures:
        raise RuntimeError("failover smoke FAILED: "
                           + "; ".join(failures))

    result = {
        "metric": "failover_exactly_once_streams",
        "value": len(res),
        "unit": "streams",
        "stream_tokens": max_new,
        "victim": victim,
        "kill_leg": {"failures": k_fail, "dup": k_dup,
                     "missing": k_missing, "spliced": k_spliced,
                     "parity_mismatch": k_parity,
                     "sessions": kill_snap},
        "resume_fault_leg": {"terminal": f_terminal, "dup": f_dup,
                             "error": res_f[0]["err"],
                             "sessions": fault_snap},
        "watchdog_leg": {"failures": w_fail, "dup": w_dup,
                         "missing": w_missing,
                         "parity_mismatch": w_parity,
                         "sessions": watch_snap},
        "gates": gates,
        "backend": jax.default_backend(),
    }
    line = json.dumps(result)
    if out:
        with open(out, "w") as f:
            f.write(line + "\n")
    return result


def bench_transport_smoke(out=None):
    """ISSUE 20 acceptance (docs/SERVING.md "Wire protocol"): the
    zero-copy binary transport against the HTTP/JSON debug surface.
    Five legs on one warm engine (cb=on) plus a two-engine fleet:

      * A/B leg: interleaved closed-loop unary decodes over ONE
        persistent binary connection vs the keep-alive HTTP handle.
        Gates: binary p50 < HTTP p50, and the `singa_wire_*`
        serialization-time split shows the binary encode path
        spending LESS wall time than the JSON path spends per token
        (where the saved time comes from);
      * PARITY leg: the streamed token sequence over the binary
        transport is BIT-IDENTICAL to the HTTP ndjson stream and to
        the unary result (greedy determinism across transports);
      * SPLICE leg: a mixed fleet (one binary-capable engine, one
        HTTP-only) loses the binary engine mid-stream — the session
        machinery splices the remainder from the HTTP sibling with
        zero client-visible failures, zero duplicate and zero missing
        tokens, bit-identical to an uninterrupted reference;
      * FUZZ leg: garbage magic, truncations at every cut point,
        oversized length prefixes and random bytes against the live
        listener — every one is a counted `wire_malformed_total`
        close within the timeout, never a hang, and the listener
        keeps serving;
      * FAULT leg: `wire.frame` drop/corrupt/tear injected on the
        binary path — the negotiating handle absorbs each one by
        falling back to HTTP with zero client-visible failures.
    `out` writes the JSON line to a file as well
    (scripts/transport_smoke.sh -> BENCH_pr20.json)."""
    import socket as _socket
    import tempfile
    import threading

    import jax

    from singa_tpu.core.net import build_net
    from singa_tpu.models.transformer import transformer_lm
    from singa_tpu.serve import (BinaryEngineHandle, EngineFleet,
                                 HttpEngineHandle, InferenceEngine,
                                 InferenceServer,
                                 NegotiatingEngineHandle, RouterSpec,
                                 ServeSpec, wire)
    from singa_tpu.utils.checkpoint import CheckpointManager
    from singa_tpu.utils.faults import FaultSchedule, inject

    vocab, plen, seq = 64, 4, 64
    cfg = transformer_lm(vocab_size=vocab, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=seq,
                         batchsize=2)
    net = build_net(cfg, "kTest",
                    {"data": {"input": (seq,), "target": (seq,)}})
    params = net.init_params(jax.random.PRNGKey(0))
    spec = ServeSpec(buckets=((2, seq),), max_new_tokens=32,
                     batch_window_s=0.002, request_timeout_s=60.0,
                     cb="on", cb_slots=3, cb_block_len=8)

    def make_server(wire_on=True):
        eng = InferenceEngine(net, spec, params=params,
                              log_fn=lambda s: None)
        srv = InferenceServer(eng, port=0, wire_on=wire_on,
                              log_fn=lambda s: None)
        srv.start()
        return srv

    prompt = np.arange(1, 1 + plen, dtype=np.int32)
    srv = make_server(wire_on=True)
    host, port = srv.address
    hh = HttpEngineHandle("e0", f"http://{host}:{port}")
    bh = BinaryEngineHandle("e0", srv.wire_address)

    # -- A/B leg: interleaved closed-loop unary decodes ---------------
    n_ab = 40
    for _ in range(4):                   # warm both paths + compile
        hh.request("generate", prompt, timeout=30)
        bh.request("generate", prompt, timeout=30)
    lat = {"http": [], "binary": []}
    for _ in range(n_ab):
        for name, h in (("http", hh), ("binary", bh)):
            t0 = time.perf_counter()
            h.request("generate", prompt, timeout=30)
            lat[name].append(time.perf_counter() - t0)
    p50_http = float(np.median(lat["http"]) * 1e3)
    p50_bin = float(np.median(lat["binary"]) * 1e3)

    # serialization split: stream the SAME decode over each transport
    # and charge the per-token encode cost to its own accumulator
    def _delta(before, after, *keys):
        return sum(after[k] - before[k] for k in keys)

    s_tokens = 32
    pre = wire.STATS.snapshot()
    http_stream = [ev for ev in hh.request_stream(
        prompt, timeout=60, max_new=s_tokens)]
    mid = wire.STATS.snapshot()
    bin_stream = [ev for ev in bh.request_stream(
        prompt, timeout=60, max_new=s_tokens)]
    post = wire.STATS.snapshot()
    ser_http_s = _delta(pre, mid, "json_ser_seconds",
                        "ser_seconds")
    ser_bin_s = _delta(mid, post, "json_ser_seconds", "ser_seconds")
    flushes = _delta(pre, post, "token_flushes")

    # -- PARITY leg ---------------------------------------------------
    ref = hh.request("generate", prompt, timeout=30)["tokens"]
    h_toks = [ev["token"] for ev in http_stream if "done" not in ev]
    b_toks = [ev["token"] for ev in bin_stream if "done" not in ev]
    parity_mismatch = int(h_toks != ref) + int(b_toks != ref)

    # -- FUZZ leg -----------------------------------------------------
    whole = b"".join(bytes(p) for p in wire.frame_parts(
        wire.K_REQ, 7, wire.encode_qos_header(tenant="t"),
        [wire.encode_request(wire.OP_GENERATE, [1, 2, 3])]))
    rng = np.random.default_rng(11)
    cases = [b"XX" + b"\x00" * 14,
             wire._PREAMBLE.pack(wire.MAGIC, wire.VERSION + 1,
                                 wire.K_HELLO, 0, 0, 1, 0, 0),
             wire._PREAMBLE.pack(wire.MAGIC, wire.VERSION,
                                 wire.K_REQ, 0, 0, 1, 0,
                                 wire.MAX_PAYLOAD_LEN + 1)]
    cases += [whole[:cut] for cut in range(1, len(whole), 7)]
    cases += [rng.integers(0, 256, int(rng.integers(1, 48)))
              .astype(np.uint8).tobytes() for _ in range(25)]
    fuzz_pre = wire.STATS.snapshot()["malformed"]
    fuzz_hangs = 0
    for raw in cases:
        s = _socket.create_connection(srv.wire_address, timeout=5.0)
        try:
            s.sendall(raw)
            s.shutdown(_socket.SHUT_WR)  # half-close: no more bytes
            s.settimeout(5.0)
            while s.recv(4096):          # drain until peer closes
                pass
        except (TimeoutError, _socket.timeout):
            fuzz_hangs += 1
        except OSError:
            pass                         # reset counts as closed
        finally:
            s.close()
    fuzz_malformed = wire.STATS.snapshot()["malformed"] - fuzz_pre
    fuzz_survived = int(bh.probe().get("ok", False))
    hh.close()
    bh.close()

    # -- FAULT leg: wire.frame absorbed by HTTP fallback --------------
    fault_failures = 0
    fault_pre = wire.STATS.snapshot()["faulted_frames"]
    for kind in ("error", "corrupt", "torn"):
        nh = NegotiatingEngineHandle("e0", f"http://{host}:{port}",
                                     connect_timeout_s=3.0,
                                     log_fn=lambda s: None)
        try:
            nh.probe()
            with inject(FaultSchedule.parse(f"wire.frame@0:{kind}")):
                got = nh.request("generate", prompt, timeout=30)
            if len(got["tokens"]) != s_tokens:
                fault_failures += 1
        except Exception:  # noqa: BLE001 — gated below
            fault_failures += 1
        finally:
            nh.close()
    faulted = wire.STATS.snapshot()["faulted_frames"] - fault_pre
    srv.stop()

    # -- SPLICE leg: mixed fleet loses the binary engine mid-stream ---
    s_max = 32
    a = make_server(wire_on=True)
    b = make_server(wire_on=False)
    ws = tempfile.mkdtemp(prefix="transport_smoke_")
    CheckpointManager(ws, log_fn=lambda s: None).save(
        1, params, {"t": np.zeros(())}, health={"verdict": "ok"})
    rspec = RouterSpec(probe_period_s=0.1, hedge="off",
                       request_timeout_s=60.0, wal_group_tokens=4,
                       wal_group_ms=5.0, state_snapshot_s=0.1)
    fleet = EngineFleet.adopt(
        [f"http://{h}:{p}" for h, p in (a.address, b.address)],
        workspace=ws, router_spec=rspec, log_fn=lambda s: None)
    splice_failures, splice_dup, splice_missing = 1, 0, 0
    splice_parity = 1
    try:
        fleet.start()
        deadline = time.monotonic() + 10.0
        h0 = fleet.router.handle_for("engine-0")
        while time.monotonic() < deadline and \
                h0.transport != "binary":
            time.sleep(0.05)
        splice_transport = h0.transport
        sref = [ev["token"]
                for ev in fleet.generate_stream(prompt,
                                                max_new=s_max)
                if "token" in ev]
        seen, idx, killed, err = [], [], False, None
        try:
            for ev in fleet.generate_stream(prompt, max_new=s_max):
                if "token" not in ev:
                    continue
                seen.append(int(ev["token"]))
                idx.append(int(ev["i"]))
                if len(seen) == 4 and not killed:
                    killed = True
                    a.stop()             # the whole binary worker
        except Exception as e:  # noqa: BLE001 — gated below
            err = f"{type(e).__name__}: {e}"
        splice_failures = int(err is not None)
        splice_dup = len(idx) - len(set(idx))
        splice_missing = len(set(range(s_max)) - set(idx))
        splice_parity = int(seen != sref)
    finally:
        fleet.stop()
        b.stop()
        try:
            a.stop()
        except Exception:  # noqa: BLE001 — may already be down
            pass

    gates = {
        "transport_p50_improved": {
            "value": round(p50_bin, 3), "bound": round(p50_http, 3),
            "op": "<", "pass": bool(p50_bin < p50_http)},
        "transport_ser_time_reduced": {
            "value": round(ser_bin_s * 1e6, 1),
            "bound": round(ser_http_s * 1e6, 1), "op": "<",
            "pass": bool(ser_bin_s < ser_http_s)},
        "transport_stream_parity": {
            "value": parity_mismatch, "bound": 0, "op": "==",
            "pass": bool(parity_mismatch == 0)},
        "wire_splice_exactly_once": {
            "value": splice_failures + splice_dup + splice_missing
            + splice_parity, "bound": 0, "op": "==",
            "pass": bool(splice_failures == 0 and splice_dup == 0
                         and splice_missing == 0
                         and splice_parity == 0)},
        "wire_fuzz_no_hangs": {
            "value": fuzz_hangs, "bound": 0, "op": "==",
            "pass": bool(fuzz_hangs == 0
                         and fuzz_malformed >= len(cases) - 2
                         and fuzz_survived)},
        "wire_fault_absorbed": {
            "value": fault_failures, "bound": 0, "op": "==",
            "pass": bool(fault_failures == 0 and faulted >= 3)},
    }
    failures = [f"{k}: {g['value']} not {g['op']} {g['bound']}"
                for k, g in gates.items() if not g["pass"]]
    if failures:
        raise RuntimeError("transport smoke FAILED: "
                           + "; ".join(failures))

    result = {
        "metric": "transport_p50_ms",
        "value": round(p50_bin, 3),
        "unit": "ms",
        "http_p50_ms": round(p50_http, 3),
        "requests_per_leg": n_ab,
        "ab_leg": {
            "binary_p50_ms": round(p50_bin, 3),
            "http_p50_ms": round(p50_http, 3),
            "binary_ser_us": round(ser_bin_s * 1e6, 1),
            "http_ser_us": round(ser_http_s * 1e6, 1),
            "stream_tokens": s_tokens,
            "token_flushes": flushes},
        "parity_leg": {"mismatch": parity_mismatch,
                       "tokens": len(ref)},
        "splice_leg": {"failures": splice_failures,
                       "dup": splice_dup,
                       "missing": splice_missing,
                       "parity_mismatch": splice_parity,
                       "transport_before_kill": splice_transport},
        "fuzz_leg": {"cases": len(cases), "hangs": fuzz_hangs,
                     "malformed_counted": fuzz_malformed,
                     "listener_survived": fuzz_survived},
        "fault_leg": {"client_failures": fault_failures,
                      "faulted_frames": faulted},
        "wire_stats": wire.STATS.snapshot(),
        "gates": gates,
        "backend": jax.default_backend(),
    }
    line = json.dumps(result)
    if out:
        with open(out, "w") as f:
            f.write(line + "\n")
    return result


def bench_router_smoke(out=None):
    """ISSUE 19 acceptance (docs/SERVING.md "Control-plane
    durability"): the crash-safe control plane.  Five legs:

      * RESTART leg (real SIGKILL, over HTTP): a one-worker fleet
        router subprocess serves 3 concurrent 256-token streams;
        once every client holds >= 32 tokens the router is SIGKILLed
        — no atexit, no close records: the journal tail is whatever
        the last group commit made durable — then restarted on the
        same port over the same workspace.  Every client reconnects
        with its session id + resume_from.  Gates: zero
        client-visible failures, zero duplicate and zero missing
        indices across the reconnect (exactly-once), every spliced
        stream BIT-IDENTICAL to an uninterrupted reference, >= 3
        streams recovered from the WAL;
      * HANDOFF leg (over HTTP): primary + warm `standby=True`
        router share one workspace; POST /admin/handoff mid-stream
        lame-ducks the primary (the in-flight stream finishes; a
        fresh admission gets 409 + the successor URL), POST
        /admin/promote fences the old epoch and the promoted standby
        serves the same prompt bit-identically;
      * STATE leg: a quarantine bench and a per-(tenant, class) shed
        streak survive an in-process router rebuild over the same
        workspace — the control-state snapshot closes the
        restart-launders-strikes hole;
      * OVERHEAD leg: interleaved A/B of wal=on vs wal=off fleets,
        gate: median stream tok/s ratio >= 0.97 (the WAL must cost
        <= 3% of streaming throughput);
      * WAL-FAULT leg: `router.wal@0:error` — the faulted group
        commit degrades to counted lost durability (`wal_lost`); the
        stream completes, a disk error never blocks a token.
    `out` writes the JSON line (scripts/router_smoke.sh ->
    BENCH_pr19.json)."""
    import signal
    import socket
    import subprocess
    import sys
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    import jax

    from singa_tpu.config import load_model_config
    from singa_tpu.core.net import build_net
    from singa_tpu.core.trainer import Trainer
    from singa_tpu.data import discover_input_shapes
    from singa_tpu.models.transformer import transformer_lm
    from singa_tpu.serve import EngineFleet, FleetServer, RouterSpec, \
        ServeSpec
    from singa_tpu.utils.checkpoint import CheckpointManager
    from singa_tpu.utils.faults import FaultSchedule, inject

    if jax.default_backend() != "cpu":
        # this process holds the backend and then starts a JAX child:
        # on a chip the child would hang on it (one process per chip)
        raise SystemExit(
            "--router-smoke is a CPU functional gate (a JAX child runs "
            "beside this JAX parent): run it with JAX_PLATFORMS=cpu "
            f"(got {jax.default_backend()!r})")

    vocab, plen, max_new = 64, 4, 256
    seq = 272                        # net horizon >= plen + max_new
    repo = os.path.dirname(os.path.abspath(__file__))

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def http_json(url, body=None, timeout=60.0):
        req = urllib.request.Request(
            url, data=(json.dumps(body).encode()
                       if body is not None else None),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())

    def http_stream(url, body, timeout=120.0):
        req = urllib.request.Request(
            url + "/generate", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        return urllib.request.urlopen(req, timeout=timeout)

    # ---- leg 1: real SIGKILL restart over HTTP ----------------------
    ws = tempfile.mkdtemp(prefix="router_smoke_")
    with open(os.path.join(
            repo, "examples/transformer/lm_tiny.conf")) as f:
        conf_txt = f.read().replace("seq_len: 16", f"seq_len: {seq}")
    conf = os.path.join(ws, "lm_smoke.conf")
    with open(conf, "w") as f:
        f.write(conf_txt)
    model = load_model_config(conf)
    shapes = discover_input_shapes(model, force_synthetic=True)
    trainer = Trainer(model, shapes, log_fn=lambda s: None)
    conf_net = trainer.test_net or trainer.train_net
    conf_params = conf_net.init_params(jax.random.PRNGKey(0))
    CheckpointManager(ws, log_fn=lambda s: None).save(
        1, conf_params, {"t": np.zeros(())},
        health={"verdict": "ok"})

    port = free_port()
    url = f"http://127.0.0.1:{port}"
    cmd = [sys.executable, "-m", "singa_tpu.main", "serve",
           "-model_conf", conf, "--workspace", ws,
           "--fleet", "1", "--port", str(port),
           "--serve_spec",
           f"buckets=4x{seq},max_new_tokens={max_new},"
           "batch_window_s=0.002,cb=on,cb_slots=4,cb_block_len=16",
           "--fleet_spec",
           "probe_period_s=0.2,hedge=off,request_timeout_s=120,"
           "wal_group_tokens=8,wal_group_ms=5,state_snapshot_s=0.2"]

    def launch():
        # inherits this process's environment unchanged: the parent is
        # on the CPU backend (checked on entry), so the child is too
        return subprocess.Popen(cmd, cwd=repo,
                                stdout=subprocess.DEVNULL,
                                stderr=subprocess.DEVNULL)

    def wait_healthy(proc, secs=600.0):
        deadline = time.time() + secs
        while True:
            if proc.poll() is not None:
                raise RuntimeError("router subprocess exited before "
                                   "serving /healthz")
            try:
                st, _ = http_json(url + "/healthz", timeout=2.0)
                if st == 200:
                    return
            except Exception:
                pass
            if time.time() > deadline:
                proc.kill()
                raise RuntimeError("router subprocess never became "
                                   "healthy")
            time.sleep(0.25)

    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, vocab, size=plen).tolist()
               for _ in range(3)]
    proc = launch()
    try:
        wait_healthy(proc)
        ref_http = []
        for p in prompts:
            toks = []
            with http_stream(url, {"tokens": p, "stream": True,
                                   "max_new": max_new}) as r:
                for line in r:
                    ev = json.loads(line)
                    if "token" in ev:
                        toks.append(int(ev["token"]))
            ref_http.append(toks)

        counts = [0] * 3
        results = [None] * 3
        lock = threading.Lock()

        def client(k):
            sid, seen, toks, err = None, [], [], None
            try:
                r = http_stream(url, {"tokens": prompts[k],
                                      "stream": True,
                                      "max_new": max_new})
                for line in r:
                    ev = json.loads(line)
                    if sid is None and "sid" in ev:
                        sid = ev["sid"]
                    if "token" in ev:
                        seen.append(int(ev["i"]))
                        toks.append(int(ev["token"]))
                        with lock:
                            counts[k] += 1
            except Exception as e:  # noqa: BLE001 — the SIGKILL cuts
                err = f"{type(e).__name__}: {e}"   # the connection
            with lock:
                results[k] = {"sid": sid, "seen": seen, "toks": toks,
                              "err": err}

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(3)]
        for t in threads:
            t.start()
        while True:
            with lock:
                if all(c >= 32 for c in counts):
                    break
            time.sleep(0.005)
        time.sleep(0.2)              # let a group commit reach disk
        proc.send_signal(signal.SIGKILL)
        proc.wait(30)
        for t in threads:
            t.join(60.0)

        proc = launch()
        wait_healthy(proc)
        r_fail = r_dup = r_missing = r_parity = 0
        for k, res in enumerate(results):
            if res is None or res["sid"] is None:
                r_fail += 1
                continue
            seen, toks = list(res["seen"]), list(res["toks"])
            try:
                with http_stream(url, {"stream": True,
                                       "session": res["sid"],
                                       "resume_from": len(seen)}) as r:
                    done = None
                    for line in r:
                        ev = json.loads(line)
                        if ev.get("done"):
                            done = ev
                        if "token" in ev:
                            seen.append(int(ev["i"]))
                            toks.append(int(ev["token"]))
            except Exception:
                r_fail += 1
                continue
            if done is None or done.get("error"):
                r_fail += 1
            r_dup += len(seen) - len(set(seen))
            r_missing += len(set(range(max_new)) - set(seen))
            if toks != ref_http[k] or \
                    (done or {}).get("tokens") != ref_http[k]:
                r_parity += 1
        _, snap = http_json(url + "/stats", timeout=10.0)
        r_recovered = int((snap.get("wal") or {})
                          .get("recovered_streams", 0))
        restart_epoch = int(snap.get("epoch", 0))
    finally:
        proc.kill()
        proc.wait(30)

    # ---- shared in-process fixture for legs 2-5 ---------------------
    cfg = transformer_lm(vocab_size=vocab, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=seq,
                         batchsize=2)
    net = build_net(cfg, "kTest",
                    {"data": {"input": (seq,), "target": (seq,)}})
    params = net.init_params(jax.random.PRNGKey(0))

    def make_fleet(size, ws=None, standby=False, **rkw):
        if ws is None:
            ws = tempfile.mkdtemp(prefix="router_smoke_")
            CheckpointManager(ws, log_fn=lambda s: None).save(
                1, params, {"t": np.zeros(())},
                health={"verdict": "ok"})
        spec = ServeSpec(buckets=((2, seq),), max_new_tokens=max_new,
                         batch_window_s=0.002,
                         request_timeout_s=120.0, cb="on",
                         cb_slots=3, cb_block_len=16)
        rkw.setdefault("probe_period_s", 0.1)
        rkw.setdefault("hedge", "off")
        rkw.setdefault("request_timeout_s", 120.0)
        fleet = EngineFleet.local(net, spec, size, workspace=ws,
                                  params=params,
                                  router_spec=RouterSpec(**rkw),
                                  standby=standby,
                                  log_fn=lambda s: None)
        fleet.start()
        return fleet, ws

    def run_stream(front_url, prompt, mnew):
        t0 = time.perf_counter()
        toks, done, err = [], None, None
        try:
            with http_stream(front_url, {"tokens": prompt,
                                         "stream": True,
                                         "max_new": mnew}) as r:
                for line in r:
                    ev = json.loads(line)
                    if ev.get("done"):
                        done = ev
                    if "token" in ev:
                        toks.append(int(ev["token"]))
        except Exception as e:  # noqa: BLE001 — gated below
            err = f"{type(e).__name__}: {e}"
        return {"toks": toks, "done": done, "err": err,
                "dt": time.perf_counter() - t0}

    # ---- leg 2: zero-downtime handoff over HTTP ---------------------
    primary, ws2 = make_fleet(1)
    standby, _ = make_fleet(1, ws=ws2, standby=True)
    p1, p2 = free_port(), free_port()
    url1, url2 = (f"http://127.0.0.1:{p1}", f"http://127.0.0.1:{p2}")
    front1 = FleetServer(primary, port=p1, log_fn=lambda s: None)
    front2 = FleetServer(standby, port=p2, log_fn=lambda s: None)
    front1.start()
    front2.start()
    h_fail = h_409 = h_parity = 0
    try:
        ref = run_stream(url1, prompts[0], max_new)
        if ref["err"] or ref["done"] is None:
            raise RuntimeError(f"handoff reference failed: "
                               f"{ref['err']}")
        inflight = {}

        def victim():
            inflight["res"] = run_stream(url1, prompts[0], max_new)

        vt = threading.Thread(target=victim)
        vt.start()
        time.sleep(0.3)              # mid-stream
        st, got = http_json(url1 + "/admin/handoff",
                            {"successor": url2, "retry_after": 0.2})
        if st != 200 or not got.get("lame_duck"):
            h_fail += 1
        try:
            http_json(url1 + "/generate", {"tokens": prompts[0]})
            h_fail += 1              # should have been refused
        except urllib.error.HTTPError as e:
            body = json.loads(e.read())
            if e.code == 409 and body.get("successor") == url2:
                h_409 = 1
        st, got = http_json(url2 + "/admin/promote", {})
        if st != 200 or int(got.get("epoch", 0)) < 2:
            h_fail += 1
        vt.join(300.0)
        res = inflight.get("res")
        if res is None or res["err"] or res["done"] is None:
            h_fail += 1              # in-flight must finish on the
        elif res["toks"] != ref["toks"]:   # lame duck
            h_parity += 1
        after = run_stream(url2, prompts[0], max_new)
        if after["err"] or after["done"] is None:
            h_fail += 1
        elif after["toks"] != ref["toks"]:
            h_parity += 1
        handoff_epoch = int(standby.epoch)
    finally:
        front1.stop()
        front2.stop()
        standby.stop()
        primary.stop()

    # ---- leg 3: control state survives a rebuild --------------------
    f1, ws3 = make_fleet(2, quarantine_after=2, probe_period_s=0.05,
                         readmit_base_s=30.0, state_snapshot_s=0.05)
    victim_name = f1.router.names()[-1]
    f1.router.handle_for(victim_name).kill()
    deadline = time.time() + 30.0
    while time.time() < deadline:
        if any(m["name"] == victim_name and m["quarantined"]
               for m in f1.router.members()):
            break
        time.sleep(0.02)
    f1.router._shed_backoffs.shed_delay("interactive", tenant="acme")
    f1.router._shed_backoffs.shed_delay("interactive", tenant="acme")
    time.sleep(0.3)                  # >= several snapshot periods
    f1.stop()
    f2, _ = make_fleet(2, ws=ws3, quarantine_after=2,
                       probe_period_s=0.05, readmit_base_s=30.0,
                       state_snapshot_s=0.05)
    m2 = {m["name"]: m for m in f2.router.members()}
    s_quarantine = int(m2[victim_name]["quarantined"])
    s_streak = int(f2.router._shed_backoffs.export_streaks()
                   .get("acme\tinteractive", 0) == 2)
    f2.stop()

    # ---- leg 4: WAL overhead A/B ------------------------------------
    fleet_on, _ = make_fleet(1)
    fleet_off, _ = make_fleet(1, wal="off")
    po, pf = free_port(), free_port()
    fr_on = FleetServer(fleet_on, port=po, log_fn=lambda s: None)
    fr_off = FleetServer(fleet_off, port=pf, log_fn=lambda s: None)
    fr_on.start()
    fr_off.start()
    try:
        uo, uf = f"http://127.0.0.1:{po}", f"http://127.0.0.1:{pf}"
        run_stream(uo, prompts[0], max_new)      # warm both paths
        run_stream(uf, prompts[0], max_new)
        rates = {"on": [], "off": []}
        for _ in range(5):                       # interleaved A/B
            for key, u in (("on", uo), ("off", uf)):
                r = run_stream(u, prompts[0], max_new)
                if r["err"]:
                    raise RuntimeError(f"overhead leg stream failed "
                                       f"(wal={key}): {r['err']}")
                rates[key].append(max_new / r["dt"])
        p50_on = float(np.median(rates["on"]))
        p50_off = float(np.median(rates["off"]))
        overhead_ratio = p50_on / p50_off
    finally:
        fr_on.stop()
        fr_off.stop()
        fleet_on.stop()
        fleet_off.stop()

    # ---- leg 5: WAL write fault degrades to counted loss ------------
    with inject(FaultSchedule.parse("router.wal@0:error")):
        ff, _ = make_fleet(1)
        done = None
        for ev in ff.generate_stream(prompts[0], max_new=64,
                                     timeout=120.0):
            if ev.get("done"):
                done = ev
        ff.wal.flush()
        lost = int(ff.wal_stats.snapshot()["wal_lost"])
        fault_ok = int(done is not None and not done.get("error")
                       and len(done.get("tokens") or []) == 64)
        ff.stop()

    gates = {
        "restart_stream_failures": {
            "value": r_fail, "bound": 0, "op": "==",
            "pass": bool(r_fail == 0)},
        "restart_dup_tokens": {
            "value": r_dup, "bound": 0, "op": "==",
            "pass": bool(r_dup == 0)},
        "restart_missing_tokens": {
            "value": r_missing, "bound": 0, "op": "==",
            "pass": bool(r_missing == 0)},
        "restart_parity_mismatch": {
            "value": r_parity, "bound": 0, "op": "==",
            "pass": bool(r_parity == 0)},
        "restart_recovered_streams": {
            "value": r_recovered, "bound": 3, "op": ">=",
            "pass": bool(r_recovered >= 3)},
        "handoff_client_failures": {
            "value": h_fail, "bound": 0, "op": "==",
            "pass": bool(h_fail == 0)},
        "handoff_refusal_points_successor": {
            "value": h_409, "bound": 1, "op": "==",
            "pass": bool(h_409 == 1)},
        "handoff_parity_mismatch": {
            "value": h_parity, "bound": 0, "op": "==",
            "pass": bool(h_parity == 0)},
        "state_quarantine_survived": {
            "value": s_quarantine, "bound": 1, "op": "==",
            "pass": bool(s_quarantine == 1)},
        "state_shed_streak_survived": {
            "value": s_streak, "bound": 1, "op": "==",
            "pass": bool(s_streak == 1)},
        "wal_overhead_ratio": {
            "value": round(overhead_ratio, 4), "bound": 0.97,
            "op": ">=", "pass": bool(overhead_ratio >= 0.97)},
        "wal_fault_counted_loss": {
            "value": lost, "bound": 1, "op": ">=",
            "pass": bool(lost >= 1 and fault_ok == 1)},
    }
    failures = [f"{k}: {g['value']} not {g['op']} {g['bound']}"
                for k, g in gates.items() if not g["pass"]]
    if failures:
        raise RuntimeError("router smoke FAILED: "
                           + "; ".join(failures))

    result = {
        "metric": "router_crash_safe_streams",
        "value": r_recovered,
        "unit": "streams",
        "stream_tokens": max_new,
        "restart_leg": {"failures": r_fail, "dup": r_dup,
                        "missing": r_missing,
                        "parity_mismatch": r_parity,
                        "recovered": r_recovered,
                        "epoch_after_restart": restart_epoch},
        "handoff_leg": {"failures": h_fail,
                        "refusal_points_successor": h_409,
                        "parity_mismatch": h_parity,
                        "promoted_epoch": handoff_epoch},
        "state_leg": {"quarantine_survived": s_quarantine,
                      "shed_streak_survived": s_streak},
        "overhead_leg": {"p50_tok_s_wal_on": round(p50_on, 1),
                         "p50_tok_s_wal_off": round(p50_off, 1),
                         "ratio": round(overhead_ratio, 4)},
        "wal_fault_leg": {"wal_lost": lost, "stream_ok": fault_ok},
        "gates": gates,
        "backend": jax.default_backend(),
    }
    line = json.dumps(result)
    if out:
        with open(out, "w") as f:
            f.write(line + "\n")
    return result


def bench_trace_smoke(out=None):
    """ISSUE 14 acceptance (docs/OBSERVABILITY.md): fleet-wide
    distributed tracing.  Three legs:

      * TRACE leg: a 3-engine local fleet with hedging forced
        (hedge_min_s = hedge_max_s = 1ms) serves one hedged unary
        request and one stream whose engine is KILLED mid-stream
        (failover resume).  The merged trace must show, PER request,
        exactly ONE trace id across every leg (primary + hedge +
        resume), spans from >= 2 engines on the failed-over stream,
        zero orphan spans, and per-stage attribution
        (admit/dispatch/first_token/decode) summing within 10% of the
        end-to-end latency;
      * FLIGHTREC leg: a fresh fleet with NO trace export — only the
        flight recorder armed — suffers the same mid-stream kill; the
        `stream.resume` trigger must dump the last events to
        `flightrec-failover-*.json` (post-mortem without tracing
        pre-enabled);
      * OVERHEAD leg: tracing-on must stay under the PR-6 < 3% wall
        gate (`bench_obs_overhead`, 2 interleaved reps).
    `out` writes the JSON line to a file as well
    (scripts/obs_smoke.sh -> BENCH_pr14.json)."""
    import glob
    import tempfile
    import threading

    import jax

    from singa_tpu import obs
    from singa_tpu.core.net import build_net
    from singa_tpu.models.transformer import transformer_lm
    from singa_tpu.obs import collect
    from singa_tpu.serve import EngineFleet, RouterSpec, ServeSpec
    from singa_tpu.utils.checkpoint import CheckpointManager

    vocab, plen, max_new = 64, 4, 256
    seq = 272                        # net horizon >= plen + max_new
    cfg = transformer_lm(vocab_size=vocab, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=seq,
                         batchsize=2)
    net = build_net(cfg, "kTest",
                    {"data": {"input": (seq,), "target": (seq,)}})
    params = net.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(14)
    prompt = rng.integers(1, vocab, size=plen).tolist()

    def make_fleet(size):
        ws = tempfile.mkdtemp(prefix="trace_smoke_")
        mgr = CheckpointManager(ws, log_fn=lambda s: None)
        mgr.save(1, params, {"t": np.zeros(())},
                 health={"verdict": "ok"})
        spec = ServeSpec(buckets=((2, seq),), max_new_tokens=max_new,
                         batch_window_s=0.002,
                         request_timeout_s=120.0, cb="on",
                         cb_slots=3, cb_block_len=64)
        rspec = RouterSpec(probe_period_s=0.1, quarantine_after=5,
                           request_timeout_s=120.0, hedge="on",
                           hedge_min_s=0.001, hedge_max_s=0.001)
        fleet = EngineFleet.local(net, spec, size, workspace=ws,
                                  params=params, router_spec=rspec,
                                  log_fn=lambda s: None)
        fleet.start()
        return fleet

    def killed_stream(fleet, kill_after=32):
        """One stream; once `kill_after` tokens are in hand, kill the
        engine holding the session — forces a mid-stream failover."""
        count = {"n": 0}
        lock = threading.Lock()

        def strike():
            while True:
                with lock:
                    if count["n"] >= kill_after:
                        break
                    if count["n"] < 0:
                        return
                time.sleep(0.002)
            sess = fleet.router.sessions.snapshot()["sessions"]
            if sess:
                fleet.router.handle_for(sess[0]["engine"]).kill()

        threading.Thread(target=strike, daemon=True).start()
        done = None
        for ev in fleet.generate_stream(prompt, max_new=max_new,
                                        timeout=300.0):
            if ev.get("done"):
                done = ev
            else:
                with lock:
                    count["n"] += 1
        with lock:
            count["n"] = -1
        return done

    # -- leg 1: hedged unary + killed stream, trace everything --------
    tmp = tempfile.mkdtemp(prefix="trace_smoke_obs_")
    with obs.session(obs.ObsSpec(
            trace=os.path.join(tmp, "trace.json"),
            process="router", trace_ring=65536)):
        fleet = make_fleet(3)
        try:
            fleet.generate(prompt, timeout=300.0)
            done = killed_stream(fleet)
            reqs = fleet.router.requests.snapshot()["recent"]
            merged = collect.merge([obs.trace_dump()])
        finally:
            fleet.stop()
    if done is None or not (done.get("spliced") or done.get("done")):
        raise RuntimeError("trace smoke: killed stream never finished")

    # unary rows finish "ok"; stream rows finish "done" or (after a
    # failover) "spliced" — anything else is a failed request
    rows = {r["mode"]: r for r in reqs
            if r.get("outcome") in ("ok", "done", "spliced")}
    u_row, s_row = rows.get("generate"), rows.get("stream")
    if u_row is None or s_row is None:
        raise RuntimeError(f"trace smoke: missing request rows "
                           f"({sorted(rows)})")

    def span_args(pred):
        return [e["args"] for e in merged["traceEvents"]
                if e.get("ph") == "X" and pred(e)]

    # one trace id per request: every span tagged with a request's
    # corr must carry that request's trace id and no other
    ids_per_req = max(
        len({a.get("trace") for a in span_args(
            lambda e: e["args"].get("corr") == r["corr"])})
        for r in (u_row, s_row))
    s_spans = collect.spans_of(merged, s_row["trace"])
    s_names = {e["name"] for e in s_spans}
    resume_in_trace = int("router.resume" in s_names
                          and "stream.decode" in s_names
                          and "router.stream" in s_names)
    s_engines = {e["args"].get("engine") for e in s_spans
                 if e["args"].get("engine")}
    h_legs = sum(1 for e in collect.spans_of(merged, u_row["trace"])
                 if e["name"] == "router.attempt")
    n_orphans = len(collect.orphans(merged))
    stage_err = max(
        abs(1.0 - sum(r["stages_ms"].values())
            / max(r["latency_ms"], 1e-9))
        for r in (u_row, s_row))
    timeline = collect.critical_path(merged, s_row["trace"])

    # -- leg 2: flight recorder WITHOUT tracing pre-enabled -----------
    fr_dir = tempfile.mkdtemp(prefix="trace_smoke_fr_")
    with obs.session(obs.ObsSpec(flightrec=fr_dir)):
        fleet = make_fleet(2)
        try:
            killed_stream(fleet)
        finally:
            fleet.stop()
        dumps = sorted(glob.glob(
            os.path.join(fr_dir, "flightrec-failover-*.json")))
    fr_replayed = 0
    if dumps:
        with open(dumps[-1]) as f:
            fr_replayed = int("stream.resume" in f.read())

    # -- leg 3: tracing-on overhead under the PR-6 gate ---------------
    over = bench_obs_overhead(reps=2)

    gates = {
        "trace_ids_per_request": {
            "value": ids_per_req, "bound": 1, "op": "==",
            "pass": bool(ids_per_req == 1)},
        "trace_resume_in_trace": {
            "value": resume_in_trace, "bound": 1, "op": "==",
            "pass": bool(resume_in_trace == 1)},
        "trace_hedge_legs": {
            "value": h_legs, "bound": 2, "op": ">=",
            "pass": bool(h_legs >= 2)},
        "trace_engines_spanned": {
            "value": len(s_engines), "bound": 2, "op": ">=",
            "pass": bool(len(s_engines) >= 2)},
        "trace_orphan_spans": {
            "value": n_orphans, "bound": 0, "op": "==",
            "pass": bool(n_orphans == 0)},
        "stage_attribution_err": {
            "value": round(stage_err, 4), "bound": 0.10, "op": "<",
            "pass": bool(stage_err < 0.10)},
        "flightrec_replayed": {
            "value": fr_replayed, "bound": 1, "op": "==",
            "pass": bool(fr_replayed == 1)},
        "trace_overhead": {
            "value": over["value"], "bound": 0.03, "op": "<",
            "pass": bool(over["value"] < 0.03)},
    }
    failures = [f"{k}: {g['value']} not {g['op']} {g['bound']}"
                for k, g in gates.items() if not g["pass"]]
    if failures:
        raise RuntimeError("trace smoke FAILED: "
                           + "; ".join(failures))

    result = {
        "metric": "trace_smoke_merged_trace",
        "value": ids_per_req,
        "unit": "trace_ids_per_request",
        "stream": {"trace": s_row["trace"],
                   "latency_ms": s_row["latency_ms"],
                   "stages_ms": s_row["stages_ms"],
                   "resumes": s_row.get("resumes"),
                   "engines": sorted(s_engines),
                   "spans": len(s_spans)},
        "hedged_unary": {"trace": u_row["trace"],
                         "latency_ms": u_row["latency_ms"],
                         "stages_ms": u_row["stages_ms"],
                         "hedged": u_row.get("hedged"),
                         "attempt_legs": h_legs},
        "critical_path_head": timeline[:5],
        "flightrec_dumps": len(dumps),
        "obs_overhead": over["value"],
        "gates": gates,
        "backend": jax.default_backend(),
    }
    line = json.dumps(result)
    if out:
        with open(out, "w") as f:
            f.write(line + "\n")
    return result


def bench_tenant_smoke(out=None):
    """ISSUE 18 acceptance: two tenants share ONE engine (no
    autoscaler — isolation must come from quotas, not from capacity
    chasing the spike) and the run FAILS (raises) unless:
      * tenant A's flash crowd (open-loop, >= 5x B's offered rate)
        leaves tenant B untouched: B's flash-phase p95 stays within
        1.2x its quiet-phase p95 and B completes 100% of its offered
        requests with zero sheds — A's overload is A's problem;
      * A's overflow is shed honestly (Overloaded) and consecutive
        sheds carry per-tenant ESCALATING Retry-After — the backpres-
        sure signal a well-behaved client needs to back off;
      * the per-tenant retry-budget floor holds: with A's budget and
        the shared bucket drained dry, B can still spend from its
        guaranteed floor while A cannot — one tenant's retry storm
        cannot starve another's hedges;
      * zero non-shed failures and zero harness drops.
    Records per-phase per-tenant offered/completed/shed/p95, the
    observed Retry-After ladder, and the budget-floor outcome; `out`
    writes the JSON line to a file as well
    (scripts/tenant_smoke.sh -> BENCH_pr18.json)."""
    import tempfile
    import threading

    import jax

    from singa_tpu.core.net import build_net
    from singa_tpu.models.transformer import transformer_lm
    from singa_tpu.serve import (EngineFleet, Overloaded, RouterSpec,
                                 ServeSpec, TenantRegistry)
    from singa_tpu.serve.traffic import TrafficGen, steady
    from singa_tpu.utils.checkpoint import CheckpointManager

    vocab, seq = 64, 16
    cfg = transformer_lm(vocab_size=vocab, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=seq,
                         batchsize=2)
    net = build_net(cfg, "kTest",
                    {"data": {"input": (seq,), "target": (seq,)}})
    params = net.init_params(jax.random.PRNGKey(0))

    ws = tempfile.mkdtemp(prefix="tenant_smoke_")
    mgr = CheckpointManager(ws, log_fn=lambda s: None)
    mgr.save(1, params, {"t": np.zeros(())}, health={"verdict": "ok"})

    # hard partition: each tenant gets 1 of the 2 cb slots and its
    # own queue carve-out, so A flooding ITS queue cannot touch B's
    spec = ServeSpec(buckets=((2, 16),), max_new_tokens=24,
                     batch_window_s=0.002, request_timeout_s=30.0,
                     queue_capacity=8, cb="on", cb_slots=2,
                     cb_block_len=8)
    reg = TenantRegistry.parse(
        "a,queue_frac=0.25,slot_frac=0.5,kv_frac=0.5,budget_floor=4,"
        "brownout_batch_frac=0.125;"
        "b,queue_frac=0.5,slot_frac=0.5,kv_frac=0.5,budget_floor=4")
    fleet = EngineFleet.local(
        net, spec, 1, workspace=ws, params=params,
        router_spec=RouterSpec(probe_period_s=0.05,
                               quarantine_after=3),
        tenancy=reg, log_fn=lambda s: None)
    fleet.start()

    gen = TrafficGen(
        lambda toks, **kw: fleet.generate(toks.tolist(), **kw),
        vocab=vocab, seed=0, log_fn=lambda s: None)
    # quiet: both tenants well inside one engine's capacity; flash:
    # A jumps to ~10x B's rate (>= 5x its own quiet share) while B
    # keeps its quiet cadence
    phases = [steady("quiet", 8.0, 4.0, prompt_lens=(4, 8),
                     tenants=("a", "b"),
                     tenant_weights=(1.0, 1.0)),
              steady("flash", 10.0, 44.0, prompt_lens=(4, 8),
                     tenants=("a", "b"),
                     tenant_weights=(10.0, 1.0))]
    rep = gen.run(phases, drain_timeout_s=30.0)

    # -- Retry-After escalation sub-test ------------------------------
    # A's spec tightened its own batch brownout to 0.125 (shed batch
    # whenever the queue is non-empty), so while interactive fillers
    # keep the queue occupied, A's batch probes shed CONSECUTIVELY —
    # their (tenant, class) streak never resets, and each shed's
    # Retry-After must climb the per-(tenant, class) ladder.
    stop_fill = threading.Event()

    def _fill():
        while not stop_fill.is_set():
            try:
                fleet.generate(list(range(1, 9)), tenant="a",
                               timeout=10.0)
            except Exception:  # noqa: BLE001 — filler sheds are the
                pass           # pressure, not the measurement

    fillers = [threading.Thread(target=_fill, daemon=True)
               for _ in range(8)]
    for t in fillers:
        t.start()
    time.sleep(0.3)                      # let the queue fill
    retry_afters = []
    for _ in range(10):
        try:
            fleet.generate([1, 2, 3, 4], tenant="a",
                           priority="batch", timeout=10.0)
        except Overloaded as e:
            retry_afters.append(float(e.retry_after))
        except Exception:  # noqa: BLE001 — budget stops etc. don't
            pass           # carry a Retry-After; only sheds gate
        time.sleep(0.02)
    stop_fill.set()
    for t in fillers:
        t.join(15.0)
    esc_ratio = (max(retry_afters) / max(min(retry_afters), 1e-9)
                 if len(retry_afters) >= 5 else 0.0)

    # -- budget-floor sub-test ----------------------------------------
    # drain A's budget AND the shared bucket through A, then B must
    # still be able to spend from its guaranteed floor while A is dry
    ba = fleet.tenancy.budget("a")
    bb = fleet.tenancy.budget("b")
    drained = 0
    while ba.spend() and drained < 10_000:
        drained += 1
    b_admitted = bool(bb.spend())
    a_exhausted = not ba.spend()
    fleet.stop()

    tot = rep["totals"]
    quiet = next(r for r in rep["phases"] if r["name"] == "quiet")
    flash = next(r for r in rep["phases"] if r["name"] == "flash")
    qb = quiet["by_tenant"].get("b", {})
    fb = flash["by_tenant"].get("b", {})
    fa = flash["by_tenant"].get("a", {})
    tb = tot["by_tenant"].get("b", {})
    b_p95_ratio = (fb["p95_ms"] / qb["p95_ms"]
                   if fb.get("p95_ms") and qb.get("p95_ms") else 0.0)
    b_completion = (tb.get("completed", 0) / tb["offered"]
                    if tb.get("offered") else 0.0)
    a_vs_b_offered = (fa.get("offered", 0) / fb["offered"]
                      if fb.get("offered") else 0.0)

    gates = {
        "tenant_b_p95_isolated": {
            "value": round(b_p95_ratio, 4), "bound": 1.2,
            "op": "<=", "pass": bool(0.0 < b_p95_ratio <= 1.2)},
        "tenant_b_completion": {
            "value": round(b_completion, 4), "bound": 1.0,
            "op": ">=", "pass": bool(b_completion >= 1.0)},
        "tenant_b_zero_shed": {
            "value": tb.get("shed", 0), "bound": 0, "op": "==",
            "pass": bool(tb.get("shed", 0) == 0)},
        "tenant_a_overloaded": {
            "value": round(a_vs_b_offered, 2), "bound": 5.0,
            "op": ">=", "pass": bool(a_vs_b_offered >= 5.0)},
        "tenant_a_shed_overflow": {
            "value": fa.get("shed", 0), "bound": 1, "op": ">=",
            "pass": bool(fa.get("shed", 0) >= 1)},
        "tenant_a_retry_escalation": {
            "value": round(esc_ratio, 2), "bound": 1.5, "op": ">=",
            "pass": bool(esc_ratio >= 1.5)},
        "budget_floor_b_admitted": {
            "value": int(b_admitted), "bound": 1, "op": "==",
            "pass": bool(b_admitted)},
        "budget_floor_a_exhausted": {
            "value": int(a_exhausted), "bound": 1, "op": "==",
            "pass": bool(a_exhausted)},
        "zero_failures": {
            "value": tot["failed"], "bound": 0, "op": "==",
            "pass": bool(tot["failed"] == 0)},
        "zero_harness_drops": {
            "value": tot["dropped_harness"], "bound": 0, "op": "==",
            "pass": bool(tot["dropped_harness"] == 0)},
    }
    failures = [f"{k}: {g['value']} not {g['op']} {g['bound']}"
                for k, g in gates.items() if not g["pass"]]
    if failures:
        raise RuntimeError("tenant smoke FAILED: "
                           + "; ".join(failures)
                           + f" (errors={tot['errors'][:3]})")

    result = {
        "metric": "tenant_smoke_b_p95_isolation_ratio",
        "value": round(b_p95_ratio, 4),
        "unit": "flash_p95_over_quiet_p95",
        "quiet": {"offered": quiet["offered"],
                  "by_tenant": quiet["by_tenant"]},
        "flash": {"offered": flash["offered"],
                  "by_tenant": flash["by_tenant"]},
        "totals_by_tenant": tot["by_tenant"],
        "retry_afters": [round(r, 4) for r in retry_afters],
        "retry_escalation_ratio": round(esc_ratio, 2),
        "budget_drained_through_a": drained,
        "gates": gates,
        "backend": jax.default_backend(),
    }
    line = json.dumps(result)
    if out:
        with open(out, "w") as f:
            f.write(line + "\n")
    return result


def main() -> None:
    from singa_tpu.utils import compile_cache
    compile_cache.enable()
    if "--cpu-baseline" in sys.argv:
        bench_cpu_baseline()
        return
    if "--feed-smoke" in sys.argv:
        out = None
        if "--out" in sys.argv:
            out = sys.argv[sys.argv.index("--out") + 1]
        print(json.dumps(bench_feed_smoke(out=out)))
        return
    if "--serve-smoke" in sys.argv:
        out = None
        if "--out" in sys.argv:
            out = sys.argv[sys.argv.index("--out") + 1]
        print(json.dumps(bench_serve_smoke(out=out)))
        return
    if "--fleet-smoke" in sys.argv:
        out = None
        if "--out" in sys.argv:
            out = sys.argv[sys.argv.index("--out") + 1]
        print(json.dumps(bench_fleet_smoke(out=out)))
        return
    if "--pipeline-smoke" in sys.argv:
        out = None
        if "--out" in sys.argv:
            out = sys.argv[sys.argv.index("--out") + 1]
        print(json.dumps(bench_pipeline_smoke(out=out)))
        return
    if "--cb-smoke" in sys.argv:
        out = None
        if "--out" in sys.argv:
            out = sys.argv[sys.argv.index("--out") + 1]
        print(json.dumps(bench_cb_smoke(out=out)))
        return
    if "--traffic-smoke" in sys.argv:
        out = None
        if "--out" in sys.argv:
            out = sys.argv[sys.argv.index("--out") + 1]
        print(json.dumps(bench_traffic_smoke(out=out)))
        return
    if "--tail-smoke" in sys.argv:
        out = None
        if "--out" in sys.argv:
            out = sys.argv[sys.argv.index("--out") + 1]
        print(json.dumps(bench_tail_smoke(out=out)))
        return
    if "--failover-smoke" in sys.argv:
        out = None
        if "--out" in sys.argv:
            out = sys.argv[sys.argv.index("--out") + 1]
        print(json.dumps(bench_failover_smoke(out=out)))
        return
    if "--transport-smoke" in sys.argv:
        out = None
        if "--out" in sys.argv:
            out = sys.argv[sys.argv.index("--out") + 1]
        print(json.dumps(bench_transport_smoke(out=out)))
        return
    if "--router-smoke" in sys.argv:
        out = None
        if "--out" in sys.argv:
            out = sys.argv[sys.argv.index("--out") + 1]
        print(json.dumps(bench_router_smoke(out=out)))
        return
    if "--trace-smoke" in sys.argv:
        out = None
        if "--out" in sys.argv:
            out = sys.argv[sys.argv.index("--out") + 1]
        print(json.dumps(bench_trace_smoke(out=out)))
        return
    if "--tenant-smoke" in sys.argv:
        out = None
        if "--out" in sys.argv:
            out = sys.argv[sys.argv.index("--out") + 1]
        print(json.dumps(bench_tenant_smoke(out=out)))
        return
    if "--obs-overhead" in sys.argv:
        out = None
        if "--out" in sys.argv:
            out = sys.argv[sys.argv.index("--out") + 1]
        print(json.dumps(bench_obs_overhead(out=out)))
        return
    if "--perf-smoke" in sys.argv:
        out = None
        if "--out" in sys.argv:
            out = sys.argv[sys.argv.index("--out") + 1]
        print(json.dumps(bench_perf_smoke(out=out)))
        return
    # transformer FIRST, AlexNet second, long context last: the order
    # the round-5 record was taken in.  A failing leg fails the run.
    t = bench_transformer_mfu()
    primary = bench_alexnet_mfu()
    primary["transformer_lm_mfu"] = t["value"]
    primary["transformer_tok_sec"] = t["tok_sec"]
    # long-context aux (VERDICT r3 item 2): D=128 geometry (6x128
    # heads — BASELINE.md "D=128 prediction measured") and the same
    # 50-step windows the gated metrics use.
    lc = bench_transformer_mfu(batch_size=8, seq_len=4096, iters=50,
                               head_dim=128)
    primary["longctx_s4096_mfu"] = lc["value"]
    primary["longctx_s4096_tok_sec"] = lc["tok_sec"]
    primary["longctx_s4096_geometry"] = "12L 768E 6H D128"
    from singa_tpu.utils.flops import device_info
    primary["device"] = device_info()
    print(json.dumps(primary))
    if "--extra" in sys.argv:
        # transformer MFU is not repeated here: main() already ran it
        # for the primary line's aux keys
        for fn in (bench_lenet, bench_quick_mfu, bench_decode):
            print(json.dumps(fn()), file=sys.stderr)


if __name__ == "__main__":
    main()
