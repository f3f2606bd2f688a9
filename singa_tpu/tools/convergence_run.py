"""North-star gate 1: train examples/mnist/conv.conf to >=99% test
accuracy and record time-to-99 (BASELINE.md tracked metric).

The reference's convergence configs train on real MNIST shards
(examples/mnist/conv.conf:1-21; accuracy printed by the Performance
blob, worker.cc:376-386).  This environment has zero egress and no
local MNIST, so the run uses the learnable synthetic source
(singa_tpu.data.synthetic): fixed per-class templates, a *held-out
test stream* (same templates, independent noise/labels — the model
must generalize, not memoize batches), and a noise level set so the
net starts at chance and has to learn.

Writes CONVERGENCE.json at the repo root.  Two wall-clocks are reported:
`time_to_99_seconds` from the start of run() (includes XLA compiles —
what a user experiences) and `train_time_to_99_seconds` counting every
train chunk and eval at warm-execution speed (programs pre-compiled
before timing starts).

Usage: python -m singa_tpu.tools.convergence_run [--target 0.99]
       [--max-steps 10000] [--out CONVERGENCE.json] [--noise-std 96]
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np


def run(conf: str, target: float, max_steps: int, out: str,
        noise_std: float, chunk: int, test_batches: int,
        log=print) -> dict:
    t_start = time.time()
    import jax

    from ..config import load_model_config
    from ..core.trainer import Trainer
    from ..data.synthetic import synthetic_image_batches

    cfg = load_model_config(conf)
    batch = next(l.data_param.batchsize for l in cfg.neuralnet.layer
                 if l.data_param)
    trainer = Trainer(cfg, {"data": {"pixel": (28, 28), "label": ()}},
                      log_fn=log)
    params, opt_state = trainer.init(seed=0)

    train_iter = synthetic_image_batches(batch, seed=7, stream_seed=100,
                                         noise_std=noise_std)
    # held-out split: same templates (seed), independent stream
    test_set = []
    test_iter = synthetic_image_batches(1000, seed=7, stream_seed=200,
                                        noise_std=noise_std)
    for _ in range(test_batches):
        test_set.append(next(test_iter))

    def test_accuracy(p):
        accs = [float(trainer.test_step(p, b)["precision"])
                for b in test_set]
        return float(np.mean(accs))

    rng = jax.random.PRNGKey(1)
    step = 0
    train_s = 0.0
    result = None
    acc0 = test_accuracy(params)   # also compiles test_step
    log(f"step-0 test accuracy {acc0:.4f} (chance ~0.10)")
    # pre-compile the scan program so every timed chunk below is warm
    # execution (train_time_to_99_seconds counts ALL train steps + all
    # evals, excluding only XLA compilation)
    warm = [next(train_iter) for _ in range(chunk)]
    warm_stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *warm)
    # through the trainer's AOT cache: CompileWatch times the compile,
    # CostWatch harvests it, and later consumers reuse the same
    # executable instead of compiling their own
    trainer.compiled_scan(params, opt_state, warm_stacked, 0, rng,
                          chunk, True)
    while step < max_steps:
        n = min(chunk, max_steps - step)
        batches = ([next(train_iter) for _ in range(n)]
                   if step or n != chunk else warm)
        stacked = (jax.tree_util.tree_map(lambda *xs: np.stack(xs),
                                          *batches)
                   if step or n != chunk else warm_stacked)
        t0 = time.perf_counter()
        params, opt_state, _ = trainer.train_steps(
            params, opt_state, stacked, step, rng, n, True)
        jax.block_until_ready(
            jax.tree_util.tree_leaves(params)[0])
        train_s += time.perf_counter() - t0
        step += n
        t0 = time.perf_counter()
        acc = test_accuracy(params)
        train_s += time.perf_counter() - t0
        log(f"step-{step} test accuracy {acc:.4f}")
        if acc >= target and result is None:
            result = {
                "mnist_test_accuracy": round(acc, 4),
                "steps_to_99": step,
                "time_to_99_seconds": round(time.time() - t_start, 2),
                "train_time_to_99_seconds": round(train_s, 2),
            }
            break
    final = {
        "conf": os.path.relpath(conf),
        "target": target,
        "data": f"synthetic-learnable(noise_std={noise_std}, "
                f"held-out stream)",
        "batchsize": batch,
        "test_samples": 1000 * test_batches,
        "device": str(jax.devices()[0]),
        "reached": result is not None,
        **(result or {"mnist_test_accuracy": round(acc, 4),
                      "steps_run": step}),
    }
    with open(out, "w") as f:
        json.dump(final, f, indent=1)
    log(json.dumps(final))
    return final


def main():
    from ..utils import compile_cache
    compile_cache.enable()
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap = argparse.ArgumentParser()
    ap.add_argument("--conf",
                    default=os.path.join(repo, "examples/mnist/conv.conf"))
    ap.add_argument("--target", type=float, default=0.99)
    ap.add_argument("--max-steps", type=int, default=10000)
    ap.add_argument("--out",
                    default=os.path.join(repo, "CONVERGENCE.json"))
    ap.add_argument("--noise-std", type=float, default=96.0)
    ap.add_argument("--chunk", type=int, default=100)
    ap.add_argument("--test-batches", type=int, default=10)
    a = ap.parse_args()
    run(a.conf, a.target, a.max_steps, a.out, a.noise_std, a.chunk,
        a.test_batches)


if __name__ == "__main__":
    main()
