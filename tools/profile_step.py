"""Per-op TPU profile of a training step: capture a jax.profiler trace
around a few scan iterations and print a per-op duration table
attributed to Python source, so MFU work targets measured cost centers.

    python tools/profile_step.py [--model alexnet|transformer]
        [--batch 8192] [--iters 3] [--top 40]

Parsing: the benchmark's reader (`benchmark/trace/reduce.py`) over the
trace's xplane file: per-op SELF time on the device planes' `XLA Ops`
line (a loop's body nests inside the loop's own event), divided by the
iteration count.  Op names are XLA instruction names; the tag beside
each is its `op_name` and source line from the compiled module's text.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_alexnet(batch):
    import jax

    from singa_tpu.core.trainer import Trainer
    from singa_tpu.models.vision import alexnet_cifar10_full

    cfg = alexnet_cifar10_full(batchsize=batch)
    cfg.precision = "bfloat16"
    trainer = Trainer(cfg, {"data": {"pixel": (3, 32, 32), "label": ()}},
                      log_fn=lambda s: None)
    params, opt_state = trainer.init(seed=0)
    rng = np.random.default_rng(0)
    batch_d = {"data": {
        "pixel": jax.device_put(
            rng.standard_normal((batch, 3, 32, 32)).astype(np.float32)),
        "label": jax.device_put(
            rng.integers(0, 10, (batch,)).astype(np.int32)),
    }}
    return trainer, params, opt_state, batch_d


def build_transformer(batch, seq_len=1024):
    import jax

    from singa_tpu.core.trainer import Trainer
    from singa_tpu.models.transformer import (synthetic_token_batches,
                                              transformer_lm)

    cfg = transformer_lm(vocab_size=32768, num_layers=12, embed_dim=768,
                         num_heads=12, head_dim=64, seq_len=seq_len,
                         batchsize=batch)
    cfg.precision = "bfloat16"
    trainer = Trainer(cfg, {"data": {"input": (seq_len,),
                                     "target": (seq_len,)}},
                      log_fn=lambda s: None)
    params, opt_state = trainer.init(seed=0)
    batch_d = next(synthetic_token_batches(batch, seq_len, 32768))
    batch_d = jax.tree_util.tree_map(jax.device_put, batch_d)
    return trainer, params, opt_state, batch_d


def capture(trainer, params, opt_state, batch_d, iters, outdir):
    import jax

    from singa_tpu.utils.profiler import hard_sync

    key = jax.random.PRNGKey(0)
    # warm/compile outside the trace
    params, opt_state, _ = trainer.train_steps(
        params, opt_state, batch_d, 0, key, iters)
    hard_sync(params)
    with jax.profiler.trace(outdir):
        params, opt_state, _ = trainer.train_steps(
            params, opt_state, batch_d, iters, key, iters)
        hard_sync(params)


def attribute(trainer, params, opt_state, batch_d, iters):
    """Map HLO op names -> (source_file:line, op_name metadata) from the
    compiled train_steps text, so trace fusion names become readable."""
    import jax

    from singa_tpu.utils.profiler import hlo_attribution

    key = jax.random.PRNGKey(0)
    txt = trainer.train_steps.lower(
        params, opt_state, batch_d, 0, key, iters).compile().as_text()
    return hlo_attribution(txt)


def parse(outdir, iters, top, attr=None):
    import collections
    import glob

    from benchmark.trace import reduce as reducer

    paths = sorted(glob.glob(os.path.join(
        outdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise SystemExit(f"no profiler trace under {outdir}")
    per_op = collections.Counter()
    for plane in reducer.read_planes(paths[-1]):
        if reducer.DEVICE_PLANE.match(plane["name"]):
            ops = reducer.line_events(plane, reducer.OPS_LINE)
            for line, sec in reducer.self_times(ops).items():
                per_op[line.split(" = ")[0].lstrip("%")] += sec * 1e6
    total_us = sum(per_op.values())
    if not total_us:
        raise SystemExit(f"no device op in the trace under {outdir} "
                         f"(device planes are /device:TPU:<n>)")
    print(f"# trace {outdir}")
    print(f"# total device time {total_us / 1e3 / iters:.2f} ms/step over "
          f"{iters} iters, {len(per_op)} distinct ops")
    print(f"{'ms/step':>9s}  {'%':>5s}  op")
    for name, us in per_op.most_common(top):
        tag = (attr or {}).get(name, "")
        print(f"{us / 1e3 / iters:9.3f}  {100 * us / total_us:5.1f}  "
              f"{name[:40]:40s}  {tag[:120]}")


def main():
    from singa_tpu.utils import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="alexnet",
                    choices=["alexnet", "transformer"])
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--outdir", default="/tmp/prof_step")
    ap.add_argument("--seq", type=int, default=1024,
                    help="transformer sequence length")
    args = ap.parse_args()
    if args.model == "alexnet":
        built = build_alexnet(args.batch or 8192)
    else:
        built = build_transformer(args.batch or max(8192 // args.seq, 1),
                                  args.seq)
    trainer, params, opt_state, batch_d = built
    attr = attribute(trainer, params, opt_state, batch_d, args.iters)
    capture(*built, args.iters, args.outdir)
    parse(args.outdir, args.iters, args.top, attr)


if __name__ == "__main__":
    main()
