"""Checks of what the code assumes about the runtime on the chip.

Not part of `chip_smoke.py` (which is pass/fail on the main path):
these establish one fact each and print it as one JSON line, naming
the device.  One process, run through the chip tool:

    python tools/chip_checks.py [sync] [feeder] [tokens]

  sync     `jax.block_until_ready` against a host fetch of the result,
           on one timed window of the 12x768 train scan: after either
           barrier the other must find nothing left to wait for.
  feeder   `Trainer.run` at scan_chunk 16 with the DeviceFeeder on and
           off: the per-step losses must be bit-identical (the staging
           buffers are reused; device_put is asynchronous).
  tokens   one fixed prompt, greedy, through the static generate
           bucket and through continuous batching at full width: do
           the tokens agree, and if not, from which index.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

VOCAB, SEQ, BATCH = 32768, 1024, 32


def lm_config(layers: int, seq: int = SEQ, batch: int = BATCH,
              heads=(12, 64)):
    from singa_tpu.models.transformer import transformer_lm
    return transformer_lm(vocab_size=VOCAB, num_layers=layers,
                          embed_dim=768, num_heads=heads[0],
                          head_dim=heads[1],
                          seq_len=seq, batchsize=batch,
                          precision="bfloat16")


def report(check: str, **fields) -> None:
    from singa_tpu.utils.flops import device_info
    print(json.dumps({"check": check, **device_info(), **fields}),
          flush=True)


def check_sync() -> None:
    import jax

    from singa_tpu.core.trainer import Trainer
    from singa_tpu.models.transformer import synthetic_token_batches

    steps = 8
    trainer = Trainer(lm_config(12), {"data": {"input": (SEQ,),
                                               "target": (SEQ,)}},
                      log_fn=lambda s: None)
    params, opt = trainer.init(seed=0)
    batch = jax.device_put(next(synthetic_token_batches(BATCH, SEQ,
                                                        VOCAB)))
    key = jax.random.PRNGKey(0)

    def fetch(tree):
        leaf = jax.tree_util.tree_leaves(tree)[0]
        np.asarray(leaf.ravel()[:1])

    def window(first, second):
        nonlocal params, opt
        t0 = time.perf_counter()
        params, opt, m = trainer.train_steps(params, opt, batch, 0, key,
                                             steps)
        first((params, opt, m))
        t1 = time.perf_counter()
        second((params, opt, m))
        return t1 - t0, time.perf_counter() - t1

    window(jax.block_until_ready, fetch)           # compile + warm
    block, then_fetch = window(jax.block_until_ready, fetch)
    fetched, then_block = window(fetch, jax.block_until_ready)
    report("sync", steps=steps,
           block_until_ready_s=round(block, 4),
           fetch_after_block_s=round(then_fetch, 4),
           host_fetch_s=round(fetched, 4),
           block_after_fetch_s=round(then_block, 4))


def check_feeder() -> None:
    from singa_tpu.core.trainer import Trainer
    from singa_tpu.models.transformer import synthetic_token_batches

    cfg = lm_config(2)
    cfg.train_steps, cfg.display_frequency = 64, 0
    trainer = Trainer(cfg, {"data": {"input": (SEQ,), "target": (SEQ,)}},
                      log_fn=lambda s: None, donate=False)

    def run(feeder):
        losses = {}
        p, o = trainer.init(seed=0)
        trainer.run(p, o, synthetic_token_batches(BATCH, SEQ, VOCAB,
                                                  seed=3),
                    seed=0, scan_chunk=16, feeder=feeder,
                    hooks=[lambda s, m: losses.__setitem__(
                        s, float(m["loss"]))])
        return [losses[s] for s in sorted(losses)]

    on, off = run(True), run(False)
    diff = [i for i, (a, b) in enumerate(zip(on, off)) if a != b]
    report("feeder", steps=len(on), scan_chunk=16,
           identical=(not diff and len(on) == len(off) == 64),
           first_differing_step=diff[0] if diff else None,
           first_loss=on[0], last_loss=on[-1])


def check_tokens() -> None:
    import jax

    from singa_tpu.core.net import build_net
    from singa_tpu.serve import InferenceEngine, InferenceServer, ServeSpec

    # 6 x 128: the paged decode kernel refuses a head_dim of 64 on the chip
    net = build_net(lm_config(12, heads=(6, 128)), "kTest",
                    {"data": {"input": (SEQ,), "target": (SEQ,)}})
    params = net.init_params(jax.random.PRNGKey(0))
    prompt = np.random.default_rng(0).integers(0, VOCAB, 100).astype(
        "int32")
    base = "buckets=8x128,max_new_tokens=64"
    out = {}
    for name, spec in (("static", base),
                       ("cb", base + ",cb=on,cb_slots=8,cb_block_len=16")):
        engine = InferenceEngine(net, ServeSpec.parse(spec), params=params,
                                 log_fn=lambda s: None)
        with InferenceServer(engine, http=False,
                             log_fn=lambda s: None) as server:
            out[name] = [int(t) for t in
                         server.generate(prompt, timeout=120)["tokens"]]
            dtype = str(engine.serve_dtype)
    diff = [i for i, (a, b) in enumerate(zip(out["static"], out["cb"]))
            if a != b]
    report("tokens", served_dtype=dtype, prompt_len=int(prompt.size),
           new_tokens=[len(out["static"]), len(out["cb"])],
           agree=(not diff and len(out["static"]) == len(out["cb"])),
           first_differing_index=diff[0] if diff else None)


CHECKS = {"sync": check_sync, "feeder": check_feeder,
          "tokens": check_tokens}


def main() -> None:
    from singa_tpu.utils import compile_cache
    compile_cache.enable()
    import jax
    if jax.default_backend() != "tpu":
        raise SystemExit(f"chip_checks: JAX's default backend is "
                         f"{jax.default_backend()!r}, not 'tpu'")
    for name in sys.argv[1:] or list(CHECKS):
        CHECKS[name]()


if __name__ == "__main__":
    main()
