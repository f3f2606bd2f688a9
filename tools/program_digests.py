"""Digests of the programs the benchmark's cells run, to show that a
change left them alone (a builder's tool; no chip, a few minutes):

    python tools/program_digests.py [--root TREE] [cell ...]

lowers, at the published sizes and for a described v5e, every cb
program of a serving cell (`cb_prefill_<w>` or `cb_chunk_<w>` a rung,
`cb_decode`) and the training cell's `train_step` and `train_steps`
(one scan chunk), on `ShapeDtypeStruct`s alone, and prints the first 12
hex digits of the sha256 of each one's StableHLO with the source
locations struck.  Nothing is compiled and nothing runs.

To compare two trees, unpack BOTH at ONE path, one after the other
(`git archive <commit> | tar -x -C <dir>`, `--root <dir>`): a Mosaic
kernel's serialized body holds the checkout's path, so two paths give
every program with a kernel in it another digest (CHANGES.md, PR 45)."""
import argparse
import hashlib
import importlib
import json
import os
import re
import sys
import traceback

CELLS = ("serve-chat-r80", "serve-code-sat", "serve-kimi-decode-sat",
         "serve-zaya-reason-sat", "serve-trinity-agent-sat",
         "serve-pangu-think-sat", "serve-solar-docqa-sat",
         "train-s4096-1chip")
WEIGHTS = ("weights", "kimi_weights", "zaya_weights", "trinity_weights",
           "pangu_weights", "solar_weights")
_LOC = re.compile(r"^#loc.*\n|\s*loc\([^\n]*\)$", re.M)


def digest(lowered) -> str:
    text = _LOC.sub("", lowered.as_text())
    return hashlib.sha256(text.encode()).hexdigest()[:12]


class _Built(Exception):
    """Carries the engine out of a runner's `build` before it loads."""


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        help="the tree to lower (default: this checkout)")
    ap.add_argument("cells", nargs="*", default=list(CELLS))
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    os.chdir(root)
    sys.path.insert(0, root)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import singa_tpu.ops.attention as att
    from benchmark import harness
    from singa_tpu.serve.engine import InferenceEngine

    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    att._on_tpu = lambda: True      # the chip's branches, kernels and all
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)

    def shapes_of(tree):
        return jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), tree)

    # weights as shapes: a cell's runner builds its engine on them
    for name in WEIGHTS:
        mod = importlib.import_module("benchmark." + name)

        def tree(cfg, seed, dtype, *a, _m=mod, **k):
            return {n: sds(s, dtype) for n, s, _ in _m.leaf_table(cfg)}
        mod.tree = tree

    real_init = InferenceEngine.__init__

    def init(self, net, spec, params=None, **kw):
        real_init(self, net, spec, params={}, **kw)
        self._params = params

    def load(self):
        raise _Built(self)

    InferenceEngine.__init__, InferenceEngine.load = init, load

    def show(cell, name, lowered, out):
        out[name] = digest(lowered)
        print(cell, name, out[name], flush=True)

    def serving(cellname):
        cell = harness.Cell(cellname)
        runner = cell.load("runners", cell.spec["runner"])
        try:
            runner.build(cell, 1)
        except _Built as e:
            eng = e.args[0]
        spec, params = eng.spec, eng._params
        pools = shapes_of(eng._pools_spec())
        rng = sds((2,), jnp.uint32)
        out = {}
        for width in spec.cb_prefill_widths:
            if eng.chunks_prompts:
                name, fn = f"cb_chunk_{width}", eng._build_cb_chunk(width)
                small = (sds((3,), jnp.int32),
                         sds((spec.cb_blocks_per_slot + 1,), jnp.int32))
            else:
                name, fn = f"cb_prefill_{width}", eng._build_cb_prefill(width)
                small = (sds((), jnp.int32),
                         sds((width // spec.cb_block_len
                              + int(eng._per_slot_state),), jnp.int32))
            show(cellname, name, jax.jit(fn, donate_argnums=(1,)).lower(
                params, pools, sds((1, width), jnp.int32), *small, rng), out)
        s = spec.cb_slots
        small = [sds((eng._cb_width,), jnp.int32),
                 sds((s, spec.cb_blocks_per_slot), jnp.int32)]
        if not eng.drafts:
            small.insert(1, sds((s,), jnp.int32))
        show(cellname, "cb_decode",
             jax.jit(eng._build_cb_decode(), donate_argnums=(1,)).lower(
                 params, pools, *small, rng), out)
        return out

    def training(cellname):
        from benchmark import weights
        from benchmark.runners import train as tr
        from benchmark.traffic import token_batches
        from singa_tpu.core.trainer import Trainer
        from singa_tpu.data import discover_input_shapes
        cell = harness.Cell(cellname)
        cfg, tc = cell.config, cell.config["train"]
        model = tr.model_config(cfg, int(cell.traffic["seq_len"]),
                                tc["batch"], tc["precision"])
        o = tc["optimizer"]
        model.updater.type = o["type"]
        model.updater.base_learning_rate = o["learning_rate"]
        model.updater.beta1, model.updater.beta2 = o["beta1"], o["beta2"]
        model.updater.delta = o["epsilon"]
        model.display_frequency = 10 ** 9
        if tc.get("flash_blocks"):
            att.set_flash_blocks(tuple(tc["flash_blocks"]))
        trainer = Trainer(model,
                          discover_input_shapes(model, force_synthetic=True),
                          log_fn=lambda *a, **k: None)
        params = {tr.program_name(n): sds(s, jnp.float32)
                  for n, s, _ in weights.leaf_table(cfg)}
        opt = shapes_of(jax.eval_shape(trainer.updater.init, params))
        one_batch = next(iter(token_batches.batches(
            cell.traffic, 1, tc["batch"], cfg["vocab_size"])))
        step, rng = sds((), jnp.int32), sds((2,), jnp.uint32)
        chunk = int(tc["scan_chunk"])
        out = {}
        show(cellname, "train_step", trainer.train_step.lower(
            params, opt, shapes_of(one_batch), step, rng), out)
        stacked = jax.tree_util.tree_map(
            lambda a: sds((chunk,) + a.shape, a.dtype), one_batch)
        show(cellname, "train_steps", trainer.train_steps.lower(
            params, opt, stacked, step, rng, chunk, True), out)
        return out

    result, failed = {}, 0
    for c in args.cells:
        try:
            result[c] = training(c) if c.startswith("train") else serving(c)
        except Exception as e:  # noqa: BLE001 — the other cells still print
            traceback.print_exc()
            result[c] = {"error": repr(e)}
            failed = 1
    print(json.dumps(result, indent=1))
    return failed


if __name__ == "__main__":
    sys.exit(main())
