"""Runner: steady training through `Trainer.run`, feeder on, in scan
chunks, the way `singa_tpu.main --synthetic --scan_chunk k` drives it.

Order of a run: (1) the plain reference follows the first chunk's
steps from the seed's weights and batches, before anything of the
program is on the device, and keeps only numbers; (2) the program's
trainer is built over the seed's weights and `Trainer.run` drives the
first chunk through the feeder; its per-step losses, Adam's moments and
the parameters' change are compared with the reference's; (3) a second
chunk is timed to size the window; (4) the same trainer, state and
iterator run the window: whole chunks for about `--seconds`, ended by
`block_until_ready`.

The chunk is the number of steps the reference follows, because the
scan program hands back its state only at a chunk's end: the first
gradient "as the optimizer gets it" is read from Adam's first moment
after the chunk, not after one step (PERF.md section 2).
"""

from __future__ import annotations

import gc
import math
import os
import time
from typing import Dict, List, Optional

import numpy as np

from benchmark import harness, stats, weights
from benchmark.reference import dense_lm
from benchmark.runners.serve_cb import model_config, program_name
from benchmark.trace import capture

# Limits of the comparison, each beside its reason; the cell's file may
# carry its own (`limits`), set from readings on the chip (PERF.md 2).
#   loss        |loss - reference| at each step.  Hardly moved by
#               precision; there to catch a part of the batch left out.
#   moment      worst leaf's gap between the norm of Adam's first
#               moment and the reference's, over the reference's norm
#               of that leaf or of the median leaf: the gradients as
#               the optimizer got them.  The number precision moves.
#   sample      worst leaf's norm of (first moment - reference's) over
#               a strided sample of 65536 entries, against the
#               reference sample's norm (of that leaf or the median
#               leaf).  A norm hardly feels rounding noise (it adds in
#               quadrature: fp8 moved `moment` 1.5 x, PERF.md 2), the
#               entries do; this is the number the lower precision has
#               to fail.
#   (the defaults hold for the float32 CPU rehearsal only)
#   delta       the same for the norm of the parameters' change; there
#               to catch a step that returns its state unchanged.
DEFAULT_LIMITS = {"loss": 1e-4, "moment": 1e-4, "delta": 1e-4,
                  "sample": 1e-4}


def _quiet(*a, **k):
    return None


def worst_leaf(got: Dict[str, float], ref: Dict[str, float]) -> float:
    """Largest |got - ref| over max(ref of that leaf, ref of the median
    leaf): a gap between norms, not the norm of a difference."""
    floor = stats.median(list(ref.values()))
    return max(abs(got[k] - ref[k]) / max(ref[k], floor) for k in ref)


def sample_error(got: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]
                 ) -> float:
    """Largest ||got - ref|| over max(||ref|| of that leaf's sample,
    ||ref|| of the median leaf's)."""
    norm = {k: float(np.linalg.norm(v)) for k, v in ref.items()}
    floor = stats.median(list(norm.values()))
    return max(float(np.linalg.norm(np.asarray(got[k], np.float32) - ref[k]))
               / max(norm[k], floor) for k in ref)


def reference(cell, seed: int, steps: int, round_to: Optional[str] = None
              ) -> Dict:
    from benchmark.traffic import token_batches
    import jax.numpy as jnp
    cfg, tc = cell.config, cell.config["train"]
    it = token_batches.batches(cell.traffic, seed, tc["batch"],
                               cfg["vocab_size"])
    rows = [next(it)["data"] for _ in range(steps)]
    key = weights.seed_key(seed)
    table = {n: (s, std) for n, s, std in weights.leaf_table(cfg)}

    def get_leaf(name):
        shape, std = table[name]
        return weights.leaf(key, name, tuple(shape), std, jnp.float32)

    out = dense_lm.train_steps(rows, list(table), get_leaf, cfg,
                               tc["optimizer"], round_to=round_to)
    gc.collect()
    return out


def compare(cmp_: harness.Compared, got: Dict, ref: Dict, limits: Dict
            ) -> None:
    for i, (a, b) in enumerate(zip(got["loss"], ref["loss"])):
        cmp_.add(f"loss_step{i}", abs(a - b), limits["loss"])
    cmp_.add("moment_worst_leaf",
             worst_leaf(got["m_norm"], ref["m_norm"]), limits["moment"])
    cmp_.add("moment_sample_error",
             sample_error(got["m_sample"], ref["m_sample"]),
             limits["sample"])
    cmp_.add("delta_worst_leaf",
             worst_leaf(got["delta_norm"], ref["delta_norm"]),
             limits["delta"])


class Program:
    """The trainer, its state and its feed: one object from set-up
    through the window."""

    def __init__(self, cell, seed: int, broken: bool = False):
        import jax
        import jax.numpy as jnp
        from singa_tpu.core.trainer import Trainer
        from singa_tpu.data import discover_input_shapes
        from benchmark.traffic import token_batches

        self.cell, self.seed = cell, seed
        cfg, tc = cell.config, cell.config["train"]
        self.cfg, self.tc = cfg, tc
        self.seq = int(cell.traffic["seq_len"])
        model = model_config(cfg, self.seq, tc["batch"], tc["precision"])
        o = tc["optimizer"]
        model.updater.type = o["type"]
        model.updater.base_learning_rate = o["learning_rate"]
        model.updater.beta1, model.updater.beta2 = o["beta1"], o["beta2"]
        model.updater.delta = o["epsilon"]
        model.display_frequency = 10 ** 9
        if tc.get("flash_blocks"):
            from singa_tpu.ops.attention import set_flash_blocks
            set_flash_blocks(tuple(tc["flash_blocks"]))
        self.mesh = None
        if tc.get("cluster_conf"):
            from singa_tpu.config import load_cluster_config
            from singa_tpu.parallel import mesh_from_cluster
            self.mesh = mesh_from_cluster(
                load_cluster_config(os.path.join(cell.root,
                                                 tc["cluster_conf"])),
                model.neuralnet.partition_type,
                devices=jax.devices()[:cell.chips])
        self.trainer = Trainer(
            model, discover_input_shapes(model, force_synthetic=True),
            log_fn=_quiet, mesh=self.mesh, donate=not broken)
        shardings = None
        if self.mesh is not None:
            from singa_tpu.parallel.partition import (param_shardings,
                                                      replicated)
            sh = param_shardings(self.mesh, self.trainer.train_net, "model",
                                 pad_uneven=True)
            shardings = {n: sh.get(program_name(n), replicated(self.mesh))
                         for n, _, _ in weights.leaf_table(cfg)}
        made = weights.tree(cfg, seed, jnp.float32, shardings)
        self.params = {program_name(k): v for k, v in made.items()}
        del made
        self.opt_state = self.trainer.updater.init(self.params)
        if broken:
            self._return_state_unchanged()
        self.it = token_batches.batches(cell.traffic, seed, tc["batch"],
                                        cfg["vocab_size"])
        self.losses: List[float] = []
        self.drained: List[float] = []     # when each step's metrics came
        self.step = 0

    def _return_state_unchanged(self) -> None:
        """For the harness's own test: the timed path broken underneath,
        a step that returns the state it was given."""
        real = self.trainer.train_steps

        def broken(params, opt_state, *rest):
            _, _, metrics = real(params, opt_state, *rest)
            return params, opt_state, metrics

        self.trainer.train_steps = broken

    def run_chunks(self, n: int) -> float:
        """`n` more chunks through `Trainer.run`; seconds until the new
        state is ready on the device."""
        import jax
        chunk = self.tc["scan_chunk"]
        self.trainer.cfg.train_steps = self.step + n * chunk
        t0 = time.perf_counter()
        self.params, self.opt_state, _ = self.trainer.run(
            self.params, self.opt_state, self.it, start_step=self.step,
            seed=self.seed & 0x7FFFFFFF,
            hooks=[self._on_step],
            scan_chunk=chunk, feeder=bool(self.tc["feeder"]))
        jax.block_until_ready((self.params, self.opt_state))
        self.step += n * chunk
        return time.perf_counter() - t0

    def _on_step(self, step: int, metrics: Dict) -> None:
        self.losses.append(float(metrics["loss"]))
        self.drained.append(time.perf_counter())

    def state_norms(self) -> Dict:
        """Norm and sample of Adam's first moment, and the norm of the
        parameters' change since
        the seed's weights (made again leaf by leaf, never kept)."""
        import jax
        import jax.numpy as jnp
        key = weights.seed_key(self.seed)
        norm = jax.jit(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))))
        dnorm = jax.jit(lambda p, p0: jnp.sqrt(jnp.sum(jnp.square(p - p0))))
        take = jax.jit(dense_lm.sample)
        m, d, ms = {}, {}, {}
        for n, shape, std in weights.leaf_table(self.cfg):
            pn = program_name(n)
            m[n] = float(norm(self.opt_state["history"][pn]))
            ms[n] = np.asarray(take(self.opt_state["history"][pn]))
            p0 = weights.leaf(key, n, tuple(shape), std, jnp.float32)
            d[n] = float(dnorm(self.params[pn], p0))
        return {"m_norm": m, "delta_norm": d, "m_sample": ms}


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        t_process: float, compile_log, control: Optional[str] = None,
        broken: bool = False) -> Dict:
    cfg, tc = cell.config, cell.config["train"]
    chunk, batch = tc["scan_chunk"], tc["batch"]
    seq = int(cell.traffic["seq_len"])
    limits = {**DEFAULT_LIMITS, **cell.spec.get("limits", {})}

    t_ref = time.perf_counter()
    ref = reference(cell, seed, chunk)
    ctl = reference(cell, seed, chunk, round_to=control) if control else None
    ref_s = time.perf_counter() - t_ref
    print(f"reference_seconds: {ref_s:.3f}", flush=True)

    laps = harness.Laps()
    prog = Program(cell, seed, broken=broken)
    laps.lap("trainer_and_weights")
    prog.run_chunks(1)                       # compiles, or loads
    laps.lap("first_chunk")
    got = {"loss": list(prog.losses), **prog.state_norms()}
    laps.lap("state_norms")
    est = prog.run_chunks(1)                 # a warm chunk, to size the window
    laps.lap("second_chunk")
    n_chunks = max(int(seconds / est), 1)
    before = compile_log.snapshot()
    wait0 = prog.trainer.timer.times.get("wait", 0.0)
    tr = capture.Capture(cell, trace, n_chunks * est, span_s=max(2 * est, 3.0))
    n_before = len(prog.losses)
    setup_s = time.perf_counter() - t_process - ref_s

    # ---- the window --------------------------------------------------
    tr.arm()
    wall = prog.run_chunks(n_chunks)
    tr.stop()
    # ------------------------------------------------------------------
    after = compile_log.snapshot()
    drains = sorted(set(prog.drained[n_before:]))
    print(f"window: {n_chunks} chunks in {wall:.3f} s (a warm chunk took "
          f"{est:.3f} s); longest wait between metric drains "
          f"{max(b - a for a, b in zip(drains, drains[1:])) if len(drains) > 1 else 0.0:.3f} s; "
          f"trainer timer {dict(prog.trainer.timer.times)}", flush=True)
    wait_s = prog.trainer.timer.times.get("wait", 0.0) - wait0
    window_losses = prog.losses[n_before:]
    steps = n_chunks * chunk
    tokens = steps * batch * seq

    cmp_ = harness.Compared()
    cmp_.add("compiles_in_window", after["compiles"] - before["compiles"], 0)
    bad = [x for x in window_losses if not math.isfinite(x)]
    cmp_.add("steps_not_finite", len(bad) + steps - len(window_losses), 0)
    compare(cmp_, got, ref, limits)
    if ctl is not None:
        for k in ("moment", "delta"):
            name = {"moment": "m_norm", "delta": "delta_norm"}[k]
            print(f"control {control} {k}_worst_leaf: "
                  f"{worst_leaf(ctl[name], ref[name])!r}", flush=True)
        print(f"control {control} moment_sample_error: "
              f"{sample_error(ctl['m_sample'], ref['m_sample'])!r}",
              flush=True)
        print(f"control {control} loss: "
              f"{[abs(a - b) for a, b in zip(ctl['loss'], ref['loss'])]!r}",
              flush=True)

    e2e = {"setup_s": setup_s, "train_tok_s": tokens / wall}
    facts = {
        "cell": cell.name, "config": cfg, "traffic": cell.traffic,
        "peaks": harness.peaks(cell), "chips": cell.chips,
        "window_s": wall, "end_to_end": e2e,
        "compile": {**after, "setup_s": setup_s},
        "counters": {"feed_wait_s": wait_s, "steps": steps,
                     "batch": batch, "seq_len": seq},
        "spans": [], "trace_span": tr.host_span, "itemsize": 2,
        "trace": tr.reduce()}
    return {"correct": cmp_.ok, "attempted": steps,
            "failed": len(bad), "end_to_end": e2e, "facts": facts,
            "compared": cmp_.rows,
            "control": None if ctl is None else {
                "moment": worst_leaf(ctl["m_norm"], ref["m_norm"]),
                "sample": sample_error(ctl["m_sample"], ref["m_sample"]),
                "delta": worst_leaf(ctl["delta_norm"], ref["delta_norm"])},
            "counts": {"steps": steps, "tokens": tokens,
                       "chunks": n_chunks}}
