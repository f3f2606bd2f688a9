"""Trainer: the TPU-native Worker (reference src/worker/worker.cc).

The reference Worker spawns Executor threads that walk the layer DAG,
block on bridges/param versions, and push gradients at a ZMQ parameter
server.  Here the entire TrainOneBatch (worker.cc:187-316) — forward,
backward, and updater — is ONE jitted function; data parallelism is a
mesh sharding over the batch dim with XLA inserting the gradient psum
(see singa_tpu.parallel), so there is no parameter-server plane and no
CPU compute in the inner loop.

Cadence semantics preserved from ModelProto (model.proto:2-47):
  train_steps, test_steps, test_frequency/test_after_steps,
  validation_*, display_*; Performance metric averaging over the display
  interval (worker.cc:350-386); per-phase wall-time report in the style
  of TimerInfo (worker.h:91-114).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import obs
from ..obs import perf
from ..config.schema import ModelConfig
from ..utils import faults
from .net import NeuralNet, build_net
from .updater import Updater, make_updater

#: chunks the DeviceFeeder stages ahead where `feeder_depth` is not
#: given (docs/PERFORMANCE.md)
FEEDER_DEPTH = 2


@dataclass
class Performance:
    """Metric aggregation over an interval (worker.cc:350-386)."""
    totals: Dict[str, float] = field(default_factory=dict)
    counter: int = 0

    def update(self, metrics: Dict[str, jnp.ndarray]) -> None:
        for k, v in metrics.items():
            self.totals[k] = self.totals.get(k, 0.0) + float(v)
        self.counter += 1

    def to_string(self) -> str:
        n = max(self.counter, 1)
        return ", ".join(f"{k} : {v / n:.6f}"
                         for k, v in sorted(self.totals.items()))

    def averages(self) -> Dict[str, float]:
        n = max(self.counter, 1)
        return {k: v / n for k, v in self.totals.items()}

    def reset(self) -> None:
        self.totals.clear()
        self.counter = 0


@dataclass
class TimerInfo:
    """Per-phase wall-time accumulator (worker.h:91-114).  Host phases:
    `wait` (blocked on the batch source / DeviceFeeder), `stage` (stack
    + device_put — ON the critical path in the synchronous loop, a
    producer-thread measurement that OVERLAPS `train` when the feeder
    is active, so wait+stage+train can exceed wall time there — see
    docs/PERFORMANCE.md), `train` (dispatch + device sync).  The
    device-side fwd/bwd/update split the reference timed around each
    phase call is one fused XLA program here: there is no boundary to
    time."""
    times: Dict[str, float] = field(default_factory=dict)
    steps: int = 0

    def add(self, phase: str, seconds: float) -> None:
        self.times[phase] = self.times.get(phase, 0.0) + seconds

    def to_string(self) -> str:
        total = sum(self.times.values()) or 1.0
        parts = [f"{k}: {v / max(self.steps, 1) * 1e3:.2f}ms "
                 f"({100 * v / total:.0f}%)"
                 for k, v in self.times.items()]
        return "Time per step — " + ", ".join(parts)

    def reset(self) -> None:
        self.times.clear()
        self.steps = 0

    def register_into(self, registry,
                      prefix: str = "singa_train") -> None:
        """Register this timer's phase totals into an
        `obs.MetricsRegistry` as a pull-time collector — additive; the
        timer's own API and report are untouched."""
        from ..obs.metrics import Sample

        def collect():
            out = [Sample(f"{prefix}_steps_total", "counter",
                          "training steps timed", float(self.steps))]
            for phase, secs in sorted(self.times.items()):
                out.append(Sample(
                    f"{prefix}_phase_{phase}_seconds_total", "counter",
                    f"cumulative host seconds in the {phase!r} phase",
                    secs))
            return out

        registry.register_collector(collect)


class Trainer:
    """Single-controller training driver.

    `data_factory(phase, net)` must return an iterator of batch dicts
    matching the net's data layers (see singa_tpu.data.pipeline).
    """

    def __init__(self, model_cfg: ModelConfig,
                 input_shapes: Dict[str, Dict[str, tuple]],
                 log_fn: Optional[Callable[[str], None]] = None,
                 donate: bool = True, mesh=None, n_micro: int = 0,
                 ngroups: int = 1, health=None):
        """`mesh` + layers carrying locationid stage marks → the staged
        region runs pipelined over the mesh's "pipe" axis (see
        parallel.pipeline_net); `n_micro` sets the GPipe microbatch
        count (default 2·pipe — ClusterProto.pipeline_microbatches maps
        here from main.py).

        When UpdaterProto's consistency knobs request the async tier
        (param_type Elastic with moving_rate > 0, or RandomSync —
        parallel.elastic.async_active), `run` exchanges params with a
        center copy at sync_frequency after warmup_steps, exactly the
        reference worker's cadence (worker.cc:44-55); `ngroups` scales
        Elastic's alpha = moving_rate/ngroups (param_manager.cc:15).
        Multi-replica groups run through parallel.elastic.ReplicaSet.

        `health` (a utils.health.HealthMonitor) arms the numeric-health
        sentinel: the compiled train step gains device-side probes
        (grad/param norms, update ratio) that ride the deferred metrics
        ring, the ring drain classifies each step, fatal verdicts raise
        a structured NumericDivergence, and checkpoint saves carry (and
        are gated on) the window's health verdict.  None (the default)
        compiles exactly the pre-health step program."""
        self.cfg = model_cfg
        # default: the structured component logger (obs.log satellite)
        # — human-readable "[trainer] ..." lines, warning+ mirrored to
        # the event log when a session is live.  A caller-provided
        # log_fn (tests, serve_main) is used verbatim as before.
        self.log = log_fn if log_fn is not None \
            else obs.get_logger("trainer")
        self.mesh = mesh
        self.health = health
        self._donate = donate
        self.compute_dtype = (jnp.bfloat16
                              if model_cfg.precision == "bfloat16" else None)
        self.train_net = build_net(model_cfg, "kTrain", input_shapes)
        self.test_net = self._maybe_net("kTest", input_shapes)
        self.val_net = self._maybe_net("kValidation", input_shapes)
        # sequence-parallel nets shard token dims over "seq" too —
        # input placement (_batch_place/_chunk_place) must match
        self._uses_sp = any(
            l.attention_param and l.attention_param.seq_parallel != "none"
            for l in (model_cfg.neuralnet.layer
                      if model_cfg.neuralnet else []))
        self.updater = make_updater(model_cfg.updater)
        self.multipliers = self.train_net.multipliers()
        self._pipeline_nets = self._maybe_pipeline(n_micro)
        from ..parallel.elastic import ElasticController, async_active
        self.elastic = (ElasticController(model_cfg.updater, ngroups,
                                          log_fn=self.log)
                        if async_active(model_cfg.updater) else None)
        self._build_steps(donate)
        # AOT executables from `compiled_scan`, keyed by geometry —
        # one compile serves HLO text, cost harvesting, AND execution
        self._aot_cache: Dict[tuple, Any] = {}
        self.perf = Performance()
        self.timer = TimerInfo()
        # post-save publication hook (step, verdict) — the closed-loop
        # pipeline's train→serve seam (core/pipeline.py wires it).
        # Fires AFTER a snapshot is durably on disk with its health
        # verdict recorded; the cadence path drains the metrics ring
        # before every save, so drain-before-publish holds for free.
        # Observer semantics: a raising hook is logged, never a step
        # failure.
        self.on_checkpoint: Optional[Callable[[int, Optional[str]],
                                              None]] = None
        for nm, freq, steps in (
                ("test", model_cfg.test_frequency, model_cfg.test_steps),
                ("validation", model_cfg.validation_frequency,
                 model_cfg.validation_steps)):
            if freq > 0 and steps <= 0:
                self.log(f"warning: {nm}_frequency is set but "
                         f"{nm}_steps is 0 — no {nm} net is built and "
                         f"{nm} evaluation will not run (the reference "
                         f"gates eval nets on the step count, "
                         f"worker.cc:16-27)")

    def _maybe_pipeline(self, n_micro: int) -> Dict[int, Any]:
        """{id(net): PipelineNet} when the config marks stages AND the
        mesh has a pipe axis > 1; {} otherwise (locationid marks are
        inert on a flat mesh, matching the reference running a
        location-annotated net on a single worker)."""
        mesh = self.mesh
        has_pipe = (mesh is not None and "pipe" in getattr(mesh, "shape", {})
                    and mesh.shape["pipe"] > 1)
        staged = any(l.locationid > 0
                     for l in self.cfg.neuralnet.layer)
        if not (has_pipe and staged):
            return {}
        from ..parallel.pipeline_net import (HeteroPipelineNet,
                                             NonUniformStages,
                                             PipelineNet)
        n_micro = n_micro or 2 * mesh.shape["pipe"]
        nets = {}
        for net in (self.train_net, self.test_net, self.val_net):
            if net is not None:
                try:
                    nets[id(net)] = PipelineNet(net, n_micro)
                except NonUniformStages as e:
                    # the reference pipelines arbitrary locationid
                    # layouts (neuralnet.cc:198-323); non-stackable
                    # stages take the switch-dispatch form
                    self.log(f"pipeline: stages not SPMD-stackable "
                             f"({e}); using HeteroPipelineNet")
                    nets[id(net)] = HeteroPipelineNet(net, n_micro)
        return nets

    def _net_apply(self, net):
        """net.apply, or the pipelined equivalent when configured."""
        pnet = self._pipeline_nets.get(id(net))
        return net.apply if pnet is None else pnet.apply

    def _maybe_net(self, phase: str, input_shapes) -> Optional[NeuralNet]:
        """Build the eval net for `phase`, or None when the phase is not
        configured.  Mirrors the reference Worker, which builds the
        test/validation nets only when their step counts are set
        (worker.cc:16-27: `if(model.test_steps()) SetupNeuralNet(kTest)`)
        — e.g. conv.conf's two same-named per-phase data layers exclude
        kTrain/kTest but not kValidation, so a kValidation build would
        see duplicate nodes; with validation unconfigured it is never
        attempted.  A phase whose filtered layers lack a data or loss
        layer is also legitimately absent, but a CONFIGURED phase that
        fails to build (typo'd srclayer, bad shapes) raises instead of
        silently disabling evaluation (round-1 review: the old bare
        `except Exception` swallowed real config errors)."""
        steps = (self.cfg.test_steps if phase == "kTest"
                 else self.cfg.validation_steps)
        if steps <= 0:
            return None
        from .layers import LAYER_REGISTRY
        cfgs = [l for l in self.cfg.neuralnet.layer if phase not in l.exclude]
        has_data = any(getattr(LAYER_REGISTRY.get(l.type), "is_data", False)
                       for l in cfgs)
        has_loss = any(getattr(LAYER_REGISTRY.get(l.type), "is_loss", False)
                       for l in cfgs)
        if not (has_data and has_loss):
            return None
        net = build_net(self.cfg, phase, input_shapes)
        return net if net._loss_layers() else None

    # -- compiled steps ----------------------------------------------------
    #: TPU compiler options for conv-family step programs, passed per
    #: compile through jit(compiler_options=...).  The scoped-VMEM
    #: budget (default 16MB) caps XLA's fusion depth.  On this
    #: installation (libtpu 0.0.34, one v5e chip, PR 21's chip run) the
    #: AlexNet gate workload (batch 8192, bf16) steps in 121.5 ms with
    #: the option and 127.8 ms without.  The sweep that picked 112MB
    #: (96MB 136 -> 128 ms, 120MB slightly worse, 128MB spilling to
    #: 2.8 s/step; BASELINE.md) and the transformer's regression
    #: under this budget (0.201 -> 0.179 MFU) date from an earlier
    #: installation and were not re-measured.  `auto` applies the
    #: option only to nets whose widest convolution has >= 96 filters
    #: (see _compiler_options): smaller nets do not need it.
    TPU_CONV_COMPILER_OPTIONS = {"xla_tpu_scoped_vmem_limit_kib": "114688"}
    #: Attention-family programs get a MODEST raise instead.  It is
    #: required, not a tuning: at the 16MB default this libtpu refuses
    #: the flash kernels' (512, 1024) blocks at 12 heads x 64 ("Scoped
    #: allocation with size 21.01M and limit 16.00M exceeded scoped
    #: vmem limit"); at 32MB the 12x768 S=1024 batch-32 step compiles
    #: and runs (PR 21's chip run).
    TPU_ATTN_COMPILER_OPTIONS = {"xla_tpu_scoped_vmem_limit_kib": "32768"}

    def _compiler_options(self):
        from ..ops.attention import _on_tpu
        if not _on_tpu():
            return None
        # Escape hatch (VERDICT r2 item 9): ModelProto scoped_vmem
        # (auto|on|off) selects the policy, so a user whose net trips
        # the auto heuristic either way is never at the mercy of the
        # filter-count proxy.
        mode = getattr(self.cfg, "scoped_vmem", "auto")
        if mode not in ("auto", "on", "off"):
            raise ValueError(
                f"scoped_vmem must be auto|on|off, got {mode!r}")
        if mode == "off":
            return None
        # Budgets are per FAMILY: attention-family nets take the modest
        # raise, everything else the conv budget.  "on" forces the
        # family-sized budget even where auto's heuristic would skip
        # it (e.g. LeNet-scale convs); it never selects the wrong
        # family's budget.
        attn = any(l.cfg.type in ("kAttention", "kLMHeadLoss")
                   for l in self.train_net.layers.values())
        family = (self.TPU_ATTN_COMPILER_OPTIONS if attn
                  else self.TPU_CONV_COMPILER_OPTIONS)
        if mode == "on":
            return dict(family)
        # auto: attention nets always need it; conv stacks only at
        # AlexNet scale.  (An earlier installation hung the LeNet
        # compile under the raised budget; libtpu 0.0.34 compiles it
        # in about the time it takes without — the heuristic stays
        # because small nets gain nothing from the option.)
        if attn:
            return dict(family)
        widths = [l.num_filters for l in self.train_net.layers.values()
                  if l.cfg.type == "kConvolution"]
        if widths and max(widths) >= 96:
            return dict(family)
        return None

    def _build_steps(self, donate: bool) -> None:
        net, updater, mults = self.train_net, self.updater, self.multipliers
        mesh, cdtype = self.mesh, self.compute_dtype
        net_apply = self._net_apply(net)
        copts = self._compiler_options()
        # device-side numeric probes fuse into the step program only
        # when a monitor is armed — the default compiles the exact
        # pre-health program (and metrics dict)
        health_on = self.health is not None
        if health_on:
            from ..utils.health import health_probes
        # `poison` (None in every normal call — extra traced argument
        # only when a step.grad fault fires) scales the gradients: NaN
        # for the `nan` kind, SPIKE_SCALE for `spike` — the silent
        # numeric failures the health tier exists to catch
        poisoned = (lambda grads, pz: grads if pz is None else
                    jax.tree_util.tree_map(lambda g: g * pz, grads))

        def train_step(params, opt_state, batch, step, rng, poison=None):
            def loss_fn(p):
                loss, metrics, _ = net_apply(p, batch, rng=rng, train=True,
                                             mesh=mesh, compute_dtype=cdtype,
                                             step=step)
                return loss, metrics
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            grads = poisoned(grads, poison)
            new_params, opt_state = updater.update(
                step, grads, params, opt_state, multipliers=mults)
            if health_on:
                metrics = {**metrics,
                           **health_probes(grads, params, new_params)}
            return new_params, opt_state, metrics

        donate_args = (0, 1) if donate else ()
        self.train_step = jax.jit(train_step, donate_argnums=donate_args,
                                  compiler_options=copts)

        def train_scan(params, opt_state, batches, start_step, rng, nsteps,
                       stacked=False, poison=None):
            """`nsteps` training steps in ONE compiled program (lax.scan).

            Removes the per-step host dispatch from the inner loop — the
            TPU analogue of the reference keeping its hot loop inside the
            Executor thread (worker.cc:98-106) instead of crossing a
            process boundary per batch.  With `stacked=True` every leaf
            of `batches` carries a leading `nsteps` axis that is scanned
            over (a fresh batch per step); with the default False,
            `batches` is a single batch reused every step.  `poison`
            (None normally; an (nsteps,) grad-scale vector when a
            step.grad fault fires inside the chunk) is scanned over
            alongside the steps.  Returns stacked per-step metrics.
            """
            def body(carry, xs):
                p, o = carry
                step, batch, pz = xs
                if batch is None:
                    batch = batches
                step_rng = jax.random.fold_in(rng, step)

                def loss_fn(pp):
                    loss, metrics, _ = net_apply(
                        pp, batch, rng=step_rng, train=True, mesh=mesh,
                        compute_dtype=cdtype, step=step)
                    return loss, metrics
                (_, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(p)
                grads = poisoned(grads, pz)
                new_p, o = updater.update(step, grads, p, o,
                                          multipliers=mults)
                if health_on:
                    metrics = {**metrics,
                               **health_probes(grads, p, new_p)}
                return (new_p, o), metrics

            steps = start_step + jnp.arange(nsteps)
            if stacked:
                bad = [x.shape for x in jax.tree_util.tree_leaves(batches)
                       if getattr(x, "ndim", 0) < 1 or x.shape[0] != nsteps]
                if bad:
                    raise ValueError(
                        f"stacked=True needs a leading {nsteps}-axis on "
                        f"every batch leaf; got shapes {bad}")
            xs = (steps, batches if stacked else None, poison)
            (params, opt_state), metrics = jax.lax.scan(
                body, (params, opt_state), xs, length=nsteps)
            return params, opt_state, metrics

        self.train_steps = jax.jit(train_scan, static_argnums=(5, 6),
                                   donate_argnums=donate_args,
                                   compiler_options=copts)

        def make_eval(net):
            apply_fn = self._net_apply(net)

            def eval_step(params, batch):
                _, metrics, _ = apply_fn(params, batch, train=False,
                                         mesh=mesh, compute_dtype=cdtype)
                return metrics
            return jax.jit(eval_step, compiler_options=copts)

        self.test_step = make_eval(self.test_net) if self.test_net else None
        self.val_step = make_eval(self.val_net) if self.val_net else None

        def make_eval_scan(net):
            apply_fn = self._net_apply(net)

            def eval_scan(params, batches):
                """Stacked eval batches → stacked metrics in ONE
                compiled program — the eval counterpart of train_scan
                (one dispatch per chunk instead of one per batch)."""
                def body(carry, batch):
                    _, metrics, _ = apply_fn(params, batch, train=False,
                                             mesh=mesh,
                                             compute_dtype=cdtype)
                    return carry, metrics
                _, ms = jax.lax.scan(body, None, batches)
                return ms
            return jax.jit(eval_scan, compiler_options=copts)

        # evaluate() looks the fused variant up by the step_fn handed to
        # it, so external callers passing custom fns keep per-batch eval
        self._eval_scans = {}
        if self.test_step is not None:
            self._eval_scans[id(self.test_step)] = \
                make_eval_scan(self.test_net)
        if self.val_step is not None:
            self._eval_scans[id(self.val_step)] = \
                make_eval_scan(self.val_net)

        def debug_step(params, batch, step, rng):
            """Per-layer activations + param grads for DebugInfo
            (neuralnet.cc:350-378 prints data AND grad norms)."""
            def loss_fn(p):
                loss, _, outputs = net_apply(
                    p, batch, rng=rng, train=True, mesh=mesh,
                    compute_dtype=cdtype, step=step)
                return loss, outputs
            (_, outputs), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            return outputs, grads

        self.debug_step = (jax.jit(debug_step, compiler_options=copts)
                           if self.cfg.debug else None)

    def compiled_scan(self, params, opt_state, batches, start_step,
                      rng, nsteps: int, stacked: bool = False):
        """The AOT-compiled fused-scan executable for this geometry,
        compiled at most once and cached.  Every consumer of the
        compiled program — the convergence tool's pre-timing warmup,
        CostWatch harvesting
        — goes through here, so diagnostics never re-lower+recompile a
        program the trainer already owns.  Call the returned
        executable with the five traced args only (statics are baked
        in): `compiled(params, opt_state, batches, step, rng)`."""
        leaves = jax.tree_util.tree_leaves(batches)
        key = (int(nsteps), bool(stacked),
               tuple((tuple(x.shape), str(x.dtype)) for x in leaves))
        got = self._aot_cache.get(key)
        if got is not None:
            perf.lookup_hit("train_scan")
            return got
        with obs.span("trainer.compile", nsteps=nsteps,
                      stacked=stacked), \
             perf.compile_span("train_scan",
                               geometry=f"steps={nsteps},"
                                        f"stacked={stacked}",
                               scope="train"):
            got = self.train_steps.lower(
                params, opt_state, batches, start_step, rng, nsteps,
                stacked).compile()
        perf.harvest("train_scan", got)
        self._aot_cache[key] = got
        return got

    # -- init --------------------------------------------------------------
    def init(self, seed: int = 0):
        rng = jax.random.PRNGKey(seed)
        params = self.train_net.init_params(rng)
        opt_state = self.updater.init(params)
        # MemoryWatch analytic components — on backends with no
        # memory_stats() (CPU) these ARE the HBM model
        perf.set_memory_tree("train_params", params, scope="train")
        perf.set_memory_tree("opt_state", opt_state, scope="train")
        return params, opt_state

    # -- input placement + feed pipeline knobs -----------------------------
    def _batch_place(self, batch):
        """Sharded device placement for ONE batch (batch dim 0): under
        a mesh the batch dim shards over "data" (token dims over "seq"
        for sequence-parallel nets); without a mesh the batch is left
        to the jitted step's own placement."""
        if self.mesh is None:
            return batch
        from ..parallel import (batch_shardings, seq_batch_shardings,
                                shard_batch)
        return shard_batch(self.mesh, batch,
                           shardings_fn=(seq_batch_shardings
                                         if self._uses_sp
                                         else batch_shardings))

    def _chunk_place(self, stacked):
        """Placement for a STACKED chunk (leading scan axis, batch at
        dim 1): sharded device_put under the mesh — the fix for
        jnp.stack landing chunks on the default device — or a plain
        async device_put without one (either way the transfer can
        overlap the previous chunk's scan)."""
        if self.mesh is None:
            return jax.device_put(stacked)
        from ..parallel import place_chunk
        return place_chunk(self.mesh, stacked,
                           seq_axis=("seq" if self._uses_sp else None))

    def _chunk_plan(self, start_step: int, scan_chunk: int):
        """Deterministic (start, length) chunk descriptors covering
        [start_step, train_steps) with the SAME cadence cuts the run
        loop computes — so the DeviceFeeder stages ahead without ever
        pulling a batch the loop won't train on, and a Supervisor
        restart (new start_step, fast-forwarded iterator) replays the
        identical consumption.  Pure in step (cadence config +
        elastic.sync_now are stateless predicates), so producer-thread
        evaluation is safe."""
        step = start_step
        while step < self.cfg.train_steps:
            n = self._next_chunk_len(step, scan_chunk)
            yield step, n
            step += n

    # -- cadence helpers (worker.h:127-160 semantics) ----------------------
    def _now(self, step, freq, after) -> bool:
        return freq > 0 and step >= after and step % freq == 0

    def display_now(self, step):
        return self._now(step, self.cfg.display_frequency,
                         self.cfg.display_after_steps)

    def test_now(self, step):
        return self._now(step, self.cfg.test_frequency,
                         self.cfg.test_after_steps)

    def validate_now(self, step):
        return self._now(step, self.cfg.validation_frequency,
                         self.cfg.validation_after_steps)

    # -- loops -------------------------------------------------------------
    def evaluate(self, params, data_iter: Iterator, steps: int,
                 step_fn, scan_chunk: int = 25,
                 feeder: Optional[bool] = None) -> Dict[str, float]:
        """Average metrics over `steps` eval batches.  When `step_fn` is
        one of the trainer's own eval steps, full chunks of `scan_chunk`
        batches run as ONE fused lax.scan dispatch (same amortization as
        the train loop's scan_chunk), consuming pre-staged chunks from a
        DeviceFeeder (staging overlaps the previous chunk's eval scan);
        the remainder and custom step_fns dispatch per batch.  Chunks
        and single batches both land sharded under the trainer's mesh.
        `feeder=False` stages inline instead (None: the feeder)."""
        perf = Performance()
        steps = max(steps, 1)
        scan_fn = getattr(self, "_eval_scans", {}).get(id(step_fn))
        done = 0
        chunk = min(steps, max(scan_chunk, 1))
        if scan_fn is not None and chunk > 1:
            def eat(ms):
                for i in range(chunk):
                    perf.update({k: v[i] for k, v in ms.items()})
            nchunks = steps // chunk
            if (feeder is None or feeder) and nchunks > 0:
                from ..data.feed import DeviceFeeder
                fd = DeviceFeeder(
                    data_iter, ((i * chunk, chunk)
                                for i in range(nchunks)),
                    place=self._chunk_place,
                    depth=FEEDER_DEPTH, capacity=chunk)
                try:
                    for _ in range(nchunks):
                        eat(jax.device_get(
                            scan_fn(params, fd.get().batches)))
                finally:
                    # stops the staging thread only — the remainder
                    # below keeps reading the same (untouched) iterator
                    fd.close()
                done = nchunks * chunk
            else:
                from ..data.feed import ChunkStager
                stager = ChunkStager(self._chunk_place, capacity=chunk)
                while steps - done >= chunk:
                    batches = [next(data_iter) for _ in range(chunk)]
                    eat(jax.device_get(
                        scan_fn(params, stager.stage(batches))))
                    done += chunk
        for _ in range(steps - done):
            batch = self._batch_place(next(data_iter))
            perf.update(jax.device_get(step_fn(params, batch)))
        return perf.averages()

    def _next_chunk_len(self, step: int, scan_chunk: int) -> int:
        """Longest chunk [step, step+n) that crosses no test/validate/
        checkpoint boundary (those must run on the host between compiled
        chunks); display steps may fall inside a chunk because their
        metrics come back stacked."""
        n = min(scan_chunk, self.cfg.train_steps - step)

        def next_event(freq, after):
            # smallest multiple of freq that is > step and >= after
            if freq <= 0:
                return None
            m = (step // freq + 1) * freq
            if m < after:
                m = -(-after // freq) * freq
            return m

        if self.elastic is not None:
            # chunks may not run past a sync step: the center exchange
            # happens on the host after that step completes
            freq = self.cfg.updater.sync_frequency
            warm = self.cfg.updater.warmup_steps
            e = (warm if step < warm
                 else warm + ((step - warm) // freq + 1) * freq)
            if self.elastic.sync_now(step):
                e = step
            n = min(n, e - step + 1)
        for freq, after in ((self.cfg.test_frequency,
                             self.cfg.test_after_steps),
                            (self.cfg.validation_frequency,
                             self.cfg.validation_after_steps)):
            e = next_event(freq, after)
            if e is not None:
                n = min(n, e - step)
        f = self.cfg.checkpoint_frequency
        if f > 0:
            # saves fire after steps s with (s+1) % f == 0; a chunk may
            # end on such a step but not run past it
            s_ck = ((step + 1 + f - 1) // f) * f - 1
            n = min(n, s_ck - step + 1)
        return max(n, 1)

    def run(self, params, opt_state,
            train_iter: Iterator,
            test_iter_factory: Optional[Callable[[], Iterator]] = None,
            val_iter_factory: Optional[Callable[[], Iterator]] = None,
            start_step: int = 0, seed: int = 0,
            hooks: Optional[List[Callable[[int, Dict], None]]] = None,
            workspace: Optional[str] = None, scan_chunk: int = 0,
            feeder: Optional[bool] = None, feeder_depth: int = 0):
        """The Worker::Run loop (worker.cc:98-106).  With `workspace`,
        checkpoints {params, opt_state, step} at checkpoint_frequency and
        on completion (the resume path the reference left as a TODO,
        worker.cc:65-67).

        `scan_chunk > 1` runs up to that many steps per device dispatch
        via the fused lax.scan program (train_steps); cadence events
        (test/validate/checkpoint/display) still fire at exactly the
        reference steps because chunks are cut at their boundaries.
        By default the chunked loop is OVERLAPPED: a DeviceFeeder
        thread stages chunk k+1 (stack into reusable buffers + sharded
        device_put) while chunk k's scan runs, and per-chunk metrics
        stay on device in a small ring, drained only at display/eval/
        checkpoint boundaries — the host never blocks on data or
        metrics between chunks (docs/PERFORMANCE.md).  `feeder=False`
        selects the synchronous fallback, which stages inline through
        the SAME sharded placement helper; `feeder_depth` bounds how
        many chunks the feeder runs ahead (0: FEEDER_DEPTH).  Both
        paths produce bit-identical trajectories (tests/test_feed.py).

        Preemption safety (the failure-recovery story the reference
        lacks, SURVEY.md §5 — any process death hangs its job): while a
        checkpoint manager is active, SIGTERM/SIGINT trigger a final
        snapshot at the current step and a clean early return, so a
        preempted TPU job resumes from where it stopped instead of its
        last cadence checkpoint."""
        if self.cfg.alg == "kContrastiveDivergence":
            return self.run_cd(params, opt_state, train_iter,
                               test_iter_factory=test_iter_factory,
                               val_iter_factory=val_iter_factory,
                               hooks=hooks, scan_chunk=scan_chunk,
                               start_step=start_step, seed=seed,
                               workspace=workspace)
        ckpt, interrupted, old_handlers = self._ckpt_guard(workspace)
        rng = jax.random.PRNGKey(seed ^ 0x5eed)
        if self.elastic is not None:
            # center seeds lazily from the first post-warmup params
            # inside maybe_sync (worker.cc:50-55 pushes AFTER warmup)
            self.log(f"async consistency tier active: "
                     f"{self.cfg.updater.param_type} sync_frequency="
                     f"{self.cfg.updater.sync_frequency} warmup="
                     f"{self.cfg.updater.warmup_steps}")
        history: List[Dict[str, float]] = []
        step = start_step
        chunked = bool(scan_chunk and scan_chunk > 1)
        fd = stager = None
        depth = (int(feeder_depth) if feeder_depth and feeder_depth > 0
                 else FEEDER_DEPTH)
        if chunked and (feeder is None or feeder):
            from ..data.feed import DeviceFeeder
            fd = DeviceFeeder(train_iter,
                              self._chunk_plan(start_step, scan_chunk),
                              place=self._chunk_place,
                              depth=depth,
                              capacity=scan_chunk)
        elif chunked:
            from ..data.feed import ChunkStager
            stager = ChunkStager(self._chunk_place, capacity=scan_chunk)

        # Deferred metric drain: per-chunk metrics stay device-resident
        # in `pending` and are fetched in order only at boundaries.
        # With the feeder the ring holds depth+1 chunks — the drain's
        # device_get doubles as backpressure, bounding in-flight
        # dispatches (and their staged input buffers) instead of letting
        # the host race arbitrarily far ahead.  Without it the ring is 1
        # (the synchronous per-chunk fetch, exactly the old loop).
        ring = depth + 1 if fd is not None else 1
        pending: List[tuple] = []
        staged_credit = [0.0]   # feeder stage_seconds already reported
        last_dbg = [None]       # newest single-batch view (debug/profile)

        def _drain():
            if not pending:
                return
            with obs.span("trainer.drain", chunks=len(pending)):
                _drain_chunks()

        def _drain_chunks():
            while pending:
                s0, n, md, stacked = pending.pop(0)
                tg = time.perf_counter()
                md = jax.device_get(md)   # device sync: train time
                self.timer.add("train", time.perf_counter() - tg)
                per_step = ([{k: v[i] for k, v in md.items()}
                             for i in range(n)] if stacked else [md])
                for i, m in enumerate(per_step):
                    s = s0 + i
                    if self.health is not None:
                        # classify as the ring drains — the probes rode
                        # the deferred metrics, so detection costs no
                        # extra host sync; a fatal verdict aborts the
                        # attempt BEFORE this step reaches hooks or a
                        # checkpoint (the save below drains first)
                        verdict = self.health.observe(s, m)
                        if verdict.status != "ok":
                            obs.emit_event(
                                "health.verdict", step=s,
                                status=verdict.status,
                                metric=verdict.metric,
                                value=(float(verdict.value)
                                       if verdict.value is not None
                                       else None),
                                fatal=verdict.fatal)
                        if verdict.fatal:
                            raise verdict.to_error()
                    self.perf.update(m)
                    if hooks:
                        for h in hooks:
                            self._call_hook(h, s, m)
                    if self.display_now(s):
                        self.log(f"step-{s}: {self.perf.to_string()}")
                        self.log(self.timer.to_string())
                        self.perf.reset()

        try:
            while step < self.cfg.train_steps:
                faults.maybe_fault("step.train")
                if interrupted:
                    _drain()   # hooks/logs for every trained step first
                    self.log(f"signal {interrupted[0]} received: checkpointing "
                             f"at step {step} and stopping")
                    self._save_checkpoint(ckpt, step, params, opt_state)
                    break
                if self.val_step and self.validate_now(step) and val_iter_factory:
                    _drain()
                    avg = self.evaluate(params, val_iter_factory(),
                                        self.cfg.validation_steps, self.val_step)
                    self.log(f"step-{step} validation: " + ", ".join(
                        f"{k} : {v:.6f}" for k, v in sorted(avg.items())))
                if self.test_step and self.test_now(step) and test_iter_factory:
                    _drain()
                    avg = self.evaluate(params, test_iter_factory(),
                                        self.cfg.test_steps, self.test_step)
                    self.log(f"step-{step} test: " + ", ".join(
                        f"{k} : {v:.6f}" for k, v in sorted(avg.items())))
                    history.append({"step": step, **avg})

                n = self._next_chunk_len(step, scan_chunk) if chunked else 1
                poison = self._grad_poison(n)
                t0 = time.perf_counter()
                if not chunked:
                    batch = next(train_iter)
                    t1 = time.perf_counter()
                    batch = self._batch_place(batch)
                    t2 = time.perf_counter()
                    with obs.span("trainer.chunk", start=step, steps=1):
                        params, opt_state, metrics = self.train_step(
                            params, opt_state, batch, step,
                            jax.random.fold_in(rng, step),
                            poison[0] if poison is not None else None)
                    t3 = time.perf_counter()
                    pending.append((step, 1, metrics, False))
                    last_dbg[0] = batch
                elif fd is not None:
                    with obs.span("feeder.wait", start=step):
                        # blocks only if staging is behind
                        chunk = fd.get()
                    t1 = time.perf_counter()
                    if chunk.start != step or chunk.length != n:
                        from ..data.feed import FeedError
                        raise FeedError(
                            f"feed plan diverged: staged chunk "
                            f"[{chunk.start}, +{chunk.length}) vs loop "
                            f"[{step}, +{n})")
                    t2 = t1
                    with obs.span("trainer.chunk", start=step, steps=n):
                        params, opt_state, metrics = self.train_steps(
                            params, opt_state, chunk.batches, step, rng,
                            n, True, poison)
                    t3 = time.perf_counter()
                    pending.append((step, n, metrics, True))
                    last_dbg[0] = jax.tree_util.tree_map(
                        lambda x: x[n - 1], chunk.batches)
                    # producer-side staging time since the last sample —
                    # real host work, but OFF the critical path
                    self.timer.add("stage",
                                   fd.stage_seconds - staged_credit[0])
                    staged_credit[0] = fd.stage_seconds
                else:
                    batches = [next(train_iter) for _ in range(n)]
                    t1 = time.perf_counter()
                    with obs.span("feeder.stage", start=step, steps=n):
                        stacked = stager.stage(batches)
                    t2 = time.perf_counter()
                    with obs.span("trainer.chunk", start=step, steps=n):
                        params, opt_state, metrics = self.train_steps(
                            params, opt_state, stacked, step, rng, n,
                            True, poison)
                    t3 = time.perf_counter()
                    pending.append((step, n, metrics, True))
                    last_dbg[0] = jax.tree_util.tree_map(
                        lambda x: x[n - 1], stacked)
                self.timer.add("wait", t1 - t0)
                if t2 > t1:
                    self.timer.add("stage", t2 - t1)
                self.timer.add("train", t3 - t2)
                self.timer.steps += n
                # first completed train dispatch: cold-start readiness
                # latch (first call wins; later chunks are no-ops)
                perf.mark_training_ready()
                perf.observe_step("train_scan", (t3 - t2) / max(n, 1))
                if (len(pending) >= ring
                        or any(self.display_now(step + i)
                               for i in range(n))):
                    _drain()
                if (self.debug_step is not None
                        and any(self.display_now(step + i) for i in range(n))):
                    # debug norms reflect the post-chunk params, so label
                    # them with the chunk's last step, not a mid-chunk one
                    s_dbg = step + n - 1
                    outs, grads = self.debug_step(
                        params, last_dbg[0], s_dbg,
                        jax.random.fold_in(rng, s_dbg))
                    self.log(f"step-{s_dbg} debug:\n" +
                             self.train_net.debug_info(params, outs, grads))
                if self.elastic is not None:
                    # chunks are cut so at most the LAST step is a sync step
                    params = self.elastic.maybe_sync(
                        step + n - 1, params,
                        rng=jax.random.fold_in(rng, step + n - 1))
                last = step + n - 1
                if (ckpt is not None and self.cfg.checkpoint_frequency > 0
                        and last >= self.cfg.checkpoint_after_steps
                        and (last + 1) % self.cfg.checkpoint_frequency == 0):
                    # drain BEFORE the save: every hook/metric below the
                    # snapshot step has fired (and the health monitor
                    # has classified every step the snapshot contains),
                    # so a crash-and-restore never leaves a hook gap —
                    # and a poisoned state never reaches the save
                    _drain()
                    self._save_checkpoint(ckpt, last + 1, params,
                                          opt_state)
                step += n
            _drain()
        finally:
            # an exception mid-loop (injected fault, data failure) must
            # not leave our signal handlers installed in the
            # supervisor's process, nor the feed thread running
            if fd is not None:
                fd.close()
            self._ckpt_unguard(old_handlers)
        if (ckpt is not None and not interrupted
                and self.cfg.train_steps > start_step):
            self._save_checkpoint(ckpt, self.cfg.train_steps, params,
                                  opt_state)
        return params, opt_state, history

    def _grad_poison(self, n: int):
        """Consult the `step.grad` fault site once per step about to be
        dispatched; an (n,) float32 scale vector when any fires, else
        None (the common case — the compiled program is untouched and
        no extra operand is transferred)."""
        if faults.active() is None:
            return None
        from ..utils.health import SPIKE_SCALE
        codes = [faults.maybe_fault("step.grad") for _ in range(n)]
        if not any(codes):
            return None
        import numpy as np
        scale = {"nan": float("nan"), "spike": SPIKE_SCALE}
        return np.asarray([scale.get(c, 1.0) for c in codes], np.float32)

    def _call_hook(self, hook, step, metrics) -> None:
        """User hooks are observers, not training logic: one raising
        must not look like a step failure (it would burn a Supervisor
        restart) — log and continue."""
        try:
            hook(step, metrics)
        except Exception as e:  # noqa: BLE001 — any user-hook failure
            name = getattr(hook, "__name__", repr(hook))
            self.log(f"warning: user hook {name} raised at step {step} "
                     f"({type(e).__name__}: {e}); continuing")

    def _save_checkpoint(self, ckpt, step, params, opt_state) -> bool:
        """Cadence/final/signal snapshot, gated on the health verdict:
        a window the monitor classified as fatal is REFUSED (restoring
        it would faithfully resume the divergence), a suspect (spike)
        window saves but carries its verdict in MANIFEST.json so
        `skip_unhealthy` restores can walk past it."""
        if ckpt is None:
            return False
        if self.health is None:
            ckpt.save(step, *self._ckpt_state(params, opt_state))
            self._publish(step, None)
            return True
        if not self.health.ok_to_save():
            rec = self.health.snapshot_health()
            self.log(f"health: refusing checkpoint at step {step} "
                     f"(verdict {rec['verdict']!r} — restoring this "
                     f"snapshot would resume the divergence)")
            obs.emit_event("ckpt.refused", step=step,
                           verdict=rec["verdict"])
            return False
        rec = self.health.snapshot_health()
        ckpt.save(step, *self._ckpt_state(params, opt_state),
                  health=rec)
        self.health.mark_snapshot()
        self._publish(step, rec.get("verdict"))
        return True

    def _publish(self, step: int, verdict) -> None:
        """Fire the post-save publication hook (`on_checkpoint`).
        Runs after the snapshot (and its manifest verdict) is on disk
        — the point where a serving tier may trust the step.  Hook
        failures are logged observer-style, exactly like user hooks:
        publication is telemetry for the loop, not training logic."""
        hook = self.on_checkpoint
        if hook is None:
            return
        try:
            hook(step, verdict)
        except Exception as e:  # noqa: BLE001 — observer, not logic
            self.log(f"warning: checkpoint publish hook raised at "
                     f"step {step} ({type(e).__name__}: {e}); "
                     f"continuing")

    def apply_lr_backoff(self, factor: float) -> float:
        """Scale the effective learning rate by `factor` (the
        Supervisor's divergence-rescue knob) and rebuild the compiled
        steps — the schedule value is baked in at trace time, so the
        jitted programs must be re-traced for the scale to apply.
        Returns the cumulative scale."""
        self.updater.lr_scale *= float(factor)
        self._build_steps(self._donate)
        self.log(f"health: learning-rate backoff x{factor:g} applied "
                 f"(cumulative scale {self.updater.lr_scale:g})")
        return self.updater.lr_scale

    def _ckpt_state(self, params, opt_state):
        """Checkpoint payload: padded-storage params/opt state (uneven
        partition dims, parallel/partition.py pad_params) sliced back
        to spec shapes so checkpoints stay mesh-portable — a restore
        under any mesh (or none) re-pads via shard_params."""
        net = self.train_net
        return (net.unpad_params(params),
                {k: net.unpad_params(t) for k, t in opt_state.items()})

    def _ckpt_guard(self, workspace):
        """(ckpt_manager, interrupted, old_handlers) — the shared
        checkpoint + SIGTERM/SIGINT machinery of run()/run_cd().  Pair
        with _ckpt_unguard(old_handlers)."""
        ckpt = None
        if workspace and self.cfg.checkpoint_frequency > 0:
            from ..utils.checkpoint import CheckpointManager
            ckpt = CheckpointManager(workspace, log_fn=self.log)
        interrupted: List[int] = []
        old_handlers: Dict[Any, Any] = {}
        if ckpt is not None:
            import signal

            def _on_signal(signum, frame):
                interrupted.append(signum)

            for sig in (signal.SIGTERM, signal.SIGINT):
                try:
                    old_handlers[sig] = signal.signal(sig, _on_signal)
                except ValueError:   # non-main thread: no signal hooks
                    break
        return ckpt, interrupted, old_handlers

    @staticmethod
    def _ckpt_unguard(old_handlers) -> None:
        if old_handlers:
            import signal
            for sig, h in old_handlers.items():
                signal.signal(sig, h)

    def run_cd(self, params, opt_state, train_iter: Iterator,
               test_iter_factory=None, val_iter_factory=None,
               hooks: Optional[List[Callable[[int, Dict], None]]] = None,
               scan_chunk: int = 0,
               start_step: int = 0, seed: int = 0,
               workspace: Optional[str] = None):
        """kContrastiveDivergence training (ModelProto.alg,
        model.proto:40-44): greedy layer-wise CD-k over the net's kRBM
        layers.  The training budget splits evenly across RBMs (classic
        greedy stacking: each trains on the hidden probabilities of the
        ones before it); the parser prefix and each RBM's Gibbs chain
        run in one jitted step, updates through the ordinary Updater.
        RBMProto.persistent runs PCD: the Gibbs chain continues from
        the previous step's chain end instead of the data batch.
        Checkpoint cadence and SIGTERM/SIGINT snapshots behave exactly
        as in run() (PCD chain state is per-run and restarts from the
        data on resume — standard PCD practice)."""
        import functools

        from ..models.rbm import cd_grads

        net = self.train_net
        rbm_names = [n for n in net.topo
                     if getattr(net.layers[n], "is_rbm", False)]
        if not rbm_names:
            raise ValueError("alg kContrastiveDivergence needs at least "
                             "one kRBM layer in the net")
        mesh, cdtype = self.mesh, self.compute_dtype
        updater, mults = self.updater, self.multipliers

        @functools.partial(jax.jit, static_argnums=(4,))
        def cd_step(params, opt_state, batch, rng, idx, step, chain):
            name = rbm_names[idx]
            layer = net.layers[name]
            prefix = net.topo[:net.topo.index(name)]
            _, _, outputs = net.apply(params, batch, train=False,
                                      mesh=mesh, compute_dtype=cdtype,
                                      layer_subset=prefix)
            v = outputs[layer.cfg.srclayers[0]]
            v = v.reshape(v.shape[0], -1).astype(jnp.float32)
            grads, recon, chain_end = cd_grads(
                layer.cd_view(params), v, rng, k=layer.cd_k,
                persistent=chain)
            named = layer.named_grads(grads)
            sub_p = {k: params[k] for k in named}
            sub_s = {sk: {k: sv[k] for k in named}
                     for sk, sv in opt_state.items()}
            sub_m = {k: mults[k] for k in named}
            new_p, new_s = updater.update(step, named, sub_p, sub_s,
                                          multipliers=sub_m)
            params = {**params, **new_p}
            opt_state = {sk: {**opt_state[sk], **new_s[sk]}
                         for sk in opt_state}
            return params, opt_state, recon, chain_end

        if scan_chunk and scan_chunk > 1:
            self.log("warning: scan_chunk is not supported for CD "
                     "training (host-side greedy phase switching); "
                     "running per-step")
        for nm, it, step_fn in (("test", test_iter_factory, self.test_step),
                                ("validation", val_iter_factory,
                                 self.val_step)):
            if it is not None and step_fn is None:
                self.log(f"warning: {nm} iterator supplied but this CD "
                         f"net built no {nm} eval step (no loss layer "
                         f"in that phase); skipping {nm} evaluation "
                         "(reconstruction error is the training metric)")

        total = self.cfg.train_steps
        n = len(rbm_names)
        rng = jax.random.PRNGKey(seed ^ 0xCD)
        history: List[Dict[str, float]] = []
        chains: Dict[int, Any] = {}   # PCD chain per RBM index
        ckpt, interrupted, old_handlers = self._ckpt_guard(workspace)
        step = start_step
        try:
            for step in range(start_step, total):
                faults.maybe_fault("step.train")
                if interrupted:
                    self.log(f"signal {interrupted[0]} received: "
                             f"checkpointing at step {step} and stopping")
                    ckpt.save(step, *self._ckpt_state(params, opt_state))
                    break
                if (self.test_step and self.test_now(step)
                        and test_iter_factory):
                    avg = self.evaluate(params, test_iter_factory(),
                                        self.cfg.test_steps, self.test_step)
                    self.log(f"step-{step} test: " + ", ".join(
                        f"{k} : {v:.6f}" for k, v in sorted(avg.items())))
                if (self.val_step and self.validate_now(step)
                        and val_iter_factory):
                    avg = self.evaluate(params, val_iter_factory(),
                                        self.cfg.validation_steps,
                                        self.val_step)
                    self.log(f"step-{step} validation: " + ", ".join(
                        f"{k} : {v:.6f}" for k, v in sorted(avg.items())))
                idx = min(step * n // max(total, 1), n - 1)
                layer = net.layers[rbm_names[idx]]
                batch = next(train_iter)
                params, opt_state, recon, chain_end = cd_step(
                    params, opt_state, batch, jax.random.fold_in(rng, step),
                    idx, step, chains.get(idx) if layer.persistent else None)
                if layer.persistent:
                    chains[idx] = chain_end
                self.perf.update({"recon": recon})
                if hooks:
                    m_cd = {"recon": float(recon), "rbm": idx}
                    for h in hooks:
                        self._call_hook(h, step, m_cd)
                if self.display_now(step):
                    self.log(f"step-{step} cd[{rbm_names[idx]}]: "
                             f"{self.perf.to_string()}")
                    history.append({"step": step, "rbm": idx,
                                    **self.perf.averages()})
                    self.perf.reset()
                if (ckpt is not None and self.cfg.checkpoint_frequency > 0
                        and step >= self.cfg.checkpoint_after_steps
                        and (step + 1) % self.cfg.checkpoint_frequency == 0):
                    ckpt.save(step + 1, *self._ckpt_state(params, opt_state))
        finally:
            self._ckpt_unguard(old_handlers)
        if ckpt is not None and not interrupted and total > start_step:
            ckpt.save(total, *self._ckpt_state(params, opt_state))
        return params, opt_state, history

    def resume(self, params, opt_state, workspace: str,
               skip_unhealthy: bool = False):
        """Restore the latest snapshot (Worker::Resume, finally real).
        Returns (params, opt_state, start_step).  `skip_unhealthy`
        walks back past snapshots whose recorded health verdict is not
        "ok" (the Supervisor's divergence rescue — restore the last
        numerically GOOD state, not the last readable one).

        Checkpoints are saved spec-shaped (_ckpt_state unpads the
        pad-to-divisible storage of uneven partition dims), so the
        restore template must be spec-shaped too — the caller may hand
        us padded, sharded live arrays (main.py resumes AFTER
        shard_params).  After the restore, re-pad + re-shard under the
        trainer's mesh so the padded sharded layout survives a
        resume."""
        from ..utils.checkpoint import CheckpointManager
        net = self.train_net
        # abstract template: checkpoint-shaped (spec, unpadded) leaves
        # WITHOUT materializing sliced copies of the live state — at
        # restore time the live padded arrays, a concrete template, and
        # the restored arrays would otherwise coexist.  Each leaf
        # carries an explicit sharding (the live array's where the
        # shapes match; replicated for pad-sliced leaves, re-sharded
        # below) so the restore never depends on the sharding recorded
        # in the checkpoint — which may come from a different topology.
        tpl_p, tpl_o = jax.eval_shape(self._ckpt_state, params, opt_state)

        def shard_tpl(tpl, live):
            rep = None
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                rep = NamedSharding(self.mesh, PartitionSpec())
            out = {}
            for k, t in tpl.items():
                arr = live.get(k)
                sh = (arr.sharding
                      if (hasattr(arr, "sharding")
                          and tuple(arr.shape) == tuple(t.shape))
                      else rep)
                out[k] = (jax.ShapeDtypeStruct(t.shape, t.dtype,
                                               sharding=sh)
                          if sh is not None else t)
            return out

        tpl_p = shard_tpl(tpl_p, params)
        tpl_o = {k: shard_tpl(t, opt_state.get(k, {}))
                 for k, t in tpl_o.items()}
        restored = CheckpointManager(workspace, log_fn=self.log).restore(
            template={"params": tpl_p, "opt_state": tpl_o},
            skip_unhealthy=skip_unhealthy)
        if restored is None:
            return params, opt_state, 0
        rp, ro, step = restored
        if self.mesh is not None:
            from ..parallel import shard_opt_state, shard_params
            rp = shard_params(self.mesh, net, rp)
            ro = shard_opt_state(self.mesh, net, ro)
        return rp, ro, step
