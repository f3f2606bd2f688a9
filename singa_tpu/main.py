"""CLI entry point — the reference `singa` binary's flag surface.

Reference: /root/reference/src/main.cc:13-18 — flags -procsID, -hostfile,
-cluster_conf, -model_conf.  The reference forks Server/Worker
personalities by process id (main.cc:49-55); on TPU there is no
parameter-server personality (gradient aggregation is a compiled psum),
so every process is a worker and -procsID/-hostfile map to
jax.distributed process coordinates for multi-host runs.

Usage:
    python -m singa_tpu.main -model_conf examples/mnist/conv.conf \
        -cluster_conf examples/mnist/cluster.conf [-procsID 0] [-hostfile h]

Serving (the inference tier, singa_tpu/serve/):
    python -m singa_tpu.main serve -model_conf lm.conf \
        --workspace ws [--port 8000] [--serve_spec 'buckets=4x16/8x32,...']
follows the trainer's checkpoints in the workspace (hot-reload) and
serves /generate, /predict, /stats, /metrics, /healthz over stdlib
HTTP.  With `cb=on` in the serve spec, /generate runs continuous
batching over a paged KV cache and streams tokens as produced when
the request body carries `"stream": true` (docs/SERVING.md).
`serve --fleet N` runs N pinned engine workers behind a
health-driven router with canary rollout/auto-rollback;
`serve --fleet_hostfile h` adopts already-running `serve --pinned`
processes as the fleet.

Closed-loop pipeline (docs/PIPELINE.md):
    python -m singa_tpu.main pipeline -model_conf lm.conf \
        --workspace ws --synthetic [--fleet 2] [--smoke 50]
runs the supervised trainer AND the serving fleet concurrently against
one workspace: every health-blessed checkpoint is canaried and
promoted to traffic within bounded lag, and a DIVERGED step is never
served by more than the canary.  All subcommands take `--obs on
[--obs_spec ...]` for the unified telemetry layer
(docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import argparse
import os
import sys

from . import obs
from .config import load_cluster_config, load_model_config
from .core.trainer import Trainer


def make_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="singa_tpu",
        description="TPU-native SINGA-capability training runtime")
    # single-dash long flags, gflags style (main.cc:13-18)
    ap.add_argument("-model_conf", "--model_conf", required=True)
    ap.add_argument("-cluster_conf", "--cluster_conf", default=None)
    ap.add_argument("-procsID", "--procsID", type=int, default=0)
    ap.add_argument("-hostfile", "--hostfile", default=None)
    ap.add_argument("-v", type=int, default=0, help="verbosity (glog style)")
    # TPU-native extras
    ap.add_argument("--synthetic", action="store_true",
                    help="use a synthetic learnable dataset (no egress env)")
    ap.add_argument("--steps", type=int, default=None,
                    help="override ModelProto.train_steps")
    ap.add_argument("--batchsize", type=int, default=0,
                    help="override every data layer's batchsize")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="resume from latest checkpoint in the workspace")
    ap.add_argument("--max-restarts", "--max_restarts", type=int,
                    dest="max_restarts", default=0,
                    help="supervise the run: on a step/pipeline failure "
                         "restore the latest valid checkpoint, replay "
                         "data, and retry with backoff up to N times "
                         "(0 = unsupervised; see docs/FAULT_TOLERANCE.md)")
    ap.add_argument("--fault_spec", default=None,
                    help="deterministic fault injection: comma-separated "
                         "site@visit[:kind] entries, e.g. "
                         "'step.train@7:preempt,ckpt.save@1:torn' "
                         "(sites/kinds in singa_tpu/utils/faults.py)")
    ap.add_argument("--health", choices=("on", "off"), default="on",
                    help="numeric-health sentinel: device-side "
                         "loss/grad-norm/update-ratio probes fused into "
                         "the train step, host-side OK/SPIKE/NONFINITE/"
                         "DIVERGED classification, checkpoint verdicts, "
                         "and (under --max-restarts) divergence rescue "
                         "(see docs/FAULT_TOLERANCE.md)")
    ap.add_argument("--health_spec", default=None,
                    help="health thresholds + rescue policy: comma-"
                         "separated key=value entries over the "
                         "HealthSpec fields, e.g. 'grad_norm_max=1e4,"
                         "spike_mad=8,patience=3,blame_batches=1,"
                         "lr_backoff=0.5' "
                         "(singa_tpu/utils/health.py)")
    ap.add_argument("--workspace", default=None,
                    help="override ClusterProto.workspace")
    ap.add_argument("--scan_chunk", type=int, default=0,
                    help="run up to N steps per device dispatch (fused "
                         "lax.scan inner loop; cadence events still fire "
                         "at their exact steps)")
    ap.add_argument("--feeder", choices=("auto", "on", "off"),
                    default="auto",
                    help="overlapped host/device feed pipeline for the "
                         "chunked loop: a background thread stages the "
                         "next chunk (stack + sharded device_put) while "
                         "the current one trains (auto = on when "
                         "scan_chunk > 1; see docs/PERFORMANCE.md)")
    ap.add_argument("--feeder_depth", "--feeder-depth", type=int,
                    dest="feeder_depth", default=0,
                    help="staged chunks the feeder may run ahead "
                         "(0 = 2)")
    _add_obs_flags(ap)
    return ap


def _add_obs_flags(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--obs", choices=("on", "off"), default="off",
                    help="unified telemetry: span tracing (Chrome "
                         "trace JSON, Perfetto-loadable), a metrics "
                         "registry, and a structured JSONL event log "
                         "(see docs/OBSERVABILITY.md); artifacts "
                         "default under <workspace>/obs/")
    ap.add_argument("--obs_spec", default=None,
                    help="telemetry config: comma-separated key=value "
                         "over the ObsSpec fields, e.g. "
                         "'trace=/tmp/t.json,events=/tmp/e.jsonl,"
                         "metrics_period_s=5,max_spans=100000,"
                         "trace_ring=65536,max_events_mb=64,"
                         "process=worker-0,sample=tail,"
                         "sample_slow_ms=250,flightrec=/tmp/fr' "
                         "(singa_tpu/obs/__init__.py)")


def _obs_enable(args, workspace=None) -> bool:
    """Arm the process-global telemetry session from --obs/--obs_spec.
    Bare `--obs on` defaults both artifacts under `<workspace>/obs/`
    (`./obs/` without a workspace).  Returns True when a session was
    installed — the caller owns the matching `obs.disable()`."""
    if getattr(args, "obs", "off") != "on":
        if getattr(args, "obs_spec", None):
            obs.get_logger("main")("warning: --obs_spec given with "
                                   "--obs off; telemetry stays "
                                   "disabled")
        return False
    spec = obs.ObsSpec.parse(getattr(args, "obs_spec", None))
    base = os.path.join(workspace or ".", "obs")
    if not spec.trace:
        spec.trace = os.path.join(base, "trace.json")
    if not spec.events:
        spec.events = os.path.join(base, "events.jsonl")
    if not spec.flightrec:
        # post-mortem flight recorder armed by default: triggered
        # dumps land next to the other obs artifacts
        spec.flightrec = os.path.join(base, "flightrec")
    obs.enable(spec)
    return True


def make_serve_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="singa_tpu serve",
        description="TPU-native inference serving tier "
                    "(docs/SERVING.md): micro-batched request "
                    "scheduler over compiled bucket programs, with "
                    "checkpoint hot-reload")
    ap.add_argument("-model_conf", "--model_conf", required=True)
    ap.add_argument("--workspace", default=None,
                    help="checkpoint workspace to serve from and "
                         "hot-reload against (the trainer's "
                         "--workspace); omit to serve fresh-init "
                         "params (smoke/dev only)")
    ap.add_argument("--serve_spec", default=None,
                    help="serving config: comma-separated key=value "
                         "over the ServeSpec fields, buckets as "
                         "BxP '/' entries, e.g. 'buckets=1x16/4x32,"
                         "max_new_tokens=32,eos_id=2,"
                         "batch_window_s=0.005'; cb=on enables "
                         "continuous batching over the paged KV cache "
                         "(cb_slots, cb_block_len, cb_blocks, "
                         "cb_prompt_cap) with streaming POST "
                         "/generate (singa_tpu/serve/engine.py, "
                         "docs/SERVING.md)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000,
                    help="HTTP port (0 = ephemeral)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", type=int, default=0, metavar="N",
                    help="serve N synthetic in-process requests, "
                         "print the stats snapshot as JSON, and exit "
                         "(no HTTP listener)")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="serving fleet: spawn N in-process pinned "
                         "engine workers behind a health-driven "
                         "router with canary rollout/auto-rollback "
                         "(docs/SERVING.md)")
    ap.add_argument("--fleet_hostfile", default=None,
                    help="adopt an already-running fleet instead of "
                         "spawning one: one engine host[:port] per "
                         "line (each a `serve --pinned` process); "
                         "mutually exclusive with --fleet")
    ap.add_argument("--fleet_spec", default=None,
                    help="router config: comma-separated key=value "
                         "over the RouterSpec fields, e.g. "
                         "'probe_period_s=0.25,quarantine_after=2,"
                         "readmit_base_s=0.25' "
                         "(singa_tpu/serve/router.py)")
    ap.add_argument("--rollout_spec", default=None,
                    help="rollout config: comma-separated key=value "
                         "over the RolloutSpec fields, e.g. "
                         "'window_s=2,min_requests=10,p95_ratio=3' "
                         "(singa_tpu/serve/fleet.py)")
    ap.add_argument("--autoscale_spec", default=None,
                    help="enable the SLO-driven autoscaler over the "
                         "fleet: comma-separated key=value over the "
                         "AutoScaleSpec fields, e.g. 'slo_p95_ms=200,"
                         "max_shed_rate=0.02,min_engines=1,"
                         "max_engines=4,cooldown_s=5,window_s=10' "
                         "(singa_tpu/serve/autoscale.py; needs "
                         "--fleet, not --fleet_hostfile)")
    ap.add_argument("--tenant_spec", default=None,
                    help="multi-tenant QoS envelopes: ';'-separated "
                         "tenants, each 'name,key=value,...' over the "
                         "TenantSpec fields, e.g. 'a,queue_frac=0.25,"
                         "budget_floor=4;b,queue_frac=0.5' — quotas, "
                         "retry-budget floors, and brownout overrides "
                         "enforced per tenant at admission "
                         "(singa_tpu/serve/tenancy.py, "
                         "docs/SERVING.md); unnamed clients ride the "
                         "unquota'd `default` tenant")
    ap.add_argument("--pinned", action="store_true",
                    help="run this engine as a fleet member: never "
                         "self-reload; only the rollout controller's "
                         "POST /admin/reload moves the served params")
    ap.add_argument("--standby", action="store_true",
                    help="start the fleet router as a warm STANDBY "
                         "over the same --workspace as the primary: "
                         "engines load and warm but the data plane "
                         "stays 503 until POST /admin/promote claims "
                         "the next epoch, fences the primary's "
                         "session WAL, and replays it "
                         "(docs/SERVING.md, control-plane "
                         "durability; needs --fleet/--fleet_hostfile)")
    ap.add_argument("--wire", action="store_true",
                    help="start the zero-copy binary framed listener "
                         "beside the HTTP frontend (ephemeral port "
                         "unless --wire_port): /healthz advertises "
                         "it and transport=auto fleet routers "
                         "upgrade this engine's data plane to it, "
                         "falling back to HTTP on any wire failure "
                         "(singa_tpu/serve/wire.py, docs/SERVING.md)")
    ap.add_argument("--wire_port", type=int, default=0,
                    help="binary transport port (0 = ephemeral; "
                         "implies --wire when nonzero)")
    ap.add_argument("--transport", default="auto",
                    choices=("auto", "http"),
                    help="fleet data plane for adopted (hostfile) "
                         "engines: auto = negotiate binary per "
                         "engine via /healthz wire_port with "
                         "automatic HTTP fallback; http = pin the "
                         "debug surface (singa_tpu/serve/wire.py)")
    ap.add_argument("--fault_spec", default=None,
                    help="deterministic fault injection over the "
                         "serve.* and fleet.* sites "
                         "(singa_tpu/utils/faults.py)")
    _add_obs_flags(ap)
    return ap


def serve_main(argv) -> int:
    """The `serve` subcommand: build the inference net from the model
    config, load the latest healthy checkpoint, and serve."""
    import json as _json

    args = make_serve_argparser().parse_args(argv)
    if args.fleet and args.fleet_hostfile:
        print("error: --fleet and --fleet_hostfile are mutually "
              "exclusive (spawn a fleet OR adopt one)",
              file=sys.stderr)
        return 2
    if args.standby and not (args.fleet or args.fleet_hostfile):
        print("error: --standby is a fleet-router mode (needs "
              "--fleet or --fleet_hostfile)", file=sys.stderr)
        return 2
    from .utils.faults import FaultSchedule, inject
    schedule = (FaultSchedule.parse(args.fault_spec, seed=args.seed)
                if args.fault_spec else None)
    log = obs.get_logger("serve")
    obs_on = _obs_enable(args, args.workspace)
    try:
        model = load_model_config(args.model_conf)
        from .data import discover_input_shapes
        input_shapes = discover_input_shapes(model, force_synthetic=True)
        trainer = Trainer(model, input_shapes, log_fn=lambda s: None)
        # the inference net: test phase when the config defines one,
        # else the train net (same params either way)
        net = trainer.test_net or trainer.train_net

        import jax

        _log_device(log)
        from .serve import InferenceEngine, InferenceServer, ServeSpec
        spec = (ServeSpec.parse(args.serve_spec) if args.serve_spec
                else ServeSpec())
        # fresh-init fallback so a checkpoint-less workspace still
        # serves (engine.load prefers any restorable healthy snapshot)
        fallback = net.init_params(jax.random.PRNGKey(args.seed))
        if args.fleet or args.fleet_hostfile:
            return _fleet_main(args, net, spec, fallback, schedule,
                               log)
        engine = InferenceEngine(net, spec, workspace=args.workspace,
                                 params=fallback, log_fn=log,
                                 pinned=args.pinned)
        reg = obs.registry()
        if reg is not None:
            engine.stats.register_into(reg)
        from .serve import TenantRegistry
        tenancy = (TenantRegistry.parse(args.tenant_spec)
                   if args.tenant_spec else None)

        with inject(schedule):
            if schedule is not None:
                log(f"fault injection active: {args.fault_spec} "
                    f"(seed {args.seed})")
            server = InferenceServer(engine, host=args.host,
                                     port=args.port,
                                     http=(args.smoke == 0),
                                     tenancy=tenancy, log_fn=log,
                                     wire_on=(args.smoke == 0
                                              and (args.wire
                                                   or args.wire_port
                                                   > 0)),
                                     wire_port=args.wire_port)
            server.start()
            if engine.params_step < 0:
                log("warning: serving fresh-init params (no "
                    "restorable checkpoint in the workspace)")
            try:
                if args.smoke > 0:
                    import numpy as np
                    rng = np.random.default_rng(args.seed)
                    vocab = _serve_vocab(net)
                    cap = (spec.cb_max_prompt_len if spec.cb_on
                           else spec.max_prompt_len)
                    for i in range(args.smoke):
                        plen = int(rng.integers(1, cap + 1))
                        prompt = rng.integers(0, vocab,
                                              plen).astype("int32")
                        out = server.generate(prompt)
                        shape = (f"finish {out['finish']}"
                                 if "finish" in out
                                 else f"bucket {out.get('bucket')}")
                        log(f"smoke {i}: plen={plen} -> "
                            f"{len(out['tokens'])} tokens "
                            f"(step {out['step']}, {shape})")
                    print(_json.dumps(server.snapshot()))
                    return 0
                import time
                while True:
                    time.sleep(3600)
            except KeyboardInterrupt:
                log("serve: shutting down")
                print(_json.dumps(server.snapshot()))
                return 0
            finally:
                server.stop()
    finally:
        if obs_on:
            obs.disable()


def _fleet_main(args, net, spec, fallback, schedule, log) -> int:
    """The fleet branch of `serve`: N pinned engine workers behind a
    `Router` + `RolloutController`, fronted by `FleetServer` (or
    driven in-process under --smoke)."""
    import json as _json

    from .serve import (AutoScaler, AutoScaleSpec, EngineFleet,
                        FleetServer, RolloutSpec, RouterSpec,
                        TenantRegistry)
    from .utils.faults import inject

    router_spec = RouterSpec.parse(args.fleet_spec)
    rollout_spec = RolloutSpec.parse(args.rollout_spec)
    autoscale_spec = (AutoScaleSpec.parse(args.autoscale_spec)
                      if args.autoscale_spec is not None else None)
    tenancy = (TenantRegistry.parse(args.tenant_spec)
               if args.tenant_spec else None)
    if args.pinned:
        log("warning: --pinned is a member flag; the fleet's workers "
            "are always pinned — ignoring")
    with inject(schedule):
        if schedule is not None:
            log(f"fault injection active: {args.fault_spec} "
                f"(seed {args.seed})")
        if args.fleet_hostfile:
            fleet = EngineFleet.from_hostfile(
                args.fleet_hostfile, workspace=args.workspace,
                router_spec=router_spec, rollout_spec=rollout_spec,
                tenancy=tenancy, standby=args.standby, log_fn=log,
                transport=args.transport)
        else:
            fleet = EngineFleet.local(
                net, spec, args.fleet, workspace=args.workspace,
                params=fallback, router_spec=router_spec,
                rollout_spec=rollout_spec, tenancy=tenancy,
                standby=args.standby, log_fn=log)
        scaler = None
        if autoscale_spec is not None and args.standby:
            log("warning: --autoscale_spec ignored on a standby "
                "router (no traffic signal to scale on until "
                "promote)")
        elif autoscale_spec is not None:
            if not fleet.can_grow():
                log("warning: --autoscale_spec on an adopted "
                    "(hostfile) fleet can only scale DOWN — spawning "
                    "remote workers is deployment's job")
            scaler = AutoScaler(fleet, spec=autoscale_spec, log_fn=log)
            # cooldown/streak survive a router restart: without this a
            # crash forgets the flap damping and can oscillate
            fleet.add_state_provider("autoscale", scaler.export_state,
                                     scaler.restore_state)
        reg = obs.registry()
        if reg is not None:
            fleet.router.stats.register_into(reg)
            if scaler is not None:
                scaler.register_into(reg)
        fleet.start()
        if scaler is not None:
            scaler.start()
        try:
            if args.smoke > 0:
                import numpy as np
                rng = np.random.default_rng(args.seed)
                vocab = _serve_vocab(net)
                for i in range(args.smoke):
                    plen = int(rng.integers(1, spec.max_prompt_len + 1))
                    prompt = rng.integers(0, vocab,
                                          plen).astype("int32")
                    out = fleet.generate(prompt)
                    log(f"smoke {i}: plen={plen} -> "
                        f"{len(out['tokens'])} tokens on "
                        f"{out['engine']} (step {out['step']})")
                snap = fleet.snapshot()
                if scaler is not None:
                    snap["autoscale"] = scaler.snapshot()
                print(_json.dumps(snap))
                return 0
            front = FleetServer(fleet, host=args.host, port=args.port,
                                log_fn=log)
            front.start()
            try:
                import time
                while True:
                    time.sleep(3600)
            except KeyboardInterrupt:
                log("fleet: shutting down")
                print(_json.dumps(fleet.snapshot()))
                return 0
            finally:
                front.stop()
        finally:
            if scaler is not None:
                scaler.stop()
            fleet.stop()


def _serve_vocab(net) -> int:
    for layer in net.layers.values():
        for attr in ("vocab_size", "vocab"):
            v = getattr(layer, attr, None)
            if isinstance(v, int) and v > 1:
                return v
    return 256


def make_pipeline_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="singa_tpu pipeline",
        description="closed-loop train-and-serve (docs/PIPELINE.md): "
                    "a supervised trainer and a serving fleet run "
                    "concurrently against ONE workspace — every "
                    "health-blessed checkpoint is canaried and "
                    "promoted to traffic within bounded lag, and a "
                    "DIVERGED step is never served by more than the "
                    "canary")
    ap.add_argument("-model_conf", "--model_conf", required=True)
    ap.add_argument("--workspace", required=True,
                    help="the shared checkpoint workspace — the "
                         "trainer publishes into it, the fleet "
                         "promotes out of it")
    ap.add_argument("--steps", type=int, default=None,
                    help="override ModelProto.train_steps")
    ap.add_argument("--batchsize", type=int, default=0,
                    help="override every data layer's batchsize")
    ap.add_argument("--synthetic", action="store_true",
                    help="use a synthetic learnable dataset")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="resume training from the workspace's latest "
                         "healthy checkpoint")
    ap.add_argument("--max-restarts", "--max_restarts", type=int,
                    dest="max_restarts", default=3,
                    help="trainer supervision budget (pipeline mode "
                         "is always supervised; default 3)")
    ap.add_argument("--scan_chunk", type=int, default=0)
    ap.add_argument("--health", choices=("on", "off"), default="on",
                    help="numeric-health sentinel on the trainer — "
                         "checkpoint verdicts are what bless a step "
                         "for promotion (docs/FAULT_TOLERANCE.md)")
    ap.add_argument("--health_spec", default=None)
    ap.add_argument("--fault_spec", default=None,
                    help="deterministic fault injection across BOTH "
                         "halves (train + serve sites, plus "
                         "pipeline.publish; singa_tpu/utils/faults.py)")
    ap.add_argument("--serve_spec", default=None,
                    help="ServeSpec for the fleet's engines")
    ap.add_argument("--fleet", type=int, default=2, metavar="N",
                    help="serving fleet size (default 2: one canary, "
                         "one stable)")
    ap.add_argument("--fleet_spec", default=None,
                    help="RouterSpec key=value entries")
    ap.add_argument("--rollout_spec", default=None,
                    help="RolloutSpec key=value entries (poll_s "
                         "bounds the fingerprint-poll half of the "
                         "blessed-to-served lag)")
    ap.add_argument("--pipeline_spec", default=None,
                    help="PipelineSpec key=value entries, e.g. "
                         "'lag_alarm_s=10,join_s=600' "
                         "(singa_tpu/core/pipeline.py)")
    ap.add_argument("--autoscale_spec", default=None,
                    help="enable the SLO-driven autoscaler over the "
                         "pipeline's fleet (AutoScaleSpec key=value "
                         "entries; the blessed-to-served lag joins "
                         "its pressure signals)")
    ap.add_argument("--smoke", type=int, default=0, metavar="N",
                    help="drive >= N in-process client requests while "
                         "training runs, wait for the loop to drain "
                         "(blessed == served), print the pipeline "
                         "snapshot as JSON, and exit (no HTTP)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000,
                    help="FleetServer HTTP port (0 = ephemeral)")
    _add_obs_flags(ap)
    return ap


def pipeline_main(argv) -> int:
    """The `pipeline` subcommand: trainer + fleet, one workspace, the
    `PipelineController` owning the seam."""
    import json as _json
    import time as _time

    args = make_pipeline_argparser().parse_args(argv)
    from .utils.faults import FaultSchedule, inject
    schedule = (FaultSchedule.parse(args.fault_spec, seed=args.seed)
                if args.fault_spec else None)
    log = obs.get_logger("pipeline")
    obs_on = _obs_enable(args, args.workspace)
    try:
        model = load_model_config(args.model_conf)
        if args.steps is not None:
            model.train_steps = args.steps
        from .data import discover_input_shapes, resolve_data_source
        if args.batchsize:
            for layer in (model.neuralnet.layer
                          if model.neuralnet else []):
                if layer.data_param:
                    layer.data_param.batchsize = args.batchsize
                if layer.seqdata_param:
                    layer.seqdata_param.batchsize = args.batchsize
        input_shapes = discover_input_shapes(
            model, force_synthetic=args.synthetic)

        from .utils.health import HealthMonitor, HealthSpec
        health_spec = HealthSpec.parse(args.health_spec)
        health = (HealthMonitor(health_spec,
                                log_fn=obs.get_logger("health"))
                  if args.health == "on" else None)
        if health is None:
            log("warning: --health off means every checkpoint "
                "publishes unclassified — only the canary gate "
                "stands between a diverged step and traffic")

        trainer = Trainer(model, input_shapes, health=health)
        reg = obs.registry()
        if reg is not None:
            trainer.timer.register_into(reg)
            if health is not None:
                health.register_into(reg)

        from .core.pipeline import PipelineController, PipelineSpec
        from .core.supervisor import Supervisor, TrainingAborted
        sup = Supervisor(trainer, args.workspace,
                         max_restarts=max(args.max_restarts, 1),
                         max_divergences=health_spec.max_divergences,
                         blame_batches=health_spec.blame_batches,
                         lr_backoff=health_spec.lr_backoff,
                         log=obs.get_logger("supervisor"))

        train_layer = next(
            (l for l in model.neuralnet.layer
             if l.type in ("kShardData", "kLMDBData", "kSequenceData")
             and "kTrain" not in l.exclude),
            None)
        if train_layer is None:
            bs = 64
        elif train_layer.type == "kSequenceData":
            bs = (train_layer.seqdata_param.batchsize
                  if train_layer.seqdata_param else 64)
        else:
            bs = train_layer.data_param.batchsize

        def make_train_iter():
            it, _ = resolve_data_source(
                model, bs, seed=args.seed,
                force_synthetic=args.synthetic,
                sample_shapes=input_shapes)
            return it

        import jax

        from .serve import (AutoScaleSpec, EngineFleet, FleetServer,
                            RolloutSpec, RouterSpec, ServeSpec)
        spec = (ServeSpec.parse(args.serve_spec) if args.serve_spec
                else ServeSpec())
        net = trainer.test_net or trainer.train_net
        fallback = net.init_params(jax.random.PRNGKey(args.seed))
        fleet = EngineFleet.local(
            net, spec, args.fleet, workspace=args.workspace,
            params=fallback, router_spec=RouterSpec.parse(args.fleet_spec),
            rollout_spec=RolloutSpec.parse(args.rollout_spec),
            log_fn=obs.get_logger("fleet"))
        ctl = PipelineController(
            sup, fleet, args.workspace,
            spec=PipelineSpec.parse(args.pipeline_spec),
            autoscale_spec=(AutoScaleSpec.parse(args.autoscale_spec)
                            if args.autoscale_spec is not None
                            else None),
            log_fn=log)
        if reg is not None:
            fleet.router.stats.register_into(reg)
            ctl.register_into(reg)

        with inject(schedule):
            if schedule is not None:
                log(f"fault injection active: {args.fault_spec} "
                    f"(seed {args.seed})")
            ctl.start(make_train_iter, seed=args.seed,
                      scan_chunk=args.scan_chunk, resume=args.resume)
            try:
                if args.smoke > 0:
                    rc = _pipeline_smoke(ctl, net, args, log)
                    print(_json.dumps(ctl.snapshot()))
                    return rc
                front = FleetServer(fleet, host=args.host,
                                    port=args.port, log_fn=log)
                ctl.register_into(front.metrics)
                front.start()
                try:
                    while not ctl.wait(timeout=1.0):
                        pass
                    if isinstance(ctl.train_error, TrainingAborted):
                        log(f"error: {ctl.train_error}")
                    log("pipeline: training finished; fleet keeps "
                        "serving (Ctrl-C to stop)")
                    while True:
                        _time.sleep(3600)
                except KeyboardInterrupt:
                    log("pipeline: shutting down")
                    print(_json.dumps(ctl.snapshot()))
                    return 0
                finally:
                    front.stop()
            finally:
                ctl.stop()
    finally:
        if obs_on:
            obs.disable()


def _pipeline_smoke(ctl, net, args, log) -> int:
    """In-process client loop for `pipeline --smoke N`: keep requests
    flowing while training runs, then wait for the loop to drain
    (every blessed step promoted).  Exit 0 only when training
    finished, no client request failed, and blessed == served."""
    import time as _time

    import numpy as np

    rng = np.random.default_rng(args.seed)
    vocab = _serve_vocab(net)
    sent = failed = 0
    drain_deadline = None
    while True:
        train_done = not ctl.train_running()
        lag = ctl.lag()
        if train_done and drain_deadline is None:
            # bounded drain: give the rollout a few alarm windows to
            # promote the tail, then report whatever lag remains
            drain_deadline = _time.monotonic() + \
                3 * float(ctl.spec.lag_alarm_s)
        drained = lag["lag_steps"] == 0
        if train_done and sent >= args.smoke and \
                (drained or ctl.train_error is not None
                 or _time.monotonic() >= drain_deadline):
            break
        plen = int(rng.integers(1, 9))
        prompt = rng.integers(0, vocab, plen).astype("int32")
        try:
            out = ctl.generate(prompt)
            sent += 1
            if sent % 25 == 0 or sent == 1:
                log(f"smoke {sent}: step {out['step']} on "
                    f"{out['engine']} (blessed "
                    f"{lag['blessed_step']}, served "
                    f"{lag['served_step']})")
        except Exception as e:  # noqa: BLE001 — a failure is the verdict
            failed += 1
            log(f"warning: smoke request failed "
                f"({type(e).__name__}: {e})")
            _time.sleep(0.05)
    lag = ctl.lag()
    ok = (ctl.train_error is None and failed == 0
          and lag["lag_steps"] == 0)
    log(f"pipeline smoke: {sent} requests ({failed} failed), "
        f"blessed {lag['blessed_step']} served {lag['served_step']}"
        + ("" if ctl.train_error is None
           else f", training FAILED: {ctl.train_error!r}"))
    return 0 if ok else 1


def _log_device(log) -> None:
    """Name what this run executes on, in its own log: a run that fell
    back to the CPU must not read like one that used the chip."""
    from .utils.flops import device_info
    d = device_info()
    log(f"device: platform={d['platform']} kind={d['kind']!r} "
        f"count={d['count']} jax={d['jax']} jaxlib={d['jaxlib']} "
        f"libtpu={d['libtpu']}")


def main(argv=None) -> int:
    from .utils import compile_cache
    compile_cache.enable()
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "serve":
        return serve_main(argv[1:])
    if argv and argv[0] == "pipeline":
        return pipeline_main(argv[1:])
    args = make_argparser().parse_args(argv)
    from .utils.faults import FaultSchedule, inject
    schedule = (FaultSchedule.parse(args.fault_spec, seed=args.seed)
                if args.fault_spec else None)
    obs_on = _obs_enable(args, args.workspace)
    try:
        if schedule is not None:
            obs.get_logger("main")(
                f"fault injection active: {args.fault_spec} "
                f"(seed {args.seed})")
        with inject(schedule):
            return _run(args)
    finally:
        if obs_on:
            obs.disable()


def _run(args) -> int:
    log = obs.get_logger("main")
    model = load_model_config(args.model_conf)
    cluster = (load_cluster_config(args.cluster_conf)
               if args.cluster_conf else None)

    # Multi-host bootstrap BEFORE any jax device query: -procsID/-hostfile
    # are the reference's launch coordinates (run.sh:20-37); here they
    # seed jax.distributed so jax.devices() spans every host.
    if args.hostfile:
        from .parallel.bootstrap import DEFAULT_PORT, distributed_init
        port = cluster.start_port if cluster else DEFAULT_PORT
        if distributed_init(args.procsID, args.hostfile, port=port):
            log(f"jax.distributed initialized: process {args.procsID}")
    if args.steps is not None:
        model.train_steps = args.steps

    # data-layer discovery: real sources are peeked for their true
    # record geometry, synthetic mode infers it from the parser configs
    # (the reference's Setup-reads-a-record contract, layer.cc:388-392)
    from .data import discover_input_shapes
    if args.batchsize:
        for layer in (model.neuralnet.layer if model.neuralnet else []):
            if layer.data_param:
                layer.data_param.batchsize = args.batchsize
            if layer.seqdata_param:
                layer.seqdata_param.batchsize = args.batchsize
    input_shapes = discover_input_shapes(
        model, force_synthetic=args.synthetic)

    # Mesh from the cluster config: engages DP/TP/SP/EP shardings when
    # more than one device is visible (ClusterProto topology → Mesh,
    # the reference's Cluster singleton role, cluster.h:20-121).
    import jax
    _log_device(log)
    mesh = None
    if cluster is not None and len(jax.devices()) > 1:
        from .parallel import mesh_from_cluster
        ptype = model.neuralnet.partition_type if model.neuralnet else "kNone"
        mesh = mesh_from_cluster(cluster, ptype)
        log(f"mesh: {dict(mesh.shape)} over {len(jax.devices())} devices")

    # worker-group topology (cluster.h:49-60): nworkers/nprocs_per_group
    # data-parallel groups; with the async consistency tier active each
    # group is a replica against the shared center (ReplicaSet below)
    ngroups = 1
    if cluster is not None and not cluster.synchronous:
        ngroups = max(cluster.nworkers
                      // max(cluster.nprocs_per_group, 1), 1)

    # numeric-health sentinel: probes compile into the train step only
    # when armed; --health off restores the exact pre-health program
    from .utils.health import HealthMonitor, HealthSpec
    health_spec = HealthSpec.parse(args.health_spec)
    health = (HealthMonitor(health_spec,
                            log_fn=obs.get_logger("health"))
              if args.health == "on" else None)
    if args.health == "off" and args.health_spec:
        log("warning: --health_spec given with --health off; the "
            "monitor is disabled and the spec only configures the "
            "supervisor's divergence policy")

    trainer = Trainer(model, input_shapes, mesh=mesh,
                      n_micro=(cluster.pipeline_microbatches
                               if cluster else 0),
                      ngroups=ngroups, health=health)
    # additive metric collectors (no-op without --obs on): the per-phase
    # timer and the health-verdict tallies feed the periodic dump
    reg = obs.registry()
    if reg is not None:
        trainer.timer.register_into(reg)
        if health is not None:
            health.register_into(reg)

    from .parallel.elastic import async_active
    async_multi = ngroups > 1 and async_active(model.updater)

    workspace = args.workspace or (cluster.workspace if cluster else None)
    # an explicit --workspace is a request to checkpoint: default to a
    # final snapshot when the config doesn't set a cadence
    if args.workspace and model.checkpoint_frequency == 0:
        model.checkpoint_frequency = max(model.train_steps, 1)
    train_layer = next(
        (l for l in model.neuralnet.layer
         if l.type in ("kShardData", "kLMDBData", "kSequenceData")
         and "kTrain" not in l.exclude),
        None)
    if train_layer is None:
        bs = 64
    elif train_layer.type == "kSequenceData":
        bs = (train_layer.seqdata_param.batchsize
              if train_layer.seqdata_param else 64)
    else:
        bs = train_layer.data_param.batchsize

    # Data source: shard files if the configured path exists locally,
    # else the synthetic source (reference configs point at dead hosts).
    from .data import resolve_data_source

    if async_multi:
        # multi-group async tier: each group trains its own replica and
        # exchanges with the shared center at the UpdaterProto cadence.
        # Branches BEFORE single-group state (init/sharding/prefetch)
        # is built — none of it is used on this path.
        from .parallel.elastic import ReplicaSet
        for flag, what in ((args.resume, "--resume"),
                           (workspace, "checkpointing (workspace)"),
                           (mesh is not None, "mesh sharding")):
            if flag:
                log(f"warning: {what} is not supported on the "
                    f"multi-group async simulation path; ignoring")
        log(f"async replica groups: {ngroups} x "
            f"{model.updater.param_type}")
        # ClusterProto.bandwidth/nservers drive the runtime SyncConfig
        # (param_manager.cc:85-93): after warmup the RandomSync sample
        # ratio adapts to the configured pipe
        rs = ReplicaSet(trainer, ngroups, seed=args.seed,
                        bandwidth_mb_s=(cluster.bandwidth
                                        if cluster else 0.0),
                        nservers=(cluster.nservers or 1
                                  if cluster else 1))
        # same task (seed), a distinct sample stream per replica
        iters = [resolve_data_source(
                     model, bs, seed=args.seed,
                     stream_seed=args.seed + 1000 * (g + 1),
                     force_synthetic=args.synthetic,
                     sample_shapes=input_shapes)[0]
                 for g in range(ngroups)]
        center, history = rs.run(iters, model.train_steps,
                                 seed=args.seed)
        last = history[0][-1] if history and history[0] else {}
        log(f"training done (center of {ngroups} replicas)" +
            (": " + ", ".join(f"{k} : {v:.6f}"
                              for k, v in sorted(last.items()))
             if last else ""))
        test_factory = resolve_data_source(
            model, bs, seed=args.seed,
            force_synthetic=args.synthetic,
            sample_shapes=input_shapes)[1]
        if trainer.test_step is not None and test_factory is not None \
                and center is not None and model.test_steps > 0:
            avg = trainer.evaluate(center, test_factory(),
                                   model.test_steps, trainer.test_step)
            log("center test: " + ", ".join(
                f"{k} : {v:.6f}" for k, v in sorted(avg.items())))
        return 0

    # Batch placement (sharded device_put under the mesh) is the
    # trainer's job now — _batch_place/_chunk_place inside run() and
    # evaluate() — so iterators stay HOST-side and the feed pipeline
    # can stage them into reusable buffers without a device round-trip.
    def make_train_iter():
        it, _ = resolve_data_source(
            model, bs, seed=args.seed, force_synthetic=args.synthetic,
            sample_shapes=input_shapes)
        return it

    _, test_factory = resolve_data_source(
        model, bs, seed=args.seed, force_synthetic=args.synthetic,
        sample_shapes=input_shapes)

    if args.resume and not workspace:
        log("warning: --resume given but no workspace configured "
            "(set --workspace or ClusterProto.workspace); "
            "starting from scratch")

    # auto → None: Trainer.run's default (on for chunked loops)
    feeder_flag = {"auto": None, "on": True, "off": False}[args.feeder]
    if args.feeder == "on" and args.scan_chunk <= 1:
        log("warning: --feeder on has no effect without "
            "--scan_chunk > 1 (the feeder stages whole scan chunks)")

    if args.max_restarts > 0:
        # supervised runtime: restore-the-last-valid-snapshot + replay
        # on failure, the recovery loop the reference left as a TODO
        # (Worker::Resume, worker.cc:65-67)
        from .core.supervisor import Supervisor, TrainingAborted
        sup = Supervisor(trainer, workspace,
                         max_restarts=args.max_restarts,
                         max_divergences=health_spec.max_divergences,
                         blame_batches=health_spec.blame_batches,
                         lr_backoff=health_spec.lr_backoff,
                         log=obs.get_logger("supervisor"))
        try:
            params, opt_state, history = sup.run(
                make_train_iter, test_iter_factory=test_factory,
                seed=args.seed, scan_chunk=args.scan_chunk,
                resume=args.resume, feeder=feeder_flag,
                feeder_depth=args.feeder_depth)
        except TrainingAborted as e:
            log(f"error: {e}")
            return 1
    else:
        params, opt_state = trainer.init(seed=args.seed)
        if mesh is not None:
            from .parallel import shard_opt_state, shard_params
            params = shard_params(mesh, trainer.train_net, params)
            opt_state = shard_opt_state(mesh, trainer.train_net,
                                        opt_state)
        start_step = 0
        if args.resume and workspace:
            params, opt_state, start_step = trainer.resume(
                params, opt_state, workspace)
            if start_step > 0:
                log(f"resumed from step {start_step}")
            else:
                log(f"no checkpoint found in {workspace}; "
                    "starting from scratch")
        params, opt_state, history = trainer.run(
            params, opt_state, make_train_iter(),
            test_iter_factory=test_factory,
            seed=args.seed, start_step=start_step, workspace=workspace,
            scan_chunk=args.scan_chunk, feeder=feeder_flag,
            feeder_depth=args.feeder_depth)
    final = trainer.perf.to_string()
    log("training done" + (f": {final}" if final else
                           f" at step {model.train_steps}"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
