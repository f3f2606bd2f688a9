#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              one chip: train, serve, conv legs
    python chip_smoke.py --chips 4    four chips: dryrun, ref, tp, sp legs
    python chip_smoke.py --rehearsal  CPU, tiny widths (debugging only)

Drives the main path through the entry point users call —
`singa_tpu.main.main(argv)`, what `python -m singa_tpu.main` runs — at
the full width of the 12-layer x 768 transformer LM (12 heads x 64,
vocab 32768, tied fused head, S=1024, batch 32, bf16) with random
weights from a seed:

  train   `-model_conf <12x768> --synthetic --steps N --scan_chunk k`,
          health sentinel on.  Before it, the same trainer's scan step
          is AOT-compiled through `Trainer.compiled_scan` (the compile
          CompileWatch times) and its text must hold Mosaic custom
          calls for the flash forward, dq, dkv and the fused head.
  serve   `serve -model_conf <12x768> --smoke N` with cb=on: exactly
          the two cb programs compile, every request returns its
          tokens, no recompile anomaly.  The same 768 wide as 6 heads
          x 128: the paged decode attention kernel copies whole
          (sublane, 128-lane) tiles and refuses a head_dim of 64 on
          the chip (ops/paged_attention.py).
  conv    `-model_conf examples/cifar10/alexnet.conf --synthetic`.

`--chips 4` runs `__graft_entry__.dryrun_multichip(4)`, then the train
leg on one device (ref), on examples/transformer/dp2_tp2_cluster.conf
(tp) and on dp2_sp2_cluster.conf with ring attention (sp): sharded params must span four devices with balanced
per-device bytes, and the first-step loss must match ref.

One process per chip: THIS process never imports JAX.  Each leg is a
child process (`chip_smoke.py --leg NAME`), run to its end before the
next starts, killed with its process group on timeout.  A leg that
fails fails the script; there is no error it records and carries on
from.  Without a TPU as JAX's default backend the first leg exits 3
with one line and nothing is printed as a result.  Nothing printed is
a claim: times here include compilation and one-off costs.

The last line of standard output is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
NO_TPU = 3               # exit code: JAX's default backend is not a TPU
BUDGET_S = 1150.0        # every leg together, compilation included

FULL = dict(vocab=32768, layers=12, embed=768, heads=12, head_dim=64,
            seq=1024, batch=32, steps=24, chunk=8, serve_heads=(6, 128),
            serve_spec="buckets=8x128,max_new_tokens=64,cb=on,"
                       "cb_slots=8,cb_block_len=16",
            requests=8, conv_args=["--steps", "8", "--scan_chunk", "4"])
TINY = dict(vocab=2048, layers=1, embed=128, heads=2, head_dim=64,
            seq=128, batch=4, steps=4, chunk=2, serve_heads=(1, 128),
            serve_spec="buckets=2x16,max_new_tokens=4,cb=on,"
                       "cb_slots=2,cb_block_len=4",
            requests=2, conv_args=["--steps", "2", "--scan_chunk", "2",
                                   "--batchsize", "4"])

LEGS = {1: ("train", "serve", "conv"), 4: ("dryrun", "ref", "tp", "sp")}
CLUSTERS = {"tp": "examples/transformer/dp2_tp2_cluster.conf",
            "sp": "examples/transformer/dp2_sp2_cluster.conf"}
# the legs that are `Leg.train` under another layout
TRAIN_LEGS = {"train": dict(mosaic=True), "ref": {},
              "tp": dict(cluster="tp"),
              "sp": dict(cluster="sp", seq_parallel="ring")}


# -- parent: no JAX ---------------------------------------------------------

def run_leg(name: str, work: str, rehearsal: bool, timeout: float) -> dict:
    """Run one leg as a child process; its last stdout line is its
    JSON record.  Raises unless the child exits 0 with ok: true."""
    cmd = [sys.executable, os.path.abspath(__file__), "--leg", name,
           "--work", work]
    env, tag = None, ""          # the child inherits this environment
    if rehearsal:
        # the one place a platform is forced: the explicit rehearsal
        cmd.append("--rehearsal")
        tag = "rehearsal platform=cpu | "
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        if name in LEGS[4]:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
    proc = subprocess.Popen(cmd, cwd=REPO, env=env,
                            stdout=subprocess.PIPE, text=True,
                            # rehearsal: stderr too gets the cpu tag
                            stderr=subprocess.STDOUT if rehearsal else None,
                            start_new_session=True)
    timer = threading.Timer(timeout, os.killpg,
                            (proc.pid, signal.SIGKILL))
    try:
        timer.start()
        last = ""
        for line in proc.stdout:
            line = line.rstrip("\n")
            if line.startswith('{"leg": '):
                last = line
            if line:
                print(tag + line, flush=True)
        rc = proc.wait()
    finally:
        timer.cancel()
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
    if rc == NO_TPU:
        raise SystemExit(NO_TPU)       # the child said why, in one line
    if rc != 0:
        raise SystemExit(f"chip_smoke: leg {name!r} failed "
                         f"(exit code {rc})")
    rec = json.loads(last or "{}")
    if rec.get("ok") is not True or rec.get("leg") != name:
        raise SystemExit(f"chip_smoke: leg {name!r} left no ok record")
    return rec


def parent(args) -> int:
    if not os.path.isdir(os.path.join(REPO, "singa_tpu")):
        print("chip_smoke: the singa_tpu package is not next to this "
              "script; nothing to run", file=sys.stderr)
        return 2
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    deadline = time.monotonic() + BUDGET_S
    try:
        recs = {}
        for name in LEGS[args.chips]:
            left = deadline - time.monotonic()
            if left <= 0:
                raise SystemExit("chip_smoke: out of time before leg "
                                 f"{name!r}")
            recs[name] = run_leg(name, work, args.rehearsal, left)
        if args.chips == 4:
            check_four_chips(recs)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    first = next(iter(recs.values()))
    final = {"ok": True,
             "device": {"platform": first["platform"],
                        "kind": first["device_kind"],
                        "count": first["device_count"]}}
    if args.rehearsal:
        final["rehearsal"] = True
    print(json.dumps(final), flush=True)
    return 0


def check_four_chips(recs: dict) -> None:
    """The cross-leg fact of the four-chip run: the sharded legs' first
    loss matches the one-device leg's."""
    ref = recs["ref"]["first_loss"]
    for name in ("tp", "sp"):
        got = recs[name]["first_loss"]
        if abs(got - ref) > 0.05:
            raise SystemExit(f"chip_smoke: leg {name!r} first-step loss "
                             f"{got} differs from one-device {ref}")


# -- child: one leg, one process, the only one that touches JAX -------------

class Tee(io.TextIOBase):
    """stdout that also keeps what passes, so a leg can check what the
    entry point logged."""

    def __init__(self, stream):
        self.stream, self.kept = stream, []

    def write(self, s):
        self.kept.append(s)
        return self.stream.write(s)

    def flush(self):
        self.stream.flush()

    def text(self) -> str:
        return "".join(self.kept)


class Leg:
    def __init__(self, name: str, work: str, rehearsal: bool):
        self.name, self.work, self.rehearsal = name, work, rehearsal
        self.size = TINY if rehearsal else FULL
        sys.path.insert(0, REPO)
        from singa_tpu.utils import compile_cache
        self.cache_dir = compile_cache.enable()
        import jax
        backend = jax.default_backend()
        if backend != ("cpu" if rehearsal else "tpu"):
            print(f"chip_smoke: JAX's default backend is {backend!r}, "
                  f"not {'cpu (rehearsal)' if rehearsal else 'tpu'}; "
                  f"nothing was run", file=sys.stderr)
            raise SystemExit(NO_TPU)
        want = 4 if name in LEGS[4] else 1
        if len(jax.devices()) < want:
            raise SystemExit(f"chip_smoke: leg {name!r} needs {want} "
                             f"devices, JAX reports {len(jax.devices())}")
        # JAX's own account of compiles and of its persistent cache
        self.xla = {"backend_compile_seconds": 0.0, "cache_hits": 0,
                    "cache_misses": 0}
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.xla["backend_compile_seconds"] += seconds

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.xla["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.xla["cache_misses"] += 1

    # -- what every leg reports --------------------------------------------
    def record(self, **fields) -> dict:
        import jax
        from singa_tpu.obs import perf
        from singa_tpu.utils.flops import device_info
        d, snap = device_info(), perf.snapshot()
        peaks = [int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
                 for dev in jax.devices()]
        rec = {"leg": self.name, "ok": True,
               "platform": d["platform"], "device_kind": d["kind"],
               "device_count": d["count"], "jax": d["jax"],
               "jaxlib": d["jaxlib"], "libtpu": d["libtpu"],
               "peak_bytes_in_use": peaks if len(peaks) > 1 else peaks[0],
               "compile_cache_dir": self.cache_dir,
               "singa_compile_seconds": {
                   "sum": snap["compile_seconds_sum"],
                   "by_program": {r["program"]: r["seconds"]
                                  for r in snap["records"]}},
               "xla": {k: round(v, 3) for k, v in self.xla.items()}}
        if self.rehearsal:
            rec["rehearsal"] = True
        rec.update(fields)
        return rec

    # -- configs from the tracked builders ---------------------------------
    def lm_conf(self, seq_parallel: str = "none", heads=None) -> str:
        from singa_tpu.config import model_config_to_text
        from singa_tpu.models.transformer import transformer_lm
        z = self.size
        num_heads, head_dim = heads or (z["heads"], z["head_dim"])
        cfg = transformer_lm(
            vocab_size=z["vocab"], num_layers=z["layers"],
            embed_dim=z["embed"], num_heads=num_heads,
            head_dim=head_dim, seq_len=z["seq"],
            batchsize=z["batch"], precision="bfloat16",
            seq_parallel=seq_parallel)
        cfg.display_frequency = 1        # every step's loss in the log
        path = os.path.join(self.work, f"lm_{self.name}.conf")
        with open(path, "w") as f:
            f.write(model_config_to_text(cfg))
        return path

    def main(self, argv) -> str:
        """`singa_tpu.main.main(argv)`; returns what it printed."""
        from singa_tpu import main as cli
        tee = Tee(sys.stdout)
        with contextlib.redirect_stdout(tee):
            rc = cli.main(argv)
        if rc != 0:
            raise SystemExit(f"chip_smoke: singa_tpu.main {argv} "
                             f"returned {rc}")
        return tee.text()

    # -- legs ----------------------------------------------------------------
    def train(self, cluster: str = "", seq_parallel: str = "none",
              mosaic: bool = False) -> dict:
        z = self.size
        conf = self.lm_conf(seq_parallel)
        fields = {}
        if mosaic:
            fields["mosaic_custom_calls"] = self.compiled_kernels(conf)
        argv = ["-model_conf", conf, "--synthetic",
                "--steps", str(z["steps"]),
                "--scan_chunk", str(z["chunk"])] + self.obs_args()
        if cluster:
            cpath = os.path.join(REPO, CLUSTERS[cluster])
            argv += ["-cluster_conf", cpath]
            fields["layout"] = self.layout(conf, cpath)
        text = self.main(argv)
        # display_frequency 1: one loss line per step
        fields.update(self.check_training(text, z["steps"], z["steps"],
                                          math.log(z["vocab"])))
        return self.record(**fields)

    def compiled_kernels(self, conf: str) -> dict:
        """AOT-compile the scan step of the trainer `main` is about to
        build (same config, health probes on) and count the Mosaic
        custom calls of each kernel in the compiled text."""
        import jax
        import jax.numpy as jnp
        from singa_tpu.config import load_model_config
        from singa_tpu.core.trainer import Trainer
        from singa_tpu.data import discover_input_shapes
        from singa_tpu.ops.attention import KERNEL_NAMES
        from singa_tpu.ops.head_loss import KERNEL_NAME
        from singa_tpu.utils.health import HealthMonitor

        z = self.size
        model = load_model_config(conf)
        trainer = Trainer(model,
                          discover_input_shapes(model,
                                                force_synthetic=True),
                          log_fn=lambda s: None, health=HealthMonitor())
        params, opt_state = jax.eval_shape(lambda: trainer.init(0))
        tok = jax.ShapeDtypeStruct((z["chunk"], z["batch"], z["seq"]),
                                   jnp.int32)
        text = trainer.compiled_scan(
            params, opt_state, {"data": {"input": tok, "target": tok}},
            0, jax.random.PRNGKey(0), z["chunk"], True).as_text()
        if self.rehearsal:
            return {"not_applicable": "kernels are interpreted on cpu"}
        calls = [line for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        counts = {k: sum(k in line for line in calls)
                  for k in KERNEL_NAMES + (KERNEL_NAME,)}
        missing = [k for k, n in counts.items() if n == 0]
        if missing:
            raise SystemExit(f"chip_smoke: no Mosaic custom call for "
                             f"{missing} in the compiled train step — "
                             f"interpreted or replaced kernels")
        return counts

    def layout(self, conf: str, cluster_conf: str) -> dict:
        """Where `main` puts the state under this cluster config: the
        same mesh and `shard_params` call it makes."""
        import jax
        from singa_tpu.config import (load_cluster_config,
                                      load_model_config)
        from singa_tpu.core.net import build_net
        from singa_tpu.data import discover_input_shapes
        from singa_tpu.parallel import mesh_from_cluster, shard_params

        model = load_model_config(conf)
        mesh = mesh_from_cluster(load_cluster_config(cluster_conf),
                                 model.neuralnet.partition_type)
        net = build_net(model, "kTrain",
                        discover_input_shapes(model, force_synthetic=True))
        params = shard_params(mesh, net,
                              net.init_params(jax.random.PRNGKey(0)))
        jax.block_until_ready(params)    # the unsharded copy is gone
        wq = params["attn0/wq"]
        shards = sorted({tuple(s.data.shape)
                         for s in wq.addressable_shards})
        held = {d: 0 for d in jax.devices()}
        for arr in params.values():
            for shard in arr.addressable_shards:
                held[shard.device] += shard.data.nbytes
        held = list(held.values())
        out = {"mesh": {k: v for k, v in mesh.shape.items() if v > 1},
               "attn0/wq": list(wq.shape),
               "attn0/wq_devices": len(wq.sharding.device_set),
               "attn0/wq_shard_shapes": [list(s) for s in shards],
               "param_bytes_per_device": held,
               "bytes_in_use": [
                   int((d.memory_stats() or {}).get("bytes_in_use", 0))
                   for d in jax.devices()]}
        if out["attn0/wq_devices"] != len(jax.devices()):
            raise SystemExit(f"chip_smoke: attn0/wq lives on "
                             f"{out['attn0/wq_devices']} devices: {out}")
        if "model" in out["mesh"] and \
                shards != [(wq.shape[0], wq.shape[1] // 2)]:
            raise SystemExit(f"chip_smoke: attn0/wq is not split in "
                             f"half over the model axis: {out}")
        if min(held) < 0.8 * max(held):
            raise SystemExit(f"chip_smoke: the sharded params are not "
                             f"spread evenly over the devices: {out}")
        return out

    def obs_args(self) -> list:
        """Telemetry on, every artifact inside the work directory.  A
        metrics period arms the dumper, whose dump on exit is how a
        leg reads the health verdicts."""
        w = os.path.join(self.work, self.name)
        return ["--obs", "on", "--obs_spec",
                f"events={w}.events,trace={w}.trace,flightrec={w}.fr,"
                f"metrics_period_s=600"]

    def check_training(self, text: str, steps: int, logged: int,
                       ln_vocab: float = 0.0) -> dict:
        """What `main` logged and dumped must show `steps` healthy
        steps: `logged` finite losses, `training done`, every health
        verdict ok."""
        losses = [float(x) for x in re.findall(
            r"(?:step-\d+|training done): .*?\bloss : ([-+.\deinfa]+)",
            text)]
        if len(losses) != logged or not all(map(math.isfinite, losses)):
            raise SystemExit(f"chip_smoke: wanted {logged} finite losses "
                             f"in the log, found {losses}")
        if "training done" not in text:
            raise SystemExit("chip_smoke: `training done` not reached")
        if ln_vocab and not ln_vocab - 0.5 < losses[0] < ln_vocab + 1.5:
            # random weights: the first loss is near ln(vocab)
            raise SystemExit(f"chip_smoke: first loss {losses[0]} is "
                             f"not near ln(vocab) = {ln_vocab:.3f}")
        health = self.final_metrics("singa_health_verdict_")
        ok = health.get("singa_health_verdict_ok_total", 0)
        if ok != steps or sum(health.values()) != steps:
            raise SystemExit(f"chip_smoke: health verdicts over {steps} "
                             f"steps were {health}")
        return {"steps": steps, "first_loss": losses[0],
                "last_loss": losses[-1], "health_verdict": "ok"}

    def final_metrics(self, prefix: str) -> dict:
        """The run's last metrics dump (obs flushes one on exit)."""
        last = {}
        with open(os.path.join(self.work, self.name + ".events")) as f:
            for line in f:
                ev = json.loads(line)
                if ev.get("kind") == "metrics":
                    last = ev["metrics"]
        return {k: v for k, v in last.items() if k.startswith(prefix)}

    def serve(self) -> dict:
        from singa_tpu.obs import perf
        z = self.size
        text = self.main(["serve", "-model_conf",
                          self.lm_conf(heads=z["serve_heads"]),
                          "--smoke", str(z["requests"]),
                          "--serve_spec", z["serve_spec"]])
        snap = json.loads(text.strip().splitlines()[-1])
        max_new = int(re.search(r"max_new_tokens=(\d+)",
                                z["serve_spec"]).group(1))
        answers = [int(n) for n in re.findall(
            r"smoke \d+: plen=\d+ -> (\d+) tokens", text)]
        dtype = re.search(r"serving checkpoint step -?\d+ as (\w+)", text)
        p = perf.snapshot()
        facts = {"requests": len(answers), "tokens_each": answers,
                 "completed": snap["completed"], "failed": snap["failed"],
                 "generated_tokens": snap["generated_tokens"],
                 "compiles": snap["compiles"],
                 "compiled_programs": sorted(p["compiles"]),
                 "recompile_anomalies": p["anomalies"],
                 "served_dtype": dtype.group(1) if dtype else None}
        want = {"requests": z["requests"],
                "tokens_each": [max_new] * z["requests"],
                "completed": z["requests"], "failed": 0,
                "generated_tokens": max_new * z["requests"],
                "compiles": 2,
                "compiled_programs": ["cb_decode", "cb_prefill"],
                "recompile_anomalies": 0}
        wrong = {k: facts[k] for k in want if facts[k] != want[k]}
        if wrong or not facts["served_dtype"]:
            raise SystemExit(f"chip_smoke: serve leg got {facts}, "
                             f"wanted {want}")
        return self.record(**facts)

    def conv(self) -> dict:
        z = self.size
        argv = ["-model_conf",
                os.path.join(REPO, "examples/cifar10/alexnet.conf"),
                "--synthetic"] + z["conv_args"] + self.obs_args()
        text = self.main(argv)
        # the conf displays every 100 steps: step 0, then the mean of
        # the rest on the `training done` line
        return self.record(**self.check_training(
            text, int(z["conv_args"][1]), 2))

    def dryrun(self) -> dict:
        import __graft_entry__
        tee = Tee(sys.stdout)
        with contextlib.redirect_stdout(tee):
            __graft_entry__.dryrun_multichip(4)
        done = re.findall(r"dryrun_multichip\(4\) ([\w+-]+):", tee.text())
        if "skipped" in tee.text():
            raise SystemExit("chip_smoke: dryrun_multichip(4) skipped a "
                             "leg")
        return self.record(parity_legs=done)

    def run(self) -> dict:
        if self.name in TRAIN_LEGS:
            return self.train(**TRAIN_LEGS[self.name])
        return getattr(self, self.name)()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU, tiny widths; every line says so")
    ap.add_argument("--leg", choices=LEGS[1] + LEGS[4],
                    help=argparse.SUPPRESS)
    ap.add_argument("--work", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.leg is None:
        return parent(args)
    rec = Leg(args.leg, args.work, args.rehearsal).run()
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
