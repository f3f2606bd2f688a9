"""Profiling — the reference's TimerInfo phase report (worker.h:91-114)
plus TPU-native jax.profiler traces.

The reference accumulates tForward_/tBackward_/tSyncData_/tSyncParam_
around each phase and prints "% of step per phase".  Under XLA the
fwd/bwd/update are one fused program, so the phase split comes from the
profiler trace instead; the host-visible split (data wait vs device
step) is kept in trainer.TimerInfo with the same report format.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Iterator, Optional

import jax


def hard_sync(tree) -> None:
    """Barrier: returns once every array in `tree` is computed.  The
    name timing code uses for `jax.block_until_ready`."""
    jax.block_until_ready(tree)


@contextlib.contextmanager
def trace(logdir: str) -> Iterator[None]:
    """Capture a jax.profiler trace viewable in TensorBoard/XProf."""
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class StepTimer:
    """Wall-clock step timing with compile-step exclusion."""

    def __init__(self, skip_first: int = 1):
        self.skip = skip_first
        self.times = []
        self._t0: Optional[float] = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        if self.skip > 0:
            self.skip -= 1
        else:
            self.times.append(dt)

    def mean(self) -> float:
        return sum(self.times) / max(len(self.times), 1)

    def steps_per_sec(self) -> float:
        m = self.mean()
        return 1.0 / m if m else 0.0


def hlo_attribution(compiled_text: str) -> dict:
    """HLO instruction name → "op_name  [file:line]" tag from the
    compiled module's metadata (the mapping tools/profile_step.py
    prints next to each hot op)."""
    import re

    attr = {}
    for m in re.finditer(
            r"%?([\w.\-]+) = [^\n]*metadata={([^}]*)}", compiled_text):
        name, meta = m.group(1), m.group(2)
        op = re.search(r'op_name="([^"]*)"', meta)
        src = re.search(r'source_file="([^"]*)"', meta)
        line = re.search(r"source_line=(\d+)", meta)
        tag = op.group(1) if op else ""
        if src:
            tag += (f"  [{os.path.basename(src.group(1))}:"
                    f"{line.group(1) if line else '?'}]")
        if tag:
            attr[name] = tag
    return attr


def parse_trace_ops(outdir: str):
    """Per-op device time from the newest profiler trace under `outdir`:
    returns (Counter op-name → microseconds, total_us).  Device pids
    cover TPU and the CPU backend (tests)."""
    import collections
    import glob
    import gzip
    import json

    paths = glob.glob(os.path.join(
        outdir, "plugins/profile/*/*.trace.json.gz"))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {outdir}")
    with gzip.open(max(paths, key=os.path.getmtime), "rt") as f:
        events = json.load(f)["traceEvents"]
    pid_names = {e["pid"]: e["args"]["name"] for e in events
                 if e.get("ph") == "M" and e.get("name") == "process_name"
                 and "args" in e}
    dev_pids = {p for p, n in pid_names.items()
                if "TPU" in n or "/device" in n.lower()
                or "cpu" in n.lower()}
    per_op = collections.Counter()
    for e in events:
        if e.get("ph") == "X" and e.get("pid") in dev_pids:
            per_op[e.get("name", "?")] += e.get("dur", 0)
    return per_op, sum(per_op.values())


def classify_phase(tag: str) -> str:
    """fwd / bwd / update from an HLO attribution tag.

    The jaxpr path in op_name marks reverse-mode ops with transpose(
    (value_and_grad's backward); updater ops carry updater.py source.
    An XLA fusion spanning phases keeps one representative metadata —
    the shares are a per-fusion attribution, not an exact split (the
    reference's per-phase timers had the same blur from async queues,
    worker.h:91-114)."""
    if "updater.py" in tag:
        return "update"
    if "transpose(" in tag:
        return "bwd"
    return "fwd"


def phase_shares(outdir: str, compiled_text: str) -> dict:
    """{"fwd": f, "bwd": b, "update": u, "coverage": c} — phase
    fractions of ATTRIBUTED device time plus the attributed/total
    coverage ratio, from a captured trace + the compiled module text.
    Coverage travels with the shares so the report can qualify them:
    a fusion spanning phases keeps one representative metadata (see
    classify_phase), and at small-model scale that blur can swallow a
    whole phase — "update 0%" with 70% coverage is attribution loss,
    not a free optimizer."""
    per_op, total = parse_trace_ops(outdir)
    attr = hlo_attribution(compiled_text)
    shares = {"fwd": 0.0, "bwd": 0.0, "update": 0.0}
    attributed = 0
    for name, us in per_op.items():
        tag = attr.get(name.split("(")[0])
        if tag is None:
            continue
        attributed += us
        shares[classify_phase(tag)] += us
    denom = attributed or total or 1
    out = {k: v / denom for k, v in shares.items()}
    out["coverage"] = attributed / (total or 1)
    return out


def flops_of(fn, *args) -> Optional[float]:
    """Analytical FLOP estimate of a jitted function via XLA cost
    analysis — used for MFU reporting in bench.py."""
    try:
        lowered = jax.jit(fn).lower(*args)
        cost = lowered.compile().cost_analysis()
        return float(cost.get("flops", 0.0)) if cost else None
    except Exception:
        return None
