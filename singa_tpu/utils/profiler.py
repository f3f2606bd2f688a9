"""Profiling — the reference's TimerInfo phase report (worker.h:91-114)
plus helpers for timing and reading compiled programs.

The reference accumulates tForward_/tBackward_/tSyncData_/tSyncParam_
around each phase and prints "% of step per phase".  Under XLA the
fwd/bwd/update are one fused program with no phase boundary to time;
the host-visible split (data wait vs device step) is kept in
trainer.TimerInfo with the same report format, and the program's
`obs.span`s lie in any `jax.profiler` trace beside the device's ops
(PERF.md, "Reading a v5e trace").
"""

from __future__ import annotations

import os
import time
from typing import Optional

import jax


def hard_sync(tree) -> None:
    """Barrier: returns once every array in `tree` is computed.  The
    name timing code uses for `jax.block_until_ready`."""
    jax.block_until_ready(tree)


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


def flops_of(fn, *args) -> Optional[float]:
    """Analytical FLOP estimate of a jitted function via XLA cost
    analysis — used for MFU reporting in bench.py."""
    try:
        lowered = jax.jit(fn).lower(*args)
        cost = lowered.compile().cost_analysis()
        return float(cost.get("flops", 0.0)) if cost else None
    except Exception:
        return None
