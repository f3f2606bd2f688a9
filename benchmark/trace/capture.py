"""Take the profiler's trace of a short span at the end of a window."""

from __future__ import annotations

import glob
import os
import shutil
import threading
import time
from typing import Dict, Optional, Tuple

from benchmark.trace import reduce as reducer


class Capture:
    """With `on`, traces the last `span_s` seconds of a window of
    `seconds` (all of a shorter one).  The trace goes to a fixed
    directory inside the checkout, emptied first."""

    def __init__(self, cell, on: bool, seconds: float, span_s: float):
        self.on = on
        self.dir = os.path.join(cell.root, ".bench_trace", cell.name)
        self.delay = max(seconds - span_s, 0.0)
        self.host_span: Optional[Tuple[float, float]] = None
        self._timer: Optional[threading.Timer] = None
        self._started = threading.Event()
        self._t0 = 0.0

    def _start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # device ops and TraceAnnotations only
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._t0 = time.perf_counter()
        self._started.set()

    def arm(self) -> None:
        if not self.on:
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        self._timer = threading.Timer(self.delay, self._start)
        self._timer.daemon = True
        self._timer.start()

    def stop(self) -> None:
        if not self.on:
            return
        self._timer.join()
        if self._started.is_set():
            import jax
            t1 = time.perf_counter()
            jax.profiler.stop_trace()
            self.host_span = (self._t0, t1)

    def path(self) -> Optional[str]:
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None

    def reduce(self) -> Optional[Dict]:
        if not self.on or self.path() is None:
            return None
        return reducer.reduce(self.path())
