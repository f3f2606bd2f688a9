"""Open-loop request traffic from a mix's parameters.

One general generator: a mix is a data file beside this one, and a new
mix needs no code.  Arrival scheduling follows
`singa_tpu/serve/traffic.py:TrafficGen` (a due time per request, sent
whether or not earlier ones have finished), with three faults of the
original cured here: latency is counted from when a request was DUE,
not from when it was sent; how late each send ran is reported; lengths
come from the mix, not from `(4, 8)`.

Every seed gets the same schedule: the prompt lengths, output lengths
and arrival gaps are the (i + 1/2)/N quantiles of the mix's
distributions (so their medians are the stated ones), put in an order
drawn from the mix's own `schedule_seed`.  The run's seed draws the
token values (and the weights).  The order is not the seed's because it
decides the tail: just below the knee the 95th percentile of the time
to first token read 136, 186 and 396 ms for three orders of one set of
sizes (my chip runs, PR 23), which no bound could hold.  A cell's tail
is therefore the tail of one replayed schedule.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, NamedTuple

import numpy as np

_N01 = statistics.NormalDist()


class Request(NamedTuple):
    due_s: float            # seconds after the window opens
    tokens: np.ndarray      # (plen,) int32 prompt
    max_new: int            # output tokens asked for (eos is off)


def _quantiles(n: int) -> List[float]:
    return [(i + 0.5) / n for i in range(n)]


def lengths(spec: Dict, n: int) -> np.ndarray:
    """`n` lengths: quantiles of a lognormal with the given median and
    sigma, clipped to [lo, hi]."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    xs = [spec["median"] * math.exp(spec["sigma"] * _N01.inv_cdf(u))
          for u in _quantiles(n)]
    return np.clip(np.rint(xs), spec["lo"], spec["hi"]).astype(np.int64)


def gaps(rate_rps: float, n: int) -> np.ndarray:
    """`n` gaps between arrivals: quantiles of the exponential with
    mean 1/rate (a Poisson process), rescaled so that they add up to
    exactly n / rate."""
    g = np.array([-math.log(1.0 - u) for u in _quantiles(n)])
    return g * (n / rate_rps) / g.sum()


def generate(mix: Dict, seed: int, seconds: float, vocab: int
             ) -> List[Request]:
    """The requests due inside a window of `seconds`."""
    n = max(int(round(mix["rate_rps"] * seconds)), 1)
    order = np.random.default_rng(int(mix["schedule_seed"]))
    plens = order.permutation(lengths(mix["prompt"], n))
    olens = order.permutation(lengths(mix["output"], n))
    due = np.cumsum(order.permutation(gaps(mix["rate_rps"], n)))
    rng = np.random.default_rng(int(seed))
    due -= due[0] * 0.5                 # the first is due inside, not at 0
    return [Request(float(due[i]),
                    rng.integers(0, vocab, int(plens[i])).astype(np.int32),
                    int(olens[i])) for i in range(n)]
