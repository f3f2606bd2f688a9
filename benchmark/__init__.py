"""The yardstick: BENCHMARK.json's cells, run one at a time by run.py.

Everything a cell needs that is not the system under test lives here:
traffic generation, seeded weights, the plain reference, the reduction
from spans, counters and the profiler's trace to metrics, the table of
peaks, and the comparison that decides `correct`.  From `singa_tpu` the
runners take the system under test and nothing else.
"""
