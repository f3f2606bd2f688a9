"""Percentile, interval and roofline arithmetic on hand-made inputs,
the operation counts, and the trace reducer's pieces."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, opcount, stats  # noqa: E402
from benchmark.trace import reduce as R  # noqa: E402


def test_percentile_interpolates_between_order_statistics():
    xs = [10, 20, 30, 40, 50]
    assert stats.percentile(xs, 0) == 10 and stats.percentile(xs, 100) == 50
    assert stats.percentile(xs, 50) == 30 == stats.median(xs)
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_iqr_spread_is_the_contracts():
    xs = [100, 101, 102, 103, 104, 105]
    # statistics.quantiles(n=4): q1 100.75, q3 104.25; median 102.5
    assert stats.iqr_spread(xs) == pytest.approx(3.5 / 102.5)


def test_union_and_idle_share():
    busy = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (4.0, 4.5), (9.0, 9.0)]
    assert stats.union(busy) == [(0.0, 2.0), (3.0, 4.5)]
    assert stats.union_length(busy) == pytest.approx(3.5)
    assert stats.idle_share(busy, 0.0, 5.0) == pytest.approx(0.3)
    assert stats.idle_share(busy, 1.0, 3.0) == pytest.approx(0.5)
    assert stats.gaps(busy, 0.0, 5.0) == [(2.0, 3.0), (4.5, 5.0)]
    with pytest.raises(ValueError):
        stats.idle_share(busy, 2.0, 2.0)


def test_roofline_share_takes_the_longer_bound_and_is_not_clamped():
    # 1e12 ops at 1e12/s = 1 s; 1e9 bytes at 1e10/s = 0.1 s -> compute bound
    assert stats.roofline_share(1e12, 1e9, 2.0, 1e12, 1e10) == 50.0
    assert stats.roofline_share(1e9, 1e11, 20.0, 1e12, 1e10) == 50.0
    assert stats.roofline_share(1e12, 0, 0.5, 1e12, 1e10) == 200.0


CFG = harness.read_json(ROOT, "benchmark", "configs",
                        "mistral7b-train-l2.json")


def test_train_flops_per_token_by_hand():
    per_layer = 218103808                     # matrices, no norm scales
    assert opcount.layer_matmul_params(CFG) == per_layer
    fwd = (2 * per_layer * 2 + 2 * 4096 * 32768
           + 2 * (4 * 4096 * 32 * 128) / 2)
    assert opcount.train_flops_per_token(CFG, 4096) == pytest.approx(3 * fwd)
    assert 3.5e9 < opcount.train_flops_per_token(CFG, 4096) < 3.7e9


def test_decode_bytes_and_prefill_flops_by_hand():
    serve = harness.read_json(ROOT, "benchmark", "configs",
                              "mistral7b-serve-l16.json")
    assert opcount.kv_bytes_per_token(serve, 2) == 64 * 1024
    w = opcount.weight_bytes(serve, 2)
    assert 7.2e9 < w < 7.3e9
    assert opcount.decode_step_needed_bytes(serve, 1000, 2) == w + 65536e3
    # a short prompt needs fewer operations than the padded width does
    assert (opcount.prefill_needed_flops(serve, 128)
            < opcount.prefill_needed_flops(serve, 1024) / 7)


def test_flash_kernel_counts():
    f = opcount.flash_call_flops("singa_flash_fwd", 2, 32, 4096, 128)
    assert f == 2 * (2 * 4096 * 4096 * 128 / 2) * 2 * 32
    assert opcount.flash_call_flops("singa_flash_dkv", 2, 32, 4096, 128) == 2 * f
    assert opcount.flash_call_bytes("singa_flash_fwd", 1, 32, 8, 4096, 128,
                                    2) == (2 * 4096 * 4096 + 2 * 4096 * 1024) * 2


def test_self_times_subtract_children_from_a_loop():
    ev = [("while", 0.0, 10.0), ("fusion.1", 1.0, 4.0), ("fusion.2", 4.0, 9.0),
          ("copy", 12.0, 13.0)]
    got = R.self_times(ev)
    assert got == {"fusion.1": 3.0, "fusion.2": 5.0, "while": 2.0,
                   "copy": 1.0}
    assert R.short("%fusion.123") == "fusion" and R.short("copy") == "copy"


def test_covering_names_a_gap_by_the_shortest_span_over_it():
    spans = [("engine.decode", 0.0, 10.0), ("host.fetch", 4.0, 5.0)]
    assert R.covering(spans, 4.5) == "host.fetch"
    assert R.covering(spans, 7.0) == "engine.decode"
    assert R.covering(spans, 11.0) is None
