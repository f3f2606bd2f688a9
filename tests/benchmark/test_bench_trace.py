"""The xplane reducer on two trimmed traces recorded on the v5e in
PR 23 (benchmark/trace/fixtures/): one padded prefill among five decode
steps of the continuous-batching engine, and one three-step chunk of
the training scan with its six calls of each flash kernel."""

import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.trace import reduce as R  # noqa: E402

FIX = os.path.join(ROOT, "benchmark", "trace", "fixtures")


@pytest.fixture(scope="module")
def cb():
    return R.reduce(os.path.join(FIX, "v5e_cb_prefill_decode.json.gz"))


@pytest.fixture(scope="module")
def scan():
    return R.reduce(os.path.join(FIX, "v5e_train_scan_step.json.gz"))


def test_device_planes_and_busy_union(cb, scan):
    assert cb["devices"] == 1 and scan["devices"] == 1
    assert cb["window_s"] == pytest.approx(0.184933, rel=1e-4)
    assert cb["busy_s"] == pytest.approx(0.171065, rel=1e-4)
    # the scan keeps the chip busy but for 22 microseconds; the ops line
    # nests each loop's body in the loop's event and is not counted twice
    assert scan["window_s"] - scan["busy_s"] == pytest.approx(2.2e-5, rel=0.05)
    assert sum(scan["ops"].values()) == pytest.approx(scan["busy_s"],
                                                       rel=1e-3)


def test_the_two_cb_programs_are_told_apart_by_the_hosts_span(cb):
    """Both compile from a function called `fn`, so both are `jit_fn`
    in the trace; the benchmark's span around each call names them."""
    assert "jit_fn" in cb["module_names"]
    by = cb["modules_by_span"]
    assert by["engine.prefill"]["main"] == by["engine.decode"]["main"] == "jit_fn"
    assert by["engine.prefill"]["runs"] == 1
    # three decode steps; the two one-microsecond programs that convert
    # a call's arguments are not runs of the step
    assert by["engine.decode"]["runs"] == 3
    assert by["engine.decode"]["other_seconds"] < 1e-5
    assert by["engine.prefill"]["seconds"] == pytest.approx(0.057679, rel=1e-4)
    assert by["engine.decode"]["seconds"] / 3 == pytest.approx(0.037652,
                                                                rel=1e-3)


def test_idle_gaps_are_named_by_what_the_host_was_doing(cb):
    gaps = dict(cb["breakdown"]["idle_gaps"])
    assert set(gaps) <= {"engine.decode", "engine.prefill", "no_span"}
    assert sum(gaps.values()) == pytest.approx(
        cb["window_s"] - cb["busy_s"], rel=1e-6)
    assert len(cb["breakdown"]["device_ops"]) <= 10
    assert cb["breakdown"]["device_ops"][0][0] in ("copy", "fusion")


def test_flash_kernels_are_told_apart_by_what_they_return(scan):
    assert "jit_train_scan" in scan["module_names"]
    calls = scan["kernel_calls"]
    assert calls == {"(bf16[2,4096,4096], f32[2,4096,32])": 6,
                     "(bf16[2,4096,1024], bf16[2,4096,1024])": 6,
                     "bf16[2,4096,4096]": 6}     # 2 layers x 3 steps each
    cell = harness.Cell("train-s4096-1chip")
    spec = importlib.util.spec_from_file_location(
        "flash_reader", os.path.join(ROOT, "benchmark", "layer_metrics",
                                     "flash_roofline.train.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    facts = {"trace": scan, "config": cell.config, "chips": 1,
             "peaks": cell.peaks_table["TPU v5 lite"],
             "counters": {"batch": 2, "seq_len": 4096}}
    share = reader.read(facts)
    # by hand: 9 causal S x S x D matmuls of 2 rows x 32 heads, 6 calls
    # each, at 197 TFLOP/s, over the 65.9 ms the three kernels took
    per = 2 * 4096 * 4096 * 128 / 2 * 2 * 32
    assert share == pytest.approx(100 * 6 * 9 * per / 197e12 / 0.0659335,
                                  rel=1e-3)
    assert 50 < share < 65
    facts["trace"] = dict(scan, kernels={})
    assert reader.read(facts) is None           # nothing to read
