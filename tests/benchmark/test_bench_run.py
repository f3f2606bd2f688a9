"""`run.py` end to end at the tiny preset on the CPU: the last line's
keys, a run without a TPU refused, the timed path broken underneath
seen as not correct, and a new cell and metric added by files alone."""

import io
import json
import os
import shutil
import subprocess
import sys
import time
from contextlib import redirect_stdout

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, run as bench_run  # noqa: E402

MANIFEST = harness.read_json(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _last_line(argv):
    out = io.StringIO()
    with redirect_stdout(out):
        assert bench_run.main(argv) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1]), out.getvalue()


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_preset_prints_the_contracts_last_line(name, trace):
    line, text = _last_line(["--workload", name, "--seed", str(2 ** 31 + 11),
                             "--seconds", "2", "--trace", str(trace),
                             "--rehearsal", "1"])
    assert LINE_KEYS <= set(line)
    assert set(line) - LINE_KEYS <= {"rehearsal", "counts", "readers",
                                     "breakdown"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # a CPU run names its device and carries no metric
    assert line["device"]["platform"] == "cpu" and line["metrics"] == {}
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert "compared " in text and "(limit " in text
    if trace:
        assert "compile_s" in line["readers"]


def test_no_tpu_no_result():
    """Without the rehearsal switch a run on the CPU exits non-zero and
    prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == harness.NO_ACCELERATOR
    assert '"correct"' not in p.stdout
    assert "not 'tpu'" in p.stderr


def test_only_the_benchmarks_files_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths` there is no system to measure: non-zero, no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0 and '"correct"' not in p.stdout


def _run(cell, **kw):
    log = harness.start_jax(cell)
    runner = cell.load("runners", cell.spec["runner"])
    return runner.run(cell, seed=5, seconds=1.5, trace=False,
                      t_process=time.perf_counter(), compile_log=log, **kw)


def test_an_altered_token_is_not_correct():
    cell = harness.Cell("serve-chat-r80", rehearsal=True)
    out = _run(cell, broken=True)
    assert out["correct"] is False
    bad = [r["name"] for r in out["compared"] if not r["ok"]]
    assert bad == ["served_gap", "served_gap_mean"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct():
    cell = harness.Cell("train-s4096-1chip", rehearsal=True)
    out = _run(cell, broken=True)
    assert out["correct"] is False
    bad = {r["name"] for r in out["compared"] if not r["ok"]}
    assert "delta_worst_leaf" in bad and "moment_worst_leaf" in bad


def test_a_cell_and_a_metric_are_added_by_files_alone(tmp_path):
    """A later PR's view: a temporary copy of the benchmark gains a
    traffic mix, a workload file, a per-layer reader and entries in
    BENCHMARK.json; no file that was there is edited, and the tiny
    preset runs the new cell and reads the new metric."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "singa_tpu"), tmp_path / "singa_tpu")
    bench = tmp_path / "benchmark"
    mix = harness.read_json(ROOT, "benchmark", "traffic", "code-sat.json")
    mix["tiny"]["rate_rps"] = 25.0
    (bench / "traffic" / "dummy-mix.json").write_text(json.dumps(mix))
    spec = harness.read_json(ROOT, "benchmark", "workloads",
                             "serve-code-sat.json")
    (bench / "workloads" / "dummy-cell.json").write_text(
        json.dumps(dict(spec, traffic="dummy-mix")))
    (bench / "layer_metrics" / "dummy_steps.py").write_text(
        "def read(facts):\n    return facts['counters']['cb_steps']\n")
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["workloads"].append(
        {"name": "dummy-cell", "config": "mistral7b-serve-l16",
         "traffic": "dummy-mix", "chips": 1, "why": "a test's cell"})
    for m in manifest["end_to_end"]:
        if m["name"] == "out_tok_s":
            m["workloads"].append("dummy-cell")
    manifest["per_layer"].append(
        {"name": "dummy_steps", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "scheduler (serve/scheduler.py)",
         "moves": "out_tok_s", "workloads": ["dummy-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    cell = harness.Cell("dummy-cell", rehearsal=True, root=str(tmp_path))
    log = harness.start_jax(cell)
    out = cell.load("runners", cell.spec["runner"]).run(
        cell, seed=9, seconds=1.5, trace=True,
        t_process=time.perf_counter(), compile_log=log)
    line = json.loads(harness.result_line(cell, True, out))
    assert line["correct"] is True and line["counts"]["requests"] == 38
    assert "dummy_steps" in line["readers"]
    assert "compile_s" in line["readers"]    # no `workloads` key: every cell
