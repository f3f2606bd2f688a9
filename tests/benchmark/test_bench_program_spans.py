"""The readers of the program's own spans (PR 24) on a trimmed v5e
trace, benchmark/trace/fixtures/v5e_cb_program_spans.json.gz: two
decode-only scheduler steps, one admitting step (one prefill), two more
decode steps of `serve-chat-r80`, with the program's spans and their
stats, the runner's two wrappers, and the TPU runtime's enqueue /
completion events that tie a program run to the host's clock."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.layer_metrics import _program_spans as P  # noqa: E402
from benchmark.trace import reduce as R  # noqa: E402

FIX = os.path.join(ROOT, "benchmark", "trace", "fixtures")
NEW = os.path.join(FIX, "v5e_cb_program_spans.json.gz")
MANIFEST = harness.read_json(ROOT, "BENCHMARK.json")
NEW_METRICS = ["step_host_ms.chat", "step_host_ms.sat", "step_upload_ms.chat",
               "step_dispatch_ms.chat", "step_fetch_ms.chat",
               "step_rest_ms.chat", "admit_stall_ms.chat",
               "queue_wait_ms.chat", "step_clock_slack_ms.chat"]
FACTS = {"cell": "serve-chat-r80", "trace": {"devices": 1}}


@pytest.fixture()
def traced(monkeypatch):
    """Point the readers at the fixture, as the kept trace of a run."""
    monkeypatch.setattr(P, "trace_path", lambda facts: NEW)
    monkeypatch.setattr(P, "_CACHE", {})
    return P.of(FACTS)


def _read(name, facts):
    cell = harness.Cell("serve-chat-r80")
    return cell.load("layer_metrics", name).read(facts)


def test_program_spans_and_their_stats_come_back(traced):
    assert {k: len(v) for k, v in traced.spans.items()} == {
        "scheduler.admit": 1, "scheduler.step": 5,
        "scheduler.admit_pending": 1, "scheduler.prefill": 1,
        "scheduler.decode": 5, "engine.cb_decode": 5, "engine.upload": 5,
        "engine.dispatch": 5, "engine.fetch": 5,
        "engine.prefill": 1, "engine.decode": 5}      # the runner's two
    (_, _, prefill), = traced.spans["scheduler.prefill"]
    assert prefill["queue_ms"] == pytest.approx(40.4749, abs=1e-4)
    assert prefill["plen"] == 271 and prefill["slot"] == 11
    assert prefill["corr"].startswith("cbreq-")
    assert [st["active"] for _, _, st in traced.spans["scheduler.step"]] \
        == [12, 12, 12, 13, 13]
    assert [st["pending"] for _, _, st in traced.spans["scheduler.step"]] \
        == [0, 0, 1, 0, 0]


def test_device_plane_is_moved_onto_the_hosts_clock(traced):
    """Unshifted, the runs of this trace start 2.1 ms before the host
    began to enqueue them; causality gives the shift and how far it can
    be off."""
    assert traced.clock_shift == pytest.approx(2.0952e-3, rel=1e-3)
    assert traced.clock_slack == pytest.approx(0.285e-3, rel=1e-2)
    assert P.clock_slack_ms(FACTS) == pytest.approx(0.285, rel=1e-2)
    raw = P.Trace([dict(p, lines=[dict(
        ln, stats=[None] * len(ln["events"])) for ln in p["lines"]])
        for p in P.read_planes(NEW)])        # no run_id: nothing to align by
    assert raw.clock_shift == 0.0 and raw.clock_slack is None
    assert traced.busy[0][0] - raw.busy[0][0] == pytest.approx(
        traced.clock_shift)
    # as recorded, step programs start while their call is still
    # uploading its arguments; on the host's clock none does (the
    # microsecond programs that convert an argument do run then)
    uploads = traced.intervals("engine.upload")
    early = lambda tr: [s for s, e in tr.busy[1:] if e - s > 1e-3   # noqa: E731
                        and any(a <= s <= b for a, b in uploads)]
    assert len(early(raw)) >= 2 and not early(traced)


def test_step_parts_sum_to_step_host(traced):
    parts = P.step_host_parts(FACTS)
    assert parts["host"] == pytest.approx(3.3430, rel=1e-3)
    assert parts["upload"] == pytest.approx(0.9110, rel=1e-3)
    assert parts["dispatch"] == pytest.approx(1.0631, rel=1e-3)
    assert parts["fetch"] == pytest.approx(1.0587, rel=1e-3)
    assert parts["rest"] == pytest.approx(0.3101, rel=1e-3)
    assert parts["upload"] + parts["dispatch"] + parts["fetch"] \
        + parts["rest"] == pytest.approx(parts["host"], rel=1e-9)
    # by hand: the device's idle time inside the steps, less what lies
    # in the admission (the prefill's own upload, dispatch and fetch)
    steps = traced.intervals("scheduler.step")
    admit = traced.intervals("scheduler.admit_pending")
    idle = P.overlap(traced.idle, steps) - P.overlap(traced.idle, admit)
    assert len(steps) == 4               # the first began before the trace
    assert parts["host"] == pytest.approx(1e3 * idle / 4, rel=1e-9)
    # the admission is counted by admit_stall_ms, not here
    assert P.overlap(traced.idle, admit) > 2e-3


def test_admission_readers(traced):
    (s, e), = traced.intervals("scheduler.admit_pending")
    assert P.admit_stall_ms(FACTS) == pytest.approx(1e3 * (e - s))
    assert 60.4 < P.admit_stall_ms(FACTS) < 61.5      # one 60.4 ms prefill
    assert P.queue_wait_ms(FACTS) == pytest.approx(40.4749, abs=1e-4)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_each_new_metric_has_a_reader_that_reads_the_fixture(traced, name):
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    layers = {m["layer"] for m in MANIFEST["per_layer"]
              if m["name"] not in NEW_METRICS}
    assert entry["layer"] in layers            # an existing layer's string
    assert entry["better"] == "lower" and entry["unit"] == "ms"
    assert entry["source"] == ("program_span" if name.startswith(
        ("admit_", "queue_")) else "device_trace")
    assert (entry["layer"] == "device") == name.startswith("step_clock")
    value = _read(name, FACTS)
    assert value is not None and value > 0
    want = {"step_host_ms": "host", "step_upload_ms": "upload",
            "step_dispatch_ms": "dispatch", "step_fetch_ms": "fetch",
            "step_rest_ms": "rest"}.get(name.split(".")[0])
    if want:
        assert value == P.step_host_parts(FACTS)[want]


@pytest.mark.parametrize("name", NEW_METRICS)
@pytest.mark.parametrize("case", ["rehearsal", "no_kept_trace",
                                  "program_before_pr24",
                                  "parent_under_these_files"])
def test_readers_return_none_where_there_is_nothing_to_read(
        monkeypatch, name, case):
    monkeypatch.setattr(P, "_CACHE", {})
    facts = dict(FACTS)
    if case == "parent_under_these_files":
        # the parent's program traced with this PR's benchmark files:
        # the runner's two wrappers and the runtime's events are there
        # (so the planes can be aligned), no span of the program is
        planes = P.read_planes(NEW)
        for p in planes:
            for ln in p["lines"]:
                keep = [i for i, (n, _, _) in enumerate(ln["events"])
                        if not n.startswith(("scheduler.", "engine.c",
                                             "engine.u", "engine.di",
                                             "engine.f"))]
                ln["events"] = [ln["events"][i] for i in keep]
                ln["stats"] = [ln["stats"][i] for i in keep]
        tr = P.Trace(planes)
        assert set(tr.spans) == {"engine.prefill", "engine.decode"}
        assert tr.clock_slack is not None
        monkeypatch.setattr(P, "trace_path", lambda f: NEW)
        monkeypatch.setattr(P, "load", lambda path: tr)
        assert _read(name, facts) is None
        return
    if case == "rehearsal":              # the CPU run: no device plane
        facts["trace"] = {"busy_s": 0.0, "window_s": 0.0, "devices": 0}
        monkeypatch.setattr(P, "trace_path", lambda f: NEW)
    elif case == "no_kept_trace":
        monkeypatch.setattr(P, "trace_path", lambda f: None)
    else:       # PR 23's trace: the runner's two wrappers and no other span
        monkeypatch.setattr(P, "trace_path", lambda f: os.path.join(
            FIX, "v5e_cb_prefill_decode.json.gz"))
    assert _read(name, facts) is None


def test_kept_trace_is_found_by_the_cells_name(tmp_path, monkeypatch):
    monkeypatch.setattr(P, "ROOT", str(tmp_path))
    assert P.trace_path(FACTS) is None
    run = tmp_path / ".bench_trace" / "serve-chat-r80" / "plugins" / \
        "profile" / "2026_09_27"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(b"")
    assert P.trace_path(FACTS) == str(run / "host.xplane.pb")
    assert P.trace_path({"cell": "serve-code-sat"}) is None


def test_old_reducer_reads_the_new_fixture_and_keeps_its_numbers():
    """The new spans are not among the names the reducer attributes by,
    so the runner's wrappers still own the programs; and the two old
    fixtures reduce to what PR 23 recorded."""
    new = R.reduce(NEW)
    by = new["modules_by_span"]
    assert by["engine.decode"]["main"] == "jit_cb_decode"
    assert by["engine.decode"]["runs"] == 5
    assert by["engine.prefill"]["main"] == "jit_cb_prefill"
    assert by["engine.prefill"]["runs"] == 1
    assert set(dict(new["breakdown"]["idle_gaps"])) <= {
        "engine.decode", "engine.prefill", "no_span"}
    emitted = {n for p in P.read_planes(NEW) for ln in p["lines"]
               for n, _, _ in ln["events"] if n.startswith(P.PROGRAM_PREFIXES)}
    assert not emitted & {"host.fetch", "sched.admit"}
    cb = R.reduce(os.path.join(FIX, "v5e_cb_prefill_decode.json.gz"))
    assert cb["window_s"] == pytest.approx(0.184933, rel=1e-4)
    assert cb["busy_s"] == pytest.approx(0.171065, rel=1e-4)
    scan = R.reduce(os.path.join(FIX, "v5e_train_scan_step.json.gz"))
    assert scan["window_s"] - scan["busy_s"] == pytest.approx(2.2e-5,
                                                               rel=0.05)


def test_interval_arithmetic():
    a, b = [(0.0, 2.0), (3.0, 5.0)], [(1.0, 4.0), (4.5, 6.0)]
    assert P.intersect(a, b) == [(1.0, 2.0), (3.0, 4.0), (4.5, 5.0)]
    assert P.overlap(a, b) == pytest.approx(2.5)
    assert P.intersect(a, []) == [] and P.overlap([], b) == 0.0
