"""The readers of the serving step's paths (PR 37,
benchmark/layer_metrics/_step_paths.py): one partition of a traced
span's device-idle time by the innermost span of the scheduler's loop,
the share of decode steps that went ahead, what a step that did not
costs, and the stall account's running values.  Held on a trace written
out by hand (every part's seconds known) and on two trimmed v5e traces
of PR 37's tree (benchmark/trace/fixtures/; the loop's spans with their
stats, the runner's wrappers, the runtime's enqueue / completion events
and the program runs, as PR 24's fixture has them):
`v5e_cb_full_house_spans.json.gz`, thirteen steps of
`serve-trinity-agent-sat` (seed 2147484904): twelve ahead, one admission
behind a step in flight, the first step of the refilled house;
`v5e_cb_round_trip_spans.json.gz`, nine steps of `serve-kimi-decode-sat`
(seed 2147484901), every one a round trip, two admissions into a house
with nothing in flight.  No traced run of any cell held both paths (the
Kimi cell's house was never full in three traced runs, the others'
always) or a drain (`why` 1): the trace by hand and
tests/test_scheduler_paths.py hold those."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.layer_metrics import _program_spans as P  # noqa: E402
from benchmark.layer_metrics import _step_paths as SP  # noqa: E402
from benchmark.trace import reduce as R  # noqa: E402

FIX = os.path.join(ROOT, "benchmark", "trace", "fixtures")
NEW = os.path.join(FIX, "v5e_cb_full_house_spans.json.gz")
TRIPS = os.path.join(FIX, "v5e_cb_round_trip_spans.json.gz")
PR24 = os.path.join(FIX, "v5e_cb_program_spans.json.gz")
MANIFEST = harness.read_json(ROOT, "BENCHMARK.json")
CELLS = ["serve-code-sat", "serve-kimi-decode-sat", "serve-zaya-reason-sat",
         "serve-trinity-agent-sat"]
IDLE = ["idle_decode_handover", "idle_decode_wait", "idle_emit",
        "idle_admit", "idle_step_rest", "idle_no_work", "idle_unnamed"]
METRICS = IDLE + ["step_ahead_share", "round_trip_host_ms", "stall_s",
                  "stall_wait_s"]
FACTS = {"cell": "serve-kimi-decode-sat", "trace": {"devices": 1}}
MS = 1e-3


def _read(name, facts=FACTS):
    cell = harness.Cell("serve-kimi-decode-sat")
    return cell.load("layer_metrics", name).read(facts)


def _planes(host, busy):
    """Planes as `_program_spans.read_planes` gives them, from
    (name, start_ms, end_ms[, stats]) rows and busy (start_ms, end_ms)."""
    events = [(r[0], r[1] * MS, r[2] * MS) for r in host]
    stats = [(r[3] if len(r) > 3 else {}) for r in host]
    return [
        {"name": "/host:CPU", "lines": [
            {"name": "serve-cb", "events": events, "stats": stats}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": R.OPS_LINE, "stats": [None] * len(busy),
             "events": [("fusion", s * MS, e * MS) for s, e in busy]}]}]


# The device runs in [0, 10], [14, 24], [30, 40], [50, 60] ms: 20 ms
# idle of 60.  Two round trips, an admitting step, a wait with nothing
# to do, a step ahead; and what lies under each, to the tenth of a ms.
BUSY = [(0, 10), (14, 24), (30, 40), (50, 60)]
HAND = [
    ("scheduler.step", 0.5, 12, {"stall_ms": 0, "stall_wait_ms": 0}),
    ("scheduler.decode", 0.6, 11.9, {"active": 3, "ahead": 0}),
    ("engine.cb_decode", 1, 10.5), ("engine.upload", 1, 1.2),
    ("engine.dispatch", 1.2, 2), ("engine.fetch", 2, 10.5),
    ("scheduler.emit", 10.5, 11.5, {"slots": 3}),
    ("scheduler.step", 12.5, 26, {"stall_ms": 0, "stall_wait_ms": 0}),
    ("scheduler.decode", 12.6, 25.9, {"active": 3, "ahead": 0}),
    ("engine.cb_decode", 13, 24.5), ("engine.upload", 13, 13.2),
    ("engine.dispatch", 13.2, 14.5), ("engine.fetch", 14.5, 24.5),
    ("scheduler.emit", 24.5, 25.5, {"slots": 3}),
    ("scheduler.step", 26.5, 45, {"stall_ms": 1234, "stall_wait_ms": 0}),
    ("scheduler.admit_pending", 27, 44),
    ("scheduler.prefill", 27.5, 43.5, {"plen": 200, "width": 256}),
    ("engine.cb_prefill", 28, 30.5, {"width": 256}),
    ("engine.cb_prefill_fetch", 30.5, 43),
    ("scheduler.wait", 45, 48),
    ("scheduler.step", 49, 59.5, {"stall_ms": 1234, "stall_wait_ms": 234}),
    ("scheduler.decode", 49.1, 59, {"active": 4, "ahead": 1}),
    ("engine.cb_decode", 49.2, 50.5), ("engine.upload", 49.2, 49.4),
    ("engine.dispatch", 49.4, 50.5),
    ("scheduler.collect", 50.5, 58, {"why": 0}),
    ("engine.fetch", 50.5, 57), ("scheduler.emit", 57, 58, {"slots": 4}),
    # another thread's, and the runner's wrapper: neither is the loop's
    ("scheduler.admit", 12.1, 12.3), ("engine.decode", 0.9, 10.6),
]
BY_HAND = {"idle_decode_wait": 1.0, "idle_emit": 2.0,
           "idle_decode_handover": 1.8, "idle_admit": 7.0,
           "idle_step_rest": 3.2, "idle_no_work": 3.0, "idle_unnamed": 2.0}


@pytest.fixture()
def by_hand(monkeypatch):
    tr = P.Trace(_planes(HAND, BUSY))
    monkeypatch.setattr(P, "trace_path", lambda facts: "by-hand")
    monkeypatch.setattr(P, "_CACHE", {"by-hand": tr})
    return tr


def test_the_partition_by_hand(by_hand):
    assert (by_hand.lo, by_hand.hi) == (0.0, 60 * MS)
    assert sum(e - s for s, e in by_hand.idle) == pytest.approx(20 * MS)
    parts = SP.idle_partition(FACTS)
    assert parts["span"] == pytest.approx(60 * MS)
    for name, ms in BY_HAND.items():
        assert parts[name[len("idle_"):]] == pytest.approx(ms * MS), name
        assert _read(name) == pytest.approx(100 * ms / 60), name
    # one partition: the seven sum to the trace's own idle share
    assert sum(_read(n) for n in IDLE) == pytest.approx(100 * 20 / 60)
    assert sum(BY_HAND.values()) == pytest.approx(20)


def test_steps_ahead_and_what_the_others_cost_by_hand(by_hand):
    assert _read("step_ahead_share") == pytest.approx(100 / 3)
    # the two round trips idle 2.0 and 1.5 + 2.0 ms; the admitting step
    # has no decode and the step ahead is not counted
    assert _read("round_trip_host_ms") == pytest.approx(5.5 / 2)
    # the account since the scheduler started, off the LAST step
    assert _read("stall_s") == pytest.approx(1.234)
    assert _read("stall_wait_s") == pytest.approx(0.234)


def test_a_span_that_straddles_an_edge_keeps_its_part_inside(monkeypatch):
    """`Trace.intervals` drops such a span whole; a partition may not."""
    host = [("scheduler.step", -2, 3, {}), ("scheduler.emit", 1, 2),
            ("scheduler.step", 8, 14, {})]
    tr = P.Trace(_planes(host, [(0, 1), (2.5, 9), (11, 12)]))
    monkeypatch.setattr(P, "trace_path", lambda facts: "edges")
    monkeypatch.setattr(P, "_CACHE", {"edges": tr})
    parts = SP.idle_partition(FACTS)
    assert parts["emit"] == pytest.approx(1 * MS)          # 1 .. 2
    assert parts["step_rest"] == pytest.approx(2.5 * MS)   # 2 .. 2.5, 9 .. 11
    assert parts["unnamed"] == pytest.approx(0.0)
    assert not tr.intervals("scheduler.step")


@pytest.mark.parametrize("name", METRICS)
@pytest.mark.parametrize("case", ["rehearsal", "no_kept_trace", "pr24_trace",
                                  "parent_under_these_files",
                                  "no_device_op"])
def test_readers_return_none_where_there_is_nothing_to_read(
        monkeypatch, tmp_path, name, case):
    monkeypatch.setattr(P, "_CACHE", {})
    facts = dict(FACTS)
    if case == "rehearsal":              # the CPU run: no device plane
        facts["trace"] = {"busy_s": 0.0, "window_s": 0.0, "devices": 0}
        monkeypatch.setattr(P, "trace_path", lambda f: NEW)
    elif case == "no_kept_trace":
        monkeypatch.setattr(P, "trace_path", lambda f: None)
    elif case == "pr24_trace":           # a program with PR 24's spans only
        monkeypatch.setattr(P, "trace_path", lambda f: PR24)
        assert P.of(facts) is not None and SP.of(facts) is None
    elif case == "parent_under_these_files":
        # PR 37's parent traced with its benchmark files: every span but
        # the six that PR brought, and no attribute it added
        new = ("scheduler.collect", "scheduler.emit", "scheduler.wait",
               "engine.cb_prefill", "engine.cb_prefill_fetch")
        host = [(r[0], r[1], r[2], {k: v for k, v in (r[3] if len(r) > 3
                                                       else {}).items()
                                    if k not in ("ahead", "stall_ms",
                                                 "stall_wait_ms")})
                for r in HAND if r[0] not in new]
        tr = P.Trace(_planes(host, BUSY))
        monkeypatch.setattr(P, "trace_path", lambda f: "parent")
        monkeypatch.setattr(P, "_CACHE", {"parent": tr})
        assert P.step_host_parts(facts) is not None   # PR 24's still read
    else:                                # spans, and the device ran nothing
        import gzip
        import json
        path = str(tmp_path / "idle.json.gz")
        with gzip.open(path, "wt") as f:
            json.dump(_planes(HAND, []), f)
        monkeypatch.setattr(P, "trace_path", lambda f: path)
    assert _read(name, facts) is None


@pytest.mark.parametrize("name", METRICS)
def test_manifest_entry_and_file(name):
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry["workloads"] == CELLS and entry["moves"] == "out_tok_s"
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    before = {m["layer"] for m in MANIFEST["per_layer"]
              if m["name"] not in METRICS}
    assert entry["layer"] in before            # an existing layer's string
    want = {"idle_admit": "serving engine (serve/engine.py)",
            "idle_unnamed": "device"}.get(name,
                                          "scheduler (serve/scheduler.py)")
    assert entry["layer"] == want
    unit, source = {"step_ahead_share": ("%", "program_span"),
                    "round_trip_host_ms": ("ms", "device_trace"),
                    "stall_s": ("s", "program_counter"),
                    "stall_wait_s": ("s", "program_counter")}.get(
        name, ("%", "device_trace"))
    assert (entry["unit"], entry["source"]) == (unit, source)
    assert entry["better"] == ("higher" if name == "step_ahead_share"
                               else "lower")
    for cell in CELLS:
        c = harness.Cell(cell)
        assert name in {m["name"] for m in c.metrics("per_layer")}
        assert callable(c.load("layer_metrics", name).read)
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    with open(path) as f:
        assert len(f.read().splitlines()) <= 6      # a reader is its module's


def test_the_eleven_are_the_manifests_last_and_nothing_else_moved():
    assert [m["name"] for m in MANIFEST["per_layer"][-11:]] == METRICS
    other = [c["name"] for c in MANIFEST["workloads"]
             if c["name"] not in CELLS]
    for cell in other:                    # chat and training do not list them
        names = {m["name"] for m in harness.Cell(cell).metrics("per_layer")}
        assert not names & set(METRICS)


# -- the two fixtures ----------------------------------------------------------

# ms of device idle under each part, and the readings, pinned as cut
FULL = {"parts": {"admit": 0.989861, "emit": 0.0, "decode_wait": 0.064909,
                  "decode_handover": 2.119056, "step_rest": 0.14108,
                  "no_work": 0.0, "unnamed": 0.0},
        "span": 160.079358, "ahead": 100 * 10 / 11, "trip": 2.260136,
        "spans": {"scheduler.step": 13, "scheduler.decode": 13,
                  "scheduler.collect": 13, "scheduler.emit": 13,
                  "scheduler.admit_pending": 1, "engine.cb_prefill": 1,
                  "engine.cb_prefill_fetch": 1}}
ROUND = {"parts": {"admit": 5.095925, "emit": 4.23112, "decode_wait": 7.298828,
                   "decode_handover": 16.85197, "step_rest": 2.25262,
                   "no_work": 0.0, "unnamed": 0.134429},
         "span": 375.745503, "ahead": 0.0, "trip": 3.701141,
         "spans": {"scheduler.step": 9, "scheduler.decode": 9,
                   "scheduler.emit": 9, "scheduler.admit_pending": 2,
                   "engine.cb_prefill": 2, "engine.cb_prefill_fetch": 2}}


@pytest.fixture(params=[(NEW, FULL), (TRIPS, ROUND)],
                ids=["full_house", "round_trips"])
def cut(request, monkeypatch):
    path, want = request.param
    monkeypatch.setattr(P, "trace_path", lambda facts: path)
    monkeypatch.setattr(P, "_CACHE", {})
    return SP.of(FACTS), want


def test_the_seven_sum_to_the_fixtures_device_idle(cut):
    tr, want = cut
    assert {k: len(tr.spans[k]) for k in want["spans"]} == want["spans"]
    idle = sum(e - s for s, e in tr.idle)
    parts = SP.idle_partition(FACTS)
    assert parts.pop("span") == pytest.approx(want["span"] * MS)
    assert parts == pytest.approx({k: v * MS
                                   for k, v in want["parts"].items()},
                                  abs=1e-8)
    assert sum(parts.values()) == pytest.approx(idle, rel=1e-9)
    assert sum(_read(n) for n in IDLE) == pytest.approx(
        100 * idle / (tr.hi - tr.lo), rel=1e-9)
    # the trace's own idle share, a program run at each edge
    assert 100 * idle / (tr.hi - tr.lo) == pytest.approx(
        100 * sum(want["parts"].values()) / want["span"], rel=1e-5)


@pytest.mark.parametrize("name", METRICS)
def test_each_reader_reads_the_fixtures(cut, name):
    tr, want = cut
    value = _read(name)
    assert value is not None and value >= 0
    if name in IDLE:
        assert value == pytest.approx(
            100 * want["parts"][name[len("idle_"):]] / want["span"],
            abs=1e-5)
    else:
        assert value == pytest.approx({
            "step_ahead_share": want["ahead"],
            "round_trip_host_ms": want["trip"],
            "stall_s": 0.0, "stall_wait_s": 0.0}[name], abs=1e-5)


def test_what_the_two_paths_look_like(cut):
    """A full house hides the emit loop and the wait behind the device
    and pays at an admission and in the hand-over of the step after it;
    a round trip pays in every part."""
    tr, want = cut
    ahead = [st["ahead"] for _, _, st in tr.spans["scheduler.decode"]]
    why = [st["why"] for _, _, st in tr.spans.get("scheduler.collect", [])]
    if want is FULL:
        assert ahead == [1] * 11 + [0, 1] and sorted(why) == [0] * 12 + [2]
        # the one step not ahead is the one after the admission
        (_, admitted), = tr.intervals("scheduler.admit_pending")
        first, = [s for s, _, st in tr.spans["scheduler.decode"]
                  if not st["ahead"]]
        assert 0 < first - admitted < 1e-3
        assert all(st["slots"] == 64
                   for _, _, st in tr.spans["scheduler.emit"])
        # PR 24's reader of the step's parts does not see a bare fetch
        assert P.step_host_parts(FACTS)["fetch"] == 0.0
    else:
        assert ahead == [0] * 9 and why == []
        # every step a round trip: PR 24's one number IS the yardstick
        assert _read("round_trip_host_ms") == pytest.approx(
            P.step_host_parts(FACTS)["host"], rel=1e-9)
        assert {st["width"] for _, _, st in
                tr.spans["engine.cb_prefill"]} == {512}


def test_the_old_readers_and_reducer_keep_their_numbers(monkeypatch):
    """The `.chat` readers on PR 24's fixture, and the reducer on the new
    ones: the runner's wrappers still own the program runs."""
    monkeypatch.setattr(P, "trace_path", lambda facts: PR24)
    monkeypatch.setattr(P, "_CACHE", {})
    facts = {"cell": "serve-chat-r80", "trace": {"devices": 1}}
    parts = P.step_host_parts(facts)
    assert (parts["host"], parts["upload"], parts["dispatch"],
            parts["fetch"], parts["rest"]) == pytest.approx(
        (3.3430, 0.9110, 1.0631, 1.0587, 0.3101), rel=1e-3)
    assert P.queue_wait_ms(facts) == pytest.approx(40.4749, abs=1e-4)
    for path, runs in ((NEW, 13), (TRIPS, 9)):
        by = R.reduce(path)["modules_by_span"]
        assert by["engine.decode"]["main"] == "jit_cb_decode"
        assert by["engine.decode"]["runs"] + \
            by.get("no_span", {"runs": 0})["runs"] >= runs - 1
        assert by["engine.prefill"]["main"] == "jit_cb_prefill"
