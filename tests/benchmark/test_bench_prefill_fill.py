"""`prefill_fill.chat` (PR 28): prompt tokens over compiled rows of the
traced `scheduler.prefill` spans, on the trimmed v5e trace of
tests/benchmark/test_bench_program_spans.py.  That trace is from before
the prefill ladder: its one prefill span (plen 271) carries no `width`,
which is what the parent of PR 28 gives under these benchmark files."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.layer_metrics import _program_spans as P  # noqa: E402

NEW = os.path.join(ROOT, "benchmark", "trace", "fixtures",
                   "v5e_cb_program_spans.json.gz")
MANIFEST = harness.read_json(ROOT, "BENCHMARK.json")
NAME = "prefill_fill.chat"
FACTS = {"cell": "serve-chat-r80", "trace": {"devices": 1}}


def _read(facts):
    return harness.Cell("serve-chat-r80").load("layer_metrics",
                                               NAME).read(facts)


def _with_prefills(monkeypatch, rows):
    """The fixture's trace with its `scheduler.prefill` span repeated
    once per (plen, width) of `rows` (width None: the attribute is
    left out)."""
    planes = P.read_planes(NEW)
    for p in planes:
        for ln in p["lines"]:
            events, stats = [], []
            for ev, st in zip(ln["events"], ln["stats"]):
                if ev[0] != "scheduler.prefill":
                    events.append(ev)
                    stats.append(st)
                    continue
                for plen, width in rows:
                    new = dict(st, plen=plen)
                    if width is not None:
                        new["width"] = width
                    events.append(ev)
                    stats.append(new)
            ln["events"], ln["stats"] = events, stats
    tr = P.Trace(planes)
    monkeypatch.setattr(P, "_CACHE", {})
    monkeypatch.setattr(P, "trace_path", lambda facts: NEW)
    monkeypatch.setattr(P, "load", lambda path: tr)
    return tr


def test_manifest_entry():
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_span",
                     "layer": "serving engine (serve/engine.py)",
                     "moves": "ttft_p50_ms",
                     "workloads": ["serve-chat-r80"]}
    assert MANIFEST["per_layer"][-1] is entry      # appended, nothing moved
    cell = harness.Cell("serve-chat-r80")
    assert NAME in {m["name"] for m in cell.metrics("per_layer")}
    for other in ("serve-code-sat", "serve-kimi-decode-sat",
                  "train-s4096-1chip"):
        assert NAME not in {m["name"] for m in
                            harness.Cell(other).metrics("per_layer")}


@pytest.mark.parametrize("rows,want", [
    ([(271, 512)], 100 * 271 / 512),
    ([(128, 256), (300, 512), (16, 256)], 100 * 444 / 1024),
    ([(256, 256), (512, 512)], 100.0),
    ([(128, 1024), (512, 1024)], 100 * 640 / 2048),     # one rung: the cap
])
def test_fill_is_the_sum_of_plen_over_the_sum_of_width(monkeypatch, rows,
                                                       want):
    tr = _with_prefills(monkeypatch, rows)
    assert len(tr.spans["scheduler.prefill"]) == len(rows)
    assert _read(FACTS) == pytest.approx(want)
    assert 0 < _read(FACTS) <= 100


@pytest.mark.parametrize("case", ["parent", "one_span_without_width",
                                  "no_prefill_in_the_trace", "rehearsal",
                                  "no_kept_trace"])
def test_none_where_there_is_nothing_to_read(monkeypatch, case):
    monkeypatch.setattr(P, "_CACHE", {})
    facts = dict(FACTS)
    if case == "parent":               # the fixture as it is: no `width`
        monkeypatch.setattr(P, "trace_path", lambda f: NEW)
        (_, _, st), = P.of(facts).spans["scheduler.prefill"]
        assert st["plen"] == 271 and "width" not in st
    elif case == "one_span_without_width":
        _with_prefills(monkeypatch, [(100, 256), (200, None)])
    elif case == "no_prefill_in_the_trace":
        _with_prefills(monkeypatch, [])
    elif case == "rehearsal":          # the CPU run: no device plane
        facts["trace"] = {"busy_s": 0.0, "window_s": 0.0, "devices": 0}
        monkeypatch.setattr(P, "trace_path", lambda f: NEW)
    else:
        monkeypatch.setattr(P, "trace_path", lambda f: None)
    assert _read(facts) is None
