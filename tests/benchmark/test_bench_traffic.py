"""The generators: deterministic in the seed, due times reported, the
same set of sizes for every seed, the stated medians."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, stats  # noqa: E402
from benchmark.traffic import open_loop, token_batches  # noqa: E402

MIXES = ["chat-r80", "code-sat"]


def mix(name):
    return harness.read_json(ROOT, "benchmark", "traffic", name + ".json")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = open_loop.generate(mix(name), 3000000019, 20.0, 32768)
    b = open_loop.generate(mix(name), 3000000019, 20.0, 32768)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))
    assert [r.max_new for r in a] == [r.max_new for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_replays_one_schedule_with_other_tokens(name):
    a = open_loop.generate(mix(name), 1, 20.0, 32768)
    b = open_loop.generate(mix(name), 2 ** 31 + 7, 20.0, 32768)
    assert len(a) == len(b) == round(mix(name)["rate_rps"] * 20.0)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert [len(r.tokens) for r in a] == [len(r.tokens) for r in b]
    assert [r.max_new for r in a] == [r.max_new for r in b]
    assert not all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))
    whole = open_loop.gaps(mix(name)["rate_rps"], len(a))
    for d in np.diff([r.due_s for r in a]):   # every gap is one of the set
        assert np.isclose(whole, d, rtol=1e-9, atol=1e-12).any()
    assert whole.sum() == pytest.approx(20.0)
    other = open_loop.generate(dict(mix(name), schedule_seed=1), 1, 20.0, 32768)
    assert sorted(len(r.tokens) for r in other) == sorted(len(r.tokens)
                                                          for r in a)
    assert [len(r.tokens) for r in other] != [len(r.tokens) for r in a]


@pytest.mark.parametrize("name", MIXES)
def test_due_times_are_an_open_loop_inside_the_window(name):
    reqs = open_loop.generate(mix(name), 5, 30.0, 32768)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 30.0
    mean_gap = (due[-1] - due[0]) / (len(due) - 1)
    assert mean_gap == pytest.approx(1.0 / mix(name)["rate_rps"], rel=0.05)


@pytest.mark.parametrize("name,part", [(n, p) for n in MIXES
                                       for p in ("prompt", "output")])
def test_clipped_lognormal_lengths_have_the_stated_median(name, part):
    spec = mix(name)[part]
    xs = open_loop.lengths(spec, 400)
    assert spec["lo"] <= xs.min() and xs.max() <= spec["hi"]
    assert stats.median(list(xs)) == pytest.approx(spec["median"], rel=0.02)


def test_lengths_refuse_an_unknown_distribution():
    with pytest.raises(ValueError):
        open_loop.lengths({"dist": "zipf"}, 3)


def test_token_batches_rows_differ_and_repeat_for_a_seed():
    m = {"seq_len": 64}
    a = next(token_batches.batches(m, 9, 4, 1000))["data"]
    b = next(token_batches.batches(m, 9, 4, 1000))["data"]
    assert a["input"].shape == (4, 64) and a["input"].dtype == np.int32
    assert np.array_equal(a["input"], b["input"])
    assert np.array_equal(a["input"][:, 1:], a["target"][:, :-1])
    assert len({row.tobytes() for row in a["input"]}) == 4
    c = next(token_batches.batches(m, 2 ** 31 + 1, 4, 1000))["data"]
    assert not np.array_equal(a["input"], c["input"])
