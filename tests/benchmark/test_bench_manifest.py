"""The manifest and the files it names: names and units, every cell's
files found by name, the configurations' sizes."""

import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, weights  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MANIFEST = harness.read_json(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
# the published sizes (huggingface.co/mistralai/Mistral-7B-v0.3 config.json)
PUBLISHED = {"hidden_size": 4096, "intermediate_size": 14336,
             "num_attention_heads": 32, "num_key_value_heads": 8,
             "head_dim": 128, "vocab_size": 32768, "rope_theta": 1e6,
             "rms_norm_eps": 1e-5, "max_position_embeddings": 32768,
             "tie_word_embeddings": False, "sliding_window": None}


def test_manifest_has_exactly_the_contract_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert "setup_s" in {m["name"] for m in MANIFEST["end_to_end"]}
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(len(CELLS) // 4, 1)


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_names_units_and_lines(section):
    rows = MANIFEST[section]
    names = [r["name"] for r in rows]
    assert len(names) == len(set(names))
    allowed = {"configs": {"name", "source", "file", "reduced", "why"},
               "workloads": {"name", "config", "traffic", "chips", "why"},
               "end_to_end": {"name", "unit", "better", "bound", "source",
                              "workloads"},
               "per_layer": {"name", "unit", "better", "source", "layer",
                             "moves", "workloads"}}[section]
    for r in rows:
        assert NAME.match(r["name"]), r["name"]
        assert set(r) <= allowed, set(r) - allowed
        for key in ("why", "source", "layer"):
            if key in r and section != "end_to_end" and key != "source" \
                    or (key == "source" and section == "configs"):
                assert 1 <= len(r[key]) <= 200 and "\n" not in r[key] \
                    and "\t" not in r[key], (r["name"], key)
        if "unit" in r:
            assert UNIT.match(r["unit"]), r["unit"]
            assert r["better"] in ("lower", "higher")
            assert r["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
        if section == "end_to_end":
            assert r["source"] in ("host_clock", "device_trace")
            assert 0.01 <= r["bound"] <= 0.1


def test_every_config_is_used_and_its_file_is_its_own():
    used = {w["config"] for w in MANIFEST["workloads"]}
    files = [c["file"] for c in MANIFEST["configs"]]
    assert used == {c["name"] for c in MANIFEST["configs"]}
    assert len(files) == len(set(files))
    for c in MANIFEST["configs"]:
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"(_dim|_rank|hidden_size|intermediate_size"
                                 r"|head_dim)$", key), key


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_its_files_by_name(name):
    cell = harness.Cell(name)
    runner = cell.load("runners", cell.spec["runner"])
    assert callable(runner.run)
    gen = cell.load("traffic", cell.traffic["generator"])
    assert gen is not None
    e2e = {m["name"] for m in cell.metrics("end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = cell.metrics("per_layer")
    assert layer
    for m in layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])
        assert callable(cell.load("layer_metrics", m["name"]).read)


@pytest.mark.parametrize("name", CELLS)
def test_per_layer_metrics_of_one_layer_share_its_name(name):
    layers = {}
    for m in MANIFEST["per_layer"]:
        layers.setdefault(m["layer"].split(" (")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


@pytest.mark.parametrize("entry", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_config_keeps_every_published_width(entry):
    cfg = harness.read_json(ROOT, entry["file"])
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert entry["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] < cfg["reduced_from"]["num_hidden_layers"]
    assert cfg["assumed"], "sizes set without a network are listed"


@pytest.mark.parametrize("file,count,millions", [
    ("mistral7b-serve-l16", 3758231552, 3758),
    ("mistral7b-train-l2", 704663552, 704.6),
    ("mistral7b-train-l4-dp2tp2", 1140887552, 1140.8)])
def test_parameter_counts(file, count, millions):
    """The figures of ISSUE 23: per layer 218.1 M (attention 41.9 M,
    SwiGLU 176.2 M), embedding and head 134.2 M each."""
    cfg = harness.read_json(ROOT, "benchmark", "configs", file + ".json")
    assert weights.param_count(cfg) == count == cfg["params"]
    assert abs(count / 1e6 - millions) < 1.0
    table = dict((n, s) for n, s, _ in weights.leaf_table(cfg))
    attn = sum(a * b for a, b in (table["L0.wq"], table["L0.wk"],
                                  table["L0.wv"], table["L0.wo"]))
    ffn = sum(a * b for a, b in (table["L0.w_gate"], table["L0.w_up"],
                                 table["L0.w_down"]))
    assert round(attn / 1e6, 1) == 41.9 and round(ffn / 1e6, 1) == 176.2
    assert round(table["embed"][0] * table["embed"][1] / 1e6, 1) == 134.2


def test_peaks_table_has_the_v5e_with_its_source():
    peaks = harness.read_json(ROOT, "benchmark", "peaks.json")
    row = peaks["TPU v5 lite"]
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9 and row["source"]
