"""The Trinity-Mini configuration's manifest: every published number
kept under its key, the three reduced keys with the published counts
and the deployment beside them, the parameter count and the per-layer
figures of ISSUE 35 reckoned again from the leaf table, the bytes
resident at 64 slots by kind of cache, the bytes a decode step and one
paged-kernel call cannot avoid, the cell, its readers, and its
rehearsal on the CPU."""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, trinity_opcount, trinity_weights  # noqa: E402

NAME = "trinity-mini-serve-l16-ep8"
CELL = "serve-trinity-agent-sat"
MANIFEST = harness.read_json(ROOT, "BENCHMARK.json")
ENTRY, = [c for c in MANIFEST["configs"] if c["name"] == NAME]
CFG = harness.read_json(ROOT, ENTRY["file"])
REDUCED = {"num_hidden_layers": (16, 32), "num_experts": (16, 128),
           "vocab_size": (25024, 200192)}
READERS = {"decode_step_ms.trinity", "prefill_ms.trinity",
           "slot_occupancy.trinity", "step_host_ms.trinity",
           "device_idle.trinity", "decode_roofline.trinity",
           "window_block_share.trinity", "expert_tokens_per_step.trinity",
           "expert_max_load.trinity", "paged_roofline.trinity"}


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog beside the model-configs guide is not here")
    with open(path) as f:
        row, = [r for r in map(json.loads, f) if r["name"] == "Trinity-Mini"]
    return row


def test_every_published_number_is_kept_under_its_key():
    row = _catalog()
    assert ENTRY["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            here, published = REDUCED[key]
            assert CFG[key] == here and CFG["reduced_from"][key] \
                == value == published
        else:
            assert CFG[key] == value, key


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 2048), ("num_attention_heads", 32),
    ("num_key_value_heads", 4), ("head_dim", 128),
    ("intermediate_size", 6144), ("moe_intermediate_size", 1024),
    ("n_routed_experts", 128), ("num_experts_per_tok", 8),
    ("num_shared_experts", 1), ("sliding_window", 2048),
    ("global_attn_every_n_layers", 4), ("num_dense_layers", 2),
    ("route_scale", 2.826), ("route_norm", True), ("score_func", "sigmoid"),
    ("rope_theta", 10000), ("rms_norm_eps", 1e-5), ("mup_enabled", True),
    ("tie_word_embeddings", False)])
def test_published_widths(key, value):
    """What `test_bench_manifest.py` cannot hold this file to (it holds
    every configuration to Mistral's sizes): ITS published sizes."""
    assert CFG[key] == value


def test_reduced_is_the_chips_share_with_the_deployment_beside():
    assert ENTRY["reduced"] == list(REDUCED)
    assert CFG["reduced_from"] == {k: v[1] for k, v in REDUCED.items()}
    assert (CFG["first_held_expert"], CFG["first_vocab_id"]) == (0, 0)
    types = CFG["layer_types"]
    assert len(types) == 32                     # the published list, whole
    built = types[:CFG["num_hidden_layers"]]
    assert built == (["sliding_attention"] * 3 + ["full_attention"]) * 4
    for word in ("16 v5e chips", "two pipeline stages", "8-way", "all 64 slots",
                 "4 tokens a step", "more than one chip's share"):
        assert word in CFG["deployment"], word
    for word in ("26.12 B", "52.2 GB", "experts 0-15", "0-25,023",
                 "four whole periods"):
        assert word in CFG["why_reduced"], word
    for word in ("gate", "q and k norms", "NoPE", "AFTER the sublayers",
                 "45.2548", "balancing bias", "1e-20", "paired with dim", "no biases"):
        assert any(word in line or word.lower() in line.lower()
                   for line in CFG["assumed"]), word


def test_parameter_count_and_the_per_layer_figures():
    """ISSUE 35: a sparse layer 134.49 M here (839.1 M whole), a dense
    layer 65.02 M, embedding and head 2 x 25,024 x 2,048, 2,115.4 M =
    4.23 GB in bf16."""
    assert trinity_weights.param_count(CFG) == CFG["params"] == 2115378944
    part = {k: round(v / 1e6, 2)
            for k, v in trinity_weights.counts_by_part(CFG).items()}
    assert part == {"dense_layer": 65.02, "moe_layer": 134.49,
                    "attention": 27.26, "held_experts": 100.66,
                    "router_and_shared": 6.55, "embed_and_head": 102.5}
    assert trinity_opcount.expert_params(CFG) == 3 * 2048 * 1024
    whole = dict(CFG, num_experts=128, num_hidden_layers=32,
                 vocab_size=200192)
    assert round(trinity_weights.param_count(whole) / 1e9, 2) == 26.12
    names = [n for n, _, _ in trinity_weights.leaf_table(CFG)]
    assert "head" in names and "L0.ffn.w_gate" in names
    assert "L2.moe.router_bias" in names and "L1.moe.router" not in names
    assert "L3.attention.wg" in names and "L3.mix_post_norm" in names


def test_resident_bytes_at_64_slots_by_kind():
    """bf16 weights 4.23 GB; the 4 full layers' growing blocks 4 x (64 x
    512 + 1) x 32 KB = 4.30 GB; the 12 windowed layers' rings 12 x (64 x
    129 + 1) x 32 KB = 3.25 GB: 11.8 GB of the chip's 16, where one
    table for all 16 layers would need 17.2 GB."""
    from benchmark.runners import serve_trinity
    sv = CFG["serve"]
    assert (sv["cb_slots"], sv["cb_block_len"], sv["cb_prompt_cap"],
            sv["max_new_tokens"], sv["dtype"]) == (64, 16, 2048, 6144,
                                                   "bfloat16")
    got = serve_trinity.resident_bytes(CFG)
    assert got["weights"] == 2 * CFG["params"]
    assert trinity_opcount.kv_row_bytes(CFG, 2) == 2048
    assert round(got["weights"] / 1e9, 2) == 4.23
    assert round(got["full_blocks"] / 1e9, 2) == 4.30
    assert round(got["window_rings"] / 1e9, 2) == 3.25
    total = got["weights"] + got["full_blocks"] + got["window_rings"]
    assert round(total / 1e9, 1) == 11.8 and total > 0.25 * 16e9
    one_table = 16 * (64 * 512 + 1) * 16 * 2048
    assert round(one_table / 1e9, 1) == 17.2


def test_the_bytes_a_decode_step_and_a_kernel_call_cannot_avoid():
    """64 busy slots at a context of 3,000: the full layers read every
    row, the windowed 2,048 of them."""
    n = trinity_opcount.layer_counts(CFG)
    assert n == {"full": 4, "sliding": 12, "moe": 14}
    e = trinity_opcount.expert_params(CFG) * 2
    fixed = trinity_opcount.fixed_params(CFG) * 2
    assert round(fixed / 1e9, 2) == 1.31
    assert round(14 * 16 * e / 1e9, 2) == 2.82
    live, window = 64 * 3000, 64 * 2048
    assert trinity_opcount.paged_call_bytes(CFG, window, 2) == 64 * 2048 * 2048
    paged = trinity_opcount.paged_step_bytes(CFG, live, window, 2)
    assert paged == (4 * live + 12 * window) * 2048
    whole = trinity_opcount.decode_step_needed_bytes(
        CFG, 64, live, window, 14 * 16, 2)
    assert whole == fixed + 14 * 16 * e + paged
    assert 8.8e9 < whole < 9.0e9
    fewer = trinity_opcount.decode_step_needed_bytes(
        CFG, 64, live, window, 14 * 13, 2)
    assert whole - fewer == 14 * 3 * e
    assert trinity_opcount.decode_step_flops(
        CFG, 64, live, window, 14 * 64 * 8 / 8) > 0


def test_the_cell_and_its_traffic():
    cell = harness.Cell(CELL)
    assert cell.entry["chips"] == 1 and cell.spec["runner"] == "serve_trinity"
    assert cell.spec["at_window_end"] == "cancel"
    assert cell.spec["preroll_of_window"] == pytest.approx(2 / 3, abs=1e-3)
    assert cell.spec["check_requests"] == 6
    mix = cell.traffic
    assert mix["generator"] == "open_loop" and mix["arrivals"] == "poisson"
    assert mix["prompt"] == {"dist": "lognormal", "median": 1024,
                             "sigma": 0.7, "lo": 128, "hi": 2048}
    assert mix["output"] == {"dist": "lognormal", "median": 1536,
                             "sigma": 0.7, "lo": 256, "hi": 6144}
    assert mix["schedule_seed"] == 35
    assert mix["rate_rps"] > 0 and "knee" in mix["rate_from"]
    sweep = harness.read_json(ROOT, "benchmark", "workloads", "sweeps",
                              "agent-full-house.json")
    assert sweep["workload"] == CELL
    assert mix["rate_rps"] == pytest.approx(1.25 * sweep["knee_rps"],
                                            rel=0.01)
    assert set(cell.spec["limits"]) == {"served_gap", "served_gap_mean"}
    for word in ("fp8", "no_window", "sound"):
        assert word in cell.spec["limits_from"], word
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert names == READERS | {"compile_s"}
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "out_tok_s", "setup_s"}
    out_tok_s, = [m for m in MANIFEST["end_to_end"]
                  if m["name"] == "out_tok_s"]
    assert CELL in out_tok_s["workloads"] and out_tok_s["bound"] == 0.01
    # a longest request past 4,096 positions exists in the schedule and
    # can finish inside the 75 s a run lasts
    from benchmark.traffic import open_loop
    reqs = open_loop.generate(mix, 1, 75.0, CFG["vocab_size"])
    assert any(len(r.tokens) + r.max_new > 4096 and r.due_s < 15
               and r.max_new < 3800 for r in reqs)


def test_program_names_cover_the_nets_parameters():
    from benchmark.runners import serve_trinity
    from singa_tpu.core.net import build_net
    from singa_tpu.data import discover_input_shapes
    tiny = harness._tiny(CFG)
    model = serve_trinity.model_config(tiny, 16)
    net = build_net(model, "kTrain",
                    discover_input_shapes(model, force_synthetic=True))
    table = {trinity_weights.program_name(n): tuple(s)
             for n, s, _ in trinity_weights.leaf_table(tiny)}
    assert table == {k: tuple(v.shape) for k, v in net.param_specs.items()}
    assert not net.param_aliases                          # untied head


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "benchmark", "reference",
                            "trinity.py")).read()
    assert "import singa_tpu" not in src and "from singa_tpu" not in src
    assert "from benchmark" not in src and "import benchmark" not in src


def test_the_runner_binds_its_own_names_only_for_the_length_of_a_call():
    from benchmark import kimi_weights
    from benchmark.runners import serve_kimi, serve_trinity
    assert serve_kimi.kimi_weights is kimi_weights
    spans = serve_kimi._Spans
    with serve_trinity._bound():
        assert serve_kimi.kimi_weights is trinity_weights
        assert serve_kimi.model_config is serve_trinity.model_config
        assert serve_kimi._Spans is serve_trinity._Spans
    assert serve_kimi.kimi_weights is kimi_weights
    assert serve_kimi._Spans is spans
    assert serve_kimi.model_config is not serve_trinity.model_config


def test_readers_return_none_where_there_is_nothing_to_read():
    """A program without the counters, a run without a trace or without
    the runner's window rows: the new readers return None and do not
    raise."""
    facts = {"cell": CELL, "config": CFG, "counters": {"cb_steps": 0},
             "spans": [("engine.decode", 0.0, 1.0, 5)], "trace_span": (0, 2),
             "trace": {"modules_by_span": {"engine.decode": {
                 "seconds": 1.0, "runs": 1}}},
             "peaks": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0},
             "itemsize": 2}
    cell = harness.Cell(CELL)
    for name in sorted(READERS - {"decode_step_ms.trinity",
                                  "device_idle.trinity"}):
        assert cell.load("layer_metrics", name).read(facts) is None, name
    # the decode rows without the window's: no roofline is made up
    facts["spans"] = [("engine.decode", 0.5, 0.6, 64000, 64, 200, 500)]
    assert cell.load("layer_metrics", "decode_roofline.trinity").read(
        facts) is None
    facts["counters"] = {"cb_routed_layer_steps": 14,
                         "cb_routed_assignments": 896, "cb_steps": 0}
    assert cell.load("layer_metrics", "expert_max_load.trinity").read(
        facts) is None
    assert cell.load("layer_metrics", "expert_tokens_per_step.trinity").read(
        facts) == 4.0


def test_readers_read_the_steps_own_counts():
    live, window = 64 * 3000, 64 * 2048
    rows = [("engine.decode", 0.5, 0.6, live, 64, 14 * 13, 14 * 64),
            ("engine.window", 0.5, 0.5, window)]
    facts = {"cell": CELL, "config": CFG, "spans": rows,
             "trace_span": (0.0, 1.0), "itemsize": 2,
             "trace": {"modules_by_span": {
                 "engine.decode": {"main": "jit_cb_decode",
                                   "seconds": 0.030, "runs": 2},
                 "no_span": {"main": "jit_cb_decode", "seconds": 0.015,
                             "runs": 1},
                 "engine.prefill": {"main": "jit_cb_prefill",
                                    "seconds": 0.06, "runs": 1}},
                 "ops": {"singa_paged_decode": 0.024, "fusion": 0.018}},
             "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    cell = harness.Cell(CELL)
    got = cell.load("layer_metrics", "decode_roofline.trinity").read(facts)
    need = trinity_opcount.decode_step_needed_bytes(
        CFG, 64, live, window, 14 * 13, 2)
    assert got == pytest.approx(100 * need / 819e9 / 0.015)
    assert got < 100
    got = cell.load("layer_metrics", "paged_roofline.trinity").read(facts)
    paged = trinity_opcount.paged_step_bytes(CFG, live, window, 2)
    # three runs of the decode program lie in the trace, two of them
    # under the runner's annotation: the kernel's rows hold all three
    assert got == pytest.approx(100 * 3 * paged / 819e9 / 0.024)
    assert got < 100
    c = {"cb_routed_layer_steps": 1400, "cb_routed_assignments": 89600,
         "cb_routed_max_load": 14000, "cb_decode_steps": 100,
         "cb_live_block_steps": 1200000, "cb_window_block_steps": 819200,
         "cb_slots": 64}
    read = lambda name: cell.load("layer_metrics", name).read(  # noqa: E731
        {"config": CFG, "counters": c})
    assert read("expert_tokens_per_step.trinity") == pytest.approx(4.0)
    assert read("expert_max_load.trinity") == pytest.approx(2.5)
    assert read("window_block_share.trinity") == pytest.approx(68.2667,
                                                               abs=1e-3)


@pytest.fixture(scope="module")
def rehearsal():
    from benchmark import run as bench_run
    out = io.StringIO()
    with redirect_stdout(out):
        assert bench_run.main(["--workload", CELL, "--seed",
                               str(2 ** 31 + 35), "--seconds", "3",
                               "--trace", "1", "--rehearsal", "1"]) == 0
    text = out.getvalue()
    return json.loads(text.strip().splitlines()[-1]), text


def test_rehearsal_serves_tokens_the_reference_puts_first(rehearsal):
    """float32 on the CPU: every served token is the reference's own
    choice, through rings that wrap (a window of 8 under contexts to
    56) and growing tables, the window opened onto a house already
    running."""
    line, text = rehearsal
    assert line["correct"] is True and line["failed"] == 0
    assert line["counts"]["served_tokens_compared"] > 0
    gaps = [float(row.split(": ")[1].split(" ")[0])
            for row in text.splitlines()
            if row.startswith("compared served_gap")]
    assert len(gaps) == 2 and max(gaps) < 1e-3
    assert "compared compiles_in_window: 0" in text
    assert "resident: {'params': " in text


def test_rehearsal_finds_the_counter_readers(rehearsal):
    """Those that need no device trace find something to read, and the
    window bites: the rings' walk reads less than the tables'."""
    line, text = rehearsal
    assert set(line["readers"]) >= {
        "compile_s", "decode_step_ms.trinity", "prefill_ms.trinity",
        "slot_occupancy.trinity", "expert_tokens_per_step.trinity",
        "expert_max_load.trinity", "window_block_share.trinity"}
    counters = next(r for r in text.splitlines() if r.startswith("counters"))
    found = json.loads(counters.split(": ", 1)[1].replace("'", '"'))
    assert 0 < found["cb_window_block_steps"] < found["cb_live_block_steps"]
    assert found["cb_routed_max_load"] > 0 and found["cb_block_bytes"] > 0
