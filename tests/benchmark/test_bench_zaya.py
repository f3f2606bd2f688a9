"""The ZAYA1 configuration's manifest: every published number kept
under its key, depth the one reduced key with the published count and
the deployment beside it, the parameter count and the per-layer figures
of ISSUE 33 reckoned again from the leaf table, the bytes resident at
64 slots, the bytes a decode step cannot avoid, the cell, its readers,
and its rehearsal on the CPU."""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, zaya_opcount, zaya_weights  # noqa: E402

NAME = "zaya1-8b-serve-l16"
CELL = "serve-zaya-reason-sat"
MANIFEST = harness.read_json(ROOT, "BENCHMARK.json")
ENTRY, = [c for c in MANIFEST["configs"] if c["name"] == NAME]
CFG = harness.read_json(ROOT, ENTRY["file"])
READERS = {"decode_step_ms.zaya", "prefill_ms.zaya", "slot_occupancy.zaya",
           "step_host_ms.zaya", "device_idle.zaya", "decode_roofline.zaya",
           "expert_tokens_per_step.zaya", "expert_max_load.zaya",
           "live_block_share.zaya"}


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog beside the model-configs guide is not here")
    with open(path) as f:
        row, = [r for r in map(json.loads, f) if r["name"] == "ZAYA1-8B"]
    return row


def test_every_published_number_is_kept_under_its_key():
    row = _catalog()
    assert ENTRY["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert CFG[key] == 16 and CFG["reduced_from"][key] == value == 40
        else:
            assert CFG[key] == value, key


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 2048), ("head_dim", 128), ("num_attention_heads", 8),
    ("num_key_value_heads", 2), ("moe_intermediate_size", 2048),
    ("num_experts", 16), ("num_experts_per_tok", 1),
    ("router_hidden_size", 256), ("cca_time0", 2), ("cca_time1", 2),
    ("partial_rotary_factor", 0.5), ("vocab_size", 262272),
    ("rms_norm_eps", 1e-5), ("tie_word_embeddings", True)])
def test_published_widths(key, value):
    """What `test_bench_manifest.py` cannot hold this file to (it holds
    every configuration to Mistral's sizes): ITS published sizes."""
    assert CFG[key] == value
    assert CFG["rope_parameters"]["hybrid"]["rope_theta"] == 5000000


def test_reduced_is_depth_alone_with_the_deployment_beside():
    assert ENTRY["reduced"] == ["num_hidden_layers"]
    assert CFG["reduced_from"] == {"num_hidden_layers": 40}
    assert CFG["num_hidden_layers"] == 16
    assert set(CFG["layer_types"]) == {"hybrid"}         # one kind: period 1
    assert "16 / 16 / 8" in CFG["why_reduced"]
    assert "head" in CFG["why_reduced"] and "first" in CFG["deployment"]
    for word in ("bias", "tau", "balancing", "gamma", "residual", "router",
                 "RoPE", "1e-6", "MoD"):
        assert any(word in line for line in CFG["assumed"]), word


def test_parameter_count_and_the_per_layer_figures():
    """ISSUE 33: CCA 5.58 M, router and residual scales 0.68 M, experts
    201.33 M, a layer 207.6 M, embedding = head 537.1 M, 3,858 M."""
    assert zaya_weights.param_count(CFG) == CFG["params"]
    assert abs(CFG["params"] - 3858e6) < 1e6
    part = {k: round(v / 1e6, 2)
            for k, v in zaya_weights.counts_by_part(CFG).items()}
    assert part == {"cca": 5.58, "experts": 201.33, "router": 0.66,
                    "residual_and_norms": 0.02, "layer": 207.58,
                    "embed_and_head": 537.13}
    assert zaya_opcount.expert_params(CFG) == 3 * 2048 * 2048
    names = [n for n, _, _ in zaya_weights.leaf_table(CFG)]
    assert "head" not in names                            # tied
    assert "L0.zaya_moe.gamma" not in names and "L1.zaya_moe.gamma" in names


def test_resident_bytes_at_64_slots():
    """bf16 weights 7.72 GB, K/V pool (64 x 256 + 1) blocks x 16 x 16 KB
    = 4.30 GB, tails under 10 MB: 12.0 GB of the chip's 16."""
    sv = CFG["serve"]
    assert (sv["cb_slots"], sv["cb_block_len"], sv["cb_prompt_cap"],
            sv["max_new_tokens"], sv["dtype"]) == (64, 16, 1024, 3072,
                                                   "bfloat16")
    per_slot = (sv["cb_prompt_cap"] + sv["max_new_tokens"]) // 16
    got = zaya_opcount.resident_bytes(CFG, 64, 64 * per_slot + 1, 16, 2)
    assert got["weights"] == 2 * CFG["params"]
    assert zaya_opcount.kv_bytes_per_token(CFG, 2) == 16 * 1024
    assert zaya_opcount.slot_tail_bytes(CFG, 2) == 16 * (2 * 1280 + 128) * 2
    assert round(got["weights"] / 1e9, 2) == 7.72
    assert round(got["kv_pool"] / 1e9, 2) == 4.30
    assert got["tails"] < 10e6
    assert round(got["total"] / 1e9, 1) == 12.0
    assert got["total"] > 0.25 * 16e9


def test_the_bytes_a_decode_step_cannot_avoid():
    """At 64 busy slots of ~1000 live tokens with every expert touched:
    experts 6.4 GB, other weights and the head 1.3, live rows 1.0."""
    e = zaya_opcount.expert_params(CFG) * 2
    fixed = zaya_opcount.fixed_params(CFG) * 2
    assert round(16 * 16 * e / 1e9, 1) == 6.4
    assert round(fixed / 1e9, 2) == 1.27
    live = 64 * 1000
    assert round(live * zaya_opcount.kv_bytes_per_token(CFG, 2) / 1e9,
                 1) == 1.0
    whole = zaya_opcount.decode_step_needed_bytes(CFG, 64, live, 16 * 16, 2)
    assert 8.6e9 < whole < 8.9e9
    fewer = zaya_opcount.decode_step_needed_bytes(CFG, 64, live, 16 * 14, 2)
    assert whole - fewer == 32 * e
    idle = zaya_opcount.decode_step_needed_bytes(CFG, 60, live, 16 * 16, 2)
    assert whole - idle == 2 * 4 * zaya_opcount.slot_tail_bytes(CFG, 2)
    assert zaya_opcount.decode_step_flops(CFG, 64, live, 16 * 64) > 0


def test_the_cell_and_its_traffic():
    cell = harness.Cell(CELL)
    assert cell.entry["chips"] == 1 and cell.spec["runner"] == "serve_zaya"
    assert cell.spec["at_window_end"] == "cancel"
    assert cell.spec["preroll_of_window"] == pytest.approx(2 / 3, abs=1e-3)
    assert cell.spec["check_requests"] == 6
    mix = cell.traffic
    assert mix["generator"] == "open_loop" and mix["arrivals"] == "poisson"
    assert mix["prompt"] == {"dist": "lognormal", "median": 256,
                             "sigma": 0.6, "lo": 64, "hi": 1024}
    assert mix["output"] == {"dist": "lognormal", "median": 1024,
                             "sigma": 0.6, "lo": 256, "hi": 3072}
    assert mix["rate_rps"] > 0 and "knee" in mix["rate_from"]
    assert set(cell.spec["limits"]) == {"served_gap", "served_gap_mean"}
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert names == READERS | {"compile_s"}
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "out_tok_s", "setup_s"}
    out_tok_s, = [m for m in MANIFEST["end_to_end"]
                  if m["name"] == "out_tok_s"]
    assert out_tok_s["workloads"][-1] == CELL


def test_program_names_cover_the_nets_parameters():
    from benchmark.runners import serve_zaya
    from singa_tpu.core.net import build_net
    from singa_tpu.data import discover_input_shapes
    tiny = harness._tiny(CFG)
    model = serve_zaya.model_config(tiny, 16)
    net = build_net(model, "kTrain",
                    discover_input_shapes(model, force_synthetic=True))
    table = {zaya_weights.program_name(n): tuple(s)
             for n, s, _ in zaya_weights.leaf_table(tiny)}
    assert table == {k: tuple(v.shape) for k, v in net.param_specs.items()}
    assert net.param_aliases == {"loss/w": "embed/embedding"}


def test_the_runner_binds_its_own_names_only_for_the_length_of_a_call():
    from benchmark import kimi_weights
    from benchmark.runners import serve_kimi, serve_zaya
    assert serve_kimi.kimi_weights is kimi_weights
    with serve_zaya._bound():
        assert serve_kimi.kimi_weights is zaya_weights
        assert serve_kimi.model_config is serve_zaya.model_config
    assert serve_kimi.kimi_weights is kimi_weights
    assert serve_kimi.model_config is not serve_zaya.model_config


def test_readers_return_none_where_there_is_nothing_to_read():
    """A program without the counters (the parent commit), a run without
    a trace: the new readers return None and do not raise."""
    facts = {"cell": CELL, "config": CFG, "counters": {"cb_steps": 0},
             "spans": [("engine.decode", 0.0, 1.0, 5)], "trace_span": (0, 2),
             "trace": {"modules_by_span": {"engine.decode": {
                 "seconds": 1.0, "runs": 1}}},
             "peaks": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0},
             "itemsize": 2}
    cell = harness.Cell(CELL)
    for name in sorted(READERS - {"decode_step_ms.zaya", "device_idle.zaya"}):
        assert cell.load("layer_metrics", name).read(facts) is None, name
    # routing counters without the imbalance counter: the older program
    facts["counters"] = {"cb_routed_layer_steps": 16,
                         "cb_routed_assignments": 1024, "cb_steps": 0}
    assert cell.load("layer_metrics", "expert_max_load.zaya").read(
        facts) is None
    assert cell.load("layer_metrics", "expert_tokens_per_step.zaya").read(
        facts) == 4.0


def test_readers_read_the_steps_own_counts():
    row = ("engine.decode", 0.5, 0.6, 64 * 1000, 64, 16 * 12, 16 * 64)
    facts = {"cell": CELL, "config": CFG, "spans": [row],
             "trace_span": (0.0, 1.0), "itemsize": 2,
             "trace": {"modules_by_span": {"engine.decode": {
                 "seconds": 0.016, "runs": 1}}},
             "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    cell = harness.Cell(CELL)
    got = cell.load("layer_metrics", "decode_roofline.zaya").read(facts)
    need = zaya_opcount.decode_step_needed_bytes(CFG, 64, 64000, 192, 2)
    assert got == pytest.approx(100 * need / 819e9 / 0.016)
    assert got < 100
    c = {"cb_routed_layer_steps": 1600, "cb_routed_assignments": 102400,
         "cb_routed_max_load": 16000, "cb_decode_steps": 100,
         "cb_live_block_steps": 409600, "cb_slots": 64}
    read = lambda name: cell.load("layer_metrics", name).read(  # noqa: E731
        {"config": CFG, "counters": c})
    assert read("expert_tokens_per_step.zaya") == pytest.approx(4.0)
    assert read("expert_max_load.zaya") == pytest.approx(2.5)
    assert read("live_block_share.zaya") == pytest.approx(25.0)


@pytest.fixture(scope="module")
def rehearsal():
    from benchmark import run as bench_run
    out = io.StringIO()
    with redirect_stdout(out):
        assert bench_run.main(["--workload", CELL, "--seed",
                               str(2 ** 31 + 33), "--seconds", "3",
                               "--trace", "1", "--rehearsal", "1"]) == 0
    text = out.getvalue()
    return json.loads(text.strip().splitlines()[-1]), text


def test_rehearsal_serves_tokens_the_reference_puts_first(rehearsal):
    """float32 on the CPU: every served token is the reference's own
    choice, through the paged rows and the slot tails, the window opened
    onto a house already running."""
    line, text = rehearsal
    assert line["correct"] is True and line["failed"] == 0
    assert line["counts"]["served_tokens_compared"] > 0
    gaps = [float(row.split(": ")[1].split(" ")[0])
            for row in text.splitlines()
            if row.startswith("compared served_gap")]
    assert len(gaps) == 2 and max(gaps) < 1e-3
    assert "compared compiles_in_window: 0" in text


def test_rehearsal_finds_the_counter_readers(rehearsal):
    """Those that need no device trace find something to read."""
    line, text = rehearsal
    assert set(line["readers"]) >= {
        "compile_s", "decode_step_ms.zaya", "prefill_ms.zaya",
        "slot_occupancy.zaya", "expert_tokens_per_step.zaya",
        "expert_max_load.zaya", "live_block_share.zaya"}
    counters = next(r for r in text.splitlines() if r.startswith("counters"))
    for key in ("cb_routed_max_load", "cb_live_block_steps",
                "cb_slot_state_bytes", "cb_block_bytes"):
        assert f"'{key}'" in counters
