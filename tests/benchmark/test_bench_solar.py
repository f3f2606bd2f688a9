"""The Solar-Open2 configuration's manifest: every published number
kept under its key, the three reduced keys with the published counts
and the deployment beside them, the parameter count and the per-layer
figures of ISSUE 43 reckoned again from the leaf table, the bytes
resident at 48 slots, what a chunk's mathematics needs, the cell, its
traffic's parameters, its readers, and its rehearsal on the CPU."""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, solar_opcount, solar_weights  # noqa: E402

NAME = "solar-open2-serve-l4-ep8"
CELL = "serve-solar-docqa-sat"
MANIFEST = harness.read_json(ROOT, "BENCHMARK.json")
ENTRY, = [c for c in MANIFEST["configs"] if c["name"] == NAME]
CFG = harness.read_json(ROOT, ENTRY["file"])
REDUCED = {"num_hidden_layers": (4, 48), "n_routed_experts": (40, 320),
           "vocab_size": (24576, 196608)}
READERS = {"decode_step_ms.solar", "prefill_ms.solar",
           "prefill_chunk_ms.solar", "prefill_share.solar",
           "prefill_roofline.solar", "decode_roofline.solar",
           "slot_occupancy.solar", "step_host_ms.solar",
           "device_idle.solar", "expert_tokens_per_step.solar",
           "expert_max_load.solar", "grouped_row_share.solar",
           "steps_between_chunks.solar"}
ELEVEN = {"idle_decode_handover", "idle_decode_wait", "idle_emit",
          "idle_admit", "idle_step_rest", "idle_no_work", "idle_unnamed",
          "step_ahead_share", "round_trip_host_ms", "stall_s",
          "stall_wait_s"}


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog beside the model-configs guide is not here")
    with open(path) as f:
        row, = [r for r in map(json.loads, f)
                if r["name"] == "Solar-Open2-250B"]
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
    ("hidden_size", 4096), ("num_attention_heads", 64),
    ("num_key_value_heads", 8), ("head_dim", 128),
    ("intermediate_size", 10240), ("moe_intermediate_size", 1280),
    ("router_width", 320), ("num_experts_per_tok", 8),
    ("n_shared_experts", 1), ("norm_topk_prob", True),
    ("routed_scaling_factor", 1), ("first_k_dense_replace", 0),
    ("use_rope", False), ("use_gqa_gate", True),
    ("kda_allow_neg_eigval", True), ("kda_use_full_proj", False),
    ("gqa_interval", 3), ("rms_norm_eps", 1e-5),
    ("max_position_embeddings", 1048576), ("tie_word_embeddings", False)])
def test_published_widths(key, value):
    """What `test_bench_manifest.py` cannot hold this file to (it holds
    every configuration to Mistral's sizes): ITS published sizes."""
    assert CFG[key] == value


def test_the_kda_widths_and_the_layer_kinds():
    from benchmark.reference import solar_open2
    assert CFG["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert CFG["gqa_layers"] == list(range(0, 48, 4))     # published, whole
    assert solar_open2.layer_kinds(CFG) == ["gqa", "kda", "kda", "kda"]
    whole = dict(CFG, num_hidden_layers=48)
    kinds = solar_open2.layer_kinds(whole)
    assert kinds.count("gqa") == 12 and kinds[44:] == ["gqa"] + ["kda"] * 3


def test_reduced_is_the_chips_share_with_the_deployment_beside():
    assert ENTRY["reduced"] == list(REDUCED)
    assert CFG["reduced_from"] == {k: v[1] for k, v in REDUCED.items()}
    assert (CFG["first_held_expert"], CFG["first_vocab_id"]) == (0, 0)
    for word in ("one of eight", "expert-parallel 8 ways", "48 slots",
                 "51 rows an expert", "32,768", "chunks of 2,048",
                 "more here than one chip's share"):
        assert word in CFG["deployment"], word
    for word in ("250 B", "experts 0-39", "0-24575", "ONE period",
                 "3.31 B", "6.62 GB", "12.8 GB",
                 "eleven more pipeline stages"):
        assert word in CFG["why_reduced"], word
    for word in ("sigmoid", "selection bias", "1280", "10240", "8192",
                 "num_kv_heads null", "rank head_dim = 128", "2 sigmoid",
                 "A_log", "dt_bias", "no bias", "1e-6", "router_width"):
        assert any(word in line for line in CFG["assumed"]), word


def test_parameter_count_and_the_per_layer_figures():
    """ISSUE 43: GQA mixer 109.0 M, KDA mixer 137.7 M, a layer's sparse
    part 646.2 M (40 held experts of 15.73 M), the vocabulary's eighth
    201.3 M: 3.31 B, 6.62 GB in bf16."""
    assert solar_weights.param_count(CFG) == CFG["params"] == 3308353344
    part = {k: round(v / 1e6, 1)
            for k, v in solar_weights.counts_by_part(CFG).items()}
    assert part == {"gqa": 109.1, "kda": 137.7, "sparse": 646.2,
                    "held_experts": 629.1, "embed_and_head": 201.3}
    assert solar_opcount.expert_params(CFG) == 3 * 4096 * 1280
    two = dict(CFG, num_hidden_layers=8)
    assert round(solar_weights.param_count(two) * 2 / 1e9, 1) == 12.8
    names = [n for n, _, _ in solar_weights.leaf_table(CFG)]
    assert "L0.attention.wg" in names and "L0.attention.q_norm" not in names
    assert "L1.kda.w_beta" in names and "L0.moe.router_bias" in names
    assert not any(".ffn." in n for n in names)           # no dense layer


def test_resident_bytes_at_48_slots():
    """bf16 weights 6.62 GB; 48 slots x 13.0 MB of float32 state and
    tails; a pool of 1.2 M tokens at 4 KB a token 4.92 GB: 12.2 GB of
    the chip's 16, over the driver's floor of a quarter."""
    from benchmark.runners import serve_solar
    sv = CFG["serve"]
    assert (sv["cb_slots"], sv["cb_block_len"], sv["cb_prompt_cap"],
            sv["cb_prefill_rung"], sv["max_new_tokens"], sv["dtype"],
            sv["temperature"]) == (48, 16, 32768, 2048, 1024, "bfloat16",
                                   0.0)
    assert solar_opcount.kv_bytes_per_token(CFG, 2) == 4096
    assert solar_opcount.slot_state_bytes(CFG, 2) == 3 * (
        64 * 128 * 128 * 4 + 3 * 3 * 64 * 128 * 2)
    got = serve_solar.resident_bytes(CFG)
    assert got["weights"] == 2 * CFG["params"]
    assert serve_solar.pool_blocks(CFG) == 75001
    assert round(got["kv_pool"] / 1e9, 2) == 4.92
    assert round(got["slot_states"] / 1e9, 3) == 0.625
    total = got["weights"] + got["slot_states"] + got["kv_pool"]
    assert round(total / 1e9, 1) == 12.2 and total > 0.25 * 16e9
    # admission by free blocks sometimes waits and never deadlocks: the
    # pool holds fewer than every slot's worst case, more than one's
    worst = -(-(32768 + 1024) // 16)
    assert worst < serve_solar.pool_blocks(CFG) - 1 < 48 * worst


def test_what_a_chunks_mathematics_needs():
    """A 2,048-row chunk at start 14,336 with 2,048 pairs on held
    experts a layer: the rows' matrices, the recurrence a token, the
    causal half and the live prefix, and the head on one row of a last
    chunk only."""
    rows, start, pairs = 2048, 14336, 4 * 2048
    need = solar_opcount.chunk_needed_flops(CFG, rows, start, pairs, False)
    matrices = 2.0 * rows * solar_opcount.row_params(CFG)
    experts = 2.0 * pairs * solar_opcount.expert_params(CFG)
    state = rows * 3 * 64 * 128 * 128 * 7.0
    attend = (rows * start + rows * (rows + 1) / 2) * 64 * 4.0 * 128
    assert need == pytest.approx(matrices + experts + state + attend)
    last = solar_opcount.chunk_needed_flops(CFG, rows, start, pairs, True)
    assert last - need == 2.0 * 4096 * 24576
    # the dense walk would be 40 experts a row and layer
    dense = 2.0 * rows * 4 * 40 * solar_opcount.expert_params(CFG)
    assert dense / experts == pytest.approx(40.0)
    assert 13e12 < solar_opcount.prompt_needed_flops(CFG, 9500) < 15e12


def test_the_cell_and_its_traffic():
    cell = harness.Cell(CELL)
    assert cell.entry["chips"] == 1 and cell.spec["runner"] == "serve_solar"
    assert cell.spec["at_window_end"] == "cancel"
    assert cell.spec["preroll_of_window"] == pytest.approx(2 / 3, abs=1e-3)
    mix = cell.traffic
    assert mix["generator"] == "open_loop" and mix["arrivals"] == "poisson"
    assert mix["prompt"] == {"dist": "lognormal", "median": 8192,
                             "sigma": 0.6, "lo": 2048, "hi": 32768}
    assert mix["output"] == {"dist": "lognormal", "median": 256,
                             "sigma": 0.5, "lo": 64, "hi": 1024}
    assert mix["schedule_seed"] == 43
    assert mix["rate_rps"] > 0 and "knee" in mix["rate_from"]
    sweep = harness.read_json(ROOT, "benchmark", "workloads", "sweeps",
                              "docqa-full-house.json")
    assert sweep["workload"] == CELL
    assert mix["rate_rps"] == pytest.approx(1.25 * sweep["knee_rps"],
                                            rel=0.01)
    assert set(cell.spec["limits"]) == {"served_gap", "served_gap_mean"}
    for word in ("fp8", "cold_chunk", "pos_eig", "sound"):
        assert word in cell.spec["limits_from"], word
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert names == READERS | ELEVEN | {"compile_s"}
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "out_tok_s", "setup_s"}
    out_tok_s, = [m for m in MANIFEST["end_to_end"]
                  if m["name"] == "out_tok_s"]
    assert CELL in out_tok_s["workloads"] and out_tok_s["bound"] == 0.01
    assert len(MANIFEST["workloads"]) == 8
    assert all(w["chips"] == 1 for w in MANIFEST["workloads"])
    # every prompt takes a chunk at start > 0 unless it is exactly 2048,
    # and the schedule's mean is the issue's "about 9,500"
    from benchmark.traffic import open_loop
    reqs = open_loop.generate(mix, 1, 75.0, CFG["vocab_size"])
    plens = [len(r.tokens) for r in reqs]
    assert min(plens) >= 2048 and max(plens) <= 32768
    assert 9000 < sum(plens) / len(plens) < 10000
    assert sum(p > 3 * 2048 for p in plens) > len(plens) / 2
    assert all(r.tokens.max() < CFG["vocab_size"] for r in reqs[:5])


def test_program_names_cover_the_nets_parameters():
    from benchmark.runners import serve_solar
    from singa_tpu.core.net import build_net
    from singa_tpu.data import discover_input_shapes
    tiny = harness._tiny(CFG)
    model = serve_solar.model_config(tiny, 64)
    net = build_net(model, "kTrain",
                    discover_input_shapes(model, force_synthetic=True))
    table = {solar_weights.program_name(n): tuple(s)
             for n, s, _ in solar_weights.leaf_table(tiny)}
    assert table == {k: tuple(v.shape) for k, v in net.param_specs.items()}
    assert not net.param_aliases                          # untied head
    gqa, kda = net.layers["attention0"], net.layers["kda1"]
    assert (gqa.use_rope, gqa.gate, gqa.qk_norm, gqa.window) == (
        False, True, False, 0)
    assert kda.beta_scale == 2.0


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "benchmark", "reference",
                            "solar_open2.py")).read()
    assert "import singa_tpu" not in src and "from singa_tpu" not in src
    assert "from benchmark" not in src and "import benchmark" not in src


def test_the_runners_first_import_is_a_name_this_pr_brought():
    """A program without the chunked prefill fails at the runner's first
    import, before JAX is asked for a device."""
    src = open(os.path.join(ROOT, "benchmark", "runners",
                            "serve_solar.py")).read()
    body = src.split('"""', 2)[2]
    imports = [ln for ln in body.splitlines()
               if ln.startswith(("import ", "from "))]
    assert imports[0] == "from __future__ import annotations"
    assert imports[1].startswith(
        "from singa_tpu.models.generate import forward_chunk")


def test_the_runner_binds_its_own_names_only_for_the_length_of_a_call():
    from benchmark.runners import serve_kimi, serve_solar
    theirs = (serve_kimi.build, serve_kimi._Spans, serve_kimi.check_sample,
              serve_kimi._counters)
    with serve_solar._bound({}):
        assert serve_kimi.build is serve_solar.build
        assert serve_kimi._Spans is serve_solar._Spans
        assert serve_kimi._counters is serve_solar._counters
    assert (serve_kimi.build, serve_kimi._Spans, serve_kimi.check_sample,
            serve_kimi._counters) == theirs


def test_the_sample_compared_holds_long_prompts():
    from types import SimpleNamespace as NS

    from benchmark.runners import serve_solar
    done = [NS(req=NS(tokens=[0] * n), served=[1] * 5)
            for n in (2048, 3000, 6144, 6145, 9000, 30000, 4000)]
    for seed in range(8):
        pick, long = serve_solar._pick(done, 4, seed, 2048)
        assert len(pick) == 4 and len({id(s) for s in pick}) == 4
        assert long >= 2 and long == sum(len(s.req.tokens) > 6144
                                         for s in pick)
    pick, long = serve_solar._pick(done[:3], 4, 0, 2048)
    assert len(pick) == 3 and long == 0


def test_readers_return_none_where_there_is_nothing_to_read():
    """A program without the counters, a run without a trace or without
    the runner's chunk rows: the new readers return None and do not
    raise."""
    facts = {"cell": CELL, "config": CFG, "counters": {"cb_steps": 0},
             "spans": [("engine.decode", 0.0, 1.0, 5)], "trace_span": (0, 2),
             "trace": {"modules_by_span": {}},
             "peaks": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0},
             "itemsize": 2}
    cell = harness.Cell(CELL)
    for name in sorted(READERS - {"device_idle.solar"}):
        assert cell.load("layer_metrics", name).read(facts) is None, name
    assert cell.load("layer_metrics", "device_idle.solar").read(
        {"trace": None}) is None


def test_readers_read_the_programs_own_runs(monkeypatch):
    """Device time by program name, the chunks' needs from the runner's
    rows, the counters' ratios."""
    from benchmark.layer_metrics import _solar
    runs = {"jit_cb_chunk": [0.080, 0.090, 0.040],
            "jit_cb_decode": [0.014, 0.015, 0.016]}
    monkeypatch.setattr(_solar, "programs", lambda facts: runs)
    rows = [("engine.chunk", 0.1, 0.2, 2048, 0, 2048, False, 8000),
            ("engine.chunk", 0.3, 0.4, 2048, 2048, 2048, False, 8100),
            ("engine.chunk", 0.5, 0.6, 700, 4096, 1024, True, 2900),
            ("engine.decode", 0.6, 0.7, 48 * 9000, 47, 150, 180)]
    facts = {"cell": CELL, "config": CFG, "spans": rows, "itemsize": 2,
             "trace_span": (0.0, 1.0), "trace": {"busy_s": 0.30},
             "counters": {"cb_prefill_chunks": 400, "cb_chunked_prompts": 80,
                          "cb_steps_between_chunks": 320,
                          "cb_grouped_rows": 2_900_000,
                          "cb_grouped_row_slots": 117_000_000,
                          "cb_routed_layer_steps": 2000,
                          "cb_routed_assignments": 96000,
                          "cb_routed_max_load": 21000, "cb_steps": 500,
                          "cb_active_slot_steps": 22800, "cb_slots": 48},
             "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    cell = harness.Cell(CELL)
    read = lambda name: cell.load("layer_metrics", name).read(facts)  # noqa: E731
    assert read("decode_step_ms.solar") == pytest.approx(15.0)
    assert read("prefill_chunk_ms.solar") == pytest.approx(80.0)
    assert read("prefill_ms.solar") == pytest.approx(70.0 * 5)
    assert read("prefill_share.solar") == pytest.approx(100 * 0.21 / 0.30)
    need = sum(solar_opcount.chunk_needed_flops(CFG, r[3], r[4], r[7], r[6])
               for r in rows[:3])
    got = read("prefill_roofline.solar")
    assert got == pytest.approx(100 * need / 197e12 / 0.21) and got < 100
    need_b = solar_opcount.decode_step_needed_bytes(CFG, 47, 48 * 9000, 150,
                                                    2)
    got = read("decode_roofline.solar")
    assert got == pytest.approx(100 * need_b / 819e9 / 0.015) and got < 100
    assert read("grouped_row_share.solar") == pytest.approx(2.4786, abs=1e-3)
    assert read("steps_between_chunks.solar") == pytest.approx(1.0)
    assert read("expert_tokens_per_step.solar") == pytest.approx(1.2)
    assert read("expert_max_load.solar") == pytest.approx(8.75)
    assert read("slot_occupancy.solar") == pytest.approx(95.0)


@pytest.fixture(scope="module")
def rehearsal():
    from benchmark import run as bench_run
    out = io.StringIO()
    with redirect_stdout(out):
        assert bench_run.main(["--workload", CELL, "--seed",
                               str(2 ** 31 + 43), "--seconds", "3",
                               "--trace", "0", "--rehearsal", "1"]) == 0
    text = out.getvalue()
    return json.loads(text.strip().splitlines()[-1]), text


def test_rehearsal_serves_tokens_the_reference_puts_first(rehearsal):
    """float32 on the CPU: every served token is the reference's own
    choice, through prompts of up to four chunks with decode steps
    between them, the window opened onto a house already running."""
    line, text = rehearsal
    assert line["correct"] is True and line["failed"] == 0
    assert line["counts"]["served_tokens_compared"] > 0
    gaps = [float(row.split(": ")[1].split(" ")[0])
            for row in text.splitlines()
            if row.startswith("compared served_gap")]
    assert len(gaps) == 2 and max(gaps) < 1e-3
    assert "compared compiles_in_window: 0" in text
    assert "compared long_prompts_compared: " in text
    assert "resident: {'params': " in text


def test_rehearsal_counts_the_chunks(rehearsal):
    line, text = rehearsal
    counters = next(r for r in text.splitlines() if r.startswith("counters"))
    found = json.loads(counters.split(": ", 1)[1].replace("'", '"'))
    assert found["cb_chunked_prompts"] > 10
    assert found["cb_prefill_chunks"] > 2 * found["cb_chunked_prompts"]
    # (a prompt's chunks are counted as they are read, its rows when it
    # ends: the window's two edges cut some prompts between the two)
    assert found["cb_chunk_tokens"] > 16 * found["cb_chunked_prompts"]
    assert found["cb_prefix_rows"] > found["cb_chunk_tokens"]
    assert 0 < found["cb_steps_between_chunks"] <= (
        found["cb_prefill_chunks"] - found["cb_chunked_prompts"])
    assert found["cb_routed_max_load"] > 0 and found["cb_block_bytes"] > 0
