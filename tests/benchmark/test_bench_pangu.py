"""The openPangu-Ultra-MoE configuration's manifest: every published
number kept under its key, the four reduced keys with the published
counts and the deployment beside them, the parameter count and the
per-layer figures of ISSUE 39 reckoned again from the leaf table, the
bytes resident at 64 slots, the bytes and operations a verify-and-draft
step and one paged-kernel call cannot avoid, the cell, its readers on
canned facts, and its rehearsal on the CPU."""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, pangu_opcount, pangu_weights  # noqa: E402
from test_bench_preflight import (  # noqa: E402, F401 — its fixtures
    kernels_for_tpu, one_chip, topo)

NAME = "openpangu-ultra-moe-serve-l5-ep32"
CELL = "serve-pangu-think-sat"
MANIFEST = harness.read_json(ROOT, "BENCHMARK.json")
ENTRY, = [c for c in MANIFEST["configs"] if c["name"] == NAME]
CFG = harness.read_json(ROOT, ENTRY["file"])
REDUCED = {"num_hidden_layers": (5, 61), "first_k_dense_replace": (1, 3),
           "n_routed_experts": (8, 256), "vocab_size": (19200, 153600)}
READERS = {"decode_step_ms.pangu", "prefill_ms.pangu", "slot_occupancy.pangu",
           "step_host_ms.pangu", "device_idle.pangu", "decode_roofline.pangu",
           "paged_roofline.pangu", "expert_tokens_per_step.pangu",
           "expert_max_load.pangu", "mtp_accept_share.pangu",
           "tokens_per_step.pangu", "mtp_step_share.pangu"}
GENERIC = {"idle_decode_handover", "idle_decode_wait", "idle_emit",
           "idle_admit", "idle_step_rest", "idle_no_work", "idle_unnamed",
           "step_ahead_share", "round_trip_host_ms", "stall_s",
           "stall_wait_s"}


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog beside the model-configs guide is not here")
    with open(path) as f:
        row, = [r for r in map(json.loads, f)
                if r["name"] == "openPangu-Ultra-MoE-718B"]
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
    ("hidden_size", 7680), ("num_attention_heads", 128),
    ("num_key_value_heads", 128), ("q_lora_rank", 1536),
    ("kv_lora_rank", 512), ("qk_nope_head_dim", 128),
    ("qk_rope_head_dim", 64), ("v_head_dim", 128),
    ("intermediate_size", 18432), ("moe_intermediate_size", 2048),
    ("num_experts_per_tok", 8), ("router_width", 256),
    ("n_shared_experts", 1), ("routed_scaling_factor", 2.5),
    ("norm_topk_prob", True), ("rope_theta", 25600000),
    ("rms_norm_eps", 1e-5), ("sandwich_norm", True),
    ("num_nextn_predict_layers", 1), ("tie_word_embeddings", False)])
def test_published_widths(key, value):
    """What `test_bench_manifest.py` cannot hold this file to (it holds
    every configuration to Mistral's sizes): ITS published sizes."""
    assert CFG[key] == value


def test_reduced_is_the_chips_share_with_the_deployment_beside():
    assert ENTRY["reduced"] == list(REDUCED)
    assert CFG["reduced_from"] == {k: v[1] for k, v in REDUCED.items()}
    assert (CFG["first_held_expert"], CFG["first_vocab_id"]) == (0, 0)
    for word in ("32 v5e chips", "pipeline stages", "32-way", "64 slots",
                 "4 assignments a step", "more than a tensor-parallel share"):
        assert word in CFG["deployment"], word
    for word in ("718 B", "1.44 TB", "experts 0-7", "0-19,199",
                 "published layer 2", "module whole"):
        assert word in CFG["why_reduced"], word
    for word in ("router_width 256", "pairing", "no selection bias",
                 "sandwich_norm", "W_eh", "AFTER its final norm",
                 "no biases"):
        assert any(word.lower() in line.lower()
                   for line in CFG["assumed"]), word
    sv = CFG["serve"]
    assert (sv["cb_slots"], sv["cb_block_len"], sv["cb_prompt_cap"],
            sv["max_new_tokens"], sv["dtype"], sv["temperature"]) == (
                64, 16, 1024, 6144, "bfloat16", 1.0)
    assert CFG["tiny"]["serve"]["temperature"] == 1.0


def test_parameter_count_and_the_per_layer_figures():
    """ISSUE 39: attention 196.6 M a layer, the dense layer 621.3 M, an
    expert layer 623.2 M (8 experts 377.5 M), the module 741.2 M,
    embedding and head 294.9 M: 4.15 G = 8.30 GB in bf16; the whole
    model 718 B."""
    assert pangu_weights.param_count(CFG) == 4150426880
    part = {k: round(v / 1e6, 1)
            for k, v in pangu_weights.counts_by_part(CFG).items()}
    assert part == {"dense_layer": 621.3, "moe_layer": 623.2,
                    "attention": 196.6, "held_experts": 377.5,
                    "router_and_shared": 49.2, "module": 741.2,
                    "embed_and_head": 294.9}
    assert pangu_opcount.expert_params(CFG) == 3 * 7680 * 2048
    # the uncut main stack: 719.1 B by this count, "718B" by its name;
    # with the module, 12.3 B more
    whole = dict(CFG, n_routed_experts=256, num_hidden_layers=61,
                 first_k_dense_replace=3, vocab_size=153600)
    assert round(pangu_weights.param_count(whole) / 1e9, 1) == 731.5
    assert round(pangu_weights.param_count(
        dict(whole, num_nextn_predict_layers=0)) / 1e9, 1) == 719.1
    names = [n for n, _, _ in pangu_weights.leaf_table(CFG)]
    assert "L0.ffn.w_gate" in names and "L1.moe.router" in names
    assert "L0.moe.router" not in names and "L4.mla.wq_a" in names
    # the module: its own entry and norms, a block numbered after the
    # main stack's, under the main model's embedding and head
    assert {"mtp.w_eh", "mtp.e_norm", "mtp.h_norm", "mtp.final_norm",
            "L5.mla.wq", "L5.moe.router"} <= set(names)
    assert names.count("embed") == names.count("head") == 1


def test_resident_bytes_at_64_slots():
    """bf16 weights 8.30 GB; six pools of latent rows (five layers and
    the module), 6 x (64 x 448 + 1) blocks x 16 rows x 640 x 2 B = 3.52
    GB; 11.8 GB of the chip's 16."""
    from benchmark.runners import serve_pangu
    got = serve_pangu.resident_bytes(CFG)
    assert got["weights"] == 2 * 4150426880
    assert round(got["weights"] / 1e9, 2) == 8.30
    assert got["latent_pools"] == 6 * (64 * 448 + 1) * 16 * 640 * 2
    assert round(got["latent_pools"] / 1e9, 2) == 3.52
    total = got["weights"] + got["latent_pools"] + got["draft_state"]
    assert round(total / 1e9, 1) == 11.8 and total > 0.25 * 16e9


def test_what_a_step_and_a_kernel_call_cannot_avoid():
    """64 busy slots at a context of 1,700: 6 calls over 576-value rows,
    two query rows of 128 heads each; the operations of a call over the
    peak pass its bytes over the bandwidth (this geometry lies past the
    ridge at two rows)."""
    assert pangu_opcount.latent_layers(CFG) == 6
    assert pangu_opcount.moe_layers(CFG) == 5
    assert pangu_opcount.latent_row_bytes(CFG, 2) == 1152
    live = 64 * 1700
    assert pangu_opcount.paged_call_bytes(CFG, live, 2) == live * 1152
    assert pangu_opcount.paged_call_flops(CFG, live) == \
        2 * live * 2 * 128 * (576 + 512)
    assert pangu_opcount.paged_call_flops(CFG, live, rows=1) / 197e12 \
        == pytest.approx(live * 1152 / 819e9, rel=0.02)      # the ridge
    e = pangu_opcount.expert_params(CFG) * 2
    fixed = pangu_opcount.fixed_params(CFG) * 2
    assert round(fixed / 1e9, 2) == 4.23
    whole = pangu_opcount.decode_step_needed_bytes(CFG, 64, live, 5 * 8, 2)
    assert whole == fixed + 40 * e + 6 * live * 1152
    assert 8.7e9 < whole < 8.9e9
    fewer = pangu_opcount.decode_step_needed_bytes(CFG, 64, live, 5 * 5, 2)
    assert whole - fewer == 15 * e
    flops = pangu_opcount.decode_step_flops(CFG, 64, live, 5 * 64 * 2 / 4)
    # memory holds a step longer than its operations do
    assert flops / 197e12 < whole / 819e9


def test_the_cell_and_its_traffic():
    cell = harness.Cell(CELL)
    assert cell.entry["chips"] == 1 and cell.spec["runner"] == "serve_pangu"
    assert cell.spec["at_window_end"] == "cancel"
    assert cell.spec["preroll_of_window"] == pytest.approx(2 / 3, abs=1e-3)
    assert cell.spec["check_requests"] == 6
    mix = cell.traffic
    assert mix["generator"] == "open_loop" and mix["arrivals"] == "poisson"
    assert mix["prompt"] == {"dist": "lognormal", "median": 512,
                             "sigma": 0.6, "lo": 128, "hi": 1024}
    assert mix["output"] == {"dist": "lognormal", "median": 2048,
                             "sigma": 0.6, "lo": 512, "hi": 6144}
    assert mix["schedule_seed"] == 39
    assert mix["rate_rps"] > 0 and "knee" in mix["rate_from"]
    sweep = harness.read_json(ROOT, "benchmark", "workloads", "sweeps",
                              "think-full-house.json")
    assert sweep["workload"] == CELL
    assert mix["rate_rps"] == pytest.approx(1.25 * sweep["knee_rps"],
                                            rel=0.01)
    assert set(cell.spec["limits"]) == {
        "logprob_gap", "logprob_gap_mean", "draft_logprob_gap_mean",
        "accept_z", "sampled_logprob_z"}
    for word in ("fp8", "no_rope", "accept_all", "sound", "greedy"):
        assert word in cell.spec["limits_from"], word
    # a SUBSET: later PRs append readers that list this cell
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert names >= READERS | GENERIC | {"compile_s"}
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "out_tok_s", "setup_s"}
    out_tok_s, = [m for m in MANIFEST["end_to_end"]
                  if m["name"] == "out_tok_s"]
    assert CELL in out_tok_s["workloads"] and out_tok_s["bound"] == 0.01
    for m in MANIFEST["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
    # requests short enough to finish inside the 75 s a run lasts exist
    from benchmark.traffic import open_loop
    reqs = open_loop.generate(mix, 1, 75.0, CFG["vocab_size"])
    assert sum(r.due_s < 20 and r.max_new < 1500 for r in reqs) >= 6


def test_program_names_cover_the_nets_parameters():
    from benchmark.runners import serve_pangu
    from singa_tpu.core.net import build_net
    from singa_tpu.data import discover_input_shapes
    tiny = harness._tiny(CFG)
    model = serve_pangu.model_config(tiny, 16)
    net = build_net(model, "kTrain",
                    discover_input_shapes(model, force_synthetic=True))
    table = {pangu_weights.program_name(n): tuple(s)
             for n, s, _ in pangu_weights.leaf_table(tiny)}
    assert table == {k: tuple(v.shape) for k, v in net.param_specs.items()}
    assert not net.param_aliases                          # untied head


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "benchmark", "reference",
                            "pangu.py")).read()
    assert "import singa_tpu" not in src and "from singa_tpu" not in src
    assert "from benchmark" not in src and "import benchmark" not in src


def test_the_runner_binds_its_own_names_only_for_the_length_of_a_call():
    from benchmark import kimi_weights
    from benchmark.runners import serve_kimi, serve_pangu
    check = serve_kimi.check_sample
    with serve_pangu._bound():
        assert serve_kimi.kimi_weights is pangu_weights
        assert serve_kimi.model_config is serve_pangu.model_config
        assert serve_kimi.check_sample.func is serve_pangu.check_sample
    assert serve_kimi.kimi_weights is kimi_weights
    assert serve_kimi.check_sample is check


def test_readers_return_none_where_there_is_nothing_to_read():
    """A program without the counters, a run without a trace: the new
    readers return None and do not raise."""
    facts = {"cell": CELL + "-nowhere", "config": CFG,
             "counters": {"cb_steps": 0},
             "spans": [("engine.decode", 0.0, 1.0, 5)], "trace_span": (0, 2),
             "trace": {"modules_by_span": {"engine.decode": {
                 "seconds": 1.0, "runs": 1}}},
             "peaks": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0},
             "itemsize": 2}
    cell = harness.Cell(CELL)
    for name in sorted(READERS - {"decode_step_ms.pangu",
                                  "device_idle.pangu"}):
        assert cell.load("layer_metrics", name).read(facts) is None, name


def test_readers_read_the_steps_own_counts():
    live = 64 * 1700
    rows = [("engine.decode", 0.5, 0.6, live, 64, 5 * 7, 5 * 16, 31)]
    facts = {"cell": CELL, "config": CFG, "spans": rows,
             "trace_span": (0.0, 1.0), "itemsize": 2,
             "trace": {"modules_by_span": {
                 "engine.decode": {"main": "jit_cb_decode",
                                   "seconds": 0.032, "runs": 2},
                 "no_span": {"main": "jit_cb_decode", "seconds": 0.016,
                             "runs": 1}},
                 "ops": {"singa_paged_decode": 0.0117, "fusion": 0.030}},
             "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    cell = harness.Cell(CELL)
    got = cell.load("layer_metrics", "decode_roofline.pangu").read(facts)
    need = pangu_opcount.decode_step_needed_bytes(CFG, 64, live, 5 * 7, 2)
    assert got == pytest.approx(100 * need / 819e9 / 0.016) and got < 100
    got = cell.load("layer_metrics", "paged_roofline.pangu").read(facts)
    # three runs of the program, six calls each, held by their operations
    least = pangu_opcount.paged_call_flops(CFG, live) / 197e12
    assert least > pangu_opcount.paged_call_bytes(CFG, live, 2) / 819e9
    assert got == pytest.approx(100 * 3 * 6 * least / 0.0117) and got < 100
    c = {"cb_routed_layer_steps": 500, "cb_routed_assignments": 16000,
         "cb_routed_max_load": 5000, "cb_drafts_made": 6400,
         "cb_drafts_accepted": 3072, "cb_emit_slot_steps": 6400,
         "cb_tokens_emitted": 9400, "cb_slots": 64}
    read = lambda name: cell.load("layer_metrics", name).read(  # noqa: E731
        {"config": CFG, "counters": c})
    assert read("expert_tokens_per_step.pangu") == pytest.approx(4.0)
    assert read("expert_max_load.pangu") == pytest.approx(2.5)
    assert read("mtp_accept_share.pangu") == pytest.approx(48.0)
    assert read("tokens_per_step.pangu") == pytest.approx(1.46875)


def test_the_modules_share_is_read_from_its_entrys_mark():
    """Two runs of the decode program in a trace written by hand: the
    op that reads W_eh starts 7 of 10 ms and 6 of 10 ms into them; a
    prefill's entry does not count."""
    from benchmark.layer_metrics import _pangu
    mark = _pangu.module_entry_marker(CFG)
    assert mark == "[15360,7680]"
    entry = ("%fusion.9 = bf16[128,7680]{1,0} fusion(bf16[128,15360]{1,0} "
             "%z, bf16[15360,7680]{1,0:T(8,128)(2,1)} %w_eh)")
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ("jit_cb_decode(1)", 0.000, 0.010),
            ("jit_cb_prefill(2)", 0.010, 0.030),
            ("jit_cb_decode(1)", 0.030, 0.040)]},
        {"name": "XLA Ops", "events": [
            ("%fusion.1 = bf16[128,7680]{1,0} fusion(...)", 0.000, 0.007),
            (entry, 0.007, 0.008), (entry, 0.015, 0.016),
            (entry, 0.036, 0.037)]}]}]
    facts = {"cell": CELL, "config": CFG, "trace": {"modules_by_span": {
        "engine.decode": {"main": "jit_cb_decode", "seconds": 0.02,
                          "runs": 2}}}}
    assert _pangu.mtp_step_share(facts, planes) == pytest.approx(35.0)
    assert _pangu.mtp_step_share(facts, []) is None
    # a second op of one run under the mark: the mark names nothing
    planes[0]["lines"][1]["events"].append((entry, 0.002, 0.003))
    with pytest.raises(ValueError, match="carry the module's mark"):
        _pangu.mtp_step_share(facts, planes)


@pytest.fixture(scope="module")
def rehearsal():
    from benchmark import run as bench_run
    out = io.StringIO()
    with redirect_stdout(out):
        assert bench_run.main(["--workload", CELL, "--seed",
                               str(2 ** 31 + 39), "--seconds", "3",
                               "--trace", "1", "--rehearsal", "1"]) == 0
    text = out.getvalue()
    return json.loads(text.strip().splitlines()[-1]), text


def test_rehearsal_reports_the_references_log_probabilities(rehearsal):
    """float32 on the CPU, sampled at temperature 1: what the program
    reports of every emitted token and of every draft is the
    reference's to rounding, in a house that runs steps ahead and
    rejects about every other draft."""
    line, text = rehearsal
    assert line["correct"] is True and line["failed"] == 0
    assert line["counts"]["served_tokens_compared"] > 0
    assert line["counts"]["drafts_compared"] > 0
    got = {row.split(": ")[0].split(" ")[1]: float(
        row.split(": ")[1].split(" ")[0]) for row in text.splitlines()
        if row.startswith("compared ")}
    assert got["logprob_gap"] < 1e-3 and got["logprob_gap_mean"] < 1e-4
    assert got["draft_logprob_gap_mean"] < 1e-4
    # the tokens are the main model's draws, the drafts accepted as
    # often as sum min(p, q) says, to the standard errors of the sample
    assert got["accept_z"] < 6 and got["sampled_logprob_z"] < 6
    assert "accepted of those compared" in text
    assert "served_gap" not in got               # nothing greedy to hold
    assert got["compiles_in_window"] == 0
    assert "resident: {'params': " in text


def test_rehearsal_finds_the_counter_readers(rehearsal):
    line, text = rehearsal
    assert set(line["readers"]) >= {
        "compile_s", "decode_step_ms.pangu", "prefill_ms.pangu",
        "slot_occupancy.pangu", "expert_tokens_per_step.pangu",
        "expert_max_load.pangu", "mtp_accept_share.pangu",
        "tokens_per_step.pangu"}
    counters = next(r for r in text.splitlines() if r.startswith("counters"))
    found = json.loads(counters.split(": ", 1)[1].replace("'", '"'))
    assert 0 < found["cb_drafts_accepted"] < found["cb_drafts_made"]
    assert found["cb_emit_slot_steps"] < found["cb_tokens_emitted"] \
        <= 2 * found["cb_emit_slot_steps"]
    assert found["cb_routed_max_load"] > 0 and found["cb_block_bytes"] > 0


def _control(name, seed=5, check_requests=None):
    from benchmark.runners import serve_pangu
    cell = harness.Cell(CELL, rehearsal=True)
    if check_requests:
        cell.spec = {**cell.spec, "check_requests": check_requests}
    log = harness.start_jax(cell)
    import time
    with redirect_stdout(io.StringIO()):
        got = serve_pangu.run(cell, seed=seed, seconds=2.0, trace=False,
                              t_process=time.perf_counter(), compile_log=log,
                              control=name)
    return got, {r["name"]: r for r in got["compared"]}, cell


def test_a_greedy_run_goes_through_the_greedy_comparison():
    """`--control greedy` (benchmark/probe.py): the program at
    temperature 0 with the same files; its tokens are the reference's
    first choices as the greedy cells' are, reported as the control's
    reading."""
    got, rows, cell = _control("greedy")
    assert got["correct"] and got["control"]["widest"] < 1e-3
    assert got["counts"]["served_tokens_compared"] > 0
    assert "logprob_gap" not in rows and "served_gap" not in rows
    assert cell.config["serve"]["temperature"] == 1.0     # a copy was run


def test_a_faulty_rule_comes_out_as_not_correct():
    """`--control accept_all`: the program accepting every draft reports
    the right log-probability of every token, so the three gaps pass;
    the tokens themselves do not: more drafts accepted than sum
    min(p, q) allows, and tokens the main model would not have drawn."""
    from singa_tpu.serve import engine as program
    rule = program.verify_draft
    # some 200 drafts: a rule that accepts all of them where half may be
    # lies a dozen standard errors out
    got, rows, _ = _control("accept_all", check_requests=30)
    assert rows["drafts_compared"]["value"] > 120
    assert program.verify_draft is rule
    assert not got["correct"]
    for name in ("logprob_gap", "logprob_gap_mean",
                 "draft_logprob_gap_mean"):
        assert rows[name]["ok"], name
    assert not rows["accept_z"]["ok"] and rows["accept_z"]["value"] > 6
    assert rows["sampled_logprob_z"]["value"] > 3


def test_a_control_of_the_reference_goes_through_the_comparison():
    """`--control no_rope`: the control's readings are rows of their
    own beside the sound ones, held to the same limits, so the line
    says not correct."""
    got, rows, _ = _control("no_rope")
    assert rows["logprob_gap_mean"]["ok"] and rows["accept_z"]["ok"]
    assert not rows["no_rope.logprob_gap_mean"]["ok"]
    assert not got["correct"]
    assert got["control"]["mean"] == rows["no_rope.logprob_gap_mean"]["value"]


@pytest.mark.parametrize("rows", [1, 2])
def test_the_paged_kernel_compiles_at_128_heads(rows, one_chip,
                                                kernels_for_tpu):
    """`singa_paged_decode` at the cell's geometry, for a described v5e
    (the pre-flight of `test_bench_preflight.py`, whose fixtures these
    are): 64 slots x 448 blocks of (1, 16, 640) latent rows under 128
    heads, one query row a slot and a verify step's two (256 rows of
    heads in one block of VMEM): Mosaic takes both."""
    import jax
    import jax.numpy as jnp
    from singa_tpu.ops.paged_attention import paged_decode_attention
    s, h, t = 64, 128, 448
    on = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,   # noqa: E731
                                                sharding=one_chip)
    compiled = jax.jit(lambda q, pool, tables, ntoks: paged_decode_attention(
        q, pool, tables, ntoks, value_dim=512, scale=192 ** -0.5,
        rows=rows)).lower(
        on((s, rows * h, 640), jnp.bfloat16),
        on((s * t + 1, 1, 16, 640), jnp.bfloat16), on((s, t), jnp.int32),
        on((s,), jnp.int32)).compile()
    assert "singa_paged_decode" in compiled.as_text()
