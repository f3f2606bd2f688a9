"""The Kimi-Linear configuration's manifest: the published widths kept,
the three reduced keys with the published counts and the deployment
beside them, the parameter count and the per-layer figures of ISSUE 27
reckoned again from the leaf table, the bytes resident at 96 slots, and
the counts of bytes a decode step cannot avoid."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, kimi_opcount, kimi_weights  # noqa: E402
from benchmark.reference import kimi_linear  # noqa: E402

NAME = "kimilinear-serve-l17-ep8"
CELL = "serve-kimi-decode-sat"
MANIFEST = harness.read_json(ROOT, "BENCHMARK.json")
ENTRY, = [c for c in MANIFEST["configs"] if c["name"] == NAME]
CFG = harness.read_json(ROOT, ENTRY["file"])
REDUCED = {"num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840}


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog beside the model-configs guide is not here")
    with open(path) as f:
        row, = [r for r in map(json.loads, f)
                if r["name"] == "Kimi-Linear-48B-A3B-Instruct"]
    return row


def test_every_published_number_is_kept_under_its_key():
    row = _catalog()
    assert ENTRY["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert CFG[key] != value and CFG["reduced_from"][key] == value
        else:
            assert CFG[key] == value, key


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 2304), ("intermediate_size", 9216),
    ("moe_intermediate_size", 1024), ("num_experts_per_token", 8),
    ("num_attention_heads", 32), ("kv_lora_rank", 512),
    ("qk_nope_head_dim", 128), ("qk_rope_head_dim", 64),
    ("v_head_dim", 128), ("routed_scaling_factor", 2.446),
    ("num_shared_experts", 1), ("first_k_dense_replace", 1),
    ("rms_norm_eps", 1e-5), ("n_routed_experts", 256)])
def test_published_widths(key, value):
    assert CFG[key] == value
    lin = CFG["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (32, 128, 4)


def test_reduced_is_exactly_the_three_keys_with_the_deployment_beside():
    assert ENTRY["reduced"] == list(REDUCED)
    assert CFG["reduced_from"] == REDUCED
    assert (CFG["num_hidden_layers"], CFG["num_experts"],
            CFG["vocab_size"]) == (17, 32, 20480)
    assert CFG["vocab_size"] * 8 == REDUCED["vocab_size"]
    assert CFG["num_experts"] * 8 == CFG["n_routed_experts"] == 256
    assert "8" in CFG["deployment"] or "eight" in CFG["deployment"]
    assert len(CFG["assumed"]) >= 3
    kinds = kimi_linear.layer_kinds(CFG)
    assert kinds[0] == ("kda", "dense")
    assert [i + 1 for i, (m, _) in enumerate(kinds) if m == "mla"] == [
        4, 8, 12, 16]
    assert sum(m == "kda" for m, _ in kinds) == 13
    assert all(f == "moe" for _, f in kinds[1:])


def test_parameter_count_and_the_per_layer_figures():
    """ISSUE 27: 4,534.9 M; dense layer 103 M; a KDA MoE layer 273.7 M,
    an MLA one 263.3 M, of which 226.5 M in the 32 held experts; KDA
    39.5 M, MLA 29.1 M; 94 M in embedding and head."""
    assert kimi_weights.param_count(CFG) == CFG["params"] == 4534864672
    part = {k: round(v / 1e6, 1)
            for k, v in kimi_weights.counts_by_part(CFG).items()}
    assert part == {"dense_layer": 103.2, "kda_moe_layer": 273.7,
                    "mla_moe_layer": 263.3, "kda": 39.5, "mla": 29.1,
                    "held_experts": 226.5, "embed_and_head": 94.4}
    assert kimi_opcount.expert_params(CFG) == 3 * 2304 * 1024


def test_resident_bytes_at_96_slots():
    """bf16 weights 9.07 GB, float32 KDA state 96 x 13 x 2.17 MB = 2.71
    GB, latent pool (96 x 128 + 1) blocks x 16 x 576 x 2 B x 4 layers =
    0.91 GB: 12.7 GB of the chip's 16."""
    sv = CFG["serve"]
    assert (sv["cb_slots"], sv["cb_block_len"], sv["cb_prompt_cap"],
            sv["max_new_tokens"], sv["dtype"], sv["state_dtype"]) == (
                96, 16, 1024, 1024, "bfloat16", "float32")
    per_slot = (sv["cb_prompt_cap"] + sv["max_new_tokens"]) // 16
    got = kimi_opcount.resident_bytes(CFG, 96, 96 * per_slot + 1, 16, 2)
    assert got["weights"] == 2 * CFG["params"]
    assert kimi_opcount.slot_state_bytes(CFG, 2) == 13 * (
        32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2)
    assert round(got["state"] / 1e9, 2) == 2.71
    assert round(got["latent_pool"] / 1e9, 2) == 0.91
    assert round(got["total"] / 1e9, 1) == 12.7
    assert got["total"] > 0.25 * 16e9


def test_the_bytes_a_decode_step_cannot_avoid():
    """At 96 busy slots of ~450 live tokens with every held expert
    touched: held experts 7.2 GB, state read and written 5.2, other
    weights 1.7, live latent 0.2."""
    e = kimi_opcount.expert_params(CFG) * 2
    fixed = kimi_opcount.fixed_params(CFG) * 2
    assert round(16 * 32 * e / 1e9, 1) == 7.2
    assert round(fixed / 1e9, 1) == 1.7
    assert round(2 * 96 * kimi_opcount.slot_state_bytes(CFG, 2) / 1e9,
                 1) == 5.4
    live = 96 * 450
    assert round(live * kimi_opcount.latent_bytes_per_token(CFG, 2) / 1e9,
                 1) == 0.2
    whole = kimi_opcount.decode_step_needed_bytes(CFG, 96, live, 16 * 32, 2)
    assert 14.0e9 < whole < 15.0e9
    fewer = kimi_opcount.decode_step_needed_bytes(CFG, 96, live, 16 * 30, 2)
    assert whole - fewer == 32 * e
    assert kimi_opcount.decode_step_flops(CFG, 96, live, 16 * 96) > 0


def test_the_cell_and_its_traffic():
    cell = harness.Cell(CELL)
    assert cell.entry["chips"] == 1 and cell.spec["runner"] == "serve_kimi"
    assert cell.spec["at_window_end"] == "cancel"
    mix = cell.traffic
    assert mix["generator"] == "open_loop"
    assert mix["prompt"] == {"dist": "lognormal", "median": 256,
                             "sigma": 0.6, "lo": 64, "hi": 1024}
    assert mix["output"] == {"dist": "lognormal", "median": 384,
                             "sigma": 0.5, "lo": 128, "hi": 1024}
    assert mix["rate_rps"] > 0 and "knee" in mix["rate_from"]
    assert set(cell.spec["limits"]) == {"served_gap", "served_gap_mean"}
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert names == {"compile_s", "decode_step_ms.kimi", "prefill_ms.kimi",
                     "slot_occupancy.kimi", "step_host_ms.kimi",
                     "device_idle.kimi", "decode_roofline.kimi",
                     "expert_tokens_per_step.kimi"}
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "out_tok_s", "setup_s"}


def test_program_names_cover_the_nets_parameters():
    from benchmark.runners import serve_kimi
    from singa_tpu.core.net import build_net
    from singa_tpu.data import discover_input_shapes
    tiny = harness._tiny(CFG)
    model = serve_kimi.model_config(tiny, 16)
    net = build_net(model, "kTrain",
                    discover_input_shapes(model, force_synthetic=True))
    table = {serve_kimi.program_name(n): tuple(s)
             for n, s, _ in kimi_weights.leaf_table(tiny)}
    assert table == {k: tuple(v.shape) for k, v in net.param_specs.items()}


def test_readers_return_none_where_there_is_nothing_to_read():
    """A program without the routing counters, a run without a trace:
    the new readers return None and do not raise."""
    facts = {"cell": CELL, "config": CFG, "counters": {"cb_steps": 0},
             "spans": [("engine.decode", 0.0, 1.0, 5)], "trace_span": (0, 2),
             "trace": {"modules_by_span": {"engine.decode": {
                 "seconds": 1.0, "runs": 1}}},
             "peaks": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0},
             "itemsize": 2}
    cell = harness.Cell(CELL)
    for name in ("decode_roofline.kimi", "expert_tokens_per_step.kimi",
                 "slot_occupancy.kimi", "step_host_ms.kimi"):
        assert cell.load("layer_metrics", name).read(facts) is None


def test_decode_roofline_reads_the_steps_own_counts():
    row = ("engine.decode", 0.5, 0.6, 96 * 450, 96, 16 * 30, 16 * 90)
    facts = {"cell": CELL, "config": CFG, "spans": [row],
             "trace_span": (0.0, 1.0), "itemsize": 2,
             "trace": {"modules_by_span": {"engine.decode": {
                 "seconds": 0.025, "runs": 1}}},
             "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    cell = harness.Cell(CELL)
    got = cell.load("layer_metrics", "decode_roofline.kimi").read(facts)
    need = kimi_opcount.decode_step_needed_bytes(CFG, 96, 96 * 450, 480, 2)
    assert got == pytest.approx(100 * need / 819e9 / 0.025)
    c = {"cb_routed_layer_steps": 1600, "cb_routed_assignments": 144000}
    assert cell.load("layer_metrics", "expert_tokens_per_step.kimi").read(
        {"config": CFG, "counters": c}) == pytest.approx(144000 / 1600 / 32)


# -- the window opens on a full house -----------------------------------------

def test_the_window_opens_into_a_schedule_that_is_already_running():
    """Requests due before the window are sent when they are due, the
    window opens `preroll` seconds in, once, and closes `seconds`
    later."""
    import time
    from benchmark.runners import serve_kimi
    from benchmark.traffic.open_loop import Request

    class Sched:
        def __init__(self):
            self.sent = []

        def submit(self, tokens, max_new, cancel_event):
            self.sent.append(time.perf_counter())
            return object()

    reqs = [Request(d, None, 1) for d in (0.0, 0.04, 0.12, 0.2)]
    sched, opened = Sched(), []
    t_start = time.perf_counter()
    sent, t0 = serve_kimi.drive(sched, reqs, 0.15, 0.1,
                                lambda: opened.append(time.perf_counter()))
    t_end = time.perf_counter()
    assert len(opened) == 1 and len(sent) == 4
    assert t0 - t_start == pytest.approx(0.1, abs=0.02)
    assert opened[0] >= t0 and t_end >= t0 + 0.15
    assert sched.sent[1] < opened[0] <= sched.sent[2]
    assert [s.due - (t0 - 0.1) for s in sent] == pytest.approx(
        [0.0, 0.04, 0.12, 0.2], abs=1e-6)
    # a schedule that ends before the window opens it all the same
    sent, t0 = serve_kimi.drive(sched, reqs[:1], 0.02, 0.05,
                                lambda: opened.append(0))
    assert len(opened) == 2 and time.perf_counter() >= t0 + 0.02


def test_the_cell_opens_two_thirds_of_a_window_into_its_schedule():
    assert harness.Cell(CELL).spec["preroll_of_window"] == pytest.approx(
        2 / 3, abs=1e-3)


def test_a_step_handed_over_ahead_is_a_row_of_its_own_period():
    """While every slot is busy the scheduler calls
    `dispatch_cb_decode` / `fetch_cb_decode`: the runner's rows run from
    the return of the fetch before to the return of the step's own, and
    carry the step's counts as the ordinary rows do."""
    import numpy as np
    from benchmark.runners import serve_kimi

    class Stats:
        cb_routed_experts_touched = cb_routed_assignments = 0

    class Engine:
        stats = Stats()

        def run_cb_decode(self, *a):
            return None

        def dispatch_cb_prefill(self, *a):
            return "first", "pools"

        def fetch_cb_prefill(self, flying):
            return 7

        def dispatch_cb_decode(self, *a):
            return "tokens", "pools"

        def fetch_cb_decode(self, flying):
            self.stats.cb_routed_experts_touched += 11
            self.stats.cb_routed_assignments += 24
            return np.zeros((2,), np.int32)

    engine = Engine()
    spans = serve_kimi._Spans(engine)
    ntoks = np.array([5, 9], np.int32)
    engine.dispatch_cb_decode(None, None, None, ntoks, None)
    engine.dispatch_cb_decode(None, None, None, ntoks + 1, None)
    engine.fetch_cb_decode("tokens")
    engine.dispatch_cb_prefill(None, None, None, 6, None)
    engine.fetch_cb_decode("tokens")
    assert engine.fetch_cb_prefill("first") == 7
    a, b, pre = spans.rows
    assert a[0] == b[0] == "engine.decode" and pre[0] == "engine.prefill"
    assert a[3:] == (14, 2, 11, 24) and b[3:] == (16, 2, 11, 24)
    assert b[1] == a[2]                  # from the fetch before it
    assert pre[3] == 6 and a[2] < pre[1] < b[2] < pre[2]
