"""Model FLOP/s utilization: the operations the forward and backward passes need per token (3 x forward, causal half, no recomputation; benchmark/opcount.py) times tokens/s, over chips x peak."""
from benchmark import opcount


def read(facts):
    peaks = facts.get("peaks")
    if not peaks:
        return None
    per_tok = opcount.train_flops_per_token(facts["config"],
                                            facts["counters"]["seq_len"])
    rate = facts["end_to_end"]["train_tok_s"]
    return 100.0 * per_tok * rate / (facts["chips"]
                                     * peaks["bf16_flops_per_s"])
