"""The three flash-attention kernels against their roofline: the least time their traced calls could take (operations and bytes from benchmark/opcount.py) over the time they took.  The trace names no kernel, so each is told by what it returns: forward (out, lse), dq one array of the queries' shape, dkv two of the keys' shape."""
from benchmark import opcount


def signatures(cfg, rows, seq):
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    kvd = cfg["num_key_value_heads"] * cfg["head_dim"]
    q, kv = f"bf16[{rows},{seq},{hd}]", f"bf16[{rows},{seq},{kvd}]"
    return {f"({q}, f32[{rows},{seq},{cfg['num_attention_heads']}])":
            "singa_flash_fwd", q: "singa_flash_dq",
            f"({kv}, {kv})": "singa_flash_dkv"}


def read(facts):
    tr, peaks = facts.get("trace") or {}, facts.get("peaks")
    cfg, c = facts["config"], facts["counters"]
    rows = c["batch"] // (2 if facts["chips"] > 1 else 1)    # per data shard
    known = signatures(cfg, rows, c["seq_len"])
    took = {known[s]: (sec, tr["kernel_calls"][s])
            for s, sec in (tr.get("kernels") or {}).items() if s in known}
    if len(took) < 3 or not peaks:
        return None
    least = 0.0
    for k, (_, calls) in took.items():
        f = opcount.flash_call_flops(k, rows, cfg["num_attention_heads"],
                                     c["seq_len"], cfg["head_dim"])
        b = opcount.flash_call_bytes(k, rows, cfg["num_attention_heads"],
                                     cfg["num_key_value_heads"],
                                     c["seq_len"], cfg["head_dim"], 2)
        least += calls * max(f / peaks["bf16_flops_per_s"],
                             b / peaks["hbm_bytes_per_s"])
    return 100.0 * least / sum(sec for sec, _ in took.values())
