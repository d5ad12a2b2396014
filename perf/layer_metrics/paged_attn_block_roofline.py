"""The paged attention kernel's share of its roofline under a block mask, in
the traced window. The runner reduces the trace (``facts["moe_trace"]``); a
program without the kernel's name or the spans' args gives nothing to read."""
from perf.lib import flops_moe


def read(facts):
    found, peaks = facts.get("moe_trace"), facts.get("peaks")
    if not found or not peaks or not found.get("attn_s"):
        return None
    cfg = facts["config"]
    least = 0.0
    for call in found["calls"]:
        if not call.get("attn_pairs"):
            continue
        ops, nbytes = flops_moe.attention_call_cost(
            cfg, call["q_tokens"], call["kv_tokens"], call["attn_pairs"])
        least += flops_moe.dims(cfg)["layers"] * max(
            ops / peaks["bf16_flops_per_s"],
            nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / found["attn_s"] if least else None
