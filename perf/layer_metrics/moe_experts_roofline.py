"""The grouped expert products' share of their roofline in the traced
window. The runner reduces the trace (``facts["moe_trace"]``); a program
without the scopes, the spans' args or the experts gives nothing to read."""
from perf.lib import flops_moe


def read(facts):
    found, peaks = facts.get("moe_trace"), facts.get("peaks")
    if not found or not peaks or not found.get("experts_s"):
        return None
    least = 0.0
    for call in found["calls"]:
        if not call.get("moe_experts_touched"):
            continue        # a step nobody fetched the counts of
        ops, nbytes = flops_moe.experts_call_cost(
            facts["config"], call["moe_assignments"],
            call["moe_experts_touched"])
        least += max(ops / peaks["bf16_flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / found["experts_s"] if least else None
