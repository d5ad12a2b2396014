"""The paged attention kernel's share of its roofline in a model with
window and full layers, in the traced window: the time of every
``paged_attention`` call against the least the keys in view BY KIND allow.
The runner reduces the trace (``facts["moe_trace"]``); a program without
the kernel's name or the spans' sums by kind gives nothing to read."""
from perf.lib import flops_afmoe


def read(facts):
    found, peaks = facts.get("moe_trace"), facts.get("peaks")
    if not found or not peaks or not found.get("attn_s"):
        return None
    least = sum(flops_afmoe.attention_step_least_s(facts["config"], call,
                                                   peaks)
                for call in found["calls"])
    return 100.0 * least / found["attn_s"] if least else None
