"""The KDA recurrence kernel's share of its roofline in the traced window:
the time of every ``kda_recurrent`` call against the least its live state
rows and lanes allow. The runner reduces the trace (``facts["moe_trace"]``
of ``serve_kimi_linear.py``); a program without the kernel's name or the
spans' ``state_rows`` gives nothing to read."""
from perf.lib import flops_kimi_linear as flops


def read(facts):
    found, peaks = facts.get("moe_trace"), facts.get("peaks")
    if not found or not peaks or not found.get("kda_s"):
        return None
    layers = flops.dims(facts["config"])["kda_layers"]
    least = sum(layers * flops.least_s(flops.kda_call_cost(
        facts["config"], call["state_rows"], call["q_tokens"]), peaks)
        for call in found["calls"] if call.get("state_rows"))
    return 100.0 * least / found["kda_s"] if least else None
