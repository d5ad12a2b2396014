"""The latent form of the paged attention kernel's share of its roofline
in the traced window: the time of every ``paged_latent_attention`` call
against the least the latent entries in view allow. The runner reduces the
trace (``facts["moe_trace"]`` of ``serve_kimi_linear.py``); a program
without the kernel's name gives nothing to read."""
from perf.lib import flops_kimi_linear as flops


def read(facts):
    found, peaks = facts.get("moe_trace"), facts.get("peaks")
    if not found or not peaks or not found.get("latent_s"):
        return None
    layers = flops.dims(facts["config"])["mla_layers"]
    least = sum(layers * flops.least_s(flops.latent_call_cost(
        facts["config"], call["q_tokens"], call["kv_tokens"],
        call["attn_pairs"]), peaks)
        for call in found["calls"] if call.get("attn_pairs"))
    return 100.0 * least / found["latent_s"] if least else None
