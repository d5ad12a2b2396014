"""The paged attention kernel's share of its roofline in the traced window."""
import json
import os

from perf.lib import flops, trace

with open(os.path.splitext(__file__)[0] + ".json") as _f:
    NEEDLE = json.load(_f)["kernel_name_contains"]


def read(facts):
    reduced, calls = facts.get("trace"), facts.get("traced_calls")
    if not reduced or not calls or not facts.get("peaks"):
        return None
    seconds = trace.kernel_seconds(reduced, NEEDLE)
    if not seconds:
        return None
    m = flops.decoder_dims(facts["config"])
    least = 0.0
    for _t, q_lens, kv_lens, _c, _w in calls:
        ops, nbytes = flops.paged_attention_call_cost(
            q_lens, kv_lens, m["heads"], m["kv_heads"], m["head_dim"])
        least += m["layers"] * max(
            ops / facts["peaks"]["bf16_flops_per_s"],
            nbytes / facts["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
