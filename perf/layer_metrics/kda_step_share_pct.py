"""The KDA layers' share (projections, convolutions, gates and the
recurrence, the scope ``decoder.kda``) of all device time in the traced
window. The runner reduces the trace (``facts["moe_trace"]`` of
``serve_kimi_linear.py``); a program without the scope gives nothing."""


def read(facts):
    found = facts.get("moe_trace")
    if not found or not found.get("device_s") or not found.get(
            "kda_scope_s"):
        return None
    return 100.0 * found["kda_scope_s"] / found["device_s"]
