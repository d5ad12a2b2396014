"""Routing's share (router, top-k, sort, gather; weighting, scatter-add) of
all device time in the traced window, by ``jax.named_scope``."""


def read(facts):
    found = facts.get("moe_trace")
    if not found or not found.get("device_s") or found.get(
            "route_s") is None:
        return None
    return 100.0 * found["route_s"] / found["device_s"]
