"""The whole serving step's share of the chip's peak over the window."""


def read(facts):
    flops = facts.get("processed_flops")
    if not flops or not facts.get("peaks"):
        return None
    return 100.0 * flops / (facts["window_s"]
                            * facts["peaks"]["bf16_flops_per_s"])
