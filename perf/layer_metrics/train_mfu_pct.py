"""The whole training step's share of the chip's peak over the window."""


def read(facts):
    if not facts.get("peaks") or not facts.get("step_flops"):
        return None
    # a traced run reads its step time after the profiler has stopped
    step_s = facts.get("untraced_step_ms",
                       facts["end_to_end"]["train_step_ms"]) / 1e3
    return 100.0 * facts["step_flops"] / step_s / (
        facts["peaks"]["bf16_flops_per_s"])
