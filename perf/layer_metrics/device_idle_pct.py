"""Share of the traced window in which no operation ran on the device."""


def read(facts):
    reduced = facts.get("trace")
    if not reduced or reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
