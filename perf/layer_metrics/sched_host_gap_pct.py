"""Share of the window the host spends from a step's ids being on the host
to the return of the next step's dispatch (not the device's idle share)."""


def read(facts):
    h = (facts.get("histograms") or {}).get("serving.decode.host_gap_ms")
    if not h or not h.get("count") or not facts.get("window_s"):
        return None
    return 100.0 * h["sum"] / (facts["window_s"] * 1000.0)
