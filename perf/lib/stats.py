"""Window statistics: from what a window saw to its end-to-end metrics.

Every statistic is over all the work and all the time of the window: a
stall inside it moves each of them.
"""
import bisect
import math


def percentile(values, q):
    """Nearest-rank percentile, the higher neighbour: with 60 values the
    95th is the 57th in rising order."""
    vals = sorted(values)
    if not vals:
        return None
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[rank - 1]


def serving_window(requests, t_open, t_close, t_given_up):
    """End-to-end numbers of a serving window.

    ``requests``: one record per request ever submitted, with ``submit``
    (host clock, s), ``token_times`` (arrival of each generated token) and
    ``failed``. ``t_given_up`` is when the harness stopped waiting for first
    tokens after the close: a request with none by then, or a failed one,
    counts as having waited that long.
    """
    ttft, gaps, tokens_in = [], [], 0
    for r in requests:
        times = r["token_times"]
        if t_open <= r["submit"] < t_close:
            if times and not r["failed"]:
                ttft.append(times[0] - r["submit"])
            else:
                ttft.append(t_given_up - r["submit"])
        for i, t in enumerate(times):
            if t_open <= t < t_close:
                tokens_in += 1
                if i > 0:
                    gaps.append(t - times[i - 1])
    window = t_close - t_open
    out = {"serve_tokens_per_s": tokens_in / window,
           "requests_started": len(ttft), "token_gaps": len(gaps),
           "tokens_in_window": tokens_in}
    if ttft:
        out["ttft_p95_ms"] = percentile(ttft, 95) * 1e3
        out["ttft_p50_ms"] = percentile(ttft, 50) * 1e3
    if gaps:
        out["token_gap_p95_ms"] = percentile(gaps, 95) * 1e3
        out["token_gap_p50_ms"] = percentile(gaps, 50) * 1e3
    return out


STEP_APART_S = 0.03


def serving_schedule(requests, t_open):
    """What a serving window's tails are made from, laid out so that two
    runs can be held side by side: the scheduler's step clock (the instants
    at which tokens arrived, which come in one burst a step) and one row
    per request ever submitted, the ramp's too, in order of submission.
    Times are milliseconds after ``t_open``; a ``*_step`` is the index of
    the last step answered by then; -1 stands for "never"."""
    clock = []
    for t in sorted(t for r in requests for t in r["token_times"]):
        if not clock or t - clock[-1] > STEP_APART_S:
            clock.append(t)

    def ms(t):
        return -1 if t is None else round((t - t_open) * 1e3, 1)

    def step(t):
        return -1 if t is None else bisect.bisect_right(clock, t) - 1

    rows = []
    for r in sorted(requests, key=lambda r: r["submit"]):
        first = r["token_times"][0] if r["token_times"] else None
        last = r["token_times"][-1] if r.get("done") is not None else None
        spec = r.get("spec") or {}
        rows.append([
            r.get("client", -1), len(spec.get("prompt", ())),
            spec.get("max_new", -1), int(spec.get("temperature", 0) <= 0),
            ms(r["submit"]), ms(first), ms(last), step(r["submit"]),
            step(first), step(last), len(r["token_times"]),
            int((r.get("result") or {}).get("steps_to_first_token", -1))])
    return {"columns": ["client", "prompt", "answer", "greedy", "submit_ms",
                        "first_ms", "done_ms", "submit_step", "first_step",
                        "done_step", "tokens", "steps_to_first_token"],
            "rows": rows, "step_clock_ms": [ms(t) for t in clock]}


def training_window(t_open, t_close, steps):
    """The whole window over the steps completed in it. ``t_close`` is when
    the fetch of a value that depends on the last step returned."""
    if steps < 1:
        raise ValueError("a training window needs at least one step")
    return {"train_step_ms": (t_close - t_open) / steps * 1e3,
            "steps": steps}


def median(values):
    return percentile(values, 50)
