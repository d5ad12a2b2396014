"""From a profiler trace to numbers: device busy union, idle share, time by
operation name, the longest idle gaps and what the host was doing in them.

The reduction works on plain events, ``(plane, line, name, start_ns,
dur_ns)``; ``read_xplane`` turns the profiler's ``.xplane.pb`` into them.
``data/small_trace.json`` is a cut of a recorded v5e trace to check it on.
"""
import glob
import json
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
# on a TPU plane the operations are on this line; "XLA Modules" and
# "Steps" span whole programs, idle stretches inside them included
OP_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "perf."


def start(trace_dir):
    """Start the profiler with its Python tracer off: that tracer slows
    the host by a third and floods the trace; the benchmark's own spans
    (``jax.profiler.TraceAnnotation``) are host-tracer events and stay."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


_OPCODE = re.compile(r"[}\)] ([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_KIND = re.compile(r"kind=(k\w+)")


def short_name(text):
    """A device operation's trace name is its whole HLO line. Operations
    that differ only by layer share a short name: the opcode, then the
    custom call's target, or a fusion's kind and the shape it gives."""
    if " = " not in text:
        return text[:80]
    _lhs, rhs = text.split(" = ", 1)
    op = _OPCODE.search(rhs)
    opcode = op.group(1) if op else "op"
    target = _TARGET.search(rhs)
    if target:
        return f"{opcode} {target.group(1)}"
    shape = rhs.split("{", 1)[0].split(" ", 1)[0]
    if shape.startswith("("):
        shape += ",..)"
    kind = _KIND.search(rhs)
    if kind:
        opcode = f"{opcode} {kind.group(1)}"
    return f"{opcode} {shape}"[:80]


def read_xplane(trace_dir):
    """Events of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    events = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if device and line.name != OP_LINE:
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_SPAN_PREFIX):
                    continue
                events.append((plane.name, line.name,
                               short_name(ev.name) if device else ev.name,
                               int(ev.start_ns), int(ev.duration_ns)))
    return events


def _union(intervals):
    """Merged, sorted intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce_events(events, t_open_ns=None, t_close_ns=None, top=10):
    """Busy and idle of the device planes over the traced window.

    The window is [t_open_ns, t_close_ns] on the trace's clock, or from
    the first device event's start to the last one's end. Returns None
    where no operation ran on a device.
    """
    by_plane = {}
    host = []
    for plane, _line, name, start, dur in events:
        if plane.startswith(DEVICE_PLANE_PREFIX):
            by_plane.setdefault(plane, []).append((name, start, start + dur))
        elif name.startswith(HOST_SPAN_PREFIX):
            host.append((name, start, start + dur))
    if not by_plane:
        return None
    lo = min(s for evs in by_plane.values() for _n, s, _e in evs)
    hi = max(e for evs in by_plane.values() for _n, _s, e in evs)
    lo = lo if t_open_ns is None else t_open_ns
    hi = hi if t_close_ns is None else t_close_ns
    window = hi - lo
    busy_total = 0
    by_name = {}
    gaps = []
    for evs in by_plane.values():
        clipped = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                   if e > lo and s < hi]
        merged = _union([(s, e) for _n, s, e in clipped])
        busy_total += sum(e - s for s, e in merged)
        for n, s, e in clipped:
            by_name[n] = by_name.get(n, 0) + (e - s)
        edges = [[lo, lo]] + merged + [[hi, hi]]
        for a, b in zip(edges, edges[1:]):
            if b[0] > a[1]:
                gaps.append((a[1], b[0]))
    n_planes = len(by_plane)
    gaps.sort(key=lambda g: g[0] - g[1])
    idle_by_host = _attribute(gaps, host)
    return {
        "busy_s": busy_total / n_planes / 1e9,
        "window_s": window / 1e9,
        "chips": n_planes,
        "by_name_s": {n: t / n_planes / 1e9 for n, t in by_name.items()},
        "device_ops": [[n, t / n_planes / 1e9] for n, t in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, t / n_planes / 1e9] for k, t in sorted(
            idle_by_host.items(), key=lambda kv: -kv[1])[:top]],
        "longest_gap_s": (gaps[0][1] - gaps[0][0]) / 1e9 if gaps else 0.0,
    }


SHORT_GAP_NS = 10_000


def _attribute(gaps, host):
    """Idle nanoseconds by what the host was doing: each gap's time goes
    to the benchmark's own host spans that overlap it, the innermost
    (shortest) span claiming first; what no span covers is
    ``unattributed``, and gaps under 10 us are lumped together."""
    spans = sorted(host, key=lambda h: h[2] - h[1])
    out = {}
    for s, e in gaps:
        if e - s < SHORT_GAP_NS:
            out["between operations (<10us)"] = out.get(
                "between operations (<10us)", 0) + (e - s)
            continue
        free = [(s, e)]
        for name, hs, he in spans:
            if he <= s or hs >= e or not free:
                continue
            rest = []
            for fs, fe in free:
                lo, hi = max(fs, hs), min(fe, he)
                if hi > lo:
                    out[name] = out.get(name, 0) + (hi - lo)
                    rest += [(fs, lo)] if lo > fs else []
                    rest += [(hi, fe)] if fe > hi else []
                else:
                    rest.append((fs, fe))
            free = rest
        left = sum(fe - fs for fs, fe in free)
        if left:
            out["unattributed"] = out.get("unattributed", 0) + left
    return out


def kernel_seconds(reduced, needle):
    """Device time of the operations whose name contains ``needle``, or
    None where there is none."""
    hits = [t for n, t in reduced["by_name_s"].items() if needle in n]
    return sum(hits) if hits else None


def load_recorded(path):
    with open(path) as f:
        return [tuple(e) for e in json.load(f)["events"]]
