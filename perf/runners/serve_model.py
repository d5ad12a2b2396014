"""Runner of served configurations that the program's MODEL module describes
(``paddle_tpu/models``): the spec, the weights and the engine's shapes are
asked of the model the configuration names (``model.module`` /
``model.spec``), not built here from one family's keys as
``serve_decoder.py`` does for XGLM.

Drives ``DecodeEngine.submit`` / ``stream_tokens`` in this process from
closed-loop clients, as ``serve_decoder.py`` does (its clients, window and
instrumentation are used as they are), and hands ``perf/run.py`` the same
``facts``. What differs is what a block-diffusion model needs: answers
rounded up to whole blocks, ``denoise_steps`` on every request, the
operations of ``perf/lib/flops_moe.py``, a comparison of each checked
request's recorded denoise passes with the plain reference, and, in a
traced run, device time by ``jax.named_scope`` (``perf/lib/xplane.py``).

As a tool, on the chip at the cell's own size (the benchmark's runs never
run the control):

    python3 perf/runners/serve_model.py --workload <cell> --seeds 1,2 \
        [--seconds 15] [--fault unmask_order|wrong_token]

prints for each seed the program's readings and, judged by the same checks,
each control's (the reference one precision down in the program's place:
weights and stored activations in float8_e4m3, and beside it weights alone)
with its verdict; with ``--fault``, those of a program that unmasks one lane
out of order, or answers a token that is not its choice.
"""
import importlib
import json
import os
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf.lib import flops_moe, stats, traffic, xplane  # noqa: E402
from perf.lib import trace as tracelib  # noqa: E402
from perf.lib.device import memory_peak_bytes  # noqa: E402
from perf.lib.loader import BenchmarkError, load_module  # noqa: E402

_sd = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "serve_decoder.py"), "serve_decoder")

CONTROL = "float8_e4m3"
CONTROLS = (CONTROL, "float8_e4m3_weights")
DEVICE_CALL = "serving.decode.device_call"
CALL_ARGS = ("slots", "chunk", "width", "q_tokens", "kv_tokens",
             "attn_pairs", "prefill_slots", "denoise_slots", "commit_slots",
             "moe_assignments", "moe_experts_touched")


def model_spec(cfg):
    """The program's model of this configuration. A program without the
    module cannot run the cell: a BenchmarkError, at once."""
    try:
        cls = getattr(importlib.import_module(cfg["model"]["module"]),
                      cfg["model"]["spec"])
    except (ImportError, AttributeError) as e:
        raise BenchmarkError(
            f"the program has no model {cfg['model']['module']}."
            f"{cfg['model']['spec']} ({type(e).__name__}: {e}): it cannot "
            f"run configuration {cfg['name']!r}")
    assumed = cfg["assumed"]
    return cls.from_config(cfg, block_length=int(assumed["block_length"]),
                           mask_token_id=int(assumed["mask_token_id"]),
                           dtype=cfg["precision"]["weights"])


class _WithDenoiseSteps:
    """The engine as serve_decoder's closed-loop client drives it, with the
    mix's ``denoise_steps`` on every submit (the block model's request
    field, which that client does not know)."""

    def __init__(self, engine, denoise_steps):
        self._engine, self._steps = engine, int(denoise_steps)

    def submit(self, *args, **kw):
        return self._engine.submit(*args, denoise_steps=self._steps, **kw)

    def __getattr__(self, name):
        return getattr(self._engine, name)


def sessions_of(mix, vocab, seed, block):
    """The mix's sessions with each answer rounded up to a whole block."""
    sessions = traffic.closed_loop_sessions(mix, vocab, seed)
    for client in sessions:
        for session in client:
            for r in session:
                r["max_new"] = -(-r["max_new"] // block) * block
    return sessions


def _processed_flops(cfg, log, t_open, t_close, block, steps):
    """Operations needed by what was processed inside the window: the
    prompt's whole blocks (spread evenly from submit to the first tokens)
    and, for each token that arrived, its share of its block's passes
    (denoise and commit, every lane unembedded)."""
    total = 0.0
    for r in log:
        times = r["token_times"]
        if not times:
            continue
        n_prompt = len(r["spec"]["prompt"])
        whole = n_prompt // block * block
        span = max(times[0] - r["submit"], 1e-9)
        share = max(0.0, min(times[0], t_close) - max(r["submit"], t_open))
        total += share / span * flops_moe.prefill_flops(cfg, 0, whole, block)
        for i, t in enumerate(times):
            if t_open <= t < t_close:
                first = (n_prompt + i) // block * block
                total += flops_moe.block_flops(cfg, first, block,
                                               steps) / block
    return total


def _serve(ctx, spec, params, phases):
    """Load the engine, ramp, hold the window open for ``seconds`` and wait
    for the first tokens still owed (serve_decoder's window, for a model
    the program describes)."""
    import jax

    from paddle_tpu.observability import metrics
    from paddle_tpu.serving.decode import DecodeEngine

    cell, seconds = ctx["cell"], float(ctx["seconds"])
    eng_opts, mix = cell["engine"], cell["traffic"]
    sessions = sessions_of(mix, spec.vocab, ctx["seed"], spec.block_length)
    t_phase = time.perf_counter()
    engine = DecodeEngine(
        spec, name=cell["name"], slots=list(eng_opts["slots"]),
        page_size=int(eng_opts["page_size"]),
        num_pages=int(eng_opts["num_pages"]),
        max_seq_len=int(eng_opts["max_seq_len"]), params=params)
    phases["engine_load_and_warm"] = time.perf_counter() - t_phase
    t_phase = time.perf_counter()
    checks, log, calls, notes = [], [], [], []
    state = {"closed": threading.Event(), "give_up": threading.Event(),
             "mu": threading.Lock()}
    undo = lambda: None
    try:
        route = engine.stats()["attention_route"]
        checks.append(("attention_route_is_expected",
                       float(route == list(cell["expect_route"])), 1.0,
                       route == list(cell["expect_route"])))
        annotate = _sd._no_span
        if ctx["trace"]:
            annotate, undo = _sd._instrument(engine, calls, notes)
        served = _WithDenoiseSteps(engine, mix["denoise_steps"])
        clients = [_sd._Client(i, served, s, log, state, annotate)
                   for i, s in enumerate(sessions)]
        for c in clients:
            c.start()
        # the ramp ends after a fixed amount of WORK, as serve_decoder's
        ramp_deadline = time.perf_counter() + float(mix["ramp_max_s"])
        while time.perf_counter() < ramp_deadline:
            with state["mu"]:
                made = sum(len(r["token_times"]) for r in log)
            if made >= int(mix["ramp_tokens"]):
                break
            time.sleep(0.005)
        checks.append(("ramp_tokens_made", float(made),
                       float(mix["ramp_tokens"]),
                       made >= int(mix["ramp_tokens"])))
        metrics.reset_metrics("serving.")
        compiles0 = _sd._compiles(metrics)
        t_open = time.perf_counter()
        phases["ramp"] = t_open - t_phase
        if ctx["trace"]:
            time.sleep(min(1.0, seconds / 4))
            tracelib.start(ctx["trace_dir"])
            time.sleep(min(float(cell["trace_seconds"]), seconds / 2))
            jax.profiler.stop_trace()
        time.sleep(max(0.0, t_open + seconds - time.perf_counter()))
        t_close = time.perf_counter()
        state["closed"].set()
        snap = metrics.snapshot("serving.")
        compiled = _sd._compiles(metrics) - compiles0
        deadline = t_close + float(mix.get("first_token_wait_s", 60.0))
        while time.perf_counter() < deadline and any(
                c.is_alive() for c in clients):
            time.sleep(0.02)
        state["give_up"].set()
        t_given_up = time.perf_counter()
        for c in clients:
            c.join(30.0)
        stuck = [c for c in clients if c.is_alive()]
        errors = [c.error for c in clients if c.error is not None]
        exhausted = [c for c in clients if c.exhausted]
        peak = memory_peak_bytes(ctx["devices"]) if ctx["devices"] else None
    finally:
        undo()
        engine.stop(drain=False)
    checks += [
        ("compiles_inside_window", float(compiled), 0.0, compiled == 0),
        ("client_errors", float(len(errors) + len(stuck)), 0.0,
         not errors and not stuck),
        ("clients_out_of_traffic", float(len(exhausted)), 0.0, not exhausted),
    ]
    if errors:
        checks.append(("first_client_error:" + repr(errors[0])[:120],
                       1.0, 0.0, False))
    with state["mu"]:
        log = list(log)
    return {"log": log, "t_open": t_open, "t_close": t_close,
            "t_given_up": t_given_up, "checks": checks, "snap": snap,
            "peak": peak, "notes": notes}


def _picked_passes(n_passes, want):
    """Which of a request's denoise passes are compared: the first, and
    others spread evenly over the rest."""
    if n_passes <= want:
        return list(range(n_passes))
    return sorted({round(i * (n_passes - 1) / (want - 1))
                   for i in range(want)})


def judge(got, limits):
    """The checks one set of readings is held to, the program's or a
    control's alike: ``(name, value, limit, passed)``."""
    checks = []
    for name in ("served_logit_gap", "first_rank_gap_mean_sq"):
        checks.append((name, got[name], float(limits[name]),
                       0.0 <= got[name] <= float(limits[name])))
    # a pass's order gap is negative where it unmasked in order (its
    # least confident unmasked lane above the best lane left behind), so
    # the mean over the compared passes lies under 0; with no order
    # compared nothing was read, and that fails
    checks.append(("unmask_confidence_gap", got["unmask_confidence_gap"],
                   float(limits["unmask_confidence_gap"]),
                   got["orders_compared"] > 0
                   and got["unmask_confidence_gap"]
                   <= float(limits["unmask_confidence_gap"])))
    checks.append(("tokens_compared", float(got["tokens_compared"]),
                   float(limits["min_tokens_compared"]),
                   got["tokens_compared"]
                   >= int(limits["min_tokens_compared"])))
    return checks


def compare(ctx, params, sample):
    """The sample of finished greedy requests against the plain reference,
    pass by pass: the checks, the readings and the seconds it took. With
    ``ctx["control"]`` (one precision or several) also each control's
    readings and its verdict by the same checks (the reference at that
    precision in the program's place, judged in the reference's logits),
    under ``readings["controls"]``."""
    cfg, cell, ref = ctx["config"], ctx["cell"], ctx["reference"]
    want = int(cell["check_passes_per_request"])
    t0 = time.perf_counter()

    def read(judged_by=None):
        tok, order, rank = [np.zeros((0,))], [], [np.zeros((0,))]
        for r in sample:
            passes = r["result"].get("passes") or []
            for i in _picked_passes(len(passes), want):
                g = ref.pass_gaps(
                    params, cfg, r["spec"]["prompt"], r["tokens"],
                    passes[i], judged_by,
                    r["result"].get("first_topk") if i == 0 else None)
                tok.append(np.asarray(g["token_gaps"]))
                if g["order_gap"] is not None:
                    order.append(g["order_gap"])
                if g["rank_gaps"] is not None:
                    rank.append(np.asarray(g["rank_gaps"]))
        tok, rank = np.concatenate(tok), np.concatenate(rank)
        return {"served_logit_gap": float(tok.max()) if tok.size else -1.0,
                "unmask_confidence_gap": (float(np.mean(order)) if order
                                          else 1.0),
                "unmask_confidence_gap_max": (float(max(order)) if order
                                              else 1.0),
                "first_rank_gap_mean_sq": (float(np.mean(np.square(rank)))
                                           if rank.size else -1.0),
                "tokens_compared": int(tok.size),
                "tokens_off_the_best": int((tok > 0).sum()),
                "orders_compared": len(order),
                "orders_out_of_order": int(sum(g > 0 for g in order)),
                "ranks_compared": int(rank.size)}

    readings = read()
    reference_s = time.perf_counter() - t0
    checks = judge(readings, cell["limits"])
    controls = ctx.get("control") or ()
    if isinstance(controls, str):
        controls = (controls,)
    if controls and sample:
        readings["controls"] = {}
        for precision in controls:
            got = read(precision)
            verdict = judge(got, cell["limits"])
            readings["controls"][precision] = dict(
                got, correct=all(ok for _n, _v, _l, ok in verdict),
                failed_by=[n for n, _v, _l, ok in verdict if not ok])
    return checks, readings, reference_s


def moe_trace(trace_dir, bench):
    """Device time by scope and by kernel and the device calls' args, from
    the trace: what ``moe_experts_roofline``, ``moe_route_share_pct`` and
    ``paged_attn_block_roofline`` read."""
    desc = {n: json.load(open(bench.path("layer_metrics", n + ".json")))
            for n in ("moe_experts_roofline", "moe_route_share_pct",
                      "paged_attn_block_roofline")}
    experts = (desc["moe_experts_roofline"]["scope_contains"]
               + desc["moe_experts_roofline"]["name_contains"])
    route = desc["moe_route_share_pct"]["scope_contains"]
    kernel = desc["paged_attn_block_roofline"]["kernel_name_contains"]
    out = {"experts_s": 0.0, "route_s": 0.0, "attn_s": 0.0, "device_s": 0.0,
           "calls": [], "scopes_seen": 0, "custom_calls_s": {}}
    for plane in xplane.read(xplane.newest(trace_dir)):
        device = plane["name"].startswith(tracelib.DEVICE_PLANE_PREFIX)
        for line in plane["lines"]:
            if device and line["name"] != tracelib.OP_LINE:
                continue
            for ev in line["events"]:
                if not device:
                    if ev["name"] == DEVICE_CALL:
                        out["calls"].append({k: ev["stats"].get(k)
                                             for k in CALL_ARGS})
                    continue
                text = ev["name"] + " " + " ".join(
                    v for v in ev["stats"].values() if isinstance(v, str))
                seconds = ev["dur_ns"] / 1e9
                out["device_s"] += seconds
                out["scopes_seen"] += "decoder." in text
                # an operation's trace name is its HLO line: the kernel is
                # the instruction that carries the name the program gave it
                head = ev["name"].split(" = ", 1)[0]
                if "custom-call(" in ev["name"]:
                    key = head.rstrip("0123456789.")
                    out["custom_calls_s"][key] = out["custom_calls_s"].get(
                        key, 0.0) + seconds
                if kernel in head:
                    out["attn_s"] += seconds
                elif any(n in text for n in experts):
                    out["experts_s"] += seconds
                elif any(n in text for n in route):
                    out["route_s"] += seconds
    return out


def run(ctx):
    """One run of one serving cell. Returns the facts the harness turns
    into the result line."""
    import jax

    cfg, cell = ctx["config"], ctx["cell"]
    spec = model_spec(cfg)
    block = spec.block_length
    steps = int(cell["traffic"]["denoise_steps"])
    phases = {"imports": time.perf_counter() - ctx["t_start"]}
    t_phase = time.perf_counter()
    params = jax.block_until_ready(spec.device_arrays(ctx["seed"]))
    phases["weights"] = time.perf_counter() - t_phase
    w = _serve(ctx, spec, params, phases)
    log, t_open, t_close = w["log"], w["t_open"], w["t_close"]

    e2e = stats.serving_window(log, t_open, t_close, w["t_given_up"])
    e2e["setup_s"] = t_open - ctx["t_start"]
    in_window = [r for r in log if t_open <= r["submit"] < t_close]
    failed = sum(1 for r in in_window if r["failed"] or not r["token_times"])
    short = [r for r in log if r["done"] is not None and not r["failed"]
             and len(r["tokens"]) != r["spec"]["max_new"]]
    checks = w["checks"] + [
        ("requests_failed", float(failed), 0.0, failed == 0),
        ("answers_of_wrong_length", float(len(short)), 0.0, not short)]

    # the comparison with the plain reference, after the window has closed,
    # the peak has been read and the engine's pools are freed
    sample = _sd._check_sample(log, t_open, t_close, ctx["seed"],
                               int(cell["check_requests"]))
    compared, readings, reference_s = compare(ctx, params, sample)
    snap = w["snap"]
    facts = {
        "readings": readings, "setup_phases": phases, "notes": w["notes"],
        "schedule": stats.serving_schedule(log, t_open),
        "end_to_end": e2e, "attempted": len(in_window), "failed": failed,
        "checks": checks + compared, "memory_peak_bytes": w["peak"],
        "reference_s": reference_s, "histograms": {
            k: v for k, v in snap.items() if isinstance(v, dict)},
        "counters": {k: v for k, v in snap.items()
                     if not isinstance(v, dict)},
        "window_s": t_close - t_open, "config": cfg, "cell": cell,
        "peaks": ctx["peaks"], "trace": None,
        "prompt_tokens_submitted": sum(
            len(r["spec"]["prompt"]) for r in in_window),
        "processed_flops": _processed_flops(cfg, log, t_open, t_close,
                                            block, steps),
        "requests_finished": sum(
            1 for r in log if r["done"] is not None
            and t_open <= r["done"] < t_close),
    }
    if ctx["trace"]:
        facts["trace"] = tracelib.reduce_events(
            tracelib.read_xplane(ctx["trace_dir"]))
        facts["moe_trace"] = moe_trace(ctx["trace_dir"], ctx["bench"])
        found = facts["moe_trace"]
        facts["notes"].append(
            "moe trace: " + json.dumps({k: v for k, v in found.items()
                                        if k != "calls"})
            + f" over {len(found['calls'])} device calls")
    return facts


def plant_unmask_order(every=2):
    """The fault: every second device call, in each slot that leaves a
    masked lane behind, ONE lane is unmasked out of order (the least
    confident masked lane in place of the most confident), where the
    step's answer is read."""
    import jax.numpy as jnp

    from paddle_tpu.serving.decode import DecodeEngine

    real, calls = DecodeEngine._run_step_arrays, {"n": 0}

    def altered(self, *args, **kw):
        out, logits = real(self, *args, **kw)
        calls["n"] += 1
        masked = kw.get("masked")
        if calls["n"] % every or masked is None or not masked.any():
            return out, logits
        conf = np.asarray(out["confidence"])
        unmask = np.array(out["unmask"])
        for row in range(len(unmask)):
            left = masked[row] & ~unmask[row]
            if unmask[row].any() and left.any():
                lanes = np.arange(unmask.shape[1])
                best = max(lanes[unmask[row]], key=lambda j: conf[row, j])
                worst = min(lanes[left], key=lambda j: conf[row, j])
                unmask[row, best], unmask[row, worst] = False, True
        return dict(out, unmask=jnp.asarray(unmask)), logits

    DecodeEngine._run_step_arrays = altered
    return lambda: setattr(DecodeEngine, "_run_step_arrays", real)


def plant_wrong_token(every=7):
    """The fault: every seventh device call that runs a denoise pass, the
    first lane each slot unmasks answers the token after its choice
    (``(id + 1) % vocab``), where the step's answer is read."""
    import jax.numpy as jnp

    from paddle_tpu.serving.decode import DecodeEngine

    real, calls = DecodeEngine._run_step_arrays, {"n": 0}

    def altered(self, *args, **kw):
        out, logits = real(self, *args, **kw)
        masked = kw.get("masked")
        if masked is None or not masked.any():
            return out, logits
        calls["n"] += 1
        if calls["n"] % every:
            return out, logits
        ids, unmask = np.array(out["ids"]), np.asarray(out["unmask"])
        for row in np.flatnonzero(unmask.any(axis=1)):
            lane = int(np.flatnonzero(unmask[row])[0])
            ids[row, lane] = (ids[row, lane] + 1) % logits.shape[-1]
        return dict(out, ids=jnp.asarray(ids)), logits

    DecodeEngine._run_step_arrays = altered
    return lambda: setattr(DecodeEngine, "_run_step_arrays", real)


FAULTS = {"unmask_order": plant_unmask_order,
          "wrong_token": plant_wrong_token}


def main(argv=None):
    import argparse

    from perf.run import open_cell

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--fault", choices=sorted(FAULTS), default=None)
    args = ap.parse_args(argv)
    _bench, cell_ctx = open_cell(args.workload)
    if args.fault:
        FAULTS[args.fault]()
    for seed in (int(s) for s in args.seeds.split(",")):
        # a planted fault is read in the program's own numbers: no control
        ctx = dict(cell_ctx, seed=seed, seconds=args.seconds, trace=False,
                   trace_dir=None, t_start=time.perf_counter(),
                   control=None if args.fault else CONTROLS)
        facts = run(ctx)
        print("READINGS " + json.dumps({
            "workload": args.workload, "seed": seed, "fault": args.fault,
            "readings": facts["readings"],
            "checks": {n: [v, l, ok] for n, v, l, ok in facts["checks"]},
            "end_to_end": facts["end_to_end"],
            "reference_s": facts["reference_s"],
            "memory_peak_bytes": facts["memory_peak_bytes"]},
            default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
