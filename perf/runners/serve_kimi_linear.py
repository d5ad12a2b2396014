"""Runner of the served `kimi_linear` configuration (Kimi-Linear: KDA layers
whose fixed-size state the engine keeps a row a sequence, beside a latent
MLA layer over a one-array paged pool, and a chip's share of the experts).

It drives ``DecodeEngine`` through ``serve_decoder.py``'s closed-loop
clients and hands ``perf/run.py`` the same ``facts`` as
``serve_causal_model.py``, whose model lookup (``model_spec``) and
``plant_wrong_token`` it loads and reuses as that file loads
``serve_decoder.py``. What is its own:

- the operations of ``perf/lib/flops_kimi_linear.py`` (an MLA layer's lane
  sees every key before it, a KDA layer's work a lane does not grow);
- the window's close. A request of this traffic takes minutes (up to 8192
  generated tokens, one a step), so at the close the requests sent before
  the window are still being answered. They are withdrawn at once; a
  request sent INSIDE the window is waited for, up to
  ``first_token_wait_s``, as ``serve_causal_model.py``'s window waits for
  all;
- the comparison (``compare``, ``judge``): ``serve_causal_model.py``'s
  readings and ``served_gap_mean``, the mean over the compared tokens of
  how far a served token's reference logit lies under the reference's
  best. A reading is judged where the cell's ``limits`` name it, and only
  there: the others are printed;
- the checks after the window, on the engine the window ran on, all in
  flight at once: ``check_long_requests`` greedy prompts of
  ``check_long_tokens`` to ``check_long_max`` tokens (the latent pool read
  over many pages) and ``check_short_requests`` greedy prompts of
  ``check_short_tokens`` tokens at most, each in a state row that a
  window's sequence held before it (a row that is not started from zero
  shows in their first tokens). They are in no end-to-end number;
- the trace's sums (``kimi_trace``): the ``kda_recurrent`` and
  ``paged_latent_attention`` kernels' device time, the time under the
  ``decoder.kda`` scope, routing's, and the device calls' args.

As a tool, on the chip at the cell's own size (the benchmark's runs never
run a control or a fault):

    python3 perf/runners/serve_kimi_linear.py --workload <cell> \
        --seeds 1,2 [--seconds 15] [--ramp-tokens N] \
        [--fault none,state_carry,latent_short,wrong_token]

prints for each seed the program's readings, the control's (the
reference one precision down in the program's place) with its verdict, and
the witness's (the reference at the program's own bfloat16, in its place:
what the stated precision alone moves);
with ``--fault``, those of a program in which a sequence's state row is
not started from zero, whose MLA layer sees one key too few, or which
answers, every seventh device call, the token after its choice (several,
comma-joined, in turn in one process; ``none`` the program itself). The
faults are planted where a call's arrays are built or its answer read:
the compiled programs are the program's own.
"""
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

from perf.lib import flops_kimi_linear as flops  # noqa: E402
from perf.lib import stats, traffic, xplane  # noqa: E402
from perf.lib import trace as tracelib  # noqa: E402
from perf.lib.device import memory_peak_bytes  # noqa: E402
from perf.lib.loader import load_module  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_sd = load_module(os.path.join(_HERE, "serve_decoder.py"), "serve_decoder")
_scm = load_module(os.path.join(_HERE, "serve_causal_model.py"),
                   "serve_causal_model")

# the reference one precision down: float8_e4m3 weights and activations
# (each precision of the reference compiles its own layer programs); the
# witness beside it, the reference at the stated bfloat16
CONTROLS = ("float8_e4m3",)
WITNESSES = ("bfloat16",)
# what the comparison reads; a cell judges those its limits name
READINGS = ("served_logit_gap", "served_gap_mean", "first_rank_gap_mean_sq",
            "long_rank_gap_q1_sq", "long_off_best_pct")
DEVICE_CALL = "serving.decode.device_call"
CALL_ARGS = ("slots", "chunk", "width", "q_tokens", "kv_tokens",
             "attn_pairs", "moe_assignments", "moe_experts_touched",
             "state_rows")
KDA_KERNEL = "kda_recurrent"
LATENT_KERNEL = "paged_latent_attention"
KDA_SCOPE = "decoder.kda"
ROUTE_SCOPE = "decoder.moe.route"


def _processed_flops(cfg, log, t_open, t_close):
    """Operations needed by the tokens processed inside the window: prompt
    tokens (spread evenly from submit to the first token) and generated
    tokens (at their arrival)."""
    total = 0.0
    for r in log:
        times = r["token_times"]
        if not times:
            continue
        n_prompt = len(r["spec"]["prompt"])
        span = max(times[0] - r["submit"], 1e-9)
        share = max(0.0, min(times[0], t_close) - max(r["submit"], t_open))
        total += share / span * flops.span_flops(cfg, 0, n_prompt, 1)
        for i, t in enumerate(times[1:], start=1):
            if t_open <= t < t_close:
                at = n_prompt + i - 1
                total += flops.span_flops(cfg, at, at + 1, 1)
    return total


def _checks_after(engine, cell, vocab, seed):
    """After the window: the long and the short greedy checks, lengths
    fixed, ids from the seed, all in flight at once. Records as the
    clients' (``spec``, ``tokens``, ``result``, ``kind``)."""
    rng = np.random.Generator(np.random.PCG64(np.random.SeedSequence(
        [int(seed), 0x6b696d69])))
    lengths = [("long", n) for n in traffic.size_grid(
        {"lo": int(cell["check_long_tokens"]) + 1,
         "hi": int(cell["check_long_max"])},
        int(cell["check_long_requests"]))]
    lengths += [("short", n) for n in traffic.size_grid(
        {"lo": 1, "hi": int(cell["check_short_tokens"])},
        int(cell["check_short_requests"]))]
    recs = []
    for kind, n in lengths:
        answer = int(cell["check_" + kind + "_answer"])
        prompt = rng.integers(0, vocab, size=n, dtype=np.int64).astype(
            np.int32)
        spec = {"prompt": prompt, "max_new": answer, "temperature": 0.0}
        recs.append({"spec": spec, "kind": kind, "req": engine.submit(
            prompt, max_new_tokens=answer, temperature=0.0,
            topk_first=int(cell["traffic"]["greedy_topk_first"]))})
    for rec in recs:
        req = rec.pop("req")
        if not req.ev.wait(float(cell["check_wait_s"])):
            engine.cancel(req, "check timed out")
        rec["failed"] = req.error is not None or req.result is None
        rec["result"] = req.result or {}
        rec["tokens"] = list(rec["result"].get("tokens", ()))
        if req.error is not None:
            rec["error"] = repr(req.error)[:200]
    return recs


def judge(got, limits):
    """The checks one set of readings is held to, the program's or a
    control's alike: ``(name, value, limit, passed)``, for each reading the
    limits name and for the counts compared."""
    checks = [(name, got[name], float(limits[name]),
               0.0 <= got[name] <= float(limits[name]))
              for name in READINGS if name in limits]
    for name in ("tokens_compared", "long_requests_compared"):
        need = int(limits["min_" + name])
        checks.append((name, float(got[name]), float(need),
                       got[name] >= need))
    return checks


def compare(ctx, params, sample):
    """The checked requests against the plain reference: the checks, the
    readings and the seconds it took. With ``ctx["control"]`` also each
    control's (or witness's) readings and verdict by the same checks,
    under ``readings["controls"]``."""
    cell, ref = ctx["cell"], ctx["reference"]
    long_tokens = int(cell["check_long_tokens"])
    t0 = time.perf_counter()

    def read(judged_by=None):
        tok, rank, long_ms, rows = [np.zeros((0,))], [np.zeros((0,))], [], []
        for r in sample:
            t, k = ref.served_gaps(
                params, ctx["config"], r["spec"]["prompt"], r["tokens"],
                judged_by, r["result"].get("first_topk"))
            t = np.asarray(t)
            tok.append(t)
            row = {"context": _scm._context(r), "tokens": int(t.size),
                   "off_best": int((t > 0).sum()),
                   "gap_max": float(t.max()) if t.size else -1.0}
            if k is not None:
                k = np.asarray(k)
                rank.append(k)
                row["rank_gap_mean_sq"] = _sd._mean_sq(k)
                if row["context"] > long_tokens:
                    long_ms.append(row["rank_gap_mean_sq"])
            rows.append(row)
        tok, rank = np.concatenate(tok), np.concatenate(rank)
        long_rows = [row for row in rows if row["context"] > long_tokens]
        long_toks = sum(row["tokens"] for row in long_rows)
        return {"served_logit_gap": float(tok.max()) if tok.size else -1.0,
                # a token the program did not choose lies units under the
                # best: one in seven lifts this mean by a unit, where
                # rounding moves it by hundredths
                "served_gap_mean": float(tok.mean()) if tok.size else -1.0,
                "first_rank_gap_mean_sq": (_sd._mean_sq(rank)
                                           if rank.size else -1.0),
                # the lower quartile of the long requests' own mean
                # squares at their first generated position
                "long_rank_gap_q1_sq": (float(np.quantile(long_ms, 0.25))
                                        if long_ms else -1.0),
                "long_rank_gap_mean_sq": (float(np.mean(long_ms))
                                          if long_ms else -1.0),
                "long_off_best_pct": (100.0 * sum(
                    row["off_best"] for row in long_rows) / long_toks
                    if long_toks else -1.0),
                "tokens_compared": int(tok.size),
                "tokens_off_the_best": int((tok > 0).sum()),
                "ranks_compared": int(rank.size),
                "long_requests_compared": len(long_rows),
                "per_request": rows}

    readings = read()
    reference_s = time.perf_counter() - t0
    checks = judge(readings, cell["limits"])
    if ctx.get("control") and sample:
        readings["controls"] = {}
        for precision in ctx["control"]:
            got = read(precision)
            verdict = judge(got, cell["limits"])
            readings["controls"][precision] = dict(
                got, correct=all(ok for _n, _v, _l, ok in verdict),
                failed_by=[n for n, _v, _l, ok in verdict if not ok])
    return checks, readings, reference_s


def _serve(ctx, spec, params, phases):
    """Load the engine with the cell's options, ramp, hold the window open
    for ``seconds``, withdraw what was sent before it, wait for the first
    tokens owed to what was sent inside it, then serve the checks."""
    import jax

    from paddle_tpu.observability import metrics
    from paddle_tpu.serving.decode import DecodeEngine

    cell, seconds = ctx["cell"], float(ctx["seconds"])
    mix = cell["traffic"]
    sessions = traffic.closed_loop_sessions(mix, spec.vocab, ctx["seed"])
    t_phase = time.perf_counter()
    engine = DecodeEngine(spec, name=cell["name"], params=params,
                          **cell["engine"])
    phases["engine_load_and_warm"] = time.perf_counter() - t_phase
    t_phase = time.perf_counter()
    checks, log, notes = [], [], []
    closed, mu = threading.Event(), threading.Lock()
    # a client's own give-up: one sent before the window is withdrawn at
    # the close, one sent inside it waits for its first token
    states = [{"closed": closed, "give_up": threading.Event(), "mu": mu}
              for _ in sessions]
    undo = lambda: None
    try:
        route = engine.stats()["attention_route"]
        checks.append(("attention_route_is_expected",
                       float(route == list(cell["expect_route"])), 1.0,
                       route == list(cell["expect_route"])))
        annotate = _sd._no_span
        if ctx["trace"]:
            annotate, undo = _sd._instrument(engine, [], notes)
        clients = [_sd._Client(i, engine, s, log, st, annotate)
                   for i, (s, st) in enumerate(zip(sessions, states))]
        for c in clients:
            c.start()
        # the ramp ends after a fixed amount of WORK, as serve_decoder's
        ramp_deadline = time.perf_counter() + float(mix["ramp_max_s"])
        while time.perf_counter() < ramp_deadline:
            with mu:
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
        closed.set()
        snap = metrics.snapshot("serving.")
        compiled = _sd._compiles(metrics) - compiles0
        with mu:
            sent = {r["client"]: r["submit"] for r in log}
        for i, st in enumerate(states):
            if sent.get(i, t_open) < t_open:
                st["give_up"].set()
        deadline = t_close + float(mix["first_token_wait_s"])
        while time.perf_counter() < deadline and any(
                c.is_alive() for c in clients):
            time.sleep(0.02)
        for st in states:
            st["give_up"].set()
        t_given_up = time.perf_counter()
        for c in clients:
            c.join(30.0)
        stuck = [c for c in clients if c.is_alive()]
        errors = [c.error for c in clients if c.error is not None]
        exhausted = [c for c in clients if c.exhausted]
        peak = memory_peak_bytes(ctx["devices"]) if ctx["devices"] else None
        undo()
        undo = lambda: None
        t_checks = time.perf_counter()
        after = _checks_after(engine, cell, spec.vocab, ctx["seed"])
        checks_s = time.perf_counter() - t_checks
        kv = engine.stats()
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
    with mu:
        log = list(log)
    return {"log": log, "t_open": t_open, "t_close": t_close,
            "t_given_up": t_given_up, "checks": checks, "snap": snap,
            "peak": peak, "notes": notes, "after": after,
            "checks_s": checks_s, "kv": kv}


def kimi_trace(trace_dir):
    """Device time by kernel and by scope and the device calls' args, from
    the trace: what ``kda_state_roofline``, ``paged_attn_latent_roofline``,
    ``kda_step_share_pct`` and ``moe_route_share_pct`` read."""
    out = {"kda_s": 0.0, "latent_s": 0.0, "kda_scope_s": 0.0,
           "route_s": 0.0, "device_s": 0.0, "calls": [], "scopes_seen": 0}
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
                # a kernel is the instruction that carries the name the
                # program gave it
                head = ev["name"].split(" = ", 1)[0]
                if KDA_KERNEL in head:
                    out["kda_s"] += seconds
                elif LATENT_KERNEL in head:
                    out["latent_s"] += seconds
                if KDA_SCOPE + "/" in text or KDA_KERNEL in head:
                    out["kda_scope_s"] += seconds
                elif ROUTE_SCOPE in text:
                    out["route_s"] += seconds
    return out


def run(ctx):
    """One run of the serving cell. Returns the facts the harness turns
    into the result line."""
    import jax

    cfg, cell = ctx["config"], ctx["cell"]
    spec = _scm.model_spec(cfg)
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
    short = [r for r in log + w["after"]
             if r.get("done", True) is not None and not r["failed"]
             and len(r["tokens"]) != r["spec"]["max_new"]]
    after_failed = [r for r in w["after"] if r["failed"]]
    short_checks = [r for r in w["after"]
                    if r["kind"] == "short" and not r["failed"]]
    checks = w["checks"] + [
        ("requests_failed", float(failed), 0.0, failed == 0),
        ("answers_of_wrong_length", float(len(short)), 0.0, not short),
        ("checks_after_failed", float(len(after_failed)), 0.0,
         not after_failed),
        ("short_requests_compared", float(len(short_checks)),
         float(cell["limits"]["min_short_requests_compared"]),
         len(short_checks) >= int(
             cell["limits"]["min_short_requests_compared"]))]

    # the comparison with the plain reference, after the window has closed,
    # the peak has been read and the engine's pools are freed
    sample = _sd._check_sample(log, t_open, t_close, ctx["seed"],
                               int(cell["check_requests"]))
    sample += [r for r in w["after"] if not r["failed"]]
    compared, readings, reference_s = compare(ctx, params, sample)
    snap = w["snap"]
    finished = [r for r in log if r["done"] is not None
                and t_open <= r["done"] < t_close]
    kv = w["kv"]
    facts = {
        "readings": readings, "setup_phases": phases,
        "notes": w["notes"] + [
            "checks after the window: %d served in %.1f s" % (
                len(w["after"]), w["checks_s"]),
            "finished in the window: %d requests, %d sent in it" % (
                len(finished), sum(1 for r in in_window)),
            # a run that stood still says where: inside a step (array
            # build to the ids on the host) or between two
            "longest in the window, ms: " + json.dumps({
                k.rsplit(".", 1)[-1]: snap[k].get("max")
                for k in ("serving.decode.step_ms",
                          "serving.decode.sched_ms")
                if isinstance(snap.get(k), dict)}),
            "pools after the run: " + json.dumps(
                {k: kv[k] for k in ("kv", "state", "kv_hbm_bytes")})],
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
        "processed_flops": _processed_flops(cfg, log, t_open, t_close),
        "requests_finished": len(finished),
    }
    if ctx["trace"]:
        facts["trace"] = tracelib.reduce_events(
            tracelib.read_xplane(ctx["trace_dir"]))
        facts["moe_trace"] = found = kimi_trace(ctx["trace_dir"])
        facts["notes"].append(
            "kimi trace: " + json.dumps({k: v for k, v in found.items()
                                         if k != "calls"})
            + f" over {len(found['calls'])} device calls")
    return facts


def _plant_arrays(alter):
    """A fault planted where every call's arrays are built, as
    ``serve_causal_model.py``'s ``page_early``: the compiled programs are
    the program's own, only what they are handed differs."""
    from paddle_tpu.serving.decode import DecodeEngine

    real = DecodeEngine._build_arrays

    def altered(self, *args, **kw):
        return alter(real(self, *args, **kw))

    DecodeEngine._build_arrays = altered
    return lambda: setattr(DecodeEngine, "_build_arrays", real)


def plant_state_carry():
    """The fault: a sequence's state row is never started from zero: its
    KDA state and its convolutions' tails carry on from whatever the row
    held, the sequence's that held the row before."""
    def alter(out):
        tables = out[3]
        return out[:3] + (tables._replace(
            fresh=np.zeros_like(tables.fresh)),) + out[4:]
    return _plant_arrays(alter)


def plant_latent_short():
    """The fault: the MLA layer's kernel sees one key too few (each lane's
    own entry, the newest, is not in view): the keys each row has, as the
    call hands them to it, one short."""
    def alter(out):
        return out[:4] + (np.maximum(out[4] - 1, 0),) + out[5:]
    return _plant_arrays(alter)


plant_wrong_token = _scm.plant_wrong_token

FAULTS = {"state_carry": plant_state_carry,
          "latent_short": plant_latent_short,
          "wrong_token": plant_wrong_token}


def main(argv=None):
    import argparse

    from perf.run import open_cell

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--fault", default=None,
                    help="one of %s, or several joined by commas, each "
                         "planted in turn over every seed (``none``: the "
                         "program with its control)" % sorted(FAULTS))
    ap.add_argument("--ramp-tokens", type=int, default=None,
                    help="the ramp's work in place of the cell's (a "
                         "fault's run reads the checks after the window)")
    args = ap.parse_args(argv)
    _bench, cell_ctx = open_cell(args.workload)
    if args.ramp_tokens is not None:
        cell = dict(cell_ctx["cell"])
        cell["traffic"] = dict(cell["traffic"],
                               ramp_tokens=args.ramp_tokens)
        cell_ctx = dict(cell_ctx, cell=cell)
    faults = (args.fault or "none").split(",")
    for fault in faults:
        if fault != "none" and fault not in FAULTS:
            ap.error(f"unknown fault {fault!r}; known: {sorted(FAULTS)}")
    for fault in faults:
        undo = FAULTS[fault]() if fault != "none" else (lambda: None)
        try:
            for seed in (int(s) for s in args.seeds.split(",")):
                # a planted fault is read in the program's own numbers: no
                # control
                ctx = dict(cell_ctx, seed=seed, seconds=args.seconds,
                           trace=False, trace_dir=None,
                           t_start=time.perf_counter(),
                           control=(CONTROLS + WITNESSES
                                    if fault == "none" else None))
                facts = run(ctx)
                print("READINGS " + json.dumps({
                    "workload": args.workload, "seed": seed,
                    "fault": None if fault == "none" else fault,
                    "readings": facts["readings"],
                    "checks": {n: [v, l, ok]
                               for n, v, l, ok in facts["checks"]},
                    "end_to_end": facts["end_to_end"],
                    "reference_s": facts["reference_s"],
                    "memory_peak_bytes": facts["memory_peak_bytes"],
                    "setup_phases": facts["setup_phases"],
                    "notes": facts["notes"]},
                    default=float), flush=True)
        finally:
            undo()
    return 0


if __name__ == "__main__":
    sys.exit(main())
