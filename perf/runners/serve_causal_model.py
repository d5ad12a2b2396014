"""Runner of served CAUSAL configurations that the program's model module
describes (``paddle_tpu/models``; ``model.module`` / ``model.spec`` in the
configuration's file), with the cell's engine options handed to
``DecodeEngine`` as they stand (``prefill_chunk`` and ``num_window_pages``
among them). Trinity-Mini is the first: window and full attention layers
over a cache a kind.

It drives ``DecodeEngine.submit`` / ``stream_tokens`` from
``serve_decoder.py``'s closed-loop clients, window and instrumentation, and
hands ``perf/run.py`` the same ``facts``; the trace is reduced by
``serve_model.py``'s ``moe_trace`` with the device calls' sums by layer
kind beside the rest. What is its own:

- the operations of ``perf/lib/flops_afmoe.py`` (a window layer's lane sees
  at most a window of keys);
- the comparison: the reference runs prompt + answer of each checked
  request in ONE pass and the run compares LOGITS (``served_logit_gap``,
  ``first_rank_gap_mean_sq`` over the best tokens the engine ranked at the
  first generated position, and ``long_rank_gap_q1_sq``, the lower quartile
  of that mean square request by request over the requests past
  ``check_long_tokens``, and ``long_off_best_pct``, the share of those
  requests' served tokens that are not the reference's best). Checked are ``check_requests`` finished greedy
  requests of the window, the longest among them, and
  ``check_long_requests`` more whose context passes ``check_long_tokens``:
  a window of 51 s finishes only two or three such greedy requests, so once
  the window has shut and its clients have withdrawn, the SAME engine
  serves that many greedy prompts of fixed lengths from the seed, all in
  flight at once, before it is stopped. They are outside the window and in
  no end-to-end number.

As a tool, on the chip at the cell's own size (the benchmark's runs never
run a control or a fault):

    python3 perf/runners/serve_causal_model.py --workload <cell> \
        --seeds 1,2 [--seconds 15] \
        [--fault window_short|window_long|page_early|wrong_token]

prints for each seed the program's readings and each control's (the
reference one precision down in the program's place) with its verdict; with
``--fault``, those of a program whose window layers see one key too few or
too many, whose window kind's oldest page in view is one the cache has
given to another sequence, or which answers, every seventh device call, the
token after its choice.
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

from perf.lib import flops_afmoe, stats, traffic  # noqa: E402
from perf.lib import trace as tracelib  # noqa: E402
from perf.lib.device import memory_peak_bytes  # noqa: E402
from perf.lib.loader import BenchmarkError, load_module  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_sd = load_module(os.path.join(_HERE, "serve_decoder.py"), "serve_decoder")
_sm = load_module(os.path.join(_HERE, "serve_model.py"), "serve_model")

CONTROLS = ("float8_e4m3", "float8_e4m3_weights")
KIND_ARGS = ("kv_tokens_full", "kv_tokens_window", "attn_pairs_full",
             "attn_pairs_window")


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
    return cls.from_config(cfg, dtype=cfg["precision"]["weights"])


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
        total += share / span * flops_afmoe.span_flops(cfg, 0, n_prompt, 1)
        for i, t in enumerate(times[1:], start=1):
            if t_open <= t < t_close:
                at = n_prompt + i - 1
                total += flops_afmoe.span_flops(cfg, at, at + 1, 1)
    return total


def _long_checks(engine, cell, vocab, seed):
    """After the window: ``check_long_requests`` greedy requests whose
    context passes ``check_long_tokens``, lengths fixed, ids from the
    seed, all in flight at once on the engine the window ran on. Records
    as the clients' (``spec``, ``tokens``, ``result``)."""
    want = int(cell.get("check_long_requests", 0))
    if not want:
        return []
    rng = np.random.Generator(np.random.PCG64(np.random.SeedSequence(
        [int(seed), 0x6c6f6e67])))
    answer = int(cell["check_long_answer"])
    lengths = traffic.size_grid(
        {"lo": int(cell["check_long_tokens"]) + 1,
         "hi": int(cell["traffic"]["suffix_len"]["hi"])}, want)
    recs = []
    for n in lengths:
        prompt = rng.integers(0, vocab, size=n, dtype=np.int64).astype(
            np.int32)
        spec = {"prompt": prompt, "max_new": answer, "temperature": 0.0}
        recs.append({"spec": spec, "req": engine.submit(
            prompt, max_new_tokens=answer, temperature=0.0,
            topk_first=int(cell["traffic"]["greedy_topk_first"]))})
    for rec in recs:
        req = rec.pop("req")
        if not req.ev.wait(float(cell.get("check_long_wait_s", 300.0))):
            engine.cancel(req, "long check timed out")
        rec["failed"] = req.error is not None or req.result is None
        rec["result"] = req.result or {}
        rec["tokens"] = list(rec["result"].get("tokens", ()))
        if req.error is not None:
            rec["error"] = repr(req.error)[:200]
    return recs


def _serve(ctx, spec, params, phases):
    """Load the engine with the cell's options, ramp, hold the window open
    for ``seconds``, wait for the first tokens still owed, then serve the
    long checks (serve_decoder's window, for a model the program
    describes)."""
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
        clients = [_sd._Client(i, engine, s, log, state, annotate)
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
        undo()
        undo = lambda: None
        t_long = time.perf_counter()
        long_recs = _long_checks(engine, cell, spec.vocab, ctx["seed"])
        long_s = time.perf_counter() - t_long
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
    with state["mu"]:
        log = list(log)
    return {"log": log, "t_open": t_open, "t_close": t_close,
            "t_given_up": t_given_up, "checks": checks, "snap": snap,
            "peak": peak, "notes": notes, "long": long_recs,
            "long_s": long_s, "kv": kv}


def _context(r):
    return len(r["spec"]["prompt"]) + len(r["tokens"])


def judge(got, limits):
    """The checks one set of readings is held to, the program's or a
    control's alike: ``(name, value, limit, passed)``."""
    checks = [(name, got[name], float(limits[name]),
               0.0 <= got[name] <= float(limits[name]))
              for name in ("served_logit_gap", "first_rank_gap_mean_sq",
                           "long_rank_gap_q1_sq", "long_off_best_pct")]
    checks.append(("tokens_compared", float(got["tokens_compared"]),
                   float(limits["min_tokens_compared"]),
                   got["tokens_compared"]
                   >= int(limits["min_tokens_compared"])))
    checks.append(("long_requests_compared",
                   float(got["long_requests_compared"]),
                   float(limits["min_long_requests_compared"]),
                   got["long_requests_compared"]
                   >= int(limits["min_long_requests_compared"])))
    return checks


def compare(ctx, params, sample):
    """The checked requests against the plain reference: the checks, the
    readings and the seconds it took. With ``ctx["control"]`` also each
    control's readings and verdict by the same checks, under
    ``readings["controls"]``."""
    cfg, cell, ref = ctx["config"], ctx["cell"], ctx["reference"]
    long_tokens = int(cell["check_long_tokens"])
    t0 = time.perf_counter()

    def read(judged_by=None):
        tok, rank, long_ms, rows = [np.zeros((0,))], [np.zeros((0,))], [], []
        for r in sample:
            t, k = ref.served_gaps(
                params, cfg, r["spec"]["prompt"], r["tokens"], judged_by,
                r["result"].get("first_topk"))
            t = np.asarray(t)
            tok.append(t)
            row = {"context": _context(r), "tokens": int(t.size),
                   "off_best": int((t > 0).sum()),
                   "gap_max": float(t.max()) if t.size else -1.0}
            if k is not None:
                k = np.asarray(k)
                rank.append(k)
                row["rank_gap_mean_sq"] = _sd._mean_sq(k)
                if _context(r) > long_tokens:
                    long_ms.append(row["rank_gap_mean_sq"])
            rows.append(row)
        tok, rank = np.concatenate(tok), np.concatenate(rank)
        long_rows = [row for row in rows if row["context"] > long_tokens]
        long_toks = sum(row["tokens"] for row in long_rows)
        return {"served_logit_gap": float(tok.max()) if tok.size else -1.0,
                "first_rank_gap_mean_sq": (_sd._mean_sq(rank)
                                           if rank.size else -1.0),
                # the lower quartile of the long requests' own mean
                # squares: a flipped 8th/9th expert lifts one request's
                # by a hundredfold and leaves this where it was; a fault
                # in what every sequence past the window reads lifts all
                "long_rank_gap_q1_sq": (float(np.quantile(long_ms, 0.25))
                                        if long_ms else -1.0),
                "long_rank_gap_mean_sq": (float(np.mean(long_ms))
                                          if long_ms else -1.0),
                # of the long requests' served tokens, the share that is
                # not the reference's best: bfloat16 flips an expert under
                # one token in ten; keys that are not the sequence's own
                # at EVERY decoding step lift it threefold
                "long_off_best_pct": (100.0 * sum(
                    row["off_best"] for row in long_rows) / long_toks
                    if long_toks else -1.0),
                "tokens_compared": int(tok.size),
                "tokens_off_the_best": int((tok > 0).sum()),
                "ranks_compared": int(rank.size),
                "long_requests_compared": sum(
                    1 for r in sample if _context(r) > long_tokens),
                "per_request": rows}

    readings = read()
    reference_s = time.perf_counter() - t0
    checks = judge(readings, cell["limits"])
    controls = ctx.get("control") or ()
    if controls and sample:
        readings["controls"] = {}
        for precision in controls:
            got = read(precision)
            verdict = judge(got, cell["limits"])
            readings["controls"][precision] = dict(
                got, correct=all(ok for _n, _v, _l, ok in verdict),
                failed_by=[n for n, _v, _l, ok in verdict if not ok])
    return checks, readings, reference_s


def kind_trace(trace_dir, bench):
    """``serve_model.py``'s reduction of the trace, its device calls with
    the sums by layer kind beside the rest."""
    base = _sm.CALL_ARGS
    _sm.CALL_ARGS = tuple(base) + KIND_ARGS
    try:
        return _sm.moe_trace(trace_dir, bench)
    finally:
        _sm.CALL_ARGS = base


def run(ctx):
    """One run of one serving cell. Returns the facts the harness turns
    into the result line."""
    import jax

    cfg, cell = ctx["config"], ctx["cell"]
    spec = model_spec(cfg)
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
    short = [r for r in log + w["long"]
             if r.get("done", True) is not None and not r["failed"]
             and len(r["tokens"]) != r["spec"]["max_new"]]
    long_failed = [r for r in w["long"] if r["failed"]]
    checks = w["checks"] + [
        ("requests_failed", float(failed), 0.0, failed == 0),
        ("answers_of_wrong_length", float(len(short)), 0.0, not short),
        ("long_checks_failed", float(len(long_failed)), 0.0,
         not long_failed)]

    # the comparison with the plain reference, after the window has closed,
    # the peak has been read and the engine's pools are freed
    sample = _sd._check_sample(log, t_open, t_close, ctx["seed"],
                               int(cell["check_requests"]))
    sample += [r for r in w["long"] if not r["failed"]]
    compared, readings, reference_s = compare(ctx, params, sample)
    snap = w["snap"]
    finished = [r for r in log if r["done"] is not None
                and t_open <= r["done"] < t_close]
    facts = {
        "readings": readings, "setup_phases": phases,
        "notes": w["notes"] + [
            "long checks: %d served in %.1f s after the window" % (
                len(w["long"]), w["long_s"]),
            "finished in the window: %d requests, %d past the window of "
            "%d keys" % (len(finished), sum(
                1 for r in finished if _context(r) > spec.window),
                spec.window),
            # a run that stood still says where: inside a step (array
            # build to the ids on the host) or between two
            "longest in the window, ms: " + json.dumps({
                k.rsplit(".", 1)[-1]: snap[k].get("max")
                for k in ("serving.decode.step_ms",
                          "serving.decode.sched_ms")
                if isinstance(snap.get(k), dict)}),
            "kv after the run: " + json.dumps(
                {k: w["kv"][k] for k in ("kv", "kv_window",
                                         "kv_hbm_bytes")})],
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
        facts["moe_trace"] = kind_trace(ctx["trace_dir"], ctx["bench"])
        found = facts["moe_trace"]
        facts["notes"].append(
            "moe trace: " + json.dumps({k: v for k, v in found.items()
                                        if k != "calls"})
            + f" over {len(found['calls'])} device calls")
    return facts


def plant_window(delta):
    """The fault: the window layers' kernel calls see ``delta`` keys more
    (or fewer) than the model's window, while the cache gives pages back
    by the true one."""
    from paddle_tpu.fluid.ops.pallas_kernels import paged_attention as pa

    real = pa.paged_attention

    def altered(*args, window=None, **kw):
        if window is not None:
            window = int(window) + delta
        return real(*args, window=window, **kw)

    pa.paged_attention = altered
    return lambda: setattr(pa, "paged_attention", real)


def plant_page_early():
    """The fault: a window layer's page given back one page early. The
    oldest page a sequence past its window still sees is given to ANOTHER
    sequence while the first goes on reading it: here, in every table a
    step is built from, its column 0 names the page the next such row
    holds there."""
    from paddle_tpu.serving.decode import DecodeEngine

    real = DecodeEngine._build_arrays

    def altered(self, *args, **kw):
        out = real(self, *args, **kw)
        tables = out[3]
        past = np.flatnonzero(np.asarray(tables.starts) > 0)
        if len(past) > 1:
            window = np.array(tables.window)
            window[past, 0] = np.roll(window[past, 0], 1)
            out = out[:3] + (tables._replace(window=window),) + out[4:]
        return out

    DecodeEngine._build_arrays = altered
    return lambda: setattr(DecodeEngine, "_build_arrays", real)


def plant_wrong_token(every=7):
    """The fault: every seventh device call answers, in every slot, the
    token after the program's choice (``(id + 1) % vocab``), where the
    step's answer is read; the engine goes on from the token it answered,
    as the reference does. It is ``served_logit_gap``'s upper reading."""
    import jax.numpy as jnp

    from paddle_tpu.serving.decode import DecodeEngine

    real, calls = DecodeEngine._run_step_arrays, {"n": 0}

    def altered(self, *args, **kw):
        out, logits = real(self, *args, **kw)
        calls["n"] += 1
        if calls["n"] % every:
            return out, logits
        # a model whose pass reports nothing beside its ids hands them bare
        was = out["ids"] if isinstance(out, dict) else out
        ids = jnp.asarray((np.asarray(was) + 1) % logits.shape[-1],
                          was.dtype)
        return (dict(out, ids=ids) if isinstance(out, dict) else ids), logits

    DecodeEngine._run_step_arrays = altered
    return lambda: setattr(DecodeEngine, "_run_step_arrays", real)


FAULTS = {"window_short": lambda: plant_window(-1),
          "window_long": lambda: plant_window(+1),
          "page_early": plant_page_early,
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
            "memory_peak_bytes": facts["memory_peak_bytes"],
            "notes": facts["notes"]},
            default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
