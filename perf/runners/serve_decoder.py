"""Runner of served decoder configurations.

Drives ``DecodeEngine.submit`` / ``stream_tokens`` in this process (the call
``serving/server.py`` makes for every generate RPC) from closed-loop clients,
so the window covers admission, the scheduler, page reservation and the
prefix cache, chunked prefill and decode through the paged kernel, and
host-side sampling. The RPC front is not in the window.
"""
import contextlib
import math
import threading
import time

import numpy as np

from perf.lib import flops as flopslib
from perf.lib import stats, traffic
from perf.lib import trace as tracelib
from perf.lib.device import memory_peak_bytes


def make_weights(cfg, seed):
    """The whole parameter tree, in the engine's layout, made on the device
    in one jitted call from the seed, in the type it is served in."""
    import jax
    import jax.numpy as jnp

    d, ffn = int(cfg["d_model"]), int(cfg["ffn_dim"])
    vocab, layers = int(cfg["vocab_size"]), int(cfg["num_layers"])
    gain = float(cfg["init"]["branch_out_gain"])

    @jax.jit
    def make(key):
        keys = iter(jax.random.split(key, 1 + 6 * layers))

        def mat(fan_in, shape, scale=1.0):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * (scale / math.sqrt(fan_in)))

        def ln():
            return (jnp.ones((d,), jnp.float32), jnp.zeros((d,), jnp.float32))

        tree = {"tok_emb": mat(d, (vocab, d)), "lnf": ln()}
        for l in range(layers):
            tree[f"layer{l}"] = {
                "ln1": ln(), "wq": mat(d, (d, d)), "wk": mat(d, (d, d)),
                "wv": mat(d, (d, d)), "wo": mat(d, (d, d), gain),
                "ln2": ln(), "w1": mat(d, (d, ffn)),
                "w2": mat(ffn, (ffn, d), gain)}
        return tree

    return make(jax.random.key(int(seed) % (2 ** 63)))


class _Client(threading.Thread):
    """One closed-loop client: its next request goes out when the last one
    has ended and the mix's pause has passed. Records every token's arrival
    on the host clock."""

    def __init__(self, index, engine, sessions, log, state, annotate):
        super().__init__(daemon=True)
        self.index, self.engine, self.sessions = index, engine, sessions
        self.log, self.state, self.annotate = log, state, annotate
        self.error = None
        self.exhausted = False

    def run(self):
        try:
            for session in self.sessions:
                for spec in session:
                    if spec["think_s"]:
                        time.sleep(spec["think_s"])
                    if self.state["closed"].is_set():
                        return
                    self._one(spec)
            self.exhausted = True
        except BaseException as e:  # surfaced by the runner after join
            self.error = e

    def _one(self, spec):
        rec = {"submit": time.perf_counter(), "token_times": [],
               "failed": False, "spec": spec, "tokens": [], "result": None,
               "done": None, "client": self.index}
        with self.state["mu"]:
            self.log.append(rec)
        try:
            with self.annotate("perf.client.submit"):
                req = self.engine.submit(
                    spec["prompt"], max_new_tokens=spec["max_new"],
                    temperature=spec["temperature"], seed=spec["seed"],
                    topk_first=spec["topk_first"])
            offset = 0
            while True:
                out = self.engine.stream_tokens(req, offset, timeout=1.0)
                now = time.perf_counter()
                rec["token_times"] += [now] * len(out["tokens"])
                rec["tokens"] += out["tokens"]
                offset = out["next_offset"]
                if out["done"]:
                    rec["result"], rec["done"] = out["result"], now
                    return
                if self.state["closed"].is_set() and (
                        offset > 0 or self.state["give_up"].is_set()):
                    # the window is shut and this request has its first
                    # token (or the wait for it is over): withdraw it
                    self.engine.cancel(req, "window closed")
                    return
        except Exception as e:
            rec["failed"] = True
            rec["error"] = f"{type(e).__name__}: {e}"


def _no_span(_name):
    return contextlib.nullcontext()


# The three names inside the program that a traced run wraps from outside
# (PERF.md, Open questions: the interface the `tracing` issue should move
# into the program). A name the program no longer has is skipped, not an
# error: the run goes on and says which span it could not place.
ENGINE_DEVICE_CALL = "_run_step_arrays"
ENGINE_STEP = "_step"
SAMPLER = "sample_token"


def _instrument(engine, calls, notes):
    """Traced runs only: host spans on the profiler's clock around the
    scheduler's step, its device call and the host-side sampler, and a log
    of each device call's shapes for the kernel's roofline."""
    import jax

    from paddle_tpu.serving import decode as decode_mod

    ann = jax.profiler.TraceAnnotation
    placed = []

    def place(owner, name, wrap):
        real = getattr(owner, name, None)
        if real is None:
            notes.append(f"span not placed: {name}")
            return
        # an instance's wrapper shadows its class's method: deleting it
        # puts the method back and leaves no cycle that keeps the pools
        placed.append((owner, name, real, name in vars(owner)))
        setattr(owner, name, wrap(real))

    def device_call(real):
        def traced(*args, **kw):
            if len(args) == 5:
                tokens, _positions, q_lens, tables, lens = args
                calls.append((time.perf_counter(), np.array(q_lens),
                              np.array(lens), int(tokens.shape[1]),
                              int(tables.shape[1])))
            with ann("perf.engine.device_call"):
                return real(*args, **kw)
        return traced

    def span(label):
        def wrap(real):
            def traced(*a, **k):
                with ann(label):
                    return real(*a, **k)
            return traced
        return wrap

    place(engine, ENGINE_DEVICE_CALL, device_call)
    place(engine, ENGINE_STEP, span("perf.engine.scheduler_step"))
    place(decode_mod, SAMPLER, span("perf.engine.sample_token"))

    def undo():
        for owner, name, real, own in placed:
            if own:
                setattr(owner, name, real)
            else:
                delattr(owner, name)

    return ann, undo


def _processed_flops(cfg, log, t_open, t_close):
    """Operations needed by the tokens processed inside the window: prompt
    tokens not served from the cache (spread evenly from submit to the
    first token) and generated tokens (at their arrival)."""
    total = 0.0
    for r in log:
        times = r["token_times"]
        if not times:
            continue
        res = r["result"] or {}
        n_prompt = len(r["spec"]["prompt"])
        cached = int(res.get("cached_tokens", 0))
        span = max(times[0] - r["submit"], 1e-9)
        share = max(0.0, min(times[0], t_close) - max(r["submit"], t_open))
        total += share / span * flopslib.decoder_span_flops(
            cfg, cached, n_prompt, logits=1)
        for i, t in enumerate(times[1:], start=1):
            if t_open <= t < t_close:
                total += flopslib.decoder_token_flops(cfg, n_prompt + i, True)
    return total


def _check_sample(log, t_open, t_close, seed, want):
    """The finished greedy requests of the window that the reference is run
    over: the longest, one served from cached prefix pages if there is one,
    and others drawn from the seed."""
    done = [r for r in log if r["done"] is not None and not r["failed"]
            and r["spec"]["temperature"] <= 0.0
            and t_open <= r["done"] < t_close]
    if not done:
        return []
    rng = np.random.Generator(np.random.PCG64(np.random.SeedSequence(
        [int(seed), 0x636b])))
    size = lambda r: len(r["spec"]["prompt"]) + len(r["tokens"])
    picked = [max(done, key=size)]
    cached = [r for r in done if r["result"].get("cached_tokens", 0) > 0
              and r is not picked[0]]
    if cached:
        picked.append(cached[int(rng.integers(len(cached)))])
    rest = [r for r in done if all(r is not p for p in picked)]
    for i in rng.permutation(len(rest))[:max(0, want - len(picked))]:
        picked.append(rest[int(i)])
    return picked


def _compiles(metrics):
    return (metrics.counter("serving.decode.compiles").value()
            + metrics.counter("serving.kv.pagemove_compiles").value())


def _serve(ctx, params, phases):
    """Load the engine, ramp, hold the window open for ``seconds`` and wait
    for the first tokens still owed. The engine and its pools are local to
    this function: when it returns they are freed."""
    import jax

    from paddle_tpu.observability import metrics
    from paddle_tpu.serving.decode import DecodeEngine, DecoderSpec

    cfg, cell, seconds = ctx["config"], ctx["cell"], float(ctx["seconds"])
    eng_opts, mix = cell["engine"], cell["traffic"]
    sessions = traffic.closed_loop_sessions(
        mix, int(cfg["vocab_size"]), ctx["seed"])
    t_phase = time.perf_counter()
    spec = DecoderSpec(
        vocab=int(cfg["vocab_size"]), d_model=int(cfg["d_model"]),
        n_layers=int(cfg["num_layers"]), n_heads=int(cfg["attention_heads"]),
        n_kv_heads=int(cfg["attention_heads"]), seed=0)
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
        if engine.prefix_cache_enabled:
            # the engine's warm() leaves the copy-on-write page copy to
            # compile at its first use, once for each number of pairs a
            # step can batch: warm them here, garbage page onto itself
            with engine._step_mu:
                for n in range(1, max(eng_opts["slots"]) + 1):
                    engine.cache.copy_pages([(0, 0)] * n)
        phases["page_copy_warm"] = time.perf_counter() - t_phase
        t_phase = time.perf_counter()
        route = engine.stats()["attention_route"]
        checks.append(("attention_route_is_expected",
                       float(route == list(cell["expect_route"])), 1.0,
                       route == list(cell["expect_route"])))
        annotate = _no_span
        if ctx["trace"]:
            annotate, undo = _instrument(engine, calls, notes)
        clients = [_Client(i, engine, s, log, state, annotate)
                   for i, s in enumerate(sessions)]
        for c in clients:
            c.start()
        # the ramp: clients fall out of step with each other, the prefix
        # cache fills, and whatever compiles lazily compiles. Set-up. It
        # ends after a fixed amount of WORK, not of time, so that every
        # run's window opens at the same point of the same sequence
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
        compiles0 = _compiles(metrics)
        t_open = time.perf_counter()
        phases["ramp"] = t_open - t_phase
        traced = None
        if ctx["trace"]:
            time.sleep(min(1.0, seconds / 4))
            tracelib.start(ctx["trace_dir"])
            t_trace0 = time.perf_counter()
            time.sleep(min(float(cell["trace_seconds"]), seconds / 2))
            traced = (t_trace0, time.perf_counter())
            jax.profiler.stop_trace()
        time.sleep(max(0.0, t_open + seconds - time.perf_counter()))
        t_close = time.perf_counter()
        state["closed"].set()
        snap = metrics.snapshot("serving.")
        compiled = _compiles(metrics) - compiles0
        # requests that started inside the window and have no first token
        # yet are waited for: late is late, and the wait counts
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
            "peak": peak, "notes": notes,
            "calls": [c for c in calls if traced
                      and traced[0] <= c[0] < traced[1]]}


def _mean_sq(x):
    return float(np.mean(np.square(x))) if x.size else None


def _compare(ctx, params, sample):
    """The sample of finished greedy requests against the plain reference:
    the checks, the readings they came from and the seconds it took."""
    cfg, limits, ref = ctx["config"], ctx["cell"]["limits"], ctx["reference"]
    t0 = time.perf_counter()

    def read(judged_by=None, against="stated"):
        """(token gaps, rank gaps) of the sample through the reference, or
        of the control where ``judged_by`` is a lower precision."""
        tok, rank = [np.zeros((0,))], [np.zeros((0,))]
        for r in sample:
            t, k = ref.served_gaps(
                params, cfg, r["spec"]["prompt"], r["tokens"], judged_by,
                r["result"].get("first_topk"), against)
            tok.append(np.asarray(t))
            if k is not None:
                rank.append(np.asarray(k))
        return np.concatenate(tok), np.concatenate(rank)

    gaps, rank_gaps = read()
    n_tokens = int(gaps.size)
    n_cached = sum(int(r["result"].get("cached_tokens", 0) > 0)
                   for r in sample)
    widest = float(gaps.max()) if n_tokens else -1.0
    rank_ms = _mean_sq(rank_gaps) if rank_gaps.size else -1.0
    reference_s = time.perf_counter() - t0
    checks = [
        ("served_logit_gap", widest, float(limits["served_logit_gap"]),
         0.0 <= widest <= float(limits["served_logit_gap"])),
        ("first_rank_gap_mean_sq", rank_ms,
         float(limits["first_rank_gap_mean_sq"]),
         0.0 <= rank_ms <= float(limits["first_rank_gap_mean_sq"])),
        ("tokens_compared", float(n_tokens),
         float(limits["min_tokens_compared"]),
         n_tokens >= int(limits["min_tokens_compared"])),
    ]
    if limits.get("min_cached_requests_compared"):
        need = int(limits["min_cached_requests_compared"])
        checks.append(("cached_requests_compared", float(n_cached),
                       float(need), n_cached >= need))
    readings = {"served_logit_gap": widest, "tokens_compared": n_tokens,
                "tokens_off_the_best": int((gaps > 0).sum()),
                "first_rank_gap_mean_sq": rank_ms,
                "ranks_compared": int(rank_gaps.size)}
    if ctx.get("control") and sample:
        # perf/limits.py only: the same prompts and tokens through the
        # reference one precision down, which has to read as not correct,
        # and the program's own reading against float32 at `highest`
        cgaps, crank = read(ctx["control"])
        hgaps, hrank = read(None, "highest")
        readings.update(
            against_highest_gap=float(hgaps.max()),
            against_highest_first_rank_gap_mean_sq=_mean_sq(hrank),
            control_gap=float(cgaps.max()),
            control_tokens_off_the_best=int((cgaps > 0).sum()),
            control_first_rank_gap_mean_sq=_mean_sq(crank))
    return checks, readings, reference_s


def run(ctx):
    """One run of one serving cell. Returns the facts the harness turns
    into the result line."""
    import jax

    cfg, cell = ctx["config"], ctx["cell"]
    phases = {"imports": time.perf_counter() - ctx["t_start"]}
    t_phase = time.perf_counter()
    params = jax.block_until_ready(make_weights(cfg, ctx["seed"]))
    phases["weights"] = time.perf_counter() - t_phase
    w = _serve(ctx, params, phases)
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
    sample = _check_sample(log, t_open, t_close, ctx["seed"],
                           int(cell["check_requests"]))
    compared, readings, reference_s = _compare(ctx, params, sample)
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
        "processed_flops": _processed_flops(cfg, log, t_open, t_close),
        "requests_finished": sum(
            1 for r in log if r["done"] is not None
            and t_open <= r["done"] < t_close),
    }
    if ctx["trace"]:
        facts["trace"] = tracelib.reduce_events(
            tracelib.read_xplane(ctx["trace_dir"]))
        facts["traced_calls"] = w["calls"]
    return facts
