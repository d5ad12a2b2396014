"""Runner of training configurations that go through ``fluid.Executor``.

Set-up builds ONE object, the compiled step with its state in a scope,
drives it from the seed through its first steps with the window's own call
and feed, and hands that same object to the window: back-to-back
``Executor.run(..., return_numpy=False)`` on a device-resident batch, closed
by a fetch of a value that depends on the last step. The plain reference
then follows the first steps from the same weights and batch.
"""
import contextlib
import importlib
import sys
import time

import numpy as np

from perf.lib import flops as flopslib
from perf.lib import stats, traffic
from perf.lib import trace as tracelib
from perf.lib.device import fetch_scalar, memory_peak_bytes


def _leaf_norms(leaves):
    import jax.numpy as jnp

    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in leaves])


def _delta_norms(now, before):
    return _leaf_norms([a - b for a, b in zip(now, before)])


def worst_leaf_gap(mine, ref, keep=None):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger; which leaf; and the median leaf's gap.
    ``keep`` masks leaves out."""
    mine, ref = np.asarray(mine, np.float64), np.asarray(ref, np.float64)
    gap = np.abs(mine - ref) / np.maximum(ref, np.median(ref))
    kept = gap if keep is None else gap[keep]
    if keep is not None:
        gap = np.where(keep, gap, 0.0)
    return float(gap.max()), int(gap.argmax()), float(np.median(kept))


def compare(prog, ref):
    """The numbers of a training cell from the program's readings and the
    reference's: each a dict with ``losses`` and the per-leaf norms
    ``grad_norms`` (first gradient) and ``change_norms`` (parameters' change
    over the steps followed). The worst leaf's gaps swing from seed to seed
    with whichever single leaf is noisiest; the median leaf's change is the
    steady number that tells a lower precision apart (PERF.md)."""
    lp, lr = np.asarray(prog["losses"], np.float64), np.asarray(
        ref["losses"], np.float64)
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    grad_gap, grad_leaf, grad_median = worst_leaf_gap(
        prog["grad_norms"], ref["grad_norms"])
    # a leaf whose gradient is nought to rounding in the reference moves by
    # round-off alone: out of the change by a rule on the reference's
    # gradient, not by name
    g = np.asarray(ref["grad_norms"], np.float64)
    keep = g >= 1e-3 * np.median(g)
    change_gap, change_leaf, change_median = worst_leaf_gap(
        prog["change_norms"], ref["change_norms"], keep)
    return {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
            "param_change_gap": change_gap, "grad_leaf": grad_leaf,
            "change_leaf": change_leaf,
            "first_loss_gap": float(abs(lp[0] - lr[0]) / abs(lr[0])),
            "median_leaf_grad_gap": grad_median,
            "median_leaf_change_gap": change_median,
            "leaves_left_out": int((~keep).sum())}


def reference_readings(ref, cfg, params0, img, label, steps, **how):
    """The reference's (or, with ``operand_bits``, the control's) readings
    over the first ``steps`` steps."""
    losses, grad1, after = ref.train_steps(
        [p for p in params0], img, label, cfg, steps, **how)
    return {"losses": [float(x) for x in losses],
            "grad_norms": np.asarray(_leaf_norms(grad1)),
            "change_norms": np.asarray(_delta_norms(after, params0))}


def build(cfg, cell, seed, ref):
    """The compiled step with its state: programs, scope, executor, the
    seeded weights (set over the program's own initialisation) and batch."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.flags import set_flags
    from paddle_tpu.fluid.framework import Program, program_guard

    set_flags(dict(cfg["flags"]))
    model = cfg["model"]
    builder = getattr(importlib.import_module(model["module"]),
                      model["builder"])
    main, startup, scope = Program(), Program(), fluid.Scope()
    main.random_seed = startup.random_seed = int(seed) % (2 ** 31)
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            img = layers.data(name="img", shape=list(cfg["image"]),
                              dtype="float32")
            label = layers.data(name="label", shape=[1], dtype="int64")
            avg_cost = builder(img, label, **model["kwargs"])[0]
            opt = getattr(fluid.optimizer, cfg["optimizer"])(
                learning_rate=float(cfg["learning_rate"]),
                momentum=float(cfg["momentum"]))
            opt.minimize(avg_cost)
        exe = fluid.Executor()
        exe.run(startup)
    names = [p.name for p in main.global_block().all_parameters()]
    shapes = [tuple(p.shape) for p in main.global_block().all_parameters()]
    if shapes != [tuple(s) for s in ref.param_shapes(cfg)]:
        raise ValueError(
            "the program's parameters are not the reference's, leaf by "
            "leaf in the order the layers run")
    params0 = jax.jit(lambda key: ref.init_params(cfg, key))(
        jax.random.key(int(seed) % (2 ** 63)))
    for name, value in zip(names, params0):
        # a copy: the executor donates its state into every step
        scope.set_var(name, jnp.array(value, copy=True))
    velocity = [opt._accumulators["velocity"][n].name for n in names]
    images, labels = traffic.resident_batch(
        cell["traffic"], cfg["image"], int(cfg["class_dim"]), seed)
    return {"main": main, "scope": scope, "exe": exe, "fluid": fluid,
            "avg_cost": avg_cost, "names": names, "velocity": velocity,
            "params0": params0, "feed": {"img": images, "label": labels}}


def first_steps(obj, steps, step_fn):
    """Drive the object through its first steps by the window's own call
    and feed; read each loss, the first gradient as the optimizer got it
    (Momentum's velocity after one step from zero) and the parameters'
    change after the last."""
    scope, losses, grad_norms = obj["scope"], [], None
    for i in range(steps):
        losses.append(step_fn(obj)[0])
        if i == 0:
            grad_norms = _leaf_norms(
                [scope.find_var(n) for n in obj["velocity"]])
    change = _delta_norms([scope.find_var(n) for n in obj["names"]],
                          obj["params0"])
    return {"losses": [float(np.ravel(np.asarray(x))[0]) for x in losses],
            "grad_norms": np.asarray(grad_norms),
            "change_norms": np.asarray(change)}


def step(obj):
    """The window's call: one training step, nothing fetched."""
    with obj["fluid"].scope_guard(obj["scope"]):
        return obj["exe"].run(obj["main"], feed=obj["feed"],
                              fetch_list=[obj["avg_cost"]],
                              return_numpy=False)


def run(ctx):
    import jax

    cfg, cell = ctx["config"], ctx["cell"]
    seed, seconds = ctx["seed"], float(ctx["seconds"])
    ref = ctx["reference"]
    n_ref = int(cell["reference_steps"])
    phases = {"imports": time.perf_counter() - ctx["t_start"]}
    t_phase = time.perf_counter()
    obj = ctx.get("build", build)(cfg, cell, seed, ref)
    phases["build_program_and_state"] = time.perf_counter() - t_phase
    t_phase = time.perf_counter()
    # a test plants a fault under the window's call through ctx["step"]
    step_fn = ctx.get("step", step)
    prog = first_steps(obj, n_ref, step_fn)
    phases["first_steps_with_compile"] = time.perf_counter() - t_phase
    lag = int(cell["traffic"]["max_in_flight"])
    annotate = (jax.profiler.TraceAnnotation if ctx["trace"]
                else (lambda _n: contextlib.nullcontext()))
    fetch_scalar(obj["scope"].find_var(obj["names"][0]))

    pending, dispatch_ms, steps, traced = [], [], 0, None
    clean = None
    t_open = time.perf_counter()
    setup_s = t_open - ctx["t_start"]
    t_trace_on = t_open + min(1.0, seconds / 4)
    t_trace_off = None
    while True:
        now = time.perf_counter()
        if now >= t_open + seconds:
            break
        if ctx["trace"] and traced is None and now >= t_trace_on:
            tracelib.start(ctx["trace_dir"])
            traced = time.perf_counter()
            t_trace_off = traced + min(float(cell["trace_seconds"]),
                                       seconds / 2)
        if t_trace_off is not None and now >= t_trace_off:
            # stopping the profiler holds this thread for seconds and the
            # device runs dry: the steps after it, counted from an empty
            # queue, are the stretch the traced run's step time is read from
            jax.profiler.stop_trace()
            t_trace_off = None
            while pending:
                pending.pop(0).block_until_ready()
            clean = (time.perf_counter(), steps)
        t0 = time.perf_counter()
        with annotate("perf.executor.run"):
            out = step_fn(obj)
        dispatch_ms.append((time.perf_counter() - t0) * 1e3)
        steps += 1
        pending.append(out[0])
        if len(pending) > lag:
            # bound the queue without starving the device: wait for the
            # step `lag` back, never for the newest
            with annotate("perf.wait_for_older_step"):
                pending.pop(0).block_until_ready()
    if t_trace_off is not None:
        jax.profiler.stop_trace()
    # the fetch that ends the window: a parameter after the last update
    fetch_scalar(obj["scope"].find_var(obj["names"][0]))
    t_close = time.perf_counter()
    e2e = stats.training_window(t_open, t_close, steps)
    e2e["setup_s"] = setup_s
    step_ms = e2e["train_step_ms"]
    if clean is not None and steps > clean[1]:
        step_ms = (t_close - clean[0]) / (steps - clean[1]) * 1e3

    # the allocator's peak counts live arrays; the compiled step's
    # temporaries (the activations kept for the backward pass) come from
    # the executable's own memory analysis, unless the peak shows them
    peak = memory = xla_flops = None
    live = memory_peak_bytes(ctx["devices"]) if ctx["devices"] else None
    if live is not None:
        with obj["fluid"].scope_guard(obj["scope"]):
            jfn, args = obj["exe"].lowered(
                obj["main"], feed=obj["feed"],
                fetch_list=[obj["avg_cost"]], scope=obj["scope"])
        compiled = jfn.lower(*args).compile()
        m = compiled.memory_analysis()
        inside = live >= m.argument_size_in_bytes + m.temp_size_in_bytes
        peak = live if inside else live + int(m.temp_size_in_bytes)
        memory = {"allocator_peak_bytes": live,
                  "step_temporaries_bytes": int(m.temp_size_in_bytes),
                  "step_arguments_bytes": int(m.argument_size_in_bytes),
                  "temporaries_inside_allocator_peak": bool(inside),
                  "allocator_stats": {
                      k: int(v) for k, v in
                      (ctx["devices"][0].memory_stats() or {}).items()
                      if isinstance(v, (int, float))}}
        cost = compiled.cost_analysis()
        xla_flops = float((cost or {}).get("flops", 0.0)) or None

    # free the program's state, then follow its first steps in the reference
    params0, feed = obj["params0"], obj["feed"]
    obj["exe"].close()
    for name in list(obj["scope"].var_names()):
        obj["scope"].drop_var(name)
    obj.clear()
    t0 = time.perf_counter()
    reading = reference_readings(ref, cfg, params0, feed["img"],
                                 feed["label"], n_ref)
    got = compare(prog, reading)
    reference_s = time.perf_counter() - t0
    limits = cell["limits"]
    finite = bool(np.isfinite(prog["losses"]).all())
    checks = [(k, got[k], float(limit), got[k] <= float(limit))
              for k, limit in limits.items()]
    checks.append(("losses_finite", float(finite), 1.0, finite))
    batch = int(cell["traffic"]["batch"])
    step_flops = batch * flopslib.resnet_train_flops(cfg)
    facts = {
        "end_to_end": e2e, "attempted": steps, "failed": 0,
        "checks": checks, "memory_peak_bytes": peak,
        "reference_s": reference_s, "histograms": {}, "counters": {},
        "window_s": t_close - t_open, "config": cfg, "cell": cell,
        "peaks": ctx["peaks"], "trace": None, "dispatch_ms": dispatch_ms,
        "step_flops": step_flops, "xla_step_flops": xla_flops,
        "untraced_step_ms": step_ms, "memory": memory,
        "setup_phases": phases,
        "readings": {"program": prog["losses"],
                     "reference": reading["losses"], **got},
    }
    if ctx.get("control"):
        # perf/limits.py only: the control and the faults that can be
        # planted in the reference put in the program's place, each read
        # against the reference as the program is
        half = int(cell["traffic"]["batch"]) // 2
        facts["readings"]["control"] = compare(reference_readings(
            ref, cfg, params0, feed["img"], feed["label"], n_ref,
            operand_bits=int(ctx["control"])), reading)
        facts["readings"]["fault_half_batch"] = compare(reference_readings(
            ref, cfg, params0, feed["img"][:half], feed["label"][:half],
            n_ref), reading)
    if xla_flops:
        print(f"# step operations: layer shapes {step_flops:.4e}, XLA "
              f"cost_analysis {xla_flops:.4e}", flush=True, file=sys.stderr)
    if traced is not None:
        facts["trace"] = tracelib.reduce_events(
            tracelib.read_xplane(ctx["trace_dir"]))
    return facts
