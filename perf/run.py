"""One run of one benchmark cell, in a new process.

    python3 perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Refuses to start without a TPU (there is no fallback and no flag that lifts
it), finds the cell, its configuration, runner, reference and per-layer
readers by the names in BENCHMARK.json, and prints one JSON object as the
last line of its standard output. Everything that belongs to one cell, one
configuration or one metric is a file of its own under perf/.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perf.lib import device as devicelib  # noqa: E402
from perf.lib.loader import Benchmark, BenchmarkError  # noqa: E402


def result_line(bench, cell_name, facts, devices, trace):
    """The contract's last line from what a runner found."""
    checks = facts["checks"]
    correct = all(ok for _n, _v, _l, ok in checks)
    units = {m["name"]: m["unit"] for m in
             bench.doc["end_to_end"] + bench.doc["per_layer"]}
    metrics = {}
    if trace:
        for entry, desc in bench.per_layer(cell_name):
            value = bench.read_layer_metric(entry, desc, facts)
            if value is not None:
                metrics[entry["name"]] = {"value": value,
                                          "unit": units[entry["name"]]}
    else:
        for name in bench.end_to_end(cell_name):
            if facts["end_to_end"].get(name) is None:
                raise BenchmarkError(
                    f"{cell_name}: the window gave no {name}")
            metrics[name] = {"value": facts["end_to_end"][name],
                             "unit": units[name]}
    device = dict(devicelib.describe(devices),
                  memory_peak_bytes=facts["memory_peak_bytes"])
    line = {"correct": correct, "attempted": facts["attempted"],
            "failed": facts["failed"], "metrics": metrics, "device": device}
    if trace and facts.get("trace"):
        device["busy_s"] = facts["trace"]["busy_s"]
        device["window_s"] = facts["trace"]["window_s"]
        line["breakdown"] = {"device_ops": facts["trace"]["device_ops"],
                             "idle_gaps": facts["trace"]["idle_gaps"]}
    # the numbers compared, each beside its limit: last, as the contract has it
    line["checks"] = {n: {"value": v, "limit": l, "ok": ok}
                      for n, v, l, ok in checks}
    return line


def earlier_lines(facts, line):
    """What a run found besides the result: `# name: json` lines that go
    to standard error before the CHECK lines, the longest first. The last
    line of standard output holds only the keys the contract names."""
    window = {k: v for k, v in facts["end_to_end"].items()
              if k not in line["metrics"]}
    found = [("schedule", facts.get("schedule")),
             ("set-up, seconds by phase", facts.get("setup_phases")),
             ("counters", {k: v for k, v in (facts.get("counters")
                                              or {}).items() if v} or None),
             ("window", window), ("memory", facts.get("memory")),
             ("reference_s", facts.get("reference_s"))]
    out = ["# " + note for note in facts.get("notes") or []]
    out += [f"# {name}: {json.dumps(value)}" for name, value in found
            if value is not None]
    return out


def report(bench, cell_name, facts, devices, trace, out, err):
    """Print a run: the earlier lines and each number compared beside its
    limit on ``err``, then the result's line on ``out``."""
    line = result_line(bench, cell_name, facts, devices, trace)
    for text in earlier_lines(facts, line):
        print(text, file=err)
    for name, c in line["checks"].items():
        print(f"CHECK {name}: value {c['value']!r} limit {c['limit']!r} "
              f"-> {'ok' if c['ok'] else 'NOT CORRECT'}", file=err)
    err.flush()
    print(json.dumps(line), file=out, flush=True)


def open_cell(workload):
    """The cell, its configuration and the chips it runs on; JAX's compile
    cache placed inside the checkout. Raises without a TPU."""
    bench = Benchmark(ROOT)
    cell = bench.cell(workload)
    config = bench.config(cell["config"])

    import jax

    devices = devicelib.require_tpu(int(cell["chips"]))
    peaks = bench.peaks(devices[0].device_kind)
    import paddle_tpu  # noqa: F401  (places the compile cache in the checkout)

    # every program goes to the persistent cache, the small ones of the
    # reference too, so that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return bench, {"bench": bench, "cell": cell, "config": config,
                   "devices": devices, "peaks": peaks,
                   "reference": bench.reference(cell["config"])}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, ctx = open_cell(args.workload)
    config, devices = ctx["config"], ctx["devices"]
    # under TMPDIR, which the driver gives each side for its own
    trace_dir = tempfile.mkdtemp(prefix="perf_trace_") if args.trace else None
    ctx.update(seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
               trace_dir=trace_dir, t_start=T_START)
    try:
        facts = bench.runner(config["runner"]).run(ctx)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    report(bench, args.workload, facts, devices, bool(args.trace),
           sys.stdout, sys.stderr)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except (BenchmarkError, devicelib.NoAccelerator) as e:
        print(f"perf/run.py: {e}", file=sys.stderr)
        sys.exit(3)
