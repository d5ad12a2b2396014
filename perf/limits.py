"""The readings a cell's limits are set from, on the chip.

    python3 perf/limits.py --workload <name> --seeds 1,2,3 --seconds <s>

Runs the cell once per seed in ONE process, at the cell's own size and load
with a short window, and prints for each seed what the program read against
the plain reference and what the control read (the reference one precision
down, put in the program's place), with, for a training cell, the fault that
can be planted in the reference (half of the batch left out). ``--fault
altered_token`` plants a serving cell's fault in the program instead. The
benchmark's own runs never run the control; this tool and the tests do.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perf.run import open_cell  # noqa: E402

# the nearest precision below the one each runner's configurations state
CONTROL = {"serve_decoder": "bfloat16", "train_fluid": 3}


def plant_altered_token(every=7):
    """A serving cell's fault: every seventh scheduler step hands the
    sampler its logits shifted by one token id, so the token comes out
    altered where it is produced."""
    import jax.numpy as jnp

    from paddle_tpu.serving.decode import DecodeEngine

    real, calls = DecodeEngine._run_step_arrays, {"n": 0}

    def altered(self, *args):
        logits = real(self, *args)
        calls["n"] += 1
        return (jnp.roll(logits, 1, axis=-1) if calls["n"] % every == 0
                else logits)

    DecodeEngine._run_step_arrays = altered


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--fault", choices=("altered_token",), default=None)
    args = ap.parse_args(argv)
    bench, cell_ctx = open_cell(args.workload)
    if args.fault == "altered_token":
        plant_altered_token()
    runner = bench.runner(cell_ctx["config"]["runner"])
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = dict(cell_ctx, seed=seed, seconds=args.seconds, trace=False,
                   trace_dir=None, t_start=time.perf_counter(),
                   control=CONTROL[cell_ctx["config"]["runner"]])
        facts = runner.run(ctx)
        print("READINGS " + json.dumps({
            "workload": args.workload, "seed": seed,
            "readings": facts["readings"],
            "checks": {n: [v, l, ok] for n, v, l, ok in facts["checks"]},
            "end_to_end": facts["end_to_end"],
            "reference_s": facts["reference_s"],
            "memory_peak_bytes": facts["memory_peak_bytes"]},
            default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
