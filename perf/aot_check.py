"""Ahead-of-time compiles for a described v5e, without a chip.

    JAX_PLATFORMS=cpu python3 perf/aot_check.py [xglm|resnet] [num_pages ...]

Compiles the widest step of each configuration with the chip's own
compilers (XLA-TPU and Mosaic) and prints its memory analysis: what fixes
`num_pages` and the batch in the cells' files, and what catches a step that
does not fit at no chip time. A compile that passes is not a chip run.
"""
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from perf.lib.loader import Benchmark  # noqa: E402

HBM = 16 * 1024 ** 3


def _report(what, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(json.dumps({
        "what": what, "arguments": m.argument_size_in_bytes,
        "outputs": m.output_size_in_bytes, "aliased": m.alias_size_in_bytes,
        "temporaries": m.temp_size_in_bytes, "total": total,
        "share_of_16GiB": round(total / HBM, 4)}), flush=True)


def xglm(bench, one_chip, pages_list):
    """decoder_step_chunked at slots 16, C 16, the widest table bucket, with
    the pools donated as the engine donates them."""
    from paddle_tpu.fluid import flags
    from paddle_tpu.serving.decode import DecoderSpec, decoder_step_chunked

    # the process is on the CPU, where the program would interpret the
    # kernel: steer it to the compiled kernel here, in the scratch script
    flags.set_flags({"use_pallas_kernels": True})
    flags.pallas_interpret = lambda: False
    cfg = bench.config("xglm-1.7b")
    cell = bench.cell("xglm17b_chat")
    spec = DecoderSpec(vocab=cfg["vocab_size"], d_model=cfg["d_model"],
                       n_layers=cfg["num_layers"],
                       n_heads=cfg["attention_heads"])
    runner = bench.runner("serve_decoder")
    shapes = jax.eval_shape(lambda: runner.make_weights(cfg, 0))
    on = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(on, shapes)
    slots = max(cell["engine"]["slots"])
    ps = cell["engine"]["page_size"]
    width = -(-cell["engine"]["max_seq_len"] // ps)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)

    def step(p, tokens, positions, q_lens, k, v, tables, lens):
        return decoder_step_chunked(p, spec, tokens, positions, q_lens, k, v,
                                    tables, lens)

    for pages in pages_list:
        pool = jax.ShapeDtypeStruct(
            (spec.n_layers, pages, ps, spec.n_kv_heads, spec.head_dim),
            jnp.float32, sharding=one_chip)
        for chunk in (16, 1):
            compiled = jax.jit(step, donate_argnums=(4, 5)).lower(
                params, i32(slots, chunk), i32(slots, chunk), i32(slots),
                pool, pool, i32(slots, width), i32(slots)).compile()
            _report(f"xglm-1.7b step slots={slots} C={chunk} W={width} "
                    f"num_pages={pages}", compiled)


def resnet(bench, one_chip, batches):
    """The ResNet-50 training step as fluid.Executor compiles it."""
    runner = bench.runner("train_fluid")
    cfg = bench.config("resnet50")
    ref = bench.reference("resnet50")
    for batch in batches:
        cell = bench.cell("resnet50_train")
        cell["traffic"]["batch"] = batch
        # the weights and the batch only lend their shapes
        obj = runner.build(cfg, cell, 0, ref)
        with obj["fluid"].scope_guard(obj["scope"]):
            jfn, args = obj["exe"].lowered(
                obj["main"], feed=obj["feed"], fetch_list=[obj["avg_cost"]],
                scope=obj["scope"])
        on = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=one_chip)
        compiled = jfn.lower(*jax.tree.map(on, args)).compile()
        _report(f"resnet50 train step batch={batch}", compiled)
        cost = compiled.cost_analysis() or {}
        print(json.dumps({"xla_flops_per_step": cost.get("flops")}))


def main(argv):
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    bench = Benchmark(ROOT)
    which = argv[0] if argv else "all"
    nums = [int(a) for a in argv[1:]]
    if which in ("xglm", "all"):
        xglm(bench, one_chip, nums or [1024])
    if which in ("resnet", "all"):
        resnet(bench, one_chip, nums or [128])


if __name__ == "__main__":
    main(sys.argv[1:])
