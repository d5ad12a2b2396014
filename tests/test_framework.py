"""Program/Block/Operator construction + serialization round-trip
(reference tests: test_program.py, test_protobuf_descs.py)."""
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.framework import Program, program_guard


def test_program_construction():
    main = Program()
    startup = Program()
    with program_guard(main, startup):
        x = layers.data(name="x", shape=[13], dtype="float32")
        y = layers.fc(input=x, size=4, act="relu")
        assert y.shape == (-1, 4)
        out = layers.fc(input=y, size=1)
        assert out.shape == (-1, 1)
    block = main.global_block()
    op_types = [op.type for op in block.ops]
    assert "mul" in op_types
    assert "elementwise_add" in op_types
    assert "relu" in op_types
    # params created in both programs
    params = block.all_parameters()
    assert len(params) == 4  # 2x weight + 2x bias
    startup_types = [op.type for op in startup.global_block().ops]
    assert "uniform_random" in startup_types  # xavier default
    assert "fill_constant" in startup_types  # bias init


def test_program_serialization_roundtrip():
    main = Program()
    startup = Program()
    with program_guard(main, startup):
        x = layers.data(name="x", shape=[8], dtype="float32")
        y = layers.fc(input=x, size=2)
    data = main.to_bytes()
    clone = Program.parse_from_bytes(data)
    assert clone.to_bytes() == data
    assert [op.type for op in clone.global_block().ops] == [
        op.type for op in main.global_block().ops
    ]


def test_clone_for_test_flips_is_test():
    main = Program()
    startup = Program()
    with program_guard(main, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        d = layers.dropout(x, dropout_prob=0.5)
    test_prog = main.clone(for_test=True)
    drop_ops = [op for op in test_prog.global_block().ops if op.type == "dropout"]
    assert drop_ops and drop_ops[0].attrs["is_test"] is True
    # original untouched
    drop_ops = [op for op in main.global_block().ops if op.type == "dropout"]
    assert drop_ops[0].attrs["is_test"] is False


def test_variable_shape_inference_conv():
    main = Program()
    startup = Program()
    with program_guard(main, startup):
        img = layers.data(name="img", shape=[3, 32, 32], dtype="float32")
        c = layers.conv2d(input=img, num_filters=8, filter_size=3, padding=1)
        assert c.shape == (-1, 8, 32, 32)
        p = layers.pool2d(input=c, pool_size=2, pool_stride=2)
        assert p.shape == (-1, 8, 16, 16)


def test_broken_emitter_surfaces_at_build_time():
    """A buggy emitter (arbitrary exception during abstract eval) must warn
    at program-build time, not silently defer to a runtime traceback
    (round-2 review weak #5)."""
    import warnings

    import pytest

    from paddle_tpu.fluid import registry

    @registry.register_op("broken_emitter_for_test")
    def _broken(ctx, ins, attrs):
        raise KeyError("deliberately broken emitter")

    from paddle_tpu.fluid.flags import set_flags

    # this test pins the default (non-strict) warn-once behavior; conftest
    # turns strict mode on for CI, so switch it off here and restore after
    set_flags({"strict_shape_inference": False})
    try:
        main = Program()
        startup = Program()
        with pytest.warns(RuntimeWarning, match="broken_emitter_for_test"):
            with program_guard(main, startup):
                x = layers.data(name="bx", shape=[4], dtype="float32")
                out = main.current_block().create_var(
                    name="b_out", shape=None, dtype="float32"
                )
                main.current_block().append_op(
                    "broken_emitter_for_test",
                    inputs={"X": [x.name]},
                    outputs={"Out": [out.name]},
                )
        # warned once per op type only
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            main2 = Program()
            with program_guard(main2, Program()):
                x2 = layers.data(name="bx2", shape=[4], dtype="float32")
                out2 = main2.current_block().create_var(
                    name="b_out2", shape=None, dtype="float32"
                )
                main2.current_block().append_op(
                    "broken_emitter_for_test",
                    inputs={"X": [x2.name]},
                    outputs={"Out": [out2.name]},
                )
    finally:
        set_flags({"strict_shape_inference": True})
        registry.OPS.pop("broken_emitter_for_test", None)


def test_strict_shape_inference_escalates_emitter_bugs():
    """FLAGS['strict_shape_inference'] (on in conftest for CI) turns the
    warn-once path for UNEXPECTED abstract-eval failures into a hard
    build-time error (reference shape_inference.h enforce semantics);
    with the flag off it stays a warning."""
    import warnings as _warnings

    import pytest

    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.flags import FLAGS, set_flags
    from paddle_tpu.fluid.framework import Program, program_guard
    from paddle_tpu.fluid.registry import OPS, register_op

    name = "deliberately_broken_emitter_op"

    @register_op(name)
    def _broken(ctx, ins, attrs):
        raise KeyError("emitter bug: missing slot")

    assert FLAGS["strict_shape_inference"]  # conftest turned it on
    try:
        prog, startup = Program(), Program()
        with program_guard(prog, startup):
            x = layers.data(name="sbx", shape=[4], dtype="float32")
            blk = prog.global_block()
            blk.create_var(name="sbout", dtype="float32", shape=[4])
            with pytest.raises(RuntimeError,
                               match="strict_shape_inference"):
                blk.append_op(name, inputs={"X": ["sbx"]},
                              outputs={"Out": ["sbout"]})
        # default mode: warn once, keep building
        set_flags({"strict_shape_inference": False})
        prog2, startup2 = Program(), Program()
        with program_guard(prog2, startup2):
            layers.data(name="sbx", shape=[4], dtype="float32")
            blk2 = prog2.global_block()
            blk2.create_var(name="sbout", dtype="float32", shape=[4])
            with _warnings.catch_warnings(record=True) as rec:
                _warnings.simplefilter("always")
                blk2.append_op(name, inputs={"X": ["sbx"]},
                               outputs={"Out": ["sbout"]})
            assert any("emitter" in str(w.message) for w in rec), [
                str(w.message) for w in rec]
    finally:
        set_flags({"strict_shape_inference": True})
        OPS.pop(name, None)
