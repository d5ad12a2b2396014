"""Executor lowering + scope state (reference test_executor_and_mul.py)."""
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.framework import Program, program_guard


def _fresh():
    return Program(), Program(), fluid.Scope()


def test_mul_executor():
    main, startup, scope = _fresh()
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            x = layers.data(name="x", shape=[3], dtype="float32")
            y = layers.data(name="y", shape=[3, 4], dtype="float32",
                            append_batch_size=False)
            out = layers.mul(x, y)
        exe = fluid.Executor()
        a = np.random.rand(5, 3).astype(np.float32)
        b = np.random.rand(3, 4).astype(np.float32)
        (res,) = exe.run(main, feed={"x": a, "y": b}, fetch_list=[out])
        np.testing.assert_allclose(res, a @ b, rtol=1e-5)


def test_persistable_state_updates():
    main, startup, scope = _fresh()
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            x = layers.data(name="x", shape=[2], dtype="float32")
            w = layers.create_parameter(shape=[2], dtype="float32", name="w")
            out = layers.elementwise_add(x, w)
            # in-place update of w: w = w + x summed over batch? keep simple:
        exe = fluid.Executor()
        exe.run(startup)
        assert scope.has_var("w")
        a = np.ones((1, 2), dtype=np.float32)
        (res,) = exe.run(main, feed={"x": a}, fetch_list=[out])
        assert res.shape == (1, 2)


def test_feed_fetch_roundtrip():
    main, startup, scope = _fresh()
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            x = layers.data(name="x", shape=[4], dtype="float32")
            y = layers.scale(x, scale=3.0, bias=1.0)
        exe = fluid.Executor()
        a = np.arange(8, dtype=np.float32).reshape(2, 4)
        (res,) = exe.run(main, feed={"x": a}, fetch_list=[y])
        np.testing.assert_allclose(res, a * 3 + 1, rtol=1e-6)


def test_uninitialized_var_raises():
    main, startup, scope = _fresh()
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            x = layers.data(name="x", shape=[4], dtype="float32")
            w = layers.create_parameter(shape=[4], dtype="float32", name="w2")
            out = layers.elementwise_add(x, w)
        exe = fluid.Executor()
        a = np.ones((1, 4), dtype=np.float32)
        try:
            exe.run(main, feed={"x": a}, fetch_list=[out])
            raised = False
        except RuntimeError as e:
            raised = "not initialized" in str(e)
        assert raised


def test_executor_program_cache():
    main, startup, scope = _fresh()
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            x = layers.data(name="x", shape=[4], dtype="float32")
            y = layers.scale(x, scale=2.0)
        exe = fluid.Executor()
        a = np.ones((2, 4), dtype=np.float32)
        exe.run(main, feed={"x": a}, fetch_list=[y])
        n_cached = len(exe._cache[main])
        exe.run(main, feed={"x": a}, fetch_list=[y])
        assert len(exe._cache[main]) == n_cached  # hit, no recompile
        exe.run(main, feed={"x": np.ones((3, 4), dtype=np.float32)},
                fetch_list=[y])
        assert len(exe._cache[main]) == n_cached + 1  # new shape, new entry


def test_trace_flags_in_jit_cache_key():
    """Toggling a trace-affecting flag (amp) after a program has run must
    recompile, not silently reuse the stale executable."""
    import jax.numpy as jnp

    from paddle_tpu.fluid.flags import set_flags

    main, startup, scope = Program(), Program(), fluid.Scope()
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            x = layers.data(name="x", shape=[8], dtype="float32")
            w = layers.create_parameter(shape=[8, 8], dtype="float32",
                                        name="cache_w")
            out = layers.mul(x, w)
        exe = fluid.Executor()
        exe.run(startup)
        xv = np.random.RandomState(0).rand(4, 8).astype(np.float32)
        (o32,) = exe.run(main, feed={"x": xv}, fetch_list=[out],
                         return_numpy=False)
        set_flags({"amp": True})
        try:
            (oamp,) = exe.run(main, feed={"x": xv}, fetch_list=[out],
                              return_numpy=False)
        finally:
            set_flags({"amp": False})
        # amp result is the bf16-rounded product — different bits than f32
        # (if the cache ignored the flag these would be identical arrays)
        a, b = np.asarray(o32), np.asarray(oamp)
        ref32 = xv @ np.asarray(scope.find_var("cache_w"))
        refbf = (xv.astype(jnp.bfloat16) @ np.asarray(
            scope.find_var("cache_w")).astype(jnp.bfloat16)).astype(
                np.float32)
        np.testing.assert_allclose(a, ref32, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(b, refbf, rtol=1e-5, atol=1e-6)
        assert not np.array_equal(a, b)


def test_lowered_shares_cache_with_run():
    """Executor.lowered() (AOT inspection handle, used by benchmarks/) maps
    to the same jitted entry run() uses, and its compiled object reports a
    cost analysis."""
    import jax

    main, startup, scope = Program(), Program(), fluid.Scope()
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            x = layers.data(name="x", shape=[4], dtype="float32")
            y = layers.fc(input=x, size=3)
            loss = layers.mean(y)
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor()
        exe.run(startup)
        feed = {"x": np.ones((2, 4), np.float32)}
        jfn, args = exe.lowered(main, feed, [loss], scope)
        comp = jfn.lower(*args).compile()
        assert comp.cost_analysis().get("flops", 0.0) > 0
        exe.run(main, feed=feed, fetch_list=[loss])
        jfn2, _ = exe.lowered(main, feed, [loss], scope)
        assert jfn is jfn2


def test_weighted_average():
    """reference fluid/average.py WeightedAverage."""
    from paddle_tpu.fluid.average import WeightedAverage

    wa = WeightedAverage()
    with pytest.raises(ValueError):
        wa.eval()
    wa.add(2.0, weight=1)
    wa.add(3.0, weight=3)
    assert wa.eval() == pytest.approx((2.0 + 3.0 * 3) / 4)
    wa.reset()
    # elementwise numerator for array values (reference average.py keeps
    # value*weight as an array; eval() is the weighted elementwise mean)
    wa.add(np.array([4.0, 6.0]), weight=1)
    wa.add(np.array([8.0, 2.0]), weight=3)
    np.testing.assert_allclose(wa.eval(), [(4 + 24) / 4, (6 + 6) / 4])
    with pytest.raises(ValueError):
        wa.add(1.0, weight=np.array([1.0, 2.0]))  # weight must be a number
    wa.reset()
    wa.add(7.0, weight=1)
    assert wa.eval() == 7.0


def test_default_scope_funcs():
    """reference fluid/default_scope_funcs.py: thread-local scope stack."""
    from paddle_tpu.fluid import default_scope_funcs as dsf
    from paddle_tpu.fluid.executor import _scope_tls

    root = dsf.get_cur_scope()
    depth = len(getattr(_scope_tls, "stack", []) or [])
    try:
        dsf.var("a")
        assert dsf.find_var("a") is None  # created, unset
        root.set_var("a", 5)
        assert dsf.find_var("a") == 5

        child = dsf.enter_local_scope()
        assert dsf.get_cur_scope() is child
        assert dsf.find_var("a") == 5       # parent chain visible
        # local-only create: a child var SHADOWS the parent's
        child.set_var("b", 9)
        dsf.var("a")
        assert dsf.find_var("a") is None
        dsf.leave_local_scope()
        assert dsf.get_cur_scope() is root
        assert dsf.find_var("b") is None    # local scope gone
        assert dsf.find_var("a") == 5       # shadow gone with it

        out = dsf.scoped_function(lambda: dsf.find_var("a"))
        assert out == 5
        with pytest.raises(RuntimeError):
            dsf.leave_local_scope()
        # a scope_guard frame is never ours to pop
        with fluid.scope_guard(fluid.Scope()):
            with pytest.raises(RuntimeError):
                dsf.leave_local_scope()
    finally:
        root.drop_var("a")
        stack = getattr(_scope_tls, "stack", []) or []
        del stack[depth:]  # unwind anything a failed assert left behind


def test_scope_guard_unwinds_orphaned_local_scopes():
    """A scope_guard exiting with an unmatched enter_local_scope must pop
    its OWN frame (by identity) and discard the orphan — not leak its
    scope as the thread's current scope; later enter/leave pairs work."""
    from paddle_tpu.fluid import default_scope_funcs as dsf

    root = dsf.get_cur_scope()
    s = fluid.Scope()
    with fluid.scope_guard(s):
        dsf.enter_local_scope()  # deliberately unmatched
    assert dsf.get_cur_scope() is root
    # no cascade: a fresh matched pair still works
    dsf.enter_local_scope()
    dsf.leave_local_scope()
    assert dsf.get_cur_scope() is root


def test_in_graph_save_load_ops(tmp_path):
    """save/load as OPS in a program (reference save_op.cc, load_combine_op
    .cc): a save program can be emitted, serialized, and run anywhere —
    including by a second process that never saw the python io.py call."""
    import jax.numpy as jnp

    from paddle_tpu.fluid.framework import Program as P
    from paddle_tpu.fluid.io import _build_load_program, _build_save_program

    scope = fluid.Scope()
    scope.set_var("sv.a", jnp.arange(6.0).reshape(2, 3))
    scope.set_var("sv.b", jnp.ones((4,)) * 7)
    save_prog = _build_save_program(["sv.a", "sv.b"], str(tmp_path))
    types = [op.type for op in save_prog.global_block().ops]
    assert types == ["save", "save"]
    # desc round-trip: the save program itself is shippable
    shipped = P.parse_from_bytes(save_prog.to_bytes())
    exe = fluid.Executor()
    with fluid.scope_guard(scope):
        exe.run(shipped)
    assert (tmp_path / "sv.a.npy").exists()

    scope2 = fluid.Scope()
    load_prog = _build_load_program(["sv.a", "sv.b"], str(tmp_path))
    with fluid.scope_guard(scope2):
        exe.run(load_prog)
    np.testing.assert_allclose(np.asarray(scope2.find_var("sv.a")),
                               np.arange(6.0).reshape(2, 3))
    np.testing.assert_allclose(np.asarray(scope2.find_var("sv.b")),
                               np.ones((4,)) * 7)

    # combined single-file form (save_combine / load_combine)
    cp = _build_save_program(["sv.a", "sv.b"], str(tmp_path),
                             filename="all")
    assert [op.type for op in cp.global_block().ops] == ["save_combine"]
    with fluid.scope_guard(scope):
        exe.run(cp)
    scope3 = fluid.Scope()
    with fluid.scope_guard(scope3):
        exe.run(_build_load_program(["sv.a", "sv.b"], str(tmp_path),
                                    filename="all"))
    np.testing.assert_allclose(np.asarray(scope3.find_var("sv.b")),
                               np.ones((4,)) * 7)


def test_tensor_save_load_layer_api(tmp_path):
    """layers.save/load emit the in-graph io ops (reference
    layers/tensor.py save/load)."""
    import jax.numpy as jnp

    main, startup, scope = _fresh()
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            w = layers.create_parameter(shape=[3], dtype="float32",
                                        name="tsl.w")
            layers.save(w, str(tmp_path / "w"))
        exe = fluid.Executor()
        exe.run(startup)
        exe.run(main)
    assert (tmp_path / "w.npy").exists()

    main2 = Program()
    with program_guard(main2, Program()):
        out = main2.global_block().create_var(name="tsl.w2", shape=[3],
                                              dtype="float32",
                                              persistable=True)
        layers.load(out, str(tmp_path / "w"))
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        fluid.Executor().run(main2)
    np.testing.assert_allclose(np.asarray(scope2.find_var("tsl.w2")),
                               np.asarray(scope.find_var("tsl.w")))


def test_random_seed_set_after_first_run_takes_effect():
    """random_seed is baked into the lowered trace, so the jit cache must
    key on it: setting prog.random_seed AFTER a cached run is a plain
    attribute write (no version bump) and previously kept serving the
    unseeded entry. Seeded runs must be reproducible tick-for-tick."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.framework import Program, program_guard

    def build():
        main, startup = Program(), Program()
        with program_guard(main, startup):
            x = layers.data(name="x", shape=[8], dtype="float32")
            h = layers.dropout(x, dropout_prob=0.5)
            out = layers.mean(h)
        return main, startup, out

    feed = {"x": np.ones((4, 8), np.float32)}

    main, startup, out = build()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[out])  # caches the UNSEEDED fn

        def three_runs(seed):
            main.random_seed = seed
            main._rng_tick = 0  # rewind the deterministic run counter
            return [float(np.asarray(
                exe.run(main, feed=feed, fetch_list=[out])[0]).ravel()[0])
                for _ in range(3)]

        a = three_runs(123)
        b = three_runs(123)
        # the seed set AFTER the first (cached, unseeded) run governs
        # later runs, tick-for-tick — previously the stale cache entry
        # kept serving unseeded randomness and a == b failed
        assert a == b, (a, b)
        c = three_runs(321)
        assert a != c, "different seeds must give different streams"
