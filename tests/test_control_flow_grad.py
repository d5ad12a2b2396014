"""Control-flow completion: backward-through-While (bounded scan), IfElse,
DynamicRNN (reference while_op.cc:96, layers/control_flow.py:1252,1354)."""
import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.framework import Program, program_guard


def _run(prog, startup, feed, fetch, scope=None, init=None):
    scope = scope or fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        for k, v in (init or {}).items():
            scope.set_var(k, jnp.asarray(v))
        return exe.run(prog, feed=feed, fetch_list=fetch), scope


def test_while_forward_unbounded_still_works():
    prog, startup = Program(), Program()
    with program_guard(prog, startup):
        i = layers.fill_constant(shape=[1], dtype="int64", value=0)
        n = layers.fill_constant(shape=[1], dtype="int64", value=5)
        acc = layers.fill_constant(shape=[1], dtype="float32", value=0.0)
        cond = layers.less_than(i, n)
        w = layers.While(cond)
        with w.block():
            acc2 = layers.scale(acc, scale=1.0, bias=2.0)
            layers.assign(acc2, acc)
            layers.increment(i, value=1, in_place=True)
            layers.less_than(i, n, cond=cond)
    (out,), _ = _run(prog, startup, {}, [acc])
    assert float(out.ravel()[0]) == 10.0


def test_while_backward_without_max_steps_trains():
    """round-4 review (r3 item 6) done-bar: a DYNAMIC-trip-count While — no
    max_steps anywhere, the bound comes from a runtime-fed tensor — trains
    under append_backward. The grad is the recompute-replay custom vjp
    (ops/control_flow.py:_while_grad, reference while_op.cc:96); the
    analytic gradient for n doublings of y = x@W is 2^n * x^T @ dmean."""
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 3
    with program_guard(prog, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        n_steps = layers.data(name="n_steps", shape=[1], dtype="int64",
                              append_batch_size=False)
        y = layers.fc(input=x, size=4, param_attr="uw_w", bias_attr=False)
        i = layers.fill_constant(shape=[1], dtype="int64", value=0)
        cond = layers.less_than(i, n_steps)
        w = layers.While(cond)  # NO max_steps
        with w.block():
            y2 = layers.scale(y, scale=2.0)
            layers.assign(y2, y)
            layers.increment(i, value=1, in_place=True)
            layers.less_than(i, n_steps, cond=cond)
        loss = layers.mean(y)
        fluid.optimizer.SGD(learning_rate=0.0).minimize(loss)

    x_np = np.ones((2, 4), np.float32)
    w0 = np.eye(4, dtype=np.float32)
    for n in (3, 5):  # the SAME compiled program, different trip counts
        (g,), _ = _run(prog, startup,
                       {"x": x_np, "n_steps": np.array([n], np.int64)},
                       ["uw_w@GRAD"], init={"uw_w": w0})
        expected = (2.0 ** n) * x_np.T @ (np.ones((2, 4), np.float32) / 8.0)
        np.testing.assert_allclose(np.asarray(g), expected, rtol=1e-5,
                                   err_msg=f"n={n}")


def test_while_backward_with_max_steps_trains():
    """loss = mean(w*x doubled 3 times) -> d loss/d w == 8 * mean-grad; the
    bounded-scan lowering must produce the exact analytic gradient."""
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 3
    with program_guard(prog, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        y = layers.fc(input=x, size=4, param_attr="while_w", bias_attr=False)
        i = layers.fill_constant(shape=[1], dtype="int64", value=0)
        n = layers.fill_constant(shape=[1], dtype="int64", value=3)
        cond = layers.less_than(i, n)
        w = layers.While(cond, max_steps=8)  # bound > trip count: exercises masking
        with w.block():
            y2 = layers.scale(y, scale=2.0)
            layers.assign(y2, y)
            layers.increment(i, value=1, in_place=True)
            layers.less_than(i, n, cond=cond)
        loss = layers.mean(y)
        fluid.optimizer.SGD(learning_rate=0.0).minimize(loss)

    x_np = np.ones((2, 4), np.float32)
    w0 = np.eye(4, dtype=np.float32)
    (g,), _ = _run(prog, startup, {"x": x_np}, ["while_w@GRAD"],
                   init={"while_w": w0})
    # y = x @ W; loop doubles 3x -> loss = mean(8 * x @ W)
    # dloss/dW = 8 * x^T @ (ones/8)  (mean over 8 elements)
    expected = 8.0 * x_np.T @ (np.ones((2, 4), np.float32) / 8.0)
    np.testing.assert_allclose(np.asarray(g), expected, rtol=1e-5)


def test_ifelse_forward_and_backward():
    """Piecewise function: rows with x.sum()>0 scaled by 3, others by -1.
    Forward must match numpy; gradient through both branches must be the
    per-row selected scale."""
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 5
    with program_guard(prog, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        x.stop_gradient = False
        s = layers.reduce_sum(x, dim=1, keep_dim=True)
        zero = layers.fill_constant(shape=[1], dtype="float32", value=0.0)
        cond = layers.less_than(zero, s)  # [N,1] bool: sum > 0
        ie = layers.IfElse(cond)
        with ie.true_block():
            d = ie.input(x)
            ie.output(layers.scale(d, scale=3.0))
        with ie.false_block():
            d = ie.input(x)
            ie.output(layers.scale(d, scale=-1.0))
        (merged,) = ie()
        loss = layers.reduce_sum(merged)
        fluid.backward.append_backward(loss, parameter_list=["x"])

    x_np = np.array([[1, 1, 1, 1], [-1, -1, -1, -1], [2, -1, 0, 0]],
                    np.float32)
    (out, gx), _ = _run(prog, startup, {"x": x_np}, [merged, "x@GRAD"])
    expected = np.where(x_np.sum(1, keepdims=True) > 0, 3.0 * x_np, -x_np)
    np.testing.assert_allclose(out, expected, rtol=1e-6)
    gexp = np.where(x_np.sum(1, keepdims=True) > 0, 3.0, -1.0) * np.ones_like(x_np)
    np.testing.assert_allclose(gx, gexp, rtol=1e-6)


def test_dynamic_rnn_matches_manual_masked_scan():
    """DynamicRNN accumulator (h = h_prev + x_t) over ragged lengths: outputs
    are zero past each length, memory freezes, sequence_last_step returns the
    true final state."""
    N, T, D = 3, 5, 2
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 7
    with program_guard(prog, startup):
        x = layers.data(name="x", shape=[D], dtype="float32", lod_level=1)
        drnn = layers.DynamicRNN()
        with drnn.block():
            x_t = drnn.step_input(x)
            h_prev = drnn.memory(shape=[D], value=0.0)
            h = layers.elementwise_add(x=x_t, y=h_prev)
            drnn.update_memory(h_prev, h)
            drnn.output(h)
        out = drnn()
        last = layers.sequence_last_step(out)

    rng = np.random.RandomState(0)
    x_np = rng.rand(N, T, D).astype(np.float32)
    lens = np.array([5, 2, 3], np.int32)
    (seq, fin), _ = _run(prog, startup,
                         {"x": x_np, "x@LEN": lens}, [out, last])
    for i in range(N):
        run = np.cumsum(x_np[i], axis=0)
        for t in range(T):
            if t < lens[i]:
                np.testing.assert_allclose(seq[i, t], run[t], rtol=1e-5)
            else:
                assert np.all(seq[i, t] == 0)
        np.testing.assert_allclose(fin[i], run[lens[i] - 1], rtol=1e-5)


def test_dynamic_rnn_trains_sentiment_style():
    """A fc-cell DynamicRNN classifier trains: loss decreases over steps.
    Exercises grads through scan + masking + static_input."""
    N, T, D, H = 8, 6, 4, 8
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 9
    with program_guard(prog, startup):
        x = layers.data(name="x", shape=[D], dtype="float32", lod_level=1)
        bias = layers.data(name="bias", shape=[D], dtype="float32")
        label = layers.data(name="label", shape=[1], dtype="int64")
        drnn = layers.DynamicRNN()
        with drnn.block():
            x_t = drnn.step_input(x)
            b = drnn.static_input(bias)
            h_prev = drnn.memory(shape=[H], value=0.0)
            xt_b = layers.elementwise_add(x=x_t, y=b)
            h = layers.fc(input=[xt_b, h_prev], size=H, act="tanh")
            drnn.update_memory(h_prev, h)
            drnn.output(h)
        out = drnn()
        last = layers.sequence_last_step(out)
        logit = layers.fc(input=last, size=2)
        loss = layers.mean(
            layers.softmax_with_cross_entropy(logits=logit, label=label))
        fluid.optimizer.Adam(learning_rate=0.05).minimize(loss)

    rng = np.random.RandomState(1)
    x_np = rng.rand(N, T, D).astype(np.float32)
    lens = rng.randint(1, T + 1, size=(N,)).astype(np.int32)
    y_np = (x_np[np.arange(N), 0, 0] > 0.5).astype(np.int64)[:, None]
    b_np = 0.1 * np.ones((N, D), np.float32)
    scope = fluid.Scope()
    losses = []
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        for _ in range(25):
            (l,) = exe.run(prog, feed={
                "x": x_np, "x@LEN": lens, "bias": b_np, "label": y_np,
            }, fetch_list=[loss])
            losses.append(float(np.asarray(l).ravel()[0]))
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])


def test_conditional_block_now_differentiable():
    prog, startup = Program(), Program()
    with program_guard(prog, startup):
        x = layers.data(name="x", shape=[2], dtype="float32")
        x.stop_gradient = False
        flag = layers.fill_constant(shape=[1], dtype="bool", value=True)
        y = layers.scale(x, scale=1.0)
        cb = layers.ConditionalBlock([flag])
        with cb.block():
            y2 = layers.scale(y, scale=4.0)
            layers.assign(y2, y)
        loss = layers.reduce_sum(y)
        fluid.backward.append_backward(loss, parameter_list=["x"])
    x_np = np.ones((1, 2), np.float32)
    (gx,), _ = _run(prog, startup, {"x": x_np}, ["x@GRAD"])
    np.testing.assert_allclose(gx, 4.0 * np.ones((1, 2), np.float32))


def test_ifelse_branch_reads_cond_as_data():
    """A branch may consume the cond tensor itself (e.g. cast it) — it
    arrives through the Cond slot but must be bound in the branch env."""
    import paddle_tpu.fluid as fluid

    main, startup, scope = (Program(), Program(), fluid.Scope())
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            x = layers.data(name="x", shape=[1], dtype="float32")
            half = layers.fill_constant(shape=[1], dtype="float32", value=0.5)
            cond = layers.less_than(half, x)  # [N,1] bool
            ie = layers.IfElse(cond)
            with ie.true_block():
                d = ie.input(x)
                ie.output(layers.elementwise_add(
                    d, layers.cast(cond, "float32")))
            with ie.false_block():
                d = ie.input(x)
                ie.output(layers.scale(d, scale=10.0))
            (out,) = ie()
        exe = fluid.Executor()
        exe.run(startup)
        xv = np.array([[0.9], [0.1]], np.float32)
        (o,) = exe.run(main, feed={"x": xv}, fetch_list=[out])
        np.testing.assert_allclose(np.asarray(o), [[1.9], [1.0]], rtol=1e-6)


def test_dynamic_rnn_grad_bf16_mixed_exit_steps_vs_f64():
    """bf16 boundary case (round-3 review item 8): sequences in ONE batch exit
    at different steps; params train under amp (bf16 MXU compute); the
    program's gradient is checked against a float64 central-difference
    numeric gradient of an independent numpy replica of the masked scan.
    Tolerance is loose but stated: bf16 has ~8 mantissa bits, so rel err
    up to 4e-2 on the summed grad is expected (reference op_test.py:97
    numeric-grad discipline with max_relative_error)."""
    from paddle_tpu.fluid.flags import set_flags

    N, T, D, H = 4, 5, 3, 4
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 17
    with program_guard(prog, startup):
        x = layers.data(name="x", shape=[D], dtype="float32", lod_level=1)
        drnn = layers.DynamicRNN()
        with drnn.block():
            x_t = drnn.step_input(x)
            h_prev = drnn.memory(shape=[H], value=0.0)
            hx = layers.fc(input=x_t, size=H, bias_attr=False,
                           param_attr="bf16.wx", act=None)
            hh = layers.fc(input=h_prev, size=H, bias_attr=False,
                           param_attr="bf16.wh", act=None)
            h = layers.tanh(layers.elementwise_add(x=hx, y=hh))
            drnn.update_memory(h_prev, h)
            drnn.output(h)
        out = drnn()
        last = layers.sequence_last_step(out)
        loss = layers.mean(last)
        fluid.optimizer.SGD(learning_rate=0.0).minimize(loss)

    rng = np.random.RandomState(2)
    x_np = rng.uniform(-1, 1, (N, T, D)).astype(np.float32)
    lens = np.array([5, 2, 3, 1], np.int32)  # mixed exit steps
    wx0 = rng.uniform(-0.5, 0.5, (D, H)).astype(np.float32)
    wh0 = rng.uniform(-0.5, 0.5, (H, H)).astype(np.float32)

    set_flags({"amp": True})
    try:
        (gwx, gwh), _ = _run(
            prog, startup, {"x": x_np, "x@LEN": lens},
            ["bf16.wx@GRAD", "bf16.wh@GRAD"],
            init={"bf16.wx": wx0, "bf16.wh": wh0})
    finally:
        set_flags({"amp": False})

    def f64_loss(wx, wh):
        last = np.zeros((N, H), np.float64)
        for i in range(N):
            h = np.zeros(H, np.float64)
            for t in range(int(lens[i])):
                h = np.tanh(x_np[i, t].astype(np.float64) @ wx + h @ wh)
            last[i] = h
        return last.mean()

    def numeric_grad(w, which, eps=1e-5):
        g = np.zeros_like(w, np.float64)
        for idx in np.ndindex(w.shape):
            wp = w.astype(np.float64).copy(); wp[idx] += eps
            wm = w.astype(np.float64).copy(); wm[idx] -= eps
            if which == "wx":
                g[idx] = (f64_loss(wp, wh0.astype(np.float64))
                          - f64_loss(wm, wh0.astype(np.float64))) / (2 * eps)
            else:
                g[idx] = (f64_loss(wx0.astype(np.float64), wp)
                          - f64_loss(wx0.astype(np.float64), wm)) / (2 * eps)
        return g

    for got, which in ((gwx, "wx"), (gwh, "wh")):
        want = numeric_grad(wx0 if which == "wx" else wh0, which)
        denom = np.abs(want).max() + 1e-8
        rel = np.abs(np.asarray(got, np.float64) - want).max() / denom
        assert rel < 4e-2, (which, rel)


def test_while_grad_step_evals_linear_in_T():
    """round-4 review item 5 done-bar: the unbounded while-grad is segment-
    checkpointed replay — total step-fn evaluations for trip count T must
    be ~4T (primal T + count/record T + segment rebuild ~T + vjp T), NOT
    the O(T^2) of replay-from-zero (T=200 would be ~20k evals there)."""
    from paddle_tpu.fluid.flags import set_flags
    from paddle_tpu.fluid.ops import control_flow as cf

    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 3
    with program_guard(prog, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        n_steps = layers.data(name="n_steps", shape=[1], dtype="int64",
                              append_batch_size=False)
        y = layers.fc(input=x, size=4, param_attr="cnt_w", bias_attr=False)
        i = layers.fill_constant(shape=[1], dtype="int64", value=0)
        cond = layers.less_than(i, n_steps)
        w = layers.While(cond)  # NO max_steps: dynamic trip count
        with w.block():
            y2 = layers.scale(y, scale=1.01)
            layers.assign(y2, y)
            layers.increment(i, value=1, in_place=True)
            layers.less_than(i, n_steps, cond=cond)
        loss = layers.mean(y)
        fluid.optimizer.SGD(learning_rate=0.0).minimize(loss)

    T = 200
    x_np = np.ones((2, 4), np.float32)
    w0 = np.eye(4, dtype=np.float32)
    set_flags({"count_while_step_evals": True})
    try:
        cf.step_evals_reset()
        (g,), _ = _run(prog, startup,
                       {"x": x_np, "n_steps": np.array([T], np.int64)},
                       ["cnt_w@GRAD"], init={"cnt_w": w0})
        evals = cf.step_evals()
    finally:
        set_flags({"count_while_step_evals": False})
    expected = (1.01 ** T) * x_np.T @ (np.ones((2, 4), np.float32) / 8.0)
    np.testing.assert_allclose(np.asarray(g), expected, rtol=1e-4)
    # linear bound with slack for segment padding; quadratic would be ~20k
    assert 0 < evals <= 6 * T + 400, evals


def test_while_grad_checkpoint_overflow_stays_correct():
    """Trip counts beyond S*C degrade to longer replays but must stay
    numerically EXACT (overflow segments replay from the last slot)."""
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 3
    with program_guard(prog, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        n_steps = layers.data(name="n_steps", shape=[1], dtype="int64",
                              append_batch_size=False)
        y = layers.fc(input=x, size=4, param_attr="ovf_w", bias_attr=False)
        i = layers.fill_constant(shape=[1], dtype="int64", value=0)
        cond = layers.less_than(i, n_steps)
        # S*C = 24 << T = 60: three overflow segments replay from slot C-1
        w = layers.While(cond, grad_segment_len=8, grad_max_segments=3)
        with w.block():
            y2 = layers.scale(y, scale=1.01)
            layers.assign(y2, y)
            layers.increment(i, value=1, in_place=True)
            layers.less_than(i, n_steps, cond=cond)
        loss = layers.mean(y)
        fluid.optimizer.SGD(learning_rate=0.0).minimize(loss)

    T = 60
    x_np = np.ones((2, 4), np.float32)
    w0 = np.eye(4, dtype=np.float32)
    (g,), _ = _run(prog, startup,
                   {"x": x_np, "n_steps": np.array([T], np.int64)},
                   ["ovf_w@GRAD"], init={"ovf_w": w0})
    expected = (1.01 ** T) * x_np.T @ (np.ones((2, 4), np.float32) / 8.0)
    np.testing.assert_allclose(np.asarray(g), expected, rtol=1e-4)
