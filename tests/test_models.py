"""Model zoo smoke + convergence tests (reference book/benchmark configs:
recognize_digits LeNet, resnet, transformer)."""
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.framework import Program, program_guard
from paddle_tpu.models import lenet, resnet, transformer


def test_lenet_mnist_converges():
    main, startup, scope = Program(), Program(), fluid.Scope()
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            img = layers.data(name="img", shape=[1, 28, 28], dtype="float32")
            label = layers.data(name="label", shape=[1], dtype="int64")
            avg_cost, acc, pred = lenet.build(img, label)
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(avg_cost)
        exe = fluid.Executor()
        exe.run(startup)
        rng = np.random.RandomState(0)
        losses = []
        for step in range(30):
            x = rng.rand(16, 1, 28, 28).astype(np.float32)
            y = rng.randint(0, 10, size=(16, 1)).astype(np.int64)
            # plant signal: brighten a label-dependent row block
            for i in range(16):
                x[i, 0, y[i, 0] * 2:(y[i, 0] * 2 + 3)] += 2.0
            loss, a = exe.run(main, feed={"img": x, "label": y},
                              fetch_list=[avg_cost, acc])
            losses.append(float(loss[0]))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]


def test_resnet_cifar_smoke():
    main, startup, scope = Program(), Program(), fluid.Scope()
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            img = layers.data(name="img", shape=[3, 32, 32], dtype="float32")
            label = layers.data(name="label", shape=[1], dtype="int64")
            avg_cost, acc, pred = resnet.build_train(
                img, label, class_dim=10, depth=8, variant="cifar10"
            )
            fluid.optimizer.Momentum(learning_rate=0.01, momentum=0.9).minimize(
                avg_cost
            )
        exe = fluid.Executor()
        exe.run(startup)
        rng = np.random.RandomState(0)
        for step in range(2):
            x = rng.rand(4, 3, 32, 32).astype(np.float32)
            y = rng.randint(0, 10, size=(4, 1)).astype(np.int64)
            (loss,) = exe.run(main, feed={"img": x, "label": y},
                              fetch_list=[avg_cost])
            assert np.isfinite(loss).all()
        # BN stats must have moved off their init
        bn_means = [n for n in scope.var_names() if "batch_norm" in n]
        assert bn_means


def test_resnet_cifar_fused_inference_build():
    """fused=True builds the whole net through conv2d_bn_relu (the
    inference conv+bn fold; Pallas alternate kernel under the flag) and
    executes a forward pass."""
    main, startup, scope = Program(), Program(), fluid.Scope()
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            img = layers.data(name="img", shape=[3, 32, 32], dtype="float32")
            logits = resnet.resnet_cifar10(img, class_dim=10, depth=8,
                                           is_test=True, fused=True)
        assert any(op.type == "conv2d_bn_relu"
                   for op in main.global_block().ops)
        assert not any(op.type == "batch_norm"
                       for op in main.global_block().ops)
        exe = fluid.Executor()
        exe.run(startup)
        x = np.random.RandomState(1).rand(2, 3, 32, 32).astype(np.float32)
        (out,) = exe.run(main, feed={"img": x}, fetch_list=[logits])
        assert out.shape == (2, 10) and np.isfinite(out).all()


def test_resnet50_imagenet_builds():
    main, startup = Program(), Program()
    with program_guard(main, startup):
        img = layers.data(name="img", shape=[3, 224, 224], dtype="float32")
        label = layers.data(name="label", shape=[1], dtype="int64")
        avg_cost, acc, pred = resnet.build_train(img, label, class_dim=1000,
                                                 depth=50)
    n_params = len(main.global_block().all_parameters())
    # 53 convs + 53 BN(scale+bias) + fc(w+b) = 161 trainable params
    assert n_params == 161
    assert pred.shape == (-1, 1000)


def test_transformer_copy_task_converges():
    cfg = transformer.TransformerConfig(
        src_vocab=50, trg_vocab=50, max_len=8, d_model=32, n_heads=4,
        d_ff=64, n_layers=1, dropout=0.0,
    )
    main, startup, scope = Program(), Program(), fluid.Scope()
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            src = layers.data(name="src", shape=[cfg.max_len], dtype="int64")
            trg = layers.data(name="trg", shape=[cfg.max_len], dtype="int64")
            lbl = layers.data(name="lbl", shape=[cfg.max_len, 1], dtype="int64")
            avg_cost, logits = transformer.build_train(cfg, src, trg, lbl)
            fluid.optimizer.Adam(learning_rate=3e-3).minimize(avg_cost)
        exe = fluid.Executor()
        exe.run(startup)
        rng = np.random.RandomState(0)
        s = rng.randint(3, 50, size=(16, cfg.max_len)).astype(np.int64)
        t = np.concatenate([np.zeros((16, 1), np.int64), s[:, :-1]], axis=1)
        losses = []
        for step in range(60):
            losses.append(float(exe.run(
                main, feed={"src": s, "trg": t, "lbl": s[:, :, None]},
                fetch_list=[avg_cost],
            )[0][0]))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0] * 0.5, losses[::10]


def test_amp_flag_trains_lenet():
    """FLAGS['amp']: bf16 MXU operands / f32 accumulation. The model must
    still converge and master weights must stay float32."""
    import paddle_tpu
    from paddle_tpu.fluid.flags import set_flags
    from paddle_tpu.models import lenet

    set_flags({"amp": True})
    try:
        main, startup, scope = Program(), Program(), fluid.Scope()
        main.random_seed = startup.random_seed = 9
        with fluid.scope_guard(scope):
            with program_guard(main, startup):
                img = layers.data(name="img", shape=[1, 28, 28],
                                  dtype="float32")
                label = layers.data(name="label", shape=[1], dtype="int64")
                avg_cost, acc, _ = lenet.build(img, label)
                fluid.optimizer.Adam(learning_rate=1e-3).minimize(avg_cost)
            exe = fluid.Executor()
            exe.run(startup)
            reader = paddle_tpu.batch(paddle_tpu.dataset.mnist.train(),
                                      batch_size=64)
            feeder = fluid.DataFeeder(feed_list=[img, label], program=main)
            losses = []
            for i, data in enumerate(reader()):
                if i >= 12:
                    break
                (l,) = exe.run(main, feed=feeder.feed(data),
                               fetch_list=[avg_cost])
                losses.append(float(np.asarray(l).reshape(-1)[0]))
            assert np.isfinite(losses[-1])
            assert min(losses[1:]) < losses[0], losses
            w = scope.find_var(main.global_block().all_parameters()[0].name)
            assert str(np.asarray(w).dtype) == "float32"
    finally:
        set_flags({"amp": False})


@pytest.mark.parametrize("name", ["alexnet", "googlenet", "smallnet"])
def test_legacy_benchmark_models_train_step(name):
    """The legacy K40m benchmark suite models (reference benchmark/
    {alexnet,googlenet,smallnet_mnist_cifar}.py) build and take a training
    step; reduced spatial dims (96 vs the benchmark's 224) keep the CPU
    compile fast while exercising every stage (alexnet's stride-4 stem +
    3 pools needs >=67px; googlenet's head is a global pool)."""
    from paddle_tpu.models import alexnet, googlenet, smallnet

    mod = {"alexnet": alexnet, "googlenet": googlenet,
           "smallnet": smallnet}[name]
    shape = [3, 32, 32] if name == "smallnet" else [3, 96, 96]
    class_dim = 10 if name == "smallnet" else 1000
    main, startup, scope = Program(), Program(), fluid.Scope()
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            img = layers.data(name="img", shape=shape, dtype="float32")
            label = layers.data(name="label", shape=[1], dtype="int64")
            avg_cost, acc, pred = mod.build_train(
                img, label, class_dim=class_dim)
            fluid.optimizer.Momentum(
                learning_rate=0.01, momentum=0.9).minimize(avg_cost)
        exe = fluid.Executor()
        exe.run(startup)
        rng = np.random.RandomState(0)
        x = rng.rand(2, *shape).astype(np.float32)
        y = rng.randint(0, class_dim, size=(2, 1)).astype(np.int64)
        for _ in range(2):
            (loss,) = exe.run(main, feed={"img": x, "label": y},
                              fetch_list=[avg_cost])
            assert np.isfinite(loss).all()


def test_fluid_benchmark_suite_quick_mode():
    """The reference benchmark/fluid suite's remaining workloads (mnist,
    vgg, stacked_dynamic_lstm) run end-to-end through the bench harness in
    CPU quick mode: one JSON line each, finite losses that move."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["SUITE_ALLOW_CPU"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks",
                                      "fluid_suite_bench.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [json.loads(ln) for ln in proc.stdout.splitlines()
            if ln.strip().startswith("{")]
    by_name = {r.get("workload"): r for r in rows}
    assert set(by_name) == {"mnist", "vgg", "stacked_lstm"}, rows
    for r in by_name.values():
        assert r["finite"] and r["distinct_losses"] >= 2, r
        assert r["quick_mode"] and r["backend"] == "cpu", r


def test_graft_entry_is_full_train_step():
    """round-4 review weak 7: entry() must compile-check what bench.py
    measures — batch-norm TRAINING stats, the backward, and the Momentum
    update — not a forward-only inference graph."""
    import os
    import sys

    import jax
    import numpy as np

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        import __graft_entry__ as g
    finally:
        sys.path.pop(0)
    fn, args = g.entry()
    state, img, label = args
    loss, new_state = jax.jit(fn)(state, img, label)
    loss = float(np.asarray(loss).reshape(-1)[0])
    assert np.isfinite(loss)
    # the optimizer ran: trainable params moved
    moved = [k for k in new_state
             if k in state and np.asarray(state[k]).dtype.kind == "f"
             and not np.array_equal(np.asarray(state[k]),
                                    np.asarray(new_state[k]))]
    assert len(moved) > 100, len(moved)
    # momentum velocity accumulators are part of the carried state
    assert any("velocity" in k for k in new_state), sorted(new_state)[:5]
