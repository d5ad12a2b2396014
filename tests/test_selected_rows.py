"""Sparse gradient path (SelectedRows capability).

Mirrors the reference's sparse tests (test_lookup_table_op.py sparse grad,
math/selected_rows_functor tests, sparse sgd/adam kernels): lookup_table
is_sparse grads never materialize dense [V, D]; optimizers apply row-wise.
"""
import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.framework import Program, program_guard
from paddle_tpu.fluid.selected_rows import SelectedRows, add_any


def test_merged_sums_duplicates():
    rows = jnp.array([3, 1, 3, 7, 1], dtype=jnp.int32)
    vals = jnp.arange(10, dtype=jnp.float32).reshape(5, 2)
    sr = SelectedRows(rows, vals, height=10)
    r_s, merged, mask = sr.merged()
    np.testing.assert_array_equal(np.asarray(r_s), [1, 1, 3, 3, 7])
    # scatter-add of mask*merged must equal the dense scatter of raw values
    dense_via_merge = np.zeros((10, 2), np.float32)
    np.add.at(dense_via_merge, np.asarray(r_s),
              np.asarray(mask)[:, None] * np.asarray(merged))
    np.testing.assert_allclose(dense_via_merge, np.asarray(sr.to_dense()))


def test_add_any_sparse_sparse_and_mixed():
    a = SelectedRows(jnp.array([0, 2]), jnp.ones((2, 3)), 4)
    b = SelectedRows(jnp.array([2, 3]), 2 * jnp.ones((2, 3)), 4)
    ss = add_any(a, b)
    assert isinstance(ss, SelectedRows)
    np.testing.assert_allclose(
        np.asarray(ss.to_dense()),
        np.asarray(a.to_dense() + b.to_dense()))
    mixed = add_any(a, jnp.full((4, 3), 5.0))
    assert not isinstance(mixed, SelectedRows)
    np.testing.assert_allclose(
        np.asarray(mixed), np.asarray(a.to_dense()) + 5.0)


def _embedding_program(is_sparse, optimizer_fn, vocab=50, dim=8, seed=7):
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = seed
    with program_guard(prog, startup):
        ids = layers.data(name="ids", shape=[1], dtype="int64")
        label = layers.data(name="label", shape=[dim], dtype="float32")
        emb = layers.embedding(
            input=ids, size=[vocab, dim], is_sparse=is_sparse,
            param_attr="emb_w")
        cost = layers.mean(layers.square_error_cost(input=emb, label=label))
        optimizer_fn().minimize(cost)
    return prog, startup, cost


def _train_w(is_sparse, optimizer_fn, steps=3):
    prog, startup, cost = _embedding_program(is_sparse, optimizer_fn)
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    # identical W across the two runs (program hashes differ, so startup
    # randomness would differ); fixed id set across steps so lazy sparse
    # moments match dense exactly (untouched rows keep zero moments)
    w0 = rng.rand(50, 8).astype(np.float32) * 0.1
    ids = rng.randint(0, 50, size=(16, 1)).astype(np.int64)
    ids[3] = ids[5] = ids[9]  # duplicates — exercises MergeAdd semantics
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        scope.set_var("emb_w", jnp.asarray(w0))
        for _ in range(steps):
            lbl = rng.rand(16, 8).astype(np.float32)
            exe.run(prog, feed={"ids": ids, "label": lbl}, fetch_list=[cost])
        w = np.asarray(scope.find_var("emb_w"))
    return w


@pytest.mark.parametrize("opt", [
    lambda: fluid.optimizer.SGD(learning_rate=0.1),
    lambda: fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9),
    lambda: fluid.optimizer.Adam(learning_rate=0.1),
    lambda: fluid.optimizer.Adagrad(learning_rate=0.1),
])
def test_sparse_matches_dense_update(opt):
    """Row-wise lazy update == dense update: untouched rows see zero grad in
    the dense path, and zero-grad steps leave sgd/momentum/adagrad params
    unmoved; adam's lazy mode matches because moments start at zero and only
    batch rows ever become nonzero."""
    w_dense = _train_w(False, opt)
    w_sparse = _train_w(True, opt)
    np.testing.assert_allclose(w_sparse, w_dense, rtol=2e-5, atol=2e-6)


def test_sparse_grad_is_selected_rows_in_ir_and_at_runtime():
    prog, startup, cost = _embedding_program(
        True, lambda: fluid.optimizer.SGD(learning_rate=0.0))
    gvar = prog.global_block().var("emb_w@GRAD")
    assert gvar.desc.type == "selected_rows"
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        ids = np.array([[1], [4], [1], [9]], dtype=np.int64)
        lbl = np.ones((4, 8), np.float32)
        (g,) = exe.run(prog, feed={"ids": ids, "label": lbl},
                       fetch_list=["emb_w@GRAD"])
        assert isinstance(g, SelectedRows)
        assert g.value.shape == (4, 8)  # [N, D], never [V, D]
        assert g.height == 50
        # sparse grad densifies to exactly the dense-path gradient
        w = np.asarray(scope.find_var("emb_w"))
        dense = np.zeros((50, 8), np.float32)
        emb_out = w[ids[:, 0]]
        dy = 2.0 * (emb_out - lbl) / lbl.size
        np.add.at(dense, ids[:, 0], dy)
        np.testing.assert_allclose(np.asarray(g.to_dense()), dense,
                                   rtol=1e-5, atol=1e-6)


def test_large_vocab_word2vec_style_training():
    """100k-vocab embedding trains sparse: grad stays [N, D] and loss drops
    (review item 3's acceptance bar — no dense [V, D] materialization on the
    grad path)."""
    V, D, N = 100_000, 64, 64
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 11
    with program_guard(prog, startup):
        ids = layers.data(name="ids", shape=[1], dtype="int64")
        label = layers.data(name="label", shape=[1], dtype="int64")
        emb = layers.embedding(input=ids, size=[V, D], is_sparse=True,
                               param_attr="w2v_emb")
        fc = layers.fc(input=emb, size=32, act="relu")
        logit = layers.fc(input=fc, size=16)
        # small softmax head; the sparse path under test is the embedding
        loss = layers.mean(layers.softmax_with_cross_entropy(
            logits=logit, label=label))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    gvar = prog.global_block().var("w2v_emb@GRAD")
    assert gvar.desc.type == "selected_rows"
    scope = fluid.Scope()
    rng = np.random.RandomState(3)
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        losses = []
        ids_np = rng.randint(0, V, size=(N, 1)).astype(np.int64)
        lbl_np = (ids_np % 16).astype(np.int64)
        for _ in range(8):
            out = exe.run(prog, feed={"ids": ids_np, "label": lbl_np},
                          fetch_list=[loss, "w2v_emb@GRAD"])
            losses.append(float(np.asarray(out[0])))
            assert isinstance(out[1], SelectedRows)
            assert out[1].value.shape == (N, D)
    assert losses[-1] < losses[0] * 0.9, losses


def test_row_sharded_embedding_under_parallel_executor():
    """Row-sharded embedding table (the reference's distributed lookup table /
    split_selected_rows capability, doc/fluid/design/dist_train/
    distributed_lookup_table_design.md): W sharded over a model axis via a
    plan rule, sparse grads applied SPMD — result matches the single-device
    dense run."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel import ShardingPlan, make_mesh

    def build():
        prog, startup = Program(), Program()
        prog.random_seed = startup.random_seed = 13
        with program_guard(prog, startup):
            ids = layers.data(name="ids", shape=[1], dtype="int64")
            label = layers.data(name="label", shape=[8], dtype="float32")
            emb = layers.embedding(input=ids, size=[64, 8], is_sparse=True,
                                   param_attr="shard_emb")
            cost = layers.mean(
                layers.square_error_cost(input=emb, label=label))
            fluid.optimizer.SGD(learning_rate=0.5).minimize(cost)
        return prog, startup, cost

    rng = np.random.RandomState(1)
    w0 = rng.rand(64, 8).astype(np.float32)
    ids = rng.randint(0, 64, size=(16, 1)).astype(np.int64)
    ids[0] = ids[7]
    lbl = rng.rand(16, 8).astype(np.float32)

    # single-device reference run
    prog, startup, cost = build()
    scope1 = fluid.Scope()
    with fluid.scope_guard(scope1):
        exe = fluid.Executor()
        exe.run(startup)
        scope1.set_var("shard_emb", jnp.asarray(w0))
        exe.run(prog, feed={"ids": ids, "label": lbl}, fetch_list=[cost])
        w_ref = np.asarray(scope1.find_var("shard_emb"))

    # row-sharded over 'mp' on a dp×mp mesh
    prog, startup, cost = build()
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe = fluid.Executor()
        exe.run(startup)
        scope2.set_var("shard_emb", jnp.asarray(w0))
        plan = ShardingPlan(rules=[("shard_emb", P("mp", None))],
                            batch_axis="dp")
        pe = fluid.ParallelExecutor(
            main_program=prog, loss_name=cost.name,
            mesh=make_mesh({"dp": 2, "mp": 4}), sharding_plan=plan)
        pe.run(fetch_list=[cost], feed={"ids": ids, "label": lbl})
        w_pe = np.asarray(scope2.find_var("shard_emb"))
    np.testing.assert_allclose(w_pe, w_ref, rtol=1e-5, atol=1e-6)


def test_global_norm_clip_on_sparse_grad():
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 5
    with program_guard(prog, startup):
        ids = layers.data(name="ids", shape=[1], dtype="int64")
        label = layers.data(name="label", shape=[4], dtype="float32")
        emb = layers.embedding(input=ids, size=[20, 4], is_sparse=True,
                               param_attr="clip_emb")
        cost = layers.mean(layers.square_error_cost(input=emb, label=label))
        fluid.clip.set_gradient_clip(
            fluid.clip.GradientClipByGlobalNorm(clip_norm=1e-4), program=prog)
        fluid.optimizer.SGD(learning_rate=1.0).minimize(cost)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        w0 = np.asarray(scope.find_var("clip_emb")).copy()
        ids_np = np.array([[2], [2], [7]], dtype=np.int64)
        lbl = 100.0 * np.ones((3, 4), np.float32)
        exe.run(prog, feed={"ids": ids_np, "label": lbl}, fetch_list=[cost])
        w1 = np.asarray(scope.find_var("clip_emb"))
    moved = np.abs(w1 - w0).sum()
    # clipped to global norm 1e-4 with lr 1.0: total movement is tiny but
    # nonzero, and only the touched rows moved
    assert 0 < moved < 1e-3
    untouched = np.delete(np.abs(w1 - w0), [2, 7], axis=0)
    assert untouched.sum() == 0.0
