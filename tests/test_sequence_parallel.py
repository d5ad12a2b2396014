"""Ring attention / Ulysses sequence parallelism vs. dense reference
attention, forward and backward, on the 8-device CPU mesh."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.parallel import make_mesh
from paddle_tpu.parallel.sequence_parallel import (
    ring_attention_shard,
    sequence_parallel_attention,
)


def dense_attention(q, k, v, causal=False, scale=None):
    """Straightforward softmax attention in f64 as ground truth."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = np.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        sq, sk = s.shape[2], s.shape[3]
        mask = np.arange(sq)[:, None] >= np.arange(sk)[None, :]
        s = np.where(mask[None, None], s, -np.inf)
    s = s - s.max(axis=-1, keepdims=True)
    p = np.exp(s)
    p /= p.sum(axis=-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


def _qkv(b=2, s=32, h=8, d=8, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: rng.randn(b, s, h, d).astype(np.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_single_device_flash_matches_dense(causal):
    q, k, v = _qkv()
    out = ring_attention_shard(q, k, v, None, causal, None)
    np.testing.assert_allclose(
        np.asarray(out), dense_attention(q, k, v, causal), atol=2e-5
    )


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("axes", [{"sp": 8}, {"dp": 2, "sp": 4}])
def test_sp_attention_matches_dense(impl, causal, axes):
    mesh = make_mesh(axes)
    q, k, v = _qkv()
    batch_axis = "dp" if "dp" in axes else None
    out = sequence_parallel_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), mesh,
        seq_axis="sp", batch_axis=batch_axis, causal=causal, impl=impl,
    )
    np.testing.assert_allclose(
        np.asarray(out), dense_attention(q, k, v, causal), atol=2e-5
    )


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [False, True])
def test_sp_attention_grads_match_dense(impl, causal):
    mesh = make_mesh({"sp": 8})
    q, k, v = _qkv(s=16)

    def loss_sp(q, k, v):
        out = sequence_parallel_attention(
            q, k, v, mesh, seq_axis="sp", causal=causal, impl=impl
        )
        return jnp.sum(jnp.sin(out))

    def loss_dense(q, k, v):
        scale = q.shape[-1] ** -0.5
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        if causal:
            sq = s.shape[2]
            m = jnp.arange(sq)[:, None] >= jnp.arange(sq)[None, :]
            s = jnp.where(m[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.sin(jnp.einsum("bhqk,bkhd->bqhd", p, v)))

    gs = jax.grad(loss_sp, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    )
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    )
    for a, b, name in zip(gs, gd, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5, err_msg=f"d{name}"
        )


def test_cross_attention_different_kv_len():
    # ring attention with Sq != Sk (cross-attention)
    mesh = make_mesh({"sp": 8})
    rng = np.random.RandomState(3)
    q = rng.randn(2, 16, 4, 8).astype(np.float32)
    k = rng.randn(2, 32, 4, 8).astype(np.float32)
    v = rng.randn(2, 32, 4, 8).astype(np.float32)
    out = sequence_parallel_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), mesh, seq_axis="sp"
    )
    np.testing.assert_allclose(
        np.asarray(out), dense_attention(q, k, v), atol=2e-5
    )


def test_ring_attention_layer_in_program():
    """The ring_attention op through the Program/Executor path, single-device
    fallback + gradient via the generic vjp grad path."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.framework import Program, program_guard

    main, startup, scope = Program(), Program(), fluid.Scope()
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            q = layers.data(name="q", shape=[16, 4, 8], dtype="float32")
            k = layers.data(name="k", shape=[16, 4, 8], dtype="float32")
            v = layers.data(name="v", shape=[16, 4, 8], dtype="float32")
            out = layers.ring_attention(q, k, v, causal=True)
            # a param so minimize() has something to optimize
            proj = layers.fc(input=out, size=4, num_flatten_dims=3)
            loss = layers.mean(proj)
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor()
        exe.run(startup)
        qn, kn, vn = _qkv(s=16)
        outv, lossv = exe.run(
            main, feed={"q": qn, "k": kn, "v": vn}, fetch_list=[out, loss]
        )
    np.testing.assert_allclose(
        outv, dense_attention(qn, kn, vn, causal=True), atol=2e-5
    )
    assert np.isfinite(lossv).all()


def test_ring_attention_layer_parallel_executor():
    """ring_attention under ParallelExecutor on a dp x sp mesh: training step
    runs SPMD and matches the single-device loss."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.framework import Program, program_guard
    from paddle_tpu.parallel import plan_sequence_parallel

    def build():
        from paddle_tpu.fluid import unique_name

        main, startup = Program(), Program()
        main.random_seed = startup.random_seed = 7
        with unique_name.guard(), program_guard(main, startup):
            q = layers.data(name="q", shape=[16, 4, 8], dtype="float32")
            k = layers.data(name="k", shape=[16, 4, 8], dtype="float32")
            v = layers.data(name="v", shape=[16, 4, 8], dtype="float32")
            out = layers.ring_attention(q, k, v, causal=True)
            proj = layers.fc(input=out, size=4, num_flatten_dims=3)
            loss = layers.mean(proj)
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        return main, startup, loss

    qn, kn, vn = _qkv(b=4, s=16)
    feed = {"q": qn, "k": kn, "v": vn}

    # single-device reference
    scope1 = fluid.Scope()
    with fluid.scope_guard(scope1):
        main, startup, loss = build()
        exe = fluid.Executor()
        exe.run(startup)
        (ref_loss,) = exe.run(main, feed=feed, fetch_list=[loss])

    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        main, startup, loss = build()
        exe = fluid.Executor()
        exe.run(startup)
        mesh = make_mesh({"dp": 2, "sp": 4})
        pe = fluid.ParallelExecutor(
            loss_name=loss.name, main_program=main, mesh=mesh,
            sharding_plan=plan_sequence_parallel(),
        )
        (sp_loss,) = pe.run(fetch_list=[loss], feed=feed)

    np.testing.assert_allclose(ref_loss, sp_loss, atol=1e-5)


def test_transformer_seq_parallel_trains():
    # the ring shard index rides in as a P(sp)-sharded iota input
    # instead of lax.axis_index (PR 14)
    """Flagship model with seq_parallel=True on a dp x sp mesh: loss
    decreases over steps (capability: long-context sharded attention)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.framework import Program, program_guard
    from paddle_tpu.models import transformer
    from paddle_tpu.parallel import plan_sequence_parallel

    cfg = transformer.TransformerConfig(
        src_vocab=40, trg_vocab=40, max_len=8, d_model=32, n_heads=4,
        d_ff=64, n_layers=1, dropout=0.0, seq_parallel=True,
    )
    main, startup, scope = Program(), Program(), fluid.Scope()
    with fluid.scope_guard(scope):
        with program_guard(main, startup):
            src = layers.data(name="src", shape=[cfg.max_len], dtype="int64")
            trg = layers.data(name="trg", shape=[cfg.max_len], dtype="int64")
            lbl = layers.data(name="lbl", shape=[cfg.max_len, 1], dtype="int64")
            avg_cost, _ = transformer.build_train(cfg, src, trg, lbl)
            fluid.optimizer.Adam(learning_rate=3e-3).minimize(avg_cost)
        exe = fluid.Executor()
        exe.run(startup)
        mesh = make_mesh({"dp": 2, "sp": 4})
        pe = fluid.ParallelExecutor(
            loss_name=avg_cost.name, main_program=main, mesh=mesh,
            sharding_plan=plan_sequence_parallel(),
        )
        rng = np.random.RandomState(0)
        losses = []
        for _ in range(10):
            s = rng.randint(3, 40, size=(8, cfg.max_len)).astype(np.int64)
            t = np.concatenate([np.zeros((8, 1), np.int64), s[:, :-1]], axis=1)
            losses.append(pe.run(
                fetch_list=[avg_cost],
                feed={"src": s, "trg": t, "lbl": s[:, :, None]},
            )[0].item())
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]
