"""Pallas kernels (flash attention, fused layer norm) in interpret mode on
CPU vs dense references, forward and backward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.fluid.ops.pallas_kernels import flash_attention, fused_layer_norm


def dense_attention(q, k, v, causal=False, scale=None):
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


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(2, 64, 2, 16), (1, 24, 3, 8)])
def test_flash_attention_forward(causal, shape):
    b, s, h, d = shape
    rng = np.random.RandomState(0)
    q, k, v = (rng.randn(b, s, h, d).astype(np.float32) for _ in range(3))
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=causal, block_q=16, block_k=16,
                          interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), dense_attention(q, k, v, causal), atol=2e-5
    )


def test_flash_attention_cross_lengths():
    rng = np.random.RandomState(1)
    q = rng.randn(2, 16, 2, 8).astype(np.float32)
    k = rng.randn(2, 48, 2, 8).astype(np.float32)
    v = rng.randn(2, 48, 2, 8).astype(np.float32)
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          block_q=8, block_k=16, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), dense_attention(q, k, v), atol=2e-5
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grads(causal):
    rng = np.random.RandomState(2)
    q, k, v = (rng.randn(1, 16, 2, 8).astype(np.float32) for _ in range(3))

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=causal, block_q=8, block_k=8, interpret=True)))

    def loss_dense(q, k, v):
        scale = q.shape[-1] ** -0.5
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        if causal:
            sq = s.shape[2]
            m = jnp.arange(sq)[:, None] >= jnp.arange(sq)[None, :]
            s = jnp.where(m[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.sin(jnp.einsum("bhqk,bkhd->bqhd", p, v)))

    args = tuple(jnp.asarray(x) for x in (q, k, v))
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(*args)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(*args)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5,
                                   err_msg=f"d{name}")


def test_fused_layer_norm_matches_reference():
    rng = np.random.RandomState(3)
    x = rng.randn(6, 5, 32).astype(np.float32) * 3 + 1
    scale = rng.randn(5 * 32).astype(np.float32)
    bias = rng.randn(5 * 32).astype(np.float32)
    y, mean, var = fused_layer_norm(
        jnp.asarray(x), jnp.asarray(scale), jnp.asarray(bias),
        begin_norm_axis=1, interpret=True)
    x2 = x.reshape(6, -1).astype(np.float64)
    mu = x2.mean(1, keepdims=True)
    vr = x2.var(1, keepdims=True)
    ref = ((x2 - mu) / np.sqrt(vr + 1e-5) * scale + bias).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(y), ref, atol=2e-5)
    np.testing.assert_allclose(np.asarray(mean), mu[:, 0], atol=1e-5)
    np.testing.assert_allclose(np.asarray(var), vr[:, 0], atol=2e-4)


def test_fused_layer_norm_grads():
    rng = np.random.RandomState(4)
    x = rng.randn(8, 16).astype(np.float32)
    scale = rng.randn(16).astype(np.float32)
    bias = rng.randn(16).astype(np.float32)

    def loss_fused(x, s, b):
        y, _, _ = fused_layer_norm(x, s, b, interpret=True)
        return jnp.sum(jnp.sin(y))

    def loss_ref(x, s, b):
        mu = x.mean(1, keepdims=True)
        vr = ((x - mu) ** 2).mean(1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(vr + 1e-5) * s + b
        return jnp.sum(jnp.sin(y))

    args = tuple(jnp.asarray(a) for a in (x, scale, bias))
    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(*args)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(*args)
    for a, b, name in zip(gf, gr, ["dx", "dscale", "dbias"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5,
                                   err_msg=name)


def test_layer_norm_op_uses_pallas_when_forced():
    """Program-level: forcing the flag routes layer_norm through the fused
    kernel (interpret mode on CPU) and still trains."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.flags import set_flags
    from paddle_tpu.fluid.framework import Program, program_guard

    # flash_min_seq 0: the routing threshold (flags.py) would otherwise
    # send these tiny sequences to the XLA path and stop exercising the
    # kernel this test exists for
    from paddle_tpu.fluid.flags import get_flag

    prev_min_seq = get_flag("flash_min_seq")
    set_flags({"use_pallas_kernels": True, "flash_min_seq": 0})
    try:
        main, startup, scope = Program(), Program(), fluid.Scope()
        with fluid.scope_guard(scope):
            with program_guard(main, startup):
                x = layers.data(name="x", shape=[16], dtype="float32")
                y = layers.data(name="y", shape=[16], dtype="float32")
                h = layers.layer_norm(x)
                # ring_attention falls through to the pallas flash kernel
                q = layers.data(name="q", shape=[8, 2, 4], dtype="float32")
                att = layers.ring_attention(q, q, q, causal=True)
                cost = layers.elementwise_add(
                    layers.mean(layers.square_error_cost(input=h, label=y)),
                    layers.scale(layers.mean(att), scale=0.0))
                fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)
            exe = fluid.Executor()
            exe.run(startup)
            rng = np.random.RandomState(0)
            xs = rng.randn(4, 16).astype(np.float32)
            ys = np.tanh(xs)
            l0 = exe.run(main, feed={"x": xs, "y": ys,
                                     "q": rng.randn(2, 8, 2, 4).astype(np.float32)},
                         fetch_list=[cost])[0].item()
            assert np.isfinite(l0)
    finally:
        set_flags({"use_pallas_kernels": "auto",
                   "flash_min_seq": prev_min_seq})


def test_flash_attention_non_multiple_of_8_lengths():
    # padding path: sequence lengths not divisible by the block or by 8
    rng = np.random.RandomState(5)
    q = rng.randn(1, 13, 2, 8).astype(np.float32)
    k = rng.randn(1, 21, 2, 8).astype(np.float32)
    v = rng.randn(1, 21, 2, 8).astype(np.float32)
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=False, block_q=8, block_k=8, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), dense_attention(q, k, v, False), atol=2e-5)
    # causal with equal ragged lengths
    out = flash_attention(jnp.asarray(q), jnp.asarray(q), jnp.asarray(q),
                          causal=True, block_q=8, block_k=8, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), dense_attention(q, q, q, True), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grads_multiblock_and_padding(causal):
    # backward kernels must handle several blocks per grid row AND the
    # zero-padded tail (13/21 are not multiples of 8)
    rng = np.random.RandomState(7)
    sq = sk = 21 if causal else 13
    q = rng.randn(1, sq, 2, 8).astype(np.float32)
    k = rng.randn(1, sk if causal else 21, 2, 8).astype(np.float32)
    v = rng.randn(1, sk if causal else 21, 2, 8).astype(np.float32)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=causal, block_q=8, block_k=8, interpret=True)))

    def loss_dense(q, k, v):
        scale = q.shape[-1] ** -0.5
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        if causal:
            m = (jnp.arange(s.shape[2])[:, None]
                 >= jnp.arange(s.shape[3])[None, :])
            s = jnp.where(m[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.sin(jnp.einsum("bhqk,bkhd->bqhd", p, v)))

    args = tuple(jnp.asarray(x) for x in (q, k, v))
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(*args)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(*args)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   err_msg=f"d{name}")


def test_flash_attention_reachable_under_parallel_executor():
    """SPMD wiring: with use_pallas_kernels forced and a dp mesh (no seq
    axis), the ring_attention op routes through the pallas kernel inside
    shard_map — and matches the XLA path run on the same params/feed."""
    import jax
    from jax.sharding import Mesh

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.flags import set_flags
    from paddle_tpu.fluid.framework import Program, program_guard

    main, startup = Program(), Program()
    main.random_seed = startup.random_seed = 5
    with program_guard(main, startup):
        q = layers.data(name="q", shape=[16, 2, 8], dtype="float32")
        att = layers.ring_attention(q, q, q, causal=True, batch_axis="dp")
        out = layers.mean(att)
    rng = np.random.RandomState(3)
    feed = {"q": rng.randn(4, 16, 2, 8).astype(np.float32)}
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        mesh = Mesh(np.asarray(jax.devices()[:4]), ("dp",))
        pe = fluid.ParallelExecutor(main_program=main, mesh=mesh)
        (xla_att,) = pe.run(feed=feed, fetch_list=[att])
        from paddle_tpu.fluid.flags import get_flag

        prev_min_seq = get_flag("flash_min_seq")
        set_flags({"use_pallas_kernels": True,
                   "flash_min_seq": 0})  # interpret auto on CPU
        try:
            pe2 = fluid.ParallelExecutor(main_program=main, mesh=mesh)
            (pl_att,) = pe2.run(feed=feed, fetch_list=[att])
        finally:
            set_flags({"use_pallas_kernels": "auto",
                       "flash_min_seq": prev_min_seq})
    np.testing.assert_allclose(np.asarray(pl_att), np.asarray(xla_att),
                               atol=3e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_bf16_fwd_and_grads(causal):
    """bf16-native kernel path (r3 perf pass: operands stay bf16 into the
    MXU dots, f32 accumulation): matches the dense f32 reference to bf16
    tolerance, forward and backward."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(11)
    b, s, h, d = 2, 24, 2, 16
    qf = rng.randn(b, s, h, d).astype(np.float32)
    kf = rng.randn(b, s, h, d).astype(np.float32)
    vf = rng.randn(b, s, h, d).astype(np.float32)
    q, k, v = (jnp.asarray(x, jnp.bfloat16) for x in (qf, kf, vf))

    out = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8,
                          interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = dense_attention(qf, kf, vf, causal)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=0.1, atol=0.05)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=8,
                                       block_k=8, interpret=True)
                       .astype(jnp.float32) ** 2)

    gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert gq.dtype == gk.dtype == gv.dtype == jnp.bfloat16

    def dense_loss(q, k, v):
        scale = q.shape[-1] ** -0.5
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        if causal:
            sq = sc.shape[2]
            m = jnp.arange(sq)[:, None] >= jnp.arange(sq)[None, :]
            sc = jnp.where(m[None, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bkhd->bqhd", pr, v) ** 2)

    rq, rk, rv = jax.grad(dense_loss, argnums=(0, 1, 2))(
        jnp.asarray(qf), jnp.asarray(kf), jnp.asarray(vf))
    for g, r in ((gq, rq), (gk, rk), (gv, rv)):
        g32, r32 = np.asarray(g, np.float32), np.asarray(r)
        denom = np.abs(r32).max() + 1e-6
        assert np.abs(g32 - r32).max() / denom < 0.15


# --- fused conv + folded-bn + relu (round-4 review item 6: the ResNet hot
# chain as a blocked Pallas GEMM; reference conv_mkldnn_op.cc axis) --------


def _conv_ref(x, w, scale, shift, stride, padding, relu):
    out = jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32),
        window_strides=(stride, stride),
        padding=[(padding, padding), (padding, padding)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    f = w.shape[0]
    out = out * scale.reshape(1, f, 1, 1) + shift.reshape(1, f, 1, 1)
    return jnp.maximum(out, 0.0) if relu else out


@pytest.mark.parametrize("shape,f,k,stride,padding,relu", [
    ((2, 8, 10, 10), 16, 3, 1, 1, True),     # resnet-style 3x3
    ((2, 8, 9, 9), 12, 3, 2, 0, True),       # stride-2, odd spatial, odd F
    ((2, 16, 7, 7), 32, 1, 1, 0, False),     # 1x1 projection, no relu
    ((1, 3, 12, 12), 7, 5, 2, 2, True),      # 5x5, prime F (pad path)
])
def test_fused_conv_bn_relu_forward(shape, f, k, stride, padding, relu):
    from paddle_tpu.fluid.ops.pallas_kernels import fused_conv_bn_relu

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    w = jnp.asarray(rng.randn(f, shape[1], k, k).astype(np.float32) * 0.1)
    scale = jnp.asarray(rng.rand(f).astype(np.float32) + 0.5)
    shift = jnp.asarray(rng.randn(f).astype(np.float32) * 0.1)
    got = fused_conv_bn_relu(x, w, scale, shift, stride=stride,
                             padding=padding, relu=relu, block_m=32,
                             block_f=128, interpret=True)
    ref = _conv_ref(x, w, scale, shift, stride, padding, relu)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_fused_conv_bn_relu_grads():
    from paddle_tpu.fluid.ops.pallas_kernels import fused_conv_bn_relu

    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 4, 8, 8).astype(np.float32))
    w = jnp.asarray(rng.randn(6, 4, 3, 3).astype(np.float32) * 0.2)
    scale = jnp.asarray(rng.rand(6).astype(np.float32) + 0.5)
    shift = jnp.asarray(rng.randn(6).astype(np.float32) * 0.1)

    def loss(x, w, s, b):
        return jnp.sum(fused_conv_bn_relu(
            x, w, s, b, stride=1, padding=1, relu=True, block_m=32,
            interpret=True) ** 2)

    def ref_loss(x, w, s, b):
        return jnp.sum(_conv_ref(x, w, s, b, 1, 1, True) ** 2)

    got = jax.grad(loss, argnums=(0, 1, 2, 3))(x, w, scale, shift)
    ref = jax.grad(ref_loss, argnums=(0, 1, 2, 3))(x, w, scale, shift)
    for g, r in zip(got, ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-3, atol=1e-3)


def test_fused_conv_bn_relu_bf16():
    from paddle_tpu.fluid.ops.pallas_kernels import fused_conv_bn_relu

    rng = np.random.RandomState(2)
    xf = rng.randn(2, 4, 8, 8).astype(np.float32)
    wf = (rng.randn(8, 4, 3, 3) * 0.2).astype(np.float32)
    scale = jnp.asarray(rng.rand(8).astype(np.float32) + 0.5)
    shift = jnp.asarray(rng.randn(8).astype(np.float32) * 0.1)
    x, w = jnp.asarray(xf, jnp.bfloat16), jnp.asarray(wf, jnp.bfloat16)
    out = fused_conv_bn_relu(x, w, scale, shift, stride=1, padding=1,
                             block_m=32, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = _conv_ref(jnp.asarray(xf), jnp.asarray(wf), scale, shift, 1, 1,
                    True)
    denom = np.abs(np.asarray(ref)).max() + 1e-6
    assert np.abs(np.asarray(out, np.float32) - np.asarray(ref)).max() \
        / denom < 0.1


def test_fold_bn_matches_batch_norm_inference():
    """fold_bn(gamma, beta, mean, var) + fused kernel == conv followed by
    inference batch_norm + relu."""
    from paddle_tpu.fluid.ops.pallas_kernels import (fold_bn,
                                                     fused_conv_bn_relu)

    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 4, 8, 8).astype(np.float32))
    w = jnp.asarray(rng.randn(6, 4, 3, 3).astype(np.float32) * 0.2)
    gamma = jnp.asarray(rng.rand(6).astype(np.float32) + 0.5)
    beta = jnp.asarray(rng.randn(6).astype(np.float32))
    mean = jnp.asarray(rng.randn(6).astype(np.float32) * 0.1)
    var = jnp.asarray(rng.rand(6).astype(np.float32) + 0.2)
    eps = 1e-5
    scale, shift = fold_bn(gamma, beta, mean, var, eps)
    got = fused_conv_bn_relu(x, w, scale, shift, stride=1, padding=1,
                             block_m=32, interpret=True)
    conv = jax.lax.conv_general_dilated(
        x, w, (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    bn = (conv - mean.reshape(1, 6, 1, 1)) * jax.lax.rsqrt(
        var.reshape(1, 6, 1, 1) + eps) * gamma.reshape(1, 6, 1, 1) \
        + beta.reshape(1, 6, 1, 1)
    ref = jnp.maximum(bn, 0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_conv2d_bn_relu_op_uses_pallas_when_forced():
    """Program-level: the conv2d_bn_relu layer routes through the fused
    kernel under the flag and still trains (fwd+bwd through the op)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.flags import set_flags
    from paddle_tpu.fluid.framework import Program, program_guard

    set_flags({"use_pallas_kernels": True})
    try:
        main, startup, scope = Program(), Program(), fluid.Scope()
        with fluid.scope_guard(scope):
            with program_guard(main, startup):
                x = layers.data(name="x", shape=[3, 8, 8], dtype="float32")
                y = layers.data(name="y", shape=[1], dtype="float32")
                h = layers.conv2d_bn_relu(x, num_filters=4, filter_size=3,
                                          stride=1, padding=1)
                pool = layers.pool2d(h, pool_size=8, pool_type="avg")
                pred = layers.fc(input=pool, size=1)
                cost = layers.mean(
                    layers.square_error_cost(input=pred, label=y))
                fluid.optimizer.SGD(learning_rate=0.01).minimize(cost)
            exe = fluid.Executor()
            exe.run(startup)
            rng = np.random.RandomState(4)
            feed = {"x": rng.randn(4, 3, 8, 8).astype(np.float32),
                    "y": rng.randn(4, 1).astype(np.float32)}
            l0 = exe.run(main, feed=feed, fetch_list=[cost])[0].item()
            l1 = exe.run(main, feed=feed, fetch_list=[cost])[0].item()
            assert np.isfinite(l0) and np.isfinite(l1) and l1 < l0
    finally:
        set_flags({"use_pallas_kernels": "auto"})


# --- the paged kernel's work follows kv_lens and q_lens (ISSUE 31) --------
# The kernel neither fetches nor folds a table column past a slot's last
# live page, and multiplies no query lane past q_len: whatever lies there
# cannot reach the output. A kernel that multiplied it and masked the
# product (0 x NaN) would fail every case.

_PAGED_KV = (0, 1, 13, 16, 32)      # dead, one key, mid-page, boundary, full
_PAGED_Q = (0, 1, 4, 16)            # dead, a decoding lane, a block, all of C


@pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32pool", "bf16pool"])
@pytest.mark.parametrize("heads", [(16, 16), (32, 4)],
                         ids=["h16_16", "h32_4"])
@pytest.mark.parametrize("block_length", [1, 4])
def test_paged_kernel_touches_nothing_past_kv_len_or_q_len(
        block_length, heads, pool_dtype):
    """One slot for each (kv_len, q_len) of 5 x 4 at C 16, page 8 and a
    table of 4 columns. Every table column past a slot's last live page
    names a page of NaN (the garbage page among them) and every q lane
    past q_len is NaN: the output is finite, equals the reference on
    clean data, and is exactly zero on dead lanes and dead slots."""
    from paddle_tpu.fluid.ops.pallas_kernels.paged_attention import (
        _paged_attention_pallas, paged_attention_reference)

    hq, hkv = heads
    c, d, ps, w = 16, 8, 8, 4
    kv_lens = np.repeat(_PAGED_KV, len(_PAGED_Q)).astype(np.int32)
    q_lens = np.minimum(np.tile(_PAGED_Q, len(_PAGED_KV)),
                        kv_lens).astype(np.int32)
    b = len(kv_lens)
    rng = np.random.RandomState(31)
    # page 0 is the garbage page, page 1 a page some slot reserved and
    # nobody wrote; live pages are each slot's own
    tables = np.ones((b, w), np.int32)
    tables[:, w // 2:] = 0
    for i, n in enumerate(kv_lens):
        live = -(-int(n) // ps)
        tables[i, :live] = 2 + i * w + np.arange(live)
    pages = 2 + b * w
    k = rng.randn(pages, ps, hkv, d).astype(np.float32)
    v = rng.randn(pages, ps, hkv, d).astype(np.float32)
    q = rng.randn(b, c, hq, d).astype(np.float32)
    dead_lane = np.arange(c)[None, :] >= q_lens[:, None]        # [B, C]

    def call(fn, q, k, v, **kw):
        return np.asarray(fn(
            jnp.asarray(q), jnp.asarray(k, pool_dtype),
            jnp.asarray(v, pool_dtype), jnp.asarray(tables),
            jnp.asarray(kv_lens), q_lens=jnp.asarray(q_lens),
            block_length=block_length, **kw))

    want = call(paged_attention_reference, q, k, v)
    k[:2] = v[:2] = np.nan
    q[dead_lane] = np.nan
    got = call(_paged_attention_pallas, q, k, v, interpret=True)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(got[dead_lane], 0.0)
    assert dead_lane[q_lens == 0].all() and (kv_lens == 0).sum() == 4
    # every live lane of every live slot saw a key
    assert np.abs(got[~dead_lane]).max(axis=(-1, -2)).min() > 0


# --- the chunk fold on the MXU (ISSUE 35) ---------------------------------
# A slot that carries many lanes has them folded into a live page by two
# matrix products a kv head; one with few walks them one by one. Which,
# `folds_by_dot` says, from C, rep, the pools' dtype and q_len. Both folds
# are one online softmax: against the reference and against each other at
# the tolerances the lane loop's cases hold.

def _dot_case(geometry, pool_dtype, q_dtype, mode):
    """One call of eight slots in the kernel's layouts: q_lens on both
    sides of the predicate's lane count (a full chunk, partial ones,
    decoding slots, a dead one), kv_lens mid-page and on a boundary.
    Returns what both implementations take, clean and poisoned."""
    from paddle_tpu.fluid.ops.pallas_kernels import paged_attention as pa

    c, hq, hkv = geometry
    d, ps = 8, 8
    n = pa.DOT_MIN_LANES
    block = 4 if mode == "block" else 1
    q_lens = np.array([c, n - 1, n, n + 1, 1, 1, 0, c // 2 + 3], np.int32)
    kv_lens = np.array([c + 21, 40, n, 3 * n + 5, 1, 77, 0, c // 2 + 3],
                       np.int32)
    if block > 1:           # chunks of whole blocks, a pass of one block
        q_lens = np.array([c, 4, 8, 12, 4, 4, 0, c // 2 + 4], np.int32)
        kv_lens = np.array([c + 20, 40, 8, 36, 4, 76, 0, c // 2 + 4],
                           np.int32)
    assert (q_lens <= np.minimum(c, kv_lens)).all()
    b = len(q_lens)
    kw = {"block_length": block}
    window = None
    if mode == "window":
        # a window shorter than the longest sequences: their tables start
        # past the sequence's first pages
        window = 2 * ps + 3
        kw = {"window": window}
    first = (np.maximum(kv_lens - q_lens - (window or 10 ** 6) + 1, 0)
             // ps)
    held = -(-kv_lens // ps) - first
    w = int(held.max()) + 2
    rng = np.random.RandomState(35)
    # pages 0 and 1 are nobody's: every column past a slot's last page
    tables = np.tile(np.array([1, 0], np.int32), (b, w))[:, :w]
    for i in range(b):
        tables[i, :held[i]] = 2 + i * w + np.arange(held[i])
    pages = 2 + b * w
    k = rng.randn(pages, ps, hkv, d).astype(np.float32)
    v = rng.randn(pages, ps, hkv, d).astype(np.float32)
    q = rng.randn(b, c, hq, d).astype(np.float32)
    if window is not None:
        kw["table_starts"] = jnp.asarray(first.astype(np.int32))
    dead_lane = np.arange(c)[None, :] >= q_lens[:, None]

    def call(fn, q, k, v, **more):
        return np.asarray(fn(
            jnp.asarray(q, q_dtype), jnp.asarray(k, pool_dtype),
            jnp.asarray(v, pool_dtype), jnp.asarray(tables),
            jnp.asarray(kv_lens), q_lens=jnp.asarray(q_lens), **kw,
            **more).astype(jnp.float32))

    kn, vn, qn = k.copy(), v.copy(), q.copy()
    kn[:2] = vn[:2] = np.nan
    qn[dead_lane] = np.nan
    return call, (q, k, v), (qn, kn, vn), dead_lane, q_lens


_DOT_GEOMETRIES = {"rep8": (16, 32, 4), "rep1": (32, 2, 2),
                   "rep8_c64": (64, 32, 4)}


@pytest.mark.parametrize("mode", ["causal", "window", "block"])
@pytest.mark.parametrize("dtypes", [
    ("rep8", jnp.bfloat16, jnp.float32), ("rep8", jnp.bfloat16, jnp.bfloat16),
    ("rep1", jnp.bfloat16, jnp.float32), ("rep1", jnp.bfloat16, jnp.bfloat16),
    ("rep8_c64", jnp.float32, jnp.float32)],
    ids=["rep8_bf16pool_f32q", "rep8_bf16", "rep1_bf16pool_f32q",
         "rep1_bf16", "rep8_f32"])
def test_paged_kernel_dot_fold_is_the_lane_loops_softmax(dtypes, mode,
                                                          monkeypatch):
    """The dot fold against `paged_attention_reference` and against the
    lane loop (the same call with the dot fold not traced) on a mixed
    call: a full chunk, partial chunks on each side of the predicate's
    lane count, decoding slots, a dead slot; causal, under a window whose
    tables start past a window's length, and under the block mask. Dead
    lanes and dead slots are exact zeros, and NaN planted in every column
    past a slot's last page and every lane past q_len does not reach the
    output."""
    from paddle_tpu.fluid.ops.pallas_kernels import paged_attention as pa

    name, pool_dtype, q_dtype = dtypes
    c, hq, hkv = _DOT_GEOMETRIES[name]
    call, clean, poisoned, dead_lane, q_lens = _dot_case(
        (c, hq, hkv), pool_dtype, q_dtype, mode)
    # the geometry qualifies, and the call holds slots on both sides
    assert pa.folds_by_dot(c, hq // hkv, pool_dtype)
    takes = np.asarray(pa.folds_by_dot(c, hq // hkv, pool_dtype, q_lens))
    # (a block model's passes are whole blocks, all of them the dot fold's)
    assert takes.any() and (mode == "block"
                            or (~takes & (q_lens > 0)).any())
    want = call(pa.paged_attention_reference, *clean)
    got = call(pa._paged_attention_pallas, *poisoned, interpret=True)
    monkeypatch.setattr(pa, "DOT_MIN_ROWS", {
        key: 10 ** 9 for key in pa.DOT_MIN_ROWS})
    assert not pa.folds_by_dot(c, hq // hkv, pool_dtype)
    lanes = call(pa._paged_attention_pallas, *poisoned, interpret=True)
    assert np.isfinite(got).all() and np.isfinite(lanes).all()
    np.testing.assert_array_equal(got[dead_lane], 0.0)
    assert np.abs(got[~dead_lane]).max(axis=(-1, -2)).min() > 0
    if q_dtype == jnp.float32:
        # the tolerances of the lane loop's cases above
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(got, lanes, rtol=2e-5, atol=2e-6)
    else:
        # a bfloat16 output: one step of its 8 bits (a float32 sum in
        # another order can land on the other side of a rounding)
        np.testing.assert_allclose(got, want, rtol=2.0 ** -7, atol=1e-6)
        np.testing.assert_allclose(got, lanes, rtol=2.0 ** -7, atol=1e-6)
    # a slot the predicate leaves to the lane loop is the lane loop's to
    # the bit, in a program that has both folds
    np.testing.assert_array_equal(got[~takes], lanes[~takes])


# the primitives of one kernel call, counted on PR 34's tree (d2744e8):
# name -> ((C, Hq, Hkv, dtype, block_length), counts)
_LANE_LOOP_PROGRAMS = {
    "chat_c16": ((16, 2, 2, jnp.float32, 1), {
        "add": 7, "broadcast_in_dim": 10, "cond": 3,
        "convert_element_type": 6, "div": 2, "eq": 2, "exp": 2, "gather": 1,
        "get": 10, "iota": 2, "jit": 2, "le": 1, "lt": 3, "max": 3,
        "min": 1, "mul": 8, "pallas_call": 1, "program_id": 2,
        "reduce_max": 1, "reduce_sum": 3, "reshape": 1, "select_n": 2,
        "sub": 4, "swap": 7, "while": 1}),
    "decode_c1": ((1, 8, 1, jnp.bfloat16, 1), {
        "add": 7, "broadcast_in_dim": 12, "cond": 3,
        "convert_element_type": 10, "div": 2, "eq": 2, "exp": 2,
        "gather": 1, "get": 10, "iota": 2, "jit": 2, "le": 1, "lt": 3,
        "max": 3, "min": 1, "mul": 8, "pallas_call": 1, "program_id": 2,
        "reduce_max": 1, "reduce_sum": 3, "reshape": 3, "select_n": 2,
        "sub": 4, "swap": 7, "while": 1})}


@pytest.mark.parametrize("name", sorted(_LANE_LOOP_PROGRAMS))
def test_a_geometry_the_predicate_rejects_traces_the_program_it_did(name):
    """`folds_by_dot`'s static half: the dense family's chunk (C 16, one
    query head a kv head, float32) and every one-token program trace the
    lane loop alone, primitive for primitive what PR 34's tree traced;
    the same heads at a chunk the predicate accepts trace a second
    output and the products beside it."""
    from test_afmoe_serving import _primitives

    from paddle_tpu.fluid.ops.pallas_kernels import paged_attention as pa

    (c, hq, hkv, dtype, block), want = _LANE_LOOP_PROGRAMS[name]
    assert not pa.folds_by_dot(c, hq // hkv, dtype)

    def count(c):
        q = jnp.zeros((2, c, hq, 8), dtype)
        pages = jnp.zeros((16, 4, hkv, 8), dtype)
        return dict(_primitives(jax.make_jaxpr(
            lambda *a: pa._paged_attention_pallas(
                *a[:5], q_lens=a[5], interpret=True, block_length=block))(
            q, pages, pages, jnp.zeros((2, 3), jnp.int32),
            jnp.array([5, 9]), jnp.array([1, min(c, 4)])).jaxpr))

    assert count(c) == want
    wide = pa.DOT_MIN_ROWS[jnp.dtype(dtype).name] * hkv // hq
    assert pa.folds_by_dot(wide, hq // hkv, dtype)
    got = count(wide)
    assert got["dot_general"] == 2 * hkv and "dot_general" not in want


# --- compiled for a TPU v5e without one ----------------------------------
# The cases above run the kernels INTERPRETED; Mosaic, the compiler the
# chip uses, has never seen them. libtpu can describe a v5e topology to a
# CPU-only process, and lowering against its devices runs the real
# XLA-TPU and Mosaic compilers. A compile that passes says nothing about
# numerics or speed (chip_smoke.py checks those on the chip); a compile
# that fails is a fact, and this is the only guard a CPU lane can give
# against a Mosaic refusal.

@pytest.fixture(scope="module")
def v5e():
    """Devices of an AOT v5e:2x2 topology (no chip attached)."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — the one named skip condition
        pytest.skip(f"libtpu cannot describe a v5e:2x2 topology on this "
                    f"machine: {type(e).__name__}: {e}")
    return topo.devices


def _on(dev_or_sharding, shape, dtype):
    from jax.sharding import Sharding, SingleDeviceSharding

    sh = (dev_or_sharding if isinstance(dev_or_sharding, Sharding)
          else SingleDeviceSharding(dev_or_sharding))
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)


@pytest.mark.parametrize("chunk,hq,hkv,dtype,block_length,width,dot", [
    (1, 16, 16, jnp.float32, 1, 64, False),
    (16, 16, 16, jnp.float32, 1, 64, False),
    (4, 32, 4, jnp.bfloat16, 4, 64, True),
    (16, 32, 4, jnp.bfloat16, 4, 64, True),
    (1, 32, 4, jnp.bfloat16, 1, 576, False),
    (64, 32, 4, jnp.bfloat16, 1, 576, True)],
    ids=["chat_c1", "chat_c16", "block_c4", "block_c16", "trinity_c1",
         "trinity_c64"])
def test_paged_kernel_compiles_for_v5e(v5e, chunk, hq, hkv, dtype,
                                       block_length, width, dot):
    """The served attention geometries, heads of 128 and page 16. The
    dense family's: 16 query / 16 kv heads, float32 q and pools, causal
    — single-token decode (C=1) and a prefill chunk (C=16). The block
    model's: 32 query / 4 kv heads, bfloat16 q and pools, blocks of 4 —
    a block pass (C=4) and a prefill chunk (C=16). The full layer of
    ``trinity_mini_longmix`` (ISSUE 35): the same heads, causal, a table
    of 576 columns, a decode token and a prefill chunk of 64 lanes.
    ``dot``: whether the program holds the dot fold beside the lane loop
    (``folds_by_dot``'s static half), and so a second output."""
    from paddle_tpu.fluid.ops.pallas_kernels.paged_attention import (
        _paged_attention_pallas, folds_by_dot)

    assert folds_by_dot(chunk, hq // hkv, dtype) == dot
    b, d, ps, pages = 16, 128, 16, 128
    d0 = v5e[0]
    jax.jit(lambda q, k, v, t, n, m: _paged_attention_pallas(
        q, k, v, t, n, q_lens=m, block_length=block_length)).lower(
        _on(d0, (b, chunk, hq, d), dtype),
        _on(d0, (pages, ps, hkv, d), dtype),
        _on(d0, (pages, ps, hkv, d), dtype),
        _on(d0, (b, width), jnp.int32), _on(d0, (b,), jnp.int32),
        _on(d0, (b,), jnp.int32)).compile()


@pytest.mark.parametrize("window_cols,chunk", [(133, 1), (133, 64)],
                         ids=["window_c1", "window_c64"])
def test_windowed_paged_kernel_compiles_for_v5e(v5e, window_cols, chunk):
    """The window layers' geometry of ``trinity_mini_longmix`` (ISSUE 34):
    32 query / 4 kv heads of 128, bfloat16, window 2048 over a table of
    133 columns that starts at ``table_starts``, a decode token and a
    prefill chunk of 64 lanes (whose program holds the dot fold,
    ISSUE 35, beside the lane loop the decode token's is left with)."""
    from paddle_tpu.fluid.ops.pallas_kernels.paged_attention import (
        _paged_attention_pallas, folds_by_dot)

    assert folds_by_dot(chunk, 32 // 4, jnp.bfloat16) == (chunk == 64)
    b, d, ps, pages = 16, 128, 16, 256
    d0 = v5e[0]
    text = jax.jit(lambda q, k, v, t, n, m, s: _paged_attention_pallas(
        q, k, v, t, n, q_lens=m, window=2048, table_starts=s)).lower(
        _on(d0, (b, chunk, 32, d), jnp.bfloat16),
        _on(d0, (pages, ps, 4, d), jnp.bfloat16),
        _on(d0, (pages, ps, 4, d), jnp.bfloat16),
        _on(d0, (b, window_cols), jnp.int32), _on(d0, (b,), jnp.int32),
        _on(d0, (b,), jnp.int32), _on(d0, (b,), jnp.int32)
    ).compile().as_text()
    assert "paged_attention" in text


@pytest.mark.parametrize("chunk", [1, 32], ids=["kimi_c1", "kimi_c32"])
def test_latent_paged_kernel_compiles_for_v5e(v5e, chunk):
    """The latent form at ``kimi_linear_longgen``'s geometry: 128 slots of
    32 heads, entries of 640 bfloat16 (512 of them the value), pages of
    16, a table of 1024 columns folded 8 pages a grid step, and the pool
    of 131073 pages (2.7 GB) handed to the kernel where it lies: no
    temporary as large as the pool."""
    from paddle_tpu.fluid.ops.pallas_kernels.paged_attention import (
        _latent_call, latent_pages_per_step)

    assert latent_pages_per_step(16, 1024) == 8
    b, h, d, ps, pages, w = 128, 32, 640, 16, 131073, 1024
    d0 = v5e[0]
    compiled = jax.jit(lambda q, pool, t, n, m: _latent_call(
        q, pool, t, n, m, scale=192 ** -0.5, value_dim=512,
        interpret=False)).lower(
        _on(d0, (b, chunk, h, d), jnp.bfloat16),
        _on(d0, (pages, ps, d), jnp.bfloat16),
        _on(d0, (b, w), jnp.int32), _on(d0, (b,), jnp.int32),
        _on(d0, (b,), jnp.int32)).compile()
    assert "paged_latent_attention" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < pages * ps * d


@pytest.mark.parametrize("rows,f", [(512, 768), (2048, 768), (128, 1024),
                                    (8192, 1024)],
                         ids=["block_c4", "block_c16", "afmoe_c1",
                              "afmoe_c64"])
def test_moe_gmm_compiles_for_v5e(v5e, rows, f):
    """The served expert geometries (128 experts of 2048 x 768 and, ISSUE
    34, of 2048 x 1024, bfloat16, top-8) at the step shapes of 16 slots:
    the gated gate-and-up product written in bfloat16, then ``down`` in
    float32, whole 3 and 4 MB panels."""
    from paddle_tpu.fluid.ops.pallas_kernels.moe_gmm import moe_gmm

    e, d = 128, 2048
    d0 = v5e[0]

    def experts(xs, gate, up, down, counts):
        act = moe_gmm(xs, up, counts, gate=gate)
        return moe_gmm(act, down, counts)

    text = jax.jit(experts).lower(
        _on(d0, (rows, d), jnp.bfloat16), _on(d0, (e, d, f), jnp.bfloat16),
        _on(d0, (e, d, f), jnp.bfloat16), _on(d0, (e, f, d), jnp.bfloat16),
        _on(d0, (e,), jnp.int32)).compile().as_text()
    # the name a device trace finds the kernel by (moe_experts_roofline)
    assert text.count("moe_gmm") >= 2 and "ragged-dot" not in text


def test_flash_attention_fwd_bwd_compiles_for_v5e(v5e):
    from paddle_tpu.fluid.ops.pallas_kernels import flash_attention

    x = _on(v5e[0], (1, 4096, 8, 64), jnp.bfloat16)
    jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True)
                                .astype(jnp.float32)),
        argnums=(0, 1, 2))).lower(x, x, x).compile()


def test_fused_layer_norm_fwd_bwd_compiles_for_v5e(v5e):
    """8192 rows = 64 row blocks: the shape at which the 1-D stats
    outputs were refused (Mosaic T(128) vs XLA T(1024) for f32[8192])."""
    from paddle_tpu.fluid.ops.pallas_kernels import fused_layer_norm

    n, f = 8192, 512
    d0 = v5e[0]
    jax.jit(jax.grad(
        lambda x, s, b: jnp.sum(fused_layer_norm(x, s, b)[0]),
        argnums=(0, 1, 2))).lower(
        _on(d0, (n, f), jnp.float32), _on(d0, (f,), jnp.float32),
        _on(d0, (f,), jnp.float32)).compile()


def test_tp4_decode_step_compiles_for_v5e(v5e):
    """The step a ``load_decoder(mesh_axes="tp=4")`` engine compiles: KV
    pool sharded over kv heads, params by the decoder rules, attention
    the reference BY NAME (the engine's choice under a mesh — a Mosaic
    kernel handed to GSPMD is refused: 'cannot be automatically
    partitioned')."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.mesh import decoder_rules
    from paddle_tpu.mesh.spec import _tree_map_named
    from paddle_tpu.serving.decode import (DecoderSpec,
                                           decoder_step_chunked,
                                           seeded_decoder_arrays)

    spec = DecoderSpec(vocab=512, d_model=2048, n_layers=1, n_heads=16,
                       n_kv_heads=16, seed=0)
    mesh = Mesh(np.asarray(v5e).reshape(4), ("tp",))
    rules = decoder_rules()
    params = _tree_map_named(
        seeded_decoder_arrays(spec),
        lambda name, a: _on(
            NamedSharding(mesh, rules.spec_for(name, a.ndim)),
            a.shape, jnp.float32))
    pool_sh = NamedSharding(mesh, P(None, None, None, "tp", None))
    rep = NamedSharding(mesh, P())
    b, c, w = 1, 16, 16
    pool = _on(pool_sh, (1, 256, 16, 16, 128), jnp.float32)

    def step(params, tokens, positions, q_lens, k_pool, v_pool, tables,
             lens):
        return decoder_step_chunked(params, spec, tokens, positions,
                                    q_lens, k_pool, v_pool, tables, lens,
                                    attention_impl="reference")

    jax.jit(step, donate_argnums=(4, 5),
            out_shardings=(pool_sh, pool_sh, rep)).lower(
        params, _on(rep, (b, c), jnp.int32), _on(rep, (b, c), jnp.int32),
        _on(rep, (b,), jnp.int32), pool, pool,
        _on(rep, (b, w), jnp.int32), _on(rep, (b,), jnp.int32)).compile()
