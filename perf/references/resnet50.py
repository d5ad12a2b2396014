"""Plain reference of the `resnet50` training configuration.

ResNet-50 (He et al., arXiv:1512.03385; the bottleneck layout of the
reference framework's benchmark/fluid/resnet.py): forward pass, mean
softmax cross-entropy, gradients and the Momentum update in straightforward
``jax.numpy``, float32 with every product at ``highest`` precision. It
imports nothing of the program. Each bottleneck is rematerialised in the
backward pass so that batch 128 fits beside nothing else.

Parameters are a flat list in the order the layers run: for each
convolution its filter [out, in, k, k], then its batch norm's scale and
bias; the shortcut's before the block's own three; last the classifier's
weight [in, classes] and bias.

``operand_bits`` is the control: the operands of every convolution and of
the classifier rounded to that many mantissa bits in the forward pass, and
the gradients that the backward pass's convolutions take as operands
rounded to one bit fewer (3 = fp8 with ideal scaling as it is trained in:
e4m3 forward, e5m2 backward), which has to come out as not correct.
"""
import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
STAGES = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
BN_EPS = 1e-5


def conv_plan(cfg):
    """(cin, cout, k, stride, last) of every convolution, in parameter
    order; ``last`` marks the convolution that ends a bottleneck's branch."""
    plan = [(int(cfg["image"][0]), 64, 7, 2, False)]
    cin = 64
    for s, (count, width) in enumerate(zip(STAGES[int(cfg["depth"])],
                                           (64, 128, 256, 512))):
        for b in range(count):
            stride = 2 if (b == 0 and s > 0) else 1
            if cin != width * 4:
                plan.append((cin, width * 4, 1, stride, False))
            plan += [(cin, width, 1, stride, False),
                     (width, width, 3, 1, False),
                     (width, width * 4, 1, 1, True)]
            cin = width * 4
    return plan


def param_shapes(cfg):
    shapes = []
    for cin, cout, k, _s, _last in conv_plan(cfg):
        shapes += [(cout, cin, k, k), (cout,), (cout,)]
    return shapes + [(512 * 4, int(cfg["class_dim"])),
                     (int(cfg["class_dim"]),)]


def init_params(cfg, key):
    """Seeded weights: filters and the classifier normal with std
    sqrt(2/fan_in), batch-norm scale 1 and bias 0, classifier bias 0; the
    batch norm that ends a bottleneck's branch gets the scale
    ``init.last_bn_scale`` (the usual damped start of residual branches).
    Traceable: the benchmark makes them in one jitted call."""
    plan = conv_plan(cfg)
    keys = iter(jax.random.split(key, len(plan) + 1))

    def normal(shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * (2.0 / fan_in) ** 0.5)

    out = []
    damped = float(cfg["init"]["last_bn_scale"])
    for cin, cout, k, _s, last in plan:
        out += [normal((cout, cin, k, k), cin * k * k),
                jnp.full((cout,), damped if last else 1.0, jnp.float32),
                jnp.zeros((cout,), jnp.float32)]
    classes = int(cfg["class_dim"])
    return out + [normal((512 * 4, classes), 512 * 4),
                  jnp.zeros((classes,), jnp.float32)]


def _round_mantissa(x, bits):
    shift = 23 - bits
    i = jax.lax.bitcast_convert_type(x, jnp.uint32)
    i = (i + jnp.uint32(1 << (shift - 1))) & jnp.uint32(
        0xFFFFFFFF ^ ((1 << shift) - 1))
    return jax.lax.bitcast_convert_type(i, jnp.float32)


def _rounded(x, bits):
    """``x`` with its mantissa rounded to ``bits`` bits; the gradient
    passes straight through."""
    if bits is None:
        return x
    return x + jax.lax.stop_gradient(_round_mantissa(x, bits) - x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rounded_gradient(y, bits):
    """``y`` unchanged; the gradient that comes back for it rounded to
    ``bits`` mantissa bits before the backward convolutions take it."""
    return y


def _rounded_gradient_fwd(y, bits):
    return y, None


def _rounded_gradient_bwd(bits, _res, g):
    return (_round_mantissa(g, bits),)


_rounded_gradient.defvjp(_rounded_gradient_fwd, _rounded_gradient_bwd)


def _conv_bn(x, w, scale, bias, stride, relu, bits):
    pad = w.shape[2] // 2
    y = jax.lax.conv_general_dilated(
        _rounded(x, bits), _rounded(w, bits), (stride, stride),
        [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HI)
    if bits is not None:
        y = _rounded_gradient(y, bits - 1)
    mean = jnp.mean(y, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(y - mean), axis=(0, 2, 3), keepdims=True)
    y = ((y - mean) * jax.lax.rsqrt(var + BN_EPS)
         * scale.reshape(1, -1, 1, 1) + bias.reshape(1, -1, 1, 1))
    return jax.nn.relu(y) if relu else y


def _bottleneck(x, ps, stride, has_short, bits):
    short = x
    if has_short:
        short = _conv_bn(x, *ps[:3], stride, False, bits)
        ps = ps[3:]
    y = _conv_bn(x, *ps[0:3], stride, True, bits)
    y = _conv_bn(y, *ps[3:6], 1, True, bits)
    y = _conv_bn(y, *ps[6:9], 1, False, bits)
    return jax.nn.relu(short + y)


def loss(params, img, label, cfg, operand_bits=None):
    """Mean softmax cross-entropy of the batch, batch-norm in training
    mode (batch statistics)."""
    bits = operand_bits
    x = _conv_bn(img, *params[0:3], 2, True, bits)
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
        [(0, 0), (0, 0), (1, 1), (1, 1)])
    at, cin = 3, 64
    for s, (count, width) in enumerate(zip(STAGES[int(cfg["depth"])],
                                           (64, 128, 256, 512))):
        for b in range(count):
            stride = 2 if (b == 0 and s > 0) else 1
            has_short = cin != width * 4
            n = 12 if has_short else 9
            block = jax.checkpoint(functools.partial(
                _bottleneck, stride=stride, has_short=has_short, bits=bits))
            x = block(x, tuple(params[at:at + n]))
            at, cin = at + n, width * 4
    x = jnp.mean(x, axis=(2, 3))
    logits = jnp.matmul(_rounded(x, bits), _rounded(params[at], bits),
                        precision=HI)
    if bits is not None:
        logits = _rounded_gradient(logits, bits - 1)
    logits = logits + params[at + 1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, label.reshape(-1, 1), axis=-1)
    return -jnp.mean(picked)


@functools.partial(jax.jit, static_argnames=("depth", "operand_bits"))
def _step(params, velocity, img, label, lr, mu, depth, operand_bits):
    cfg = {"depth": depth}
    value, grads = jax.value_and_grad(loss)(params, img, label, cfg,
                                            operand_bits)
    velocity = [mu * v + g for v, g in zip(velocity, grads)]
    params = [p - lr * v for p, v in zip(params, velocity)]
    return params, velocity, value, grads


def train_steps(params, img, label, cfg, steps, operand_bits=None):
    """``steps`` Momentum steps from ``params`` on one resident batch.
    Returns each step's loss, the first step's gradient and the parameters
    after the last."""
    velocity = [jnp.zeros_like(p) for p in params]
    lr, mu = float(cfg["learning_rate"]), float(cfg["momentum"])
    losses, first_grad = [], None
    for i in range(steps):
        params, velocity, value, grads = _step(
            list(params), velocity, img, label, lr, mu,
            int(cfg["depth"]), operand_bits)
        losses.append(value)
        if i == 0:
            first_grad = grads
    return losses, first_grad, params
