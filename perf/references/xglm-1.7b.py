"""Plain reference of the `xglm-1.7b` configuration as it is run.

The full forward pass over one whole sequence in straightforward
``jax.numpy``: no cache, no batching, no kernel. It imports nothing of the
program. Layer by layer, one jitted block each, so that it fits beside the
weights.

It computes in the precision the configuration states
(``precision.reference_matmul``): float32 everywhere, attention's products at
``highest``, and the operands of the weight matmuls (q, k, v, out, fc1, fc2,
the output head) rounded to bfloat16 and accumulated in float32, which is
what a TPU's default precision does to float32 operands
(``bf16_operands_f32_accumulate``). ``precision="highest"`` is the same pass
with every product at ``highest``: the engine's reading against it is
recorded in PERF.md, and a CPU rehearsal, where the program's float32
matmuls are exact, compares with it.

The architecture is XGLM's (Lin et al., arXiv:2112.10668): token embedding
scaled by sqrt(d) plus sinusoidal positions, pre-LayerNorm blocks of causal
self-attention and a 4x GELU MLP, a final LayerNorm, output head tied to
the embedding. The departures are the configuration file's ``assumed``
list: no biases on the linear layers, LayerNorm eps 1e-6, positions from 0
with sin and cos halves and frequencies exp(-ln(1e4) i/half), tanh GELU.

``precision="bfloat16"`` is the control: the same pass with weights and
activations stored in bfloat16 (float32 inside LayerNorm and softmax), the
step a later PR would be tempted by (ROADMAP S3), which has to come out as
not correct.
"""
import functools
import math

import jax
import jax.numpy as jnp

PAD = 128


def _layer_norm(x, gain, bias, eps):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * gain + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _positions(n, d_model):
    half = d_model // 2
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * freq[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _weight_matmul(a, w, mode):
    """``a @ w`` for a weight ``w`` in one of the three precisions."""
    if mode == "highest":
        return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)
    out = jnp.float32 if mode == "bf16_operands_f32_accumulate" else None
    return jnp.matmul(a.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                      preferred_element_type=out)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "mode"))
def _block(x, lp, heads, eps, mode):
    """One pre-LayerNorm block over a whole sequence ``x`` [n, d]."""
    hi = jax.lax.Precision.HIGHEST
    dtype = jnp.bfloat16 if mode == "bfloat16" else jnp.float32
    n, d = x.shape
    hd = d // heads
    mm = functools.partial(_weight_matmul, mode=mode)
    h = _layer_norm(x, *lp["ln1"], eps).astype(dtype)
    q = mm(h, lp["wq"]).reshape(n, heads, hd)
    k = mm(h, lp["wk"]).reshape(n, heads, hd)
    v = mm(h, lp["wv"]).reshape(n, heads, hd)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=hi,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(dtype)
    a = jnp.einsum("hqk,khd->qhd", p, v, precision=hi).reshape(n, d)
    x = (x + mm(a, lp["wo"])).astype(dtype)
    h2 = _layer_norm(x, *lp["ln2"], eps).astype(dtype)
    x = x + mm(_gelu_tanh(mm(h2, lp["w1"])), lp["w2"])
    return x.astype(dtype)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(x_rows, lnf, tok_emb, eps, mode):
    h = _layer_norm(x_rows, *lnf, eps)
    if mode == "highest":
        return jnp.matmul(h, tok_emb.T,
                          precision=jax.lax.Precision.HIGHEST)
    return jnp.matmul(h.astype(jnp.bfloat16),
                      tok_emb.astype(jnp.bfloat16).T,
                      preferred_element_type=jnp.float32)


def _mode(cfg, precision):
    if precision == "stated":
        return cfg["precision"]["reference_matmul"]
    if precision not in ("highest", "bfloat16"):
        raise ValueError(f"unknown precision {precision!r}")
    return precision


ROW_PAD = 64


def logits_at(params, cfg, tokens, rows, precision="stated"):
    """Logits [len(rows), vocab] (float32) that follow positions ``rows``
    of the sequence ``tokens``. The sequence is padded to a multiple of
    128 and the rows to one of 64 (the last row repeated; the caller cuts
    them off), so that few shapes compile; causal attention keeps the
    padding out of every row asked for."""
    mode = _mode(cfg, precision)
    dtype = jnp.bfloat16 if mode == "bfloat16" else jnp.float32
    d = int(cfg["d_model"])
    eps = float(cfg["layer_norm_eps"])
    n = len(tokens)
    padded = -(-n // PAD) * PAD
    toks = jnp.zeros((padded,), jnp.int32).at[:n].set(
        jnp.asarray(tokens, jnp.int32))
    x = (params["tok_emb"][toks].astype(jnp.float32) * math.sqrt(d)
         + _positions(padded, d)).astype(dtype)
    for l in range(int(cfg["num_layers"])):
        x = _block(x, params[f"layer{l}"], int(cfg["attention_heads"]),
                   eps, mode)
    rows = list(rows)
    take = rows + [rows[-1]] * (-len(rows) % ROW_PAD)
    return _head(x[jnp.asarray(take, jnp.int32)], params["lnf"],
                 params["tok_emb"], eps, mode)


@jax.jit
def _gaps(ref, judged):
    best = jnp.max(ref, axis=-1)
    mine = jnp.take_along_axis(ref, judged[:, None], axis=-1)[:, 0]
    return best - mine


@jax.jit
def _rank_gaps(ref_row, ranked):
    """How far the reference's logit of the token ranked j-th lies from
    the reference's own j-th best, for every rank given."""
    best = jax.lax.top_k(ref_row, ranked.shape[0])[0]
    return best - ref_row[ranked]


def served_gaps(params, cfg, prompt, served, judged_by=None,
                first_topk=None, against="stated"):
    """For each served token, how far its logit lies below the best logit
    at its position, both read from this reference's pass over the prompt
    with the served tokens appended; and, where the program handed back
    the order of its best ``k`` tokens at the first generated position,
    the gap between the reference's logit of the token it ranked j-th and
    the reference's j-th best, for each j. With ``judged_by`` a lower
    precision, the tokens and the order judged are those that pass puts
    first, read in the reference's logits: the control's gaps."""
    import numpy as np

    seq = list(prompt) + list(served)
    n = len(served)
    rows = [len(prompt) - 1 + i for i in range(n)]
    ref = logits_at(params, cfg, seq, rows, against)
    if judged_by is None:
        padded = list(served) + [served[-1]] * (ref.shape[0] - n)
        judged = jnp.asarray(padded, jnp.int32)
        ranked = (None if first_topk is None
                  else jnp.asarray(first_topk, jnp.int32))
    else:
        low = logits_at(params, cfg, seq, rows, judged_by)
        judged = jnp.argmax(low, axis=-1)
        ranked = (None if first_topk is None
                  else jax.lax.top_k(low[0], len(first_topk))[1])
    token_gaps = np.asarray(_gaps(ref, judged))[:n]
    rank_gaps = (None if ranked is None
                 else np.asarray(_rank_gaps(ref[0], ranked)))
    return token_gaps, rank_gaps
