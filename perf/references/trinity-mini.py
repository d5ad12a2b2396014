"""Plain reference of the `trinity-mini` configuration as it is run.

The forward pass of the ``afmoe`` architecture (Arcee's Trinity: window and
full attention layers, gated attention under sandwich norms, a dense SwiGLU
layer, sigmoid-routed experts beside a shared one) over one whole sequence
in straightforward ``jax.numpy``: float32, every product at ``highest``,
the dense causal and window masks, no cache, no kernel, no batching, no
grouped product. It imports nothing of the program and takes only the
seed's weights (whatever dtype they are stored in, they are cast to float32
here). The tier-1 tests load this same file by path.

A layer, for hidden ``x`` [n, d] (config keys in brackets; no biases; the
equations are `transformers` ``models/afmoe/modeling_afmoe.py``'s, listed
under ``assumed`` in the configuration's file where the keys do not say):

1. ``h = RMSNorm(x; ln_in, [rms_norm_eps])``; ``q = h wq`` -> heads x
   head_dim, ``k = h wk``, ``v = h wv`` -> kv heads x head_dim, ``g = h
   wg``; ``q, k <- RMSNorm_head_dim(.; q_norm / k_norm)`` per head; on a
   ``sliding_attention`` layer ONLY, rotary on the whole head dimension,
   rotate-half pairing, theta [rope_theta], no scaling (a ``full_attention``
   layer carries no positions); ``a = softmax(q k^T / sqrt(head_dim) + M)
   v``, each group of heads/kv_heads query heads on one key head; ``x <- x
   + RMSNorm((a * sigmoid(g)) wo; ln_post_attn)``.
2. ``h = RMSNorm(x; ln_pre_mlp)``. On a layer below [num_dense_layers]:
   ``f = (silu(h gate) * h up) down``. On every other: ``s = sigmoid(h
   router)``; the [num_experts_per_tok] experts with the largest ``s +
   expert_bias`` are kept, weighed ``s / sum(s kept)`` [route_norm] times
   [route_scale]; ``f = Shared(h) + sum_e w_e Expert_e(h)``, every expert a
   SwiGLU. ``x <- x + RMSNorm(f; ln_post_mlp)``.
3. The embedding is scaled by sqrt(hidden_size) [mup_enabled]; after the
   last layer ``RMSNorm(x; lnf)``, logits ``= x head`` (untied).

The mask M: position ``i`` sees key ``j`` iff ``j <= i`` and, on a sliding
layer, ``j > i - [sliding_window]``. Logits at position ``i`` predict the
token at ``i + 1``.

Departures from the published code, none of which changes a number beyond
float32 rounding: the sequence is padded to a multiple of 1024 (causal masks
keep the padding out of every row asked for); attention is computed in
blocks of 512 queries and of one key head's query heads, so that an
8 704-token sequence's scores fit beside the weights (the published code
holds all of them at once); every expert is applied to every token, one
expert after another, and weighed by zero where the token did not choose it
(the published code gathers each expert's tokens); rotary angles, norms and
softmax in float32 throughout (the published code runs in bfloat16).

``precision="float8_e4m3"`` is the control, one precision below the
bfloat16 the configuration states: the same pass with every weight rounded
to float8_e4m3 and every activation rounded to float8_e4m3 where the
configuration's program stores it in bfloat16 (a normed input, q, k, v, the
gated attention output, a branch's output into the residual stream, the
gated products of the SwiGLUs). It has to come out as not correct.
``precision="float8_e4m3_weights"`` is read beside it: the weights rounded
to float8_e4m3 and those activations to bfloat16.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PAD = 1024         # sequences are padded to a multiple: few shapes compile
Q_BLOCK = 512      # queries a block of the attention
ROW_PAD = 64       # rows asked for are padded to a multiple
HI = jax.lax.Precision.HIGHEST
SLIDING = "sliding_attention"


def dims(cfg):
    return {"d": int(cfg["hidden_size"]),
            "layers": int(cfg["num_hidden_layers"]),
            "dense_layers": int(cfg["num_dense_layers"]),
            "heads": int(cfg["num_attention_heads"]),
            "kv_heads": int(cfg["num_key_value_heads"]),
            "head_dim": int(cfg["head_dim"]),
            "top_k": int(cfg["num_experts_per_tok"]),
            "route_norm": bool(cfg.get("route_norm", True)),
            "route_scale": float(cfg.get("route_scale", 1.0)),
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "window": int(cfg["sliding_window"]),
            "mup": bool(cfg.get("mup_enabled", False)),
            "types": tuple(cfg["layer_types"])}


def _weight(w, precision):
    """A weight as the pass uses it: float32, or rounded to float8_e4m3
    first (both controls)."""
    if precision in ("float8_e4m3", "float8_e4m3_weights"):
        w = w.astype(jnp.float8_e4m3fn)
    return w.astype(jnp.float32)


def _stored(x, precision):
    """An activation as the pass stores it between operations: float32, or
    rounded to float8_e4m3 (the control) or to bfloat16 (the second)."""
    if precision == "float8_e4m3":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if precision == "float8_e4m3_weights":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * gain


def _rotary(x, positions, theta):
    """``x`` [n, heads, D]: lane i pairs with lane i + D/2."""
    half = x.shape[-1] // 2
    inv = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                  * 2.0 / x.shape[-1])
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(a, w):
    return jnp.matmul(a, w, precision=HI)


def attention(q, k, v, window):
    """``softmax(q k^T / sqrt(D) + M) v`` for ``q`` [n, heads, D] and ``k``,
    ``v`` [n, kv_heads, D] under the causal mask, behind which a ``window``
    (None: none) also hides key ``j <= i - window``. One key head's query
    heads and ``Q_BLOCK`` queries at a time."""
    n, heads, d = q.shape
    kv_heads = k.shape[1]
    rep = heads // kv_heads
    keys = jnp.arange(n)

    def one(args):
        qb, first, kh, vh = args              # [rep, Q, D], (), [n, D] x 2
        s = jnp.einsum("hqd,kd->hqk", qb, kh, precision=HI) / math.sqrt(d)
        at = first + jnp.arange(qb.shape[1])
        sees = keys[None, :] <= at[:, None]
        if window is not None:
            sees &= keys[None, :] > at[:, None] - window
        p = jax.nn.softmax(jnp.where(sees[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,kd->hqd", p, vh, precision=HI)

    blocks = n // Q_BLOCK
    # [kv_heads, blocks, rep, Q, D]: a block of queries of one key head
    qs = q.reshape(blocks, Q_BLOCK, kv_heads, rep, d).transpose(2, 0, 3, 1, 4)
    out = jax.lax.map(
        lambda head: jax.lax.map(
            lambda blk: one((blk[0], blk[1], head[1], head[2])),
            (head[0], jnp.arange(blocks) * Q_BLOCK)),
        (qs, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    # [kv_heads, blocks, rep, Q, D] -> [n, heads * D]
    return out.transpose(1, 3, 0, 2, 4).reshape(n, heads * d)


def route_weights(h, router, bias, top_k, route_norm, route_scale):
    """[n, E] float32: each token's weight on every expert, zero outside
    the ``top_k`` with the largest ``sigmoid score + bias``; the bias
    selects and never weighs."""
    s = jax.nn.sigmoid(_mm(h, router))
    _, idx = jax.lax.top_k(s + bias, top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if route_norm:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(
        w * route_scale)


def swiglu(h, gate, up, down, precision):
    a = _stored(jax.nn.silu(_mm(h, gate)) * _mm(h, up), precision)
    return _mm(a, down)


def experts(h, weights, gate, up, down, precision):
    """``sum_e weights[:, e] * Expert_e(h)``, every expert applied to every
    token, one expert after another."""
    def one(y, e):
        g, u, dn, w = e
        g, u, dn = (_weight(m, precision) for m in (g, u, dn))
        return y + w[:, None] * swiglu(h, g, u, dn, precision), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (gate, up, down, weights.T))
    return y


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "top_k", "route_norm", "route_scale",
    "eps", "theta", "window", "precision"))
def _block(x, lp, heads, kv_heads, head_dim, top_k, route_norm, route_scale,
           eps, theta, window, precision):
    """One layer over a whole padded sequence ``x`` [N, d]; ``window`` None
    marks a full layer (no window, no positions)."""
    wt = functools.partial(_weight, precision=precision)
    st = functools.partial(_stored, precision=precision)
    big = x.shape[0]
    pos = jnp.arange(big)
    h = st(_rms(x, wt(lp["ln_in"]), eps))
    q = _rms(_mm(h, wt(lp["wq"])).reshape(big, heads, head_dim),
             wt(lp["q_norm"]), eps)
    k = _rms(_mm(h, wt(lp["wk"])).reshape(big, kv_heads, head_dim),
             wt(lp["k_norm"]), eps)
    v = st(_mm(h, wt(lp["wv"])).reshape(big, kv_heads, head_dim))
    gate = jax.nn.sigmoid(_mm(h, wt(lp["wg"])))
    if window is not None:
        q, k = _rotary(q, pos, theta), _rotary(k, pos, theta)
    a = st(attention(st(q), st(k), v, window))
    x = st(x + _rms(_mm(st(a * gate), wt(lp["wo"])), wt(lp["ln_post_attn"]),
                    eps))
    h2 = st(_rms(x, wt(lp["ln_pre_mlp"]), eps))
    if "router" not in lp:
        f = swiglu(h2, wt(lp["gate"]), wt(lp["up"]), wt(lp["down"]),
                   precision)
    else:
        w = route_weights(h2, wt(lp["router"]),
                          lp["expert_bias"].astype(jnp.float32), top_k,
                          route_norm, route_scale)
        f = experts(h2, w, lp["gate"], lp["up"], lp["down"],
                    precision) + swiglu(
            h2, wt(lp["shared_gate"]), wt(lp["shared_up"]),
            wt(lp["shared_down"]), precision)
    return st(x + _rms(f, wt(lp["ln_post_mlp"]), eps))


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x_rows, lnf, head, eps, precision):
    return _mm(_stored(_rms(x_rows, _weight(lnf, precision), eps),
                       precision), _weight(head, precision))


def logits_at(params, cfg, tokens, rows, precision="stated"):
    """Logits [>= len(rows), vocab] (float32) that FOLLOW positions
    ``rows`` of the sequence ``tokens`` (the rows are padded to a multiple
    of 64 with the last repeated; the caller cuts them off)."""
    m = dims(cfg)
    n = len(tokens)
    big = -(-n // PAD) * PAD
    toks = jnp.zeros((big,), jnp.int32).at[:n].set(
        jnp.asarray(tokens, jnp.int32))
    x = _weight(params["tok_emb"][toks], precision)
    if m["mup"]:
        x = _stored(x * math.sqrt(m["d"]), precision)
    for l in range(m["layers"]):
        x = _block(x, params[f"layer{l}"], m["heads"], m["kv_heads"],
                   m["head_dim"], m["top_k"], m["route_norm"],
                   m["route_scale"], m["eps"], m["theta"],
                   m["window"] if m["types"][l] == SLIDING else None,
                   precision)
    rows = list(rows)
    take = rows + [rows[-1]] * (-len(rows) % ROW_PAD)
    return _head(x[jnp.asarray(take, jnp.int32)], params["lnf"],
                 params["head"], m["eps"], precision)


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
                first_topk=None):
    """For each served token, how far its logit lies below the best logit
    at its position, both read from this reference's ONE pass over the
    prompt with the served tokens appended; and, where the program handed
    back the order of its best ``k`` tokens at the first generated
    position, the gap between the reference's logit of the token it ranked
    j-th and the reference's j-th best, for each j. With ``judged_by`` a
    lower precision, the tokens and the order judged are those that pass
    puts first, read in the reference's logits: the control's gaps."""
    seq = list(prompt) + list(served)
    n = len(served)
    rows = [len(prompt) - 1 + i for i in range(n)]
    ref = logits_at(params, cfg, seq, rows)
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
