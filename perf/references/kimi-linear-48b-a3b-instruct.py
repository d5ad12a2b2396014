"""Plain reference of the `kimi-linear-48b-a3b-instruct` configuration as it
is run.

The forward pass of the ``kimi_linear`` architecture (Moonshot's
Kimi-Linear: gated delta-rule linear attention layers, latent attention
layers, a dense SwiGLU layer, sigmoid-routed experts beside a shared one)
over one whole sequence in straightforward ``jax.numpy``: float32, every
product at ``highest``, no cache, no kernel, no batching, no grouped
product. It imports nothing of the program and takes only the seed's
weights (cast to float32 here, whatever they are stored in). The tier-1
tests load this same file by path.

A layer, for hidden ``x`` [n, d] (config keys in brackets; no biases but
``dt_bias``; the equations are those of the family's modelling code,
``modeling_kimi.py``'s ``KimiDeltaAttention`` and ``KimiMLAAttention`` and
fla's ``naive_recurrent_kda``, listed under ``assumed`` in the
configuration's file where the keys do not say):

1. ``h = RMSNorm(x; ln_in, [rms_norm_eps])``.
2. A KDA layer ([linear_attn_config.kda_layers]; H heads of K = V
   [head_dim]): ``q, k, v = SiLU(conv(h W))`` each, ``conv`` a causal
   depthwise convolution of [short_conv_kernel_size] taps a channel over
   the sequence (zeros before its start); q and k L2-normed per head, q
   times ``K^-1/2``; ``beta = sigmoid(h wb)`` a head; ``g = -exp(A_log_h)
   softplus(h f_a f_b + dt_bias)`` a channel; per head, token by token
   from ``S = 0 [K, V]``: ``S <- exp(g_t)[:, None] S; S <- S + beta_t k_t
   (v_t - k_t S); o_t = q_t S``; out ``(RMSNorm_V(o; o_norm) * sigmoid(h
   g_a g_b)) wo``.
3. An MLA layer ([full_attn_layers]; no rotary [mla_use_nope], no query
   compression [q_lora_rank null]): ``q = h wq`` a head ``[q_nope (nope);
   q_pe (rope)]``; ``[c; k_pe] = h wkva``, ``c <- RMSNorm(c; kv_norm)``;
   ``[k_nope; v] = c wkvb`` a head; the key of head i is ``[k_nope_i;
   k_pe]`` (k_pe shared by the heads); ``a = softmax(q k^T / sqrt(nope +
   rope) + M) v`` under the causal mask M, UN-ABSORBED (the program's
   absorbed form is its own); out ``a wo``.
4. ``x <- x + out``; ``h = RMSNorm(x; ln_ff)``. A layer below
   [first_k_dense_replace]: ``f = (silu(h gate) * h up) down``. Every
   other: ``s = sigmoid(h router)`` over the router's
   [num_experts_published] outputs; the [num_experts_per_token] with the
   largest ``s + expert_bias`` are kept, weighed ``s / sum(s kept)``
   [moe_renormalize] times [routed_scaling_factor]; ``f = Shared(h) +
   sum_e w_e Expert_e(h)`` over the experts this chip holds
   ([experts_held]: what the other chip's experts add is left out, in the
   program and here alike); every expert a SwiGLU. ``x <- x + f``.
5. After the last layer ``RMSNorm(x; lnf)``, logits ``= x head`` (untied).
   Logits at position ``i`` predict the token at ``i + 1``.

Departures from the published code, none of which changes a number beyond
float32 rounding: the sequence is padded to a power of two of at least 8192
tokens, so that two lengths compile whatever the checks' lengths (causal
masks and the recurrence keep the padding out of every row asked for);
attention runs a head and 512 queries at a time so that a
16 384-token sequence's scores fit; every held expert is applied to every
token, one expert after another, weighed by zero where the token did not
choose it; norms, the decay and the recurrence in float32 throughout (the
published code runs its matrices in bfloat16).

``precision="float8_e4m3"`` is the control, one precision below the
bfloat16 the configuration states: every weight rounded to float8_e4m3 and
every activation rounded to float8_e4m3 where the configuration's program
stores it in bfloat16 (a normed input, the projections before the
convolution, the latent entry, the attention output, a branch's output
into the residual stream, the gated products of the SwiGLUs); the state,
the decay and beta stay float32, as the program keeps them.
``precision="bfloat16"`` rounds the same weights and activations to the
bfloat16 the configuration states: the witness of what that precision
alone moves, beside the control.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

MIN_PAD = 8192     # sequences are padded to a power of two at least this
Q_BLOCK = 512      # queries a block of the attention
ROW_PAD = 64       # rows asked for are padded to a multiple
HI = jax.lax.Precision.HIGHEST


def dims(cfg):
    lin = cfg["linear_attn_config"]
    return {"layers": int(cfg["num_hidden_layers"]),
            "kda": tuple(int(l) - 1 for l in lin["kda_layers"]),
            "dense_layers": int(cfg["first_k_dense_replace"]),
            "heads": int(cfg["num_attention_heads"]),
            "kda_dim": int(lin["head_dim"]),
            "nope": int(cfg["qk_nope_head_dim"]),
            "rope": int(cfg["qk_rope_head_dim"]),
            "rank": int(cfg["kv_lora_rank"]),
            "v_dim": int(cfg["v_head_dim"]),
            "top_k": int(cfg["num_experts_per_token"]),
            "held": tuple(int(e) for e in cfg.get(
                "experts_held", (0, cfg["num_experts"]))),
            "renorm": bool(cfg.get("moe_renormalize", True)),
            "scale": float(cfg.get("routed_scaling_factor", 1.0)),
            "eps": float(cfg["rms_norm_eps"])}


ROUNDED = {"float8_e4m3": jnp.float8_e4m3fn, "bfloat16": jnp.bfloat16}


def _weight(w, precision):
    """A weight as the pass uses it: float32, or rounded first to a lower
    precision (the control, the witness)."""
    if precision in ROUNDED:
        w = w.astype(ROUNDED[precision])
    return w.astype(jnp.float32)


def _stored(x, precision):
    """An activation as the pass stores it between operations: float32, or
    rounded to a lower precision (the control, the witness)."""
    if precision in ROUNDED:
        return x.astype(ROUNDED[precision]).astype(jnp.float32)
    return x


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * gain


def _mm(a, w):
    return jnp.matmul(a, w, precision=HI)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def causal_conv(x, w):
    """``y[t] = sum_i w[i] x[t - (taps - 1) + i]`` per channel, zeros
    before the sequence: ``x`` [n, ch], ``w`` [taps, ch]."""
    taps = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return sum(w[i] * xp[i:i + x.shape[0]] for i in range(taps))


def delta_rule(q, k, v, g, beta):
    """The gated delta rule token by token over ``q``, ``k``, ``g`` [n, H,
    K], ``v`` [n, H, V], ``beta`` [n, H], from a zero state ``S [H, K,
    V]``: ``o`` [n, H, V]."""
    def one(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[:, :, None]
        kv = jnp.einsum("hk,hkv->hv", kt, s, precision=HI)
        s = s + bt[:, None, None] * kt[:, :, None] * (vt - kv)[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", qt, s, precision=HI)

    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    _, o = jax.lax.scan(one, s0, (q, k, v, g, beta))
    return o


def attention(q, k, v):
    """``softmax(q k^T / sqrt(D) + M) v`` under the causal mask, for ``q``,
    ``k`` [n, heads, D] and ``v`` [n, heads, V]: a head and ``Q_BLOCK``
    queries at a time."""
    n, heads, d = q.shape
    keys = jnp.arange(n)

    def one(args):
        qb, first, kh, vh = args              # [Q, D], (), [n, D], [n, V]
        s = jnp.einsum("qd,kd->qk", qb, kh, precision=HI) / math.sqrt(d)
        at = first + jnp.arange(qb.shape[0])
        p = jax.nn.softmax(jnp.where(keys[None, :] <= at[:, None], s,
                                     -jnp.inf), axis=-1)
        return jnp.einsum("qk,kv->qv", p, vh, precision=HI)

    blocks = n // Q_BLOCK
    qs = q.reshape(blocks, Q_BLOCK, heads, d).transpose(2, 0, 1, 3)
    out = jax.lax.map(
        lambda head: jax.lax.map(
            lambda blk: one((blk[0], blk[1], head[1], head[2])),
            (head[0], jnp.arange(blocks) * Q_BLOCK)),
        (qs, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    # [heads, blocks, Q, V] -> [n, heads, V]
    return out.transpose(1, 2, 0, 3).reshape(n, heads, -1)


def route_weights(h, router, bias, top_k, renorm, scale, held):
    """[n, held experts] float32: each token's weight on every expert this
    chip holds, zero outside the ``top_k`` of ALL the router's experts
    with the largest ``sigmoid score + bias``; the bias selects and never
    weighs, the weights are renormalised over all ``top_k``."""
    s = jax.nn.sigmoid(_mm(h, router))
    _, idx = jax.lax.top_k(s + bias, top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if renorm:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    full = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(
        w * scale)
    return full[:, held[0]:held[1]]


def swiglu(h, gate, up, down, precision):
    a = _stored(jax.nn.silu(_mm(h, gate)) * _mm(h, up), precision)
    return _mm(a, down)


def experts(h, weights, gate, up, down, precision):
    """``sum_e weights[:, e] * Expert_e(h)`` over the held experts, every
    expert applied to every token, one expert after another."""
    def one(y, e):
        g, u, dn, w = e
        g, u, dn = (_weight(m, precision) for m in (g, u, dn))
        return y + w[:, None] * swiglu(h, g, u, dn, precision), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (gate, up, down, weights.T))
    return y


def _kda(h, lp, heads, kd, eps, precision):
    wt = functools.partial(_weight, precision=precision)
    st = functools.partial(_stored, precision=precision)
    n = h.shape[0]
    qkv = [st(_mm(h, wt(lp[w]))) for w in ("wq", "wk", "wv")]
    q, k, v = (jax.nn.silu(causal_conv(x, wt(lp[c]))).reshape(n, heads, kd)
               for x, c in zip(qkv, ("conv_q", "conv_k", "conv_v")))
    q = _l2norm(q) * kd ** -0.5
    k = _l2norm(k)
    beta = jax.nn.sigmoid(_mm(h, wt(lp["wb"])))
    f = _mm(st(_mm(h, wt(lp["f_a"]))), wt(lp["f_b"]))
    g = (-jnp.exp(lp["A_log"].astype(jnp.float32))[:, None]
         * jax.nn.softplus(f + lp["dt_bias"].astype(jnp.float32)
                           ).reshape(n, heads, kd))
    gate = jax.nn.sigmoid(_mm(st(_mm(h, wt(lp["g_a"]))), wt(lp["g_b"])))
    o = _rms(delta_rule(q, k, v, g, beta), wt(lp["o_norm"]), eps)
    return _mm(st(o.reshape(n, heads * kd) * gate), wt(lp["wo"]))


def _mla(h, lp, heads, nope, rope, rank, v_dim, eps, precision):
    wt = functools.partial(_weight, precision=precision)
    st = functools.partial(_stored, precision=precision)
    n = h.shape[0]
    q = st(_mm(h, wt(lp["wq"])).reshape(n, heads, nope + rope))
    kva = _mm(h, wt(lp["wkva"]))
    c = st(_rms(kva[:, :rank], wt(lp["kv_norm"]), eps))
    k_pe = st(kva[:, rank:])
    kv = _mm(c, wt(lp["wkvb"])).reshape(n, heads, nope + v_dim)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe[:, None, :], (n, heads, rope))], axis=-1)
    a = st(attention(q, k, kv[..., nope:]))
    return _mm(a.reshape(n, heads * v_dim), wt(lp["wo"]))


@functools.partial(jax.jit, static_argnames=(
    "kind", "heads", "kd", "nope", "rope", "rank", "v_dim", "top_k",
    "renorm", "scale", "held", "eps", "precision"))
def _block(x, lp, kind, heads, kd, nope, rope, rank, v_dim, top_k, renorm,
           scale, held, eps, precision):
    """One layer over a whole padded sequence ``x`` [N, d]."""
    wt = functools.partial(_weight, precision=precision)
    st = functools.partial(_stored, precision=precision)
    h = st(_rms(x, wt(lp["ln_in"]), eps))
    if kind == "kda":
        mixed = _kda(h, lp, heads, kd, eps, precision)
    else:
        mixed = _mla(h, lp, heads, nope, rope, rank, v_dim, eps, precision)
    x = st(x + mixed)
    h2 = st(_rms(x, wt(lp["ln_ff"]), eps))
    if "router" not in lp:
        f = swiglu(h2, wt(lp["gate"]), wt(lp["up"]), wt(lp["down"]),
                   precision)
    else:
        w = route_weights(h2, wt(lp["router"]),
                          lp["expert_bias"].astype(jnp.float32), top_k,
                          renorm, scale, held)
        f = experts(h2, w, lp["gate"], lp["up"], lp["down"],
                    precision) + swiglu(
            h2, wt(lp["shared_gate"]), wt(lp["shared_up"]),
            wt(lp["shared_down"]), precision)
    return st(x + f)


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
    big = max(MIN_PAD, 1 << (n - 1).bit_length())
    toks = jnp.zeros((big,), jnp.int32).at[:n].set(
        jnp.asarray(tokens, jnp.int32))
    x = _weight(params["tok_emb"][toks], precision)
    for l in range(m["layers"]):
        x = _block(x, params[f"layer{l}"],
                   "kda" if l in m["kda"] else "mla", m["heads"],
                   m["kda_dim"], m["nope"], m["rope"], m["rank"],
                   m["v_dim"], m["top_k"], m["renorm"], m["scale"],
                   m["held"], m["eps"], precision)
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
