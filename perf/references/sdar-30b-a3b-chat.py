"""Plain reference of the `sdar-30b-a3b-chat` configuration as it is run.

The forward pass of the ``sdar_moe`` architecture (a Qwen3-MoE block under
SDAR's block-diffusion mask) over one whole sequence in straightforward
``jax.numpy``: float32, every product at ``highest``, no cache, no kernel,
no batching, no grouped product, and the generation loop on top of it. It
imports nothing of the program and takes only the seed's weights (whatever
dtype they are stored in, they are cast to float32 here). Layer by layer,
one jitted block each, and the experts one after another, so that float32
fits beside the weights. The tier-1 tests load this same file by path.

The layer, for hidden ``x`` [n, d] (config keys in brackets; no biases):

1. ``h = RMSNorm(x; ln1, eps)``; ``q = h wq`` -> heads x head_dim, ``k = h
   wk``, ``v = h wv`` -> kv heads x head_dim; ``q, k <- RMSNorm_head_dim(.;
   q_norm / k_norm)`` per head; rotary on the whole head dimension,
   rotate-half pairing, theta [rope_theta], no scaling; ``a = softmax(q k^T
   / sqrt(head_dim) + M) v``, each group of heads/kv_heads query heads on
   one key head; ``x <- x + a wo``.
2. ``h = RMSNorm(x; ln2)``; ``p = softmax(h router)`` over all experts; the
   [num_experts_per_tok] largest are kept and renormalised to sum 1
   [norm_topk_prob]; ``x <- x + sum_e w_e (silu(h gate_e) * h up_e) down_e``.
3. After the last layer ``RMSNorm(x; lnf)``, logits ``= x head`` (untied,
   embedding not scaled).

The mask M (``assumed.block_length`` B): position ``i`` sees key ``j`` iff
``j < (i // B + 1) * B`` and ``j < n``: causal between blocks, both ways
inside one. Logits at position ``i`` predict the token AT ``i`` (no shift).

Generation (``generate``): the ``P // B`` whole blocks of the prompt are
context; the ``P mod B`` tokens left over open the first generated block
unmasked. A block starts as its known tokens and ``assumed.mask_token_id``
elsewhere. A denoise pass takes at every masked lane the best token ``x0``
and its confidence ``softmax(logits)[x0]`` and unmasks the ``B /
denoise_steps`` most confident masked lanes (ties to the lower lane). With
no mask left the block's tokens are answered, as far as ``max_new`` and
``eos_id`` let (the rest of the block is dropped).

``precision="float8_e4m3"`` is the control, one precision below the
bfloat16 the configuration states: the same pass with every weight rounded
to float8_e4m3 and every activation rounded to float8_e4m3 where the
configuration's program stores it in bfloat16 (a normed input, q, k, v, a
branch's output into the residual stream, the experts' gated product). It
has to come out as not correct. ``precision="float8_e4m3_weights"`` is a
second control, read beside it: the weights rounded to float8_e4m3 and
those activations to bfloat16, as a step with 8-bit weights would hold
them.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PAD = 128          # sequences are padded to 128, 256, 512, ...: few shapes compile
HI = jax.lax.Precision.HIGHEST


def dims(cfg):
    a = cfg.get("assumed", {})
    return {"d": int(cfg["hidden_size"]),
            "layers": int(cfg["num_hidden_layers"]),
            "heads": int(cfg["num_attention_heads"]),
            "kv_heads": int(cfg["num_key_value_heads"]),
            "head_dim": int(cfg["head_dim"]),
            "experts": int(cfg["num_experts"]),
            "top_k": int(cfg["num_experts_per_tok"]),
            "norm_topk": bool(cfg.get("norm_topk_prob", True)),
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "vocab": int(cfg["vocab_size"]),
            "block": int(a.get("block_length", 4)),
            "mask_id": int(a.get("mask_token_id",
                                 int(cfg["vocab_size"]) - 1))}


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


def router_weights(h, router, top_k, norm_topk):
    """[n, E] float32: each token's weight on every expert, zero outside
    its ``top_k`` best."""
    p = jax.nn.softmax(jnp.matmul(h, router, precision=HI), axis=-1)
    w, idx = jax.lax.top_k(p, top_k)
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return jnp.zeros_like(p).at[jnp.arange(p.shape[0])[:, None], idx].set(w)


def experts(h, weights, gate, up, down, precision="stated"):
    """``sum_e weights[:, e] * (silu(h gate_e) * h up_e) down_e``, every
    expert applied to every token, one expert after another."""
    def one(y, e):
        g, u, dn, w = e
        g, u, dn = (_weight(m, precision) for m in (g, u, dn))
        a = _stored(jax.nn.silu(jnp.matmul(h, g, precision=HI)) * jnp.matmul(
            h, u, precision=HI), precision)
        return y + w[:, None] * jnp.matmul(a, dn, precision=HI), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (gate, up, down, weights.T))
    return y


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "top_k", "norm_topk", "eps", "theta",
    "block", "precision"))
def _block(x, n, lp, heads, kv_heads, head_dim, top_k, norm_topk, eps,
           theta, block, precision):
    """One layer over a whole padded sequence ``x`` [N, d] of which the
    first ``n`` positions exist."""
    wt = functools.partial(_weight, precision=precision)
    st = functools.partial(_stored, precision=precision)
    big = x.shape[0]
    pos = jnp.arange(big)
    h = st(_rms(x, wt(lp["ln1"]), eps))
    q = jnp.matmul(h, wt(lp["wq"]), precision=HI).reshape(
        big, heads, head_dim)
    k = jnp.matmul(h, wt(lp["wk"]), precision=HI).reshape(
        big, kv_heads, head_dim)
    v = st(jnp.matmul(h, wt(lp["wv"]), precision=HI).reshape(
        big, kv_heads, head_dim))
    q = st(_rotary(_rms(q, wt(lp["q_norm"]), eps), pos, theta))
    k = st(_rotary(_rms(k, wt(lp["k_norm"]), eps), pos, theta))
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(head_dim)
    sees = (pos[None, :] < (pos[:, None] // block + 1) * block) & (
        pos[None, :] < n)
    s = jnp.where(sees[None], s, -jnp.inf)
    # a padding row sees nothing if its block lies past n: keep it finite
    p = jax.nn.softmax(jnp.where(jnp.any(sees, axis=-1)[None, :, None],
                                 s, 0.0), axis=-1)
    a = st(jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(big, -1))
    x = st(x + jnp.matmul(a, wt(lp["wo"]), precision=HI))
    h2 = st(_rms(x, wt(lp["ln2"]), eps))
    w = router_weights(h2, wt(lp["router"]), top_k, norm_topk)
    return st(x + experts(h2, w, lp["gate"], lp["up"], lp["down"],
                          precision))


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x_rows, lnf, head, eps, precision):
    return jnp.matmul(_stored(_rms(x_rows, _weight(lnf, precision), eps),
                              precision),
                      _weight(head, precision), precision=HI)


def logits_at(params, cfg, tokens, rows, precision="stated"):
    """Logits [len(rows), vocab] (float32) AT positions ``rows`` of the
    sequence ``tokens`` under the block mask."""
    m = dims(cfg)
    n = len(tokens)
    big = PAD
    while big < n:
        big *= 2
    toks = jnp.zeros((big,), jnp.int32).at[:n].set(
        jnp.asarray(tokens, jnp.int32))
    x = params["tok_emb"][toks].astype(jnp.float32)
    for l in range(m["layers"]):
        x = _block(x, jnp.int32(n), params[f"layer{l}"], m["heads"],
                   m["kv_heads"], m["head_dim"], m["top_k"],
                   m["norm_topk"], m["eps"], m["theta"], m["block"],
                   precision)
    return _head(x[jnp.asarray(list(rows), jnp.int32)], params["lnf"],
                 params["head"], m["eps"], precision)


def choose(logits, masked, n_unmask):
    """A denoise pass's choice from its block's logits [B, V] (numpy):
    ``(x0 [B], confidence [B], unmask [B])``: the best token at every
    lane, its softmax probability, and the ``n_unmask`` most confident of
    the ``masked`` lanes, ties to the lower lane."""
    logits = np.asarray(logits, np.float64)
    x0 = logits.argmax(axis=-1)
    z = logits - logits.max(axis=-1, keepdims=True)
    conf = np.exp(z[np.arange(len(x0)), x0]) / np.exp(z).sum(axis=-1)
    lanes = [j for j in range(len(x0)) if masked[j]]
    lanes.sort(key=lambda j: (-conf[j], j))
    unmask = np.zeros(len(x0), bool)
    unmask[lanes[:n_unmask]] = True
    return x0, conf, unmask


def generate(params, cfg, prompt, max_new, denoise_steps=None, eos_id=None,
             precision="stated"):
    """The greedy generation loop: ``(tokens, passes)``, the answered
    tokens and one record a denoise pass (``pos``, ``input``, ``masked``,
    ``ids``, ``confidence``, ``unmasked``)."""
    m = dims(cfg)
    b, mask_id = m["block"], m["mask_id"]
    steps = b if denoise_steps is None else int(denoise_steps)
    prompt = [int(t) for t in prompt]
    seq = prompt[:len(prompt) // b * b]
    known = prompt[len(seq):]
    out, passes = [], []
    while True:
        block = known + [mask_id] * (b - len(known))
        masked = [False] * len(known) + [True] * (b - len(known))
        while any(masked):
            rows = range(len(seq), len(seq) + b)
            x0, conf, unmask = choose(
                np.asarray(logits_at(params, cfg, seq + block, rows,
                                     precision)), masked, b // steps)
            passes.append({"pos": len(seq), "input": list(block),
                           "masked": list(masked),
                           "ids": [int(t) for t in x0],
                           "confidence": [float(c) for c in conf],
                           "unmasked": [bool(u) for u in unmask]})
            for j in np.flatnonzero(unmask):
                block[j], masked[j] = int(x0[j]), False
        for tok in block[len(known):]:
            out.append(tok)
            if len(out) >= max_new or tok == eos_id:
                return out, passes
        seq, known = seq + block, []


def pass_gaps(params, cfg, prompt, served, record, judged_by=None,
              first_topk=None):
    """One recorded denoise pass of a served request against this
    reference: the reference runs prompt + committed blocks + the pass's
    input block in one pass and reads, in ITS logits,

    - ``token_gaps``: for each lane the pass unmasked, how far the chosen
      token's logit lies under the best logit at its position;
    - ``order_gap``: log-confidence (log softmax of the chosen token) of
      the best masked lane left behind minus that of the least confident
      lane unmasked (None where either set is empty): negative where the
      pass unmasked in order, positive where it did not;
    - ``rank_gaps``: where ``first_topk`` is the program's order of its
      best k tokens at the pass's first masked lane, the gap between the
      reference's logit of the token ranked j-th and its own j-th best.

    With ``judged_by`` a lower precision, the choice judged is the one
    that pass makes from the same input (the control's)."""
    b = dims(cfg)["block"]
    pos = int(record["pos"])
    seq = (list(prompt) + list(served))[:pos] + list(record["input"])
    ref = np.asarray(logits_at(params, cfg, seq, range(pos, pos + b)),
                     np.float64)
    masked = list(record["masked"])
    lane0 = masked.index(True)
    if judged_by is None:
        ids = np.asarray(record["ids"])
        unmask = np.asarray(record["unmasked"], bool)
        ranked = None if first_topk is None else np.asarray(first_topk)
    else:
        low = np.asarray(logits_at(params, cfg, seq, range(pos, pos + b),
                                   judged_by))
        ids, _conf, unmask = choose(low, masked, int(sum(
            record["unmasked"])))
        ranked = (None if first_topk is None
                  else np.argsort(-low[lane0], kind="stable")[
                      :len(first_topk)])
    lanes = np.arange(b)
    chosen = ref[lanes, ids]
    logconf = chosen - (ref.max(axis=-1) + np.log(np.exp(
        ref - ref.max(axis=-1, keepdims=True)).sum(axis=-1)))
    left = np.asarray(masked, bool) & ~unmask
    out = {"token_gaps": (ref.max(axis=-1) - chosen)[unmask],
           "order_gap": (float(logconf[left].max() - logconf[unmask].min())
                         if left.any() and unmask.any() else None),
           "rank_gaps": None}
    if ranked is not None:
        best = -np.sort(-ref[lane0], kind="stable")[:len(ranked)]
        out["rank_gaps"] = best - ref[lane0][ranked]
    return out
