"""Operations and bytes of the `afmoe` family (Trinity: window and full
attention layers, gated attention, a dense SwiGLU layer, sigmoid-routed
experts beside a shared one), from shapes alone. A multiply-add is two
operations. Only ACTIVE parameters count: the experts a token is routed to
and the shared one, and the output head only for the lanes that are
unembedded. A window layer's lane at position p sees ``min(p + 1,
sliding_window)`` keys, a full layer's ``p + 1``.
"""

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}
SLIDING = "sliding_attention"


def dims(cfg):
    types = list(cfg["layer_types"])
    return {"d": int(cfg["hidden_size"]),
            "layers": int(cfg["num_hidden_layers"]),
            "dense_layers": int(cfg["num_dense_layers"]),
            "window_layers": types.count(SLIDING),
            "full_layers": len(types) - types.count(SLIDING),
            "heads": int(cfg["num_attention_heads"]),
            "kv_heads": int(cfg["num_key_value_heads"]),
            "head_dim": int(cfg["head_dim"]),
            "dense_ffn": int(cfg["intermediate_size"]),
            "ffn": int(cfg["moe_intermediate_size"]),
            "experts": int(cfg["num_experts"]),
            "top_k": int(cfg["num_experts_per_tok"]),
            "shared": int(cfg.get("num_shared_experts", 0)),
            "window": int(cfg["sliding_window"]),
            "vocab": int(cfg["vocab_size"])}


def layer_params(cfg):
    """(attention with its six gains, dense FF, expert FF) parameters of a
    layer."""
    m = dims(cfg)
    d, hd = m["d"], m["head_dim"]
    attn = (d * m["heads"] * hd * 3 + d * m["kv_heads"] * hd * 2
            + 4 * d + 2 * hd)
    dense = 3 * d * m["dense_ffn"]
    moe = (d * m["experts"] + m["experts"]
           + (m["experts"] + m["shared"]) * 3 * d * m["ffn"])
    return attn, dense, moe


def param_count(cfg):
    m = dims(cfg)
    attn, dense, moe = layer_params(cfg)
    return (2 * m["vocab"] * m["d"] + m["d"] + m["layers"] * attn
            + m["dense_layers"] * dense
            + (m["layers"] - m["dense_layers"]) * moe)


def kv_bytes_per_token(cfg, dtype_bytes=2):
    """(a full layer's kind, a window layer's kind) bytes a token, all of
    the kind's layers."""
    m = dims(cfg)
    one = 2 * m["kv_heads"] * m["head_dim"] * dtype_bytes
    return m["full_layers"] * one, m["window_layers"] * one


def lane_matmul_flops(cfg):
    """One lane through every layer's products with weights (the attention
    itself apart)."""
    m = dims(cfg)
    d, hd = m["d"], m["head_dim"]
    proj = 2 * (d * m["heads"] * hd * 3 + d * m["kv_heads"] * hd * 2)
    dense = 2 * 3 * d * m["dense_ffn"]
    moe = (2 * d * m["experts"]
           + (m["top_k"] + m["shared"]) * 2 * 3 * d * m["ffn"])
    return (m["layers"] * proj + m["dense_layers"] * dense
            + (m["layers"] - m["dense_layers"]) * moe)


def span_flops(cfg, start, stop, logits):
    """Positions [start, stop) of one sequence, ``logits`` of them
    unembedded: a lane at position p has p + 1 keys in a full layer and
    min(p + 1, window) in a window layer."""
    m = dims(cfg)
    n = max(0, stop - start)
    if not n:
        return 0
    full_keys = (start + 1 + stop) * n // 2
    w = m["window"]
    low = min(max(w - start, 0), n)     # lanes that see fewer than a window
    window_keys = low * (2 * (start + 1) + low - 1) // 2 + w * (n - low)
    attn = 4 * m["heads"] * m["head_dim"] * (
        m["full_layers"] * full_keys + m["window_layers"] * window_keys)
    return (n * lane_matmul_flops(cfg) + attn
            + logits * 2 * m["d"] * m["vocab"])


def attention_call_cost(cfg, q_tokens, kv_tokens, attn_pairs):
    """(operations, bytes) of ONE layer's attention in one step: its
    query-key pairs (each a multiply-add into the scores and one into the
    output over ``head_dim`` for every query head); the K and V in view
    read once from the pool at the width the configuration states, q in
    and the output out at the activations' width. The caller gives a
    layer kind's own sums."""
    m = dims(cfg)
    pool = ITEMSIZE[cfg["precision"]["kv_pools"].split()[0]]
    act = ITEMSIZE[cfg["precision"]["activations_between_layers"].split()[0]]
    flops = 4 * m["heads"] * m["head_dim"] * attn_pairs
    nbytes = (2 * m["kv_heads"] * m["head_dim"] * kv_tokens * pool
              + 2 * m["heads"] * m["head_dim"] * q_tokens * act)
    return flops, nbytes


def attention_step_least_s(cfg, call, peaks):
    """The least seconds the chip could take over the attention of one
    step call, every layer: each kind's layers at the larger of that
    kind's operations/peak and bytes/bandwidth (``call`` holds the
    ``serving.decode.device_call`` span's sums by kind)."""
    m = dims(cfg)
    least = 0.0
    for kind in ("full", "window"):
        pairs = call.get("attn_pairs_" + kind)
        if not pairs:
            continue
        ops, nbytes = attention_call_cost(
            cfg, call["q_tokens"], call["kv_tokens_" + kind], pairs)
        least += m[kind + "_layers"] * max(
            ops / peaks["bf16_flops_per_s"],
            nbytes / peaks["hbm_bytes_per_s"])
    return least
