"""Operations and bytes of the `kimi_linear` family (Kimi-Linear: gated
delta-rule KDA layers, latent MLA layers, a dense SwiGLU layer,
sigmoid-routed experts beside a shared one, this chip holding a share of
them), from shapes alone. A multiply-add is two operations. Only ACTIVE
parameters count: the shared expert and, of a token's routed experts, the
share this chip holds (``num_experts_per_token`` times held over
published: what the chip computes on average), and the output head only
for the lanes that are unembedded. An MLA layer's lane at position p sees
``p + 1`` keys; a KDA layer's work a lane does not grow with p.
"""

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def dims(cfg):
    lin = cfg["linear_attn_config"]
    held = cfg.get("experts_held", (0, cfg["num_experts"]))
    return {"d": int(cfg["hidden_size"]),
            "layers": int(cfg["num_hidden_layers"]),
            "kda_layers": len(lin["kda_layers"]),
            "mla_layers": len(lin["full_attn_layers"]),
            "dense_layers": int(cfg["first_k_dense_replace"]),
            "heads": int(cfg["num_attention_heads"]),
            "kda_dim": int(lin["head_dim"]),
            "taps": int(lin["short_conv_kernel_size"]),
            "nope": int(cfg["qk_nope_head_dim"]),
            "rope": int(cfg["qk_rope_head_dim"]),
            "rank": int(cfg["kv_lora_rank"]),
            "v_dim": int(cfg["v_head_dim"]),
            "dense_ffn": int(cfg["intermediate_size"]),
            "ffn": int(cfg["moe_intermediate_size"]),
            "router": int(cfg.get("num_experts_published",
                                  cfg["num_experts"])),
            "held": int(held[1]) - int(held[0]),
            "top_k": int(cfg["num_experts_per_token"]),
            "shared": int(cfg.get("num_shared_experts", 0)),
            "vocab": int(cfg["vocab_size"])}


def layer_params(cfg):
    """(KDA mixer, MLA mixer, dense FF, expert FF at the held experts)
    parameters, gains and float32 vectors included."""
    m = dims(cfg)
    d, h, k = m["d"], m["heads"], m["kda_dim"]
    hk = h * k
    kda = (3 * d * hk + 3 * m["taps"] * hk + 2 * (d * k + k * hk) + hk
           + d * h + h + k + hk * d)
    mla = (d * h * (m["nope"] + m["rope"]) + d * (m["rank"] + m["rope"])
           + m["rank"] + m["rank"] * h * (m["nope"] + m["v_dim"])
           + h * m["v_dim"] * d)
    dense = 3 * d * m["dense_ffn"]
    moe = (d * m["router"] + m["router"]
           + (m["held"] + m["shared"]) * 3 * d * m["ffn"])
    return kda, mla, dense, moe


def param_count(cfg):
    m = dims(cfg)
    kda, mla, dense, moe = layer_params(cfg)
    return (2 * m["vocab"] * m["d"] + m["d"] + m["layers"] * 2 * m["d"]
            + m["kda_layers"] * kda + m["mla_layers"] * mla
            + m["dense_layers"] * dense
            + (m["layers"] - m["dense_layers"]) * moe)


def latent_bytes_per_token(cfg, dtype_bytes=2):
    """The latent entry a token, every MLA layer: ``kv_lora_rank +
    qk_rope_head_dim`` values."""
    m = dims(cfg)
    return m["mla_layers"] * (m["rank"] + m["rope"]) * dtype_bytes


def state_bytes_per_sequence(cfg, act_bytes=2):
    """A sequence's state, every KDA layer: ``S [H, K, K]`` float32 and
    the convolutions' last ``taps - 1`` inputs of q, k and v."""
    m = dims(cfg)
    hk = m["heads"] * m["kda_dim"]
    return m["kda_layers"] * (hk * m["kda_dim"] * 4
                              + (m["taps"] - 1) * 3 * hk * act_bytes)


def recurrence_flops(cfg):
    """One lane of one head of the delta rule: the decay, ``S^T k``, the
    rank-one update and ``S^T q`` over the ``K x V`` state."""
    k = dims(cfg)["kda_dim"]
    return 7 * k * k


def lane_matmul_flops(cfg):
    """One lane through every layer: the products with weights, the
    convolutions and the KDA recurrence (MLA's attention itself apart)."""
    m = dims(cfg)
    d, h, k = m["d"], m["heads"], m["kda_dim"]
    hk = h * k
    kda = (2 * (3 * d * hk + 2 * (d * k + k * hk) + d * h + hk * d)
           + 2 * m["taps"] * 3 * hk + h * recurrence_flops(cfg))
    mla = 2 * (d * h * (m["nope"] + m["rope"]) + d * (m["rank"] + m["rope"])
               + h * m["nope"] * m["rank"] + h * m["rank"] * m["v_dim"]
               + h * m["v_dim"] * d)
    dense = 2 * 3 * d * m["dense_ffn"]
    active = m["top_k"] * m["held"] / m["router"] + m["shared"]
    moe = 2 * d * m["router"] + active * 2 * 3 * d * m["ffn"]
    return (m["kda_layers"] * kda + m["mla_layers"] * mla
            + m["dense_layers"] * dense
            + (m["layers"] - m["dense_layers"]) * moe)


def latent_pair_flops(cfg):
    """One query-key pair of one MLA layer, every head: the score over the
    latent entry and the value over its first ``kv_lora_rank`` columns."""
    m = dims(cfg)
    return 2 * m["heads"] * (m["rank"] + m["rope"] + m["rank"])


def span_flops(cfg, start, stop, logits):
    """Positions [start, stop) of one sequence, ``logits`` of them
    unembedded: a lane at position p has p + 1 keys in an MLA layer."""
    m = dims(cfg)
    n = max(0, stop - start)
    if not n:
        return 0
    keys = (start + 1 + stop) * n // 2
    return (n * lane_matmul_flops(cfg)
            + m["mla_layers"] * latent_pair_flops(cfg) * keys
            + logits * 2 * m["d"] * m["vocab"])


def kda_call_cost(cfg, state_rows, q_tokens):
    """(operations, bytes) of ONE KDA layer's recurrence in one step call:
    ``state_rows`` live rows' state read and written once, ``q_tokens``
    live lanes' q, k, v, g (float32) and beta in and o out, and the
    recurrence over every live lane."""
    m = dims(cfg)
    h, k = m["heads"], m["kda_dim"]
    state = h * k * k * 4
    lane = (4 * h * k + h + h * k) * 4
    return (q_tokens * h * recurrence_flops(cfg),
            2 * state_rows * state + q_tokens * lane)


def latent_call_cost(cfg, q_tokens, kv_tokens, attn_pairs):
    """(operations, bytes) of ONE MLA layer's attention in one step call:
    its query-key pairs (``latent_pair_flops`` each); the latent entries
    in view read once from the pool at the width the configuration states
    (``precision.latent_pool``), the absorbed queries in and the outputs
    out at the activations' width."""
    m = dims(cfg)
    pool = ITEMSIZE[cfg["precision"]["latent_pool"].split()[0]]
    act = ITEMSIZE[cfg["precision"]["activations_between_layers"].split()[0]]
    entry = m["rank"] + m["rope"]
    return (latent_pair_flops(cfg) * attn_pairs,
            entry * kv_tokens * pool
            + m["heads"] * (entry + m["rank"]) * q_tokens * act)


def least_s(cost, peaks):
    """The least seconds an (operations, bytes) pair takes on the chip."""
    ops, nbytes = cost
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
