"""Operations and bytes of the `sdar_moe` family (a Qwen3-MoE block under
block diffusion), from shapes alone. A multiply-add is two operations. Only
ACTIVE parameters count: the experts a token is routed to, and the output
head only for the lanes that are unembedded.
"""


def dims(cfg):
    return {"d": int(cfg["hidden_size"]),
            "layers": int(cfg["num_hidden_layers"]),
            "heads": int(cfg["num_attention_heads"]),
            "kv_heads": int(cfg["num_key_value_heads"]),
            "head_dim": int(cfg["head_dim"]),
            "ffn": int(cfg["moe_intermediate_size"]),
            "experts": int(cfg["num_experts"]),
            "top_k": int(cfg["num_experts_per_tok"]),
            "vocab": int(cfg["vocab_size"])}


def param_count(cfg):
    m = dims(cfg)
    d, hd = m["d"], m["head_dim"]
    per_layer = (d * m["heads"] * hd * 2 + d * m["kv_heads"] * hd * 2
                 + d * m["experts"] + m["experts"] * 3 * d * m["ffn"]
                 + 2 * d + 2 * hd)
    return 2 * m["vocab"] * d + m["layers"] * per_layer + d


def kv_bytes_per_token(cfg, dtype_bytes=2):
    m = dims(cfg)
    return 2 * m["layers"] * m["kv_heads"] * m["head_dim"] * dtype_bytes


def lane_flops(cfg, keys, with_logits):
    """One lane through every layer with ``keys`` keys in view, and through
    the output head if it is unembedded."""
    m = dims(cfg)
    d, hd = m["d"], m["head_dim"]
    proj = 2 * (d * m["heads"] * hd * 2 + d * m["kv_heads"] * hd * 2)
    moe = 2 * d * m["experts"] + m["top_k"] * 2 * 3 * d * m["ffn"]
    attn = 2 * 2 * keys * m["heads"] * hd
    head = 2 * d * m["vocab"] if with_logits else 0
    return m["layers"] * (proj + moe + attn) + head


def prefill_flops(cfg, start, stop, block):
    """Prompt positions [start, stop), whole blocks, no logits: a lane in
    block b sees the keys up to its block's end."""
    total = 0
    for first in range(start, stop, block):
        total += block * lane_flops(cfg, first + block, False)
    return total


def block_flops(cfg, first, block, denoise_steps):
    """One generated block at positions [first, first + block): its denoise
    passes and its commit pass, every one of `block` unembedded lanes."""
    return (denoise_steps + 1) * block * lane_flops(cfg, first + block, True)


def experts_call_cost(cfg, assignments, experts_touched, dtype_bytes=2):
    """(operations, bytes) of the grouped expert products of one step, all
    layers: ``assignments`` token-to-expert assignments (each three products
    of d x ffn) and the weights of the ``experts_touched`` expert matrices
    sets read once, plus the tokens in (d) and out (d)."""
    m = dims(cfg)
    per_expert = 3 * m["d"] * m["ffn"]
    flops = assignments * 2 * per_expert
    nbytes = (experts_touched * per_expert
              + assignments * 2 * m["d"]) * dtype_bytes
    return flops, nbytes


ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def attention_call_cost(cfg, q_tokens, kv_tokens, attn_pairs):
    """(operations, bytes) of ONE layer's attention in one step under the
    block mask: ``attn_pairs`` query-key pairs (the step's own count: a
    lane sees the keys up to its block's end), each a multiply-add into the
    scores and one into the output over ``head_dim`` for every query head;
    the K and V in view read once from the pools at the width the
    configuration states (``precision.kv_pools``), q in and the output out
    at the activations' width."""
    m = dims(cfg)
    pool = ITEMSIZE[cfg["precision"]["kv_pools"].split()[0]]
    act = ITEMSIZE[cfg["precision"]["activations_between_layers"].split()[0]]
    flops = 4 * m["heads"] * m["head_dim"] * attn_pairs
    nbytes = (2 * m["kv_heads"] * m["head_dim"] * kv_tokens * pool
              + 2 * m["heads"] * m["head_dim"] * q_tokens * act)
    return flops, nbytes
