"""Operations and bytes that the measured work needs, from shapes alone.

A multiply-add is two operations. Only what the algorithm needs counts:
padding lanes, recomputation and dead slots do not.
"""


# --- the decoder (pre-LayerNorm, tied embeddings, dense 4x MLP) -----------

def decoder_dims(cfg):
    d = int(cfg["d_model"])
    return {"d": d, "layers": int(cfg["num_layers"]),
            "heads": int(cfg["attention_heads"]),
            "kv_heads": int(cfg.get("kv_heads", cfg["attention_heads"])),
            "head_dim": d // int(cfg["attention_heads"]),
            "ffn": int(cfg["ffn_dim"]), "vocab": int(cfg["vocab_size"])}


def decoder_param_count(cfg):
    m = decoder_dims(cfg)
    d, hd = m["d"], m["head_dim"]
    per_layer = (d * m["heads"] * hd * 2          # wq, wo
                 + d * m["kv_heads"] * hd * 2     # wk, wv
                 + 2 * d * m["ffn"]               # w1, w2
                 + 4 * d)                         # two LayerNorms
    return m["vocab"] * d + m["layers"] * per_layer + 2 * d


def kv_bytes_per_token(cfg, dtype_bytes=4):
    m = decoder_dims(cfg)
    return 2 * m["layers"] * m["kv_heads"] * m["head_dim"] * dtype_bytes


def decoder_token_flops(cfg, kv_len, with_logits):
    """One token through every layer with ``kv_len`` keys in view (itself
    included), and through the output head if its logits are used."""
    m = decoder_dims(cfg)
    d, hd = m["d"], m["head_dim"]
    proj = 2 * (d * m["heads"] * hd * 2 + d * m["kv_heads"] * hd * 2)
    mlp = 2 * 2 * d * m["ffn"]
    attn = 2 * 2 * kv_len * m["heads"] * hd
    head = 2 * d * m["vocab"] if with_logits else 0
    return m["layers"] * (proj + mlp + attn) + head


def decoder_span_flops(cfg, start, stop, logits):
    """Tokens at positions [start, stop) of one sequence, ``logits`` of
    them through the output head."""
    m = decoder_dims(cfg)
    n = stop - start
    if n <= 0:
        return 0
    base = decoder_token_flops(cfg, 0, False)
    keys = (start + 1 + stop) * n // 2          # sum of kv_len over them
    return (n * base + m["layers"] * 4 * keys * m["heads"] * m["head_dim"]
            + logits * 2 * m["d"] * m["vocab"])


def paged_attention_call_cost(q_lens, kv_lens, heads, kv_heads, head_dim,
                              dtype_bytes=4):
    """(operations, bytes) one call of the paged attention kernel needs:
    each live slot's ``q_len`` newest tokens attend causally to its
    ``kv_len`` keys; K and V rows are read once, q read and out written."""
    flops = nbytes = 0
    for q, kv in zip(q_lens, kv_lens):
        q, kv = int(q), int(kv)
        if q <= 0 or kv <= 0:
            continue
        keys = (kv - q + 1 + kv) * q // 2
        flops += 2 * 2 * keys * heads * head_dim
        nbytes += (2 * kv * kv_heads * head_dim
                   + 2 * q * heads * head_dim) * dtype_bytes
    return flops, nbytes


# --- ResNet (bottleneck, ImageNet layout) ----------------------------------

def resnet_layers(cfg):
    """Every convolution and the classifier of the network as
    (name, cin, cout, k, stride, hin), in the order they run."""
    stages = {50: [3, 4, 6, 3], 101: [3, 4, 23, 3], 152: [3, 8, 36, 3]}[
        int(cfg["depth"])]
    size = int(cfg["image"][1])
    layers = [("conv1", int(cfg["image"][0]), 64, 7, 2, size)]
    size = -(-size // 2)          # conv1, stride 2
    size = -(-size // 2)          # max pool 3x3, stride 2
    cin = 64
    for s, (count, width) in enumerate(zip(stages, (64, 128, 256, 512))):
        for b in range(count):
            stride = 2 if (b == 0 and s > 0) else 1
            name = f"res{s + 2}.{b}"
            if cin != width * 4:
                layers.append((name + ".short", cin, width * 4, 1, stride,
                               size))
            layers.append((name + ".a", cin, width, 1, stride, size))
            out = -(-size // stride)
            layers.append((name + ".b", width, width, 3, 1, out))
            layers.append((name + ".c", width, width * 4, 1, 1, out))
            cin, size = width * 4, out
    layers.append(("fc", cin, int(cfg["class_dim"]), 1, 1, 1))
    return layers


def resnet_forward_flops(cfg):
    """Forward operations an image: convolutions and the classifier."""
    total = 0
    for _name, cin, cout, k, stride, hin in resnet_layers(cfg):
        hout = -(-hin // stride)
        total += 2 * cin * cout * k * k * hout * hout
    return total


def resnet_train_flops(cfg):
    """Forward and backward an image: each layer's backward costs its
    forward twice (input and weight gradients), but the first convolution
    needs no input gradient."""
    fwd = resnet_forward_flops(cfg)
    _n, cin, cout, k, stride, hin = resnet_layers(cfg)[0]
    hout = -(-hin // stride)
    return 3 * fwd - 2 * cin * cout * k * k * hout * hout
