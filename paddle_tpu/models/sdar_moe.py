"""The ``sdar_moe`` family as a served model (ISSUE 30): a Qwen3-MoE
block (RMSNorm, grouped-query attention with per-head QK-norm and rotary
positions, a dropless top-k-of-E SwiGLU expert layer in every layer, an
untied head) that generates by DIFFUSION OVER BLOCKS of ``block_length``
tokens (SDAR): attention is causal between blocks and both ways inside
one, a block starts as ``mask_token_id`` where its tokens are not known,
and each denoise pass unmasks its most confident lanes.

The spec reads the public ``config.json`` keys (``from_config``). The
step has ``decoder_step_chunked``'s signature and runs under the same
engine, page tables and paged attention kernel as the dense decoder
(``models/decoders.py`` says what the engine asks of a model).

Precision, as the configuration states it: weights, the activations
between layers and the K/V pools in bfloat16; every product accumulates
in float32; RMSNorm statistics, rotary angles, the router's logits and
softmax, and the final logits in float32. K and V go to the pool AFTER
QK-norm and rotary. The experts are DROPLESS: the ``T * k`` assignments
are sorted by expert, grouped products run over the ragged groups
(``pallas_kernels/moe_gmm.py``: the Pallas kernel on a TPU,
``jax.lax.ragged_dot`` elsewhere), and the results are scatter-added back
with the renormalised weights. There is no capacity, no dropped token and no
``[T, E, C]`` one-hot (``parallel/moe.py`` stays what the Fluid training
path uses).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import numpy as np

__all__ = ["SdarMoeSpec", "sdar_moe_step", "moe_layer", "softmax_scores",
           "TINY_CONFIG"]

# the preset the CPU tests run: every mechanism at toy widths
TINY_CONFIG = {
    "model_type": "sdar_moe", "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000, "vocab_size": 128,
    "tie_word_embeddings": False, "attention_bias": False,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
}


class SdarMoeSpec:
    """Architecture + identity of one ``sdar_moe`` model. Parameters are
    deterministic in ``seed`` (``seeded_arrays``)."""

    _FIELDS = ("vocab", "d_model", "n_layers", "n_heads", "n_kv_heads",
               "head_dim", "expert_width", "n_experts",
               "experts_per_token", "norm_topk_prob", "rms_eps",
               "rope_theta", "block_length", "mask_token_id", "dtype",
               "seed", "eos_id")
    __slots__ = _FIELDS
    family = "sdar_moe"

    def __init__(self, vocab: int, d_model: int, n_layers: int,
                 n_heads: int, n_kv_heads: int, head_dim: int,
                 expert_width: int, n_experts: int,
                 experts_per_token: int, norm_topk_prob: bool = True,
                 rms_eps: float = 1e-6, rope_theta: float = 1e6,
                 block_length: int = 4,
                 mask_token_id: Optional[int] = None,
                 dtype: str = "bfloat16", seed: int = 0,
                 eos_id: Optional[int] = None):
        self.vocab, self.d_model = int(vocab), int(d_model)
        self.n_layers = int(n_layers)
        self.n_heads, self.n_kv_heads = int(n_heads), int(n_kv_heads)
        self.head_dim = int(head_dim)
        self.expert_width = int(expert_width)
        self.n_experts = int(n_experts)
        self.experts_per_token = int(experts_per_token)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.rms_eps, self.rope_theta = float(rms_eps), float(rope_theta)
        self.block_length = int(block_length)
        # the card's mask id lies inside the vocabulary; a model cut to a
        # smaller one takes its last id
        self.mask_token_id = int(self.vocab - 1 if mask_token_id is None
                                 else mask_token_id)
        # the ONE stated dtype of weights, activations between layers and
        # K/V pools (the published bfloat16; float32 is the CPU tests')
        self.dtype = str(dtype)
        if self.dtype not in ("bfloat16", "float32"):
            raise ValueError(f"dtype must be 'bfloat16' or 'float32', "
                             f"got {dtype!r}")
        self.seed = int(seed)
        self.eos_id = None if eos_id is None else int(eos_id)
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"n_heads {n_heads} not a multiple of "
                             f"n_kv_heads {n_kv_heads}")
        if self.head_dim % 2:
            raise ValueError(f"head_dim {head_dim} must be even (rotary "
                             f"positions pair its halves)")
        if not 1 <= self.experts_per_token <= self.n_experts:
            raise ValueError(
                f"experts_per_token {experts_per_token} outside "
                f"[1, n_experts {n_experts}]")
        if self.block_length < 1:
            raise ValueError(f"block_length must be >= 1, got "
                             f"{block_length}")
        if not 0 <= self.mask_token_id < self.vocab:
            raise ValueError(f"mask_token_id {self.mask_token_id} outside "
                             f"the vocabulary [0, {self.vocab})")

    @classmethod
    def from_config(cls, cfg: Dict[str, Any], *, block_length: int = 4,
                    mask_token_id: Optional[int] = None,
                    dtype: Optional[str] = None, seed: int = 0,
                    eos_id: Optional[int] = None) -> "SdarMoeSpec":
        """From the keys of the model's public ``config.json``; what the
        config does not give (block length, mask id) is the caller's."""
        if cfg.get("model_type", cls.family) != cls.family:
            raise ValueError(f"model_type {cfg.get('model_type')!r} is "
                             f"not {cls.family!r}")
        for key, want in (("tie_word_embeddings", False),
                          ("attention_bias", False),
                          ("decoder_sparse_step", 1),
                          ("mlp_only_layers", [])):
            if cfg.get(key, want) != want:
                raise ValueError(
                    f"config key {key!r} is {cfg[key]!r}; this family's "
                    f"step is written for {want!r}")
        if cfg.get("rope_scaling") is not None:
            raise ValueError("rope_scaling is not supported (the "
                             "published config has none)")
        return cls(
            vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
            n_layers=cfg["num_hidden_layers"],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim", cfg["hidden_size"]
                             // cfg["num_attention_heads"]),
            expert_width=cfg["moe_intermediate_size"],
            n_experts=cfg["num_experts"],
            experts_per_token=cfg["num_experts_per_tok"],
            norm_topk_prob=cfg.get("norm_topk_prob", True),
            rms_eps=cfg.get("rms_norm_eps", 1e-6),
            rope_theta=cfg.get("rope_theta", 1e6),
            block_length=block_length, mask_token_id=mask_token_id,
            dtype=dtype or cfg.get("torch_dtype", "bfloat16"),
            seed=seed, eos_id=eos_id)

    @property
    def moe_assignments_per_token(self) -> int:
        return self.experts_per_token * self.n_layers

    window = None               # every layer keeps every key

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        from .decoders import all_full

        return all_full(self.n_layers)

    @property
    def param_dtype(self) -> str:
        return self.dtype

    @property
    def pool_dtype(self) -> str:
        return self.dtype

    def to_dict(self) -> Dict[str, Any]:
        return dict({k: getattr(self, k) for k in self._FIELDS},
                    family=self.family)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "SdarMoeSpec":
        unknown = sorted(set(d) - set(cls._FIELDS) - {"family"})
        if unknown:
            raise ValueError(f"unknown SdarMoeSpec field(s) {unknown}; "
                             f"valid: {sorted(cls._FIELDS)}")
        return cls(**{k: v for k, v in d.items() if k != "family"})

    def tensors(self) -> Dict[str, Tuple[int, ...]]:
        d, dh, f = self.d_model, self.head_dim, self.expert_width
        e = self.n_experts
        out: Dict[str, Tuple[int, ...]] = {
            "tok_emb": (self.vocab, d), "lnf": (d,),
            "head": (d, self.vocab)}
        for l in range(self.n_layers):
            p = f"layer{l}"
            out.update({
                f"{p}/ln1": (d,), f"{p}/wq": (d, self.n_heads * dh),
                f"{p}/wk": (d, self.n_kv_heads * dh),
                f"{p}/wv": (d, self.n_kv_heads * dh),
                f"{p}/wo": (self.n_heads * dh, d),
                f"{p}/q_norm": (dh,), f"{p}/k_norm": (dh,),
                f"{p}/ln2": (d,), f"{p}/router": (d, e),
                f"{p}/gate": (e, d, f), f"{p}/up": (e, d, f),
                f"{p}/down": (e, f, d)})
        return out

    def _tree(self, leaf) -> Dict[str, Any]:
        """The nested parameter tree, each leaf ``leaf(index, name, shape,
        fan_in)``; fan_in 0 marks a gain."""
        tree: Dict[str, Any] = {}
        for i, (name, shape) in enumerate(self.tensors().items()):
            fan_in = (0 if len(shape) == 1 else
                      shape[-1] if name == "tok_emb" else shape[-2])
            node, parts = tree, name.split("/")
            for part in parts[:-1]:
                node = node.setdefault(part, {})
            node[parts[-1]] = leaf(i, name, shape, fan_in)
        return tree

    def seeded_arrays(self) -> Dict[str, Any]:
        """The deterministic tree as host arrays in the served dtype:
        every matrix normal with std 1/sqrt(fan_in), every gain 1. With
        unit-RMS inputs that makes the router's logits and the final
        logits N(0, 1): top-k is decided by gaps of order 0.1, not by
        rounding."""
        import jax.numpy as jnp

        dtype = jnp.dtype(self.dtype)
        rng = np.random.RandomState(self.seed)

        def leaf(_i, _name, shape, fan_in):
            if not fan_in:
                return np.ones(shape, np.float32).astype(dtype)
            return (rng.randn(*shape) / math.sqrt(fan_in)).astype(dtype)

        return self._tree(leaf)

    def device_arrays(self, seed: Optional[int] = None) -> Dict[str, Any]:
        """The same tree drawn ON THE DEVICE, leaf by leaf, in the served
        dtype (another generator than ``seeded_arrays``: the same
        distribution, not the same values): what a benchmark of 4 B
        parameters loads in seconds instead of drawing on the host."""
        import jax
        import jax.numpy as jnp

        dtype = jnp.dtype(self.dtype)
        root = jax.random.key(int(self.seed if seed is None else seed)
                              % (2 ** 63))

        @functools.partial(jax.jit, static_argnums=(1, 2))
        def draw(key, shape, fan_in):
            if not fan_in:
                return jnp.ones(shape, dtype)
            return (jax.random.normal(key, shape, jnp.float32)
                    / math.sqrt(fan_in)).astype(dtype)

        return self._tree(lambda i, _name, shape, fan_in: draw(
            jax.random.fold_in(root, i), shape, fan_in))

    def step(self, params, tokens, positions, q_lens, k_pool, v_pool,
             page_tables, kv_lens, *, all_lanes: bool = False,
             return_hidden: bool = False,
             attention_impl: Optional[str] = None,
             garbage_page: int = 0):
        if all_lanes or return_hidden:
            raise ValueError(
                "a block model has no all-lane / hidden-state form: "
                "speculation and the embed lane are causal models' "
                "(block_length 1)")
        return sdar_moe_step(params, self, tokens, positions, q_lens,
                             k_pool, v_pool, page_tables, kv_lens,
                             attention_impl=attention_impl,
                             garbage_page=garbage_page)


def _rms(x, gain, eps):
    """RMSNorm with float32 statistics; float32 out."""
    import jax
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return xf * jax.lax.rsqrt(ms + eps) * gain.astype(jnp.float32)


def _rotary(x, cos, sin):
    """Rotary positions on the whole head dimension, rotate-half
    pairing (lane i with lane i + D/2). ``x`` [..., H, D] float32,
    ``cos``/``sin`` [..., 1, D/2]."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _dot(a, w):
    """``a @ w`` with float32 accumulation and a float32 result."""
    import jax.numpy as jnp

    return jnp.dot(a, w, preferred_element_type=jnp.float32)


def softmax_scores(h, lp, spec: SdarMoeSpec):
    """This family's scoring of ``moe_layer``: softmax over the router's
    float32 logits, the ``experts_per_token`` largest, renormalised to sum
    to 1 under ``norm_topk_prob``. ``(w [T, k] float32, idx [T, k])``."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(_dot(h, lp["router"]), axis=-1)   # f32
    w, idx = jax.lax.top_k(probs, spec.experts_per_token)    # [T, k]
    if spec.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, idx


def moe_layer(h, lp, valid, spec, impl: Optional[str] = None, *,
              score=softmax_scores):
    """THE dropless expert layer of every served family, over ``h`` [T,
    d] (the normed hidden states, in the weights' dtype): ``(out [T, d]
    float32, counts [E] int32)``. The MODEL supplies the scoring
    (``score(h, lp, spec) -> (w [T, k] float32, idx [T, k] int32)``: which
    ``experts_per_token`` of ``n_experts`` a token meets and how each
    weighs; ``softmax_scores`` here, ``afmoe.sigmoid_scores``); the sort,
    the grouped products and the scatter-add are one. ``valid`` [T] marks
    the live lanes; a dead lane is routed nowhere, weighs nothing and is
    not counted. ``counts`` sums to ``valid.sum() * experts_per_token``.
    ``impl`` is the step's ``attention_impl``: ``"reference"`` names XLA's
    grouped product as it names the attention's reference
    (``moe_gmm.moe_route``)."""
    import jax
    import jax.numpy as jnp

    from ..fluid.ops.pallas_kernels.moe_gmm import grouped_dot

    t = h.shape[0]
    e, k = spec.n_experts, spec.experts_per_token
    with jax.named_scope("decoder.moe.route"):
        w, idx = score(h, lp, spec)
        # dead lanes sort behind every expert's group (sentinel E)
        idx = jnp.where(valid[:, None], idx, e).reshape(t * k)
        w = jnp.where(valid[:, None], w, 0.0).reshape(t * k)
        order = jnp.argsort(idx, stable=True)
        counts = jnp.zeros((e + 1,), jnp.int32).at[idx].add(1)[:e]
        token = order // k                                   # [T*k]
        routed = idx[order] < e
        xs = h[token]
    with jax.named_scope("decoder.moe.experts"):
        # grouped products over the ragged groups: rows [sum(counts[:j]),
        # sum(counts[:j+1])) meet expert j's matrices. The gated product
        # silu(gate) * up is rounded to the weights' dtype before down
        act = grouped_dot(xs, lp["up"], counts, gate=lp["gate"], impl=impl)
        y = grouped_dot(act, lp["down"], counts, impl=impl)  # [T*k, d] f32
    with jax.named_scope("decoder.moe.combine"):
        # rows behind the last group belong to no expert and hold whatever
        # the product left there: exact zeros from here on
        y = jnp.where(routed[:, None], y * w[order][:, None], 0.0)
        out = jnp.zeros((t, h.shape[1]), jnp.float32).at[token].add(y)
    return out, counts


def sdar_moe_step(params, spec: SdarMoeSpec, tokens, positions, q_lens,
                  k_pool, v_pool, page_tables, kv_lens, *,
                  attention_impl: Optional[str] = None,
                  garbage_page: int = 0):
    """ONE mixed step of a fixed-slot batch: each slot carries up to C
    tokens of its sequence (a prefill chunk of whole blocks, or the B
    lanes of a denoise or a commit pass), whole blocks starting at a
    multiple of B. Writes every valid lane's K/V into the paged pools
    (write-before-attend), attends under the block mask (lane at
    position ``i`` sees key ``j`` iff ``j < (i // B + 1) * B`` and ``j <
    kv_len``) and returns ``(k_pool, v_pool, logits [slots, B, vocab]
    float32, {"expert_counts": [layers, E] int32})``: logits of each
    slot's FIRST B lanes only (a block pass's; a prefill chunk's are
    garbage nobody reads, as under a causal model), lane ``i`` predicting
    the token AT ``i`` (no shift). Dead lanes write ``garbage_page``, the
    page the pools' owner keeps for them (the engine passes its cache's).
    ``attention_impl`` routes BOTH Mosaic kernels of the step:
    ``"reference"`` (the engine under a mesh) names the attention's
    reference and the experts' ``ragged_dot``; ``None`` lets the flags,
    the backend and the widths decide."""
    import jax
    import jax.numpy as jnp

    from ..fluid.ops.pallas_kernels.paged_attention import paged_attention

    b, c = tokens.shape
    bl = spec.block_length
    ps = k_pool.shape[2]
    dh, nh, nkv = spec.head_dim, spec.n_heads, spec.n_kv_heads
    act = params["tok_emb"].dtype
    with jax.named_scope("decoder.embed"):
        lane = jnp.arange(c)[None, :]
        valid = lane < q_lens[:, None]                       # [B, C]
        x = params["tok_emb"][tokens]                        # not scaled
        page = jnp.where(
            valid, jnp.take_along_axis(page_tables, positions // ps,
                                       axis=1), garbage_page)
        off = jnp.where(valid, positions % ps, 0)
        inv = jnp.exp(-math.log(spec.rope_theta)
                      * jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
        ang = positions.astype(jnp.float32)[..., None] * inv  # [B,C,D/2]
        cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    counts = []
    for l in range(spec.n_layers):
        lp = params[f"layer{l}"]
        with jax.named_scope("decoder.attn"):
            h = _rms(x, lp["ln1"], spec.rms_eps).astype(act)
            q = _dot(h, lp["wq"]).reshape(b, c, nh, dh)
            k = _dot(h, lp["wk"]).reshape(b, c, nkv, dh)
            v = _dot(h, lp["wv"]).reshape(b, c, nkv, dh)
            q = _rotary(_rms(q, lp["q_norm"], spec.rms_eps), cos,
                        sin).astype(act)
            k = _rotary(_rms(k, lp["k_norm"], spec.rms_eps), cos, sin)
        with jax.named_scope("decoder.kv_write"):
            k_pool = k_pool.at[l, page, off].set(k.astype(k_pool.dtype))
            v_pool = v_pool.at[l, page, off].set(v.astype(v_pool.dtype))
        with jax.named_scope("decoder.attn"):
            attn = paged_attention(q, k_pool[l], v_pool[l], page_tables,
                                   kv_lens, q_lens=q_lens,
                                   block_length=bl, impl=attention_impl)
            x = (x.astype(jnp.float32)
                 + _dot(attn.reshape(b, c, nh * dh), lp["wo"])
                 ).astype(act)
        h2 = _rms(x, lp["ln2"], spec.rms_eps).astype(act)
        out, n = moe_layer(h2.reshape(b * c, -1), lp, valid.reshape(-1),
                           spec, impl=attention_impl)
        counts.append(n)
        x = (x.astype(jnp.float32) + out.reshape(b, c, -1)).astype(act)
    with jax.named_scope("decoder.head"):
        hb = _rms(x[:, :bl], params["lnf"], spec.rms_eps).astype(act)
        logits = _dot(hb, params["head"])                    # [B, bl, V]
    return k_pool, v_pool, logits, {"expert_counts": jnp.stack(counts)}
