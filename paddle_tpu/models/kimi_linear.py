"""The ``kimi_linear`` family as a served model (Moonshot's Kimi-Linear):
gated delta-rule LINEAR attention layers (KDA) whose per-sequence state has
a fixed size, beside latent attention layers (MLA) whose cache is one
compressed entry a token, a leading dense SwiGLU layer and sigmoid-routed
experts beside a shared one, of which this chip may hold a share.

A layer, pre-norm, no sandwich norms and no embedding scale::

    x = x + Mix(RMSNorm(x; ln_in))
    x = x + FF(RMSNorm(x; ln_ff))

``Mix`` of a KDA layer (``linear_attn_config.kda_layers``), per head of
``head_dim`` K = V: ``q, k, v = SiLU(conv4(x W_{q,k,v}))`` (a causal
depthwise convolution of ``short_conv_kernel_size`` taps a channel), q and
k L2-normed per head, q scaled by ``K^-1/2``; ``beta = sigmoid(x W_b)`` a
head; the per-channel log-decay ``g = -exp(A_log_h) softplus(x W_fa W_fb +
dt_bias)``; per head the state ``S [K, V]`` (float32) does ``S <- Diag(exp
g_t) S; S <- S + beta_t k_t (v_t - S^T k_t)^T; o_t = S^T q_t`` lane by lane
(``pallas_kernels/kda_recurrent.py``); out ``(RMSNorm_V(o; o_norm) *
sigmoid(x W_ga W_gb)) W_o``. A sequence keeps, a KDA layer, its S and the
last ``conv - 1`` inputs of each convolution: the engine's STATE kind.

``Mix`` of an MLA layer (``full_attn_layers``; ``q_lora_rank`` null,
``mla_use_nope``: no rotary anywhere): ``q = x W_q`` a head ``[q_nope;
q_pe]``, ``[c; k_pe] = x W_kva`` with ``c <- RMSNorm(c; kv_norm)``, ``[k_nope;
v] = c W_kvb`` a head; score ``(q_nope . k_nope + q_pe . k_pe) /
sqrt(nope + rope)``, causal; out ``W_o``. With no rotation the ABSORBED form
is exact: the cache holds ``[c; k_pe]`` (``kv_lora_rank + qk_rope_head_dim``
values a token, one array, no V), the query is ``[W_uk_h q_nope; q_pe]`` a
head, attention is multi-query over that one key through the paged kernel,
its values are the key's first ``kv_lora_rank`` columns (the kernel's
``value_dim``: exact, since attention is linear in V) and each head's
output is ``a_h W_uv_h``.

``FF``: a SwiGLU of ``intermediate_size`` on the first
``first_k_dense_replace`` layers; on the rest ``Shared(h) + sum_e w_e
Expert_e(h)`` over the ``num_experts_per_token`` experts with the largest
``sigmoid(h W_r) + expert_bias`` (afmoe's ``sigmoid_scores``: the bias
selects and never weighs; ``moe_renormalize``, ``routed_scaling_factor``).
This chip may hold experts ``experts_held = [lo, hi)`` of the router's
``n_experts``: the router keeps its width and its top-k, and the layer
adds what the held experts give (``moe_layer(held=)``).

What the engine asks (``models/decoders.py``): ``layer_kinds`` is
``"state"`` for a KDA layer and ``"full"`` for an MLA layer; the full
kind's pool is ``[mla layers, P, ps, kv_lora_rank + rope]`` (zeros to
whole 128-lane rows: ``head_dim``) with ``pool_values`` False (one key a
token, no head axis, no V pool), and ``slot_state`` describes the state
kind's per-sequence arrays. The step takes ``k_pool = (latent pool, KDA
states [kda layers, R, H, V, K])``, ``v_pool = (None, conv tails [kda
layers, R, (conv - 1) 3 H K])`` and ``page_tables`` a ``StateTables``.

Precision, as the configuration states it: weights, activations between
layers and the latent pool in bfloat16; every product accumulates in
float32; the KDA state, the decay, beta, the L2 and RMS norms' statistics,
the router and the final logits in float32.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import numpy as np

from .afmoe import _swiglu, sigmoid_scores
from .sdar_moe import _dot, _rms, moe_layer

__all__ = ["KimiLinearSpec", "kimi_linear_step", "TINY_CONFIG"]

# the preset the CPU tests run, every mechanism at toy widths: the stage's
# pattern (KDA with the dense layer, KDA, KDA, MLA, KDA), half of 8
# experts held, a shared expert
TINY_CONFIG = {
    "model_type": "kimi_linear", "hidden_size": 64, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "num_experts": 4, "num_experts_published": 8, "experts_held": [0, 4],
    "num_experts_per_token": 2, "num_shared_experts": 1,
    "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
    "routed_scaling_factor": 2.446, "num_expert_group": 1, "topk_group": 1,
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "mla_use_nope": True,
    "linear_attn_config": {"full_attn_layers": [4], "head_dim": 16,
                           "kda_layers": [1, 2, 3, 5], "num_heads": 4,
                           "short_conv_kernel_size": 4},
    "rms_norm_eps": 1e-5, "rope_scaling": None,
    "tie_word_embeddings": False, "vocab_size": 128,
}

KDA, MLA = "state", "full"


class KimiLinearSpec:
    """Architecture + identity of one ``kimi_linear`` model. Parameters are
    deterministic in ``seed`` (``seeded_arrays``)."""

    _FIELDS = ("vocab", "d_model", "n_layers", "n_heads", "kda_head_dim",
               "conv_size", "kda_layers", "kv_lora_rank", "qk_nope_dim",
               "qk_rope_dim", "v_head_dim", "dense_width", "n_dense_layers",
               "expert_width", "n_experts", "experts_held",
               "experts_per_token", "route_norm", "route_scale", "rms_eps",
               "dtype", "seed", "eos_id")
    __slots__ = _FIELDS
    family = "kimi_linear"
    block_length = 1            # causal: one token a decoding pass
    mask_token_id = None
    window = None
    # the full kind's pool holds the latent entry alone: one array, no V
    pool_values = False
    # MLA is multi-query over the one latent key
    n_kv_heads = 1

    def __init__(self, vocab: int, d_model: int, n_layers: int,
                 n_heads: int, kda_head_dim: int, conv_size: int,
                 kda_layers: Tuple[int, ...], kv_lora_rank: int,
                 qk_nope_dim: int, qk_rope_dim: int, v_head_dim: int,
                 dense_width: int, n_dense_layers: int, expert_width: int,
                 n_experts: int, experts_per_token: int,
                 experts_held: Optional[Tuple[int, int]] = None,
                 route_norm: bool = True, route_scale: float = 1.0,
                 rms_eps: float = 1e-5, dtype: str = "bfloat16",
                 seed: int = 0, eos_id: Optional[int] = None):
        self.vocab, self.d_model = int(vocab), int(d_model)
        self.n_layers, self.n_heads = int(n_layers), int(n_heads)
        self.kda_head_dim, self.conv_size = int(kda_head_dim), int(conv_size)
        self.kda_layers = tuple(sorted(int(l) for l in kda_layers))
        self.kv_lora_rank = int(kv_lora_rank)
        self.qk_nope_dim, self.qk_rope_dim = int(qk_nope_dim), int(qk_rope_dim)
        self.v_head_dim = int(v_head_dim)
        self.dense_width = int(dense_width)
        self.n_dense_layers = int(n_dense_layers)
        self.expert_width = int(expert_width)
        self.n_experts = int(n_experts)
        self.experts_per_token = int(experts_per_token)
        held = (0, self.n_experts) if experts_held is None else experts_held
        self.experts_held = (int(held[0]), int(held[1]))
        self.route_norm = bool(route_norm)
        self.route_scale = float(route_scale)
        self.rms_eps = float(rms_eps)
        self.dtype = str(dtype)
        if self.dtype not in ("bfloat16", "float32"):
            raise ValueError(f"dtype must be 'bfloat16' or 'float32', "
                             f"got {dtype!r}")
        self.seed = int(seed)
        self.eos_id = None if eos_id is None else int(eos_id)
        if any(not 0 <= l < self.n_layers for l in self.kda_layers):
            raise ValueError(f"kda_layers {self.kda_layers} outside "
                             f"[0, n_layers {self.n_layers})")
        if not self.kda_layers or len(self.kda_layers) == self.n_layers:
            raise ValueError(
                "a kimi_linear model is served with both a KDA and an MLA "
                f"layer; got kda_layers {self.kda_layers} of "
                f"{self.n_layers}")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.n_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range inside [0, {self.n_experts})")
        if not 1 <= self.experts_per_token <= self.n_experts:
            raise ValueError(
                f"experts_per_token {experts_per_token} outside "
                f"[1, n_experts {n_experts}]")
        if not 0 <= self.n_dense_layers < self.n_layers:
            raise ValueError(f"n_dense_layers {n_dense_layers} outside "
                             f"[0, n_layers {n_layers})")
        if self.conv_size < 2:
            raise ValueError(f"conv_size {conv_size} must be >= 2")

    @classmethod
    def from_config(cls, cfg: Dict[str, Any], *,
                    dtype: Optional[str] = None, seed: int = 0,
                    eos_id: Optional[int] = None) -> "KimiLinearSpec":
        """From the keys of the model's public ``config.json`` (layer
        indices there count from 1). ``num_experts`` is what this chip
        holds; ``num_experts_published`` (the router's width) and
        ``experts_held`` (which of them) say the rest, and default to all
        of them. What the step is not written for is refused by name."""
        if cfg.get("model_type", cls.family) != cls.family:
            raise ValueError(f"model_type {cfg.get('model_type')!r} is "
                             f"not {cls.family!r}")
        for key, want in (("tie_word_embeddings", False),
                          ("moe_router_activation_func", "sigmoid"),
                          ("num_expert_group", 1), ("topk_group", 1),
                          ("num_shared_experts", 1), ("q_lora_rank", None),
                          ("mla_use_nope", True), ("moe_layer_freq", 1),
                          ("rope_scaling", None)):
            if cfg.get(key, want) != want:
                raise ValueError(
                    f"config key {key!r} is {cfg[key]!r}; this family's "
                    f"step is written for {want!r}")
        lin = cfg["linear_attn_config"]
        n = int(cfg["num_hidden_layers"])
        kda = [int(l) - 1 for l in lin["kda_layers"]]
        full = [int(l) - 1 for l in lin["full_attn_layers"]]
        if sorted(kda + full) != list(range(n)):
            raise ValueError(
                f"linear_attn_config's kda_layers {lin['kda_layers']} and "
                f"full_attn_layers {lin['full_attn_layers']} must name "
                f"each of layers 1..{n} once")
        if int(lin["num_heads"]) != int(cfg["num_attention_heads"]):
            raise ValueError("KDA and MLA layers are served with one head "
                             "count: linear_attn_config.num_heads "
                             f"{lin['num_heads']} != num_attention_heads "
                             f"{cfg['num_attention_heads']}")
        router = int(cfg.get("num_experts_published", cfg["num_experts"]))
        held = tuple(cfg.get("experts_held", (0, cfg["num_experts"])))
        if held[1] - held[0] != int(cfg["num_experts"]):
            raise ValueError(f"experts_held {list(held)} is not "
                             f"num_experts {cfg['num_experts']} wide")
        if int(cfg.get("v_head_dim", lin["head_dim"])) != int(
                cfg["qk_nope_head_dim"]):
            raise ValueError("the latent's head reads v_head_dim == "
                             "qk_nope_head_dim")
        return cls(
            vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
            n_layers=n, n_heads=cfg["num_attention_heads"],
            kda_head_dim=lin["head_dim"],
            conv_size=lin["short_conv_kernel_size"], kda_layers=kda,
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_dim=cfg["qk_nope_head_dim"],
            qk_rope_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            dense_width=cfg["intermediate_size"],
            n_dense_layers=cfg.get("first_k_dense_replace", 0),
            expert_width=cfg["moe_intermediate_size"], n_experts=router,
            experts_per_token=cfg["num_experts_per_token"],
            experts_held=held,
            route_norm=cfg.get("moe_renormalize", True),
            route_scale=cfg.get("routed_scaling_factor", 1.0),
            rms_eps=cfg.get("rms_norm_eps", 1e-5),
            dtype=dtype or cfg.get("torch_dtype", "bfloat16"),
            seed=seed, eos_id=eos_id)

    # -- what the engine asks (models/decoders.py) ------------------------
    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(KDA if l in self.kda_layers else MLA
                     for l in range(self.n_layers))

    @property
    def latent_dim(self) -> int:
        """The full kind's entry a token: the latent and the shared
        rotary-free key part (576 at the published widths)."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def head_dim(self) -> int:
        """The full kind's pool row a token: the entry and zeros to whole
        128-lane rows (640 at the published widths). A TPU's tiled layout
        pads a 576-wide row to 640 lanes whatever the array's shape says,
        and a pool whose last axis is 576 is laid out page axis minor,
        which the kernel cannot read a page at a time: it is turned to
        rows and back around every step."""
        return -(-self.latent_dim // 128) * 128

    @property
    def mla_layers(self) -> Tuple[int, ...]:
        return tuple(l for l in range(self.n_layers)
                     if l not in self.kda_layers)

    @property
    def slot_state(self) -> Tuple[Tuple[Tuple[int, ...], str], ...]:
        """The state kind's two arrays, ``(shape without the row axis,
        dtype)`` each, the row axis going second: every KDA layer's
        transposed state ``S^T [H, V, K]`` float32 and its convolutions'
        last ``conv - 1`` inputs ``[(conv - 1) * 3 H K]`` in the
        activations' dtype (input by input, each q's channels, then k's,
        then v's), flat so that the array's last axis is whole 128-lane
        rows."""
        n, h, d = len(self.kda_layers), self.n_heads, self.kda_head_dim
        return (((n, h, d, d), "float32"),
                ((n, (self.conv_size - 1) * 3 * h * d), self.dtype))

    @property
    def moe_assignments_per_token(self) -> int:
        return self.experts_per_token * (self.n_layers
                                         - self.n_dense_layers)

    @property
    def param_dtype(self) -> str:
        return self.dtype

    @property
    def pool_dtype(self) -> str:
        return self.dtype

    def to_dict(self) -> Dict[str, Any]:
        d = {k: getattr(self, k) for k in self._FIELDS}
        return dict(d, kda_layers=list(self.kda_layers),
                    experts_held=list(self.experts_held),
                    family=self.family)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "KimiLinearSpec":
        unknown = sorted(set(d) - set(cls._FIELDS) - {"family"})
        if unknown:
            raise ValueError(f"unknown KimiLinearSpec field(s) {unknown}; "
                             f"valid: {sorted(cls._FIELDS)}")
        return cls(**{k: v for k, v in d.items() if k != "family"})

    def tensors(self) -> Dict[str, Tuple[int, ...]]:
        d, h, kd = self.d_model, self.n_heads, self.kda_head_dim
        hk = h * kd
        lo, hi = self.experts_held
        out: Dict[str, Tuple[int, ...]] = {
            "tok_emb": (self.vocab, d), "lnf": (d,),
            "head": (d, self.vocab)}
        for l in range(self.n_layers):
            p = f"layer{l}"
            out.update({f"{p}/ln_in": (d,), f"{p}/ln_ff": (d,)})
            if l in self.kda_layers:
                out.update({
                    f"{p}/wq": (d, hk), f"{p}/wk": (d, hk),
                    f"{p}/wv": (d, hk), f"{p}/conv_q": (self.conv_size, hk),
                    f"{p}/conv_k": (self.conv_size, hk),
                    f"{p}/conv_v": (self.conv_size, hk),
                    f"{p}/f_a": (d, kd), f"{p}/f_b": (kd, hk),
                    f"{p}/dt_bias": (hk,), f"{p}/A_log": (h,),
                    f"{p}/wb": (d, h), f"{p}/g_a": (d, kd),
                    f"{p}/g_b": (kd, hk), f"{p}/o_norm": (kd,),
                    f"{p}/wo": (hk, d)})
            else:
                qd = self.qk_nope_dim + self.qk_rope_dim
                out.update({
                    f"{p}/wq": (d, h * qd),
                    f"{p}/wkva": (d, self.latent_dim),
                    f"{p}/kv_norm": (self.kv_lora_rank,),
                    f"{p}/wkvb": (self.kv_lora_rank,
                                  h * (self.qk_nope_dim + self.v_head_dim)),
                    f"{p}/wo": (h * self.v_head_dim, d)})
            if l < self.n_dense_layers:
                w = self.dense_width
                out.update({f"{p}/gate": (d, w), f"{p}/up": (d, w),
                            f"{p}/down": (w, d)})
            else:
                e, f = hi - lo, self.expert_width
                out.update({
                    f"{p}/router": (d, self.n_experts),
                    f"{p}/expert_bias": (self.n_experts,),
                    f"{p}/gate": (e, d, f), f"{p}/up": (e, d, f),
                    f"{p}/down": (e, f, d), f"{p}/shared_gate": (d, f),
                    f"{p}/shared_up": (d, f), f"{p}/shared_down": (f, d)})
        return out

    def _tree(self, leaf) -> Dict[str, Any]:
        """The nested parameter tree, each leaf ``leaf(index, shape,
        init)``: ``init`` is the fan-in of a matrix (normal, std
        1/sqrt(fan_in)), ``"one"`` a gain, ``"zero"`` a float32 bias
        (``expert_bias``, ``dt_bias``) and ``"a_log"`` the float32
        ``log(uniform(1, 16))`` of each head's decay rate."""
        tree: Dict[str, Any] = {}
        for i, (name, shape) in enumerate(self.tensors().items()):
            last = name.rsplit("/", 1)[-1]
            init = ("zero" if last in ("expert_bias", "dt_bias") else
                    "a_log" if last == "A_log" else
                    "one" if len(shape) == 1 else
                    shape[-1] if name == "tok_emb" else shape[-2])
            node, parts = tree, name.split("/")
            for part in parts[:-1]:
                node = node.setdefault(part, {})
            node[parts[-1]] = leaf(i, shape, init)
        return tree

    def seeded_arrays(self) -> Dict[str, Any]:
        """The deterministic tree as host arrays in the served dtype (the
        float32 buffers as float32)."""
        import jax.numpy as jnp

        dtype = jnp.dtype(self.dtype)
        rng = np.random.RandomState(self.seed)

        def leaf(_i, shape, init):
            if init == "zero":
                return np.zeros(shape, np.float32)
            if init == "a_log":
                return np.log(rng.uniform(1.0, 16.0, shape)).astype(
                    np.float32)
            if init == "one":
                return np.ones(shape, np.float32).astype(dtype)
            return (rng.randn(*shape) / math.sqrt(init)).astype(dtype)

        return self._tree(leaf)

    def device_arrays(self, seed: Optional[int] = None) -> Dict[str, Any]:
        """The same tree drawn ON THE DEVICE, leaf by leaf, in the served
        dtype (another generator than ``seeded_arrays``: the same
        distribution, not the same values), as ``AfmoeSpec`` has it."""
        import jax
        import jax.numpy as jnp

        dtype = jnp.dtype(self.dtype)
        root = jax.random.key(int(self.seed if seed is None else seed)
                              % (2 ** 63))

        @functools.partial(jax.jit, static_argnums=(1, 2))
        def draw(key, shape, init):
            if init == "zero":
                return jnp.zeros(shape, jnp.float32)
            if init == "a_log":
                return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                                  1.0, 16.0))
            if init == "one":
                return jnp.ones(shape, dtype)
            return (jax.random.normal(key, shape, jnp.float32)
                    / math.sqrt(init)).astype(dtype)

        return self._tree(lambda i, shape, init: draw(
            jax.random.fold_in(root, i), shape, init))

    def step(self, params, tokens, positions, q_lens, k_pool, v_pool,
             page_tables, kv_lens, *, all_lanes: bool = False,
             return_hidden: bool = False,
             attention_impl: Optional[str] = None,
             garbage_page: int = 0):
        return kimi_linear_step(
            params, self, tokens, positions, q_lens, k_pool, v_pool,
            page_tables, kv_lens, all_lanes=all_lanes,
            return_hidden=return_hidden, attention_impl=attention_impl,
            garbage_page=garbage_page)


def _short_conv(x, tail, w, q_lens, fresh):
    """The causal depthwise convolution of a chunk: ``x [B, C, Ch]`` the
    chunk's inputs, ``tail [B, K-1, Ch]`` the sequence's inputs before it
    (zeros where ``fresh``: the sequence starts here), ``w [K, Ch]``.
    Returns ``(y [B, C, Ch] float32, the new tail)``: the last K-1 inputs
    up to each row's ``q_len`` (a dead row's tail as it was)."""
    import jax.numpy as jnp

    taps = w.shape[0]
    c = x.shape[1]
    tail = jnp.where(fresh[:, None, None], jnp.zeros_like(tail), tail)
    xx = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    y = sum(xx[:, i:i + c].astype(jnp.float32) * w[i].astype(jnp.float32)
            for i in range(taps))
    at = q_lens[:, None] + jnp.arange(taps - 1)[None, :]
    return y, jnp.take_along_axis(xx, at[:, :, None], axis=1)


def _l2norm(x, eps=1e-6):
    import jax

    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


def _kda_mix(h, lp, spec: KimiLinearSpec, li, states, convs, rows, fresh,
             q_lens, impl):
    """One KDA layer's mixer over the normed ``h [B, C, d]``: ``(out [B, C,
    d] float32, states, convs)``, the state kind's arrays with layer ``li``
    carried past the chunk."""
    import jax
    import jax.numpy as jnp

    from ..fluid.ops.pallas_kernels.kda_recurrent import kda_recurrent

    b, c, _ = h.shape
    nh, kd = spec.n_heads, spec.kda_head_dim
    act = h.dtype
    live = q_lens > 0
    with jax.named_scope("decoder.kda"):
        fresh = (fresh > 0) & live
        x = jnp.concatenate([_dot(h, lp["wq"]), _dot(h, lp["wk"]),
                             _dot(h, lp["wv"])], axis=-1).astype(act)
        w = jnp.concatenate([lp["conv_q"], lp["conv_k"], lp["conv_v"]],
                            axis=-1)
        held = convs[li][rows].reshape(b, spec.conv_size - 1, -1)
        y, tail = _short_conv(x, held, w, q_lens, fresh)
        convs = convs.at[li, rows].set(
            jnp.where(live[:, None, None], tail, held).reshape(b, -1))
        y = jax.nn.silu(y).reshape(b, c, 3, nh, kd)
        q = _l2norm(y[:, :, 0]) * kd ** -0.5
        k = _l2norm(y[:, :, 1])
        v = y[:, :, 2]
        beta = jax.nn.sigmoid(_dot(h, lp["wb"]))                 # [B,C,H]
        f = _dot(_dot(h, lp["f_a"]).astype(act), lp["f_b"])
        g = (-jnp.exp(lp["A_log"])[:, None]
             * jax.nn.softplus(f + lp["dt_bias"]).reshape(b, c, nh, kd))
        gate = jax.nn.sigmoid(_dot(_dot(h, lp["g_a"]).astype(act),
                                   lp["g_b"]))                   # [B,C,H*K]
        with jax.named_scope("decoder.kda.scan"):
            o, states = kda_recurrent(q, k, v, g, beta, states, rows,
                                      q_lens, fresh, layer=li, impl=impl)
        o = _rms(o, lp["o_norm"], spec.rms_eps).reshape(b, c, nh * kd)
        out = _dot((o * gate).astype(act), lp["wo"])
    return out, states, convs


def kimi_linear_step(params, spec: KimiLinearSpec, tokens, positions, q_lens,
                     k_pool, v_pool, page_tables, kv_lens, *,
                     all_lanes: bool = False, return_hidden: bool = False,
                     attention_impl: Optional[str] = None,
                     garbage_page: int = 0):
    """ONE mixed step of a fixed-slot batch, ``decoder_step_chunked``'s
    contract for a model with a state kind beside a latent full kind:
    ``k_pool = (latent [mla layers, P, ps, D], states [kda layers, R,
    H, V, K])``, ``v_pool = (None, convs [kda layers, R, (conv - 1) 3 H
    K])``, ``page_tables`` a ``StateTables`` (the latent pool's table and
    each call row's state row and whether its sequence starts in the
    call). Every valid lane's latent entry goes to the pool through the
    table (write-before-attend; dead lanes write ``garbage_page``); a KDA
    layer carries its row's state and conv tails past the chunk, from zero
    where the row's sequence starts.
    Returns ``(k_pool, v_pool, logits, aux)`` with the pools paired as
    they came, ``logits [slots, vocab]`` float32 of each slot's newest
    lane (``[slots, C, vocab]`` with ``all_lanes``) and ``aux`` holding
    ``expert_counts [expert layers, held experts]`` (and ``hidden`` with
    ``return_hidden``)."""
    import jax
    import jax.numpy as jnp

    from ..fluid.ops.pallas_kernels.paged_attention import (
        paged_latent_attention)

    b, c = tokens.shape
    latent, states = k_pool
    _none, convs = v_pool
    ps = latent.shape[2]
    nh = spec.n_heads
    nope, rope, rank = spec.qk_nope_dim, spec.qk_rope_dim, spec.kv_lora_rank
    act = params["tok_emb"].dtype
    table, rows = page_tables.full, page_tables.rows
    with jax.named_scope("decoder.embed"):
        lane = jnp.arange(c)[None, :]
        valid = lane < q_lens[:, None]                       # [B, C]
        x = params["tok_emb"][tokens]
        page = jnp.where(valid, jnp.take_along_axis(table, positions // ps,
                                                    axis=1), garbage_page)
        off = jnp.where(valid, positions % ps, 0)
    counts = []
    for l in range(spec.n_layers):
        lp = params[f"layer{l}"]
        h = _rms(x, lp["ln_in"], spec.rms_eps).astype(act)
        if l in spec.kda_layers:
            mixed, states, convs = _kda_mix(
                h, lp, spec, spec.kda_layers.index(l), states, convs, rows,
                page_tables.fresh, q_lens, attention_impl)
        else:
            li = spec.mla_layers.index(l)
            with jax.named_scope("decoder.attn"):
                q = _dot(h, lp["wq"]).reshape(b, c, nh, nope + rope)
                kva = _dot(h, lp["wkva"])
                pad = spec.head_dim - spec.latent_dim
                entry = jnp.concatenate(
                    [_rms(kva[..., :rank], lp["kv_norm"], spec.rms_eps),
                     kva[..., rank:], jnp.zeros((b, c, pad), jnp.float32)],
                    axis=-1).astype(act)
                wkvb = lp["wkvb"].reshape(rank, nh, nope + spec.v_head_dim)
                # the absorbed query: W_uk_h q_nope beside q_pe, a head
                q = jnp.concatenate(
                    [jnp.einsum("bchn,khn->bchk", q[..., :nope].astype(act),
                                wkvb[..., :nope],
                                preferred_element_type=jnp.float32),
                     q[..., nope:], jnp.zeros((b, c, nh, pad), jnp.float32)],
                    axis=-1).astype(act)
            with jax.named_scope("decoder.kv_write"):
                # one row a token of the pool seen as [layers, P * ps, D]
                # (the same bytes): a row scatter, in place
                shape = latent.shape
                latent = latent.reshape(shape[0], -1, shape[-1]).at[
                    li, page * ps + off].set(
                        entry.astype(latent.dtype)).reshape(shape)
            with jax.named_scope("decoder.attn"):
                with jax.named_scope("decoder.attn.latent"):
                    # multi-query over the one latent key, whose first
                    # kv_lora_rank columns are the values
                    a = paged_latent_attention(
                        q, latent[li], table, kv_lens, q_lens=q_lens,
                        scale=(nope + rope) ** -0.5, value_dim=rank,
                        impl=attention_impl)
                o = jnp.einsum("bchk,khv->bchv", a,
                               wkvb[..., nope:],
                               preferred_element_type=jnp.float32)
                mixed = _dot(o.reshape(b, c, -1).astype(act), lp["wo"])
        x = (x.astype(jnp.float32) + mixed).astype(act)
        h2 = _rms(x, lp["ln_ff"], spec.rms_eps).astype(act)
        if l < spec.n_dense_layers:
            with jax.named_scope("decoder.mlp"):
                ff = _swiglu(h2, lp["gate"], lp["up"], lp["down"])
        else:
            flat = h2.reshape(b * c, -1)
            out, n = moe_layer(flat, lp, valid.reshape(-1), spec,
                               impl=attention_impl, score=sigmoid_scores,
                               held=spec.experts_held)
            counts.append(n)
            with jax.named_scope("decoder.moe.shared"):
                out = out + _swiglu(flat, lp["shared_gate"],
                                    lp["shared_up"], lp["shared_down"])
            ff = out.reshape(b, c, -1)
        x = (x.astype(jnp.float32) + ff).astype(act)
    with jax.named_scope("decoder.head"):
        if not all_lanes:
            # unembed only each slot's newest lane (dead slots: lane 0)
            last = jnp.maximum(q_lens - 1, 0)[:, None, None]
            x = jnp.take_along_axis(
                x, jnp.broadcast_to(last, (b, 1, x.shape[-1])), axis=1)[:, 0]
        hidden = _rms(x, params["lnf"], spec.rms_eps)
        logits = _dot(hidden.astype(act), params["head"])
    aux = {"expert_counts": jnp.stack(counts)} if counts else {}
    if return_hidden:
        aux["hidden"] = hidden
    return (latent, states), (None, convs), logits, aux
