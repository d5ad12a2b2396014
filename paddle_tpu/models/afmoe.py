"""The ``afmoe`` family as a served model (ISSUE 34; Arcee's Trinity):
WINDOW and FULL attention layers in one model, gated attention under
"sandwich" norms, leading dense SwiGLU layers, and sigmoid-routed experts
beside a shared one.

A layer, four RMSNorm gains each::

    x = x + post_attn_norm(Attn(input_norm(x)))
    x = x + post_mlp_norm(FF(pre_mlp_norm(x)))

``Attn``: grouped-query attention with per-head RMSNorm on q and k and an
elementwise gate, ``out = (attn * sigmoid(h W_g)) W_o``. A
``sliding_attention`` layer carries rotary positions and its query at
position ``i`` sees keys ``(i - sliding_window, i]``; a ``full_attention``
layer carries NO positions and sees every key ``<= i``. ``FF`` is a SwiGLU
of ``intermediate_size`` on the first ``num_dense_layers`` layers and, on
the rest, ``Shared(h) + sum_e w_e Expert_e(h)`` over the
``num_experts_per_tok`` experts with the largest ``sigmoid(h W_r) +
expert_bias`` (the bias selects and never weighs), ``w`` the chosen scores
renormalised (``route_norm``) times ``route_scale``. The embedding is
scaled by ``sqrt(hidden_size)`` (``mup_enabled``); the head is untied.

The spec reads the public ``config.json`` keys (``from_config``) and
answers what the engine asks of a model (``models/decoders.py``): its
``layer_kinds`` are ``"window"`` and ``"full"``, so the engine keeps a
cache a kind and the step takes each pool as a pair and the tables as a
``KindTables``. The dropless expert layer is ``sdar_moe.moe_layer``, the
one every served family runs, with this family's scoring
(``sigmoid_scores``); the shared expert runs beside it under its own
scope.

Precision, as the configuration states it: weights, the activations
between layers and the K/V pools in bfloat16; every product accumulates in
float32; RMSNorm statistics, rotary angles, the gate's sigmoid, the
router's scores, the top-k, the renormalised weights and the final logits
in float32. K and V go to the pool after QK-norm (and rotary, on a window
layer).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import numpy as np

from .sdar_moe import _dot, _rms, _rotary, moe_layer

__all__ = ["AfmoeSpec", "afmoe_step", "sigmoid_scores", "TINY_CONFIG"]

SLIDING, FULL = "sliding_attention", "full_attention"

# the preset the CPU tests run, every mechanism at toy widths: a dense
# layer, a whole period of three window layers and a full one, a window
# (8) shorter than the tests' sequences, a shared expert
TINY_CONFIG = {
    "model_type": "afmoe", "hidden_size": 64, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 2,
    "num_shared_experts": 1, "score_func": "sigmoid", "route_norm": True,
    "route_scale": 2.826, "n_group": 1, "topk_group": 1,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "rope_scaling": None,
    "sliding_window": 8, "global_attn_every_n_layers": 4,
    "layer_types": [SLIDING, SLIDING, SLIDING, SLIDING, FULL],
    "mup_enabled": True, "tie_word_embeddings": False, "vocab_size": 128,
}


class AfmoeSpec:
    """Architecture + identity of one ``afmoe`` model. Parameters are
    deterministic in ``seed`` (``seeded_arrays``)."""

    _FIELDS = ("vocab", "d_model", "n_layers", "n_heads", "n_kv_heads",
               "head_dim", "dense_width", "n_dense_layers", "expert_width",
               "n_experts", "experts_per_token", "route_norm",
               "route_scale", "rms_eps", "rope_theta", "window",
               "layer_types", "mup", "dtype", "seed", "eos_id")
    __slots__ = _FIELDS
    family = "afmoe"
    block_length = 1            # causal: one token a decoding pass
    mask_token_id = None

    def __init__(self, vocab: int, d_model: int, n_layers: int,
                 n_heads: int, n_kv_heads: int, head_dim: int,
                 dense_width: int, n_dense_layers: int, expert_width: int,
                 n_experts: int, experts_per_token: int,
                 route_norm: bool = True, route_scale: float = 1.0,
                 rms_eps: float = 1e-5, rope_theta: float = 1e4,
                 window: Optional[int] = None,
                 layer_types: Optional[Tuple[str, ...]] = None,
                 mup: bool = True, dtype: str = "bfloat16", seed: int = 0,
                 eos_id: Optional[int] = None):
        self.vocab, self.d_model = int(vocab), int(d_model)
        self.n_layers = int(n_layers)
        self.n_heads, self.n_kv_heads = int(n_heads), int(n_kv_heads)
        self.head_dim = int(head_dim)
        self.dense_width = int(dense_width)
        self.n_dense_layers = int(n_dense_layers)
        self.expert_width = int(expert_width)
        self.n_experts = int(n_experts)
        self.experts_per_token = int(experts_per_token)
        self.route_norm = bool(route_norm)
        self.route_scale = float(route_scale)
        self.rms_eps, self.rope_theta = float(rms_eps), float(rope_theta)
        self.layer_types = tuple(layer_types or ())
        self.window = None if window is None else int(window)
        self.mup = bool(mup)
        # the ONE stated dtype of weights, activations between layers and
        # K/V pools (the published bfloat16; float32 is the CPU tests')
        self.dtype = str(dtype)
        if self.dtype not in ("bfloat16", "float32"):
            raise ValueError(f"dtype must be 'bfloat16' or 'float32', "
                             f"got {dtype!r}")
        self.seed = int(seed)
        self.eos_id = None if eos_id is None else int(eos_id)
        if len(self.layer_types) != self.n_layers or any(
                t not in (SLIDING, FULL) for t in self.layer_types):
            raise ValueError(
                f"layer_types must name {self.n_layers} layers, each "
                f"{SLIDING!r} or {FULL!r}, got {self.layer_types!r}")
        if SLIDING not in self.layer_types:
            raise ValueError(
                f"an afmoe model without a {SLIDING!r} layer is not "
                f"served: the step takes each pool as the pair of its "
                f"layer kinds, got layer_types {self.layer_types!r}")
        if self.window is None or self.window < 1:
            raise ValueError(f"a {SLIDING!r} layer needs a window >= 1, "
                             f"got {window!r}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"n_heads {n_heads} not a multiple of "
                             f"n_kv_heads {n_kv_heads}")
        if self.head_dim % 2:
            raise ValueError(f"head_dim {head_dim} must be even (rotary "
                             f"positions pair its halves)")
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError(f"n_dense_layers {n_dense_layers} outside "
                             f"[0, n_layers {n_layers}]")
        if not 1 <= self.experts_per_token <= self.n_experts:
            raise ValueError(
                f"experts_per_token {experts_per_token} outside "
                f"[1, n_experts {n_experts}]")

    @classmethod
    def from_config(cls, cfg: Dict[str, Any], *,
                    dtype: Optional[str] = None, seed: int = 0,
                    eos_id: Optional[int] = None) -> "AfmoeSpec":
        """From the keys of the model's public ``config.json``. What the
        step is not written for is refused by name."""
        if cfg.get("model_type", cls.family) != cls.family:
            raise ValueError(f"model_type {cfg.get('model_type')!r} is "
                             f"not {cls.family!r}")
        for key, want in (("tie_word_embeddings", False),
                          ("score_func", "sigmoid"), ("n_group", 1),
                          ("topk_group", 1), ("num_shared_experts", 1),
                          ("rope_scaling", None),
                          ("attention_bias", False)):
            if cfg.get(key, want) != want:
                raise ValueError(
                    f"config key {key!r} is {cfg[key]!r}; this family's "
                    f"step is written for {want!r}")
        n = int(cfg["num_hidden_layers"])
        types = cfg.get("layer_types")
        if types is None:
            every = int(cfg.get("global_attn_every_n_layers", 0))
            types = [FULL if every and (l + 1) % every == 0 else SLIDING
                     for l in range(n)]
        return cls(
            vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
            n_layers=n, n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim", cfg["hidden_size"]
                             // cfg["num_attention_heads"]),
            dense_width=cfg["intermediate_size"],
            n_dense_layers=cfg.get("num_dense_layers", 0),
            expert_width=cfg["moe_intermediate_size"],
            n_experts=cfg["num_experts"],
            experts_per_token=cfg["num_experts_per_tok"],
            route_norm=cfg.get("route_norm", True),
            route_scale=cfg.get("route_scale", 1.0),
            rms_eps=cfg.get("rms_norm_eps", 1e-5),
            rope_theta=cfg.get("rope_theta", 1e4),
            window=cfg.get("sliding_window"), layer_types=tuple(types),
            mup=cfg.get("mup_enabled", False),
            dtype=dtype or cfg.get("torch_dtype", "bfloat16"),
            seed=seed, eos_id=eos_id)

    # -- what the engine asks (models/decoders.py) ------------------------
    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple("window" if t == SLIDING else "full"
                     for t in self.layer_types)

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
        return dict(d, layer_types=list(self.layer_types),
                    family=self.family)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "AfmoeSpec":
        unknown = sorted(set(d) - set(cls._FIELDS) - {"family"})
        if unknown:
            raise ValueError(f"unknown AfmoeSpec field(s) {unknown}; "
                             f"valid: {sorted(cls._FIELDS)}")
        return cls(**{k: v for k, v in d.items() if k != "family"})

    def tensors(self) -> Dict[str, Tuple[int, ...]]:
        d, dh = self.d_model, self.head_dim
        e, f = self.n_experts, self.expert_width
        out: Dict[str, Tuple[int, ...]] = {
            "tok_emb": (self.vocab, d), "lnf": (d,),
            "head": (d, self.vocab)}
        for l in range(self.n_layers):
            p = f"layer{l}"
            out.update({
                f"{p}/ln_in": (d,), f"{p}/wq": (d, self.n_heads * dh),
                f"{p}/wk": (d, self.n_kv_heads * dh),
                f"{p}/wv": (d, self.n_kv_heads * dh),
                f"{p}/wg": (d, self.n_heads * dh),
                f"{p}/wo": (self.n_heads * dh, d),
                f"{p}/q_norm": (dh,), f"{p}/k_norm": (dh,),
                f"{p}/ln_post_attn": (d,), f"{p}/ln_pre_mlp": (d,),
                f"{p}/ln_post_mlp": (d,)})
            if l < self.n_dense_layers:
                w = self.dense_width
                out.update({f"{p}/gate": (d, w), f"{p}/up": (d, w),
                            f"{p}/down": (w, d)})
            else:
                out.update({
                    f"{p}/router": (d, e), f"{p}/expert_bias": (e,),
                    f"{p}/gate": (e, d, f), f"{p}/up": (e, d, f),
                    f"{p}/down": (e, f, d), f"{p}/shared_gate": (d, f),
                    f"{p}/shared_up": (d, f), f"{p}/shared_down": (f, d)})
        return out

    def _tree(self, leaf) -> Dict[str, Any]:
        """The nested parameter tree, each leaf ``leaf(index, shape,
        fan_in)``; fan_in 0 marks a gain (ones), -1 the selection bias
        (zeros)."""
        tree: Dict[str, Any] = {}
        for i, (name, shape) in enumerate(self.tensors().items()):
            fan_in = (-1 if name.endswith("expert_bias") else
                      0 if len(shape) == 1 else
                      shape[-1] if name == "tok_emb" else shape[-2])
            node, parts = tree, name.split("/")
            for part in parts[:-1]:
                node = node.setdefault(part, {})
            node[parts[-1]] = leaf(i, shape, fan_in)
        return tree

    def seeded_arrays(self) -> Dict[str, Any]:
        """The deterministic tree as host arrays in the served dtype:
        every matrix normal with std 1/sqrt(fan_in), every gain 1, the
        selection bias 0 (float32, as the published buffer). The scaled
        embedding and every normed branch then have unit RMS, so the
        router's and the head's logits are N(0, 1): top-k of the sigmoid
        scores and the argmax are decided by gaps of order 0.01-0.1, not
        by rounding."""
        import jax.numpy as jnp

        dtype = jnp.dtype(self.dtype)
        rng = np.random.RandomState(self.seed)

        def leaf(_i, shape, fan_in):
            if fan_in < 0:
                return np.zeros(shape, np.float32)
            if not fan_in:
                return np.ones(shape, np.float32).astype(dtype)
            return (rng.randn(*shape) / math.sqrt(fan_in)).astype(dtype)

        return self._tree(leaf)

    def device_arrays(self, seed: Optional[int] = None) -> Dict[str, Any]:
        """The same tree drawn ON THE DEVICE, leaf by leaf, in the served
        dtype (another generator than ``seeded_arrays``: the same
        distribution, not the same values), as ``SdarMoeSpec`` has it."""
        import jax
        import jax.numpy as jnp

        dtype = jnp.dtype(self.dtype)
        root = jax.random.key(int(self.seed if seed is None else seed)
                              % (2 ** 63))

        @functools.partial(jax.jit, static_argnums=(1, 2))
        def draw(key, shape, fan_in):
            if fan_in < 0:
                return jnp.zeros(shape, jnp.float32)
            if not fan_in:
                return jnp.ones(shape, dtype)
            return (jax.random.normal(key, shape, jnp.float32)
                    / math.sqrt(fan_in)).astype(dtype)

        return self._tree(lambda i, shape, fan_in: draw(
            jax.random.fold_in(root, i), shape, fan_in))

    def step(self, params, tokens, positions, q_lens, k_pool, v_pool,
             page_tables, kv_lens, *, all_lanes: bool = False,
             return_hidden: bool = False,
             attention_impl: Optional[str] = None,
             garbage_page: int = 0):
        return afmoe_step(params, self, tokens, positions, q_lens, k_pool,
                          v_pool, page_tables, kv_lens, all_lanes=all_lanes,
                          return_hidden=return_hidden,
                          attention_impl=attention_impl,
                          garbage_page=garbage_page)


def sigmoid_scores(h, lp, spec: AfmoeSpec):
    """This family's scoring of ``moe_layer``: float32 sigmoid scores, the
    ``experts_per_token`` largest of ``score + expert_bias`` (the bias
    selects and never weighs), the chosen SCORES renormalised to sum to 1
    (``route_norm``) times ``route_scale``. ``(w [T, k] float32, idx [T,
    k])``."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(_dot(h, lp["router"]))                 # f32
    _, idx = jax.lax.top_k(s + lp["expert_bias"].astype(jnp.float32),
                           spec.experts_per_token)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if spec.route_norm:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * spec.route_scale, idx


def _swiglu(h, gate, up, down):
    """``down(silu(gate h) * up h)``: float32 products, the gated product
    rounded to the weights' dtype before ``down`` (as the experts')."""
    import jax

    act = jax.nn.silu(_dot(h, gate)) * _dot(h, up)
    return _dot(act.astype(h.dtype), down)


def afmoe_step(params, spec: AfmoeSpec, tokens, positions, q_lens, k_pool,
               v_pool, page_tables, kv_lens, *, all_lanes: bool = False,
               return_hidden: bool = False,
               attention_impl: Optional[str] = None,
               garbage_page: int = 0):
    """ONE mixed step of a fixed-slot batch, ``decoder_step_chunked``'s
    contract for a model with two kinds of layer: ``k_pool`` / ``v_pool``
    are each the pair ``(full kind's [full layers, P, ps, Hkv, D], window
    kind's [window layers, Pw, ps, Hkv, D])`` and ``page_tables`` a
    ``KindTables``. Every valid lane's K/V go to its layer's kind's pool
    through that kind's table (write-before-attend; dead lanes write
    ``garbage_page`` of either pool); a full layer attends causally
    through the full table, a window layer through the window table, which
    starts at logical page ``starts`` and is as wide as a window and a
    chunk, under ``window``. Returns ``(k_pool, v_pool, logits, aux)``
    with the pools paired as they came, ``logits [slots, vocab]`` float32
    of each slot's newest lane (``[slots, C, vocab]`` with ``all_lanes``)
    and ``aux`` holding ``expert_counts [expert layers, E]`` (and
    ``hidden`` with ``return_hidden``)."""
    import jax
    import jax.numpy as jnp

    from ..fluid.ops.pallas_kernels.paged_attention import paged_attention

    b, c = tokens.shape
    k_pools, v_pools = list(k_pool), list(v_pool)
    ps = k_pools[0].shape[2]
    dh, nh, nkv = spec.head_dim, spec.n_heads, spec.n_kv_heads
    act = params["tok_emb"].dtype
    with jax.named_scope("decoder.embed"):
        lane = jnp.arange(c)[None, :]
        valid = lane < q_lens[:, None]                       # [B, C]
        x = params["tok_emb"][tokens]
        if spec.mup:
            x = (x.astype(jnp.float32)
                 * math.sqrt(spec.d_model)).astype(act)
        off = jnp.where(valid, positions % ps, 0)
        # a lane's physical page, by kind: its slot's row at the token's
        # page index, counted from the row's first page
        cols = (positions // ps, jnp.clip(
            positions // ps - page_tables.starts[:, None], 0,
            page_tables.window.shape[1] - 1))
        pages = [jnp.where(
            valid, jnp.take_along_axis(table, col, axis=1), garbage_page)
            for table, col in zip(page_tables[:2], cols)]
        inv = jnp.exp(-math.log(spec.rope_theta)
                      * jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
        ang = positions.astype(jnp.float32)[..., None] * inv  # [B,C,D/2]
        cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    counts = []
    seen = [0, 0]                   # layers met so far, by kind
    for l in range(spec.n_layers):
        lp = params[f"layer{l}"]
        windowed = spec.layer_types[l] == SLIDING
        kind = int(windowed)        # which pool, table and pages
        li, seen[kind] = seen[kind], seen[kind] + 1
        with jax.named_scope("decoder.attn"):
            h = _rms(x, lp["ln_in"], spec.rms_eps).astype(act)
            q = _rms(_dot(h, lp["wq"]).reshape(b, c, nh, dh),
                     lp["q_norm"], spec.rms_eps)
            k = _rms(_dot(h, lp["wk"]).reshape(b, c, nkv, dh),
                     lp["k_norm"], spec.rms_eps)
            v = _dot(h, lp["wv"]).reshape(b, c, nkv, dh)
            gate = jax.nn.sigmoid(_dot(h, lp["wg"]))         # [B,C,H*D]
            if windowed:            # a full layer carries no positions
                q, k = _rotary(q, cos, sin), _rotary(k, cos, sin)
            q = q.astype(act)
        with jax.named_scope("decoder.kv_write"):
            k_pools[kind] = k_pools[kind].at[li, pages[kind], off].set(
                k.astype(k_pools[kind].dtype))
            v_pools[kind] = v_pools[kind].at[li, pages[kind], off].set(
                v.astype(v_pools[kind].dtype))
        with jax.named_scope("decoder.attn"):
            with jax.named_scope("decoder.attn.window" if windowed
                                 else "decoder.attn.full"):
                more = ({"window": spec.window,
                         "table_starts": page_tables.starts}
                        if windowed else {})
                attn = paged_attention(
                    q, k_pools[kind][li], v_pools[kind][li],
                    page_tables[kind], kv_lens, q_lens=q_lens,
                    impl=attention_impl, **more)
            gated = (attn.reshape(b, c, nh * dh).astype(jnp.float32)
                     * gate).astype(act)
            x = (x.astype(jnp.float32)
                 + _rms(_dot(gated, lp["wo"]), lp["ln_post_attn"],
                        spec.rms_eps)).astype(act)
        h2 = _rms(x, lp["ln_pre_mlp"], spec.rms_eps).astype(act)
        if l < spec.n_dense_layers:
            with jax.named_scope("decoder.mlp"):
                ff = _swiglu(h2, lp["gate"], lp["up"], lp["down"])
        else:
            flat = h2.reshape(b * c, -1)
            out, n = moe_layer(flat, lp, valid.reshape(-1), spec,
                               impl=attention_impl, score=sigmoid_scores)
            counts.append(n)
            with jax.named_scope("decoder.moe.shared"):
                out = out + _swiglu(flat, lp["shared_gate"],
                                    lp["shared_up"], lp["shared_down"])
            ff = out.reshape(b, c, -1)
        x = (x.astype(jnp.float32)
             + _rms(ff, lp["ln_post_mlp"], spec.rms_eps)).astype(act)
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
    return tuple(k_pools), tuple(v_pools), logits, aux
