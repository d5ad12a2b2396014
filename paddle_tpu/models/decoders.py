"""Served decoder models: what the decode engine asks of a model (D1).

A MODEL is a spec object the engine, the checkpoint validator and the
sharding rules ask, instead of each knowing one decoder's tensor names:

  ``family``          the name ``spec_from_dict`` finds it by
  ``vocab``, ``d_model``, ``n_layers``, ``n_heads``, ``n_kv_heads``,
  ``head_dim``, ``seed``, ``eos_id``
  ``block_length``    how many tokens a decoding pass carries: 1 for a
                      causal model, B for one that generates by diffusion
                      over blocks of B (attended both ways inside a block)
  ``mask_token_id``   the id a block model feeds at a masked lane
  ``pool_dtype``, ``param_dtype``   the dtypes of the paged K/V pools
                      and of the parameter tree
  ``layer_kinds``     what each layer keeps of a sequence, in layer
                      order: ``"full"`` (every key) or ``"window"`` (the
                      newest ``window`` keys); the engine holds a cache a
                      kind (``serving/kv_cache.py``). ``all_full(n)`` is
                      the answer of a model with no window
  ``window``          keys a window layer's query sees, its own among
                      them (``None`` where every layer is full)
  ``moe_assignments_per_token``   token-to-expert assignments a live
                      token makes in one pass (0 for a dense model)
  ``expert_width``    an expert's inner width, of a model that has
                      experts: with ``d_model`` what routes its grouped
                      products (``pallas_kernels/moe_gmm.moe_route``)
  ``to_dict()`` / ``from_dict()``   the checkpoint meta / wire form
  ``tensors()``       ``{flat name: shape}`` of the parameter tree
  ``seeded_arrays()`` the deterministic tree as host arrays, in the
                      dtype the model is served in
  ``step(params, tokens, positions, q_lens, k_pool, v_pool, page_tables,
         kv_lens, **kw)``   ``decoder_step_chunked``'s signature; gives
                      ``(k_pool, v_pool, logits, aux)`` with ``aux`` a
                      dict of what else the pass reports (hidden states,
                      per-expert counts). A model with window layers
                      takes and gives each pool as the pair ``(full
                      kind's [full layers, ...], window kind's [window
                      layers, ...])`` and ``page_tables`` as a
                      ``KindTables`` of the full kind's table, the window
                      kind's and the logical page the latter's rows
                      start at

The dense decoder the engine was built on (``DecoderSpec``) is one such
model, unchanged in arithmetic; ``sdar_moe.SdarMoeSpec`` is another, and
``afmoe.AfmoeSpec`` (window and full layers, gated attention, sigmoid-
routed experts beside a shared one) a third.
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import numpy as np

__all__ = ["DecoderSpec", "build_decoder_params", "seeded_decoder_arrays",
           "decoder_step", "decoder_step_chunked", "validate_draft_spec",
           "spec_from_dict", "KindTables", "all_full"]


class KindTables(NamedTuple):
    """The page tables of one call to a model with window layers:
    ``full [S, W]`` (the full kind's, as every model's), ``window [S,
    Ww]`` (the window kind's: a row holds the pages from the window's
    first on) and ``starts [S]`` (the logical page of each window row's
    column 0). ``shape`` is the full table's, the call's compiled
    ``(slots, width)`` bucket."""

    full: Any
    window: Any
    starts: Any

    @property
    def shape(self):
        return self.full.shape


def all_full(n_layers: int) -> Tuple[str, ...]:
    """``layer_kinds`` of a model whose layers all keep every key."""
    return ("full",) * int(n_layers)


class DecoderSpec:
    """Architecture + identity of a decoder the engine can serve.
    ``d_model == n_heads * head_dim`` (enforced); ``n_heads`` must be a
    multiple of ``n_kv_heads`` (GQA). Params are DETERMINISTIC in
    ``seed`` so two replicas loading the same spec serve bitwise the
    same model — and tests can reference-check outputs."""

    __slots__ = ("vocab", "d_model", "n_layers", "n_heads", "n_kv_heads",
                 "head_dim", "seed", "eos_id")
    family = "dense"
    block_length = 1            # causal: one token a decoding pass
    mask_token_id = None
    pool_dtype = "float32"
    param_dtype = "float32"
    moe_assignments_per_token = 0   # a dense model routes nothing
    window = None                   # every layer keeps every key

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return all_full(self.n_layers)

    def __init__(self, vocab: int = 64, d_model: int = 32,
                 n_layers: int = 2, n_heads: int = 4,
                 n_kv_heads: Optional[int] = None, seed: int = 0,
                 eos_id: Optional[int] = None):
        self.vocab = int(vocab)
        self.d_model = int(d_model)
        self.n_layers = int(n_layers)
        self.n_heads = int(n_heads)
        self.n_kv_heads = int(n_kv_heads if n_kv_heads is not None
                              else n_heads)
        if self.d_model % 2:
            raise ValueError(f"d_model {d_model} must be even "
                             f"(sinusoidal encoding pairs sin/cos halves)")
        if self.d_model % self.n_heads:
            raise ValueError(f"d_model {d_model} not divisible by "
                             f"n_heads {n_heads}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"n_heads {n_heads} not a multiple of "
                             f"n_kv_heads {self.n_kv_heads}")
        self.head_dim = self.d_model // self.n_heads
        self.seed = int(seed)
        self.eos_id = None if eos_id is None else int(eos_id)

    def to_dict(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in
                ("vocab", "d_model", "n_layers", "n_heads", "n_kv_heads",
                 "seed", "eos_id")}

    def tensors(self) -> Dict[str, Tuple[int, ...]]:
        """Flat ``{name: shape}`` of the parameter tree, worked out from
        the spec alone (tuples index as ``/0``, ``/1``, the checkpoint
        ``_flatten`` scheme)."""
        dm, dh = self.d_model, self.head_dim
        out: Dict[str, Tuple[int, ...]] = {
            "tok_emb": (self.vocab, dm), "lnf/0": (dm,), "lnf/1": (dm,)}
        for l in range(self.n_layers):
            p = f"layer{l}"
            out[f"{p}/ln1/0"] = (dm,)
            out[f"{p}/ln1/1"] = (dm,)
            out[f"{p}/wq"] = (dm, self.n_heads * dh)
            out[f"{p}/wk"] = (dm, self.n_kv_heads * dh)
            out[f"{p}/wv"] = (dm, self.n_kv_heads * dh)
            out[f"{p}/wo"] = (self.n_heads * dh, dm)
            out[f"{p}/ln2/0"] = (dm,)
            out[f"{p}/ln2/1"] = (dm,)
            out[f"{p}/w1"] = (dm, 4 * dm)
            out[f"{p}/w2"] = (4 * dm, dm)
        return out

    def seeded_arrays(self) -> Dict[str, Any]:
        return seeded_decoder_arrays(self)

    def step(self, params, tokens, positions, q_lens, k_pool, v_pool,
             page_tables, kv_lens, *, all_lanes: bool = False,
             return_hidden: bool = False,
             attention_impl: Optional[str] = None,
             garbage_page: int = 0):
        """``decoder_step_chunked`` as the engine asks any model:
        ``(k_pool, v_pool, logits, aux)``."""
        out = decoder_step_chunked(
            params, self, tokens, positions, q_lens, k_pool, v_pool,
            page_tables, kv_lens, all_lanes=all_lanes,
            return_hidden=return_hidden, attention_impl=attention_impl,
            garbage_page=garbage_page)
        if return_hidden:
            return out[0], out[1], out[2], {"hidden": out[3]}
        return out + ({},)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "DecoderSpec":
        allowed = ("vocab", "d_model", "n_layers", "n_heads",
                   "n_kv_heads", "seed", "eos_id")
        # reject, don't drop: a misspelled field silently deploying a
        # default-architecture decoder is a wrong-model hot-swap
        # (head_dim is derived — accepted only if consistent)
        unknown = sorted(set(d) - set(allowed) - {"head_dim"})
        if unknown:
            raise ValueError(
                f"unknown DecoderSpec field(s) {unknown}; "
                f"valid: {sorted(allowed)}")
        spec = cls(**{k: v for k, v in d.items() if k in allowed})
        if "head_dim" in d and int(d["head_dim"]) != spec.head_dim:
            raise ValueError(
                f"head_dim {d['head_dim']} contradicts d_model "
                f"{spec.d_model} / n_heads {spec.n_heads} = "
                f"{spec.head_dim} — head_dim is derived, not free")
        return spec


def validate_draft_spec(target: DecoderSpec, draft: DecoderSpec):
    """Cross-validate a speculative DRAFT decoder against its target
    (ISSUE 14 satellite): a mismatched draft must fail at LOAD, typed
    and naming the field, not mid-verify with garbage acceptance. The
    draft proposes token ids the target scores, so the vocabularies
    must be identical; page geometry (page_size / num_pages) is shared
    BY CONSTRUCTION — the draft's pool mirrors the target's allocator
    and page tables, so it cannot diverge. Everything architectural
    (layers, heads, d_model) is free: that asymmetry is the whole
    speedup."""
    for role, m in (("target", target), ("draft", draft)):
        if m.block_length != 1:
            raise ValueError(
                f"speculation needs causal models: the {role}'s "
                f"'block_length' is {m.block_length} (it generates by "
                f"diffusion over blocks; a draft proposes one token at a "
                f"time)")
    if draft.vocab != target.vocab:
        raise ValueError(
            f"draft/target DecoderSpec mismatch on field 'vocab': "
            f"draft {draft.vocab} != target {target.vocab} — the draft "
            f"proposes token ids the target must score")
    if draft.eos_id != target.eos_id:
        raise ValueError(
            f"draft/target DecoderSpec mismatch on field 'eos_id': "
            f"draft {draft.eos_id} != target {target.eos_id} — "
            f"termination is decided on committed (target-verified) "
            f"tokens, so the specs must agree on it")


def seeded_decoder_arrays(spec: DecoderSpec) -> Dict[str, Any]:
    """The deterministic parameter tree as HOST numpy arrays (seeded
    draws, scaled-normal init). Whoever serves it places it: the
    engine puts each leaf straight onto its shard of a mesh, so no
    tensor is first materialized whole on one chip."""
    rng = np.random.RandomState(spec.seed)
    dm, dh = spec.d_model, spec.head_dim

    def mat(fan_in, *shape):
        return (rng.randn(*shape) / math.sqrt(fan_in)).astype(np.float32)

    def ln():
        return (np.ones((dm,), np.float32), np.zeros((dm,), np.float32))

    params: Dict[str, Any] = {"tok_emb": mat(dm, spec.vocab, dm),
                              "lnf": ln()}
    for l in range(spec.n_layers):
        params[f"layer{l}"] = {
            "ln1": ln(),
            "wq": mat(dm, dm, spec.n_heads * dh),
            "wk": mat(dm, dm, spec.n_kv_heads * dh),
            "wv": mat(dm, dm, spec.n_kv_heads * dh),
            "wo": mat(dm, spec.n_heads * dh, dm),
            "ln2": ln(),
            "w1": mat(dm, dm, 4 * dm),
            "w2": mat(4 * dm, 4 * dm, dm),
        }
    return params


def build_decoder_params(spec: DecoderSpec) -> Dict[str, Any]:
    """``seeded_decoder_arrays`` on the default device — the test/bench
    stand-in for loading a checkpoint."""
    import jax

    return jax.device_put(seeded_decoder_arrays(spec))


def _ln(x, gb):
    import jax.numpy as jnp

    g, b = gb
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-6) * g + b


def _pos_encoding(positions, d_model):
    """Sinusoidal [B, d_model] — unbounded positions, no learned table
    to cap sequence length."""
    import jax.numpy as jnp

    half = d_model // 2
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    ang = positions[:, None].astype(jnp.float32) * freq[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def decoder_step_chunked(params, spec: DecoderSpec, tokens, positions,
                         q_lens, k_pool, v_pool, page_tables, kv_lens,
                         all_lanes: bool = False,
                         return_hidden: bool = False,
                         attention_impl: Optional[str] = None,
                         garbage_page: int = 0):
    """ONE mixed decode/prefill step for a fixed-slot batch
    (ISSUE 10). Each slot carries up to C tokens of ITS sequence — a
    prefill chunk, a single decode token at C lane 0, or nothing —
    attending causally within the chunk. Functional: writes every
    valid lane's K/V into the paged pools (dead lanes and dead slots
    write the garbage page), attends through the page tables, returns
    ``(k_pool, v_pool, logits [B, vocab])``.

    tokens/positions: [B, C] int32, lane ``j`` of slot ``i`` valid iff
    ``j < q_lens[i]`` (invalid lanes: 0/0 — masked to the garbage
    page, never trusted). kv_lens: [B] int32 — valid keys INCLUDING
    this step's q_len tokens. Chunking is pure packing: the math per
    token is identical to feeding the same tokens one step at a time
    (the chunked-vs-unchunked greedy-equality test pins it).

    Logits come back ONLY for each slot's newest lane (``q_len - 1``)
    — the one position a token is ever chosen at (a chunk that
    doesn't finish its prompt uses no logits at all). Unembedding is
    the widest matmul of the step: unembedding all C lanes would waste
    ~(C-1)/C of it plus a C-times-larger device->host transfer on
    every prefill step.

    ``all_lanes=True`` is the SPECULATIVE-VERIFY form (ISSUE 14):
    logits come back for EVERY lane (``[B, C, vocab]``) — lane ``j`` is
    the target's distribution for position ``positions[:, j] + 1``, so
    one call scores a draft's ``k`` proposals plus the bonus position.
    The full-lane unembed is exactly the price of verification (C =
    spec_k + 1 lanes, not the prefill chunk width); acceptance happens
    host-side in the engine.

    ``return_hidden=True`` (requires ``all_lanes``) additionally
    returns the final-norm hidden states ``[B, C, d_model]`` — the
    EMBEDDING/SCORING form (ISSUE 20): one chunked call yields both
    every lane's pooled-representation input and its next-token
    distribution (per-token logprobs), so prompt-only scoring requests
    ride the exact prefill path generation uses.

    ``attention_impl`` is handed to ``paged_attention`` as ``impl``:
    None lets the flags route, ``"reference"`` names the pure-jax path
    (the engine does under a mesh). ``garbage_page`` is the pool's
    page that dead lanes write to: whoever owns the pools says which
    (the engine passes its cache's).
    """
    import jax
    import jax.numpy as jnp

    from ..fluid.ops.pallas_kernels.paged_attention import paged_attention

    b, c = tokens.shape
    ps = k_pool.shape[2]
    dm, dh = spec.d_model, spec.head_dim
    # the jax.named_scope blocks name the step's device work by role
    # (ISSUE 27): every operation's op_name in the compiled program, and
    # so in a device trace, carries decoder.embed / .kv_write / .attn /
    # .mlp / .head. Trace-time metadata only
    with jax.named_scope("decoder.embed"):
        lane = jnp.arange(c)[None, :]                      # [1, C]
        valid = lane < q_lens[:, None]                     # [B, C]
        x = params["tok_emb"][tokens] * math.sqrt(dm) + \
            _pos_encoding(positions.reshape(-1), dm).reshape(b, c, dm)
        page_idx = positions // ps
        # each lane's physical page: its slot's table row at the
        # token's page index. Invalid lanes (j >= q_len, padded dead
        # slots) are FORCED to the garbage page — a live slot's row 0
        # must never be clobbered by a dead lane's position-0 write
        page = jnp.where(
            valid, jnp.take_along_axis(page_tables, page_idx, axis=1),
            garbage_page)                                  # [B, C]
        off = jnp.where(valid, positions % ps, 0)
    for l in range(spec.n_layers):
        lp = params[f"layer{l}"]
        with jax.named_scope("decoder.attn"):
            h = _ln(x, lp["ln1"])
            q = (h @ lp["wq"]).reshape(b, c, spec.n_heads, dh)
            k = (h @ lp["wk"]).reshape(b, c, spec.n_kv_heads, dh)
            v = (h @ lp["wv"]).reshape(b, c, spec.n_kv_heads, dh)
        # write the whole chunk's K/V, THEN attend: within the chunk,
        # query j sees keys i <= j of the same chunk — write-before-
        # attend makes the chunk exactly equal to sequential steps
        with jax.named_scope("decoder.kv_write"):
            k_pool = k_pool.at[l, page, off].set(k.astype(k_pool.dtype))
            v_pool = v_pool.at[l, page, off].set(v.astype(v_pool.dtype))
        with jax.named_scope("decoder.attn"):
            attn = paged_attention(q, k_pool[l], v_pool[l], page_tables,
                                   kv_lens, q_lens=q_lens,
                                   impl=attention_impl)
            x = x + attn.reshape(b, c, spec.n_heads * dh) @ lp["wo"]
        with jax.named_scope("decoder.mlp"):
            h2 = _ln(x, lp["ln2"])
            x = x + jax.nn.gelu(h2 @ lp["w1"]) @ lp["w2"]
    with jax.named_scope("decoder.head"):
        if all_lanes:
            # verify form: every lane's logits ([B, C, vocab]) — the
            # acceptance walk needs the target's distribution at each
            # proposed position, not just the newest
            h = _ln(x, params["lnf"])
            logits = h @ params["tok_emb"].T
            if return_hidden:
                return k_pool, v_pool, logits, h
            return k_pool, v_pool, logits
        # unembed only each slot's newest lane (dead slots gather lane
        # 0 — garbage the scheduler never samples)
        last = jnp.maximum(q_lens - 1, 0)[:, None, None]   # [B, 1, 1]
        x_last = jnp.take_along_axis(
            x, jnp.broadcast_to(last, (b, 1, dm)), axis=1)[:, 0]
        logits = _ln(x_last, params["lnf"]) @ params["tok_emb"].T
        return k_pool, v_pool, logits


def decoder_step(params, spec: DecoderSpec, tokens, positions,
                 k_pool, v_pool, page_tables, kv_lens):
    """The PR 6 single-token step — now the C=1 case of
    ``decoder_step_chunked`` (one implementation, so the two forms
    cannot drift). tokens/positions: [B] int32 (dead slots: 0/0 with
    an all-garbage table row); kv_lens: [B] int32 — valid keys
    INCLUDING this step's token (0 = dead slot -> exact-zero attention
    output). Returns ``(k_pool, v_pool, logits [B, vocab])``."""
    import jax.numpy as jnp

    q_lens = (kv_lens > 0).astype(jnp.int32)
    return decoder_step_chunked(
        params, spec, tokens[:, None], positions[:, None], q_lens,
        k_pool, v_pool, page_tables, kv_lens)




# --- families ------------------------------------------------------------

def spec_from_dict(d: Dict[str, Any]):
    """The model a checkpoint's meta or a wire dict names: its
    ``family`` key picks the class (absent = the dense decoder, which
    every checkpoint before the key existed holds)."""
    family = d.get("family", DecoderSpec.family)
    if family == DecoderSpec.family:
        return DecoderSpec.from_dict(
            {k: v for k, v in d.items() if k != "family"})
    # a family's module is imported only when a dict names it
    if family == "sdar_moe":
        from .sdar_moe import SdarMoeSpec

        return SdarMoeSpec.from_dict(d)
    if family == "afmoe":
        from .afmoe import AfmoeSpec

        return AfmoeSpec.from_dict(d)
    raise ValueError(f"unknown decoder family {family!r}; known: "
                     f"{[DecoderSpec.family, 'sdar_moe', 'afmoe']}")
