"""The ``kimi_linear`` model (Kimi-Linear's family): gated delta-rule (KDA)
layers whose fixed-size state the engine keeps a row a sequence, a latent
(MLA) layer over a one-array paged pool, and a chip's share of the experts.

All on the CPU at the tiny preset (5 layers: KDA with the dense layer, KDA,
KDA, MLA, KDA; d 64, 4 heads of 16, a latent of 32 + 8, 4 of 8 experts
held, top-2 of width 32 beside a shared one, vocab 128), seeded float32
weights, against the ONE plain reference the benchmark also uses,
``perf/references/kimi-linear-48b-a3b-instruct.py``, loaded by path.
"""
import collections
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.fluid.ops.pallas_kernels import kda_recurrent as kr
from paddle_tpu.fluid.ops.pallas_kernels import paged_attention as pa
from paddle_tpu.models import sdar_moe
from paddle_tpu.models.afmoe import sigmoid_scores
from paddle_tpu.models.decoders import DecoderSpec, spec_from_dict
from paddle_tpu.models.kimi_linear import TINY_CONFIG, KimiLinearSpec
from paddle_tpu.observability import metrics
from paddle_tpu.serving.decode import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf.lib.loader import Benchmark, load_module  # noqa: E402

REFERENCE = os.path.join(ROOT, "perf", "references",
                         "kimi-linear-48b-a3b-instruct.py")
CONFIG = os.path.join(ROOT, "perf", "configs",
                      "kimi-linear-48b-a3b-instruct.json")
PS = 4


@pytest.fixture(scope="module")
def ref():
    mod = load_module(REFERENCE, "kimi_reference")
    # the tiny preset's sequences: small pads, so that little compiles
    mod.MIN_PAD, mod.Q_BLOCK, mod.ROW_PAD = 64, 16, 8
    return mod


def tiny_spec(seed=3, **over):
    return KimiLinearSpec.from_config(dict(TINY_CONFIG, **over),
                                      dtype="float32", seed=seed)


@pytest.fixture(scope="module")
def shared():
    spec = tiny_spec()
    return spec, jax.device_put(spec.seeded_arrays())


def engine(spec, chunk, params=None, **kw):
    opts = dict(name="kimi", slots=[2], page_size=PS, num_pages=32,
                max_seq_len=48, prefill_chunk=chunk, params=params)
    opts.update(kw)
    return DecodeEngine(spec, **opts)


def _ctr(name):
    return metrics.counter(name).value()


# --- the model ------------------------------------------------------------

def test_reference_imports_nothing_of_the_program():
    with open(REFERENCE) as f:
        src = f.read()
    assert "paddle_tpu" not in src.replace("imports nothing of the", "")
    assert "Precision.HIGHEST" in src and "UN-ABSORBED" in src


def test_the_configuration_keeps_the_published_keys_and_sizes():
    """Each key ``reduced`` names keeps its published value beside it, the
    layer lists are the stage's cut of the published ones; the stage holds
    4.660 B parameters."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    assert set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "linear_attn_config"}
    assert (cfg["num_hidden_layers_published"],
            cfg["num_experts_published"]) == (27, 256)
    lin, pub = cfg["linear_attn_config"], cfg["linear_attn_config_published"]
    stage = cfg["num_hidden_layers"]
    for key in ("kda_layers", "full_attn_layers"):
        assert lin[key] == [l for l in pub[key] if l <= stage]
    spec = KimiLinearSpec.from_config(cfg)
    sizes = collections.Counter()
    for name, shape in spec.tensors().items():
        layer = name.rpartition("/")[0]
        if not name.endswith(("ln_in", "ln_ff")):
            sizes[layer] += int(np.prod(shape))
    assert spec.layer_kinds == ("state", "state", "state", "full", "state")
    assert sizes["layer1"] == 39_514_272 + 913_637_632       # KDA + experts
    assert sizes["layer3"] == 29_114_880 + 913_637_632       # MLA + experts
    assert sizes["layer0"] == 39_514_272 + 63_700_992        # KDA + dense
    assert sizes[""] == 2 * 163840 * 2304 + 2304
    total = sum(sizes.values()) + 5 * 2 * 2304
    assert total == cfg["sizes"]["parameters"] == 4_660_423_552
    assert spec.experts_held == (0, 128) and spec.n_experts == 256
    assert spec.latent_dim == 576 and spec.head_dim == 640
    assert spec.slot_state == (((4, 32, 128, 128), "float32"),
                               ((4, 3 * 3 * 4096), "bfloat16"))


@pytest.mark.parametrize("key,value", [
    ("moe_router_activation_func", "softmax"), ("q_lora_rank", 1536),
    ("mla_use_nope", False), ("tie_word_embeddings", True),
    ("num_expert_group", 2)])
def test_from_config_refuses_by_name_what_the_step_is_not_written_for(
        key, value):
    with pytest.raises(ValueError, match=key):
        KimiLinearSpec.from_config(dict(TINY_CONFIG, **{key: value}))


def test_the_family_is_found_by_name_and_round_trips():
    spec = tiny_spec()
    again = spec_from_dict(spec.to_dict())
    assert isinstance(again, KimiLinearSpec)
    assert again.to_dict() == spec.to_dict()
    tree = spec.seeded_arrays()
    assert len(jax.tree_util.tree_leaves(tree)) == len(spec.tensors())
    for l in spec.kda_layers:
        a = np.exp(np.asarray(tree[f"layer{l}"]["A_log"]))
        assert ((a >= 1.0) & (a <= 16.0)).all()
        assert not np.asarray(tree[f"layer{l}"]["dt_bias"]).any()
    assert "router" not in tree["layer0"]                # the dense layer
    assert tree["layer1"]["gate"].shape[0] == 4          # 4 of 8 held
    assert tree["layer1"]["router"].shape[1] == 8


# --- the kernels ------------------------------------------------------------

def _naive(state, q, k, v, g, beta):
    """fla's naive recurrence, S [H, K, V] not transposed, float64."""
    s = np.asarray(state, np.float64).transpose(0, 2, 1)
    outs = []
    for t in range(q.shape[0]):
        qt, kt, vt, gt, bt = (np.asarray(a[t], np.float64)
                              for a in (q, k, v, g, beta))
        s = s * np.exp(gt)[:, :, None]
        s = s + bt[:, None, None] * kt[:, :, None] * (
            vt - np.einsum("hk,hkv->hv", kt, s))[:, None, :]
        outs.append(np.einsum("hk,hkv->hv", qt, s))
    return np.stack(outs), s.transpose(0, 2, 1)


def _kda_inputs(rng, b, c, h=8, d=16):
    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    return (jnp.asarray(unit(rng.randn(b, c, h, d)) * d ** -0.5, jnp.float32),
            jnp.asarray(unit(rng.randn(b, c, h, d)), jnp.float32),
            jnp.asarray(rng.randn(b, c, h, d), jnp.float32),
            jnp.asarray(-np.abs(rng.randn(b, c, h, d)), jnp.float32),
            jnp.asarray(1 / (1 + np.exp(-rng.randn(b, c, h))), jnp.float32))


@pytest.mark.parametrize("impl", ["kernel", "reference"])
def test_kda_recurrence_carries_chunks_resets_reused_rows_and_skips_dead(
        impl):
    """Three call rows over state rows 3, 0 (the garbage row) and 1 of
    layer 1: row 3 carries on from its state through a chunk of 5 lanes
    and then one lane, the dead row leaves its state row as it was (fresh
    or not), and row 1 (a sequence starting in a reused row: fresh)
    starts from zero whatever the row held. The kernel in interpret mode
    and the lane scan both against the naive recurrence."""
    call = ((lambda *a, **kw: kr._kda_pallas(*a, interpret=True, **kw))
            if impl == "kernel" else kr.kda_recurrent_reference)
    rng = np.random.RandomState(5)
    state = jnp.asarray(rng.randn(2, 5, 8, 16, 16), jnp.float32)
    rows = jnp.array([3, 0, 1])
    first = _kda_inputs(rng, 3, 5)
    o1, st1 = call(*first, state, rows, jnp.array([5, 0, 3]),
                   jnp.array([0, 1, 1]), layer=1)
    second = _kda_inputs(rng, 3, 2)
    o2, st2 = call(*second, st1, rows, jnp.array([1, 0, 2]),
                   jnp.array([0, 0, 0]), layer=1)
    full = [np.concatenate([np.asarray(a[0, :5]), np.asarray(b[0, :1])])
            for a, b in zip(first, second)]
    want, want_st = _naive(state[1, 3], *full)
    got = np.concatenate([np.asarray(o1[0, :5]), np.asarray(o2[0, :1])])
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(st2[1, 3]), want_st, atol=2e-5)
    fresh = [np.concatenate([np.asarray(a[2, :3]), np.asarray(b[2, :2])])
             for a, b in zip(first, second)]
    want, want_st = _naive(np.zeros((8, 16, 16)), *fresh)
    got = np.concatenate([np.asarray(o1[2, :3]), np.asarray(o2[2, :2])])
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(st2[1, 1]), want_st, atol=2e-5)
    # dead lanes and the dead row: exact zeros; untouched rows and layers
    assert not np.asarray(o1[1]).any() and not np.asarray(o1[2, 3:]).any()
    assert not np.asarray(o2[0, 1:]).any()
    keep = np.asarray(state)
    np.testing.assert_array_equal(np.asarray(st2[0]), keep[0])
    for r in (0, 2, 4):
        np.testing.assert_array_equal(np.asarray(st2[1, r]), keep[1, r])


def _un_absorbed(entries, q_nope, q_pe, w_uk, w_uv, scale):
    """One query lane's attention over the latent ``entries`` it sees,
    each head with its own key and value made from the latent (``c
    W_uk``, ``c W_uv`` and the shared ``k_pe``): ``[heads, dv]``."""
    rank = w_uk.shape[0]
    k_nope = np.einsum("tk,khn->thn", entries[:, :rank], w_uk)
    v = np.einsum("tk,khv->thv", entries[:, :rank], w_uv)
    score = (np.einsum("hn,thn->ht", q_nope, k_nope)
             + np.einsum("hr,tr->ht", q_pe, entries[:, rank:])) * scale
    prob = np.exp(score - score.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    return np.einsum("ht,thv->hv", prob, v)


def test_the_latent_kernel_is_the_un_absorbed_attention():
    """The absorbed form through the latent pool (one key a token, values
    its first ``rank`` columns) against each head's own key and value made
    from the latent (``c W_uk``, ``c W_uv`` and the shared ``k_pe``), over
    a chunk and a decoding lane and a dead slot, in interpret mode."""
    rng = np.random.RandomState(2)
    b, c, h, nope, rope, rank, dv, p = 3, 4, 4, 8, 8, 16, 8, 12
    width = 32                       # the pool row: the entry and zeros
    lat = rng.randn(p, PS, rank + rope).astype(np.float32)
    pages = np.zeros((p, PS, width), np.float32)
    pages[..., :rank + rope] = lat
    w_uk, w_uv = rng.randn(rank, h, nope), rng.randn(rank, h, dv)
    q_nope, q_pe = rng.randn(b, c, h, nope), rng.randn(b, c, h, rope)
    q = np.zeros((b, c, h, width), np.float32)
    q[..., :rank] = np.einsum("bchn,khn->bchk", q_nope, w_uk)
    q[..., rank:rank + rope] = q_pe
    tables = rng.permutation(np.arange(1, p))[:9].reshape(b, 3)
    kv, ql = np.array([10, 0, 7]), np.array([4, 0, 1])
    scale = (nope + rope) ** -0.5
    a = pa._latent_call(jnp.asarray(q), jnp.asarray(pages),
                        jnp.asarray(tables, jnp.int32), jnp.asarray(kv),
                        jnp.asarray(ql), scale=scale, value_dim=rank,
                        interpret=True)
    got = np.einsum("bchk,khv->bchv", np.asarray(a), w_uv)
    for s in (0, 2):
        entries = lat[tables[s]].reshape(-1, rank + rope)[:kv[s]]
        for j in range(ql[s]):
            want = _un_absorbed(entries[:kv[s] - ql[s] + j + 1],
                                q_nope[s, j], q_pe[s, j], w_uk, w_uv, scale)
            np.testing.assert_allclose(got[s, j], want, rtol=1e-4,
                                       atol=1e-4)
    assert not got[1].any() and not got[2, 1:].any()
    ref = pa.paged_latent_attention_reference(
        jnp.asarray(q), jnp.asarray(pages), jnp.asarray(tables, jnp.int32),
        jnp.asarray(kv), q_lens=jnp.asarray(ql), scale=scale,
        value_dim=rank)
    np.testing.assert_allclose(np.asarray(a), np.asarray(ref), atol=1e-5)


# id: page size, table width, chunk, kv_lens, q_lens, pool dtype. The
# kernel folds k = latent_pages_per_step(page size, width) pages a grid
# step: 8 at page 16, 16 at page 8, 32 at page 4, never more than width
LATENT_BLOCK_CASES = {
    # 10 columns in blocks of 8: the table padded to 16; 150 keys end
    # mid-page in the second block, 160 on a page's end
    "width_not_a_multiple_of_k": (16, 10, 4, [150, 160, 37], [4, 1, 2],
                                  "float32"),
    # 5 columns and k 5: one block of the whole table
    "width_under_k": (16, 5, 4, [70, 33, 16], [3, 1, 4], "float32"),
    # blocks of 16 pages of 8: 203 keys end 3 rows into a page, 75 into
    # the second block; 300 in the third
    "mid_page_and_mid_block": (8, 40, 4, [203, 77, 300], [4, 2, 1],
                               "float32"),
    # three blocks of 128 keys: 200 keys leave the last one dead, 129 the
    # second with one key
    "last_block_dead": (16, 24, 4, [200, 16, 129], [4, 1, 4], "float32"),
    "dead_slot": (16, 8, 4, [0, 100, 0], [0, 4, 0], "float32"),
    "decode_c1": (16, 12, 1, [191, 1, 0, 64], [1, 1, 0, 1], "float32"),
    "chunk": (4, 40, 8, [150, 8, 129], [8, 8, 3], "float32"),
    # the served dtype: the products' bfloat16 operands and p's three
    # terms (_mxu) over a block of 128 rows
    "bfloat16": (16, 10, 4, [150, 57, 128], [4, 1, 3], "bfloat16"),
}


@pytest.mark.parametrize("case", list(LATENT_BLOCK_CASES))
def test_the_block_kernel_is_the_reference_and_the_un_absorbed_attention(
        case):
    """The latent kernel, k pages a grid step, in interpret mode against
    ``paged_latent_attention_reference`` and against each head's own key
    and value made from the latent. Every pool row at or past a slot's
    ``kv_len`` (the rest of its last page, the pages of its dead columns)
    and the garbage page hold NaN in the kernel's pool and not in the
    reference's: none of it may reach the output. Dead lanes and a dead
    slot are exact zeros."""
    ps, width, c, kv, ql, dtype = LATENT_BLOCK_CASES[case]
    kv, ql = np.array(kv), np.array(ql)
    b, h, nope, rope, rank, dv, d = len(kv), 4, 8, 8, 16, 8, 32
    rng = np.random.RandomState(sum(map(ord, case)))
    p = b * width + 1
    lat = rng.randn(p, ps, rank + rope).astype(np.float32)
    pages = np.zeros((p, ps, d), np.float32)
    pages[..., :rank + rope] = lat
    pages = np.asarray(jnp.asarray(pages, dtype).astype(jnp.float32))
    lat = pages[..., :rank + rope]
    w_uk, w_uv = rng.randn(rank, h, nope), rng.randn(rank, h, dv)
    q_nope, q_pe = rng.randn(b, c, h, nope), rng.randn(b, c, h, rope)
    q = np.zeros((b, c, h, d), np.float32)
    q[..., :rank] = np.einsum("bchn,khn->bchk", q_nope, w_uk)
    q[..., rank:rank + rope] = q_pe
    q = jnp.asarray(q, dtype)
    tables = (rng.permutation(p - 1)[:b * width] + 1).reshape(b, width)
    fouled = pages.copy()
    fouled[0] = np.nan
    for s in range(b):
        rows = fouled[tables[s]].reshape(-1, d)
        rows[kv[s]:] = np.nan
        fouled[tables[s]] = rows.reshape(width, ps, d)
    scale = (nope + rope) ** -0.5
    args = (jnp.asarray(tables, jnp.int32), jnp.asarray(kv, jnp.int32),
            jnp.asarray(ql, jnp.int32))
    a = np.asarray(pa._latent_call(
        q, jnp.asarray(fouled, dtype), *args, scale=scale, value_dim=rank,
        interpret=True).astype(jnp.float32))
    want = np.asarray(pa.paged_latent_attention_reference(
        q, jnp.asarray(pages, dtype), *args[:2], q_lens=args[2],
        scale=scale, value_dim=rank).astype(jnp.float32))
    assert a.shape == (b, c, h, rank) and np.isfinite(a).all()
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(a, want, atol=tol, rtol=tol)
    dead = np.arange(c)[None, :] >= ql[:, None]
    assert not a[dead].any()
    if dtype != "float32":
        return      # the absorbed query was rounded: the reference alone
    got = np.einsum("bchk,khv->bchv", a, w_uv)
    for s in range(b):
        entries = lat[tables[s]].reshape(-1, rank + rope)
        for j in range(ql[s]):
            np.testing.assert_allclose(got[s, j], _un_absorbed(
                entries[:kv[s] - ql[s] + j + 1], q_nope[s, j], q_pe[s, j],
                w_uk, w_uv, scale), rtol=1e-4, atol=1e-4)


def test_latent_pages_per_step_follows_page_size_and_width():
    """k pages a grid step make 128 keys, capped at the table's width."""
    k = pa.latent_pages_per_step
    assert [k(16, 1024), k(16, 10), k(16, 5), k(8, 40), k(4, 40), k(4, 3),
            k(32, 1024), k(128, 7), k(256, 7), k(16, 1)] == [
        8, 8, 5, 16, 32, 3, 4, 1, 1, 1]


def test_the_latent_steps_counter_reads_the_block_grid(shared):
    """``serving.decode.attn_latent_steps_live_pct`` by hand, then as the
    engine records it: one observation a step call of a model with a
    latent pool, the share of the latent kernel's (slot, block) steps
    that fold a live page."""
    from paddle_tpu.serving.decode import _latent_steps_live_pct

    # page 16, width 10: k 8, two blocks a slot; 150 keys take both, 128
    # one, 129 both, 0 none: 5 of 8
    kv = np.array([150, 0, 128, 129])
    assert _latent_steps_live_pct(4, 10, kv, 16) == 62.5
    # width 1024: 128 blocks a slot; 1 + 1 + 74 blocks of 128 slots x 128
    assert _latent_steps_live_pct(128, 1024, np.array(
        [1, 128, 9400] + [0] * 125), 16) == pytest.approx(
        100.0 * 76 / (128 * 128))
    spec, params = shared
    name = "serving.decode.attn_latent_steps_live_pct"
    before = metrics.snapshot(name)[name]
    eng = engine(spec, 4, params)
    try:
        seen = _watched(eng)
        req = eng.submit(list(range(1, 11)), max_new_tokens=6)
        assert req.ev.wait(300) and req.error is None
    finally:
        eng.stop(drain=False)
    after = metrics.snapshot(name)[name]
    want = [_latent_steps_live_pct(2, c["width"], c["lens"], PS)
            for c in seen]
    assert after["count"] - before["count"] == len(seen) > 0
    assert after["sum"] - before["sum"] == pytest.approx(sum(want))


def test_the_witness_rounds_less_than_the_control(ref, shared):
    """The reference at the stated bfloat16 (the witness) and at float8
    (the control) round the same weights and activations: both move the
    float32 logits, the witness by far less."""
    _spec, params = shared
    seq = list(np.random.RandomState(5).randint(0, 128, size=24))
    rows = list(range(0, 24, 3))
    exact = np.asarray(ref.logits_at(params, TINY_CONFIG, seq, rows))
    gap = {p: float(np.median(np.abs(np.asarray(ref.logits_at(
        params, TINY_CONFIG, seq, rows, p)) - exact)))
        for p in ("bfloat16", "float8_e4m3")}
    assert 0.0 < gap["bfloat16"] < gap["float8_e4m3"] / 4, gap


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """``[0, E/2)`` and ``[E/2, E)``, each a chip's share of the router's
    experts, with the shared expert counted once, give what the layer
    holding every expert gives; each share's counts are over its own
    experts and the dead lane reads zeros."""
    from paddle_tpu.models.afmoe import _swiglu

    whole = tiny_spec(experts_held=[0, 8], num_experts=8)
    lp = whole.seeded_arrays()["layer1"]
    rng = np.random.RandomState(1)
    h = jnp.asarray(rng.randn(6, 64), jnp.float32)
    valid = jnp.array([True] * 5 + [False])
    full, n_full = sdar_moe.moe_layer(h, lp, valid, whole,
                                      score=sigmoid_scores)
    parts, counts = [], []
    for lo, hi in ((0, 4), (4, 8)):
        share = dict(lp, **{k: lp[k][lo:hi] for k in ("gate", "up",
                                                      "down")})
        out, n = sdar_moe.moe_layer(h, share, valid, whole,
                                    score=sigmoid_scores, held=(lo, hi))
        parts.append(out)
        counts.append(n)
    shared_ff = _swiglu(h, lp["shared_gate"], lp["shared_up"],
                        lp["shared_down"])
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] + shared_ff),
                               np.asarray(full + shared_ff), atol=1e-5)
    np.testing.assert_array_equal(np.concatenate(counts), np.asarray(n_full))
    assert int(sum(int(n.sum()) for n in counts)) == 5 * 2
    assert not np.asarray(parts[0][5]).any()


# --- the engine -------------------------------------------------------------

def _watched(eng):
    seen, real = [], eng._run_step_arrays

    def wrapped(*args, **kw):
        ids, logits = real(*args, **kw)
        seen.append({"q": np.array(args[2]), "lens": np.array(args[4]),
                     "logits": np.asarray(logits),
                     "rows": np.array(args[3].rows),
                     "width": args[3].full.shape[1]})
        return ids, logits

    eng._run_step_arrays = wrapped
    return seen


@pytest.mark.parametrize("chunk", [4, 1])
def test_the_engines_logits_through_latent_pool_and_state_rows_are_the_refs(
        ref, shared, chunk):
    """Prefill in chunks (or a token a step) then decode: every step's
    logits against the reference's ONE pass (float32 at highest: 5e-5),
    the state row held through the sequence and given back, the latent
    pool one array with no V."""
    spec, params = shared
    eng = engine(spec, chunk, params)
    try:
        seen = _watched(eng)
        resets = _ctr("serving.decode.state_resets")
        prompt = np.random.RandomState(0).randint(0, 128, size=21)
        req = eng.submit(prompt, max_new_tokens=20)
        assert req.ev.wait(300) and req.error is None
        seq = list(prompt) + req.result["tokens"]
        calls = [c for c in seen if c["q"][0] > 0]
        rows = [int(c["lens"][0]) - 1 for c in calls]
        want = np.asarray(ref.logits_at(params, TINY_CONFIG, seq,
                                        rows))[:len(rows)]
        got = np.stack([c["logits"][0] for c in calls])
        np.testing.assert_allclose(got, want, atol=5e-5)
        assert len({int(c["rows"][0]) for c in calls}) == 1
        assert calls[0]["rows"][0] != 0 and (calls[0]["rows"][1:] == 0).all()
        assert _ctr("serving.decode.state_resets") == resets + 1
        st = eng.stats()
        assert st["state"]["rows_used"] == 0 and st["kv"]["pages_used"] == 0
        assert eng.cache.v is None and eng.cache.k.shape == (1, 32, PS, 128)
        assert eng.state_cache.k.shape == (4, 3, 4, 16, 16)
        assert st["kv_hbm_bytes"] == (eng.cache.k.size * 4
                                      + eng.state_cache.hbm_bytes)
    finally:
        eng.stop(drain=False)


def test_preempt_and_resume_serves_the_same_tokens_bitwise(shared):
    """Two sequences that cannot both grow in a small latent pool: one is
    spilled (its pages and its state row) and restored into whatever row
    is free; each serves, bit for bit, what it serves alone."""
    spec, params = shared
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, 128, size=18), rng.randint(0, 128, size=17)]
    alone = []
    eng = engine(spec, 4, params)
    try:
        for p in prompts:
            alone.append(eng.generate(p, max_new_tokens=22)["tokens"])
    finally:
        eng.stop(drain=False)
    eng = engine(spec, 4, params, num_pages=16, reservation="demand")
    try:
        before = (_ctr("serving.kv.preemptions"),
                  _ctr("serving.decode.state_spills"))
        reqs = [eng.submit(p, max_new_tokens=22, temperature=0.0)
                for p in prompts]
        for r in reqs:
            assert r.ev.wait(300) and r.error is None
        assert _ctr("serving.kv.preemptions") > before[0]
        assert _ctr("serving.decode.state_spills") > before[1]
        assert [r.result["tokens"] for r in reqs] == alone
        assert eng.cache.allocator.pages_used == 0
        assert eng.stats()["state"]["rows_used"] == 0
    finally:
        eng.stop(drain=False)


def test_a_batch_under_step_ahead_serves_what_each_serves_alone(shared):
    """Three requests through two slots, the third taking a row a finished
    sequence held, one step ahead of the answers: each request's tokens
    are those it gets alone, and nothing compiles after warm()."""
    spec, params = shared
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, 128, size=n) for n in (9, 14, 5)]
    eng = engine(spec, 4, params)
    try:
        compiles = _ctr("serving.decode.compiles")
        alone = [eng.generate(p, max_new_tokens=12)["tokens"]
                 for p in prompts]
        reqs = [eng.submit(p, max_new_tokens=12, temperature=0.0)
                for p in prompts]
        for r in reqs:
            assert r.ev.wait(300) and r.error is None
        assert [r.result["tokens"] for r in reqs] == alone
        assert _ctr("serving.decode.compiles") == compiles
        assert eng.stats()["state"]["rows_used"] == 0
    finally:
        eng.stop(drain=False)


def test_a_wider_prefill_budget_grants_each_prompt_its_chunk(shared):
    """Left unset the budget is the chunk: the first slot in its prompt
    takes it and every other one token. Set wider, each slot in its prompt
    takes up to a chunk while the budget lasts, never more than a chunk,
    and a decoding slot its one token; a budget under the chunk is
    refused."""
    import types

    spec, params = shared
    slot = lambda n, pos: types.SimpleNamespace(
        pos=pos, req=types.SimpleNamespace(prompt=[0] * n, mask=None))
    live = [slot(13, 0), slot(6, 4), slot(9, 9), slot(30, 0), slot(30, 2),
            slot(30, 0)]
    eng = engine(spec, 4, params, slots=[8], warm=False)
    wide = engine(spec, 4, params, slots=[8], warm=False, prefill_budget=10)
    try:
        assert eng.prefill_budget == 4
        assert eng._grants(live) == [4, 1, 1, 1, 1, 1]
        assert wide.prefill_budget == wide.stats()["prefill_budget"] == 10
        assert wide._grants(live) == [4, 2, 1, 4, 1, 1]
    finally:
        eng.stop(drain=False)
        wide.stop(drain=False)
    with pytest.raises(ValueError, match="prefill_budget 3 is under"):
        engine(spec, 4, params, warm=False, prefill_budget=3)


def test_a_model_with_window_and_state_layers_is_refused_by_name():
    class Both(KimiLinearSpec):
        __slots__ = ()
        window = 8

        @property
        def layer_kinds(self):
            return ("window",) + KimiLinearSpec.layer_kinds.fget(self)[1:]

    spec = Both.from_config(TINY_CONFIG, dtype="float32", seed=3)
    with pytest.raises(ValueError, match="window and state layers"):
        DecodeEngine(spec, warm=False)


@pytest.mark.parametrize("field,value", [
    ("prefix_cache", True), ("embeddings", True), ("mesh", "tp=2"),
    ("spec_k", 2), ("draft_spec", DecoderSpec(vocab=128))])
def test_what_is_not_carried_through_for_state_layers_is_refused_by_name(
        field, value):
    with pytest.raises(ValueError, match=f"state layers.*'{field}'"):
        DecodeEngine(tiny_spec(), warm=False, **{field: value})


# --- the benchmark's files and comparison, rehearsed on the tiny model -----

def test_the_benchmark_finds_the_cell_its_runner_and_its_metrics():
    bench = Benchmark(ROOT)
    bench.check_files()
    cell = bench.cell("kimi_linear_longgen")
    assert cell["engine"]["slots"] == [128]
    assert bench.end_to_end("kimi_linear_longgen") == [
        "serve_tokens_per_s", "token_gap_p95_ms", "setup_s"]
    names = [m["name"] for m, _d in bench.per_layer("kimi_linear_longgen")]
    for name in ("kda_state_roofline", "paged_attn_latent_roofline",
                 "kda_step_share_pct", "paged_attn_latent_steps_live_pct"):
        assert name in names


TINY_FILE = dict(
    TINY_CONFIG, name="kimi-tiny", runner="serve_kimi_linear",
    model={"module": "paddle_tpu.models.kimi_linear",
           "spec": "KimiLinearSpec"},
    precision={"weights": "float32", "activations_between_layers": "float32",
               "latent_pool": "float32"})
TINY_CELL = {
    "name": "tiny_longgen", "config": "kimi-tiny", "chips": 1,
    "engine": {"slots": [4], "page_size": PS, "num_pages": 64,
               "max_seq_len": 40, "prefill_chunk": 4, "prefill_budget": 16},
    "expect_route": ["paged_reference"],
    "traffic": {"kind": "closed_loop_sessions", "clients": 4,
                "sessions": 6000, "requests_per_session": 1,
                "prefix_len": None,
                "suffix_len": {"lo": 3, "hi": 20, "scale": "log"},
                "answer_len": {"lo": 6, "hi": 14, "scale": "log"},
                "temperature": 1.0, "greedy_every": 2,
                "greedy_topk_first": 16, "think_ms": 1.0,
                "think_stagger_ms": 0.5, "ramp_tokens": 40,
                "ramp_max_s": 60.0, "first_token_wait_s": 30.0},
    "trace_seconds": 0.5, "check_requests": 4,
    "check_long_requests": 3, "check_long_tokens": 16,
    "check_long_max": 24, "check_long_answer": 6,
    "check_short_requests": 4, "check_short_tokens": 3,
    "check_short_answer": 6, "check_wait_s": 120.0,
    # float32 served against float32 at highest: summation order only
    "limits": {"served_logit_gap": 1e-3, "served_gap_mean": 1e-4,
               "first_rank_gap_mean_sq": 1e-6,
               "long_rank_gap_q1_sq": 1e-6, "long_off_best_pct": 1.0,
               "min_tokens_compared": 40, "min_long_requests_compared": 3,
               "min_short_requests_compared": 4},
}
PEAKS = {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}


@pytest.fixture(scope="module")
def runner():
    return load_module(os.path.join(ROOT, "perf", "runners",
                                    "serve_kimi_linear.py"),
                       "serve_kimi_linear")


def _rehearse(runner, ref, seed, control=None):
    ctx = {"bench": None, "cell": TINY_CELL, "config": TINY_FILE,
           "devices": [], "peaks": PEAKS, "reference": ref, "seed": seed,
           "seconds": 1.5, "trace": False, "trace_dir": None,
           "t_start": time.perf_counter(), "control": control}
    facts = runner.run(ctx)
    return facts, {n: (v, l, ok) for n, v, l, ok in facts["checks"]}


def test_runner_rehearsal_is_correct_and_the_control_is_not(runner, ref):
    """The cell's runner on the CPU: window, the checks after it, the
    comparison with the reference; the control one precision down in the
    program's place fails by one of the cell's limits."""
    facts, by_name = _rehearse(runner, ref, 2 ** 31 + 4201,
                               ("float8_e4m3",))
    assert not [n for n, (_v, _l, ok) in by_name.items() if not ok], by_name
    got = facts["readings"]
    assert got["long_requests_compared"] >= 3
    assert got["tokens_compared"] >= 40 and got["ranks_compared"] > 0
    assert facts["end_to_end"]["serve_tokens_per_s"] > 0
    assert facts["processed_flops"] > 0
    assert facts["counters"]["serving.decode.state_resets"] > 0
    control = got["controls"]["float8_e4m3"]
    assert not control["correct"] and control["failed_by"]


@pytest.mark.parametrize("fault,caught_by", [
    ("wrong_token", "served_logit_gap"),
    ("state_carry", "first_rank_gap_mean_sq"),
    ("latent_short", "served_logit_gap")])
def test_a_planted_fault_is_not_correct(runner, ref, fault, caught_by):
    """Each fault of the runner's tool fails a number: a token that is not
    the program's choice lies units under the reference's best; a state
    row not started from zero moves a fresh sequence's first tokens; an
    MLA layer that does not see a lane's own entry moves every token."""
    undo = (runner.plant_wrong_token(every=3) if fault == "wrong_token"
            else runner.FAULTS[fault]())
    try:
        _facts, by_name = _rehearse(runner, ref, 430043 + len(fault))
    finally:
        undo()
    value, limit, ok = by_name[caught_by]
    assert not ok and value > 10 * limit, by_name
    if fault == "wrong_token":
        # the number the benchmark's cell judges it by
        value, limit, ok = by_name["served_gap_mean"]
        assert not ok and value > 10 * limit, by_name
    for name in ("requests_failed", "checks_after_failed",
                 "answers_of_wrong_length"):
        assert by_name[name][2], by_name
