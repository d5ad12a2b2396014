"""The ``afmoe`` model (Trinity-Mini's family), the window in the paged
kernel and the cache that holds each layer kind its own way (ISSUE 34).

All on the CPU at the tiny preset (5 layers: a dense one, then a period of
three window layers and a full one; d 64, 4/2 heads of 16, 8 experts top-2
of width 32 beside a shared one, window 8, vocab 128), seeded float32
weights, against the ONE plain reference the benchmark also uses,
``perf/references/trinity-mini.py``, loaded by path.
"""
import collections
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.fluid.ops.pallas_kernels import paged_attention as pa
from paddle_tpu.models import sdar_moe
from paddle_tpu.models.afmoe import (TINY_CONFIG, AfmoeSpec, afmoe_step,
                                     sigmoid_scores)
from paddle_tpu.models.decoders import (DecoderSpec, KindTables,
                                        spec_from_dict)
from paddle_tpu.observability import metrics
from paddle_tpu.serving.decode import DecodeEngine, _call_work
from paddle_tpu.serving.kv_cache import PageAllocator

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = os.path.join(ROOT, "perf", "references", "trinity-mini.py")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PS, WINDOW = 4, TINY_CONFIG["sliding_window"]


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("trinity_reference",
                                                  REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # the tiny preset's sequences: small pads, so that little compiles
    mod.PAD, mod.Q_BLOCK, mod.ROW_PAD = 64, 16, 8
    return mod


def tiny_spec(seed=3, **over):
    return AfmoeSpec.from_config(dict(TINY_CONFIG, **over), dtype="float32",
                                 seed=seed)


def engine(spec, chunk, params=None, **kw):
    opts = dict(name="afmoe", slots=[2], page_size=PS, num_pages=48,
                num_window_pages=24, max_seq_len=48, prefill_chunk=chunk,
                params=params)
    opts.update(kw)
    return DecodeEngine(spec, **opts)


def _ctr(name):
    return metrics.counter(name).value()


# --- the model ------------------------------------------------------------

def test_reference_imports_nothing_of_the_program():
    with open(REFERENCE) as f:
        src = f.read()
    assert "paddle_tpu" not in src.replace("imports nothing of the", "")
    assert "Precision.HIGHEST" in src and "float32" in src


def test_from_config_on_the_catalogs_keys_gives_the_published_sizes():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Mini")
    spec = AfmoeSpec.from_config(row["config"])
    sizes = collections.Counter()
    for name, shape in spec.tensors().items():
        layer, _, leaf = name.rpartition("/")
        sizes[(layer, leaf)] = int(np.prod(shape))
    dense, moe = "layer1", "layer2"     # the last dense and the first expert
    attn = sum(sizes[(dense, k)] for k in ("wq", "wk", "wv", "wg", "wo"))
    gains = sum(sizes[(dense, k)] for k in (
        "ln_in", "ln_post_attn", "ln_pre_mlp", "ln_post_mlp", "q_norm",
        "k_norm"))
    assert attn == 2048 * 4096 * 3 + 2048 * 512 * 2 == 27_262_976
    assert gains == 4 * 2048 + 2 * 128 == 8_448
    assert sum(sizes[(dense, k)] for k in ("gate", "up", "down")) \
        == 3 * 2048 * 6144 == 37_748_736
    assert sizes[(moe, "router")] == 262_144
    assert sizes[(moe, "expert_bias")] == 128
    assert sum(sizes[(moe, k)] for k in (
        "shared_gate", "shared_up", "shared_down")) == 6_291_456
    assert sum(sizes[(moe, k)] for k in ("gate", "up", "down")) \
        == 805_306_368
    per_layer = collections.Counter()
    for (layer, _leaf), n in sizes.items():
        per_layer[layer] += n
    assert per_layer[dense] == 27_262_976 + 8_448 + 37_748_736
    assert per_layer[moe] == (27_262_976 + 8_448 + 262_144 + 128
                              + 6_291_456 + 805_306_368)
    assert sizes[("", "tok_emb")] + sizes[("", "head")] == 2 * 200192 * 2048
    assert spec.layer_kinds == ("window", "window", "window", "full") * 8
    assert spec.window == 2048 and spec.n_dense_layers == 2
    assert spec.moe_assignments_per_token == 8 * 30
    assert spec.block_length == 1 and spec.expert_width == 1024


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"type": "yarn", "factor": 4.0}), ("n_group", 2),
    ("topk_group", 2), ("score_func", "softmax"),
    ("tie_word_embeddings", True)])
def test_from_config_refuses_by_name_what_the_step_is_not_written_for(
        key, value):
    with pytest.raises(ValueError, match=key):
        AfmoeSpec.from_config(dict(TINY_CONFIG, **{key: value}))


def test_a_model_of_this_family_without_a_window_layer_is_refused():
    """The step takes each pool as the pair of its layer kinds: there is
    no second path for a model whose layers are all full."""
    with pytest.raises(ValueError, match="without a 'sliding_attention'"):
        AfmoeSpec.from_config(dict(
            TINY_CONFIG, layer_types=["full_attention"] * 5))
    with pytest.raises(ValueError, match="window >= 1"):
        AfmoeSpec.from_config(dict(TINY_CONFIG, sliding_window=None))


def test_the_family_is_found_by_name_and_round_trips():
    spec = tiny_spec()
    again = spec_from_dict(spec.to_dict())
    assert isinstance(again, AfmoeSpec) and again.to_dict() == spec.to_dict()
    tree = spec.seeded_arrays()
    flat = {k: tuple(v.shape) for k, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert len(flat) == len(spec.tensors())
    assert not np.asarray(tree["layer1"]["expert_bias"]).any()
    assert "router" not in tree["layer0"]           # the dense layer
    # what the engine asks: kinds and window; the others answer all full
    assert spec.layer_kinds == ("window",) * 4 + ("full",)
    assert DecoderSpec(n_layers=3).layer_kinds == ("full",) * 3
    assert DecoderSpec().window is None
    assert sdar_moe.SdarMoeSpec.from_config(
        sdar_moe.TINY_CONFIG).layer_kinds == ("full", "full")


# --- one dropless expert layer --------------------------------------------

def _old_sdar_moe_layer(h, lp, valid, spec):
    """``moe_layer`` with the family's scoring written inside, as it was
    before the model supplied it, and the gather combine: what the shared
    layer with ``softmax_scores`` must equal to the bit."""
    from paddle_tpu.fluid.ops.pallas_kernels.moe_gmm import grouped_dot

    t = h.shape[0]
    e, k = spec.n_experts, spec.experts_per_token
    probs = jax.nn.softmax(sdar_moe._dot(h, lp["router"]), axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    if spec.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    idx = jnp.where(valid[:, None], idx, e).reshape(t * k)
    order = jnp.argsort(idx, stable=True)
    pos = jnp.argsort(order).reshape(t, k)
    counts = jnp.zeros((e + 1,), jnp.int32).at[idx].add(1)[:e]
    xs = h[order // k]
    act = grouped_dot(xs, lp["up"], counts, gate=lp["gate"])
    y = grouped_dot(act, lp["down"], counts)
    out = w[:, :1] * y[pos[:, 0]]
    for j in range(1, k):
        out = out + w[:, j:j + 1] * y[pos[:, j]]
    return jnp.where(valid[:, None], out, 0.0), counts


def test_sdar_moes_expert_layer_is_unchanged_to_the_bit():
    spec = sdar_moe.SdarMoeSpec.from_config(
        sdar_moe.TINY_CONFIG, dtype="float32", seed=5)
    lp = jax.device_put(spec.seeded_arrays()["layer0"])
    rng = np.random.RandomState(1)
    h = jnp.asarray(rng.randn(12, spec.d_model).astype(np.float32))
    valid = jnp.asarray([True] * 10 + [False] * 2)
    new, n_new = jax.jit(lambda h: sdar_moe.moe_layer(h, lp, valid, spec))(h)
    old, n_old = jax.jit(lambda h: _old_sdar_moe_layer(h, lp, valid,
                                                       spec))(h)
    assert np.array_equal(np.asarray(new), np.asarray(old))
    assert np.array_equal(np.asarray(n_new), np.asarray(n_old))


def test_afmoes_scores_sum_to_the_route_scale_and_the_bias_only_selects():
    spec = tiny_spec()
    lp = dict(jax.device_put(spec.seeded_arrays()["layer1"]))
    rng = np.random.RandomState(2)
    h = jnp.asarray(rng.randn(6, spec.d_model).astype(np.float32))
    w, idx = sigmoid_scores(h, lp, spec)
    assert w.shape == idx.shape == (6, spec.experts_per_token)
    np.testing.assert_allclose(np.asarray(w).sum(-1), spec.route_scale,
                               rtol=1e-6)
    # a bias that lifts expert 7 over all selects it for every token and
    # weighs it by its own SCORE, not by score + bias
    lp["expert_bias"] = jnp.zeros((spec.n_experts,)).at[7].set(10.0)
    wb, idxb = sigmoid_scores(h, lp, spec)
    assert (np.asarray(idxb) == 7).any(axis=-1).all()
    s = np.asarray(jax.nn.sigmoid(h @ lp["router"]))
    chosen = np.take_along_axis(s, np.asarray(idxb), axis=-1)
    np.testing.assert_allclose(
        np.asarray(wb), chosen / chosen.sum(-1, keepdims=True)
        * spec.route_scale, rtol=1e-5)


def test_the_shared_expert_is_added_once_beside_the_routed_ones(ref):
    """One expert layer of the step against the reference's formula: the
    step's output moves by exactly Shared(h) when the shared expert's
    ``down`` is zeroed."""
    spec = tiny_spec(num_hidden_layers=2, num_dense_layers=1,
                     layer_types=["sliding_attention", "full_attention"])
    params = jax.device_put(spec.seeded_arrays())
    toks = jnp.asarray([[5, 9, 11, 2]], jnp.int32)
    pos = jnp.arange(4, dtype=jnp.int32)[None]
    tables = KindTables(jnp.asarray([[1]], jnp.int32),
                        jnp.asarray([[1]], jnp.int32),
                        jnp.zeros((1,), jnp.int32))
    pools = tuple(jnp.zeros((1, 4, PS, 2, 16)) for _ in range(2))

    def logits(p):
        return np.asarray(afmoe_step(
            p, spec, toks, pos, jnp.asarray([4]), pools, pools, tables,
            jnp.asarray([4]), all_lanes=True)[2][0])

    want = np.asarray(ref.logits_at(params, spec_cfg(spec), [5, 9, 11, 2],
                                    range(4)))[:4]
    np.testing.assert_allclose(logits(params), want, atol=2e-5)
    without = jax.tree_util.tree_map(lambda a: a, params)
    without["layer1"] = dict(without["layer1"], shared_down=jnp.zeros_like(
        params["layer1"]["shared_down"]))
    assert np.abs(logits(without) - want).max() > 1e-3


def spec_cfg(spec):
    """The reference's configuration keys of a tiny spec."""
    return dict(TINY_CONFIG, num_hidden_layers=spec.n_layers,
                num_dense_layers=spec.n_dense_layers,
                layer_types=list(spec.layer_types))


# --- a window in paged_attention ------------------------------------------

def _window_case(rng, window, kvs, qs, chunk, width):
    """Pools, tables that start at each slot's first page in view, and the
    dense masked softmax's answer."""
    hkv, hq, d, b = 2, 4, 8, len(kvs)
    k_pages = rng.randn(40, PS, hkv, d).astype(np.float32)
    v_pages = rng.randn(40, PS, hkv, d).astype(np.float32)
    q = rng.randn(b, chunk, hq, d).astype(np.float32)
    tables = np.zeros((b, width), np.int32)
    starts = np.zeros(b, np.int32)
    want = np.zeros((b, chunk, hq, d), np.float32)
    nxt = 1
    for i, (kv, ql) in enumerate(zip(kvs, qs)):
        starts[i] = max(kv - ql - window + 1, 0) // PS
        held = -(-kv // PS) - starts[i]
        assert held <= width
        tables[i, :held] = np.arange(nxt, nxt + held)
        nxt += held
        keys = np.zeros((kv + PS, hkv, d), np.float32)
        vals = np.zeros_like(keys)
        for col in range(held):
            lo = (starts[i] + col) * PS
            keys[lo:lo + PS] = k_pages[tables[i, col]]
            vals[lo:lo + PS] = v_pages[tables[i, col]]
        for j in range(ql):
            at = kv - ql + j
            lo = max(0, at - window + 1)
            for h in range(hq):
                s = keys[lo:at + 1, h // 2] @ q[i, j, h] * d ** -0.5
                p = np.exp(s - s.max())
                want[i, j, h] = p / p.sum() @ vals[lo:at + 1, h // 2]
    args = [jnp.asarray(a) for a in (q, k_pages, v_pages, tables)]
    return args + [jnp.asarray(kvs, jnp.int32)], dict(
        q_lens=jnp.asarray(qs, jnp.int32), window=window,
        table_starts=jnp.asarray(starts)), want


@pytest.mark.parametrize("impl", ["reference", "kernel_interpret"])
@pytest.mark.parametrize("window,kvs,qs,chunk,width", [
    (6, [13, 5, 30, 0], [1, 1, 1, 0], 1, 4),       # ends inside a page
    (8, [13, 5, 32, 24], [1, 1, 1, 1], 1, 4),      # ends on a page's edge
    (6, [13, 9, 30, 3], [4, 3, 4, 3], 4, 5),       # a chunk, inside
    (8, [16, 9, 32, 40], [4, 4, 4, 2], 4, 5),      # a chunk, on the edge
])
def test_a_window_sees_its_newest_keys_and_nothing_behind_them(
        impl, window, kvs, qs, chunk, width):
    args, kw, want = _window_case(np.random.RandomState(0), window, kvs, qs,
                                  chunk, width)
    if impl == "reference":
        got = pa.paged_attention_reference(*args, **kw)
    else:
        got = pa._paged_attention_pallas(*args, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6)


def test_a_page_behind_the_window_is_neither_fetched_nor_folded():
    """NaN in every page the window's oldest lane cannot see, and in the
    pool's pages no table names, reaches no output."""
    args, kw, want = _window_case(np.random.RandomState(3), 6, [30, 21],
                                  [2, 1], 2, 8)
    q, k_pages, v_pages, tables, kv_lens = args
    # hand the kernel the WHOLE sequence's table (starts 0): the columns
    # behind the window then name real pages, which are poisoned
    full = np.zeros((2, 8), np.int32)
    starts = np.asarray(kw["table_starts"])
    poisoned = []
    nxt = 30
    for i in range(2):
        for col in range(8):
            if col < starts[i]:
                full[i, col] = nxt
                poisoned.append(nxt)
                nxt += 1
            elif col - starts[i] < tables.shape[1]:
                full[i, col] = int(tables[i, col - starts[i]])
    k_bad = np.array(k_pages)
    k_bad[poisoned] = np.nan
    got = pa._paged_attention_pallas(
        q, jnp.asarray(k_bad), v_pages, jnp.asarray(full), kv_lens,
        q_lens=kw["q_lens"], window=6,
        table_starts=jnp.zeros((2,), jnp.int32), interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6)
    live = pa._live_columns(jnp.asarray(full), kv_lens, PS,
                            jnp.zeros((2,), jnp.int32),
                            pa._window_floor(kv_lens, kw["q_lens"], 6))
    assert not set(np.asarray(live).ravel()) & set(poisoned)


def _primitives(jaxpr, out=None):
    out = collections.Counter() if out is None else out
    for eqn in jaxpr.eqns:
        out[eqn.primitive.name] += 1
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                if hasattr(sub, "eqns"):
                    _primitives(sub, out)
                elif hasattr(getattr(sub, "jaxpr", None), "eqns"):
                    _primitives(sub.jaxpr, out)
    return out


# the primitives of one call with no window, counted on PR 33's tree
PARENT_PRIMITIVES = {
    "reference": {
        "add": 3, "and": 1, "broadcast_in_dim": 16,
        "convert_element_type": 2, "div": 1, "dot_general": 2, "exp": 1,
        "gather": 2, "iota": 2, "jit": 1, "le": 1, "lt": 3, "max": 1,
        "mul": 2, "reduce_max": 1, "reduce_sum": 1, "reshape": 4,
        "select_n": 3, "sub": 2, "transpose": 2},
    "kernel": {
        "add": 7, "broadcast_in_dim": 12, "cond": 3,
        "convert_element_type": 6, "div": 2, "eq": 2, "exp": 2, "gather": 1,
        "get": 10, "iota": 2, "jit": 2, "le": 1, "lt": 3, "max": 3,
        "min": 1, "mul": 8, "pallas_call": 1, "program_id": 2,
        "reduce_max": 1, "reduce_sum": 3, "reshape": 3, "select_n": 2,
        "sub": 4, "swap": 7, "while": 1}}


@pytest.mark.parametrize("impl", ["reference", "kernel"])
def test_no_window_traces_the_program_it_always_did(impl):
    q = jnp.zeros((2, 4, 4, 8))
    pages = jnp.zeros((16, 4, 2, 8))
    tables = jnp.zeros((2, 3), jnp.int32)
    lens, q_lens = jnp.array([5, 9]), jnp.array([2, 4])

    def call(*a):
        if impl == "reference":
            return pa.paged_attention_reference(*a[:5], q_lens=a[5],
                                                window=None)
        return pa._paged_attention_pallas(*a[:5], q_lens=a[5],
                                          interpret=True, window=None)

    got = _primitives(jax.make_jaxpr(call)(q, pages, pages, tables, lens,
                                           q_lens).jaxpr)
    assert dict(got) == PARENT_PRIMITIVES[impl]
    with pytest.raises(ValueError, match="table_starts"):
        pa.paged_attention(q, pages, pages, tables, lens, q_lens=q_lens,
                           table_starts=jnp.zeros((2,), jnp.int32))


def test_a_calls_window_sums_follow_the_mask():
    q, kv = np.array([1, 4, 0, 3]), np.array([30, 10, 0, 5])
    work = _call_work(4, 4, 8, q, kv, window=WINDOW)
    pairs = keys = causal = 0
    for n_q, n_kv in zip(q, kv):
        seen = set()
        for j in range(n_q):
            at = n_kv - n_q + j
            mine = range(max(0, at - WINDOW + 1), at + 1)
            pairs += len(mine)
            causal += at + 1
            seen.update(mine)
        keys += len(seen)
    assert work["attn_pairs_window"] == pairs
    assert work["kv_tokens_window"] == keys
    assert work["attn_pairs_full"] == work["attn_pairs"] == causal
    assert work["kv_tokens_full"] == work["kv_tokens"] == kv.sum()
    assert "attn_pairs_window" not in _call_work(4, 4, 8, q, kv)


# --- a cache that knows layer kinds ---------------------------------------

def test_the_allocator_gives_pages_back_from_the_front():
    alloc = PageAllocator(12, PS)
    pages = alloc.alloc(7, 16)                      # 4 pages
    released = _ctr("serving.kv.window.pages_released")
    assert alloc.release_head(7, 2) == 2 and alloc.head(7) == 2
    assert alloc.pages_of(7) == pages[2:]
    assert alloc.release_head(7, 2) == 0            # nothing new behind
    assert alloc.release_head(7, 9) == 1            # keeps its last page
    assert alloc.head(7) == 3 and alloc.held_pages(7) == 1
    assert _ctr("serving.kv.window.pages_released") - released == 3
    assert list(alloc.table_starts([7, 99], 3)) == [3, 0, 0]
    alloc.free(7)
    assert alloc.pages_free == 11 and alloc.head(7) == 0
    # a sequence coming back from a spill is reserved from its first page
    assert len(alloc.alloc(8, 30, first_page=5)) == 3 and alloc.head(8) == 5


@pytest.fixture(scope="module")
def shared():
    spec = tiny_spec()
    return spec, jax.device_put(spec.seeded_arrays())


def _watched(eng):
    """Wrap the engine's device call: every call's ``(q_lens, lens,
    logits)`` and what the window kind's table held, row by row."""
    seen, real = [], eng._run_step_arrays

    def wrapped(*args, **kw):
        q_lens, tables, lens = np.array(args[2]), args[3], np.array(args[4])
        assert isinstance(tables, KindTables)
        assert tables.window.shape[1] == min(tables.full.shape[1],
                                             eng._wwidth)
        ids, logits = real(*args, **kw)
        seen.append({"q": q_lens, "lens": lens, "logits": logits,
                     "starts": np.array(tables.starts),
                     "held": (np.array(tables.window) != 0).sum(axis=1)})
        return ids, logits

    eng._run_step_arrays = wrapped
    return seen


@pytest.mark.parametrize("chunk", [4, 1])
def test_the_engines_logits_through_both_caches_are_the_references(
        ref, shared, chunk):
    """Prefill then decode, chunked and unchunked, of a sequence that
    passes the window by several pages: every step's logits against the
    reference's ONE pass (float32 at highest: 2e-5), and the window kind's
    pages given back in the round they fall behind the window."""
    spec, params = shared
    eng = engine(spec, chunk, params)
    try:
        used = (eng.cache.allocator.pages_used,
                eng.window_cache.allocator.pages_used)
        seen = _watched(eng)
        prompt = np.random.RandomState(0).randint(0, 128, size=21)
        req = eng.submit(prompt, max_new_tokens=20)
        assert req.ev.wait(300) and req.error is None
        seq = list(prompt) + req.result["tokens"]
        calls = [c for c in seen if c["q"][0] > 0]
        rows = [int(c["lens"][0]) - 1 for c in calls]
        want = np.asarray(ref.logits_at(params, TINY_CONFIG, seq,
                                        rows))[:len(rows)]
        got = np.stack([np.asarray(c["logits"][0]) for c in calls])
        np.testing.assert_allclose(got, want, atol=2e-5)
        bound = -(-(WINDOW + chunk) // PS) + 1
        for c in calls:
            oldest = int(c["lens"][0] - c["q"][0])
            assert c["starts"][0] == max(0, oldest - WINDOW + 1) // PS
            assert 1 <= c["held"][0] <= bound
        assert max(c["starts"][0] for c in calls) >= 7   # several pages
        assert (eng.cache.allocator.pages_used,
                eng.window_cache.allocator.pages_used) == used
        st = eng.stats()
        assert st["window"] == WINDOW and st["kv_window"]["pages_used"] == 0
        assert st["kv_hbm_bytes"] == (eng.cache.hbm_bytes
                                      + eng.window_cache.hbm_bytes)
        assert eng.cache.k.shape[0] == 1 and eng.window_cache.k.shape[0] == 4
    finally:
        eng.stop(drain=False)


def test_preempt_and_resume_past_the_window_serves_the_same_tokens(shared):
    """Two sequences that cannot both grow in a small full pool: one is
    spilled (both kinds) and restored from its window's first page; each
    serves what it serves alone."""
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
    eng = engine(spec, 4, params, num_pages=16, num_window_pages=10,
                 reservation="demand")
    try:
        before = _ctr("serving.kv.preemptions")
        reqs = [eng.submit(p, max_new_tokens=22, temperature=0.0)
                for p in prompts]
        for r in reqs:
            assert r.ev.wait(300) and r.error is None
        assert _ctr("serving.kv.preemptions") > before
        assert [r.result["tokens"] for r in reqs] == alone
        assert eng.cache.allocator.pages_used == 0
        assert eng.window_cache.allocator.pages_used == 0
    finally:
        eng.stop(drain=False)


@pytest.mark.parametrize("field,value", [
    ("prefix_cache", True), ("embeddings", True), ("mesh", "tp=2"),
    ("spec_k", 2), ("draft_spec", DecoderSpec(vocab=128))])
def test_what_is_not_carried_through_for_window_layers_is_refused_by_name(
        field, value):
    with pytest.raises(ValueError, match=f"window layers.*'{field}'"):
        DecodeEngine(tiny_spec(), warm=False, **{field: value})


def test_refusals_that_are_not_window_models():
    with pytest.raises(ValueError, match="num_window_pages"):
        DecodeEngine(DecoderSpec(), warm=False, num_window_pages=8)
    with pytest.raises(ValueError, match="cannot hold one sequence's"):
        DecodeEngine(tiny_spec(), warm=False, page_size=PS,
                     num_window_pages=3)


# --- the benchmark's comparison, rehearsed on the tiny model ---------------

TINY_FILE = dict(
    TINY_CONFIG, name="trinity-tiny", runner="serve_causal_model",
    model={"module": "paddle_tpu.models.afmoe", "spec": "AfmoeSpec"},
    precision={"weights": "float32"})
TINY_CELL = {
    "name": "tiny_longmix", "config": "trinity-tiny", "chips": 1,
    "engine": {"slots": [4], "page_size": PS, "num_pages": 96,
               "num_window_pages": 48, "max_seq_len": 48,
               "prefill_chunk": 4},
    "expect_route": ["paged_reference"],
    "traffic": {"kind": "closed_loop_sessions", "clients": 4,
                "sessions": 6000, "requests_per_session": 1,
                "prefix_len": None, "suffix_len": {"lo": 3, "hi": 30},
                "answer_len": {"lo": 6, "hi": 16}, "temperature": 1.0,
                "greedy_every": 2, "greedy_topk_first": 16,
                "think_ms": 1.0, "think_stagger_ms": 0.5,
                "ramp_tokens": 40, "ramp_max_s": 60.0,
                "first_token_wait_s": 30.0},
    "trace_seconds": 0.5, "check_requests": 6, "check_long_requests": 3,
    "check_long_tokens": 3 * WINDOW, "check_long_answer": 6,
    "check_long_wait_s": 120.0,
    # float32 served against float32 at highest: summation order only
    "limits": {"served_logit_gap": 1e-3, "first_rank_gap_mean_sq": 1e-6,
               "long_rank_gap_q1_sq": 1e-6, "long_off_best_pct": 1.0,
               "min_tokens_compared": 40, "min_long_requests_compared": 3},
}


PEAKS = {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}


@pytest.fixture(scope="module")
def runner():
    import sys
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from perf.lib.loader import load_module
    return load_module(os.path.join(ROOT, "perf", "runners",
                                    "serve_causal_model.py"),
                       "serve_causal_model")


def _rehearse(runner, ref, seed, control=None):
    import time
    ctx = {"bench": None, "cell": TINY_CELL, "config": TINY_FILE,
           "devices": [], "peaks": PEAKS, "reference": ref, "seed": seed,
           "seconds": 2.0, "trace": False, "trace_dir": None,
           "t_start": time.perf_counter(), "control": control}
    facts = runner.run(ctx)
    return facts, {n: (v, l, ok) for n, v, l, ok in facts["checks"]}


def test_runner_rehearsal_is_correct_and_the_control_is_not(runner, ref):
    """The cell's runner on the CPU: window, long checks after it, the
    comparison with the reference; the control one precision down in the
    program's place fails by one of the cell's limits."""
    facts, by_name = _rehearse(runner, ref, 2 ** 31 + 3401,
                               ("float8_e4m3",))
    assert not [n for n, (_v, _l, ok) in by_name.items() if not ok], by_name
    got = facts["readings"]
    assert got["long_requests_compared"] >= 3
    assert got["tokens_compared"] >= 40 and got["ranks_compared"] > 0
    assert facts["end_to_end"]["serve_tokens_per_s"] > 0
    assert facts["processed_flops"] > 0
    for name in ("serving.kv.window.held_pct",
                 "serving.decode.attn_window_skip_pct"):
        assert facts["histograms"][name]["count"] > 0
    assert facts["counters"]["serving.kv.window.pages_released"] > 0
    assert any(n.startswith("longest in the window, ms: {\"step_ms\"")
               for n in facts["notes"])
    control = got["controls"]["float8_e4m3"]
    assert not control["correct"] and control["failed_by"]
    assert control["tokens_compared"] == got["tokens_compared"]


@pytest.mark.parametrize("fault,caught_by", [
    ("wrong_token", "served_logit_gap"),
    ("window_short", "long_rank_gap_q1_sq"),
    ("window_long", "long_rank_gap_q1_sq"),
    ("page_early", "long_off_best_pct")])
def test_a_planted_fault_is_not_correct(runner, ref, fault, caught_by):
    """Each fault of the runner's tool fails the number that is its to
    catch: a token that is not the program's choice lies units under the
    reference's best (``served_logit_gap``'s upper reading); a window one
    key off, or a window page that another sequence holds, moves what
    every sequence past the window reads."""
    undo = (runner.plant_wrong_token(every=3) if fault == "wrong_token"
            else runner.FAULTS[fault]())
    try:
        facts, by_name = _rehearse(runner, ref, 340034 + len(fault))
    finally:
        undo()
    value, limit, ok = by_name[caught_by]
    assert not ok and value > 10 * limit, by_name
    if fault == "wrong_token":
        assert value > 1.0 and facts["readings"]["tokens_off_the_best"] > 0
    for name in ("requests_failed", "long_checks_failed",
                 "answers_of_wrong_length"):
        assert by_name[name][2], by_name
