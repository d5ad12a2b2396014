"""The ``sdar_moe`` model and the model interface the engine asks (ISSUE 30).

All on the CPU at the tiny preset (2 layers, d 64, 4/2 heads of 16, 8
experts top-2 of width 32, vocab 128, B 4), seeded weights, against the ONE
plain reference the benchmark also uses, ``perf/references/
sdar-30b-a3b-chat.py``, loaded by path.
"""
import contextlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.checkpoint.decoder import expected_decoder_tensors
from paddle_tpu.fluid.flags import FLAGS, set_flags
from paddle_tpu.fluid.ops.pallas_kernels import paged_attention as pa
from paddle_tpu.fluid.ops.pallas_kernels.moe_gmm import moe_route
from paddle_tpu.models.decoders import (DecoderSpec, spec_from_dict,
                                        validate_draft_spec)
from paddle_tpu.models.sdar_moe import (TINY_CONFIG, SdarMoeSpec, moe_layer,
                                        sdar_moe_step)
from paddle_tpu.serving.decode import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = os.path.join(ROOT, "perf", "references", "sdar-30b-a3b-chat.py")
MASK_ID = 127
ASSUMED = {"block_length": 4, "mask_token_id": MASK_ID}
CFG = dict(TINY_CONFIG, assumed=ASSUMED)
# the tiny preset at widths the experts' kernel tiles (multiples of 128)
WIDE_CONFIG = dict(TINY_CONFIG, hidden_size=128, head_dim=32,
                   moe_intermediate_size=128)


def load_reference():
    spec = importlib.util.spec_from_file_location("sdar_reference", REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return load_reference()


def tiny_spec(dtype="float32", seed=3, config=TINY_CONFIG, **kw):
    return SdarMoeSpec.from_config(config, block_length=4,
                                   mask_token_id=MASK_ID, dtype=dtype,
                                   seed=seed, **kw)


@contextlib.contextmanager
def pallas_forced():
    """``use_pallas_kernels`` forced on: off a TPU the kernels then run in
    interpret mode wherever their routes accept the shapes."""
    was = FLAGS["use_pallas_kernels"]
    set_flags({"use_pallas_kernels": True})
    try:
        yield
    finally:
        set_flags({"use_pallas_kernels": was})


def experts_case(impl):
    """``(config, context)`` of one implementation of the grouped products:
    ``ragged_dot`` is the tiny preset as the CPU routes it, ``gmm_interpret``
    the Pallas kernel in interpret mode at widths of 128."""
    if impl == "ragged_dot":
        return TINY_CONFIG, contextlib.nullcontext()
    return WIDE_CONFIG, pallas_forced()


def test_reference_imports_nothing_of_the_program():
    with open(REFERENCE) as f:
        src = f.read()
    assert "paddle_tpu" not in src.replace("imports nothing of the", "")
    assert 'Precision.HIGHEST' in src and "float32" in src


def test_spec_reads_the_public_config_keys_and_round_trips():
    spec = tiny_spec(dtype="bfloat16")
    assert (spec.d_model, spec.n_layers, spec.n_heads, spec.n_kv_heads,
            spec.head_dim, spec.expert_width, spec.n_experts,
            spec.experts_per_token, spec.vocab) == (64, 2, 4, 2, 16, 32, 8,
                                                    2, 128)
    assert (spec.block_length, spec.mask_token_id, spec.pool_dtype,
            spec.param_dtype) == (4, MASK_ID, "bfloat16", "bfloat16")
    assert spec.moe_assignments_per_token == 2 * 2
    again = spec_from_dict(spec.to_dict())
    assert isinstance(again, SdarMoeSpec)
    assert again.to_dict() == spec.to_dict()
    with pytest.raises(ValueError, match="nonsense"):
        SdarMoeSpec.from_dict(dict(spec.to_dict(), nonsense=1))
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        SdarMoeSpec.from_config(dict(TINY_CONFIG, tie_word_embeddings=True))
    with pytest.raises(ValueError, match="unknown decoder family"):
        spec_from_dict({"family": "nope"})


def test_the_dense_decoder_is_one_such_model():
    dense = DecoderSpec(vocab=97, d_model=32, n_layers=2, n_heads=4,
                        n_kv_heads=2, seed=11)
    assert (dense.block_length, dense.mask_token_id, dense.pool_dtype,
            dense.moe_assignments_per_token) == (1, None, "float32", 0)
    assert isinstance(spec_from_dict(dense.to_dict()), DecoderSpec)
    flat = expected_decoder_tensors(dense)
    assert flat == dense.tensors()
    assert flat["layer1/w1"] == (32, 128) and flat["lnf/1"] == (32,)


def test_tensor_names_and_shapes_match_the_seeded_tree():
    spec = tiny_spec(dtype="bfloat16")
    from paddle_tpu.mesh import flatten_param_names

    tree = spec.seeded_arrays()
    got = {n: tuple(a.shape) for n, a in flatten_param_names(tree)}
    assert got == expected_decoder_tensors(spec)
    assert got["layer0/gate"] == (8, 64, 32)
    assert got["layer1/down"] == (8, 32, 64) and got["head"] == (64, 128)
    assert all(str(a.dtype) == "bfloat16"
               for _n, a in flatten_param_names(tree))


def test_old_decoder_through_the_model_interface_gives_the_ids_it_gave():
    """Golden ids of the parent commit (bb22a8f), greedy and drawn: the
    dense decoder as one model among others is unchanged in arithmetic."""
    spec = DecoderSpec(vocab=97, d_model=32, n_layers=2, n_heads=4,
                       n_kv_heads=2, seed=11)
    eng = DecodeEngine(spec, name="golden", slots=[2], page_size=4,
                       num_pages=32, max_seq_len=40, prefill_chunk=4,
                       prefix_cache=False)
    try:
        got = [eng.generate(p, max_new_tokens=12, **kw)["tokens"]
               for p, kw in (([5, 9, 2, 77, 31, 8, 64], {}),
                             ([1, 2, 3], {"temperature": 0.9, "seed": 1234}),
                             (list(range(20, 33)), {"temperature": 0.0}))]
        with pytest.raises(ValueError, match="denoise_steps"):
            eng.submit([1, 2, 3], denoise_steps=2)
    finally:
        eng.stop()
    assert got == [[64] * 12,
                   [64, 60, 59, 46, 68, 92, 49, 39, 39, 39, 39, 39],
                   [32] * 12]


# --- the mask -------------------------------------------------------------

def _attention_case(seed, b=3, c=8, hq=4, hkv=2, d=16, ps=4, w=6):
    rng = np.random.RandomState(seed)
    pages = 1 + b * w
    q = jnp.asarray(rng.randn(b, c, hq, d), jnp.float32)
    k = jnp.asarray(rng.randn(pages, ps, hkv, d), jnp.float32)
    v = jnp.asarray(rng.randn(pages, ps, hkv, d), jnp.float32)
    tables = jnp.asarray(1 + np.arange(b * w).reshape(b, w), jnp.int32)
    return q, k, v, tables


@pytest.mark.parametrize("impl", ["reference", "kernel_interpret"])
def test_block_length_one_is_todays_causal_mask_bitwise(impl):
    q, k, v, tables = _attention_case(0)
    kv_lens = jnp.asarray([8, 13, 21], jnp.int32)
    q_lens = jnp.asarray([8, 5, 1], jnp.int32)
    fn = (pa.paged_attention_reference if impl == "reference" else
          lambda *a, **kw: pa._paged_attention_pallas(*a, interpret=True,
                                                      **kw))
    today = fn(q, k, v, tables, kv_lens, q_lens=q_lens)
    one = fn(q, k, v, tables, kv_lens, q_lens=q_lens, block_length=1)
    assert np.array_equal(np.asarray(today), np.asarray(one))
    # the causal form is the program it always was: the same jaxpr
    # whether the argument is given or not, and no `min` in it
    mk = lambda **kw: str(jax.make_jaxpr(
        lambda *a: pa.paged_attention_reference(*a, q_lens=q_lens, **kw))(
            q, k, v, tables, kv_lens))
    assert mk() == mk(block_length=1)
    assert " min " not in mk() and " min " in mk(block_length=4)


@pytest.mark.parametrize("impl", ["reference", "kernel_interpret"])
def test_block_mask_sees_its_whole_block_and_nothing_past_it(impl):
    """Against dense attention under M, by hand: lane at position i sees
    key j iff j < (i // 4 + 1) * 4 and j < kv_len."""
    q, k, v, tables = _attention_case(1)
    kv_lens = jnp.asarray([8, 12, 20], jnp.int32)   # whole blocks
    q_lens = jnp.asarray([8, 4, 0], jnp.int32)
    fn = (pa.paged_attention_reference if impl == "reference" else
          lambda *a, **kw: pa._paged_attention_pallas(*a, interpret=True,
                                                      **kw))
    got = np.asarray(fn(q, k, v, tables, kv_lens, q_lens=q_lens,
                        block_length=4))
    kd, vd = np.asarray(k)[np.asarray(tables)], np.asarray(v)[
        np.asarray(tables)]
    for s in range(3):
        keys = kd[s].reshape(-1, 2, 16).repeat(2, axis=1)
        vals = vd[s].reshape(-1, 2, 16).repeat(2, axis=1)
        for lane in range(8):
            if lane >= int(q_lens[s]):
                assert not got[s, lane].any()       # dead lanes: zeros
                continue
            pos = int(kv_lens[s]) - int(q_lens[s]) + lane
            n = min(int(kv_lens[s]), (pos // 4 + 1) * 4)
            sc = np.einsum("hd,khd->hk", np.asarray(q)[s, lane] / 4.0,
                           keys[:n])
            p = np.exp(sc - sc.max(-1, keepdims=True))
            want = np.einsum("hk,khd->hd", p / p.sum(-1, keepdims=True),
                             vals[:n])
            np.testing.assert_allclose(got[s, lane], want, atol=2e-5)


# --- the experts ----------------------------------------------------------

def _layer_params(spec, seed=0, skew_to=None):
    tree = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                        spec.seeded_arrays())
    lp = dict(tree["layer0"])
    if skew_to is not None:
        # a router that sends most tokens' first choice to ONE expert
        lp["router"] = lp["router"].at[:, skew_to].add(
            jnp.where(jnp.arange(spec.d_model) % 2 == 0, 0.6, -0.1))
    return lp


@pytest.mark.parametrize("impl", ["ragged_dot", "gmm_interpret"])
@pytest.mark.parametrize("skew", [None, 5])
def test_routing_is_dropless_and_equals_the_dense_all_experts_formula(
        ref, skew, impl):
    config, forced = experts_case(impl)
    spec = tiny_spec(config=config)
    lp = _layer_params(spec, skew_to=skew)
    rng = np.random.RandomState(7)
    t = 48
    h = jnp.asarray(np.abs(rng.randn(t, spec.d_model)) if skew is not None
                    else rng.randn(t, spec.d_model), jnp.float32)
    valid = jnp.asarray(np.arange(t) % 6 != 5)       # some dead lanes
    with forced:
        out, counts = jax.jit(lambda h, lp, v: moe_layer(h, lp, v, spec))(
            h, lp, valid)
    counts = np.asarray(counts)
    n_valid = int(np.asarray(valid).sum())
    # no capacity, no dropped token: every live token's k assignments land
    assert counts.sum() == n_valid * spec.experts_per_token
    w = np.asarray(ref.router_weights(h, lp["router"], 2, True))
    assert np.allclose(w.sum(-1), 1.0, atol=1e-6)
    assert ((w > 0).sum(-1) == 2).all()
    assert np.array_equal(counts, (w[np.asarray(valid)] > 0).sum(0))
    if skew is not None:
        # the skew holds: one expert takes at least half the live tokens
        assert counts[skew] >= n_valid // 2
    dense = np.asarray(ref.experts(h, jnp.asarray(w), lp["gate"], lp["up"],
                                   lp["down"]))
    got = np.asarray(out)
    np.testing.assert_allclose(got[np.asarray(valid)],
                               dense[np.asarray(valid)], atol=2e-5)
    assert not got[~np.asarray(valid)].any()         # dead lanes: zeros


# --- the step through the cache against the reference's full pass ----------

def _run_through_cache(spec, params, prompt, block, chunk, dtype,
                       attention_impl="reference"):
    """Prefill the prompt's whole blocks in chunks of ``chunk``, then one
    pass over ``block`` at the next position: the pass's logits [B, V]."""
    ps, pages = 4, 24
    pool = jnp.zeros((spec.n_layers, pages, ps, spec.n_kv_heads,
                      spec.head_dim), dtype)
    k, v = pool, pool
    tables = jnp.asarray(1 + np.arange(16)[None, :], jnp.int32)
    step = jax.jit(lambda p, t, pos, ql, k, v, kl: sdar_moe_step(
        p, spec, t, pos, ql, k, v, tables, kl,
        attention_impl=attention_impl), static_argnames=())
    whole = len(prompt) // 4 * 4
    done = 0
    logits = None
    for toks in ([prompt[i:min(i + chunk, whole)]
                  for i in range(0, whole, chunk)]
                 + [block]):
        n = len(toks)
        lane = np.zeros((1, chunk), np.int32)
        lane[0, :n] = toks
        pos = np.zeros((1, chunk), np.int32)
        pos[0, :n] = done + np.arange(n)
        k, v, logits, aux = step(params, jnp.asarray(lane), jnp.asarray(pos),
                                 jnp.asarray([n], jnp.int32), k, v,
                                 jnp.asarray([done + n], jnp.int32))
        done += n
    return np.asarray(logits[0]), np.asarray(aux["expert_counts"])


@pytest.mark.parametrize("impl", ["ragged_dot", "gmm_interpret"])
@pytest.mark.parametrize("p_mod_4", [0, 1, 3])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_chunks_then_a_block_pass_give_the_references_logits(
        ref, p_mod_4, dtype, impl):
    """Tolerances: in float32 the two passes differ by summation order
    only (2e-4 on logits of spread ~1); in the stated bfloat16 the
    activations between layers, the K/V pool and the products' operands
    round to 8 bits (relative 2^-9 each), which over 2 layers reads up to
    a few hundredths on a logit: 0.12, a tenth of the logits' spread, and
    the best token's probability within 12 %. ``gmm_interpret`` runs the
    step as a single-chip engine on a TPU traces it (``attention_impl``
    None): both Pallas kernels, in interpret mode."""
    config, forced = experts_case(impl)
    spec = tiny_spec(dtype=dtype, config=config)
    params = jax.device_put(spec.seeded_arrays())
    rng = np.random.RandomState(p_mod_4)
    prompt = [int(t) for t in rng.randint(0, 120, size=12 + p_mod_4)]
    left = prompt[12:]
    block = left + [MASK_ID] * (4 - len(left))
    with forced:
        got, counts = _run_through_cache(
            spec, params, prompt, block, 8, jnp.dtype(dtype),
            attention_impl="reference" if impl == "ragged_dot" else None)
    want = np.asarray(ref.logits_at(params, dict(config, assumed=ASSUMED),
                                    prompt[:12] + block, range(12, 16)))
    atol = 2e-4 if dtype == "float32" else 0.12
    np.testing.assert_allclose(got, want, atol=atol)
    assert counts.shape == (2, 8) and (counts.sum(-1) == 4 * 2).all()


def test_experts_route_by_backend_widths_and_the_callers_word():
    """The kernel where ``use_pallas_kernels`` is on and both widths are
    multiples of 128; ``ragged_dot`` off a TPU, at the tiny preset's widths
    and wherever the caller names the reference (the engine under a mesh).
    Each trace of a grouped product counts its route."""
    from paddle_tpu.observability import metrics

    kernel = metrics.counter("moe.route.gmm_kernel")
    ragged = metrics.counter("moe.route.ragged_dot")
    tiny, wide = tiny_spec(), tiny_spec(config=WIDE_CONFIG)
    assert moe_route(128, 128) == "ragged_dot"        # the CPU, flag auto
    assert FLAGS["use_pallas_kernels"] == "auto"
    with pytest.raises(ValueError, match="None or 'reference'"):
        moe_route(128, 128, "kernel")

    def trace(spec, impl):
        lp = _layer_params(spec)
        h = jnp.ones((8, spec.d_model), jnp.float32)
        before = kernel.value(), ragged.value()
        jax.jit(lambda h: moe_layer(h, lp, jnp.ones((8,), bool), spec,
                                    impl=impl)).lower(h)
        return kernel.value() - before[0], ragged.value() - before[1]

    assert trace(wide, None) == (0, 2)                # gate-and-up, down
    with pallas_forced():
        assert moe_route(wide.d_model, wide.expert_width) == "gmm_kernel"
        assert moe_route(wide.d_model, wide.expert_width,
                         "reference") == "ragged_dot"
        assert moe_route(tiny.d_model, tiny.expert_width) == "ragged_dot"
        assert trace(wide, None) == (2, 0)
        assert trace(wide, "reference") == (0, 2)
        assert trace(tiny, None) == (0, 2)
        eng = DecodeEngine(wide, name="wide", slots=[1], page_size=4,
                           num_pages=16, max_seq_len=16, warm=False)
        try:
            assert eng.stats()["experts_route"] == "gmm_kernel"
        finally:
            eng.stop()
    eng = DecodeEngine(tiny, name="tiny", slots=[1], page_size=4,
                       num_pages=16, max_seq_len=16, warm=False)
    try:
        assert eng.stats()["experts_route"] == "ragged_dot"
        assert eng.stats()["attention_route"] == ["paged_reference"]
    finally:
        eng.stop()


def test_draft_validation_names_the_block_length():
    block, dense = tiny_spec(), DecoderSpec(vocab=128)
    with pytest.raises(ValueError, match="block_length"):
        validate_draft_spec(block, dense)
    with pytest.raises(ValueError, match="block_length"):
        validate_draft_spec(dense, block)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_checkpoint_round_trip_asks_the_model_for_names_shapes_and_dtype(
        tmp_path, dtype):
    from paddle_tpu.checkpoint.decoder import (load_decoder_checkpoint,
                                               save_decoder_checkpoint)
    from paddle_tpu.checkpoint.format import CheckpointError

    spec = tiny_spec(dtype=dtype, seed=4)
    save_decoder_checkpoint(str(tmp_path / "ok"), spec)
    again, tree = load_decoder_checkpoint(str(tmp_path / "ok"))
    assert isinstance(again, SdarMoeSpec)
    assert again.to_dict() == spec.to_dict()
    assert str(tree["layer0"]["gate"].dtype) == dtype
    assert np.array_equal(np.asarray(tree["head"]),
                          spec.seeded_arrays()["head"])
    # a tree in another dtype than the model states is refused by name
    other = tiny_spec(dtype="float32" if dtype == "bfloat16" else "bfloat16",
                      seed=4)
    save_decoder_checkpoint(str(tmp_path / "bad"), spec,
                            params=other.seeded_arrays())
    with pytest.raises(CheckpointError, match="decoder contract serves"):
        load_decoder_checkpoint(str(tmp_path / "bad"))
