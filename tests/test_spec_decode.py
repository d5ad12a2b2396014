"""Speculative decoding: draft-propose, chunked-verify (ISSUE 14).

Coverage map:
  - OUTPUT PRESERVATION: greedy and seeded-sampled tokens are bitwise
    identical with speculation on vs off (the acceptance walk commits
    only the target's own per-(seed, position) choices — the ISSUE 14
    structural guarantee), including under concurrent batch
    composition;
  - fewer TARGET steps per generated token with a high-acceptance
    draft (counter-pinned — `serving.decode.target_steps`, the
    load-independent form per memory/tier1-timing-margin);
  - rejected-suffix ROLLBACK exactness: pages grown for a verify chunk
    that ended up holding only rejected tokens return to the pool
    (`PageAllocator.shrink`, `serving.kv.shrunk_pages`) and the pool is
    exact at the end — every page back;
  - churn with a draft attached performs ZERO post-warm compiles (the
    chunk ladder's spec_k+1 verify entry and the draft's own ladder are
    both pre-compiled by warm());
  - hot-swap/drain with a draft attached (registry semantics
    unchanged), preempt/spill/restore through the MIRRORED draft pool
    (one spill covers both pools, tokens bitwise vs unpreempted);
  - chaos: a generate reply killed mid-frame retransmits dedup-exact —
    zero extra target/verify steps;
  - draft/target cross-validation refused typed AT LOAD naming the
    field (vocab/eos), locally and over the load_decoder RPC; shared
    allocator geometry likewise;
  - `spec_k` resolves through the autotune cache (effective_flag) like
    every PR 8 knob;
  - the fused jitted page-move helpers (ISSUE 14 satellite): COW copy
    / spill gather / restore scatter round-trip bitwise and compile
    once per shape (`serving.kv.pagemove_compiles`).

All timing-sensitive claims are COUNTER asserts. The whole file must
stay green under PADDLE_TPU_SANITIZE=guards.
"""
import numpy as np
import pytest

from paddle_tpu.observability import metrics
from paddle_tpu.serving import (DecodeEngine, DecoderSpec, ModelRegistry,
                                ServingClient, ServingError,
                                ServingServer, validate_draft_spec)
from paddle_tpu.serving.kv_cache import PageAllocator, PagedKvCache


def _spec(**kw):
    kw.setdefault("vocab", 32)
    kw.setdefault("d_model", 16)
    kw.setdefault("n_layers", 2)
    kw.setdefault("n_heads", 2)
    kw.setdefault("n_kv_heads", 1)
    kw.setdefault("seed", 7)
    return DecoderSpec(**kw)


def _draft_small(**kw):
    """A genuinely smaller draft (the production shape): agrees with
    the target sometimes, not always — exercises the rejection path."""
    kw.setdefault("vocab", 32)
    kw.setdefault("d_model", 8)
    kw.setdefault("n_layers", 1)
    kw.setdefault("n_heads", 1)
    kw.setdefault("n_kv_heads", 1)
    kw.setdefault("seed", 3)
    return DecoderSpec(**kw)


def _engine(**kw):
    kw.setdefault("slots", [1])
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 16)
    kw.setdefault("max_seq_len", 20)
    kw.setdefault("max_queue", 8)
    kw.setdefault("prefill_chunk", 4)
    return DecodeEngine(_spec(), name=kw.pop("name", "sd"), **kw)


def _ctr(name):
    return metrics.counter(name).value()


# --- output preservation + the target-step trade -------------------------

def test_greedy_equiv_and_fewer_target_steps_high_acceptance():
    """With a draft that always agrees (same spec -> bitwise the same
    model), every proposal is accepted: tokens are identical to the
    non-speculative engine's and the TARGET-step counter shows the
    trade — one verify step commits up to k+1 tokens."""
    prompts = [[4, 9, 1], [7, 2], [1, 2, 3, 4, 5, 6]]
    off = _engine(name="sd_off")
    try:
        base = _ctr("serving.decode.target_steps")
        ref = [off.generate(p, max_new_tokens=12)["tokens"]
               for p in prompts]
        off_steps = _ctr("serving.decode.target_steps") - base
    finally:
        off.stop()
    on = _engine(name="sd_on", draft_spec=_spec(), spec_k=3)
    try:
        assert on.spec_k == 3
        base = _ctr("serving.decode.target_steps")
        out = [on.generate(p, max_new_tokens=12)["tokens"]
               for p in prompts]
        on_steps = _ctr("serving.decode.target_steps") - base
    finally:
        on.stop()
    assert out == ref, "speculation changed greedy output"
    # identical models -> the acceptance walk never breaks early
    assert _ctr("serving.decode.spec.proposed") > 0
    assert _ctr("serving.decode.spec.rejected") == 0
    assert _ctr("serving.decode.spec.accepted") == \
        _ctr("serving.decode.spec.proposed")
    # the headline: strictly fewer target-model steps, same tokens
    assert on_steps < off_steps, (on_steps, off_steps)


def test_disagreeing_draft_still_bitwise_and_counters_balance():
    """A small (fast, imperfect) draft: rejections happen, output does
    NOT change, and proposed == accepted + rejected exactly. The
    per-request result dict carries the accept_rate."""
    prompts = [[4, 9, 1], [11, 30, 2, 5]]
    off = _engine(name="sdd_off")
    try:
        ref = [off.generate(p, max_new_tokens=10)["tokens"]
               for p in prompts]
    finally:
        off.stop()
    on = _engine(name="sdd_on", draft_spec=_draft_small(), spec_k=3)
    try:
        outs = [on.generate(p, max_new_tokens=10) for p in prompts]
    finally:
        on.stop()
    assert [o["tokens"] for o in outs] == ref
    prop = _ctr("serving.decode.spec.proposed")
    acc = _ctr("serving.decode.spec.accepted")
    rej = _ctr("serving.decode.spec.rejected")
    assert prop > 0 and prop == acc + rej
    for o in outs:
        assert o["spec_proposed"] + o["spec_accepted"] >= 0
        if o["spec_proposed"]:
            assert o["accept_rate"] == round(
                o["spec_accepted"] / o["spec_proposed"], 4)
    assert sum(o["spec_proposed"] for o in outs) == prop
    assert sum(o["spec_accepted"] for o in outs) == acc
    # the accept_rate histogram saw every speculative request
    hist = metrics.snapshot().get("serving.decode.spec.accept_rate", {})
    assert hist.get("count", 0) == sum(
        1 for o in outs if o["spec_proposed"])


@pytest.mark.parametrize("top_k", [6, 0])
def test_seeded_sampling_identical_spec_on_vs_off(top_k):
    """Seeded sampling draws from an rng keyed ONLY by (seed,
    position); the verify walk re-derives the same draw per position,
    so rejection/acceptance cannot perturb the realization — same-seed
    equality with speculation on vs off, the ISSUE 14 tier-1 pin. Both
    routes (ISSUE 29): ``top_k`` > 0 is drawn on the host from fetched
    rows, the full vocabulary by ``choose_tokens`` inside the plain
    step, the draft and the verify programs — one definition each,
    shared by all three."""
    off = _engine(name="sds_off")
    on = _engine(name="sds_on", draft_spec=_draft_small(), spec_k=3)
    route = "host_choices" if top_k else "device_choices"
    try:
        for seed in (11, 303):
            base = _ctr("serving.decode." + route)
            a = off.generate([7, 2, 19], max_new_tokens=10,
                             temperature=0.9, top_k=top_k, seed=seed)
            b = on.generate([7, 2, 19], max_new_tokens=10,
                            temperature=0.9, top_k=top_k, seed=seed)
            assert a["tokens"] == b["tokens"], f"seed {seed} diverged"
            # every token of both engines took the request's one route
            assert _ctr("serving.decode." + route) - base == 20
        greedy = off.generate([7, 2, 19], max_new_tokens=10)
        assert a["tokens"] != greedy["tokens"]      # it did sample
    finally:
        off.stop()
        on.stop()


@pytest.mark.parametrize("top_k", [5, 0])
def test_spec_tokens_batch_composition_independent(top_k):
    """Speculative rounds batched with OTHER live slots commit the same
    tokens as running alone — slot assignment and co-resident
    sequences never leak into the acceptance walk."""
    on = _engine(name="sdb", slots=[2], num_pages=24,
                 draft_spec=_draft_small(), spec_k=2)
    try:
        r1 = on.submit([4, 9, 1], max_new_tokens=8, temperature=0.7,
                       top_k=top_k, seed=21)
        r2 = on.submit([8, 8, 3], max_new_tokens=8, temperature=0.7,
                       top_k=top_k, seed=22)
        assert r1.ev.wait(120) and r2.ev.wait(120)
        assert r1.error is None and r2.error is None
        solo1 = on.generate([4, 9, 1], max_new_tokens=8,
                            temperature=0.7, top_k=top_k, seed=21)
        solo2 = on.generate([8, 8, 3], max_new_tokens=8,
                            temperature=0.7, top_k=top_k, seed=22)
    finally:
        on.stop()
    assert r1.result["tokens"] == solo1["tokens"]
    assert r2.result["tokens"] == solo2["tokens"]


# --- rollback exactness + compiled shapes --------------------------------

def test_rejected_suffix_rolls_back_pages_exactly():
    """Demand-mode reservations grow to cover the whole verify write
    range (pos..pos+k); a rejection rolls the unused tail back —
    `serving.kv.shrunk_pages` moves and the pool is EXACT at the end
    (every page returned, reserved tokens un-noted)."""
    on = _engine(name="sdr", page_size=2, num_pages=24, max_seq_len=24,
                 reservation="demand", draft_spec=_draft_small(),
                 spec_k=4)
    try:
        out = on.generate([4, 9, 1], max_new_tokens=16)
        assert len(out["tokens"]) == 16
        st = on.cache.allocator.stats()
        assert st["pages_used"] == 0, st
        assert on.stats()["live"] == 0
    finally:
        on.stop()
    # page_size 2 with spec_k 4: a verify chunk spans pages, so some
    # round's rejection leaves a page holding only rejected tokens
    assert _ctr("serving.decode.spec.rejected") > 0
    assert _ctr("serving.kv.shrunk_pages") > 0


def test_spec_churn_zero_post_warm_compiles():
    """warm() pre-compiles the verify entry (spec_k+1 lanes) and the
    draft's own {1, 2, chunk} ladder alongside the target's — ragged
    speculative churn compiles NOTHING new."""
    on = _engine(name="sdc", slots=[1, 2], num_pages=32,
                 draft_spec=_draft_small(), spec_k=3)
    try:
        warm = _ctr("serving.decode.compiles")
        # the step shapes, and the host route's row fetch: one program a
        # slot count for the newest-lane logits (the target's and the
        # draft's alike) and one for the verify's lanes; and the device
        # feed of a target call from the one before: slots x chunks x
        # the slots before
        n = len(on.slot_ladder)
        assert warm == (len(on.stats()["compiled_shapes"]) + 2 * n
                        + n * len(on.chunk_ladder) * n)
        rng = np.random.RandomState(5)
        # greedy, drawn by the programs, drawn on the host: all warmed
        how = [{}, dict(temperature=0.8, seed=9),
               dict(temperature=0.8, top_k=4, seed=9)]
        reqs = [on.submit(rng.randint(0, 32, size=1 + int(rng.randint(6))),
                          max_new_tokens=1 + int(rng.randint(8)),
                          **how[i % 3])
                for i in range(6)]
        for r in reqs:
            assert r.ev.wait(120) and r.error is None
        assert _ctr("serving.decode.compiles") == warm, \
            "speculative churn minted a new compiled shape"
        assert on.cache.allocator.stats()["pages_used"] == 0
    finally:
        on.stop()


def test_spec_fault_site_fails_requests_typed():
    """`serving.decode.spec` is a named chaos seam: an injected error
    in the propose/verify round fails that round's requests typed and
    (donation off) the engine keeps serving."""
    from paddle_tpu.distributed import faults

    on = _engine(name="sdf", draft_spec=_draft_small(), spec_k=2)
    try:
        with faults.scoped("error@serving.decode.spec:0") as plan:
            req = on.submit([4, 9], max_new_tokens=6)
            assert req.ev.wait(120)
            assert isinstance(req.error, ServingError)
        assert [(k, s) for k, s, _i in plan.injected()] == \
            [("error", "serving.decode.spec")]
        # the engine survived: next request completes normally
        out = on.generate([4, 9], max_new_tokens=6)
        assert len(out["tokens"]) == 6
        assert on.cache.allocator.stats()["pages_used"] == 0
    finally:
        on.stop()


# --- registry / preemption / RPC lifecycle -------------------------------

def test_hot_swap_and_drain_with_draft_attached():
    """Registry semantics are unchanged by a draft: an in-flight
    speculative sequence finishes on the OLD engine, the flip installs
    the new one, retirement releases BOTH pools."""
    reg = ModelRegistry()
    reg.deploy("sg", lambda: _engine(name="sg", version=1,
                                     draft_spec=_draft_small(),
                                     spec_k=2))
    req = reg.get("sg").submit([1, 5], max_new_tokens=7)
    reg.deploy("sg", lambda: _engine(name="sg", version=2,
                                     draft_spec=_draft_small(),
                                     spec_k=2))
    assert req.ev.wait(120), "in-flight sequence dropped by hot-swap"
    assert req.error is None
    assert req.result["version"] == 1 and len(req.result["tokens"]) == 7
    out = reg.get("sg").generate([1, 5], max_new_tokens=7)
    assert out["version"] == 2
    assert out["tokens"] == req.result["tokens"]  # same spec, same model
    reg.unload_all()
    assert metrics.gauge("serving.decode.live_slots.sg.v2").value() == 0


def test_preempt_restore_with_draft_spills_both_pools_bitwise():
    """Preemption spills the target AND mirrored draft pages in one
    put (same page ids); restore scatters both back — tokens bitwise
    equal an unpreempted reference, every page returned."""
    prompt_len, max_new = 4, 16
    wl = [np.asarray([1 + i] * prompt_len, np.int32) for i in range(4)]
    maxseq = prompt_len + max_new
    worst = -(-maxseq // 4)
    ref_eng = _engine(name="sdp_ref", num_pages=1 + 4 * worst,
                      max_seq_len=maxseq, reservation="worst_case",
                      draft_spec=_draft_small(), spec_k=2, slots=[2])
    try:
        ref = [ref_eng.generate(p, max_new_tokens=max_new)["tokens"]
               for p in wl]
    finally:
        ref_eng.stop()
    # 8 usable pages: all four requests admit (prompt + headroom = 2
    # pages each) but two live slots growing toward `worst` (5) pages
    # MUST collide mid-decode — preemption, not luck, finishes this
    # workload
    eng = _engine(name="sdp", num_pages=1 + 8, max_seq_len=maxseq,
                  reservation="demand", draft_spec=_draft_small(),
                  spec_k=2, slots=[2])
    try:
        reqs = [eng.submit(p, max_new_tokens=max_new) for p in wl]
        for r, want in zip(reqs, ref):
            assert r.ev.wait(300), "preempting speculative decode wedged"
            assert r.error is None, r.error
            assert r.result["tokens"] == want, \
                "preemption corrupted a speculative sequence"
        assert eng.cache.allocator.stats()["pages_used"] == 0
    finally:
        eng.stop()
    assert _ctr("serving.kv.preemptions") > 0
    assert _ctr("serving.kv.restores") == _ctr("serving.kv.preemptions")


@pytest.fixture
def spec_server():
    srv = ServingServer()
    addr = srv.serve()
    cli = ServingClient(addr)
    cli.load_decoder("sgen", _spec().to_dict(), slots=[1], page_size=4,
                     num_pages=12, max_seq_len=12, prefill_chunk=4,
                     draft_spec=_spec().to_dict(), spec_k=2)
    yield srv, cli
    cli.close()
    srv.shutdown()


def test_load_decoder_rpc_with_draft(spec_server):
    _srv, cli = spec_server
    listed = cli.list_models()
    assert listed["sgen"]["kind"] == "decoder"
    out = cli.generate("sgen", [3, 1, 4], max_new_tokens=6)
    assert len(out["tokens"]) == 6
    assert out["spec_proposed"] > 0 and out["accept_rate"] == 1.0
    # a vocab-mismatched draft is refused typed AT LOAD, field named
    with pytest.raises(ValueError, match="field 'vocab'"):
        cli.load_decoder("sbad", _spec().to_dict(), slots=[1],
                         page_size=4, num_pages=12, max_seq_len=12,
                         draft_spec=_spec(vocab=64).to_dict(), spec_k=2)


@pytest.mark.chaos
def test_spec_retransmit_answered_with_zero_extra_verify_steps(
        spec_server):
    """Kill the generate REPLY mid-frame: the retransmit is answered
    from the dedup cache — the target-step counter (prefill + verify
    calls) moves EXACTLY as much as an unfaulted run of the same
    request, i.e. the sequence decoded once."""
    from paddle_tpu.distributed import faults

    _srv, cli = spec_server
    metrics.reset_metrics()
    base = _ctr("serving.decode.target_steps")
    with faults.scoped("drop@recv.generate:0") as plan:
        out = cli.generate("sgen", [2, 7], max_new_tokens=6)
    faulted_steps = _ctr("serving.decode.target_steps") - base
    assert [(k, s) for k, s, _i in plan.injected()] == \
        [("drop", "recv.generate")]
    assert len(out["tokens"]) == 6
    assert metrics.counter("rpc.client.retries").value() == 1
    assert metrics.counter("rpc.server.dedup_hits").value() == 1
    assert metrics.counter("serving.decode.completions").value() == 1
    # the same request, no fault: its step cost == the faulted run's
    base = _ctr("serving.decode.target_steps")
    out2 = cli.generate("sgen", [2, 7], max_new_tokens=6)
    clean_steps = _ctr("serving.decode.target_steps") - base
    assert out2["tokens"] == out["tokens"]
    assert faulted_steps == clean_steps, \
        "retransmit re-ran target/verify steps"


# --- typed refusals + knob resolution ------------------------------------

def test_draft_cross_validation_typed_refusals():
    with pytest.raises(ValueError, match="field 'vocab'"):
        validate_draft_spec(_spec(), _spec(vocab=64))
    with pytest.raises(ValueError, match="field 'eos_id'"):
        validate_draft_spec(_spec(), _spec(eos_id=3))
    with pytest.raises(ValueError, match="draft"):
        _engine(name="sdk", spec_k=2)           # k > 0 needs a draft
    with pytest.raises(ValueError, match="spec_k"):
        _engine(name="sdn", draft_spec=_draft_small(), spec_k=-1)


def test_mirrored_pool_geometry_refused_typed():
    """A draft pool must mirror the target's page geometry exactly —
    a mismatched shared allocator is refused at construction."""
    alloc = PageAllocator(num_pages=8, page_size=4)
    with pytest.raises(ValueError, match="geometry"):
        PagedKvCache(1, 1, 8, page_size=8, num_pages=8,
                     allocator=alloc)
    with pytest.raises(ValueError, match="geometry"):
        PagedKvCache(1, 1, 8, page_size=4, num_pages=16,
                     allocator=alloc)
    # matching geometry shares the allocator (page ids mirror)
    pool = PagedKvCache(1, 1, 8, page_size=4, num_pages=8,
                        allocator=alloc)
    assert pool.allocator is alloc
    pool.release()


def test_spec_k_resolves_through_autotune_cache():
    """spec_k is a PR 8 tunable: explicit arg > autotune cache (per
    device kind) > FLAGS cold default (0 = off — the draft is dropped
    entirely and behavior is bit-identical non-speculative)."""
    from paddle_tpu import autotune

    with autotune.scoped(enable=True) as cache:
        cache.put("spec_k", 2, source="measured")
        eng = _engine(name="sda", draft_spec=_draft_small())
        try:
            assert eng.spec_k == 2          # cache won over FLAGS' 0
            assert eng.draft_spec is not None
        finally:
            eng.stop()
    # cold default 0: the draft is dropped, engine is plain
    eng = _engine(name="sda0", draft_spec=_draft_small())
    try:
        assert eng.spec_k == 0 and eng.draft_spec is None
        assert eng.stats()["spec_k"] == 0 and eng.stats()["draft"] is None
    finally:
        eng.stop()
    # a flag/cache-sourced nonzero spec_k must NOT refuse draftless
    # deploys (a persisted TPU winner would break every plain
    # load_decoder fleet-wide): it clamps to 0; only an EXPLICIT
    # spec_k without a draft is a caller error (tested above)
    with autotune.scoped(enable=True) as cache:
        cache.put("spec_k", 3, source="measured")
        eng = _engine(name="sdap")
        try:
            assert eng.spec_k == 0
            out = eng.generate([4, 9], max_new_tokens=4)
            assert len(out["tokens"]) == 4
        finally:
            eng.stop()


def test_decoder_artifact_carries_the_speculative_trio():
    """A fleet intent deploys a drafted decoder exactly like a plain
    one: the trio rides decoder_artifact's engine kwargs verbatim."""
    from paddle_tpu.fleet.rollout import decoder_artifact

    art = decoder_artifact(spec=_spec().to_dict(), slots=[1],
                           draft_spec=_draft_small().to_dict(),
                           spec_k=2)
    assert art["action"] == "load_decoder"
    assert art["payload"]["draft_spec"] == _draft_small().to_dict()
    assert art["payload"]["spec_k"] == 2


# --- fused page-move helpers (ISSUE 14 satellite) ------------------------

def test_page_moves_roundtrip_bitwise_and_compile_once():
    """COW copy / spill gather / restore scatter are jitted batched
    ops: content round-trips bitwise and repeat moves at the SAME
    (pool shape, page count) re-use the executable —
    `serving.kv.pagemove_compiles` counts traces, not calls."""
    pool = PagedKvCache(2, 1, 8, page_size=4, num_pages=10)
    rng = np.random.RandomState(9)
    payload = rng.randn(2, 3, 4, 1, 8).astype(np.float32)
    compiles = metrics.counter("serving.kv.pagemove_compiles")

    pool.scatter_pages([1, 2, 3], payload, -payload)
    c_after_first = compiles.value()
    got_k, got_v = pool.gather_pages([1, 2, 3])
    np.testing.assert_array_equal(got_k, payload)
    np.testing.assert_array_equal(got_v, -payload)

    # COW copy: dst pages equal src pages bitwise afterwards
    pool.copy_pages([(1, 7), (3, 8)])
    ck, cv = pool.gather_pages([7, 8])
    np.testing.assert_array_equal(ck, payload[:, [0, 2]])
    np.testing.assert_array_equal(cv, -payload[:, [0, 2]])

    # repeat every move at the same shapes: zero new traces
    c0 = compiles.value()
    pool.scatter_pages([4, 5, 6], payload, -payload)
    pool.gather_pages([4, 5, 6])
    pool.copy_pages([(4, 1), (5, 2)])
    assert compiles.value() == c0, \
        "a repeat page move at a known shape re-traced"
    assert c_after_first <= c0
    pool.release()


def test_spill_store_roundtrips_draft_arrays(tmp_path):
    """HostSpillStore carries (k, v) or (k, v, draft_k, draft_v) — the
    mirrored-pool spill — through RAM and disk identically."""
    from paddle_tpu.serving.kv_cache import HostSpillStore

    rng = np.random.RandomState(2)
    arrays = tuple(rng.randn(1, 2, 4, 1, 8).astype(np.float32)
                   for _ in range(4))
    for directory in ("", str(tmp_path)):
        store = HostSpillStore(directory, label="t")
        store.put(5, *arrays)
        got = store.pop(5)
        assert len(got) == 4
        for a, b in zip(arrays, got):
            np.testing.assert_array_equal(a, b)
        assert store.pop(5) is None
        # the two-array (plain decoder) form is unchanged
        store.put(6, arrays[0], arrays[1])
        got = store.pop(6)
        assert len(got) == 2
        np.testing.assert_array_equal(got[0], arrays[0])
