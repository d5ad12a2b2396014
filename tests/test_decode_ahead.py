"""The served round runs one step ahead of its answers
(``DecodeEngine._step``): step n+1 is planned and dispatched before step
n's ids reach the host, the tokens it feeds from step n coming from the
device (``feed_tokens``), and a round waits for the answer before it only
where that answer is the host's to give.

Each case here is held against what the engine gives the same requests
served one at a time, or served with every round waiting for the one
before it, on the small causal decoder and the small block model the
other decode tests use.
"""
import threading
import time

import jax
import numpy as np
import pytest

from paddle_tpu.observability import metrics
from paddle_tpu.serving import decode
from paddle_tpu.serving.decode import DecodeEngine, DecoderSpec
from paddle_tpu.serving.errors import EngineRetired, ServingError
from test_moe_decoder import tiny_spec


def _causal(**kw):
    spec = DecoderSpec(vocab=32, d_model=16, n_layers=2, n_heads=2,
                       n_kv_heads=1, seed=7)
    kw.setdefault("slots", [1, 2, 4])
    return DecodeEngine(spec, name=kw.pop("name", "ahead"), page_size=4,
                        num_pages=64, max_seq_len=32, prefill_chunk=4,
                        max_queue=32, **kw)


@pytest.fixture(scope="module")
def causal():
    eng = _causal()
    yield eng
    eng.stop(drain=False)


@pytest.fixture(scope="module")
def blocks():
    spec = tiny_spec(dtype="float32")
    eng = DecodeEngine(spec, name="ahead_blocks", slots=[4], page_size=4,
                       num_pages=128, max_seq_len=48, prefill_chunk=8,
                       params=jax.device_put(spec.seeded_arrays()))
    yield eng
    eng.stop(drain=False)


def _prompt(n, seed, vocab):
    return [int(t) for t in
            np.random.RandomState(seed).randint(0, vocab, size=n)]


def _cases(which, sampled):
    """Four requests of different prompt and answer lengths: greedy, or
    drawn at T=1 with a seed of their own; a block model's with their own
    denoise steps and every pass recorded."""
    draw = lambda i: (dict(temperature=1.0, seed=40 + i) if sampled
                      else {})
    if which == "causal":
        shapes = [(3, 6), (9, 4), (1, 9), (14, 5)]
        return [(_prompt(p, i, 32), dict(max_new_tokens=m, **draw(i)))
                for i, (p, m) in enumerate(shapes)]
    shapes = [(5, 8, 2), (9, 6, 1), (13, 12, 4), (3, 7, 2)]
    return [(_prompt(p, 10 + i, 120),
             dict(max_new_tokens=m, denoise_steps=d, topk_first=4,
                  **draw(i)))
            for i, (p, m, d) in enumerate(shapes)]


def _submit(eng, cases, reqs=None):
    """Submit every case under the engine's condition, so that one round
    admits them all (into ``reqs`` where given: a hook of the scheduler
    may read them before they finish)."""
    reqs = [] if reqs is None else reqs
    with eng._cond:
        reqs += [eng.submit(p, **kw) for p, kw in cases]
    return reqs


def _together(eng, cases, reqs=None):
    """``_submit``, then wait for each."""
    reqs = _submit(eng, cases, reqs)
    for r in reqs:
        assert r.ev.wait(120), "a request of the batch never finished"
    return reqs


@pytest.fixture
def ahead(monkeypatch):
    """Every observation of serving.decode.ahead_pct, in order."""
    seen, hist = [], decode._m_ahead_pct
    real = hist.observe
    monkeypatch.setattr(hist, "observe",
                        lambda v: (seen.append(v), real(v)))
    return seen


@pytest.fixture
def waiting(monkeypatch):
    """Make an engine's every round wait for the answer before it: the
    order a scheduler without the step ahead keeps."""
    def wait_always(eng):
        monkeypatch.setattr(eng, "_waits_first", lambda pend, live: True)
    return wait_always


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "seeded"])
@pytest.mark.parametrize("which", ["causal", "blocks"])
def test_a_batch_that_goes_ahead_answers_what_each_request_alone_does(
        which, sampled, request, ahead):
    eng = request.getfixturevalue(which)
    cases = _cases(which, sampled)
    alone = [eng.generate(p, **kw) for p, kw in cases]
    del ahead[:]
    reqs = _together(eng, cases)
    assert all(r.error is None for r in reqs)
    for r, want in zip(reqs, alone):
        assert r.result["tokens"] == want["tokens"]
        assert len(want["tokens"]) > 0
    # nothing in the batch is the host's to choose: every round but the
    # first goes ahead of the one before it
    assert ahead[0] == 0.0 and ahead[1:] and set(ahead[1:]) == {100.0}
    if which == "blocks":
        # pass for pass what the requests did alone: inputs, masks, choices
        for r, want in zip(reqs, alone):
            got = r.result["passes"]
            assert len(got) == len(want["passes"]) > 0
            for mine, theirs in zip(got, want["passes"]):
                for key in ("pos", "input", "masked", "ids", "unmasked"):
                    assert mine[key] == theirs[key], key
                np.testing.assert_allclose(mine["confidence"],
                                           theirs["confidence"], rtol=1e-5)
            assert r.result["first_topk"] == want["first_topk"]


@pytest.mark.parametrize("which", ["causal", "blocks"])
def test_a_request_ending_at_eos_mid_batch_emits_nothing_past_it(which,
                                                                 request):
    eng = request.getfixturevalue(which)
    cases = _cases(which, False)
    whole = [eng.generate(p, **kw)["tokens"] for p, kw in cases]
    # a token that first comes later than first in some request's answer
    # ends it there; any other request that chooses that token ends too
    i, eos = next((i, t) for i, w in enumerate(whole)
                  for j, t in enumerate(w) if j and t not in w[:j])
    want = [w[:w.index(eos) + 1] if eos in w else w for w in whole]
    assert 1 < len(want[i]) < len(whole[i])
    tokens = metrics.counter("serving.decode.tokens").value()
    try:
        eng.spec.eos_id = eos
        reqs = _together(eng, cases)
    finally:
        eng.spec.eos_id = None
    assert [r.result["tokens"] for r in reqs] == want
    assert [r.produced for r in reqs] == want
    # the lane that rode the round after its eos answered nothing
    assert metrics.counter("serving.decode.tokens").value() - tokens == \
        sum(len(w) for w in want)


def test_a_top_k_request_makes_its_rounds_wait_and_keeps_the_host_route(
        causal, ahead, waiting):
    """A top_k > 0 request's tokens are chosen on the host from its logits
    row, so a round after one that reads its token waits for that answer:
    fewer rounds go ahead, and its tokens (and everyone's) are what an
    engine whose every round waits gives."""
    cases = _cases("causal", False)
    cases[2] = (cases[2][0], dict(max_new_tokens=3, temperature=1.0,
                                  top_k=4, seed=11))
    host = metrics.counter("serving.decode.host_choices").value()
    reqs = _together(causal, cases)
    assert metrics.counter("serving.decode.host_choices").value() - host \
        == 3
    # the first round, and one after each of its reads; the rounds after
    # it ends go ahead
    assert ahead[:4] == [0.0] * 4
    assert 100.0 in ahead[4:]
    got = [r.result["tokens"] for r in reqs]
    other = _causal(name="every", slots=[4])
    try:
        waiting(other)
        del ahead[:]
        assert [r.result["tokens"] for r in _together(other, cases)] == got
        assert set(ahead) == {0.0}
    finally:
        other.stop()


@pytest.mark.parametrize("k", [1, 6, 32])
def test_a_first_topk_order_is_the_rows_own_sort_in_a_batch_that_goes_ahead(
        causal, ahead, waiting, k):
    """A first_topk request's token is the program's own choice, so its
    round goes ahead like any other; its order comes from its logits row
    of the step that chose it, fetched behind that step: the same order
    an engine whose every round waits takes from the same row, led by the
    greedy token."""
    cases = _cases("causal", False)
    cases[1] = (cases[1][0], dict(max_new_tokens=4, topk_first=k))
    reqs = _together(causal, cases)
    assert set(ahead[1:]) == {100.0}
    res = reqs[1].result
    assert len(res["first_topk"]) == k and res["first_topk"][0] == \
        res["tokens"][0]
    other = _causal(name="every_topk", slots=[4])
    try:
        waiting(other)
        assert _together(other, cases)[1].result["first_topk"] == \
            res["first_topk"]
    finally:
        other.stop()


def _at_collect(eng, n, act):
    """Run ``act()`` on the scheduler thread when it collects the ids of
    its ``n``-th round: a step queued behind that round is on the device."""
    real, count = eng._collect, [0]

    def collect(rnd, sp=None):
        count[0] += 1
        if count[0] == n:
            act()
        return real(rnd, sp)

    eng._collect = collect


def test_a_cancel_while_a_step_is_queued_drops_the_request_at_its_answer(
        causal):
    cases = _cases("causal", False)
    alone = [causal.generate(p, **kw)["tokens"] for p, kw in cases]
    reqs = []
    # the fourth round's ids are collected with the fifth on the device:
    # the longest request is cancelled between them
    _at_collect(causal, 4, lambda: causal.cancel(reqs[2], "gone"))
    try:
        _together(causal, cases, reqs)
    finally:
        del causal._collect
    assert isinstance(reqs[2].error, ServingError)
    assert "canceled: gone" in str(reqs[2].error)
    assert len(reqs[2].produced) < len(alone[2])
    assert reqs[2].produced == alone[2][:len(reqs[2].produced)]
    for i in (0, 1, 3):
        assert reqs[i].error is None and reqs[i].result["tokens"] == alone[i]
    assert causal.cache.allocator.stats()["pages_used"] == 0


def test_stop_without_drain_while_a_step_is_queued_fails_what_is_in_flight():
    eng = _causal(name="stopped", slots=[4])
    stopper = []

    def stop():
        # stop() joins the scheduler: another thread calls it, and the
        # scheduler goes on once the requests are failed
        stopper.append(threading.Thread(target=eng.stop,
                                        kwargs=dict(drain=False)))
        stopper[0].start()
        deadline = time.monotonic() + 30
        while not all(r.ev.is_set() for r in reqs) and \
                time.monotonic() < deadline:
            time.sleep(0.001)

    reqs = []
    _at_collect(eng, 12, stop)
    _together(eng, [(p, dict(max_new_tokens=16))
                    for p, _kw in _cases("causal", False)], reqs)
    stopper[0].join(60)
    assert not stopper[0].is_alive() and not eng._thread.is_alive()
    for r in reqs:
        assert isinstance(r.error, EngineRetired)
        assert len(r.produced) < 16
    assert any(r.produced for r in reqs)
    assert eng.stats()["live"] == 0


@pytest.mark.parametrize("which", ["causal", "blocks"])
def test_a_churn_that_goes_ahead_compiles_nothing_after_warm(which, request):
    eng = request.getfixturevalue(which)
    compiles = metrics.counter("serving.decode.compiles").value()
    rng = np.random.RandomState(7)
    vocab = 32 if which == "causal" else 120
    how = ([{}, dict(temperature=0.7, seed=1), dict(topk_first=3),
            dict(temperature=0.7, top_k=3, seed=1)] if which == "causal"
           else [dict(denoise_steps=2), dict(denoise_steps=4,
                                             temperature=0.7, seed=1),
                 dict(denoise_steps=1, topk_first=3)])
    churn = [eng.submit(_prompt(1 + int(rng.randint(12)), i, vocab),
                        max_new_tokens=1 + int(rng.randint(10)),
                        **how[i % len(how)]) for i in range(12)]
    for r in churn:
        assert r.ev.wait(120) and r.error is None
    assert metrics.counter("serving.decode.compiles").value() == compiles
