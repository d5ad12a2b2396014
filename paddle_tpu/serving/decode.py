"""Autoregressive decode serving: continuous batching over a paged KV
cache (ISSUE 6; PAPERS.md: Ragged Paged Attention).

The one-shot engine (engine.py) answers each request with one model
run. Autoregressive decode is different in kind: a request is a
SEQUENCE of dependent steps (one per generated token), each step needs
the sequence's whole KV history on-device, and sequences finish at
ragged, data-dependent times. Two naive designs fail on TPU:

  - drain-per-batch (admit a batch, run every member to completion,
    then admit the next): short sequences finish early and their slots
    idle until the longest member drains — realized tokens/s decays
    with length variance (decode_bench measures exactly this);
  - per-sequence shapes: recompiling per ragged length mints O(shapes)
    jit entries under the traffic that can least afford compiles.

This engine does CONTINUOUS batching over FIXED compiled shapes:

  - the decode batch has a fixed slot layout — slot count padded to a
    small ladder (``FLAGS['decode_slots']``), per-slot page-table width
    padded to a derived ladder — and ``warm()`` pre-compiles every
    (slots, width) pair at load time, exactly like the one-shot
    engine's bucket warm. After warmup a churn of admits/completions
    at ragged lengths performs ZERO new compiles (tier-1 pins the
    ``serving.decode.compiles`` counter);
  - every step consumes up to ``prefill_chunk`` PROMPT tokens plus one
    generated token per decoding slot (ISSUE 10, chunked prefill):
    sequences still in their prompt are granted chunks of it — causal
    within the chunk, all slots sharing a per-step token BUDGET of
    ``prefill_budget`` prompt tokens — while sequences past their
    prompt consume their previously sampled token, all in the SAME
    compiled mixed batch (Sarathi-style). A P-token prompt completes
    prefill in ``ceil(P / prefill_chunk)`` steps instead of P, so
    time-to-first-token stops being linear in prompt length, and
    in-flight decodes never stall behind a long prompt. New sequences
    are admitted into free slots BETWEEN steps, mid-flight of everyone
    else — admission never waits for a batch boundary;
  - K/V live in the preallocated paged pool (kv_cache.py): HBM is
    bounded at construction, pages are reserved at admission (refusal
    is an immediate structured ``ServerOverloaded``) and recycled at
    completion, and the paged-attention kernel reads through the page
    tables so ragged histories share one compiled shape.

SPECULATIVE DECODING (ISSUE 14): with a small DRAFT decoder attached
(``draft_spec``/``draft_params`` + ``spec_k > 0``), every decoding slot
advances up to ``spec_k + 1`` tokens per scheduler round for ONE
target-model step: the draft proposes ``spec_k`` tokens (cheap batched
steps on its own compiled ladder), the target verifies all ``k+1``
positions in one ``decoder_step_chunked(all_lanes=True)`` call, and the
committed tokens are the target's own deterministic per-(seed,
position) choices along the longest agreeing prefix — so output is
BITWISE what the non-speculative engine emits, for greedy and seeded
sampling alike (the classic draft/verify trade from *Fast Inference
from Transformers via Speculative Decoding*, with the realization
pinned by the seeded sampler instead of stochastic rejection). The
draft's KV pool MIRRORS the target's page geometry — same allocator,
same page ids, same tables — so reservation growth, rejected-suffix
rollback (``PageAllocator.shrink``), COW, preemption spill and restore
stay one mechanism; a rejected suffix un-notes its tokens and frees
any page that held only rejected positions. ``spec_k`` is a PR 8
tunable (``effective_flag('spec_k')``, 0 = off and bit-identical old
behavior).

The model behind the step is pluggable via the ``DecoderSpec`` /
``build_decoder_params`` / ``decoder_step`` contract below; the
built-in spec'd decoder (embedding + N pre-norm transformer layers
with paged attention + tied-embedding logits, deterministic params
from a seed) is the test/bench/selftest vehicle — real checkpoints
implement the same step signature.

ONE WAY TO BUILD, RUN AND ANSWER A CALL (ISSUE 32). What one call to
the device looks like is decided in one place each:

  - the PROGRAM TABLE (``DecodeEngine._programs``): ``__init__`` jits
    each body through one helper (donation and output shardings applied
    once) into a ``_Program`` record under its tag — ``target`` (the
    plain ``_step``, or a block model's ``_block_step``), and where the
    engine has them ``verify``, ``draft`` and ``embed``. The record says
    whose params and pool a call reads, which counter it bumps and
    which further arrays it takes;
  - the RUN FUNCTION (``_run``): under ``_step_mu`` it counts a
    distinct compiled shape, bumps the program's counter, calls, and
    rebinds that program's pool. ``_run_step_arrays`` is the target
    program's entry point into it, reached by attribute at every call
    (the benchmark wraps it on the instance and plants faults on the
    class);
  - the ARRAY BUILDER (``_build_arrays``): every call is "slot i feeds
    these tokens from this position" — a prefill chunk, a decode token,
    a block pass, an embed chunk, the draft's catch-up and singles, the
    verify chunk — and one function pads the feeds to the compiled
    buckets and refuses a feed past its slot's reservation;
  - the ROUND (``_step``): prepare -> PLAN (per slot: which pass, what
    it feeds, whether anybody reads its choice) -> build -> device call
    -> ANSWER (per slot, by the pass it ran) -> retire -> notify, ONE
    STEP AHEAD: round n+1 is planned and dispatched before
    round n's ids reach the host, and round n is answered after it.
    What the plan knows without the answer it counts (positions,
    grants, who reaches ``max_new``, a block's pass); the token a
    decoding slot feeds and a block's lanes the device feeds from round
    n (``feed_tokens``). A round waits for the answer before it where
    that answer is the host's to give: a token the host chooses, a
    draft's propose/verify, a preemption (``_waits_first``,
    ``_reclaim_for_growth``). What a causal slot and a block slot differ
    in is the plan and the answer (``_plan_causal`` / ``_plan_blocks``;
    ``_answer_plain`` / ``_answer_spec`` / ``_answer_block``), chosen by
    the model's block length and the slot's state; buckets, spans,
    dispatch, waiting, timing, retirement and the wake-up are written
    once. The embed lane keeps its own slot list and scoring and shares
    ``_build_arrays``, the run function and the retire/notify tail.

LAYER KINDS (ISSUE 34). The engine asks the model what each layer keeps
of a sequence (``layer_kinds``: ``"full"`` or ``"window"``, and the
``window``). A model whose layers are all full — the dense decoder, the
block model — takes exactly the path, the shapes and the page arithmetic
above. One with window layers (``models/afmoe.py``) is served from a PAIR
of caches, ``cache`` (the full kind's) and ``window_cache``, each over its
own allocator: reservation, admission, demand growth, preemption and spill
count pages by kind (``_alloc_kinds``, ``_prepare``, ``_preempt``); each
round the window kind gives back the pages that fell behind the window of
the step's oldest lane and grows to its last write, so a sequence never
holds more than ``ceil((window + chunk - 1) / page_size) + 1`` of its
pages; and the one builder hands the one step both tables, the window
kind's as wide as that and starting at the window's first page
(``KindTables``). What assumes that a sequence's pages stay its own to the
end (the prefix cache, a draft's mirrored pool, the embed lane) and a mesh
refuse such a model by the field's name.

A model with STATE layers (``"state"``: a linear-attention layer's fixed-size
recurrent state and convolution tail, ``models/kimi_linear.py``) keeps them
in a ``SlotStateCache`` beside the full kind's pool: a row of its arrays a
live sequence, taken at admission (from a slot that ends in the round not
yet answered where none is free), given back with the pages, donated into
each step and returned like a pool, never paged. The step starts a row from
zero where ``_build_arrays`` marks its sequence as starting in the call
(``StateTables.fresh``); a preemption spills the row beside the pages and
the restore writes it back bitwise. Such a model's
full kind may keep one array a token and no V (``pool_values`` False: a
latent cache). The prefix cache, speculation, the embed lane and a mesh are
refused for it by name, as for window layers.

Lifecycle mirrors the one-shot engine so the SAME ModelRegistry
hot-swaps decoders: ``stop(drain=True)`` finishes every admitted
sequence then drops params/pools/compiled steps (executables release
on retirement); a failed ``warm()`` stops the scheduler before
re-raising so the registry's rollback leaks nothing.
"""
from __future__ import annotations

import collections
import contextlib
import math
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from ..autotune.ladder import observe as _observe_shape
from ..distributed import faults as _faults
from ..observability import metrics as _metrics, tracing as _tracing
from ..observability.log import get_logger
from .engine import bucket_for as _bucket_for, resolve_bucket_spec
from .errors import (DeadlineExceeded, EngineRetired, RequestTooLarge,
                     ServerOverloaded, ServingError)
from .kv_cache import (GARBAGE_PAGE, HostSpillStore, PagedKvCache,
                       SlotStateCache)

__all__ = ["DecoderSpec", "DecodeEngine", "build_decoder_params",
           "seeded_decoder_arrays", "decoder_step",
           "decoder_step_chunked", "width_ladder", "sample_token",
           "choose_tokens", "choose_block", "feed_tokens",
           "validate_draft_spec"]

_log = get_logger("serving")

_m_requests = _metrics.counter("serving.decode.requests")
_m_admitted = _metrics.counter("serving.decode.admitted")
_m_completions = _metrics.counter("serving.decode.completions")
_m_steps = _metrics.counter("serving.decode.steps")
_m_tokens = _metrics.counter("serving.decode.tokens")
_m_overloads = _metrics.counter("serving.decode.overloads")
_m_deadline_miss = _metrics.counter("serving.decode.deadline_misses")
_m_cancels = _metrics.counter("serving.decode.cancels")
# one inc per DISTINCT (slots, width) shape the step compiles, and per
# shape of the host route's row fetch and of the device feed — after
# warm() this must never move again (the tier-1 churn guard pins it)
_m_compiles = _metrics.counter("serving.decode.compiles")
_m_step_ms = _metrics.histogram("serving.decode.step_ms")
# the rest of a scheduler round on the host clock (ISSUE 27), one
# observation a step each: sample_ms is the host-side choice of tokens
# (the serving.decode.sample spans, summed over the step's slots; 0.0
# where every token was chosen by the step's program, ISSUE 29);
# sched_ms is whatever of the round is neither step_ms's stretch, nor
# sampling, nor the wait for work — admit + prepare + the answer phase
# without sampling, and the hand-over of the interpreter lock to the
# clients a step wakes. A round runs from the last one's end (or from
# the end of a wait for work), so the three add up to the step period.
_m_sample_ms = _metrics.histogram("serving.decode.sample_ms")
_m_sched_ms = _metrics.histogram("serving.decode.sched_ms")
# the device call's host stretches, one observation a target dispatch
# of a round each: dispatch_ms is the serving.decode.dispatch
# span's (the jitted call with its host arguments, the pools' rebind, the
# ids' copy to the host started, the draft's shadow call), release_ms the
# serving.decode.release span's (the step's device outputs let go).
# host_gap_ms is the host's stretch between two target steps: from the
# moment the newest dispatched step's ids are on the host to the return
# of the next dispatch (release, answer, the hand-over to the woken
# clients, admit, prepare, plan, build and dispatch; the device starts
# the step inside the dispatch, and the latency from its last operation
# to the ids on the host lies outside the stretch). It
# reads 0 where the step went ahead of the one before (that step's ids
# were not yet on the host: the device still held it), and a wait for
# work restarts it, as it restarts the round's clock. Other device calls
# between two target dispatches (page copies, restores, a host-route
# row's fetch, the propose/verify substep, the embed lane) lie inside it.
# ahead_pct observes, once a target dispatch, 100 where the step was
# dispatched before the ids of the step before it were on the host, and
# 0 where the host had them first (a synchronous round, or the first
# step after a wait for work)
_m_dispatch_ms = _metrics.histogram("serving.decode.dispatch_ms")
_m_release_ms = _metrics.histogram("serving.decode.release_ms")
_m_host_gap_ms = _metrics.histogram("serving.decode.host_gap_ms")
_m_ahead_pct = _metrics.histogram("serving.decode.ahead_pct")
# where a token was chosen (ISSUE 29), one increment a chosen token: by
# the step's own program (``choose_tokens``: greedy, and temperature > 0
# with top_k 0) or on the host from one fetched logits row (top_k > 0, a
# constraint mask, the first position's first_topk order). The histogram
# observes 100 * device / chosen once a step that chose a token
_m_device_choices = _metrics.counter("serving.decode.device_choices")
_m_host_choices = _metrics.counter("serving.decode.host_choices")
_m_device_choice_pct = _metrics.histogram(
    "serving.decode.device_choice_pct")
_m_queue_wait = _metrics.histogram("serving.decode.queue_wait_ms")
_m_total = _metrics.histogram("serving.decode.total_ms")
# live slots / slot bucket per step: the continuous-batching win is
# this histogram staying fat while drain-per-batch's decays
_m_occupancy = _metrics.histogram("serving.decode.occupancy")
# how much of the attention grid a step call walks holds a live page
# (ISSUE 31), one observation a step call that runs the attention:
# 100 * sum over slots of ceil(kv_len / page_size) over slot bucket x
# width bucket. The traffic and the two ladders set it, not the kernel:
# the paged kernel walks every (slot, column) and skips the empty ones,
# so the rest is what a dynamic grid would still save
_m_attn_grid_live = _metrics.histogram("serving.decode.attn_grid_live_pct")
# which fold the paged kernel's live pages take (ISSUE 35), beside it: 100 x
# the (lane, live page) folds of a step call that the kernel's dot fold
# takes (a slot's lanes against a page in two matrix products) over all of
# the call's, by the kernel's own predicate; 0 for a call whose attention
# is not the kernel's
_m_attn_dot_fold = _metrics.histogram("serving.decode.attn_dot_fold_pct")
# the latent kernel's grid, one observation a step call of a model with a
# latent pool: 100 x the (slot, block) grid steps that fold a live page
# over slot bucket x ceil(width bucket / k), k pages a block as
# ``latent_pages_per_step`` says (the kernel asks it too)
_m_attn_latent_steps = _metrics.histogram(
    "serving.decode.attn_latent_steps_live_pct")
# chunked prefill (ISSUE 10): prompt tokens consumed via prefill
# grants, per-step grant totals (prices the token-budget policy next
# to the occupancy/fragmentation gauges), and how many scheduler steps
# each request waited for its FIRST generated token — the
# load-independent evidence chunking exists for (ceil(P/chunk) + queue
# wait, vs P + queue wait unchunked)
_m_prefill_tokens = _metrics.counter("serving.decode.prefill_tokens")
_m_prefill_per_step = _metrics.histogram(
    "serving.decode.prefill_tokens_per_step")
_m_first_token_steps = _metrics.histogram(
    "serving.decode.steps_to_first_token")
# preempt+restore (ISSUE 13, demand-mode reservation): preemptions
# spill a victim's pages to host and requeue it at the front; restores
# scatter them back bitwise; demotions release a QUEUED reservation
# (no computed work lost) so a live grower can proceed
_m_preemptions = _metrics.counter("serving.kv.preemptions")
_m_restores = _metrics.counter("serving.kv.restores")
_m_demotions = _metrics.counter("serving.kv.demotions")
# speculative decoding (ISSUE 14): TARGET-model invocations — one per
# plain/prefill step AND one per verify chunk (warm included; benches
# delta it). The headline ratio is target_steps per generated token:
# spec off it is 1 per token, spec on a verify commits up to k+1
_m_target_steps = _metrics.counter("serving.decode.target_steps")
# DRAFT-model invocations (propose + prefill shadowing) — the cheap
# steps speculation trades for target steps
_m_draft_steps = _metrics.counter("serving.decode.spec.draft_steps")
# proposed == accepted + rejected, always (counter-pinned in tier-1);
# accept_rate histogram observes each finished request's ratio
_m_spec_proposed = _metrics.counter("serving.decode.spec.proposed")
_m_spec_accepted = _metrics.counter("serving.decode.spec.accepted")
_m_spec_rejected = _metrics.counter("serving.decode.spec.rejected")
_m_spec_accept_rate = _metrics.histogram(
    "serving.decode.spec.accept_rate")
# workload layer (ISSUE 20): constrained decode applies a token-mask
# automaton to the logits row before the per-(seed, position) choice
# (masked_tokens counts them); prompt-only embedding/scoring requests
# ride the chunked-prefill path in their OWN slot lane — decode
# live_slots never moves for them (counter-pinned in tier-1)
_m_masked_tokens = _metrics.counter("serving.decode.masked_tokens")
_m_embed_requests = _metrics.counter("serving.decode.embed.requests")
_m_embed_steps = _metrics.counter("serving.decode.embed.steps")
_m_embed_tokens = _metrics.counter("serving.decode.embed.tokens")
# generation by diffusion over blocks (ISSUE 30): a block model's
# decoding slot runs PASSES of block_length lanes, denoise passes that
# unmask some lanes and one commit pass whose K/V stand; a commit answers
# up to block_length tokens at once (tokens_dropped: those of a block past
# max_new or an eos). tokens_per_pass observes, once a step that ran a
# block pass, the tokens the step committed over the slots that ran one
_m_block_passes = _metrics.counter("serving.decode.block.passes")
_m_block_committed = _metrics.counter(
    "serving.decode.block.tokens_committed")
_m_block_dropped = _metrics.counter("serving.decode.block.tokens_dropped")
_m_block_tokens_per_pass = _metrics.histogram(
    "serving.decode.block.tokens_per_pass")
# sparse experts (ISSUE 30): token-to-expert assignments the steps made
# (live lanes x experts a token x layers), and, once a step that ran a
# block pass, the largest per-expert count over the mean in the step's
# worst layer (1.0 = even load)
_m_moe_assignments = _metrics.counter("serving.decode.moe.assignments")
_m_moe_load = _metrics.histogram("serving.decode.moe.load_max_over_mean")
# window layers (ISSUE 34). held_pct observes, once a round, the pages the
# window kind holds for the round's sequences over the pages the full kind
# holds for them; attn_window_skip_pct, once a step call, the share of the
# query-key pairs a causal mask would give the step's window layers that
# lie behind the window (0 until a sequence passes it)
_m_window_held = _metrics.histogram("serving.kv.window.held_pct")
_m_window_skip = _metrics.histogram("serving.decode.attn_window_skip_pct")
# state layers: state_resets counts the sequences whose state a step
# started from zero (a first chunk), state_spills
# the state rows a preemption carried to the host beside the pages
_m_state_resets = _metrics.counter("serving.decode.state_resets")
_m_state_spills = _metrics.counter("serving.decode.state_spills")


# --- the pluggable decoder model ----------------------------------------
# The engine asks a MODEL (paddle_tpu/models/decoders.py, D1): its pool
# layout, its parameter tree, its step and the block length it generates
# by. The dense decoder this engine was built on is one such model and
# keeps its names here.
from ..models.decoders import (DecoderSpec, KindTables,  # noqa: E402,F401
                               StateTables, build_decoder_params,
                               decoder_step, decoder_step_chunked,
                               seeded_decoder_arrays, spec_from_dict,
                               validate_draft_spec)


# --- sampling -----------------------------------------------------------

def sample_token(logits_row, temperature: float = 0.0, top_k: int = 0,
                 seed: int = 0, position: int = 0) -> int:
    """The HOST route's sampling policy for ONE generated token, from
    its fetched logits row (``top_k`` > 0 and masked requests; the rest
    are chosen on the device by ``choose_tokens``): greedy argmax at
    temperature 0 (the default — bitwise the PR 6 behavior), else
    temperature-scaled softmax over the ``top_k`` highest logits (0 =
    full vocab), drawn from an rng derived ONLY from ``(seed,
    position)``.

    Deterministic given the request's seed, and — because position is
    the token's absolute index in ITS sequence — independent of batch
    composition, slot assignment, and admission order: continuous
    batching cannot perturb a request's sampled output (tier-1 pins a
    request decoding identically through two differently-loaded
    engines)."""
    row = np.asarray(logits_row, np.float64)
    if temperature <= 0.0:
        return int(np.argmax(row))
    row = row / float(temperature)
    k = int(top_k)
    if 0 < k < row.size:
        kth = np.partition(row, -k)[-k]
        row = np.where(row < kth, -np.inf, row)
    row = row - row.max()
    p = np.exp(row)
    p /= p.sum()
    rng = np.random.Generator(np.random.PCG64(np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFF, int(position)])))
    return int(rng.choice(row.size, p=p))


def _top_order(row, k: int) -> List[int]:
    """The first ``k`` lanes of ``row`` in falling order of logit, ties
    by rising lane: what a stable sort of the whole row gives, from a
    partition and a sort of the lanes that can be among them (3 ms where
    the whole row's sort took 32 with the device dry, at vocab 256 008
    and k 4096)."""
    row = np.asarray(row)
    if not 0 < k < row.size or np.isnan(row).any():
        return [int(t) for t in np.argsort(-row, kind="stable")[:k]]
    kth = np.partition(row, row.size - k)[row.size - k]
    lanes = np.flatnonzero(row >= kth)      # rising, so ties stay so
    return [int(t) for t in
            lanes[np.argsort(-row[lanes], kind="stable")[:k]]]


def choose_tokens(logits, temperature, seed, position):
    """THE token choice of a step, made by the program that made the
    logits (ISSUE 29): ``logits [B, V]`` float32, ``temperature [B]``
    float32, ``seed [B]`` uint32 (the request's seed ``& 0xFFFFFFFF``),
    ``position [B]`` int32 (the new token's absolute index in ITS
    sequence) -> ``ids [B]`` int32. Traceable: the plain step, the
    speculative verify and the draft all fuse this one function, so a
    committed token is what the non-speculative engine emits whichever
    program chose it.

    A row with ``temperature == 0`` is ``argmax(logits)``, the first
    index on ties, bitwise what ``np.argmax`` gives on the host. A row
    with ``temperature > 0`` is the Gumbel-max draw ``argmax(logits / T
    + g)``, an exact draw from ``softmax(logits / T)``, with ``g`` from
    a key folded ONLY from ``(seed, position)``: deterministic given the
    request's seed and independent of batch composition, slot assignment
    and admission order, as ``sample_token`` is on the host route (the
    two draw from different generators: the same distribution, not the
    same ids). Dead rows draw garbage nobody reads."""
    import jax
    import jax.numpy as jnp

    def noise(s, p):
        # the generator is named, so that no default can change the ids
        key = jax.random.fold_in(
            jax.random.key(s, impl="threefry2x32"), p)
        return jax.random.gumbel(key, logits.shape[-1:], jnp.float32)

    with jax.named_scope("decoder.choose"):
        drawn = temperature > 0.0
        t = jnp.where(drawn, temperature, 1.0)[:, None]
        scores = jnp.where(drawn[:, None],
                           logits / t + jax.vmap(noise)(seed, position),
                           logits)
        return jnp.argmax(scores, axis=-1).astype(jnp.int32)


def choose_block(logits, temperature, seed, positions, masked, n_unmask):
    """The block form of ``choose_tokens`` (ISSUE 30), for a model that
    generates by diffusion over blocks: ``logits [S, B, V]`` float32 of a
    pass's B lanes (lane i predicts the token AT i), ``temperature [S]``,
    ``seed [S]`` uint32, ``positions [S, B]`` int32 (each lane's absolute
    index), ``masked [S, B]`` bool (the lanes still to be filled: slot
    STATE, never a comparison with the mask id) and ``n_unmask [S]`` int32
    -> ``(x0 [S, B] int32, confidence [S, B] float32, unmask [S, B]
    bool)``. ``x0`` is ``choose_tokens``' choice at every lane (argmax at
    temperature 0, else the draw keyed by (seed, position)), its
    confidence ``softmax(logits)[x0]``, and ``unmask`` marks the
    ``n_unmask`` most confident of the masked lanes, ties to the lower
    lane (confidence-ordered static unmasking). All per slot: batch
    composition cannot move a request's tokens."""
    import jax
    import jax.numpy as jnp

    s, b, v = logits.shape
    x0 = choose_tokens(logits.reshape(s * b, v), jnp.repeat(temperature, b),
                       jnp.repeat(seed, b),
                       positions.reshape(s * b)).reshape(s, b)
    with jax.named_scope("decoder.unmask"):
        chosen = jnp.take_along_axis(logits, x0[..., None], axis=-1)[..., 0]
        conf = jnp.exp(chosen - jax.nn.logsumexp(logits, axis=-1))
        score = jnp.where(masked, conf, -1.0)
        lane = jnp.arange(b)
        ahead = (score[:, None, :] > score[:, :, None]) | (
            (score[:, None, :] == score[:, :, None])
            & (lane[None, None, :] < lane[None, :, None]))
        rank = jnp.sum(ahead, axis=-1)                   # [S, B]
        unmask = masked & (rank < n_unmask[:, None])
    return x0, conf, unmask


def feed_tokens(tokens, src, ids, masked=None, unmask=None, prev_tokens=None,
                prev_masked=None):
    """The next step's input where the host does not yet hold what it
    feeds: the step before it chose that on the device. ``tokens [S, C]``
    int32 are the host-built rows; ``src [S]`` int32 names, a row, the
    row of the step before whose answer it continues from (-1: the host
    filled the row, a prefill chunk or a new admission); ``ids`` is what
    that step's program handed back. A causal row takes ``ids[src]`` at
    lane 0, the token it decodes. A block model's (``masked [S, B]``
    given, with that step's ``unmask``, its input ``prev_tokens`` and
    ``prev_masked``) carries on with its block: the lanes that pass
    unmasked take its choices, the rest keep their input, and a lane
    stays masked where it was and was not unmasked. Returns ``(tokens,
    masked)``, ``masked`` None for a causal model."""
    import jax.numpy as jnp

    take = src >= 0
    row = jnp.maximum(src, 0)
    if masked is None:
        return tokens.at[:, 0].set(
            jnp.where(take, ids[row], tokens[:, 0])), None
    b = ids.shape[1]
    opened = unmask[row]
    block = jnp.where(opened, ids[row], prev_tokens[row, :b])
    carry = take[:, None]
    return (tokens.at[:, :b].set(jnp.where(carry, block, tokens[:, :b])),
            jnp.where(carry, prev_masked[row] & ~opened, masked))


_feed_jit = None


def _feed_program():
    """``feed_tokens`` jitted once a process: every engine's calls of one
    shape share its compiled program."""
    global _feed_jit
    if _feed_jit is None:
        import jax

        _feed_jit = jax.jit(feed_tokens)
    return _feed_jit


def _live_pages(kv_lens, page_size: int) -> int:
    """Pages that hold a key a step call's slots see."""
    return int((-(-kv_lens.astype(np.int64) // page_size)).sum())


def _latent_steps_live_pct(slots: int, width: int, kv_lens,
                           page_size: int) -> float:
    """100 x the share of the latent kernel's ``slots x ceil(width / k)``
    grid steps that fold a live page, a step a block of ``k`` pages."""
    from ..fluid.ops.pallas_kernels.paged_attention import (
        latent_pages_per_step)

    k = latent_pages_per_step(page_size, width)
    return 100.0 * _live_pages(kv_lens, k * page_size) / (
        slots * -(-width // k))


def _dot_fold_pct(chunk: int, rep: int, pool_dtype, q_lens, kv_lens, *,
                  page_size: int, window: Optional[int] = None,
                  layers: Tuple[int, int] = (1, 0)) -> float:
    """100 x the share of a step call's (lane, live page) folds that the
    paged kernel's dot fold takes, ``folds_by_dot`` deciding slot by slot
    as it does in the kernel. A slot folds each of its ``q_len`` lanes
    into the pages it has in view: every page up to ``kv_len`` in each of
    ``layers[0]`` full layers, those from its oldest lane's window on in
    each of ``layers[1]`` window layers."""
    from ..fluid.ops.pallas_kernels.paged_attention import folds_by_dot

    if not folds_by_dot(chunk, rep, pool_dtype):
        return 0.0      # the call's program holds the lane loop alone
    q, kv = q_lens.astype(np.int64), kv_lens.astype(np.int64)
    pages = -(-kv // page_size)
    folds = q * pages * layers[0]
    if window is not None:
        folds += q * (pages - np.maximum(kv - q - window + 1, 0)
                      // page_size) * layers[1]
    total = int(folds.sum())
    by_dot = np.asarray(folds_by_dot(chunk, rep, pool_dtype, q))
    return 100.0 * int(folds[by_dot].sum()) / total if total else 0.0


def _call_work(slots: int, chunk: int, width: int, q_lens,
               kv_lens, block: int = 1, *,
               window: Optional[int] = None) -> Dict[str, int]:
    """``serving.decode.device_call``'s args: the compiled buckets of
    one step call and the sums its attention work follows from,
    whatever implements the call — query tokens, keys in view, and
    query-key pairs under the model's mask: a slot's ``q`` newest
    tokens, whole blocks of ``block``, see from ``kv-q+block`` up to
    ``kv`` keys (``block`` 1 is the causal mask: ``kv-q+1`` up to
    ``kv``). A layer's attention needs
    ``4 * heads * head_dim * attn_pairs`` operations and reads
    ``2 * kv_heads * head_dim * kv_tokens`` K/V elements, beside
    ``2 * heads * head_dim * q_tokens`` of q and out. Dead slots are
    0/0 and add nothing.

    A model with WINDOW layers (ISSUE 34, ``window`` keys a lane) has the
    sums by kind as well, a layer of each: ``kv_tokens_full`` /
    ``attn_pairs_full`` are the causal sums above, ``kv_tokens_window``
    the keys its window layers have in view (a slot's oldest lane sees
    ``window``, the chunk's others the lanes before them too) and
    ``attn_pairs_window`` the pairs (a lane at position p sees ``min(p +
    1, window)``)."""
    q, kv = q_lens.astype(np.int64), kv_lens.astype(np.int64)
    out = {"slots": slots, "chunk": chunk, "width": width,
           "q_tokens": int(q.sum()), "kv_tokens": int(kv.sum()),
           "attn_pairs": int(((2 * kv - q + block) * q // 2).sum())}
    if window is not None:
        # the first lane sees ``first`` keys; ``low`` lanes see fewer
        # than a window's (an arithmetic run), the rest a window's
        first = kv - q + 1
        low = np.clip(window - first + 1, 0, q)
        out.update(
            kv_tokens_full=out["kv_tokens"],
            attn_pairs_full=out["attn_pairs"],
            kv_tokens_window=int(np.where(
                q > 0, np.minimum(kv, window + q - 1), 0).sum()),
            attn_pairs_window=int((low * (2 * first + low - 1) // 2
                                   + window * (q - low)).sum()))
    return out


# --- ladders ------------------------------------------------------------

def width_ladder(max_pages: int) -> List[int]:
    """Page-table width buckets: powers of two up to (and always
    including) the worst case — the second padded dimension of the
    compiled decode shape."""
    if max_pages < 1:
        raise ValueError(f"max_pages must be >= 1, got {max_pages}")
    out, w = [], 1
    while w < max_pages:
        out.append(w)
        w *= 2
    out.append(max_pages)
    return sorted(set(out))


# --- requests / slots ---------------------------------------------------

class _DecodeRequest:
    __slots__ = ("prompt", "max_new", "deadline", "ev", "result", "error",
                 "t_enq", "seq_id", "trace_ctx", "temperature", "top_k",
                 "seed", "produced", "cached_tokens", "cow", "resume_pos",
                 "published", "carry_steps", "carry_fts", "needs_alloc",
                 "resume_dpos", "spec_proposed", "spec_accepted",
                 "mask", "mask_state", "want_topk", "first_topk",
                 "denoise_steps", "passes", "resume_wfirst")

    def __init__(self, prompt: np.ndarray, max_new: int,
                 deadline: Optional[float], seq_id: int,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 mask: Optional[Any] = None, want_topk: int = 0,
                 denoise_steps: int = 1):
        self.prompt = prompt
        self.max_new = int(max_new)
        self.deadline = deadline
        self.ev = threading.Event()
        self.result: Optional[Dict[str, Any]] = None
        self.error: Optional[BaseException] = None
        self.t_enq = time.monotonic()
        self.seq_id = seq_id
        self.trace_ctx = _tracing.wire_context()
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.seed = int(seed)
        # generated tokens, appended by the answer phase UNDER the
        # engine's _cond. Living on the REQUEST (not the slot) so
        # streaming readers (stream_tokens, ISSUE 12) can see tokens
        # the moment they exist, long before the sequence finishes
        self.produced: List[int] = []
        # prefix caching + preemption state (ISSUE 13) — on the REQUEST
        # because preemption round-trips a sequence through the queue:
        # cached_tokens = prompt tokens answered from the prefix index
        # (prefill starts past them); cow = the pending private-copy of
        # a shared partial page (executed by the scheduler before the
        # first step, then None); resume_pos/carry_* = the exact point
        # a preempted sequence continues from; needs_alloc = the
        # reservation was surrendered (preempt/demote) and admission
        # must re-reserve before taking a slot
        self.cached_tokens = 0
        self.cow: Optional[Dict[str, int]] = None
        self.resume_pos: Optional[int] = None
        self.published = False
        self.carry_steps = 0
        self.carry_fts: Optional[int] = None
        self.needs_alloc = False
        # speculative decoding (ISSUE 14): the draft pool's valid-write
        # watermark carried through preemption (mirrors resume_pos),
        # and the request's propose/accept tallies (accept_rate in the
        # result dict)
        self.resume_dpos: Optional[int] = None
        # window layers (ISSUE 34): the logical page a preempted
        # sequence's window kind began at, where its restore begins
        self.resume_wfirst = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        # constrained decode (ISSUE 20): a compiled MaskAutomaton and
        # its current state. On the REQUEST (not the slot) because the
        # state must survive preemption round-trips — produced tokens
        # never roll back on the plain path, so the automaton resumes
        # exactly where it stopped. want_topk asks the answer phase to
        # capture the FIRST generated position's top-k token order
        # (first_topk) — the n-best/beam fork point.
        self.mask = mask
        self.mask_state = mask.start if mask is not None else 0
        self.want_topk = int(want_topk)
        self.first_topk: Optional[List[int]] = None
        # block diffusion (ISSUE 30): denoise passes a block (each
        # unmasks block_length / denoise_steps lanes); a request that
        # asked for its first_topk also keeps every pass's input block,
        # choices, confidences and unmasked lanes (result["passes"]):
        # what a comparison with a plain reference needs
        self.denoise_steps = int(denoise_steps)
        self.passes: Optional[List[Dict[str, Any]]] = (
            [] if want_topk else None)

    def fail(self, err: BaseException):
        self.error = err
        self.ev.set()


class _Slot:
    __slots__ = ("req", "pos", "pages_held", "steps", "first_token_steps",
                 "pending_restore", "dpos", "block", "masked",
                 "wpages_held", "wfirst")

    def __init__(self, req: _DecodeRequest, pages_held: int):
        self.req = req
        self.pos = 0                # tokens already written to the cache
        self.pages_held = pages_held
        self.steps = 0              # scheduler steps this slot has ridden
        self.first_token_steps: Optional[int] = None
        # a preempted sequence's spilled pages must scatter back into
        # its fresh reservation BEFORE its next step (restore-before-
        # step): set at re-admission, executed by _prepare
        self.pending_restore = False
        # speculative decoding (ISSUE 14): positions validly written to
        # the DRAFT pool. Invariant: pos - 1 <= dpos <= pos — the draft
        # lags by at most one committed token (exactly one after a
        # fully-accepted round, whose last proposal it never fed
        # itself), so the next propose round catches up with a <= 2-
        # lane chunk before proposing
        self.dpos = 0
        # block diffusion (ISSUE 30): the block this slot is filling, at
        # positions [pos, pos + B) — its tokens as far as known, and
        # WHICH LANES ARE MASKED (state; a prompt that holds the mask id
        # is harmless). None = no block open (prefill, or a causal model)
        self.block: Optional[List[int]] = None
        self.masked: Optional[List[bool]] = None
        # window layers (ISSUE 34): the pages the WINDOW kind holds for
        # this sequence and the logical page the first of them is; 0/0
        # under a model whose layers are all full
        self.wpages_held = 0
        self.wfirst = 0

    def token_at(self, idx: int) -> int:
        """The sequence's token at absolute position ``idx``: a prompt
        token, or a previously generated one."""
        p = self.req.prompt
        return (int(p[idx]) if idx < len(p)
                else self.req.produced[idx - len(p)])

    def tokens_at(self, start: int, n: int):
        """The sequence's ``n`` tokens from absolute position ``start``
        on: a slice of the prompt where they all lie in it."""
        if start + n <= len(self.req.prompt):
            return self.req.prompt[start:start + n]
        return [self.token_at(i) for i in range(start, start + n)]

    def progress(self) -> str:
        return f"mid-decode after {len(self.req.produced)} tokens"


class _EmbedRequest:
    """A prompt-only embedding/scoring request (ISSUE 20): admitted by
    the same reserve-at-admission math with ``max_new = 0`` (the
    reservation is exactly the prompt's pages — there is no decode
    tail to headroom for), prefilled by the same chunked step, and
    NEVER occupying a decode slot: the embed lane has its own slot
    list and gauge, so ``serving.decode.live_slots`` is pinned
    unchanged while embeddings flow. Carries ``cow``/``seq_id``/
    ``fail`` so ``_fail_locked`` treats both request classes
    uniformly."""

    __slots__ = ("prompt", "deadline", "ev", "result", "error", "t_enq",
                 "seq_id", "trace_ctx", "cow", "hidden_sum", "logprobs")

    def __init__(self, prompt: np.ndarray, deadline: Optional[float],
                 seq_id: int, d_model: int):
        self.prompt = prompt
        self.deadline = deadline
        self.ev = threading.Event()
        self.result: Optional[Dict[str, Any]] = None
        self.error: Optional[BaseException] = None
        self.t_enq = time.monotonic()
        self.seq_id = seq_id
        self.trace_ctx = _tracing.wire_context()
        self.cow: Optional[Dict[str, int]] = None
        # float64 running sum of final-norm hidden states — mean-pooled
        # over the prompt at completion — and the per-token logprobs
        # (position p scores prompt[p+1]; P-1 values for a P-token
        # prompt), both appended by the embed answer phase under _cond
        self.hidden_sum = np.zeros(d_model, np.float64)
        self.logprobs: List[float] = []

    def fail(self, err: BaseException):
        self.error = err
        self.ev.set()


class _EmbedSlot:
    __slots__ = ("req", "pos", "pages_held", "steps")

    def __init__(self, req: _EmbedRequest, pages_held: int):
        self.req = req
        self.pos = 0                # prompt tokens already prefilled
        self.pages_held = pages_held
        self.steps = 0

    def progress(self) -> str:
        return f"mid-prefill at {self.pos} tokens"


class _Program(NamedTuple):
    """One entry of the engine's program table: a jitted body
    ``fn(params, tokens, positions, q_lens, k_pool, v_pool, tables,
    lens, ...)`` that hands back the two pools and then what it answers,
    and what ``DecodeEngine._run`` must know to call it: whether it
    reads the DRAFT's params and pool or the target's, the counter a
    call bumps, and whether it takes the sampling arrays
    (``temperature``, ``seed``) and a block model's mask arrays
    (``masked``, ``n_unmask``) after ``lens``."""

    fn: Any
    draft: bool = False
    steps: Any = _m_target_steps
    sampled: bool = True
    masked: bool = False


class _Round:
    """One decoding round between its plan and its answers (the
    scheduler thread's own): which slots ride the target program's call
    and what each feeds, what the call handed back, and what the slots'
    answers tally for the round's counters. The next round is planned
    and dispatched before this one is answered, from what the plan
    knows: how many tokens each slot is owed, which end by ``max_new``,
    and a block's masked lanes left."""

    def __init__(self, live: List[_Slot], grants: List[int]):
        # the slots answered at this round, with their grants
        self.live, self.grants = live, grants
        # the plan: the call's slots in row order, each row's (start
        # position, tokens) feed, a block model's pass a row, and the
        # decoding slots a draft proposes for (they ride no row)
        self.rows: List[_Slot] = []
        self.row_of: Dict[int, int] = {}    # id(slot) -> its row
        self.feeds: List[Tuple[int, Any]] = []
        self.kinds: List[str] = []
        self.spec: List[_Slot] = []
        # a row's source in the round before it (-1: the host fills the
        # row; else that round's row whose choice this row feeds on)
        self.src: List[int] = []
        # whether a slot of the call reads its choice, and whether one
        # reads a token the HOST chooses (the next round then waits)
        self.reads = False
        self.host = False
        self.prefill_toks = 0
        # tokens the answer will emit a slot, {id(slot): n}, as far as
        # max_new lets; the slots that reach max_new with them; a block
        # slot's masked lanes left after its denoise pass, {id(slot): n};
        # the blocks the plan opened, {row: (block, masked)}; the rows
        # whose logits row the answer reads
        self.owed: Dict[int, int] = {}
        self.ends: set = set()
        self.left: Dict[int, int] = {}
        self.opened: Dict[int, Tuple[List[int], List[bool]]] = {}
        self.wanted: List[int] = []
        # a block program's further arrays, a row a slot of the bucket,
        # and the device_call span's further args
        self.call_kw: Dict[str, np.ndarray] = {}
        self.call_args: Dict[str, int] = {}
        # the call: what the program handed back, on the device until the
        # answer collects it (``dev``) and on the host once somebody
        # reads it (``out``: ``ids`` and whatever else the program hands
        # back); the tokens and masked lanes it was fed (the next round's
        # block rows carry on from them); its logits and the wanted rows
        # of them on the device; and the substep's {id(slot): (committed,
        # k_eff, accepted)}
        self.dev: Optional[Dict[str, Any]] = None
        self.fed: Tuple[Any, Any] = (None, None)
        self.out: Optional[Dict[str, np.ndarray]] = None
        self.logits = None
        self.rows_out: Dict[int, Any] = {}
        self.spec_out: Dict[int, Tuple[List[int], int, int]] = {}
        # the answers' tallies: tokens chosen on the "device" / the
        # "host", draft tokens "proposed" / "accepted", a block model's
        # "passes" and expert "assignments" (both the plan's) and its
        # "dropped" tokens; and the host sampler's seconds
        self.n: collections.Counter = collections.Counter()
        self.sample_s = 0.0

    @contextlib.contextmanager
    def sampling(self):
        """The host route's choice of one slot's token: its span, and
        its time in the round's ``sample_ms``."""
        t0 = time.perf_counter()
        with _tracing.span("serving.decode.sample"):
            yield
        self.sample_s += time.perf_counter() - t0


# --- the engine ---------------------------------------------------------

class DecodeEngine:
    """Continuous-batching autoregressive decode over one loaded
    decoder. Registry/server-compatible: ``name``/``version``/``kind``/
    ``stats()``/``stop(drain=)`` mirror InferenceEngine, so the same
    ModelRegistry hot-swaps decoders with the same drain guarantee."""

    kind = "decoder"

    def __init__(self, spec: DecoderSpec, *, name: str = "decoder",
                 version: int = 1,
                 slots: Optional[Sequence[int]] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 max_seq_len: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefill_budget: Optional[int] = None,
                 continuous: bool = True,
                 params: Optional[Dict[str, Any]] = None,
                 prefix_cache: Optional[bool] = None,
                 reservation: Optional[str] = None,
                 spill_dir: Optional[str] = None,
                 draft_spec: Optional[Any] = None,
                 draft_params: Optional[Dict[str, Any]] = None,
                 spec_k: Optional[int] = None,
                 mesh: Optional[Any] = None,
                 mesh_rules: Optional[Any] = None,
                 embeddings: bool = False,
                 num_window_pages: Optional[int] = None,
                 warm: bool = True):
        from ..fluid.flags import FLAGS, effective_flag

        self.name = str(name)
        self.version = int(version)
        self.spec = spec
        # the block length the MODEL generates by (ISSUE 30): 1 for a
        # causal model, the engine it always was; B > 1 for generation by
        # diffusion over blocks — a decoding slot then runs passes of B
        # lanes (_plan_blocks) and a prefill chunk is whole blocks. What
        # speculation, the embed lane and the prefix cache assume (one
        # token a pass; K/V that are a function of the tokens before
        # them) does not hold for such a model: each refuses it by name
        self._block = int(spec.block_length)
        if self._block > 1:
            for field, given in (("draft_spec", draft_spec is not None),
                                 ("spec_k", bool(spec_k)),
                                 ("embeddings", bool(embeddings)),
                                 ("prefix_cache", bool(prefix_cache))):
                if given:
                    raise ValueError(
                        f"decoder '{name}' generates by diffusion over "
                        f"blocks of {self._block} (family "
                        f"{spec.family!r}): '{field}' is for causal "
                        f"models (block_length 1)")
            prefix_cache = False
        # layer kinds (ISSUE 34): a model with WINDOW layers is served
        # from a pair of caches, and its window kind gives a sequence's
        # pages back from the front as it grows. What assumes that a
        # sequence's pages, once written, stay its own to the end — a
        # shared prefix (sharers would need the window pages kept), a
        # draft's mirrored pool and its rollback, the embed lane's own
        # slots — and the mesh's sharded pools are not carried through
        # for such a model: each is refused by name where a caller asks
        # for it, and off where nobody does
        self._window = spec.window if "window" in spec.layer_kinds else None
        if self._window is not None:
            for field, given in (("draft_spec", draft_spec is not None),
                                 ("spec_k", bool(spec_k)),
                                 ("embeddings", bool(embeddings)),
                                 ("prefix_cache", bool(prefix_cache)),
                                 ("mesh", bool(mesh))):
                if given:
                    raise ValueError(
                        f"decoder '{name}' has window layers (family "
                        f"{spec.family!r}, window {self._window}): "
                        f"'{field}' is not carried through for a cache "
                        f"that gives window pages back as a sequence "
                        f"grows")
            prefix_cache, mesh = False, ""
        elif num_window_pages is not None:
            raise ValueError(
                f"'num_window_pages' sizes the window kind's pool; "
                f"decoder '{name}' (family {spec.family!r}) has no window "
                f"layer")
        # state layers: a sequence's state is a function of EVERY token
        # before it and is carried, not recomputed. A shared prefix (its
        # pages hold no state), a draft's rollback (the state cannot
        # un-see a rejected token), the embed lane's own slots and the
        # mesh's sharded pools are refused by name as for window layers
        self._stateful = "state" in spec.layer_kinds
        if self._stateful and self._window is not None:
            # the state kind's arrays take the step's second pool
            # arguments, which the window kind's pool holds
            raise ValueError(
                f"decoder '{name}' has window and state layers (family "
                f"{spec.family!r}): one engine serves one of the two "
                f"kinds beside its full layers")
        if self._stateful:
            for field, given in (("draft_spec", draft_spec is not None),
                                 ("spec_k", bool(spec_k)),
                                 ("embeddings", bool(embeddings)),
                                 ("prefix_cache", bool(prefix_cache)),
                                 ("mesh", bool(mesh))):
                if given:
                    raise ValueError(
                        f"decoder '{name}' has state layers (family "
                        f"{spec.family!r}): '{field}' is not carried "
                        f"through for a sequence whose state is carried "
                        f"from step to step")
            prefix_cache, mesh = False, ""
        # mesh-sharded serving (ISSUE 15): one replica SPANS chips.
        # `mesh` is a MeshSpec / axes dict / "tp=2" string (None reads
        # FLAGS['serving_mesh_axes']; '' = single-chip, bit-identical
        # PR 6 behavior). Params shard per name-matched `mesh_rules`
        # (default mesh.decoder_rules) and the paged KV pool shards
        # over the kv-head axis — the axis the wk/wv rules put on their
        # column dim — with the step fns' out_shardings pinned so churn
        # still compiles nothing post-warm.
        mesh_arg = FLAGS["serving_mesh_axes"] if mesh is None else mesh
        self._mesh_spec = None
        self._mesh = None
        self._mesh_rules = None
        self._kv_head_axes = None
        if mesh_arg:
            from ..mesh import (MeshSpec, ShardingRules, decoder_rules,
                                note_mesh)

            self._mesh_spec = MeshSpec.coerce(mesh_arg)
            self._mesh = self._mesh_spec.build()
            rules = ShardingRules.coerce(mesh_rules,
                                         default=decoder_rules)
            self._mesh_rules = rules
            self._kv_head_axes = self._kv_pool_axes(rules)
            self._check_kv_divisible("target", spec)
            note_mesh(self._mesh, label=f"decode:{name}.v{version}")
        # shares _step_mu with the compiled step + shape set: the lock
        # serializes every read-step-rebind against retirement's drop
        self._params = self._place_params(
            spec.seeded_arrays()
            if params is None else params)  # guarded-by: _step_mu
        # the Pallas paged kernel has no SPMD form (a shard_map form is
        # ROADMAP S5), so an engine whose pools are sharded over a mesh
        # names the pure-jax reference itself; single-chip engines let
        # the flags route. stats()/load_report show the result.
        self._attention_impl = ("reference" if self._mesh is not None
                                else None)
        # a model with experts takes the same word for its grouped
        # products: the Pallas moe_gmm single-chip on a TPU, XLA's
        # ragged_dot under a mesh, off a TPU and at widths the kernel
        # does not tile
        from ..fluid.ops.pallas_kernels.moe_gmm import moe_route

        self._experts_route = (
            moe_route(spec.d_model, spec.expert_width, self._attention_impl)
            if spec.moe_assignments_per_token else None)
        # slots="auto" resolves through the tuner exactly like the
        # one-shot engine's buckets="auto": a derived ladder from the
        # observed slot-demand histogram (or the cached one), else the
        # static FLAGS default — fixed before warm() either way
        self._slot_ladder = resolve_bucket_spec(
            FLAGS["decode_slots"] if slots is None else slots,
            tunable_id="decode_slots", fallback="1,2,4")
        self._max_slots = self._slot_ladder[-1]
        from ..fluid.ops.pallas_kernels.paged_attention import paged_route

        routes = {n: paged_route(n, self._attention_impl)
                  for n in self._slot_ladder}
        self._attention_routes = sorted(set(routes.values()))
        # the slot buckets whose step call's attention is the kernel's
        self._kernel_slots = {n for n, route in routes.items()
                              if route == "paged_kernel"}
        # what the kernel's predicate asks beside a call's chunk and
        # q_lens (head group, pools' dtype), and the layers of each kind
        self._fold_geometry = (spec.n_heads // spec.n_kv_heads,
                               spec.pool_dtype)
        self._attn_layers = (spec.layer_kinds.count("full"),
                             spec.layer_kinds.count("window"))
        # a latent pool (one array a token, no V) is read by the latent
        # kernel, whose grid steps are blocks of pages
        self._latent_pool = not getattr(spec, "pool_values", True)
        if self._mesh is not None:
            _log.info("decode %s.v%d spans mesh %s: attention takes %s "
                      "(the Pallas paged kernel has no SPMD form)",
                      self.name, self.version, self._mesh_spec,
                      ",".join(self._attention_routes))
        ps = int(FLAGS["kv_page_size"] if page_size is None else page_size)
        npages = int(FLAGS["kv_num_pages"] if num_pages is None
                     else num_pages)
        self.max_seq_len = int(FLAGS["decode_max_seq_len"]
                               if max_seq_len is None else max_seq_len)
        self._max_queue = int(FLAGS["serving_max_queue"]
                              if max_queue is None
                              else max_queue)  # guarded-by: _cond
        # drain-per-batch mode (continuous=False) exists ONLY as the
        # honest A/B baseline for decode_bench — same engine, same
        # compiled shapes, admission gated on an empty batch
        self._continuous = bool(continuous)
        # prefix caching + reservation policy (ISSUE 13). demand mode
        # reserves the prompt's pages plus kv_decode_headroom pages at
        # admission and grows mid-decode (preempting when the pool runs
        # dry); worst_case is the PR 6 reserve-everything policy, kept
        # as the bench's admitted-concurrency baseline
        self._prefix_on = bool(FLAGS["prefix_cache"]
                               if prefix_cache is None else prefix_cache)
        reservation = str(FLAGS["kv_reservation"]
                          if reservation is None else reservation)
        if reservation not in ("demand", "worst_case"):
            raise ValueError(
                f"reservation must be 'demand' or 'worst_case', "
                f"got {reservation!r}")
        self._reservation = reservation
        self._headroom_pages = max(0, int(FLAGS["kv_decode_headroom"]))
        # the FULL kind's cache, which every model has: the layers that
        # keep every key (all of them, but for a model with window layers)
        self.cache = PagedKvCache(
            spec.layer_kinds.count("full"), spec.n_kv_heads, spec.head_dim,
            page_size=ps, num_pages=npages, dtype=spec.pool_dtype,
            label=f"{self.name}.v{self.version}",
            prefix_cache=self._prefix_on,
            mesh=self._mesh, shard_spec=self._pool_spec(),
            values=getattr(spec, "pool_values", True))
        # host refuge for preempted sequences' pages (kv_spill_dir
        # moves it to disk); cleared at retirement — leaks nothing
        self._spill = HostSpillStore(
            spill_dir=spill_dir, label=f"{self.name}.v{self.version}")
        w_max = self.cache.allocator.pages_for_tokens(self.max_seq_len)
        self._width_ladder = width_ladder(w_max)
        # chunked prefill (ISSUE 10): the per-step prompt-token budget
        # AND the compiled chunk width. A PR 8 tunable: the FLAGS
        # constant is the cold default, the autotune cache overrides
        # per device kind (decode_bench seeds it via measure-or-model
        # and the observed prompt-length histogram). Clamped to the
        # longest admissible prompt (max_seq_len - 1: max_new >= 1) —
        # a wider chunk than any prompt only burns warm compiles.
        # Resolved ONCE, before warm(), like every other ladder knob.
        chunk = int(effective_flag("prefill_chunk")
                    if prefill_chunk is None else prefill_chunk)
        self._prefill_chunk = max(1, min(chunk, max(1,
                                                    self.max_seq_len - 1)))
        # a block model prefills whole blocks: its chunk is the next
        # multiple of the block length
        self._prefill_chunk = -(-self._prefill_chunk
                                // self._block) * self._block
        # the third padded dimension of the compiled step: pure-decode
        # steps ride the C=1 shapes (one token a slot — chunking
        # costs nothing when no prompt is in flight; a block model's
        # passes ride C=block_length), steps carrying a prefill grant
        # ride the C=chunk shapes
        self._chunk_ladder = sorted({self._block, self._prefill_chunk})
        # the prompt tokens a step grants over all its prefill slots;
        # each slot's grant stays within the chunk. Left unset it is the
        # chunk, so that the slot that holds the budget takes it all. A
        # wider budget fills the lanes that a step of the chunk's shape
        # computes anyway: many sequences in their prompts at once then
        # each take up to a chunk a step, not one token
        self._prefill_budget = (self._prefill_chunk if prefill_budget is None
                                else int(prefill_budget))
        if self._prefill_budget < self._prefill_chunk:
            raise ValueError(
                f"prefill_budget {self._prefill_budget} is under the "
                f"chunk ({self._prefill_chunk}): the slot that holds the "
                f"budget could never take a whole chunk")
        # the WINDOW kind's cache (ISSUE 34), over an allocator of its
        # own: a pair, as a draft's pool beside its target's, and not one
        # pool with a kind axis, because the two kinds hold different
        # NUMBERS of pages for one sequence and free them at different
        # times. A step's oldest lane sees ``window`` keys and writes up
        # to a chunk, so a sequence never needs more than
        # ``_wwidth`` pages of it, which is also the width of the table
        # its kernel call reads
        self._wcache = None
        self._wwidth = 0
        if self._window is not None:
            self._wwidth = min(w_max, -(-(self._window + self._prefill_chunk
                                          - 1) // ps) + 1)
            wpages = int(npages if num_window_pages is None
                         else num_window_pages)
            if wpages - 1 < self._wwidth:
                raise ValueError(
                    f"num_window_pages {wpages} cannot hold one sequence's "
                    f"window: {self._wwidth} pages of {ps} for window "
                    f"{self._window} and a chunk of {self._prefill_chunk}")
            self._wcache = PagedKvCache(
                spec.layer_kinds.count("window"), spec.n_kv_heads,
                spec.head_dim, page_size=ps, num_pages=wpages,
                dtype=spec.pool_dtype,
                label=f"{self.name}.v{self.version}.window")
        # the STATE kind's arrays: a row for each slot the ladder allows
        # and the garbage row, the model's per-sequence state at a fixed
        # size, counted in hbm_bytes beside the pools
        self._scache = (SlotStateCache(
            spec.slot_state, self._max_slots + 1,
            label=f"{self.name}.v{self.version}.state")
            if self._stateful else None)
        self._hbm_bytes = self.cache.hbm_bytes + sum(
            c.hbm_bytes for c in (self._wcache, self._scache)
            if c is not None)
        # speculative decoding (ISSUE 14): a small DRAFT decoder
        # proposes spec_k tokens per decoding slot per round; the
        # target verifies all k+1 positions in ONE chunked call. The
        # draft's KV pool MIRRORS the target's page geometry — same
        # allocator, same page ids, same tables — so reservation,
        # rollback, COW, spill and restore stay ONE mechanism. spec_k
        # resolves like every ladder knob: explicit arg, else the
        # autotune cache through effective_flag ('spec_k'), else the
        # FLAGS cold default (0 = off, bit-identical old behavior).
        if isinstance(draft_spec, dict):
            draft_spec = spec_from_dict(draft_spec)
        k_spec = int(effective_flag("spec_k")
                     if spec_k is None else spec_k)
        if k_spec < 0:
            raise ValueError(f"spec_k must be >= 0, got {k_spec}")
        if k_spec > 0 and draft_spec is None and spec_k is not None:
            # only an EXPLICIT spec_k without a draft is a caller error;
            # a flag/autotune-sourced value must not refuse plain
            # deploys fleet-wide once a nonzero winner is persisted —
            # engines without a draft are always off (flags.py)
            raise ValueError(
                f"spec_k {k_spec} needs a draft decoder — pass "
                "draft_spec (or draft_checkpoint_dir through the "
                "server)")
        if draft_spec is not None:
            validate_draft_spec(spec, draft_spec)
            if self._mesh is not None:
                self._check_kv_divisible("draft", draft_spec)
        if draft_spec is None:
            k_spec = 0
        # the verify chunk writes through pos + k: never past the
        # sequence cap (k_eff clamps per slot; this bounds the ladder)
        self._spec_k = max(0, min(k_spec, self.max_seq_len - 2))
        self._draft_spec = draft_spec if self._spec_k else None
        if self._spec_k:
            self._verify_lanes = self._spec_k + 1
            # draft calls: C=1 singles, a <= 2-lane catch-up chunk
            # after a fully-accepted round, and the prefill chunks it
            # shadows
            self._draft_chunk_ladder = sorted(
                {1, 2, self._prefill_chunk})
            self._draft_params = self._place_params(
                draft_spec.seeded_arrays()
                if draft_params is None
                else draft_params)  # guarded-by: _step_mu
            self._draft_cache = PagedKvCache(
                draft_spec.n_layers, draft_spec.n_kv_heads,
                draft_spec.head_dim, page_size=ps, num_pages=npages,
                dtype=draft_spec.pool_dtype,
                allocator=self.cache.allocator,
                mesh=self._mesh,
                shard_spec=self._pool_spec())  # guarded-by: _step_mu
        else:
            self._verify_lanes = 0
            self._draft_chunk_ladder = []
            self._draft_params = None  # guarded-by: _step_mu
            self._draft_cache = None  # guarded-by: _step_mu
        # embeddings/scoring lane (ISSUE 20): opt-in because it warms
        # its own all-lane compiled family (slots x widths x chunks) —
        # engines that never score must not pay those compiles
        self._embed_on = bool(embeddings)
        self._cond = threading.Condition()
        self._queue: List[_DecodeRequest] = []  # guarded-by: _cond
        self._slots: List[_Slot] = []  # guarded-by: _cond
        self._embed_queue: List[_EmbedRequest] = []  # guarded-by: _cond
        self._embed_slots: List[_EmbedSlot] = []  # guarded-by: _cond
        self._stopping = False  # guarded-by: _cond
        self._released = False  # guarded-by: _cond
        self._seq_counter = 0  # guarded-by: _cond
        self._n_requests = 0  # guarded-by: _cond
        self._n_steps = 0  # guarded-by: _cond
        # scheduler-thread only: when the round in progress began on the
        # host clock — where the last one ended, or where a wait for
        # work did (serving.decode.sched_ms)
        self._t_round = 0.0
        # scheduler-thread only: the round's seconds in step_ms's stretch
        # and in waits for a round's ids, which sched_ms leaves out
        self._waited = 0.0
        # scheduler-thread only: when the newest dispatched step's ids
        # came to the host, or where a wait for work ended; None while
        # the device holds a step whose ids the host has not collected
        # (serving.decode.host_gap_ms)
        self._t_back: Optional[float] = 0.0
        self._compiled_shapes: set = set()  # guarded-by: _step_mu
        self._g_depth = _metrics.gauge(
            f"serving.decode.queue_depth.{self.name}.v{self.version}")
        # per-instance for the same reason as queue_depth: a draining
        # old version must not clobber the live engine's value
        self._g_live = _metrics.gauge(
            f"serving.decode.live_slots.{self.name}.v{self.version}")
        # embed occupancy is its OWN gauge: embeddings completing with
        # live_slots untouched is the zero-decode-slot proof
        self._g_embed = _metrics.gauge(
            f"serving.decode.embed_slots.{self.name}.v{self.version}")

        import jax

        spec_ref = spec  # closed over; jit retraces only on shape change
        impl = self._attention_impl

        # every program that makes logits a token is chosen from also
        # chooses it (choose_tokens, ISSUE 29) and hands back (pools,
        # ids, logits): the scheduler fetches the ids, and a logits row
        # only where a request needs the host (_fetch_row)
        def _step(params, tokens, positions, q_lens, k_pool, v_pool,
                  tables, lens, temperature, seed):
            k, v, logits, aux = spec_ref.step(
                params, tokens, positions, q_lens, k_pool,
                v_pool, tables, lens, attention_impl=impl,
                garbage_page=GARBAGE_PAGE)
            # lens (the keys including this chunk) is the new token's
            # absolute index in its sequence: the position of the draw
            ids = choose_tokens(logits, temperature, seed, lens)
            # what else a model's pass reports (per-expert counts) rides
            # beside the ids, as a block program's does
            return k, v, ({"ids": ids, **aux} if aux else ids), logits

        def _block_step(params, tokens, positions, q_lens, k_pool,
                        v_pool, tables, lens, temperature, seed, masked,
                        n_unmask):
            # a block model's one program (ISSUE 30): prefill chunks,
            # denoise and commit passes slot by slot; the choice, its
            # confidence and the lanes to unmask are made here, and what
            # goes to the host is [slots, B] of each and the model's
            # per-expert counts
            k, v, logits, aux = spec_ref.step(
                params, tokens, positions, q_lens, k_pool,
                v_pool, tables, lens, attention_impl=impl,
                garbage_page=GARBAGE_PAGE)
            x0, conf, unmask = choose_block(
                logits, temperature, seed,
                positions[:, :logits.shape[1]], masked, n_unmask)
            return k, v, {"ids": x0, "confidence": conf,
                          "unmask": unmask, **aux}, logits

        # donate the pools on TPU so XLA updates the KV pages in place
        # (HBM footprint stays the preallocated pool); CPU ignores
        # donation, so skip it there to avoid per-call warnings
        donate = (bool(FLAGS["donate_state"])
                  and jax.default_backend() == "tpu")
        self._donate = donate
        jit_kw: Dict[str, Any] = {"donate_argnums": (4, 5) if donate else ()}
        if self._mesh is not None:
            # pin the step outputs: pools keep the kv-head sharding they
            # came in with, the ids and the logits come back replicated
            # (the scheduler fetches the ids, and a logits row where a
            # request is answered host-side; the embed program's hidden
            # states replicate like logits, pooling and scoring being
            # host-side, so the same pin of two pools and two replicated
            # outputs fits it). Without the pin GSPMD may choose a
            # different output layout per shape and the next step's
            # input sharding drift would mint a post-warm compile.
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as _P

            pool_sh = NamedSharding(self._mesh, self._pool_spec())
            replicated = NamedSharding(self._mesh, _P())
            jit_kw["out_shardings"] = (pool_sh, pool_sh, replicated,
                                       replicated)

        def program(body, **what) -> _Program:
            # how a body becomes a program, once: the jitted function
            # keeps the body's name, which every device operation's
            # op_name and the persistent compile cache's keys carry
            return _Program(jax.jit(body, **jit_kw), **what)

        programs = {"target": program(
            _block_step if self._block > 1 else _step,
            masked=self._block > 1)}
        if self._spec_k:
            draft_ref = self._draft_spec

            def _verify(params, tokens, positions, q_lens, k_pool,
                        v_pool, tables, lens, temperature, seed):
                import jax.numpy as jnp

                k, v, logits, _aux = spec_ref.step(
                    params, tokens, positions, q_lens, k_pool,
                    v_pool, tables, lens, all_lanes=True,
                    attention_impl=impl, garbage_page=GARBAGE_PAGE)
                b, c, vocab = logits.shape
                # a lane is a row of its own: the slot's temperature
                # and seed, and the position after the lane's own
                ids = choose_tokens(
                    logits.reshape(b * c, vocab),
                    jnp.repeat(temperature, c), jnp.repeat(seed, c),
                    (positions + 1).reshape(b * c)).reshape(b, c)
                return k, v, ids, logits

            def _draft(params, tokens, positions, q_lens, k_pool,
                       v_pool, tables, lens, temperature, seed):
                k, v, logits, _aux = draft_ref.step(
                    params, tokens, positions, q_lens,
                    k_pool, v_pool, tables, lens, attention_impl=impl,
                    garbage_page=GARBAGE_PAGE)
                return k, v, choose_tokens(logits, temperature, seed,
                                           lens), logits

            # the speculative-verify target call: same pools, all-lane
            # logits [B, C, vocab] (C = spec_k + 1) and the choice at
            # every lane, ids [B, C] (lane j's is the token at
            # positions[:, j] + 1) — one target step scores every
            # proposal plus the bonus position
            programs["verify"] = program(_verify)
            # one DRAFT step (propose singles, catch-up chunks, prefill
            # shadowing) against the mirrored draft pool — same page
            # tables as the target, newest-lane (ids, logits) like the
            # plain step's
            programs["draft"] = program(_draft, draft=True,
                                        steps=_m_draft_steps)
        if self._embed_on:
            def _embed(params, tokens, positions, q_lens, k_pool,
                       v_pool, tables, lens):
                k, v, logits, aux = spec_ref.step(
                    params, tokens, positions, q_lens, k_pool, v_pool,
                    tables, lens, all_lanes=True, return_hidden=True,
                    attention_impl=impl, garbage_page=GARBAGE_PAGE)
                return k, v, logits, aux["hidden"]

            # one EMBED step (ISSUE 20): the all-lane + hidden form
            # against the shared target pool — every prompt lane's
            # logits [B, C, vocab] (per-token scoring) and final-norm
            # hidden states [B, C, d_model] (pooling) in one call
            programs["embed"] = program(_embed, steps=_m_embed_steps,
                                        sampled=False)
        # the program table: tag -> _Program. A second entry is what
        # makes the compiled-shape keys carry their tag (_run)
        self._programs = programs  # guarded-by: _step_mu
        # one logits row (or one slot's lanes) to the host, for the
        # requests whose token is chosen there: ONE program a logits
        # shape with the row index dynamic, so that which slot asks
        # compiles nothing (warm() runs each; _fetch_row counts them)
        self._row_fn = jax.jit(
            lambda logits, i: jax.lax.dynamic_index_in_dim(
                logits, i, 0, keepdims=False))  # guarded-by: _step_mu
        self._row_shapes: set = set()  # guarded-by: _step_mu
        # the shapes the device feed (feed_tokens) has been called at,
        # counted as the row fetch's are
        self._feed_shapes: set = set()  # guarded-by: _step_mu
        # the newest dispatched round, not yet answered: the next round is
        # planned and dispatched before it is (_step); the scheduler
        # thread's own
        self._pending: Optional[_Round] = None
        # serializes warm() (caller thread) against live steps (the
        # scheduler thread): read-pools -> step -> rebind must be
        # atomic or concurrent rebinds silently drop KV writes
        self._step_mu = threading.Lock()
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"decode-{self.name}-v{self.version}")
        self._thread.start()
        if warm:
            try:
                self.warm()
            except BaseException:
                # failed warm is the registry's rollback path: the
                # scheduler thread (and the params/pools it pins) must
                # not outlive the failed deploy
                self.stop(drain=False)
                raise

    # -- public surface ---------------------------------------------------
    @property
    def slot_ladder(self) -> List[int]:
        return list(self._slot_ladder)

    @property
    def table_width_ladder(self) -> List[int]:
        return list(self._width_ladder)

    @property
    def prefill_chunk(self) -> int:
        return self._prefill_chunk

    @property
    def prefill_budget(self) -> int:
        return self._prefill_budget

    @property
    def chunk_ladder(self) -> List[int]:
        return list(self._chunk_ladder)

    @property
    def hbm_bytes(self) -> int:
        """The preallocated KV budget of every kind's pools (fixed at
        construction; a retired engine still says what it held)."""
        return self._hbm_bytes

    @property
    def window_cache(self) -> Optional[PagedKvCache]:
        """The window kind's cache (None: every layer is full)."""
        return self._wcache

    @property
    def state_cache(self) -> Optional[SlotStateCache]:
        """The state kind's arrays (None: the model has no state layer)."""
        return self._scache

    @property
    def spec_k(self) -> int:
        """Draft proposals per decoding slot per round (0 = speculation
        off — no draft loaded, bit-identical non-speculative decode)."""
        return self._spec_k

    @property
    def draft_spec(self) -> Optional[DecoderSpec]:
        return self._draft_spec

    @property
    def mesh_spec(self):
        """The MeshSpec this engine spans (None = single-chip)."""
        return self._mesh_spec

    def _place_params(self, tree):
        """Put a param tree (host numpy or jax arrays) where this
        engine computes: each leaf straight onto its shards of the mesh
        by its name-matched rule, or onto the one default device."""
        if self._mesh is not None:
            from ..mesh import shard_param_tree

            return shard_param_tree(tree, self._mesh, self._mesh_rules)
        import jax

        return jax.device_put(tree)

    @staticmethod
    def _kv_pool_axes(rules):
        """The mesh axes sharding the KV-HEAD dim of the paged pool:
        whatever the rules put on the COLUMN dim of the K projection
        (wk's columns reshape to [kv_heads, head_dim], so a tp-sharded
        wk writes tp-sharded kv heads — the pool must shard the same
        way or every step pays a reshard)."""
        spec = tuple(rules.spec_for("layer0/wk", 2))
        entry = spec[1] if len(spec) > 1 else None
        if entry is None:
            return None
        return entry if isinstance(entry, tuple) else (str(entry),)

    def _kv_shard_degree(self) -> int:
        if not self._kv_head_axes:
            return 1
        import numpy as _np

        for a in self._kv_head_axes:
            # typed here: axis_size would KeyError from deep inside
            # construction, breaking the ValueError discipline every
            # other load_decoder misconfiguration follows
            if a not in self._mesh_spec:
                raise ValueError(
                    f"decoder rules shard kv heads over axis {a!r}, "
                    f"which mesh {self._mesh_spec} does not have — add "
                    "the axis or pass matching mesh_rules")
        return int(_np.prod([self._mesh_spec.axis_size(a)
                             for a in self._kv_head_axes]))

    def _check_kv_divisible(self, what: str, spec: DecoderSpec):
        deg = self._kv_shard_degree()
        if deg > 1 and spec.n_kv_heads % deg:
            raise ValueError(
                f"{what} decoder has {spec.n_kv_heads} kv heads, not "
                f"divisible by the mesh kv-head shard degree {deg} "
                f"(axes {self._kv_head_axes} of {self._mesh_spec}) — "
                "resize the mesh or the model's kv heads")

    def _pool_spec(self):
        """PartitionSpec of the paged pools ([layers, pages, page_size,
        kv_heads, head_dim] — kv-head axis sharded, the rest
        replicated); None when unsharded."""
        if self._mesh is None:
            return None
        import jax.sharding as _shd

        ax = self._kv_head_axes
        return _shd.PartitionSpec(
            None, None, None,
            (ax if ax is None or len(ax) > 1 else ax[0]), None)

    def warm(self):
        """Pre-compile EVERY (slot-count, table-width, chunk) triple on
        an all-dead synthetic batch (writes land on the garbage page).
        After this, sequence churn at ragged lengths — prefill chunks
        included — compiles nothing: all three padded dimensions only
        ever take ladder values. With a speculative draft attached
        (ISSUE 14) the chunk ladder grows its ``spec_k + 1`` VERIFY
        entry (the all-lane-logits form) and the draft's own compiled
        ladder ({1, 2, chunk} — singles, the post-full-accept catch-up
        chunk, and the prefill chunks it shadows) warms alongside, so a
        speculative churn still performs zero post-warm compiles.
        Every program chooses its tokens itself (all-greedy here: the
        sampling arrays are data, not shape), and the row fetch of the
        host route warms once a slot count. The device feed of a step
        that goes ahead of the one before it (``feed_tokens``) warms for
        every pair of the two calls' (slots, chunk) buckets, and every
        target triple runs again with its tokens (a block model's masked
        lanes too) from that feed, the form such a step hands them in."""
        with _tracing.span("serving.decode.warmup", model=self.name,
                           version=self.version):
            calls: Dict[Tuple[int, int], _Round] = {}
            for s in self._slot_ladder:
                for w in self._width_ladder:
                    def dead(c):
                        return self._build_arrays((), (), s, c, w)

                    for c in self._chunk_ladder:
                        arrays = dead(c)
                        out, logits = self._run_step_arrays(*arrays)
                        calls[s, c] = call = _Round([], [])
                        call.dev = out if isinstance(out, dict) \
                            else {"ids": out}
                        call.fed = (arrays[0], self._dead_masked(s))
                    if self._spec_k:
                        _ids, lanes = self._run(
                            "verify", *dead(self._verify_lanes))
                        for c in self._draft_chunk_ladder:
                            self._run("draft", *dead(c))
                    if self._embed_on:
                        # the embed lane's all-lane+hidden family warms
                        # over the same triples — a mixed churn of
                        # generate + embeddings compiles nothing
                        for c in self._chunk_ladder:
                            self._run("embed", *dead(c))
                # the host route's row fetch: one program a logits
                # shape, [s, vocab] whatever the width and the chunk
                # (the draft's logits share it: its vocab is the
                # target's) and the verify's [s, lanes, vocab]; a block
                # model's [s, B, vocab] for a first pass's first_topk
                self._fetch_row(logits, 0)
                if self._spec_k:
                    self._fetch_row(lanes, 0)
            # a block round carries on from a round that was host-built or
            # itself fed from the device: both forms of the inputs it
            # reads, so a second sweep feeds from the first one's outputs
            fed = {}
            for _sweep in range(2 if self._block > 1 else 1):
                for (s, c), call in calls.items():
                    src = np.full(s, -1, np.int32)
                    for prev in calls.values():
                        fed[s, c] = self._feed(call.fed[0], src, prev,
                                               self._dead_masked(s))
                for key, call in calls.items():
                    call.fed = fed[key]
            for (s, c), (tokens, masked) in fed.items():
                for w in self._width_ladder:
                    arrays = self._build_arrays((), (), s, c, w)
                    self._run_step_arrays(tokens, *arrays[1:],
                                          **({} if masked is None
                                             else {"masked": masked}))

    def _dead_masked(self, rows: int):
        """A block program's ``masked`` for a call of ``rows`` dead rows
        (None for a causal model, whose program takes none)."""
        return (np.zeros((rows, self._block), bool) if self._block > 1
                else None)

    def submit(self, prompt: Sequence[int], max_new_tokens: int = 16,
               deadline_ms: Optional[float] = None,
               temperature: float = 0.0, top_k: int = 0,
               seed: int = 0, mask: Optional[Any] = None,
               topk_first: int = 0,
               denoise_steps: Optional[int] = None) -> _DecodeRequest:
        """Validate + reserve KV pages + enqueue. All refusals are
        synchronous and typed: ``ServerOverloaded`` (queue full OR page
        pool exhausted), ``RequestTooLarge`` (can't ever fit),
        ``EngineRetired``, ``ValueError`` (bad tokens / bad sampling
        params). ``temperature``/``top_k``/``seed`` select the sampling
        policy per request (0.0 = greedy; ``choose_tokens`` inside the
        step's program over the full vocabulary, ``sample_token`` on
        the host for ``top_k`` > 0).

        ``mask`` (ISSUE 20) constrains generation to a
        ``TokenMaskSpec`` language (spec object or its wire dict): the
        automaton's allowed-set zeroes disallowed logits BEFORE the
        per-(seed, position) choice, so constrained output is exactly
        as deterministic and batch-composition-independent as
        unconstrained. The sequence finishes early when the automaton
        has no further transition. ``topk_first`` asks for the first
        generated position's top-k token order in the result
        (``first_topk``) — the beam fork point.

        ``denoise_steps`` (ISSUE 30) is a block model's field, as
        ``temperature`` is any model's: the denoise passes a block of B
        tokens takes, each unmasking ``B / denoise_steps`` lanes (default
        B: one lane a pass; it must divide B). Such a model answers up to
        B tokens at a time, and refuses ``top_k`` and ``mask``, which
        choose on the host from one causal logits row."""
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        bl = self._block
        if bl == 1:
            if denoise_steps is not None:
                raise ValueError(
                    "'denoise_steps' is a block model's field; decoder "
                    f"'{self.name}' is causal (block_length 1)")
            denoise_steps = 1
        else:
            denoise_steps = bl if denoise_steps is None \
                else int(denoise_steps)
            if denoise_steps < 1 or bl % denoise_steps:
                raise ValueError(
                    f"denoise_steps must divide the block length {bl}, "
                    f"got {denoise_steps}")
            for field, given in (("top_k", int(top_k) > 0),
                                 ("mask", mask is not None)):
                if given:
                    raise ValueError(
                        f"'{field}' chooses on the host from one causal "
                        f"logits row; decoder '{self.name}' generates by "
                        f"diffusion over blocks of {bl}")
        if int(prompt.min()) < 0 or int(prompt.max()) >= self.spec.vocab:
            raise ValueError(
                f"prompt token ids must be in [0, {self.spec.vocab})")
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # a block model writes whole blocks: the last one to its end
        total = -(-(int(prompt.size) + max_new) // bl) * bl
        if total > self.max_seq_len:
            raise RequestTooLarge(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new}) = "
                f"{total} exceeds max_seq_len {self.max_seq_len}")
        if self._reservation == "demand" and \
                self.cache.allocator.pages_for_tokens(total) > \
                self.cache.num_pages - 1:
            # demand mode admits beyond the worst case, so the ONLY
            # hard bound is "could this sequence fit even alone, with
            # everyone else preempted" — refuse up front if not (the
            # growth path's progress guarantee depends on it)
            raise RequestTooLarge(
                f"worst case {total} tokens = "
                f"{self.cache.allocator.pages_for_tokens(total)} pages "
                f"exceeds the whole pool "
                f"({self.cache.num_pages - 1} usable pages)")
        temperature = float(temperature)
        top_k = int(top_k)
        if temperature < 0.0 or not math.isfinite(temperature):
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        topk_first = int(topk_first)
        if topk_first < 0 or topk_first > self.spec.vocab:
            raise ValueError(
                f"topk_first must be in [0, {self.spec.vocab}], got "
                f"{topk_first}")
        automaton = None
        if mask is not None:
            from .workloads.masks import MaskAutomaton, TokenMaskSpec

            if isinstance(mask, dict):
                mask = TokenMaskSpec.from_dict(mask)
            if isinstance(mask, TokenMaskSpec):
                automaton = mask.compile()
            elif isinstance(mask, MaskAutomaton):
                automaton = mask
            else:
                raise ValueError(
                    f"mask must be a TokenMaskSpec, its wire dict, or "
                    f"a MaskAutomaton, got {type(mask).__name__}")
            if automaton.max_token() >= self.spec.vocab:
                raise ValueError(
                    f"mask names token id {automaton.max_token()}, "
                    f"outside this decoder's vocab "
                    f"[0, {self.spec.vocab})")
            if not automaton.allowed(automaton.start,
                                     self.spec.vocab).any():
                raise ValueError("mask allows no first token")
        deadline = (None if deadline_ms is None
                    else time.monotonic() + float(deadline_ms) / 1e3)
        with self._cond:
            if self._stopping:
                raise EngineRetired(
                    f"decoder '{self.name}' v{self.version} is retiring")
            if len(self._queue) >= self._max_queue:
                _m_overloads.inc()
                raise ServerOverloaded(
                    f"decoder '{self.name}' queue is full "
                    f"({self._max_queue} deep)")
            self._seq_counter += 1
            seq_id = self._seq_counter
            try:
                # reserve NOW: worst_case mode takes the whole
                # prompt+max_new bound (an admitted sequence can then
                # never die of exhaustion); demand mode takes only the
                # prompt plus a small decode headroom — growth and
                # preemption own the tail (ISSUE 13). Either way the
                # pool is the admission bound (kv_cache.py) and the
                # refusal is typed and side-effect-free.
                res = self._reserve_locked(seq_id, prompt, total)
            except ServerOverloaded:
                _m_overloads.inc()
                raise
            req = _DecodeRequest(prompt, max_new, deadline, seq_id,
                                 temperature=temperature, top_k=top_k,
                                 seed=seed, mask=automaton,
                                 want_topk=topk_first,
                                 denoise_steps=denoise_steps)
            req.cached_tokens = res["cached_tokens"]
            req.cow = res["cow"]
            self._queue.append(req)
            self._n_requests += 1
            self._g_depth.set(len(self._queue))
            # instantaneous concurrency demand — what slots="auto"
            # derives its ladder from (observed outside the lock)
            demand = len(self._queue) + len(self._slots)
            self._cond.notify()
        _observe_shape("decode_slots", demand)
        # the prompt-length histogram the prefill_chunk tuner derives
        # its crossover from (bench sessions seed it, ISSUE 10)
        _observe_shape("prefill_chunk", int(prompt.size))
        _m_requests.inc()
        return req

    def generate(self, prompt: Sequence[int], max_new_tokens: int = 16,
                 deadline_ms: Optional[float] = None,
                 timeout: float = 300.0, temperature: float = 0.0,
                 top_k: int = 0, seed: int = 0,
                 mask: Optional[Any] = None,
                 topk_first: int = 0,
                 denoise_steps: Optional[int] = None) -> Dict[str, Any]:
        """Blocking convenience: submit + wait. Returns
        ``{"tokens": [...], "prompt_len": n, "version": v,
        "steps_to_first_token": k}``.
        ``temperature``/``top_k``/``seed`` thread through to the
        per-request sampler (0.0 = greedy, the default);
        ``mask``/``topk_first`` to the workload layer (ISSUE 20)."""
        req = self.submit(prompt, max_new_tokens, deadline_ms=deadline_ms,
                          temperature=temperature, top_k=top_k, seed=seed,
                          mask=mask, topk_first=topk_first,
                          denoise_steps=denoise_steps)
        if not req.ev.wait(timeout):
            # withdraw before raising: an abandoned sequence must not
            # keep its page reservation or burn further decode steps.
            # cancel() returning False means the request finished in
            # the wait-vs-cancel window — deliver that result, don't
            # discard paid-for tokens as a timeout
            if self.cancel(req):
                raise ServingError(
                    f"generate on '{self.name}' timed out after "
                    f"{timeout}s (decode scheduler wedged?)")
        if req.error is not None:
            raise req.error
        return req.result

    @property
    def embeddings_enabled(self) -> bool:
        return self._embed_on

    @property
    def prefix_cache_enabled(self) -> bool:
        return self._prefix_on

    def submit_embed(self, prompt: Sequence[int],
                     deadline_ms: Optional[float] = None
                     ) -> _EmbedRequest:
        """Enqueue a prompt-only embedding/scoring request (ISSUE 20).
        Reservation is the reserve-at-admission math with
        ``max_new = 0``: exactly the prompt's pages, taken NOW, typed
        ``ServerOverloaded`` on refusal. The request rides the chunked
        prefill path in the embed lane and never holds a decode
        slot."""
        if not self._embed_on:
            raise ServingError(
                f"decoder '{self.name}' was loaded without "
                "embeddings=True — the embed lane's compiled shapes "
                "are not warmed")
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if int(prompt.min()) < 0 or int(prompt.max()) >= self.spec.vocab:
            raise ValueError(
                f"prompt token ids must be in [0, {self.spec.vocab})")
        if int(prompt.size) > self.max_seq_len:
            raise RequestTooLarge(
                f"prompt ({prompt.size}) exceeds max_seq_len "
                f"{self.max_seq_len}")
        deadline = (None if deadline_ms is None
                    else time.monotonic() + float(deadline_ms) / 1e3)
        with self._cond:
            if self._stopping:
                raise EngineRetired(
                    f"decoder '{self.name}' v{self.version} is retiring")
            if len(self._embed_queue) >= self._max_queue:
                _m_overloads.inc()
                raise ServerOverloaded(
                    f"decoder '{self.name}' embed queue is full "
                    f"({self._max_queue} deep)")
            self._seq_counter += 1
            seq_id = self._seq_counter
            try:
                self.cache.allocator.alloc(seq_id, int(prompt.size))
            except ServerOverloaded:
                _m_overloads.inc()
                raise
            req = _EmbedRequest(prompt, deadline, seq_id,
                                self.spec.d_model)
            self._embed_queue.append(req)
            self._n_requests += 1
            self._cond.notify()
        _observe_shape("prefill_chunk", int(prompt.size))
        _m_embed_requests.inc()
        return req

    def embed(self, prompt: Sequence[int],
              deadline_ms: Optional[float] = None,
              timeout: float = 300.0) -> Dict[str, Any]:
        """Blocking convenience: submit_embed + wait. Returns
        ``{"embedding": [d_model floats] (mean-pooled final hidden
        states), "logprobs": [P-1 floats] (position p scores
        prompt[p+1]), "prompt_len": P, "version": v, "steps": n}``."""
        req = self.submit_embed(prompt, deadline_ms=deadline_ms)
        if not req.ev.wait(timeout):
            if self.cancel(req):
                raise ServingError(
                    f"embed on '{self.name}' timed out after "
                    f"{timeout}s (decode scheduler wedged?)")
        if req.error is not None:
            raise req.error
        return req.result

    def cancel(self, req: _DecodeRequest,
               msg: str = "abandoned by caller") -> bool:
        """Withdraw a submitted request whose waiter gave up: frees its
        KV pages now and fails it, so the scheduler drops the slot at
        the next answer phase instead of decoding dead work to
        completion. A step already in flight still writes through the
        page table it captured BEFORE the free — safe today because a
        re-allocated page's every position is rewritten by its new
        owner in the same step that first attends to it
        (write-before-attend); the NEXT table build degrades the
        canceled row to the garbage page. Returns False if the
        request already finished."""
        with self._cond:
            if req.ev.is_set():
                return False
            if isinstance(req, _EmbedRequest):
                if req in self._embed_queue:
                    self._embed_queue.remove(req)
            elif req in self._queue:
                self._queue.remove(req)
                self._g_depth.set(len(self._queue))
            _m_cancels.inc()
            self._fail_locked(req, ServingError(
                f"generate on '{self.name}' canceled: {msg}"))
            self._cond.notify_all()
            return True

    def stream_tokens(self, req: _DecodeRequest, offset: int,
                      timeout: float = 30.0) -> Dict[str, Any]:
        """Incremental token read for streaming generate (ISSUE 12):
        block until the sequence has tokens past ``offset`` (or it
        finished / failed / the wait lapses), then return everything
        past it. A PURE FUNCTION of (request state, offset) — it never
        advances hidden cursor state — which is what makes a
        retransmitted stream frame safe to answer from the dedup cache
        OR by re-execution: either way the client gets exactly the
        tokens at those offsets, with zero extra decode steps.

        Returns ``{"tokens", "offset", "next_offset", "done"}`` plus
        ``"result"`` once done; a failed request re-raises its typed
        error (DeadlineExceeded, EngineRetired, ...). A timeout with no
        new tokens returns an empty chunk with ``done=False`` — the
        caller polls again."""
        offset = int(offset)
        if offset < 0:
            raise ValueError(f"stream offset must be >= 0, got {offset}")
        deadline = time.monotonic() + float(timeout)
        with self._cond:
            while len(req.produced) <= offset and not req.ev.is_set():
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                # lint: allow-blocking — a bounded reader wait on the
                # engine's own condition; the answer phase notifies on
                # every step that produced a token
                self._cond.wait(remaining)
            toks = [int(t) for t in req.produced[offset:]]
            done = req.ev.is_set()
            err = req.error
            result = req.result
        if done and err is not None:
            raise err
        out: Dict[str, Any] = {"tokens": toks, "offset": offset,
                               "next_offset": offset + len(toks),
                               "done": done}
        if done:
            out["result"] = result
        return out

    def set_max_queue(self, n: int):
        with self._cond:
            self._max_queue = max(1, int(n))

    def stop(self, drain: bool = True, timeout: float = 300.0):
        """Refuse new work; ``drain`` completes every admitted AND
        queued sequence first (the hot-swap drain guarantee), else all
        are failed with EngineRetired. Then params/pools/compiled steps
        are dropped so retirement releases the executables and HBM."""
        with self._cond:
            self._stopping = True
            if not drain:
                for r in self._queue:
                    self._fail_locked(r, EngineRetired(
                        f"decoder '{self.name}' v{self.version} unloaded"))
                self._queue.clear()
                for r in self._embed_queue:
                    self._fail_locked(r, EngineRetired(
                        f"decoder '{self.name}' v{self.version} unloaded"))
                self._embed_queue.clear()
                for s in self._embed_slots:
                    if not s.req.ev.is_set():
                        self._fail_locked(s.req, EngineRetired(
                            f"decoder '{self.name}' v{self.version} "
                            "unloaded"))
                    else:
                        self._free_pages(s.req.seq_id)
                self._embed_slots = []
                for s in self._slots:
                    # a slot _complete()d mid-step may still be in
                    # _slots (removal happens under _cond after the
                    # step) — never overwrite a delivered result
                    if not s.req.ev.is_set():
                        self._fail_locked(s.req, EngineRetired(
                            f"decoder '{self.name}' v{self.version} "
                            "unloaded"))
                    else:
                        self._free_pages(s.req.seq_id)
                self._slots = []
                self._g_depth.set(0)
            self._cond.notify_all()
        self._thread.join(timeout)
        if self._thread.is_alive():  # pragma: no cover - wedged scheduler
            _log.error("decode scheduler for %s v%d did not exit in %.0fs",
                       self.name, self.version, timeout)
        # params/programs/pools drop under _step_mu — THEIR guard (guards-
        # lint finding: they used to drop under _cond while _run reads
        # them under _step_mu; safe only by join-ordering, which a
        # static model can't see and a future warm()-after-stop wouldn't
        # honor)
        with self._step_mu:
            self._params = None
            self._programs = {}
            self._row_fn = None
            self._draft_params = None
            if self._draft_cache is not None:
                # shared allocator: retire() is idempotent, the draft
                # pool's HBM frees with its own k/v drop
                self._draft_cache.release()
                self._draft_cache = None
            if self._wcache is not None:
                self._wcache.release()
            if self._scache is not None:
                self._scache.release_all()
            self.cache.release()
        # any spills that survived the drain (preempted sequences the
        # retirement failed) die with the engine — files included
        self._spill.clear()
        with self._cond:
            self._released = True
            self._g_depth.set(0)
            # the scheduler may exit between steps without a final
            # answer phase — a retired engine must not report phantom
            # live slots
            self._g_live.set(0)
            self._g_embed.set(0)

    def stats(self) -> Dict[str, Any]:
        # _compiled_shapes is _step_mu state: snapshot it under ITS lock
        # (guards-lint finding — sorted() here used to iterate the set
        # under _cond while the scheduler's _run add()ed to it under
        # _step_mu: a mid-iteration mutation raises
        # "Set changed size during iteration" on a stats scrape)
        with self._step_mu:
            shapes = sorted(self._compiled_shapes)
        with self._cond:
            return {
                "name": self.name,
                "version": self.version,
                "kind": self.kind,
                "spec": self.spec.to_dict(),
                "slots": list(self._slot_ladder),
                "table_widths": list(self._width_ladder),
                "prefill_chunk": self._prefill_chunk,
                "prefill_budget": self._prefill_budget,
                "chunk_ladder": list(self._chunk_ladder),
                "page_size": self.cache.page_size,
                "max_seq_len": self.max_seq_len,
                "continuous": self._continuous,
                "reservation": self._reservation,
                "spec_k": self._spec_k,
                "mesh": (dict(self._mesh_spec.axes)
                         if self._mesh_spec is not None else None),
                "attention_route": self._attention_routes,
                "experts_route": self._experts_route,
                "draft": (self._draft_spec.to_dict()
                          if self._draft_spec is not None else None),
                "prefix_cache": self._prefix_on,
                "prefix": self.cache.allocator.prefix_stats(),
                "spilled_sequences": self._spill.count(),
                "kv": self.cache.allocator.stats(),
                # layer kinds (ISSUE 34): the window kind's pool beside
                # the full kind's (None: every layer is full), and the
                # whole KV budget of both
                "kv_window": (self._wcache.allocator.stats()
                              if self._wcache is not None else None),
                "window": self._window,
                # state layers: the state kind's rows beside the pools
                "state": (self._scache.stats()
                          if self._scache is not None else None),
                "kv_hbm_bytes": self.hbm_bytes,
                "queue_depth": len(self._queue),
                "live": len(self._slots),
                "embeddings": self._embed_on,
                "embed_queue": len(self._embed_queue),
                "live_embed": len(self._embed_slots),
                "max_queue": self._max_queue,
                "requests": self._n_requests,
                "steps": self._n_steps,
                "compiled_shapes": shapes,
                "stopping": self._stopping,
            }

    # -- scheduler --------------------------------------------------------
    def _reserve_locked(self, seq_id: int, prompt, total: int
                        ) -> Dict[str, Any]:
        """One reservation under the engine's policy: demand = prompt
        pages + decode headroom (capped at the worst case), worst_case
        = everything. Prefix caching maps the cached chain read-only
        either way. Raises ``ServerOverloaded`` side-effect-free."""
        if self._reservation == "demand":
            reserve = min(total, len(prompt)
                          + self._headroom_pages * self.cache.page_size)
        else:
            reserve = total
        if self._prefix_on:
            return self.cache.allocator.alloc_prefix(seq_id, prompt,
                                                     reserve)
        self._alloc_kinds(seq_id, reserve)
        return {"cached_tokens": 0, "cow": None}

    def _alloc_kinds(self, seq_id: int, tokens: int, first_page: int = 0):
        """Reserve ``tokens`` in the full kind and, under a model with
        window layers, what the window kind needs of them from logical
        page ``first_page`` on: no more than the pages one sequence ever
        holds of it (``_wwidth``); what lies beyond is grown, and what
        falls behind given back, round by round (``_prepare``). A refusal
        by either leaves neither holding a page."""
        self.cache.allocator.alloc(seq_id, tokens)
        if self._wcache is not None:
            try:
                self._wcache.allocator.alloc(
                    seq_id, min(tokens, (first_page + self._wwidth)
                                * self.cache.page_size), first_page)
            except ServerOverloaded:
                self.cache.allocator.free(seq_id)
                raise

    def _free_pages(self, seq_id: int):
        """Return a sequence's pages of every kind (idempotent, as the
        allocator's ``free``)."""
        self.cache.allocator.free(seq_id)
        if self._wcache is not None:
            self._wcache.allocator.free(seq_id)
        if self._scache is not None:
            self._scache.release(seq_id)

    def _fail_locked(self, req: _DecodeRequest, err: BaseException):
        self._free_pages(req.seq_id)
        if req.cow is not None:
            # the COW source pin must not outlive the request (a pinned
            # entry is un-evictable)
            self.cache.allocator.release_cow(req.cow["key"])
            req.cow = None
        # a preempted request's host spill dies with it — cancel/
        # deadline/retirement mid-preemption leaks nothing
        self._spill.drop(req.seq_id)
        req.fail(err)

    def _drop_expired_locked(self, now: float):
        for queue in (self._queue, self._embed_queue):
            late = [r for r in queue
                    if r.deadline is not None and now > r.deadline]
            for r in late:
                _m_deadline_miss.inc()
                self._fail_locked(r, DeadlineExceeded(
                    f"request to decoder '{self.name}' missed its "
                    "deadline while queued"))
            if late:
                queue[:] = [r for r in queue if r not in late]
                self._g_depth.set(len(self._queue))

    def _admit_locked(self, ending: frozenset = frozenset()):
        """Move queued requests into free slots. Continuous mode admits
        whenever a slot is free — INTO the in-flight batch; drain mode
        (the bench baseline) only refills an empty batch. The slots in
        ``ending`` reach ``max_new`` in the round not yet answered: they
        ride no further round, so theirs count as free. A request
        whose reservation was surrendered (preempted victims sit at the
        queue FRONT, demoted reservations wherever they were) must
        re-reserve first; a refusal leaves it queued — completions and
        cache evictions free the pages it is waiting for."""
        riding = sum(1 for s in self._slots if s not in ending)
        if not self._continuous and riding:
            return
        while self._queue and riding < self._max_slots:
            req = self._queue[0]
            if req.ev.is_set():
                # canceled / expired while queued — already failed
                self._queue.pop(0)
                continue
            if req.needs_alloc:
                total = -(-(len(req.prompt) + req.max_new)
                          // self._block) * self._block
                try:
                    if req.resume_pos is not None:
                        # restore-before-step: cover what was spilled
                        # plus the decode headroom; prefix matching is
                        # deliberately NOT consulted — the spill is the
                        # bitwise truth (preempt-never-corrupts)
                        reserve = min(total, max(req.resume_pos, 1)
                                      + self._headroom_pages
                                      * self.cache.page_size)
                        self._alloc_kinds(req.seq_id, reserve,
                                          req.resume_wfirst)
                    else:
                        res = self._reserve_locked(req.seq_id,
                                                   req.prompt, total)
                        req.cached_tokens = res["cached_tokens"]
                        req.cow = res["cow"]
                except ServerOverloaded:
                    break
                req.needs_alloc = False
            if self._scache is not None:
                # a state row beside the pages; a slot that ends in the
                # round not yet answered rides no further round, so its
                # row may be handed on
                try:
                    self._scache.assign(req.seq_id, [s.req.seq_id
                                                     for s in ending])
                except ServerOverloaded:
                    break
            self._queue.pop(0)
            slot = _Slot(req,
                         self.cache.allocator.held_pages(req.seq_id))
            if self._wcache is not None:
                slot.wpages_held = self._wcache.allocator.held_pages(
                    req.seq_id)
                slot.wfirst = self._wcache.allocator.head(req.seq_id)
            if req.resume_pos is not None:
                slot.pos = req.resume_pos
                # the draft pool restores from the same spill; its
                # watermark resumes where preemption froze it
                slot.dpos = (req.resume_dpos
                             if req.resume_dpos is not None
                             else req.resume_pos)
                slot.pending_restore = True
                req.resume_pos = None
                req.resume_dpos = None
                req.resume_wfirst = 0
            else:
                # cached prompt pages are already written (and mapped):
                # prefill starts at the first uncached token — in BOTH
                # pools (the publisher's draft prefilled the same
                # pages; the COW copy below covers the tail likewise)
                slot.pos = req.cached_tokens
                slot.dpos = req.cached_tokens
            slot.steps = req.carry_steps
            slot.first_token_steps = req.carry_fts
            self._slots.append(slot)
            riding += 1
            _m_admitted.inc()
            _m_queue_wait.observe((time.monotonic() - req.t_enq) * 1e3)
        # embed admission: its own slot lane, capped by the same ladder
        # max — decode slots and live_slots are untouched. Reservation
        # happened at submit (the prompt's pages, never grown), so
        # admission is pure bookkeeping.
        while self._embed_queue and \
                len(self._embed_slots) < self._max_slots:
            ereq = self._embed_queue.pop(0)
            if ereq.ev.is_set():
                continue
            self._embed_slots.append(_EmbedSlot(
                ereq, self.cache.allocator.held_pages(ereq.seq_id)))
            _m_admitted.inc()
            _m_queue_wait.observe((time.monotonic() - ereq.t_enq) * 1e3)
        self._g_depth.set(len(self._queue))
        self._g_live.set(len(self._slots))
        self._g_embed.set(len(self._embed_slots))

    def _next_live(self, ending: Optional[frozenset]
                   ) -> Optional[Tuple[List[_Slot], List[_EmbedSlot]]]:
        # the admit span starts BEFORE the condition is taken (so it is
        # entered and left by hand): getting the condition back from
        # the clients the last step woke is the scheduler's time. The
        # round's host clock (_t_round) and the device's host gap
        # (_t_back) run on from the last round; only a wait for work,
        # below, restarts them. ``ending`` is None with no round waiting
        # for its answer; else the slots that round ends, and the
        # scheduler does not wait for work: it has that answer to give
        admit = _tracing.span("serving.decode.admit")
        admit.__enter__()
        # lint: allow-blocking — Condition.wait on the engine's own
        # condition is the scheduler's idle state by design
        with self._cond:
            while True:
                self._drop_expired_locked(time.monotonic())
                self._admit_locked(ending or frozenset())
                admit.__exit__(None, None, None)
                if self._slots or self._embed_slots:
                    return list(self._slots), list(self._embed_slots)
                if self._stopping and not self._queue \
                        and not self._embed_queue:
                    return None
                if ending is not None:
                    return [], []
                # no live slots here implies the queues are (almost
                # always) empty too — admission can't fail with every
                # slot free — so idle blocks untimed on submit()/stop()
                # notifies instead of polling 20x/s per loaded decoder;
                # the timed wait survives only for the defensive case
                # of a non-empty queue, whose deadlines need the poll
                self._cond.wait(0.05 if (self._queue
                                         or self._embed_queue)
                                else None)
                self._t_round = self._t_back = time.perf_counter()
                self._waited = 0.0
                admit = _tracing.span("serving.decode.admit")
                admit.__enter__()

    def _loop(self):  # lint: allow-unguarded(_t_round, _t_back) — this
        # thread's own clocks: nobody else reads or writes them
        self._t_round = self._t_back = time.perf_counter()
        while True:
            pend = self._pending
            nxt = self._next_live(None if pend is None
                                  else frozenset(pend.ends))
            if nxt is None:
                # retiring: the round still waiting is answered (a request
                # stop(drain=False) failed is dropped there)
                self._settle()
                return
            live, elive = nxt
            try:
                if live:
                    self._step(live)
                else:
                    self._settle()
                if elive:
                    # the embed lane runs AFTER the decode step each
                    # round: decode tokens never stall behind scoring,
                    # and a mixed churn interleaves the two lanes 1:1
                    self._embed_step(elive)
                    # the embed lane's time is no decode round's
                    self._t_round, self._waited = time.perf_counter(), 0.0
            except BaseException as e:  # a broken step fails ITS slots
                _log.error("decode step on %s v%d failed: %s: %s",
                           self.name, self.version, type(e).__name__, e)
                err = (e if isinstance(e, ServingError) else
                       ServingError(f"{type(e).__name__}: {e}"))
                # the round waiting for its answer goes with it: its
                # slots are failed below with the rest
                if pend is not None:
                    live = live + [s for s in pend.live if s not in live]
                self._pending = None
                with self._cond:
                    for s in live + elive:
                        if not s.req.ev.is_set():
                            self._fail_locked(s.req, err)
                    self._slots = [s for s in self._slots
                                   if s not in live]
                    self._embed_slots = [s for s in self._embed_slots
                                         if s not in elive]
                    self._g_live.set(len(self._slots))
                    self._g_embed.set(len(self._embed_slots))
                    if self._donate:
                        # the raising step already consumed the donated
                        # pools — k/v are deleted buffers and every
                        # later step would fail too. Retire: fail
                        # everything, refuse new submits (EngineRetired
                        # -> the server resubmits after a redeploy)
                        # instead of admitting doomed requests.
                        _log.error(
                            "decode pools for %s v%d were donated into "
                            "the failed step — retiring the engine",
                            self.name, self.version)
                        self._stopping = True
                        for s in self._slots + self._embed_slots:
                            if not s.req.ev.is_set():
                                self._fail_locked(s.req, err)
                        self._slots = []
                        self._embed_slots = []
                        for r in self._queue:
                            self._fail_locked(r, err)
                        self._queue.clear()
                        for r in self._embed_queue:
                            self._fail_locked(r, err)
                        self._embed_queue.clear()
                        self._g_depth.set(0)
                        self._g_live.set(0)
                        self._g_embed.set(0)
                        self._cond.notify_all()
                        return

    @staticmethod
    def _slot_sampling(slots: Sequence[_Slot], rows: int):
        """``(temperature [rows] float32, seed [rows] uint32)`` of a
        call's slots, what ``choose_tokens`` draws each one's token by;
        padded rows stay 0/0 and choose garbage nobody reads."""
        temperature = np.zeros(rows, np.float32)
        seed = np.zeros(rows, np.uint32)
        for i, s in enumerate(slots):
            temperature[i] = s.req.temperature
            seed[i] = s.req.seed & 0xFFFFFFFF
        return temperature, seed

    def _build_arrays(self, slots: Sequence[Any], feeds: Sequence[Any],
                      s_bucket: int, c_bucket: int, w_bucket: int,
                      tables=None):
        """THE arrays of one call, in the order every program takes
        them: ``(tokens [S, C], positions [S, C], q_lens [S], tables
        [S, W], lens [S])`` int32 at the three compiled buckets (``tables``
        a ``KindTables`` of both kinds' under a model with window layers:
        its ``shape`` is the full table's). Row i
        is ``slots[i]`` feeding ``feeds[i] = (start, tokens)``: those
        tokens at positions ``start ..``, ``q_lens`` how many, ``lens``
        the keys INCLUDING them (within the chunk, query j attends only
        keys up to its own position) — a prefill chunk, a decode token,
        a block pass, an embed chunk, the draft's catch-up and singles,
        the verify chunk alike. A ``None`` feed and the rows past
        ``slots`` are dead: all zero, written to nowhere; table columns
        past a sequence's pages (and a dead row's every column) name the
        garbage page. A feed that would write past its slot's
        reservation is refused. ``tables`` hands in those of an earlier
        call of the round over the same slots and buckets."""
        tokens = np.zeros((s_bucket, c_bucket), np.int32)
        starts = np.zeros(s_bucket, np.int32)
        q_lens = np.zeros(s_bucket, np.int32)
        for i, (s, feed) in enumerate(zip(slots, feeds)):
            if feed is None:
                continue
            start, fed = feed
            n = len(fed)
            tokens[i, :n] = fed
            starts[i] = start
            q_lens[i] = n
            self._check_reservation(s, start + n)
        # lane j of a row is position start + j, as far as the row feeds
        lanes = np.arange(c_bucket, dtype=np.int32)
        positions = np.where(lanes < q_lens[:, None],
                             starts[:, None] + lanes, np.int32(0))
        lens = starts + q_lens
        if tables is None:
            seq_ids = [s.req.seq_id for s in slots]
            tables = self.cache.table_array(seq_ids, w_bucket,
                                            rows=s_bucket)
            if self._wcache is not None:
                # the window kind's table beside it: a row holds the
                # pages from the window's first on (``starts`` says which
                # logical page that is) and is as wide as a window and a
                # chunk, or as the full table where that is narrower
                tables = KindTables(
                    tables, self._wcache.table_array(
                        seq_ids, min(w_bucket, self._wwidth),
                        rows=s_bucket),
                    self._wcache.allocator.table_starts(seq_ids, s_bucket))
            elif self._scache is not None:
                # each row's row of the state kind's arrays beside it, and
                # whether its sequence starts here (its state from zero)
                tables = StateTables(
                    tables, self._scache.rows_array(seq_ids, s_bucket),
                    ((starts == 0) & (q_lens > 0)).astype(np.int32))
        return tokens, positions, q_lens, tables, lens

    def _run(self, tag: str, tokens, positions, q_lens, tables, lens, *,
             temperature=None, seed=None, masked=None, n_unmask=None):
        """THE device call, shared by warm() and every live step: count
        a DISTINCT-shape compile, bump the program's step counter, run
        the jitted program ``tag`` of the table on ITS params and pool,
        rebind that pool, hand back the rest. Once the table holds a
        second program the shape keys carry the tag ('target' /
        'verify' / 'draft' / 'embed'), so the compiled families stay
        distinct in the same churn-pinned set; an engine with one
        program keeps bare (slots, width, chunk) triples — the two never
        mix in one set (stats() sorts it). The sampling arrays are data,
        not shape: all-greedy where the caller gives none (warm(), and a
        call whose choice nobody reads), as a block program's ``masked``
        has no lane masked."""
        with self._step_mu:
            prog = self._programs[tag]
            key = (len(tokens), tables.shape[1], tokens.shape[1])
            if len(self._programs) > 1:
                key = (tag,) + key
            if key not in self._compiled_shapes:
                self._compiled_shapes.add(key)
                _m_compiles.inc()
            prog.steps.inc()
            params, cache = ((self._draft_params, self._draft_cache)
                             if prog.draft else (self._params, self.cache))
            # a model with window or state layers takes and gives each
            # pool as the pair of its kinds (it has no draft)
            second = self._wcache or self._scache
            kinds = (cache,) if second is None else (cache, second)
            rows, args = len(tokens), ()
            if prog.sampled:
                args = ((np.zeros(rows, np.float32),
                         np.zeros(rows, np.uint32))
                        if temperature is None else (temperature, seed))
            if prog.masked:
                args += (np.zeros((rows, self._block), bool)
                         if masked is None else masked,
                         np.zeros(rows, np.int32)
                         if n_unmask is None else n_unmask)
            k, v = ((cache.k, cache.v) if len(kinds) == 1 else
                    (tuple(c.k for c in kinds), tuple(c.v for c in kinds)))
            k, v, *out = prog.fn(params, tokens, positions, q_lens, k, v,
                                 tables, lens, *args)
            if len(kinds) == 1:
                k, v = (k,), (v,)
            for c, ck, cv in zip(kinds, k, v):
                c.rebind(ck, cv)
            return tuple(out)

    def _run_step_arrays(self, tokens, positions, q_lens, tables, lens,
                         *, temperature=None, seed=None, masked=None,
                         n_unmask=None):
        """The TARGET program's entry point into ``_run``. Returns
        ``(ids [B] int32, logits [B, vocab])``, both on the device: the
        program's own choice for each slot's newest lane
        (``choose_tokens``) from ``[B]`` ``temperature`` float32 and
        ``seed`` uint32, at position ``lens`` (the new token's absolute
        index). The sampling arrays are keyword-only: the five
        positional arguments are the call's shapes, which the
        benchmark's traced runs log by position; it wraps this name on
        the instance and plants faults on the class, so the scheduler
        reaches it by attribute at every call. A block model's program
        (ISSUE 30) also takes ``masked [B, block]`` bool and ``n_unmask
        [B]`` int32 and its ``ids`` is a dict of device arrays: ``ids``,
        ``confidence``, ``unmask``, each ``[B, block]``, and what the
        model's pass reports (``expert_counts [layers, E]``)."""
        return self._run("target", tokens, positions, q_lens, tables,
                         lens, temperature=temperature, seed=seed,
                         masked=masked, n_unmask=n_unmask)

    def _feed(self, tokens, src, prev: _Round, masked=None):
        """``feed_tokens`` for a call whose rows ``src`` names carry on
        from ``prev``'s answer, which the host does not hold yet: what
        that call handed back and was fed, still on the device. Counted
        as a compiled shape the first time a shape is called, as the row
        fetch is (``warm()`` calls every one)."""
        args = (tokens, src, prev.dev["ids"])
        if masked is not None:
            args += (masked, prev.dev["unmask"]) + tuple(prev.fed)
        key = tuple(np.shape(a) for a in args)
        with self._step_mu:
            if key not in self._feed_shapes:
                self._feed_shapes.add(key)
                _m_compiles.inc()
            return _feed_program()(*args)

    def _row_async(self, logits, row: int):
        """Row ``row`` of a step's logits, its copy to the host started:
        ``[vocab]`` of the plain step's and the draft's, ``[lanes,
        vocab]`` of the verify's and a block model's. The host route's
        only transfer: one row, not the batch. Counted like a step shape
        (``serving.decode.compiles`` pins it after warm) but kept out of
        ``stats()``'s ``compiled_shapes``, which are the steps'."""
        with self._step_mu:
            if logits.shape not in self._row_shapes:
                self._row_shapes.add(logits.shape)
                _m_compiles.inc()
            out = self._row_fn(logits, np.int32(row))
        out.copy_to_host_async()
        return out

    def _fetch_row(self, logits, row: int) -> np.ndarray:
        """Row ``row`` of a step's logits on the host (``_row_async``)."""
        return np.asarray(self._row_async(logits, row))

    def _collect(self, rnd: _Round, sp=None):
        """Wait for a dispatched round's ids: on the host where a slot of
        it reads its choice (``rnd.out``), else only done on the device,
        so that at most two steps are ever queued. ``sp`` is the device
        call's span that waits, which records the per-expert counts'
        touched sets."""
        if rnd.dev is None:
            return
        if rnd.reads:
            rnd.out = {k: np.asarray(a) for k, a in rnd.dev.items()}
            if sp is not None and sp.live and "expert_counts" in rnd.out:
                sp.set_arg("moe_experts_touched", int(
                    (rnd.out["expert_counts"] > 0).sum()))
        else:
            rnd.dev["ids"].block_until_ready()

    def _release(self, rnd: _Round):
        """Let go of a collected round's device outputs: what the host
        needs of them is on the host, and a round that carries on from
        them was dispatched before they were collected."""
        if rnd.dev is None:
            return
        with _tracing.span("serving.decode.release"):
            t = time.perf_counter()
            rnd.dev = None
            rnd.fed = (None, None)
            _m_release_ms.observe((time.perf_counter() - t) * 1e3)

    def _settle(self):
        """Answer the round waiting for its answer with no step queued
        behind it: the round before one that cannot go ahead of it, the
        last before the engine runs out of work, or the round a
        preemption would take a slot of. Its ids are waited for, and from
        then on the device holds nothing until the next dispatch."""
        rnd, self._pending = self._pending, None
        if rnd is None:
            return
        t0 = time.perf_counter()
        with _tracing.span("serving.decode.wait"):
            self._collect(rnd)
        self._t_back = time.perf_counter()
        self._release(rnd)
        self._waited += time.perf_counter() - t0
        self._answer(rnd)

    def _waits_first(self, pend: _Round, live: List[_Slot]) -> bool:
        """Whether the round about to be planned must have ``pend``'s
        answer first, from what the slots carry: a token of it that the
        host chooses (``_draws_on_host``), a draft's propose/verify in
        it, or one about to be (a decoding slot, under a draft, whose
        proposals start from the token ``pend`` chooses)."""
        return pend.host or bool(pend.spec) or (self._spec_k and any(
            not s.req.ev.is_set() and s.pos >= len(s.req.prompt)
            and s.req.mask is None for s in live))

    @staticmethod
    def _due(s: _Slot, pend: Optional[_Round]) -> int:
        """The tokens a slot will have produced once every dispatched
        round is answered (``eos_id`` aside)."""
        return len(s.req.produced) + (
            pend.owed.get(id(s), 0) if pend is not None else 0)

    def _prepare(self, live: List[_Slot]
                 ) -> Tuple[List[_Slot], List[int]]:
        """Pre-step phase (scheduler thread, ISSUE 13): execute pending
        COW copies and preemption restores (device writes, batched,
        under ``_step_mu`` — the same serialization every pool touch
        gets), then grow demand-mode reservations to cover this step's
        grants, preempting/demoting when the pool runs dry. Under a model
        with window layers the window kind's pages are counted beside the
        full kind's: what fell behind the window goes back first, then
        either kind's growth is asked for, and a refusal by either is
        answered the same way. Returns the (possibly shrunk) live list
        and its grants."""
        cows: List[Tuple[int, int]] = []
        restores, wrestores, srestores = [], [], []
        spills: Dict[int, Any] = {}
        for s in live:
            if s.pending_restore:
                s.pending_restore = False
                # pop (disk-backed spills np.load) stays outside _cond
                spills[s.req.seq_id] = self._spill.pop(s.req.seq_id)
        with self._cond:
            # request state (cow, pages, spill ownership) is mutated by
            # cancel()/_fail_locked under _cond — read it under _cond
            # too, or a mid-window cancel hands us freed pages / a
            # half-released COW
            for s in live:
                if s.req.ev.is_set():
                    # canceled: pages already freed and any spill
                    # dropped; the popped arrays (if any) die here and
                    # the slot rides one last garbage-table step
                    continue
                spill = spills.get(s.req.seq_id)
                if spill is not None:
                    pages = self.cache.allocator.pages_of(s.req.seq_id)
                    if self._scache is not None:
                        # the pool's one array (no V), then the state row
                        restores.append((pages[:spill[0].shape[1]],
                                         (spill[0], None)))
                        srestores.append((
                            self._scache.row_of(s.req.seq_id), spill[1:]))
                    else:
                        restores.append((pages[:spill[0].shape[1]], spill))
                    if self._wcache is not None:
                        wpages = self._wcache.allocator.pages_of(
                            s.req.seq_id)
                        wrestores.append((wpages[:spill[2].shape[1]],
                                          spill[2], spill[3]))
                    _m_restores.inc()
                if s.req.cow is not None:
                    cows.append((s.req.cow["src"], s.req.cow["dst"]))
                    # released before the device copy runs: safe, the
                    # scheduler thread issues every device write, so an
                    # evicted-and-reused src page cannot be rewritten
                    # before copy_pages below reads it
                    self.cache.allocator.release_cow(s.req.cow["key"])
                    s.req.cow = None
        if cows or restores:
            with self._step_mu:
                self.cache.copy_pages(cows)
                if self._draft_cache is not None:
                    # the draft pool mirrors every page move: a COW
                    # tail or restored spill must be valid in BOTH
                    # pools before the slot's next step reads them
                    self._draft_cache.copy_pages(cows)
                for pages, spill in restores:
                    self.cache.scatter_pages(pages, spill[0], spill[1])
                    if self._draft_cache is not None and len(spill) == 4:
                        self._draft_cache.scatter_pages(
                            pages, spill[2], spill[3])
                for wpages, wk, wv in wrestores:
                    self._wcache.scatter_pages(wpages, wk, wv)
                for row, values in srestores:
                    self._scache.scatter_row(row, values)
        pages_for = self.cache.allocator.pages_for_tokens
        while True:
            grants = self._grants(live)
            grower = None
            for s, g in zip(live, grants):
                if s.req.ev.is_set():
                    continue  # canceled: pages gone, rides one last
                    # step through the garbage table, answered nowhere
                need = pages_for(s.pos + g)
                if need > s.pages_held:
                    grower = (s, need - s.pages_held, False)
                    break
                if self._wcache is not None:
                    # the window kind, in THIS round: the pages every key
                    # of which lies behind the window of the step's oldest
                    # lane (position pos) go back first, then the far end
                    # grows to the step's last write
                    behind = (max(0, s.pos - self._window + 1)
                              // self.cache.page_size)
                    if behind > s.wfirst:
                        gone = self._wcache.allocator.release_head(
                            s.req.seq_id, behind)
                        s.wfirst += gone
                        s.wpages_held -= gone
                    if need - s.wfirst > s.wpages_held:
                        grower = (s, need - s.wfirst - s.wpages_held, True)
                        break
            if grower is None:
                if self._wcache is not None:
                    held = sum(s.pages_held for s in live)
                    if held:
                        _m_window_held.observe(100.0 * sum(
                            s.wpages_held for s in live) / held)
                return live, grants
            s, n, windowed = grower
            try:
                if windowed:
                    self._wcache.allocator.grow(s.req.seq_id, n)
                    s.wpages_held += n
                else:
                    self.cache.allocator.grow(s.req.seq_id, n)
                    s.pages_held += n
                continue
            except ServerOverloaded:
                pass
            if self._reclaim_for_growth(s, live):
                continue
            # nothing reclaimable: the submit-time worst-case-fits-pool
            # check makes this unreachable unless an external allocator
            # user pins pages — fail typed rather than corrupt
            with self._cond:
                if not s.req.ev.is_set():
                    _m_overloads.inc()
                    self._fail_locked(s.req, ServerOverloaded(
                        f"KV pool exhausted mid-decode for seq "
                        f"{s.req.seq_id} with nothing left to preempt "
                        "— external pages pinned?"))
                self._slots = [x for x in self._slots if x is not s]
                self._g_live.set(len(self._slots))
            live = [x for x in live if x is not s]
            if not live:
                return live, []

    def _reclaim_for_growth(self, grower: _Slot,
                            live: List[_Slot]) -> bool:
        """Make pages available for a live slot's growth: demote the
        newest QUEUED reservation first (it has no computed work to
        lose — admission re-reserves it later), else preempt the
        newest live slot other than the grower (spill + requeue at the
        front). A victim spills what its answered tokens wrote: a round
        still waiting for its answer is answered first, and the slots it
        retires leave ``live`` (the caller asks again). Mutates ``live``
        in place. False = nothing left to take."""
        with self._cond:
            for req in reversed(self._queue):
                if req.ev.is_set() or req.needs_alloc:
                    continue
                self._free_pages(req.seq_id)
                if req.cow is not None:
                    self.cache.allocator.release_cow(req.cow["key"])
                    req.cow = None
                req.cached_tokens = 0
                req.needs_alloc = True
                _m_demotions.inc()
                return True
        if self._pending is not None:
            self._settle()
            with self._cond:
                live[:] = [s for s in live if s in self._slots]
            return True
        victim = None
        for s in reversed(live):
            if s is grower or s.req.ev.is_set():
                continue
            victim = s
            break
        if victim is None:
            return False
        self._preempt(victim)
        live.remove(victim)
        return True

    def _preempt(self, victim: _Slot):
        """Spill the victim's written pages to host (bitwise), free its
        reservation, and requeue it at the FRONT so preemption cannot
        become starvation. Restore scatters the spill into a fresh
        reservation and the page table rebinds — the sequence's K/V
        round-trips exactly (preempt-never-corrupts; reserve-never-dies
        was the PR 6 policy this replaces)."""
        _faults.fire("serving.decode.preempt")
        req = victim.req
        with _tracing.span("serving.decode.preempt", model=self.name,
                           version=self.version, seq=req.seq_id,
                           tokens=victim.pos):
            pages = self.cache.allocator.pages_of(req.seq_id)
            # only ACCEPTED (committed) tokens spill: victim.pos is the
            # post-rollback watermark, so a speculative round's
            # rejected writes are never carried to host
            n_keep = (self.cache.allocator.pages_for_tokens(victim.pos)
                      if victim.pos else 0)
            if n_keep:
                with self._step_mu:
                    arrays = self.cache.gather_pages(pages[:n_keep])
                    if self._draft_cache is not None:
                        arrays = arrays + self._draft_cache.gather_pages(
                            pages[:n_keep])
                    if self._wcache is not None:
                        # the window kind's pages that hold a committed
                        # key: from its first held page to pos's
                        arrays = arrays + self._wcache.gather_pages(
                            self._wcache.allocator.pages_of(req.seq_id)[
                                :n_keep - victim.wfirst])
                    if self._scache is not None:
                        # the state row, which holds every token up to
                        # pos: no round is in flight (a preemption waits
                        # for the round before it)
                        arrays = arrays + self._scache.gather_row(
                            self._scache.row_of(req.seq_id))
                        _m_state_spills.inc()
                # put (disk-backed spills savez) stays outside the
                # step mutex, same as the pop side in _prepare
                self._spill.put(req.seq_id, *arrays)
            self._free_pages(req.seq_id)
            _m_preemptions.inc()
            with self._cond:
                self._slots = [x for x in self._slots if x is not victim]
                if req.ev.is_set():
                    # canceled/stopped while we spilled: nothing will
                    # resume — drop the spill, leak nothing
                    self._spill.drop(req.seq_id)
                else:
                    req.resume_pos = victim.pos
                    req.resume_dpos = victim.dpos
                    req.resume_wfirst = victim.wfirst if victim.pos else 0
                    req.carry_steps = victim.steps
                    req.carry_fts = victim.first_token_steps
                    req.needs_alloc = True
                    self._queue.insert(0, req)
                    self._g_depth.set(len(self._queue))
                self._g_live.set(len(self._slots))

    def _k_eff(self, s: _Slot) -> int:
        """Draft proposals this slot can use THIS round: capped by
        spec_k and by how many tokens the sequence may still commit
        (a verify round commits up to k_eff + 1, which must not
        overshoot max_new — so the reservation-bound write at
        ``pos + k_eff`` also never passes the sequence cap)."""
        if not self._spec_k or s.req.ev.is_set() or \
                s.pos < len(s.req.prompt) or s.req.mask is not None:
            # masked requests never ride speculation: the draft
            # proposes from UNMASKED logits, so acceptance would decay
            # to ~0 while still paying the draft steps — and the grant
            # math below assumes plain slots advance one position
            return 0
        total = len(s.req.prompt) + s.req.max_new
        return max(0, min(self._spec_k, total - s.pos - 2))

    def _grants(self, live: List[_Slot]) -> List[int]:
        """Token-budget scheduling (Sarathi-style, ISSUE 10): every
        slot past its prompt gets its one decode token unconditionally
        — in-flight decodes NEVER stall behind a prompt — while slots
        still in prefill share a per-step budget of ``prefill_budget``
        prompt tokens (the chunk unless set wider), granted in slot
        order, no slot more than a chunk. Every prefill slot is
        guaranteed at least one token per step (at ``prefill_chunk=1``
        this is bitwise the PR 6 one-token-per-slot schedule; no slot
        ever starves), so the budget caps the CHUNKS, not progress. A
        solo prompt takes the whole budget every step: P prompt tokens
        cost ceil(P / prefill_chunk) steps instead of P.

        With speculation on (ISSUE 14) a decoding slot's grant is the
        positions its VERIFY chunk writes — ``1 + k_eff`` — so demand-
        mode growth in ``_prepare`` covers the whole speculative write
        range before the round runs; like decode tokens, speculative
        lanes are never budgeted against prefill."""
        budget, chunk = self._prefill_budget, self._prefill_chunk
        grants = []
        bl = self._block
        for s in live:
            if bl > 1:
                # a block model prefills the prompt's whole blocks, whole
                # blocks at a time (at least one a step), and then runs
                # passes of one block; the P mod B tokens left over open
                # the first generated block
                remaining = len(s.req.prompt) // bl * bl - s.pos
                g = bl
                if remaining > 0:
                    g = max(bl, min(remaining, budget, chunk) // bl * bl)
                    budget = max(0, budget - g)
                grants.append(g)
                continue
            remaining_prompt = len(s.req.prompt) - s.pos
            if remaining_prompt > 0:
                g = max(1, min(remaining_prompt, budget, chunk))
                budget = max(0, budget - g)
            else:
                g = 1 + self._k_eff(s)
            grants.append(g)
        return grants

    @staticmethod
    def _draws_on_host(req: _DecodeRequest) -> bool:
        """Where THE deterministic per-(seed, position) choice of a
        request's tokens is made, read from what the request itself
        carries (never a flag, a model or the batch, so batch
        independence holds by construction): on the device by
        ``choose_tokens`` inside the program that made the logits —
        greedy, and temperature > 0 over the full vocabulary — or on
        the host by ``_choose`` from one fetched row: ``top_k > 0``
        sampling and a constraint mask. The plain step, the draft and
        the verify acceptance walk all take a request's tokens from the
        same one of the two, so a committed token is always exactly
        what the non-speculative engine would have emitted at that
        position from those logits — spec on/off bitwise equality is
        structural, not statistical (the rejection-sampling realization
        is pinned by (seed, position), ISSUE 14)."""
        return req.mask is not None or (req.temperature > 0.0
                                        and req.top_k > 0)

    def _choose(self, row, req: _DecodeRequest, position: int) -> int:
        """The host route's choice on one logits row (masked or not):
        greedy argmax at temperature 0, else the seeded
        ``sample_token`` draw."""
        if req.temperature <= 0.0:
            return int(np.argmax(row))
        return sample_token(row, req.temperature, req.top_k, req.seed,
                            position)

    def _masked_choice(self, req: _DecodeRequest, row,
                       position: int) -> Tuple[int, bool]:
        """Constrained decode's per-token core (ISSUE 20): zero the
        disallowed lanes to -inf, make the host route's deterministic
        per-(seed, position) choice (``_choose``) on what is left, then
        advance the automaton. Masking composes cleanly with the
        sampler — softmax renormalizes over the survivors — so a
        masked request's tokens are a pure function of (seed, mask,
        prompt, params), independent of batch composition (tier-1
        asserts bitwise equality across differently-loaded engines).
        Returns ``(token, exhausted)``; exhausted means the automaton
        has no further transition — the constraint is complete and the
        sequence finishes regardless of max_new."""
        allowed = req.mask.allowed(req.mask_state, self.spec.vocab)
        masked = np.where(allowed, np.asarray(row, np.float64), -np.inf)
        tok = self._choose(masked, req, position)
        ns = req.mask.step(req.mask_state, tok)
        # an allowed token always has a transition; belt-and-braces for
        # a buggy automaton: treat a dead step as exhaustion
        if ns is None:
            return tok, True
        req.mask_state = ns
        _m_masked_tokens.inc()
        return tok, not req.mask.allowed(ns, self.spec.vocab).any()

    def _check_reservation(self, s: _Slot, end_tokens: int):
        """The reservation (grown by _prepare in demand mode) must
        cover every write a step performs. A real raise, not an
        assert: writing through a page index past the reservation
        would corrupt another sequence's pages, and ``python -O``
        strips asserts. Canceled slots are exempt — their pages are
        gone and their table row is all-garbage, so their writes land
        on the garbage page by construction."""
        if s.req.ev.is_set():
            return
        ps = self.cache.page_size
        if end_tokens > s.pages_held * ps:
            raise ServingError(
                f"chunk grant escaped seq {s.req.seq_id}'s page "
                f"reservation ({end_tokens} tokens > "
                f"{s.pages_held} pages x {ps})")
        if self._wcache is not None and \
                end_tokens > (s.wfirst + s.wpages_held) * ps:
            raise ServingError(
                f"chunk grant escaped seq {s.req.seq_id}'s window page "
                f"reservation ({end_tokens} tokens > pages {s.wfirst} + "
                f"{s.wpages_held} x {ps})")

    def _spec_substep(self, slots: List[_Slot], w_bucket: int
                      ) -> Dict[int, Tuple[List[int], int, int]]:
        """Propose-then-verify for this round's DECODING slots
        (ISSUE 14). The draft runs ``k`` batched steps on its own
        compiled ladder — one catch-up chunk (the committed tokens it
        hasn't ingested, <= 2 lanes, ending with the pending token)
        that yields proposal d_1, then k-1 singles — and the target
        verifies all k+1 positions in ONE all-lane chunked call.
        Acceptance is the deterministic walk: lane j's target choice
        (per-(seed, position), made where ``_draws_on_host`` says the
        request's are) either equals proposal d_{j+1} (accept,
        continue) or replaces it (the bonus/correction token, stop).
        Returns {id(slot): (committed tokens, k_eff, accepted)} for the
        answer phase; nothing here touches request/slot state."""
        _faults.fire("serving.decode.spec")
        s_bucket = _bucket_for(self._slot_ladder, len(slots))
        keff = [self._k_eff(s) for s in slots]
        proposals: List[List[int]] = [[] for _ in slots]
        # the slots' sampling arrays, the same through the round; the
        # positions of the choices are each call's own ``lens``
        host = [self._draws_on_host(s.req) for s in slots]
        temperature, seed = self._slot_sampling(slots, s_bucket)
        tables = None

        def call(tag, c_bucket, feeds):
            # each call's build checks ITS writes against the
            # reservation (the verify chunk's reach through pos + k_eff);
            # the page tables are the round's first call's
            nonlocal tables
            arrays = self._build_arrays(slots, feeds, s_bucket, c_bucket,
                                        w_bucket, tables)
            tables = arrays[3]
            return self._run(tag, *arrays, temperature=temperature,
                             seed=seed)

        def propose(ids, logits, j):
            """Proposal d_j of every slot that still wants one, from a
            draft call's newest-lane ``(ids, logits)``."""
            ids = np.asarray(ids)
            for i, s in enumerate(slots):
                if keff[i] >= j:
                    proposals[i].append(
                        self._choose(self._fetch_row(logits, i), s.req,
                                     s.pos + j)
                        if host[i] else int(ids[i]))

        with _tracing.span("serving.decode.spec.draft", model=self.name,
                           version=self.version, slots=s_bucket,
                           k=self._spec_k):
            # catch-up + first proposal: feed each slot the committed
            # tokens its draft pool lacks (positions dpos..pos — the
            # last is the pending token, so lens == pos + 1, d_1's own),
            # newest-lane logits -> d_1. A bonus-only slot (k_eff 0)
            # needs no proposal: a dead row
            c1 = _bucket_for(self._draft_chunk_ladder,
                             max(s.pos - s.dpos for s in slots) + 1)
            if any(ke >= 1 for ke in keff):
                propose(*call("draft", c1, [
                    (s.dpos, s.tokens_at(s.dpos, s.pos - s.dpos + 1))
                    if ke >= 1 else None
                    for s, ke in zip(slots, keff)]), 1)
                # singles: feed d_{j-1} at pos + j - 1, propose d_j
                for j in range(2, self._spec_k + 1):
                    if not any(ke >= j for ke in keff):
                        break
                    propose(*call("draft", 1, [
                        (s.pos + j - 1, proposals[i][j - 2:j - 1])
                        if keff[i] >= j else None
                        for i, s in enumerate(slots)]), j)
        # verify: ONE target call over [pending, d_1..d_k] at the
        # FIXED spec_k+1 chunk entry; lane j's logits are the target's
        # distribution for position pos+1+j
        with _tracing.span("serving.decode.spec.verify",
                           model=self.name, version=self.version,
                           slots=s_bucket, lanes=self._verify_lanes):
            ids, lg = call("verify", self._verify_lanes, [
                (s.pos, [s.token_at(s.pos)] + proposals[i])
                for i, s in enumerate(slots)])
            # ids [B, C], lg [B, C, V]
            ids = np.asarray(ids)
        out: Dict[int, Tuple[List[int], int, int]] = {}
        for i, s in enumerate(slots):
            committed: List[int] = []
            accepted = 0
            lanes = self._fetch_row(lg, i) if host[i] else None
            for j in range(keff[i] + 1):
                choice = (self._choose(lanes[j], s.req, s.pos + 1 + j)
                          if host[i] else int(ids[i, j]))
                committed.append(choice)
                if j < keff[i] and proposals[i][j] == choice:
                    accepted += 1      # d_{j+1} accepted — keep going
                else:
                    break              # bonus/correction token: stop
            out[id(s)] = (committed, keff[i], accepted)
        return out

    def _plan_causal(self, live: List[_Slot], grants: List[int],
                     pend: Optional[_Round]) -> _Round:
        """A causal model's round: decoding slots with a draft attached
        ride the propose/verify substep; prefill chunks (and everything
        when speculation is off) ride the target program's chunked step,
        each feeding its ``grant`` next tokens from ``pos``, which moves
        past them here. A decoding slot feeds the token ``pend`` chooses
        from the device (``src``): ``pend`` is not answered yet."""
        rnd = _Round(live, grants)
        for s, g in zip(live, grants):
            req = s.req
            if self._spec_k and not req.ev.is_set() \
                    and s.pos >= len(req.prompt) and req.mask is None:
                rnd.spec.append(s)
                continue
            row = len(rnd.rows)
            rnd.row_of[id(s)] = row
            rnd.rows.append(s)
            if s.pos < len(req.prompt) + len(req.produced):
                rnd.feeds.append((s.pos, s.tokens_at(s.pos, g)))
                rnd.src.append(-1)
            else:
                # a canceled slot rides through the garbage table
                rnd.feeds.append((s.pos, [0] * g))
                rnd.src.append(-1 if req.ev.is_set()
                               else pend.row_of[id(s)])
            if s.pos < len(req.prompt):
                rnd.prefill_toks += g
            if s.pos == 0 and self._scache is not None:
                _m_state_resets.inc()
            s.pos += g
            if s.pos < len(req.prompt):
                continue
            # the newest lane's choice is read: one token owed
            rnd.reads = True
            rnd.owed[id(s)] = 1
            due = self._due(s, pend)
            if due + 1 >= req.max_new:
                rnd.ends.add(s)
            if self._draws_on_host(req):
                rnd.host = True
                rnd.wanted.append(row)
            elif req.want_topk and not due:
                # the first generated token: its row gives first_topk
                rnd.wanted.append(row)
        per_token = self.spec.moe_assignments_per_token
        if per_token:
            rnd.n["assignments"] = per_token * sum(
                len(fed) for _start, fed in rnd.feeds)
            rnd.call_args = {"moe_assignments": rnd.n["assignments"]}
        if self._scache is not None:
            # the state rows the call's recurrence reads and writes
            rnd.call_args["state_rows"] = len(rnd.rows)
        return rnd

    def _open_block(self, s: _Slot, known: int
                    ) -> Tuple[List[int], List[bool]]:
        """The block at ``[s.pos, s.pos + B)`` as it opens, ``(tokens,
        masked)``: the tokens the sequence holds there (the ``P mod B``
        prompt tokens left over by the prefill of whole blocks, in the
        first generated block) stay unmasked, every other lane is masked.
        ``known``: the sequence's tokens once every dispatched round is
        answered."""
        lanes = range(s.pos, s.pos + self._block)
        masked = [p >= known for p in lanes]
        return ([self.spec.mask_token_id if m else s.token_at(p)
                 for p, m in zip(lanes, masked)], masked)

    def _plan_blocks(self, live: List[_Slot], grants: List[int],
                     pend: Optional[_Round]) -> _Round:
        """The round of a model that generates by diffusion over blocks
        (ISSUE 30). Slot by slot the ONE jitted call carries a prefill
        chunk of whole blocks, a DENOISE pass (the block with the mask
        id at its masked lanes; the program's x0 at the ``B /
        denoise_steps`` most confident masked lanes is kept) or, once no
        lane is masked, the COMMIT pass (the B real tokens, whose K/V
        later blocks read), B lanes each. Every pass writes its lanes'
        K/V (write-before-attend; the commit pass's write is the one
        that stands). A pass that carries on from ``pend``'s denoise pass
        of its block, not answered yet, takes its input block and masked
        lanes from the device (``src``): a pass unmasks ``B /
        denoise_steps`` lanes, so the host counts the lanes left, and so
        the pass."""
        bl = self._block
        rnd = _Round(live, grants)
        rnd.rows = live
        rnd.row_of = {id(s): i for i, s in enumerate(live)}
        rows = _bucket_for(self._slot_ladder, len(live))
        masked = np.zeros((rows, bl), bool)
        n_unmask = np.zeros(rows, np.int32)
        for i, (s, g) in enumerate(zip(live, grants)):
            req, start = s.req, s.pos
            rnd.src.append(-1)
            if start < len(req.prompt) // bl * bl:
                kind, fed = "prefill", req.prompt[start:start + g]
                rnd.prefill_toks += g
                s.pos += g
                rnd.kinds.append(kind)
                rnd.feeds.append((start, fed))
                continue
            due = self._due(s, pend)
            before = (pend.kinds[pend.row_of[id(s)]]
                      if pend is not None and id(s) in pend.row_of
                      else None)
            if before == "denoise":
                left, fed = pend.left[id(s)], [0] * bl
                rnd.src[i] = pend.row_of[id(s)]
            else:
                if before is None and s.block is not None:
                    block, lanes = s.block, s.masked
                else:
                    block, lanes = self._open_block(
                        s, len(req.prompt) + due)
                    rnd.opened[i] = (block, lanes)
                fed, left = block, sum(lanes)
                masked[i] = lanes
            if left:
                kind = "denoise"
                n_unmask[i] = bl // req.denoise_steps
                rnd.left[id(s)] = max(0, left - int(n_unmask[i]))
                if req.want_topk and req.first_topk is None \
                        and before != "denoise":
                    # the first pass of the first block: its row gives
                    # first_topk
                    rnd.wanted.append(i)
            else:
                # the block's tokens past what the sequence holds, as far
                # as max_new lets
                kind = "commit"
                known = len(req.prompt) + due
                owed = min(bl - max(0, known - start), req.max_new - due)
                rnd.owed[id(s)] = owed
                if due + owed >= req.max_new:
                    rnd.ends.add(s)
                s.pos += bl
            rnd.kinds.append(kind)
            rnd.feeds.append((start, fed))
        n_kind = {k + "_slots": rnd.kinds.count(k)
                  for k in ("prefill", "denoise", "commit")}
        rnd.n["passes"] = n_kind["denoise_slots"] + n_kind["commit_slots"]
        rnd.n["assignments"] = (sum(grants)
                                * self.spec.moe_assignments_per_token)
        # a step of prefill chunks alone chooses nothing anybody reads
        rnd.reads = rnd.n["passes"] > 0
        rnd.call_kw = {"masked": masked, "n_unmask": n_unmask}
        rnd.call_args = dict(n_kind, moe_assignments=rnd.n["assignments"])
        return rnd

    def _step(self, live: List[_Slot]):
        """ONE decoding round, whatever the model, ONE STEP AHEAD of its
        answers: prepare -> plan (per slot: which pass, what it feeds,
        whether anybody reads its choice) -> build -> device call (-> the
        propose/verify substep where a draft is attached) -> wait for
        the round before it -> answer THAT round (per slot, by the pass
        it ran) -> retire -> notify. This round is answered once the next
        one is dispatched, so the device has it queued while the host
        answers and plans. The plan counts what it knows before the
        answer (positions, grants, who reaches ``max_new``, a block's
        pass); what it does not — the token a decoding slot feeds, a
        block's lanes — the device feeds from the round before
        (``feed_tokens``). A round has the answer of the one before it
        first where the host chooses a token of that round, a draft
        proposes (``_waits_first``) or a preemption would take one of
        its slots (``_reclaim_for_growth``). A slot that reaches
        ``eos_id`` rides the round after as a lane whose answer is
        dropped. A causal slot and a block slot differ in the plan and
        the answer; the rest is written here, once."""
        # named chaos seam for the SCHEDULER cadence: a
        # `delay@serving.decode.step:*=0.004` plan simulates a slow
        # decoder (long-context model, contended chip) so streaming/
        # failover tests can pin mid-generation behavior without racing
        # a fast engine; `error@` fails the step's slots like any other
        # step failure. Zero cost with no plan installed.
        _faults.fire("serving.decode.step")
        pend = self._pending
        if pend is not None and self._waits_first(pend, live):
            self._settle()
            with self._cond:
                live = [s for s in live if s in self._slots]
        elif pend is not None:
            # what reaches max_new in pend rides no further round
            live = [s for s in live if s not in pend.ends]
        # restore-before-step, COW copies, demand-mode growth (may
        # preempt/demote — the returned live list is authoritative)
        if live:
            with _tracing.span("serving.decode.prepare"):
                live, grants = self._prepare(live)
        if not live:
            self._settle()
            return
        pend = self._pending
        with _tracing.span("serving.decode.plan"):
            rnd = (self._plan_blocks if self._block > 1
                   else self._plan_causal)(live, grants, pend)
            w_bucket = _bucket_for(self._width_ladder,
                                   max(s.pages_held for s in live))
        t0 = time.perf_counter()
        # one decode step joins the OLDEST live request's trace (a span
        # has one parent); per-slot request spans live in the server
        with _tracing.adopt(live[0].req.trace_ctx), \
                _tracing.span("serving.decode.step", model=self.name,
                              version=self.version, width=w_bucket,
                              prefill_tokens=rnd.prefill_toks,
                              spec_slots=len(rnd.spec), live=len(live)):
            if rnd.rows:
                s_bucket = _bucket_for(self._slot_ladder, len(rnd.rows))
                # pure-decode steps (and 1-token prefill tails) ride
                # the C=1 shapes, a block model's passes C=block_length;
                # only steps carrying a real chunk pay the chunk-wide
                # compute
                c_bucket = _bucket_for(
                    self._chunk_ladder,
                    max(len(fed) for _start, fed in rnd.feeds))
                with _tracing.span("serving.decode.build"):
                    arrays = self._build_arrays(rnd.rows, rnd.feeds,
                                                s_bucket, c_bucket, w_bucket)
                    # a chunk that ends inside its prompt chooses a
                    # token too: garbage nobody reads
                    temperature, seed = self._slot_sampling(rnd.rows,
                                                            s_bucket)
                    src = np.full(s_bucket, -1, np.int32)
                    src[:len(rnd.src)] = rnd.src
                # dispatch of the jitted step, then the round before it
                # to its ids on the host; the logits stay on the device
                with _tracing.span("serving.decode.device_call") as sp:
                    with _tracing.span("serving.decode.dispatch"):
                        t_call = time.perf_counter()
                        tokens, kw = arrays[0], dict(rnd.call_kw)
                        if (src >= 0).any():
                            tokens, fed_masked = self._feed(
                                tokens, src, pend, kw.get("masked"))
                            if fed_masked is not None:
                                kw["masked"] = fed_masked
                        rnd.fed = (tokens, kw.get("masked"))
                        # the draw's position is lens, each slot's new
                        # token's absolute index in its sequence: the
                        # (seed, position) pair that makes sampling
                        # independent of batch composition AND chunking
                        out, rnd.logits = self._run_step_arrays(
                            tokens, *arrays[1:], temperature=temperature,
                            seed=seed, **kw)
                        rnd.dev = (out if isinstance(out, dict)
                                   else {"ids": out})
                        if rnd.reads:
                            for a in rnd.dev.values():
                                a.copy_to_host_async()
                        # the host route's rows, queued behind the step
                        # and before the next one
                        for row in rnd.wanted:
                            rnd.rows_out[row] = self._row_async(
                                rnd.logits, row)
                        if self._spec_k:
                            # the draft shadows every prefill chunk so
                            # its mirrored pool tracks the committed
                            # sequence (its choice is discarded; its
                            # watermark advances in the answer phase)
                            self._run("draft", tokens, *arrays[1:])
                        t_sent = time.perf_counter()
                    _m_dispatch_ms.observe((t_sent - t_call) * 1e3)
                    _m_ahead_pct.observe(0.0 if pend is None else 100.0)
                    _m_host_gap_ms.observe(
                        0.0 if self._t_back is None
                        else (t_sent - self._t_back) * 1e3)
                    # the device holds this step until a later round
                    # collects its ids
                    self._t_back = None
                    # worked out while the device runs the step
                    self._note_call(sp, s_bucket, c_bucket, w_bucket,
                                    arrays[2], arrays[4], rnd.call_args)
                    if pend is not None:
                        # the round this step went ahead of: its ids
                        with _tracing.span("serving.decode.wait"):
                            self._collect(pend, sp)
                        self._release(pend)
            if rnd.spec:
                rnd.spec_out = self._spec_substep(rnd.spec, w_bucket)
        step_s = time.perf_counter() - t0
        self._waited += step_s
        self._observe_step(step_s, len(live), rnd.prefill_toks)
        if rnd.n["assignments"]:
            _m_moe_assignments.inc(rnd.n["assignments"])
        self._pending = rnd
        if pend is not None:
            self._answer(pend)

    def _answer(self, rnd: _Round):
        """The answer phase of a round whose ids are on the host: each
        slot's answer by the pass it ran, retire, the round's counters,
        and ONE wake-up for its tokens."""
        n = rnd.n
        now = time.monotonic()
        done: List[_Slot] = []
        notes: Dict[int, int] = {}
        # the whole answer phase holds _cond: stop(drain=False) fails
        # requests under _cond, so check-ev-then-answer must be atomic
        # with it or the two sides can each answer the same request.
        # The span opens before the condition is taken: the wait for it
        # (clients reading their streams hold it) is the phase's time
        with _tracing.span("serving.decode.answer"):
            with self._cond:
                self._n_steps += 1
                for s, g in zip(rnd.live, rnd.grants):
                    if s.req.ev.is_set():
                        # already answered — stop(drain=False) raced this
                        # step and failed the request (or it was canceled,
                        # or it ended at eos_id in the round before);
                        # don't double-answer or count a completion/token
                        done.append(s)
                        continue
                    s.steps += 1
                    if self._block > 1:
                        finished = self._answer_block(s, g, rnd)
                    elif id(s) in rnd.spec_out:
                        finished = self._answer_spec(s, rnd)
                    else:
                        finished = self._answer_plain(s, g, rnd)
                    self._retire_locked(s, finished, now, done, notes)
                chosen = n["device"] + n["host"]
                if n["proposed"]:
                    _m_spec_proposed.inc(n["proposed"])
                    _m_spec_accepted.inc(n["accepted"])
                    _m_spec_rejected.inc(n["proposed"] - n["accepted"])
                if n["passes"]:
                    _m_block_passes.inc(n["passes"])
                    _m_block_tokens_per_pass.observe(n["device"] / n["passes"])
                    if n["device"]:
                        _m_block_committed.inc(n["device"])
                if n["dropped"]:
                    _m_block_dropped.inc(n["dropped"])
                if n["device"]:
                    _m_device_choices.inc(n["device"])
                if n["host"]:
                    _m_host_choices.inc(n["host"])
                if chosen:
                    _m_tokens.inc(chosen)
                    _m_device_choice_pct.observe(100.0 * n["device"] / chosen)
                counts = (rnd.out or {}).get("expert_counts")
                if counts is not None and counts.sum():
                    _m_moe_load.observe(float(
                        (counts.max(axis=1) / np.maximum(
                            counts.mean(axis=1), 1e-9)).max()))
                # ONE wake-up delivers a round's tokens, in order
                self._close_round_locked(done, chosen > 0, notes)
                # the round's host clock, still inside the span and the
                # condition: once that is released the woken clients run,
                # and what the scheduler then waits belongs to the next
                # round's admit
                t_end = time.perf_counter()
                _m_sample_ms.observe(rnd.sample_s * 1e3)
                _m_sched_ms.observe((t_end - self._t_round - self._waited
                                     - rnd.sample_s) * 1e3)
                self._t_round, self._waited = t_end, 0.0
            # the step's logits (the host route's rows came from them)
            # are let go HERE, inside the phase and once the woken
            # clients may run, so that freeing their device buffer
            # (0.25-0.4 ms a round on a TPU v5e at 16 x 256k float32)
            # is a named stretch and not a gap before the next admit
            rnd.logits = None
            rnd.rows_out = {}

    def _emit_locked(self, s: _Slot, tok: int, rnd: _Round,
                     route: str) -> bool:
        """One generated token of a slot's answer, chosen on ``route``
        ("device" / "host"): append it, tally it, note the slot's first.
        True = the sequence ends with it (``max_new`` or ``eos_id``)."""
        s.req.produced.append(tok)
        rnd.n[route] += 1
        if s.first_token_steps is None:
            s.first_token_steps = s.steps
            _m_first_token_steps.observe(s.steps)
        return (len(s.req.produced) >= s.req.max_new
                or tok == self.spec.eos_id)

    def _answer_plain(self, s: _Slot, g: int, rnd: _Round) -> bool:
        """The answer of a causal slot that rode the target program's
        call: its chunk of ``g`` (>= 1: every live slot progresses) is
        written from its feed's start, and once the prompt is through its
        newest lane's token is the program's own choice or the host
        route's."""
        row = rnd.row_of[id(s)]
        end = rnd.feeds[row][0] + g
        if self._prefix_on and not s.req.published and \
                end >= len(s.req.prompt):
            # prompt K/V fully on-device as of THIS step: publish the
            # prompt pages into the prefix index (metadata only; from
            # here they are immutable — this sequence only ever writes
            # PAST them, and they outlive its free() as the shared
            # cache)
            self.cache.allocator.publish(s.req.seq_id, s.req.prompt)
            s.req.published = True
        if self._spec_k:
            # the draft shadowed this prefill chunk lane for lane — its
            # watermark advances in lockstep
            s.dpos = end
        if end < len(s.req.prompt):
            return False
        # row is the slot's newest lane (the step unembeds only lane
        # q_len-1): prompt token P-1 when the chunk just finished
        # prefill, else the decode token; ids[row] is the program's own
        # choice there, greedy or drawn by (seed, end)
        tok, mask_done, route = int(rnd.out["ids"][row]), False, "device"
        topk = s.req.want_topk and s.req.first_topk is None
        if topk or self._draws_on_host(s.req):
            # the host route: this slot's row, copied alone behind its
            # step (``rnd.wanted``), then today's numpy choice
            with rnd.sampling():
                tok, mask_done = self._sample(
                    s.req, np.asarray(rnd.rows_out[row]), tok, end, topk)
            route = "host"
        return self._emit_locked(s, tok, rnd, route) or mask_done

    def _answer_spec(self, s: _Slot, rnd: _Round) -> bool:
        """The answer of a decoding slot the draft proposed for: the
        committed walk of the verify (``_spec_substep``), the draft's
        watermark, and the rollback of what was grown for rejected
        positions."""
        committed, ke, acc = rnd.spec_out[id(s)]
        pos_old = s.pos
        s.req.spec_proposed += ke
        s.req.spec_accepted += acc
        rnd.n["proposed"] += ke
        rnd.n["accepted"] += acc
        route = "host" if self._draws_on_host(s.req) else "device"
        finished = False
        for tok in committed:
            s.pos += 1
            if self._emit_locked(s, tok, rnd, route):
                # tokens past an accepted eos are discarded: the
                # committed walk ends here
                finished = True
                break
        if ke > 0 and not finished:
            # draft validity watermark: the draft wrote through
            # pos_old+ke-1 and tokens are committed through pos_old+acc
            # — a fully-accepted round leaves it one token behind (it
            # never fed its own last proposal), anything else re-syncs
            s.dpos = pos_old + min(ke - 1, acc) + 1
        if not finished and self._reservation == "demand":
            # ROLLBACK (ISSUE 14): any page grown for this verify chunk
            # that now holds ONLY rejected positions goes straight back
            # to the pool; coverage for the pending token's next write
            # (pos itself) is kept so acceptance never thrashes
            # grow/shrink. note_tokens_many at the round's close records
            # the rolled-back pos — the "un-note".
            need = self.cache.allocator.pages_for_tokens(s.pos + 1)
            if s.pages_held > need:
                s.pages_held -= self.cache.allocator.shrink(
                    s.req.seq_id, s.pages_held - need)
        return finished

    def _answer_block(self, s: _Slot, g: int, rnd: _Round) -> bool:
        """A block model's slot after its pass: a prefill chunk is
        written; a denoise pass unmasks the lanes the program marked,
        at its choices; the commit pass answers up to B tokens at once —
        fewer at ``max_new`` or at ``eos_id``, the rest of the block is
        dropped. The pass's input is the block the plan opened, else the
        one the slot's last answer left."""
        i = rnd.row_of[id(s)]
        if rnd.kinds[i] == "prefill":
            return False
        start = rnd.feeds[i][0]
        block, masked = rnd.opened.get(i, (s.block, s.masked))
        if rnd.kinds[i] == "denoise":
            req = s.req
            ids, unmask = rnd.out["ids"][i], rnd.out["unmask"][i]
            if req.want_topk and req.first_topk is None:
                # the first pass's first masked lane: its order of the
                # best tokens, from one fetched row
                with rnd.sampling():
                    req.first_topk = _top_order(
                        np.asarray(rnd.rows_out[i])[masked.index(True)],
                        req.want_topk)
            if req.passes is not None:
                req.passes.append({
                    "pos": int(start),
                    "input": [int(t) for t in block],
                    "masked": list(masked),
                    "ids": [int(t) for t in ids],
                    "confidence": [float(c)
                                   for c in rnd.out["confidence"][i]],
                    "unmasked": [bool(u) for u in unmask]})
            s.block = [int(ids[j]) if unmask[j] else t
                       for j, t in enumerate(block)]
            s.masked = [m and not unmask[j] for j, m in enumerate(masked)]
            return False
        # commit: the block's tokens that are not the prompt's, in
        # order, as far as max_new and eos let
        known = len(s.req.prompt) + len(s.req.produced)
        fresh = block[max(0, known - start):]
        taken, finished = 0, False
        for tok in fresh:
            taken += 1
            if self._emit_locked(s, tok, rnd, "device"):
                finished = True
                break
        rnd.n["dropped"] += len(fresh) - taken
        s.block = s.masked = None
        return finished

    def _note_call(self, sp, slots: int, chunk: int, width: int, q_lens,
                   kv_lens, more: Dict[str, int]):
        """What a step call that runs the attention records once it is
        dispatched, while the device runs it: the share of its ``slots x
        width`` grid that holds a live page and the share of its folds
        the kernel's dot fold takes, always; the share of the latent
        kernel's grid steps that fold a live block, where the model has a
        latent pool; and on the ``device_call`` span where one is live
        ``_call_work``'s args and the round's own (``more``)."""
        ps = self.cache.page_size
        _m_attn_grid_live.observe(
            100.0 * _live_pages(kv_lens, ps) / (slots * width))
        _m_attn_dot_fold.observe(_dot_fold_pct(
            chunk, *self._fold_geometry, q_lens, kv_lens, page_size=ps,
            window=self._window, layers=self._attn_layers)
            if slots in self._kernel_slots else 0.0)
        if self._latent_pool:
            _m_attn_latent_steps.observe(
                _latent_steps_live_pct(slots, width, kv_lens, ps))
        if sp.live or self._window is not None:
            work = _call_work(slots, chunk, width, q_lens, kv_lens,
                              self._block, window=self._window)
            if work.get("attn_pairs_full"):
                _m_window_skip.observe(
                    100.0 - 100.0 * work["attn_pairs_window"]
                    / work["attn_pairs_full"])
        if sp.live:
            for key, value in {**work, **more}.items():
                sp.set_arg(key, value)

    def _observe_step(self, seconds: float, n_live: int,
                      prefill_toks: int):
        """What a round records when its device call is back, whatever
        kind of step ran it (``serving.decode.step_ms``'s stretch)."""
        _m_step_ms.observe(seconds * 1e3)
        _m_steps.inc()
        _m_occupancy.observe(
            n_live / float(_bucket_for(self._slot_ladder, n_live)))
        # prices the token-budget policy next to occupancy: how much of
        # each step's budget real prefill work consumed
        _m_prefill_per_step.observe(prefill_toks)
        if prefill_toks:
            _m_prefill_tokens.inc(prefill_toks)

    def _retire_locked(self, s, finished: bool, now: float, done: List[Any],
                       notes: Dict[int, int]):
        """The end of one slot's answer, in either lane: note how far it
        got, and complete it or fail it on a lapsed deadline."""
        notes[s.req.seq_id] = s.pos
        if finished:
            # finished beats a lapsed deadline: the result is fully
            # paid for — deliver it rather than discard
            done.append(s)
            self._complete(s)
        elif s.req.deadline is not None and now > s.req.deadline:
            _m_deadline_miss.inc()
            done.append(s)
            self._fail_locked(s.req, DeadlineExceeded(
                f"request to decoder '{self.name}' lapsed "
                + s.progress()))

    def _close_round_locked(self, done: List[Any], produced: bool,
                            notes: Dict[int, int]):
        """The last lines of a round's answers, in either lane, still
        under the condition: the allocator's notes, the done slots out
        of their lane, and the wake-up."""
        # one allocator-lock round-trip for the whole step; seqs freed
        # by _complete/_fail are skipped inside
        self.cache.allocator.note_tokens_many(notes)
        if self._wcache is not None:
            # the window kind holds a sequence's tokens from its first
            # held page on (the round's slots are still in their lane)
            ps = self.cache.page_size
            first = {s.req.seq_id: s.wfirst for s in self._slots}
            self._wcache.allocator.note_tokens_many(
                {sid: max(0, n - first.get(sid, 0) * ps)
                 for sid, n in notes.items()})
        if done:
            self._slots = [s for s in self._slots if s not in done]
            self._embed_slots = [s for s in self._embed_slots
                                 if s not in done]
            self._g_live.set(len(self._slots))
            self._g_embed.set(len(self._embed_slots))
        if done or produced:
            # wake completion waiters AND streaming readers parked in
            # stream_tokens — a token exists the moment this notify
            # lands, ceil(prompt/chunk) steps after admission, not when
            # the whole sequence finishes
            self._cond.notify_all()

    def _sample(self, req: _DecodeRequest, row, chosen: int,
                position: int, topk: bool) -> Tuple[int, bool]:
        """The host route of one slot's token, from its fetched logits
        row (the ``serving.decode.sample`` span's body): the first
        position's token order where the request asked for it, then the
        masked or the ``top_k`` choice; a request that is here for its
        ``first_topk`` alone keeps ``chosen``, the program's own choice.
        Returns ``(token, mask_exhausted)``."""
        if topk:
            # the beam fork point (ISSUE 20): the FIRST generated
            # position's token order by logit, stable-sorted so ties
            # break deterministically; order[0] == argmax, so beam 0 is
            # the greedy continuation
            req.first_topk = _top_order(row, req.want_topk)
        if req.mask is not None:
            return self._masked_choice(req, row, position)
        if self._draws_on_host(req):
            return self._choose(row, req, position), False
        return chosen, False

    def _complete(self, s):
        """Deliver a finished slot's result, in either lane."""
        self._free_pages(s.req.seq_id)
        _m_completions.inc()
        _m_total.observe((time.monotonic() - s.req.t_enq) * 1e3)
        if isinstance(s, _EmbedSlot):
            p = len(s.req.prompt)
            s.req.result = {
                "embedding": [float(x) for x in s.req.hidden_sum / p],
                "logprobs": list(s.req.logprobs),
                "prompt_len": p,
                "version": self.version,
                "steps": int(s.steps),
            }
            s.req.ev.set()
            return
        s.req.result = {
            "tokens": list(s.req.produced),
            "prompt_len": int(len(s.req.prompt)),
            "version": self.version,
            # scheduler steps from admission to the first generated
            # token — the load-independent chunked-prefill evidence
            # (ceil(P/chunk) + co-riding, vs P unchunked; for a
            # prefix-cache hit, suffix takes the prompt's place:
            # ceil((P - cached)/chunk))
            "steps_to_first_token": int(s.first_token_steps or s.steps),
            # prompt tokens answered from the prefix index instead of
            # prefilled (0 = cold)
            "cached_tokens": int(s.req.cached_tokens),
            # speculative decoding (ISSUE 14): draft proposals this
            # request saw and the fraction the target accepted (None =
            # no speculative round touched it / speculation off)
            "spec_proposed": int(s.req.spec_proposed),
            "spec_accepted": int(s.req.spec_accepted),
            "accept_rate": (
                round(s.req.spec_accepted / s.req.spec_proposed, 4)
                if s.req.spec_proposed else None),
        }
        if s.req.want_topk:
            # the beam fork point rides the ordinary result dict —
            # absent unless asked for, so every pre-existing result
            # shape is untouched
            s.req.result["first_topk"] = list(s.req.first_topk or [])
            if self._block > 1:
                s.req.result["passes"] = list(s.req.passes or [])
        if s.req.spec_proposed:
            _m_spec_accept_rate.observe(
                s.req.spec_accepted / s.req.spec_proposed)
        s.req.ev.set()

    # -- the embed lane ---------------------------------------------------
    def _embed_step(self, live: List[_EmbedSlot]):
        """One chunked-prefill step for the embedding/scoring lane
        (ISSUE 20): the same Sarathi-style token budget, array builder,
        run function and compiled ladders as generation — but the
        all-lane + hidden program, and nothing is ever sampled: every
        lane feeds the pooled-hidden accumulator and the per-token
        logprobs. Decode slots are untouched by construction (separate
        slot list)."""
        # named chaos seam for the embed cadence (mirrors
        # serving.decode.step); the workload layer's per-kind site
        # (serving.workload.embed) lives at the dispatch boundary
        _faults.fire("serving.decode.embed")
        budget, chunk = self._prefill_budget, self._prefill_chunk
        grants = []
        for s in live:
            remaining = len(s.req.prompt) - s.pos
            g = max(1, min(remaining, budget, chunk))
            budget = max(0, budget - g)
            grants.append(g)
        w_bucket = _bucket_for(self._width_ladder,
                               max(s.pages_held for s in live))
        # canceled: pages freed, a dead row
        arrays = self._build_arrays(
            live, [None if s.req.ev.is_set()
                   else (s.pos, s.req.prompt[s.pos:s.pos + g])
                   for s, g in zip(live, grants)],
            _bucket_for(self._slot_ladder, len(live)),
            _bucket_for(self._chunk_ladder, max(grants)), w_bucket)
        t0 = time.perf_counter()
        with _tracing.adopt(live[0].req.trace_ctx), \
                _tracing.span("serving.decode.embed", model=self.name,
                              version=self.version, width=w_bucket,
                              live=len(live)):
            logits, hidden = self._run("embed", *arrays)
        logits_np = np.asarray(logits)  # [B, C, vocab]
        hidden_np = np.asarray(hidden)  # [B, C, d_model]
        _m_step_ms.observe((time.perf_counter() - t0) * 1e3)
        now = time.monotonic()
        done: List[_EmbedSlot] = []
        notes: Dict[int, int] = {}
        with self._cond:
            self._n_steps += 1
            for i, (s, g) in enumerate(zip(live, grants)):
                if s.req.ev.is_set():
                    done.append(s)
                    continue
                s.steps += 1
                prompt = s.req.prompt
                s.req.hidden_sum += np.asarray(
                    hidden_np[i, :g], np.float64).sum(axis=0)
                lg = np.asarray(logits_np[i, :g], np.float64)
                # float64 log-softmax per lane; lane j (absolute
                # position pos+j) scores the NEXT prompt token — the
                # final lane has no successor inside the prompt
                mx = lg.max(axis=-1)
                lse = mx + np.log(
                    np.exp(lg - mx[:, None]).sum(axis=-1))
                for j in range(g):
                    nxt = s.pos + j + 1
                    if nxt < len(prompt):
                        s.req.logprobs.append(
                            float(lg[j, int(prompt[nxt])] - lse[j]))
                s.pos += g
                _m_embed_tokens.inc(g)
                self._retire_locked(s, s.pos >= len(prompt), now, done,
                                    notes)
            self._close_round_locked(done, False, notes)
