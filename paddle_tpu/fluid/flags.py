"""Global runtime flags — the reference exposes gflags to Python
(reference python/paddle/fluid/__init__.py:121, framework/init.cc:31:
check_nan_inf, benchmark, fraction_of_gpu_memory_to_use, ...). Same shape
here, with TPU-relevant knobs."""
from __future__ import annotations

import os
from typing import Any, Dict

# the recorder parses PADDLE_TPU_TRACE / PADDLE_TPU_TRACE_BUFFER once at
# import; FLAGS reads its LIVE state rather than re-parsing the env, so
# one parser owns both views
from ..observability import tracing as _tracing


class _Flags(dict):
    """FLAGS with read-through keys: 'trace'/'trace_buffer' always report
    the live recorder (profiler() and trace_enable() toggle it without
    going through set_flags, so a stored mirror would go stale), and
    'faults' reports the live fault-injection plan the same way
    (faults.install()/scoped() toggle it without going through
    set_flags)."""

    def __getitem__(self, k):
        if k == "trace":
            return _tracing.trace_enabled()
        if k == "trace_buffer":
            return _tracing.buffer_capacity()
        if k == "faults":
            from ..distributed import faults as _faults

            return _faults.active_spec()
        return dict.__getitem__(self, k)


FLAGS: Dict[str, Any] = _Flags({
    # numeric precision of matmul/conv inside lowered blocks:
    #   'highest' = fp32 accumulate+multiply (reference fp32 CUDA parity)
    #   'high'    = bf16x3 on TPU
    #   'default' = bf16 multiply (fastest on MXU)
    "matmul_precision": "highest",
    # sweep outputs for NaN/Inf after each run (reference FLAGS_check_nan_inf,
    # executor.cc:27)
    "check_nan_inf": False,
    # log per-run timing (reference FLAGS_benchmark, executor.cc:348)
    "benchmark": False,
    # donate state buffers to jit for in-place HBM updates
    "donate_state": True,
    # hand-written Pallas kernels for hot ops: 'auto' = measured-winner
    # routing on TPU (flash attention at seq >= flash_min_seq, fused
    # layer_norm; NOT the fused conv, which loses to XLA on every
    # measured shape — see conv2d_bn_relu); True forces every kernel on
    # regardless of the measured tables (interpret-mode off-TPU, slow —
    # tests/A-B only; attention still honors flash_min_seq, so kernel
    # tests at short seq also set flash_min_seq 0); False = plain XLA
    "use_pallas_kernels": "auto",
    # minimum sequence length at which single-device attention routes to
    # the Pallas flash kernel instead of XLA's dense path. Measured on
    # TPU v5e (benchmarks/flash_attention_bench.py, slope-sync timing,
    # bf16 fwd+bwd): flash is 0.58x XLA at S=2048 but 1.85x at S=4096 —
    # XLA's dense attention wins while the S^2 score matrix still fits
    # comfortably in HBM bandwidth, flash wins once it doesn't. 0 = always
    # flash (and long-seq tests force it to exercise the kernel).
    "flash_min_seq": 3072,
    # cost-model-driven autotuning (ISSUE 8; paddle_tpu/autotune).
    # False = every knob is exactly its hand-set FLAGS default (zero
    # overhead, the pre-autotune behavior); True = routing thresholds
    # (flash_min_seq, paged_min_slots) and "auto" serving ladders read
    # through the tuning cache per DEVICE KIND (the FLAGS constants
    # demote to cold-cache defaults), and the executor logs per-shape
    # step timings into the cache
    "autotune": False,
    # where the tuning cache persists (tuning_cache.json, atomic
    # tmp-write+rename like master.snapshot). Seeded from
    # PADDLE_TPU_AUTOTUNE_DIR; '' = in-memory only. Read once, when the
    # process cache is first created (autotune.get_cache)
    "autotune_dir": os.environ.get("PADDLE_TPU_AUTOTUNE_DIR", ""),
    # minimum decode batch (slot count) at which paged attention routes
    # to the Pallas kernel instead of the pure-jax reference when
    # kernels are enabled. 1 = kernel always — a cold-cache default
    # with no chip measurement behind it yet (ROADMAP S2), which the
    # tuner overrides per device kind (Ragged Paged Attention
    # motivates per-chip routing)
    "paged_min_slots": 1,
    # mixed precision: bf16 MXU operands with f32 accumulation for
    # conv/matmul (master weights and the rest of the graph stay f32) —
    # the standard TPU training configuration
    "amp": False,
    # tally while-loop step-fn evaluations via a host callback (tests use
    # it to pin the checkpointed while-grad at O(T) step evals)
    "count_while_step_evals": False,
    # escalate UNEXPECTED shape-inference failures (emitter bugs) from a
    # warn-once to a hard build-time error — the reference InferShape
    # enforce semantics (shape_inference.h). CI enables this; the warn
    # default keeps a conservative emitter from bricking user programs.
    "strict_shape_inference": False,
    # XLA cost accounting per compiled executable (ISSUE 3):
    #   'auto'/True = after each jit-cache miss, re-lower the program
    #                 (pure tracing, NO second XLA compile) and record
    #                 cost_analysis() flops/bytes into gauges + the
    #                 executor.compile_report() ring
    #   'full'      = additionally AOT-compile for memory_analysis()
    #                 (argument/temp/code bytes) — a REAL second XLA
    #                 compile per executable; benches opt in, training
    #                 loops shouldn't
    #   False       = off (no extra lowering at all)
    "compile_stats": "auto",
    # run the static program verifier (paddle_tpu.analysis.verify) before
    # lowering each new (program, feed signature) the executor compiles:
    # structural checks only (use-before-def, unknown vars/ops, block
    # nesting — not the abstract-eval shape re-check), so the cost is one
    # O(ops) walk per jit-cache MISS, never per step. Off by default for
    # users (the build-time inference already guards the common path);
    # tests/conftest.py turns it on suite-wide so every program any test
    # runs is verified.
    "verify_programs": False,
    # record host spans into paddle_tpu.observability.tracing from process
    # start (profiler()/trace_enable() also toggle at runtime). Purely a
    # host-side recorder: does NOT affect what gets traced/compiled, so
    # deliberately absent from trace_flags(). Reads are live (see _Flags);
    # the stored values here only seed `k in FLAGS` / sorted(FLAGS).
    "trace": _tracing.trace_enabled(),
    # span ring-buffer capacity (oldest spans drop past it)
    "trace_buffer": _tracing.buffer_capacity(),
    # deterministic fault-injection plan (distributed/faults.py spec
    # string, e.g. 'seed=7;drop@recv.push_grad:1,3'); None/'' = off.
    # Seeded from PADDLE_TPU_FAULTS; reads are live (see _Flags).
    "faults": None,
    # runtime sanitizers (ISSUE 7). 'guards' instruments the annotated
    # runtime classes (analysis/sanitize.py) so every access to a
    # '# guarded-by:'-declared attribute asserts its lock is held —
    # the dynamic validator of the static guards lint. Seeded from
    # PADDLE_TPU_SANITIZE at import; paddle_tpu/__init__ installs the
    # instrumentation at process start when set. '' = off.
    "sanitize": os.environ.get("PADDLE_TPU_SANITIZE", ""),
    # serving defaults (paddle_tpu/serving, ISSUE 5). The bucket ladder
    # is THE compile-bound knob: dynamic batches pad up to the next
    # ladder entry, so the executor jit cache holds at most one entry
    # per bucket per model version regardless of arrival pattern.
    "serving_buckets": "1,2,4,8,16",
    # admission bound: queue depth past which infer() is rejected with
    # ServerOverloaded instead of queueing into unbounded latency
    "serving_max_queue": 64,
    # batching timer: the oldest queued request waits at most this long
    # for batch-mates before its (possibly underfull) batch launches
    "serving_max_wait_ms": 5.0,
    # streaming generate (ISSUE 12): a token stream nobody polls for
    # this many seconds is presumed abandoned — the server cancels the
    # sequence (KV pages free immediately) and later continuations get
    # a typed StreamExpired. Generously past any sane client poll
    # cadence (frames block at most ~20s each by default)
    "serving_stream_ttl": 300.0,
    # decode serving (paddle_tpu/serving/decode.py, ISSUE 6). The slot
    # ladder is the decode analogue of serving_buckets: the fixed-slot
    # decode batch pads its slot count up to the next ladder entry, so
    # (together with the derived page-table-width ladder) the decode
    # step's jit cache is bounded at |slots| x |widths| shapes, all
    # pre-compiled at warm
    "decode_slots": "1,2,4",
    # KV page granularity in tokens. Smaller pages = less internal
    # fragmentation (reserve-at-admission rounds each sequence up to
    # whole pages) but wider page tables; 16 matches one v5e sublane
    # group of bf16 KV rows per head
    "kv_page_size": 16,
    # preallocated KV pool size in pages (page 0 is the reserved
    # garbage page): pages x page_size bounds decode HBM INDEPENDENT of
    # ragged sequence lengths — this is the decode admission bound
    "kv_num_pages": 128,
    # per-sequence cap on prompt + generated tokens; also sets the
    # page-table width ladder (ceil(max_seq_len / kv_page_size) is the
    # widest compiled table)
    "decode_max_seq_len": 128,
    # prefix caching (ISSUE 13): completed prompts publish their full
    # KV pages into a refcounted radix index; a request sharing a
    # cached prefix maps those pages read-only and prefills only its
    # suffix (steps-to-first-token drops to ceil(suffix/prefill_chunk))
    # with copy-on-write for the partial tail page. False = the PR 6
    # per-request-scratchpad pool, bit-identical
    "prefix_cache": True,
    # KV reservation policy (ISSUE 13): 'demand' reserves the prompt's
    # pages plus kv_decode_headroom pages at admission and grows
    # mid-decode — on exhaustion a victim spills to host and resumes
    # later (preempt-never-corrupts), so admitted concurrency is set by
    # ACTUAL token demand under long-tailed max_new_tokens;
    # 'worst_case' is the PR 6 ceil((prompt+max_new)/page_size)
    # reserve-at-admission policy (reserve-never-dies), kept as the
    # admitted-concurrency baseline
    "kv_reservation": "demand",
    # decode headroom (in pages) a demand-mode reservation adds past
    # the prompt, so the first generated tokens never immediately
    # trigger growth
    "kv_decode_headroom": 1,
    # where preempted sequences' KV pages spill ('' = host RAM; a
    # directory path = one .npz per preempted sequence, so heavy
    # preemption doesn't balloon the serving host's memory)
    "kv_spill_dir": "",
    # chunked prefill (ISSUE 10): per-step prompt-token budget AND the
    # compiled chunk width of the mixed decode step — a P-token prompt
    # completes prefill in ceil(P/prefill_chunk) steps instead of P.
    # 16 (= one kv_page_size of tokens per step) is the hand-set cold
    # default; the autotune cache overrides it per device kind
    # (DecodeEngine reads it through effective_flag; decode_bench's
    # measure-or-model session seeds measured values). 1 = chunking
    # off (bitwise the PR 6 one-token-per-step behavior)
    "prefill_chunk": 16,
    # speculative decoding (ISSUE 14): how many tokens the DRAFT
    # decoder proposes per live slot per scheduler round; the target
    # model then verifies all k+1 positions in ONE chunked step
    # (decoder_step_chunked rides the existing multi-token kernel), so
    # high draft/target agreement commits up to k+1 tokens per target
    # step. 0 = off (bit-identical non-speculative decode; engines
    # without a draft are always off regardless of this value). A PR 8
    # tunable: DecodeEngine reads it through effective_flag, so the
    # autotune cache overrides per device kind (decode_bench's
    # measure-or-model session persists the measured winner)
    "spec_k": 0,
    # SPMD mesh layer (paddle_tpu/mesh, ISSUE 15). Default TRAINING
    # mesh: a ParallelExecutor built without an explicit mesh= parses
    # this ("dp=2,tp=2,fsdp=2" — ordered named axes, sizes multiply to
    # the device count) and trains sharded; '' = the plain all-devices
    # dp mesh (bit-identical pre-mesh behavior). Pair with a
    # ShardingRules plan (mesh.transformer_rules gives the dp x tp x
    # fsdp layout for the flagship transformer)
    "mesh_axes": "",
    # default SERVING mesh for DecodeEngine/load_decoder: '' = single-
    # chip (the PR 6 engine); an axes string makes one decode replica
    # SPAN chips — params shard per mesh.decoder_rules and the paged KV
    # pool shards over the kv-head axis. A checkpoint that RECORDS a
    # mesh (save_decoder_checkpoint(mesh_axes=)) wins over this
    # default; an explicit load_decoder(mesh_axes=) wins over both
    "serving_mesh_axes": "",
    # serving fleet (paddle_tpu/fleet, ISSUE 11). Replica lease TTL in
    # seconds: a replica that misses heartbeats for this long is
    # evicted from the routing table (the pserver heartbeat/eviction
    # discipline applied to serving replicas; members beat at ttl/3)
    "fleet_lease_ttl": 5.0,
    # router-side load-report cache TTL in seconds: how stale a scraped
    # per-replica load report (free KV pages, queue depths) may be
    # before the next routing decision re-scrapes. Small = accurate
    # balancing, large = fewer load_report RPCs per routed request
    "fleet_scrape_ttl": 0.25,
    # autoscale policy loop (paddle_tpu/fleet/policy.py, ISSUE 17).
    # Evaluation cadence in seconds, and the hysteresis discipline:
    # a scale decision needs `fleet_policy_beats` CONSECUTIVE ticks of
    # the same verdict, and after any action the loop holds still for
    # `fleet_policy_cooldown` ticks (a spawning replica takes several
    # ticks to register — acting again before it lands would overshoot)
    "fleet_policy_interval": 0.5,
    "fleet_policy_beats": 3,
    "fleet_policy_cooldown": 8,
    # scale-UP floors: intent when fleet-wide free KV pages OR queue
    # headroom sits below these for `beats` consecutive ticks
    "fleet_free_page_floor": 8,
    "fleet_headroom_floor": 2,
    # scale-DOWN hysteresis margin: the fleet MINUS the drain victim
    # must retain margin x both scale-up floors — the dead band between
    # the up floor and the down bar is what keeps a boundary load from
    # flapping the fleet up and down forever
    "fleet_scale_margin": 2.0,
    # replica-count bounds the policy loop may never cross
    "fleet_min_replicas": 1,
    "fleet_max_replicas": 4,
    # replica-launcher crash-restart backoff base in seconds (doubles
    # per consecutive crash, capped launcher-side)
    "fleet_launcher_backoff": 0.25,
    # intent signing + deploy-path allowlist (fleet/auth.py). Key '' =
    # open mode (unsigned intents, bit-identical PR 11 behavior); the
    # PADDLE_TPU_FLEET_KEY env var wins over the flag so launcher-
    # spawned replica subprocesses inherit it. The allowlist is a
    # ':'-separated list of absolute dir prefixes every checkpoint_dir/
    # dirname/draft_checkpoint_dir payload path must resolve under
    # (PADDLE_TPU_FLEET_ALLOW env wins; '' = unrestricted)
    "fleet_intent_key": "",
    # previous fleet key, ACCEPTED (verify-only) during a key rotation
    # window (PADDLE_TPU_FLEET_KEY_PREV env wins). Producers always
    # sign with fleet_intent_key; set this to the old key on every
    # verifier before flipping producers, clear it when
    # fleet.auth.verified.prev_key stops moving. '' = no window
    "fleet_intent_key_prev": "",
    "fleet_intent_allowlist": "",
})


def pallas_enabled() -> bool:
    import jax

    v = FLAGS["use_pallas_kernels"]
    if v == "auto":
        return jax.default_backend() == "tpu"
    return bool(v)


def pallas_interpret() -> bool:
    """Off-TPU the kernels must run in interpreter mode."""
    import jax

    return jax.default_backend() != "tpu"


def set_flags(d: Dict[str, Any]):
    for k, v in d.items():
        if k not in FLAGS:
            raise KeyError(f"unknown flag {k!r}; known: {sorted(FLAGS)}")
        FLAGS[k] = v
        # propagate to the live recorder so set_flags is a complete
        # control surface. Each key acts independently: resizing the
        # buffer must not flip the enable bit (a profiler()-enabled
        # session stays enabled), and vice versa.
        if k == "trace":
            if v:
                _tracing.trace_enable(buffer_size=FLAGS["trace_buffer"])
            else:
                _tracing.trace_disable()
        elif k == "trace_buffer":
            _tracing.resize_buffer(int(v))
        elif k == "faults":
            from ..distributed import faults as _faults

            if v:
                _faults.install(v)
            else:
                _faults.uninstall()


def get_flag(name: str):
    return FLAGS[name]


def effective_flag(name: str, count: bool = True):
    """A routing knob's EFFECTIVE value: the FLAGS entry is the
    cold-cache default; with FLAGS['autotune'] on, a measured/derived/
    override record for this device kind in the tuning cache wins
    (each resolution counts autotune.cache.hits/misses — the evidence
    that routing reads THROUGH the cache; trace_flags passes
    count=False so per-step jit-key construction doesn't drown the
    handful of real route resolutions in thousands of increments).
    Off, this is exactly get_flag — zero overhead, bit-identical
    behavior."""
    base = FLAGS[name]
    if not FLAGS["autotune"]:
        return base
    from ..autotune import tuned_value

    return tuned_value(name, default=base, count=count)


def init_gflags(args=None):
    """reference core.init_gflags (pybind.cc:465) — accepts '--name=value'."""
    for a in args or []:
        a = a.lstrip("-")
        if "=" in a:
            k, v = a.split("=", 1)
            if v in ("true", "True"):
                v = True
            elif v in ("false", "False"):
                v = False
            set_flags({k: v})


def trace_flags() -> tuple:
    """Flags that change what gets TRACED (and therefore compiled): any
    executor jit-cache key must include them, or toggling a flag after the
    first run of a program would be silently ignored. Routing thresholds
    enter at their EFFECTIVE (tuner-resolved) value: a tuning-cache
    update changes the key, so stale executables compiled under the old
    threshold are never replayed for the new routing."""
    return (FLAGS["matmul_precision"], FLAGS["use_pallas_kernels"],
            FLAGS["amp"], FLAGS["count_while_step_evals"],
            effective_flag("flash_min_seq", count=False),
            effective_flag("paged_min_slots", count=False))
