"""Executor: lowers a Program block to ONE jitted XLA computation.

Capability-parity with the reference Executor (`paddle/fluid/framework/
executor.cc:133`, `python/paddle/fluid/executor.py:181`), rebuilt as a
compiler client:

  - The reference interprets ops one-by-one per minibatch (executor.cc:344).
    Here `_lower()` traces all op emitters in program order into a single
    Python function, jit-compiles it once per (program version, feed
    signature), and replays the compiled XLA executable per step. XLA fuses
    elementwise chains into the matmuls/convs — the op boundary exists only
    in the IR.
  - Scope (reference scope.h:39) maps var name -> device-resident jax.Array.
    Persistable vars (params, optimizer accumulators, BN stats) stay in HBM
    across steps; written state buffers are donated so updates are in-place
    at the XLA level.
  - Feed/fetch: numpy in, numpy out (reference feed_op/fetch_op become jit
    arguments/results).
"""
from __future__ import annotations

import functools
import time as _time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import core
from ..observability import metrics as _metrics, tracing as _tracing
from .flags import FLAGS
from .framework import Program, Variable, default_main_program
from .registry import EmitCtx, exec_op_descs

from .readers import READER_CREATE_OP_TYPES, create_host_reader

# observability handles (ISSUE 1): flat counters + the per-step latency
# histogram. jit_compiles vs jit_cache_hits is the first-class signal that
# a feed-shape or flag churn is retracing the program every step;
# feed_sig_cache_miss isolates the misses caused by a NEW feed signature
# against an already-compiled program version.
_m_jit_compiles = _metrics.counter("executor.jit_compiles")
_m_jit_cache_hits = _metrics.counter("executor.jit_cache_hits")
_m_feed_sig_misses = _metrics.counter("executor.feed_sig_cache_miss")
_m_step_ms = _metrics.histogram("executor.step_ms")

# XLA cost accounting (ISSUE 3): per-compiled-executable flops/bytes
# gauges (last compile wins — the report ring keeps history) plus a
# bounded compile_report() every BENCH artifact embeds, so a perf claim
# carries what the compiler SAYS the step costs next to what the wall
# clock measured. FLAGS["compile_stats"] controls the collection mode.
_m_c_flops = _metrics.gauge("executor.compile.flops")
_m_c_bytes = _metrics.gauge("executor.compile.bytes_accessed")
_m_c_trans = _metrics.gauge("executor.compile.transcendentals")
_m_c_temp = _metrics.gauge("executor.compile.temp_bytes")
_m_c_args = _metrics.gauge("executor.compile.argument_bytes")

import collections as _collections

_compile_reports: "_collections.deque" = _collections.deque(maxlen=256)


def compile_report() -> List[Dict[str, Any]]:
    """Per-compiled-executable cost records (oldest first, last 256):
    program version, feed count, cost_analysis flops/bytes, and — under
    FLAGS["compile_stats"]="full" — memory_analysis byte counts. The
    compile-cost half of every BENCH evidence dict."""
    return list(_compile_reports)


def reset_compile_report():
    _compile_reports.clear()


def _record_compile_cost(program, jfn, feed_arrays, ro_names, rw_names,
                         scope, fetch_names):
    """Best-effort: a broken analysis must never break the run. 'auto'
    costs ONE extra program trace (Lowered.cost_analysis walks the
    unoptimized HLO — no XLA compile); 'full' pays a real second compile
    for memory_analysis."""
    mode = FLAGS["compile_stats"]
    if not mode:
        return
    from .. import jax_compat as _jc

    try:
        t0 = _time.perf_counter()
        with _tracing.span("executor.compile_stats",
                           program_version=program._version):
            low = jfn.lower(
                feed_arrays,
                {n: scope.find_var(n) for n in ro_names},
                {n: scope.find_var(n) for n in rw_names},
                np.zeros((3,), np.uint32),
            )
            cost = _jc.cost_analysis_dict(low)
            rec: Dict[str, Any] = {
                "program_version": program._version,
                "n_feeds": len(feed_arrays),
                "n_fetches": len(fetch_names),
                "flops": cost.get("flops"),
                "bytes_accessed": cost.get("bytes accessed"),
                "transcendentals": cost.get("transcendentals"),
            }
            if mode == "full":
                tc = _time.perf_counter()
                comp = low.compile()
                rec["compile_ms"] = round(
                    (_time.perf_counter() - tc) * 1e3, 3)
                if not cost:  # some backends only cost the Compiled
                    cost = _jc.cost_analysis_dict(comp)
                    rec["flops"] = cost.get("flops")
                    rec["bytes_accessed"] = cost.get("bytes accessed")
                mem = _jc.memory_analysis_dict(comp)
                rec["memory"] = mem
                if "temp_size_in_bytes" in mem:
                    _m_c_temp.set(mem["temp_size_in_bytes"])
                if "argument_size_in_bytes" in mem:
                    _m_c_args.set(mem["argument_size_in_bytes"])
            rec["analysis_ms"] = round((_time.perf_counter() - t0) * 1e3, 3)
        if rec.get("flops") is not None:
            _m_c_flops.set(rec["flops"])
        if rec.get("bytes_accessed") is not None:
            _m_c_bytes.set(rec["bytes_accessed"])
        if rec.get("transcendentals") is not None:
            _m_c_trans.set(rec["transcendentals"])
        _compile_reports.append(rec)
    except Exception as e:  # evidence is optional, training is not
        from ..observability.log import get_logger

        get_logger("executor").debug("compile_stats failed: %s: %s",
                                     type(e).__name__, e)

# ops the device program never sees: feed/fetch plumbing, the host-side
# reader stack (creation ops run in the startup pre-pass; `read` resolves to
# jit feed arrays each step — readers.py explains the design), and the
# pserver transport ops (send/recv/send_barrier run as host RPC around the
# jitted step — reference send_op.cc/recv_op.cc/send_barrier_op.cc)
_SKIP_OP_TYPES = (
    {"feed", "fetch", "read", "send", "recv", "send_barrier", "send_vars",
     "prefetch", "save", "save_combine", "load", "load_combine"}
    | set(READER_CREATE_OP_TYPES)
)


class Scope:
    """name -> device array map (reference framework/scope.h:39)."""

    def __init__(self, parent: Optional["Scope"] = None):
        self._vars: Dict[str, Any] = {}
        self.parent = parent

    def find_var(self, name: str):
        s: Optional[Scope] = self
        while s is not None:
            if name in s._vars:
                return s._vars[name]
            s = s.parent
        return None

    def has_var(self, name: str) -> bool:
        return self.find_var(name) is not None

    def set_var(self, name: str, value):
        self._vars[name] = value

    def drop_var(self, name: str):
        self._vars.pop(name, None)

    def var_names(self):
        return list(self._vars)

    def new_scope(self) -> "Scope":
        return Scope(parent=self)


_global_scope = Scope()

import threading as _threading

# Per-thread guard stack (reference scope_guard swaps a process global, but
# its multithread inference path gives each thread its own Scope — a shared
# mutable "current scope" made concurrent predictors read each other's
# scopes, caught by the multithreaded C-API test). A thread with no guards
# of its own sees the process root scope.
_scope_tls = _threading.local()


def global_scope() -> Scope:
    stack = getattr(_scope_tls, "stack", None)
    return stack[-1] if stack else _global_scope


import contextlib


@contextlib.contextmanager
def scope_guard(scope: Scope):
    stack = getattr(_scope_tls, "stack", None)
    if stack is None:
        stack = _scope_tls.stack = []
    stack.append(scope)
    try:
        yield
    finally:
        # pop OUR frame by identity, unwinding any frames the body left
        # above it (e.g. an unmatched enter_local_scope) — a blind pop()
        # would remove the orphan and silently leak `scope` as the
        # thread's current scope forever
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] is scope:
                del stack[i:]
                break


def fetch_var(name: str, scope: Optional[Scope] = None, return_numpy: bool = True):
    scope = scope or global_scope()
    v = scope.find_var(name)
    if v is None:
        raise ValueError(f"var '{name}' not found in scope")
    return np.asarray(v) if return_numpy else v


def _as_name(v) -> str:
    return v.name if isinstance(v, Variable) else str(v)


def _run_reader_host_ops(block, scope: Scope) -> Dict[str, Any]:
    """Host pre-pass over a block's reader ops (reference executor.cc runs
    reader ops as ordinary OperatorBase; here they can't enter the jitted
    program). Creation ops (re)build the host reader stack into scope —
    so re-running the startup program resets the pipeline, like the
    reference's ReInit. `read` ops pop one minibatch and return it as feed
    arrays for the device program. Raises core.EOFException at end of
    data."""
    # per-program-version cache of the reader ops: the common reader-less
    # program pays one dict lookup per step, not an O(n_ops) scan
    program = block.program
    cached = getattr(program, "_reader_ops_cache", None)
    if cached is None or cached[0] != program._version:
        reader_ops = [
            op for op in block.ops
            if op.desc.type in READER_CREATE_OP_TYPES
            or op.desc.type == "read"
        ]
        program._reader_ops_cache = cached = (program._version, reader_ops)
    if not cached[1]:
        return {}
    feeds: Dict[str, Any] = {}
    for op in cached[1]:
        t = op.desc.type
        if t in READER_CREATE_OP_TYPES:
            out_name = op.desc.outputs["Out"][0]
            inner_names = op.desc.inputs.get("UnderlyingReader") or []
            inner = scope.find_var(inner_names[0]) if inner_names else None
            old = scope.find_var(out_name)
            if old is not None and hasattr(old, "close"):
                old.close()  # free prefetch threads / file handles
            out_var = block._var_recursive(out_name)
            slots = out_var.desc.reader_slots if out_var is not None else None
            scope.set_var(
                out_name,
                create_host_reader(t, op.desc.attrs, inner, slots=slots),
            )
        elif t == "read":
            reader_name = op.desc.inputs["Reader"][0]
            reader = scope.find_var(reader_name)
            if reader is None or not hasattr(reader, "read_next"):
                raise RuntimeError(
                    f"reader var '{reader_name}' has no host reader in "
                    "scope — run the startup program first"
                )
            try:
                sample = reader.read_next()
            except StopIteration:
                raise core.EOFException(
                    f"reader '{reader_name}' is exhausted"
                ) from None
            out_names = op.desc.outputs["Out"]
            if len(sample) != len(out_names):
                raise ValueError(
                    f"reader '{reader_name}' produced {len(sample)} slots, "
                    f"the read op declares {len(out_names)}"
                )
            for name, slot in zip(out_names, sample):
                if isinstance(slot, tuple):  # (padded, lengths) ragged pair
                    feeds[name], feeds[name + "@LEN"] = slot
                else:
                    feeds[name] = _conform_slot(block, name, slot)
    return feeds


def _as_feed(v):
    """Feed-dict value -> jit argument. SelectedRows pass through as the
    pytree they are (a pserver feeds sparse grads straight to the row-wise
    lazy optimizer ops)."""
    from .selected_rows import is_selected_rows

    if is_selected_rows(v) or isinstance(v, jax.Array):
        return v
    return jnp.asarray(v)


def _feed_sig_entry(v):
    from .selected_rows import is_selected_rows

    if is_selected_rows(v):
        return ("selrows", tuple(v.rows.shape), tuple(v.value.shape),
                str(v.value.dtype), v.height)
    return (tuple(v.shape), str(v.dtype))


def _dist_host_ops(block):
    """(send ops, recv ops, prefetch ops) of a block, cached per program
    version."""
    program = block.program
    cached = getattr(program, "_dist_ops_cache", None)
    if cached is None or cached[0] != program._version:
        # send_vars is the reference's async-send variant (send_vars_op.cc)
        # — same transport here, no barrier follows it
        sends = [op for op in block.ops
                 if op.desc.type in ("send", "send_vars", "send_barrier")]
        recvs = [op for op in block.ops if op.desc.type == "recv"]
        prefetches = [op for op in block.ops if op.desc.type == "prefetch"]
        program._dist_ops_cache = cached = (
            program._version, sends, recvs, prefetches)
    return cached[1], cached[2], cached[3]


def _run_recv_ops(recv_ops, scope: Scope):
    """Pull current param values from their pservers into scope BEFORE the
    step (reference recv_op.cc + concat on the trainer)."""
    from ..distributed.param_server import get_client

    for op in recv_ops:
        eps = op.desc.attrs.get("endpoints", {})
        for name in op.desc.outputs.get("Out", []):
            ep = eps.get(name)
            if ep is None:
                raise ValueError(f"recv op has no endpoint for '{name}'")
            # copy_result=False: the pulled tensor is a read-only view
            # over the RPC frame, consumed straight into jnp.asarray —
            # the old receive-side host copy was pure overhead
            scope.set_var(name, jnp.asarray(get_client(ep).call(
                "get_param", name, copy_result=False)))


def _run_prefetch_ops(prefetch_ops, feed_arrays: Dict[str, Any],
                      scope: Scope):
    """Row-granular embedding prefetch (reference prefetch_op.cc): pull
    ONLY the batch's unique rows from the pserver into a sub-table fed to
    the device step, plus locally-remapped ids. The sub-table is padded to
    the flat id count so feed shapes — and therefore the jit cache entry —
    depend only on the batch shape. The unique-id map is stashed in scope
    for the send op to translate the SelectedRows grad rows back to global
    before the push."""
    from ..distributed.param_server import get_client

    for op in prefetch_ops:
        attrs = op.desc.attrs
        ids_name = op.desc.inputs["Ids"][0]
        sub_name = op.desc.outputs["Out"][0]
        remap_name = op.desc.outputs["Remap"][0]
        ids = feed_arrays.get(ids_name)
        if ids is None:
            raise RuntimeError(
                f"prefetch op needs '{ids_name}' in the feed (ids must be "
                "host-visible to pull their rows)")
        ids = np.asarray(ids)
        flat = ids.reshape(-1).astype(np.int64)
        uniq, inverse = np.unique(flat, return_inverse=True)
        cap = max(1, flat.size)
        pad_fill = uniq[0] if uniq.size else 0
        uniq_padded = np.full((cap,), pad_fill, dtype=np.int64)
        uniq_padded[:uniq.size] = uniq
        # copy_result=False: the sub-table is a read-only view over the
        # RPC frame; copy-on-write below only when a row must be zeroed
        sub = np.asarray(get_client(attrs["endpoint"]).call(
            "get_rows", attrs["param"], uniq_padded, copy_result=False))
        padding_idx = int(attrs.get("padding_idx", -1))
        if padding_idx != -1:
            # the op-level padding zeroing was disabled at transpile time;
            # zero the padding id's row here instead (each unique id owns
            # exactly one row, so this is equivalent)
            pos = np.searchsorted(uniq, padding_idx)
            if pos < uniq.size and uniq[pos] == padding_idx:
                if not sub.flags.writeable:
                    sub = sub.copy()
                sub[pos] = 0
        feed_arrays[sub_name] = sub
        feed_arrays[remap_name] = inverse.reshape(ids.shape).astype(np.int64)
        scope.set_var(f"{attrs['param']}@PREFETCH_IDS", uniq_padded)


def _run_send_ops(send_ops, values: Dict[str, Any],
                  scope: Optional[Scope] = None):
    """Push computed gradients to their pservers AFTER the step (reference
    send_op.cc AsyncSendVariable; send_barrier_op for sync rounds). The
    barrier waits on the round number the pushes were assigned to, over a
    DEDICATED connection — on the shared channel a blocking barrier would
    starve other trainer threads' pushes to the same endpoint."""
    from .selected_rows import is_selected_rows
    from ..distributed.param_server import (get_client,
                                            note_barrier_reply)

    push_round: Dict[str, int] = {}  # endpoint -> round of this step's sends
    for op in send_ops:
        attrs = op.desc.attrs
        if op.desc.type == "send_barrier":
            tid = int(attrs.get("trainer_id", 0))
            for ep in attrs.get("endpoints", []):
                # trainer_id rides along so the pserver's failure detector
                # refreshes THIS trainer's heartbeat lease while it waits —
                # a parked trainer must never be evicted as dead, or its
                # pending pushes would be withdrawn from the round
                resp = get_client(ep, channel=f"barrier.{tid}").call(
                    "barrier", push_round.get(ep), tid)
                note_barrier_reply(ep, tid, resp)
            continue
        eps = attrs.get("endpoints", {})
        params = attrs.get("params", {})
        sparse_remap = attrs.get("sparse_remap", {})
        trainer_id = int(attrs.get("trainer_id", 0))
        for gname in op.desc.inputs.get("X", []):
            v = values[gname]
            if gname in sparse_remap and is_selected_rows(v):
                # prefetched table: grad rows are LOCAL sub-table indices;
                # translate back to global ids (and drop padding-id rows —
                # the reference zeroes their grad) before the push
                from .selected_rows import SelectedRows

                info = sparse_remap[gname]
                idmap = scope.find_var(
                    f"{info['param']}@PREFETCH_IDS") if scope else None
                if idmap is None:
                    raise RuntimeError(
                        f"send op: no prefetch id map for '{info['param']}' "
                        "— did the prefetch op run this step?")
                rows = np.asarray(idmap)[np.asarray(v.rows)]
                vals = np.asarray(v.value)
                pad = int(info.get("padding_idx", -1))
                if pad != -1:
                    keep = rows != pad
                    rows, vals = rows[keep], vals[keep]
                v = SelectedRows(rows.astype(np.int64), vals,
                                 int(info["vocab"]))
            elif gname in sparse_remap:
                # a remapped grad that arrives dense is [batch-ids, dim]
                # sub-table shaped — pushing it against the [vocab, dim]
                # pserver param would fail (or mis-apply) far from the
                # cause; fail HERE with the cause named
                info = sparse_remap[gname]
                raise RuntimeError(
                    f"send op: grad '{gname}' for prefetched table "
                    f"'{info['param']}' arrived dense (shape "
                    f"{np.asarray(v).shape}) but must be SelectedRows "
                    "over local sub-table rows — the lookup_table grad "
                    "emitter fell back to a dense gradient")
            elif not is_selected_rows(v):
                v = np.asarray(v)
            resp = get_client(eps[gname]).call(
                "push_grad", params.get(gname, gname), v, trainer_id)
            ep = eps[gname]
            if ep not in push_round and isinstance(resp, dict):
                push_round[ep] = resp.get("round")
        # the reference send op's get_vars: pull AFTER this op's pushes —
        # and after the round they joined has APPLIED (a sync server only
        # merges once every trainer pushed; barrier is a no-op on async)
        recv_eps = attrs.get("recv_endpoints", {})
        out_names = op.desc.outputs.get("Out", [])
        if out_names:
            if scope is None:
                raise RuntimeError("send op with get_vars needs a scope")
            for ep in {recv_eps[n] for n in out_names}:
                if ep in push_round:
                    get_client(ep, channel=f"barrier.{trainer_id}").call(
                        "barrier", push_round[ep], trainer_id)
            for name in out_names:
                # copy_result=False: consumed straight into jnp.asarray,
                # same zero-copy receive as _run_recv_ops above
                scope.set_var(name, jnp.asarray(
                    get_client(recv_eps[name]).call(
                        "get_param", name, copy_result=False)))


_IO_OP_TYPES = frozenset({"save", "save_combine", "load", "load_combine"})


def _io_path(op_type: str, path: str) -> str:
    """The actual on-disk path: numpy appends .npy/.npz when missing, so
    normalize once here — save's overwrite check, load's lookup, and the
    write all agree for any attr spelling."""
    if op_type in ("save", "load"):
        return path if path.endswith(".npy") else path + ".npy"
    return path if path.endswith(".npz") else path + ".npz"


def _split_io_host_ops(block):
    """(pre ops, post ops): io ops before the first device op run BEFORE
    the jitted step (loads feeding it); io ops after the last device op run
    AFTER it (saves of updated state — the reference's in-order C++
    executor gives save_op post-update values, so must we). An io op
    sandwiched BETWEEN device ops has no faithful slot in the
    one-XLA-program execution model: reject it loudly instead of silently
    saving stale values."""
    program = block.program
    cached = getattr(program, "_io_ops_cache", None)
    if cached is None or cached[0] != program._version:
        first_dev = last_dev = None
        for i, op in enumerate(block.ops):
            if op.desc.type not in _SKIP_OP_TYPES:
                if first_dev is None:
                    first_dev = i
                last_dev = i
        pre, post = [], []
        for i, op in enumerate(block.ops):
            if op.desc.type not in _IO_OP_TYPES:
                continue
            if first_dev is None or i < first_dev:
                pre.append(op)
            elif i > last_dev:
                post.append(op)
            else:
                raise RuntimeError(
                    f"{op.desc.type} op at position {i} sits between device "
                    "ops — the block lowers to ONE XLA computation, so "
                    "host-side save/load can only run before or after it; "
                    "move the op to the program's edge or a separate program"
                )
        program._io_ops_cache = cached = (program._version, pre, post)
    return cached[1], cached[2]


def _run_io_host_ops(ops, scope: Scope, extra: Optional[Dict] = None):
    """Execute save/load host ops (reference operators/save_op.cc,
    load_combine_op.cc). Formats match io.py: .npy per var, .npz combined.
    Every failure condition (missing var, overwrite conflict) is checked
    BEFORE any file is written, so an abort can't leave a partial
    checkpoint on disk. `extra` overlays values not living in scope —
    trailing saves of non-persistable temps get them fetched out of the
    jitted step (same mechanism as send ops)."""
    if not ops:
        return
    import os

    extra = extra or {}

    def lookup(n):
        return extra[n] if n in extra else scope.find_var(n)

    will_load = set()  # vars produced by earlier load ops in this group
    for op in ops:
        t = op.desc.type
        if t in ("load", "load_combine"):
            will_load.update(op.desc.outputs.get("Out", []))
            continue
        for n in op.desc.inputs.get("X", []):
            if lookup(n) is None and n not in will_load:
                raise RuntimeError(
                    f"save op: var '{n}' not found in scope — nothing "
                    "was written")
        path = _io_path(t, str(op.desc.attrs["file_path"]))
        if not op.desc.attrs.get("overwrite", True) and \
                os.path.exists(path):
            raise RuntimeError(f"save op: '{path}' exists and "
                               "overwrite=False — nothing was written")
    for op in ops:
        t = op.desc.type
        path = _io_path(t, str(op.desc.attrs["file_path"]))
        if t in ("save", "save_combine"):
            names = op.desc.inputs.get("X", [])
            arrays = {n: np.asarray(lookup(n)) for n in names}
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            if t == "save":
                np.save(path, arrays[names[0]])
            else:
                np.savez(path, **arrays)
        else:
            names = op.desc.outputs.get("Out", [])
            if t == "load":
                scope.set_var(names[0], jnp.asarray(np.load(path)))
            else:
                payload = np.load(path)
                for n in names:
                    scope.set_var(n, jnp.asarray(payload[n]))


def _conform_slot(block, name: str, slot):
    """Reshape/cast a popped batch to the declared out-var desc (the role
    DataFeeder's converters play on the feed path): record files store flat
    samples (e.g. mnist's 784-vector), the graph declares [-1, 1, 28, 28]."""
    if isinstance(slot, jax.Array):
        # a double-buffered batch was already conformed (and device_put) in
        # the worker thread — don't re-dispatch a reshape on the step loop
        return slot
    var = block._var_recursive(name)
    if var is None or var.shape is None:
        return slot
    shape = list(var.shape)
    if shape.count(-1) <= 1 and tuple(shape) != tuple(slot.shape):
        slot = slot.reshape(shape)
    if isinstance(slot, np.ndarray):
        want = np.dtype(core.convert_dtype(var.dtype)
                        if var.dtype != "bfloat16" else "float32")
        if slot.dtype != want:
            slot = slot.astype(want)
    return slot


def _block_io(block, feed_names: set, scope: Scope):
    """Classify vars of a block: state read (from scope), state written
    (persistable -> survives the run), and which must exist beforehand."""
    produced = set(feed_names)
    state_in: List[str] = []
    state_out: List[str] = []
    persistable = {
        name for name, var in block.vars.items() if var.persistable
    }
    for op in block.ops:
        if op.desc.type in _SKIP_OP_TYPES:
            continue
        for n in op.desc.input_names():
            if n and n not in produced and n not in state_in:
                state_in.append(n)
        for n in op.desc.output_names():
            if n:
                produced.add(n)
                if n in persistable and n not in state_out:
                    state_out.append(n)
    return state_in, state_out


def _lower(block, feed_names: Tuple[str, ...], fetch_names: Tuple[str, ...],
           state_in: Tuple[str, ...], state_out: Tuple[str, ...]):
    """Build the pure function feed, state_ro, state_rw, seed -> fetches,
    new_state. `seed` is a uint32[3] = (root, salt, tick) vector (see
    _next_seed): the PRNG key derives from it INSIDE the trace, so each
    run() costs one small array argument instead of 2-3 eager
    key/fold_in dispatches on the host + device (measured ~0.25 ms/step
    of pure-host time). All three components are traced values — changing
    program.random_seed between runs reuses the SAME compiled executable
    (no per-seed retrace), and the seeded stream is bit-identical to the old eager
    fold_in(fold_in(key(seed), salt), tick) chain."""
    program = block.program
    ops = [op.desc for op in block.ops if op.desc.type not in _SKIP_OP_TYPES]
    ro_names = tuple(n for n in state_in if n not in state_out)
    rw_names = tuple(n for n in state_in if n in state_out)

    def fn(feeds: Dict[str, Any], state_ro: Dict[str, Any],
           state_rw: Dict[str, Any], seed):
        with jax.default_matmul_precision(FLAGS["matmul_precision"]):
            return _body(feeds, state_ro, state_rw, seed)

    def _body(feeds, state_ro, state_rw, seed):
        seed = jnp.asarray(seed)
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(seed[0]), seed[1]), seed[2])
        env: Dict[str, Any] = {}
        env.update(state_ro)
        env.update(state_rw)
        env.update(feeds)
        ctx = EmitCtx(root_key=key, program=program)
        exec_op_descs(ctx, ops, env, keep=frozenset(fetch_names))
        fetches = []
        for n in fetch_names:
            if n not in env:
                raise ValueError(f"fetch target '{n}' was not produced by the block")
            fetches.append(env[n])
        new_state = {n: env[n] for n in state_out if n in env}
        return fetches, new_state

    return fn, ro_names, rw_names


class Executor:
    """Reference python/paddle/fluid/executor.py:181 — same run() contract."""

    def __init__(self, place: Optional[core.Place] = None):
        import weakref

        self.place = place or core.default_place()
        # outer weak map keyed by the live Program object (avoids id() reuse
        # after GC); inner dict keyed by (version, feed signature, fetches)
        self._cache: "weakref.WeakKeyDictionary[Program, Dict[Any, Any]]" = (
            weakref.WeakKeyDictionary()
        )

    def run(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence[Any]] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,
    ):
        program = program or default_main_program()
        t0 = _time.perf_counter()
        with _tracing.span("executor.step",
                           program_version=program._version):
            out = self._run_body(program, feed, fetch_list, scope,
                                 return_numpy, use_program_cache)
        step_ms = (_time.perf_counter() - t0) * 1000.0
        _m_step_ms.observe(step_ms)
        if FLAGS["autotune"] and return_numpy and \
                not getattr(self, "_last_run_compiled", True):
            # feed the tuning cache's per-shape step log (ISSUE 8) so a
            # repeat session can skip re-measuring this exact
            # (program, feed-shape) pair. Compile runs are excluded
            # (they'd poison the steady-state median), and so are
            # return_numpy=False runs: the numpy conversion inside
            # _run_body is the device barrier, so without it the wall
            # clock measures async DISPATCH, not the step
            from ..autotune.measure import note_step_timing

            try:
                note_step_timing("executor.step", program, feed or {},
                                 step_ms)
            except Exception:  # the log is evidence, the run is not
                pass
        return out

    def _run_body(self, program, feed, fetch_list, scope, return_numpy,
                  use_program_cache):
        # True until the jitted-step site proves otherwise: host-only
        # programs and compile runs never enter the step-timing log
        self._last_run_compiled = True
        feed = feed or {}
        fetch_list = fetch_list or []
        scope = scope or global_scope()

        block = program.global_block()
        io_pre, io_post = _split_io_host_ops(block)
        _run_io_host_ops(io_pre, scope)
        # host-only program (the io.py save/load flow): nothing to trace —
        # skip the jit machinery entirely rather than compiling an empty
        # XLA computation per checkpoint call
        if not any(op.desc.type not in _SKIP_OP_TYPES for op in block.ops):
            # readers/io/transport still run; fetches resolve straight from
            # host values (a read-only program fetching its minibatch, or a
            # recv-only parameter pull)
            with _tracing.span("executor.reader"):
                host_feeds = _run_reader_host_ops(block, scope)
            send_ops, recv_ops, _ = _dist_host_ops(block)
            if recv_ops:
                with _tracing.span("executor.recv"):
                    _run_recv_ops(recv_ops, scope)
            if send_ops:
                vals = {}
                for op in send_ops:
                    for n in op.desc.inputs.get("X", []):
                        v = host_feeds.get(n, feed.get(n, scope.find_var(n)))
                        if v is None:
                            raise RuntimeError(
                                f"send op: var '{n}' has no value (no "
                                "device ops produce it in this program)")
                        vals[n] = v
                with _tracing.span("executor.send"):
                    _run_send_ops(send_ops, vals, scope)
            _run_io_host_ops(io_post, scope)
            out = []
            for v in fetch_list or []:
                n = _as_name(v)
                val = host_feeds.get(n, feed.get(n, scope.find_var(n)))
                if val is None:
                    raise ValueError(
                        f"fetch target '{n}' not produced — the program "
                        "has no device ops")
                out.append(np.asarray(val) if return_numpy else val)
            return out
        with _tracing.span("executor.reader"):
            reader_feeds = _run_reader_host_ops(block, scope)
        feed_arrays = {
            k: _as_feed(v) for k, v in {**feed, **reader_feeds}.items()
        }
        fetch_names = tuple(_as_name(v) for v in fetch_list)
        # send ops (host-side, reference send_op.cc) transport gradient
        # values: fetch them out of the jitted step, push after it runs.
        # Trailing saves of non-persistable temps ride the same mechanism.
        send_ops, recv_ops, prefetch_ops = _dist_host_ops(block)
        if recv_ops:
            with _tracing.span("executor.recv"):
                _run_recv_ops(recv_ops, scope)
        if prefetch_ops:
            with _tracing.span("executor.prefetch"):
                _run_prefetch_ops(prefetch_ops, feed_arrays, scope)
        want: List[str] = []
        if send_ops:
            want += [n for op in send_ops
                     for n in op.desc.inputs.get("X", []) if n]
        save_want = [
            n for op in io_post if op.desc.type in ("save", "save_combine")
            for n in op.desc.inputs.get("X", [])
            if n and scope.find_var(n) is None
        ]
        want += save_want
        extra_fetches = tuple(dict.fromkeys(
            n for n in want if n not in fetch_names))
        jfn, ro_names, rw_names, state_out = self._entry(
            program, feed_arrays, fetch_names + extra_fetches, scope,
            use_program_cache
        )
        state_ro = {n: scope.find_var(n) for n in ro_names}
        state_rw = {n: scope.find_var(n) for n in rw_names}
        seed = _next_seed(program)
        t0 = _time.perf_counter() if FLAGS["benchmark"] else 0.0
        if getattr(self, "_compiled_now", False):
            # jax.jit is lazy: the actual trace + XLA compile happens on
            # THIS first call, so the compile span must wrap it (the
            # executor.lower span above only covers building the python
            # callable) — otherwise a multi-second TPU compile hides
            # inside the first executor.step and poisons step_ms's max
            with _tracing.span("executor.jit_compile",
                               program_version=program._version):
                fetches, new_state = jfn(feed_arrays, state_ro, state_rw,
                                         seed)
            self._compiled_now = False
        else:
            fetches, new_state = jfn(feed_arrays, state_ro, state_rw, seed)
            self._last_run_compiled = False
        if FLAGS["benchmark"]:
            jax.block_until_ready(fetches)
            print(f"[benchmark] run took {(_time.perf_counter()-t0)*1000:.3f} ms")
        for n, v in new_state.items():
            scope.set_var(n, v)
        fetched_vals = dict(zip(fetch_names + extra_fetches, fetches))
        if send_ops:
            with _tracing.span("executor.send"):
                _run_send_ops(send_ops, fetched_vals, scope)
        fetches = fetches[:len(fetch_names)]
        # trailing save ops see the POST-step scope (reference in-order
        # save_op semantics: a train+checkpoint program saves updated
        # state); non-persistable temps come from the fetched overlay
        _run_io_host_ops(io_post, scope, extra=fetched_vals)
        if FLAGS["check_nan_inf"]:
            # reference FLAGS_check_nan_inf sweep (executor.cc:352-360)
            from .selected_rows import is_selected_rows

            for name, v in list(new_state.items()) + list(zip(fetch_names, fetches)):
                arr = np.asarray(v.value if is_selected_rows(v) else v)
                if np.issubdtype(arr.dtype, np.floating) and not np.isfinite(arr).all():
                    raise FloatingPointError(f"var '{name}' contains NaN/Inf")
        if return_numpy:
            from .selected_rows import is_selected_rows

            return [f if is_selected_rows(f) else np.asarray(f) for f in fetches]
        return list(fetches)

    def _entry(self, program, feed_arrays, fetch_names, scope,
               use_program_cache):
        """Find-or-build the jitted step for (program version, feed
        signature, fetches, trace flags)."""
        from .flags import trace_flags

        block = program.global_block()
        feed_sig = tuple(
            sorted((k, _feed_sig_entry(v)) for k, v in feed_arrays.items())
        )
        # random_seed does NOT participate: the seed/salt/tick vector is
        # a traced ARGUMENT (_lower), so one executable serves every seed
        # and setting prog.random_seed after a cached run takes effect
        # immediately (regression-tested)
        cache_key = (program._version, feed_sig, fetch_names, trace_flags())
        prog_cache = self._cache.setdefault(program, {})
        entry = prog_cache.get(cache_key) if use_program_cache else None
        if entry is None:
            # a miss against a program version that already has compiled
            # entries means the FEED SIGNATURE (or fetch/flag set) churned
            # — the retrace source the feed_sig counter isolates
            if any(k[0] == program._version for k in prog_cache):
                _m_feed_sig_misses.inc()
            _m_jit_compiles.inc()
            self._compiled_now = True
            if FLAGS["verify_programs"]:
                # pre-lowering IR verification (ISSUE 4): refuse a
                # malformed program HERE, with op-indexed diagnostics,
                # instead of deep inside a JAX trace. Structural checks
                # only — one O(ops) walk per compile, not per step.
                from ..analysis.verify import assert_valid

                assert_valid(
                    program, check_shapes=False,
                    fetch_targets=[n for n in fetch_names],
                    header="program failed verification before lowering "
                           "(FLAGS['verify_programs'] is on)")
            with _tracing.span("executor.lower",
                               program_version=program._version):
                state_in, state_out = _block_io(block, set(feed_arrays),
                                                scope)
                missing = [n for n in state_in if not scope.has_var(n)]
                if missing:
                    raise RuntimeError(
                        f"vars {missing} are read by the program but not "
                        "initialized in scope — run the startup program "
                        "first or feed them"
                    )
                fn, ro_names, rw_names = _lower(
                    block, tuple(feed_arrays), fetch_names, tuple(state_in),
                    tuple(state_out),
                )
                donate = (2,) if FLAGS["donate_state"] else ()
                jfn = jax.jit(fn, donate_argnums=donate)
            entry = (jfn, ro_names, rw_names, tuple(state_out))
            if use_program_cache:
                prog_cache[cache_key] = entry
            _record_compile_cost(program, jfn, feed_arrays, ro_names,
                                 rw_names, scope, fetch_names)
        else:
            _m_jit_cache_hits.inc()
            self._compiled_now = False
        return entry

    def lowered(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence[Any]] = None,
        scope: Optional[Scope] = None,
    ):
        """AOT handle onto the exact cache entry run() would use: returns
        (jfn, args) where jfn is the jitted step function and args the
        (feed, state_ro, state_rw, seed) tuple for these shapes. Callers can
        jfn.lower(*args).compile() for cost_analysis()/memory_analysis()
        without a second compile — the jit object is shared with run(), so
        AOT and traced calls hit one executable (used by benchmarks/)."""
        program = program or default_main_program()
        feed = feed or {}
        scope = scope or global_scope()
        feed_arrays = {k: _as_feed(v) for k, v in feed.items()}
        entry = self._entry(program, feed_arrays,
                            tuple(_as_name(v) for v in fetch_list or []),
                            scope, use_program_cache=True)
        jfn, ro_names, rw_names, _ = entry
        args = (
            feed_arrays,
            {n: scope.find_var(n) for n in ro_names},
            {n: scope.find_var(n) for n in rw_names},
            np.zeros((3,), np.uint32),
        )
        return jfn, args

    def close(self):
        self._cache.clear()


class _StepCounter:
    def __init__(self):
        self._n = 0

    def next(self) -> int:
        self._n += 1
        return self._n


_step_counter = _StepCounter()


def _next_seed(program: Program):
    """Per-run (root, salt, tick) uint32 vector — the key derives from it
    inside the jitted step (_lower._body). A seeded program is fully
    deterministic (its own run counter); seed 0 draws from a
    process-global counter (reference: seed 0 = fresh randomness each
    run).

    The root key is salted with a content hash of the program so that two
    *different* programs sharing one random_seed (e.g. startup + main,
    whose op-seed counters both start at 1) draw from independent
    streams, while two identical builds still match bit-for-bit."""
    if program.random_seed:
        import zlib

        if getattr(program, "_rng_salt_version", None) != program._version:
            program._rng_salt = zlib.crc32(program.to_bytes())
            program._rng_salt_version = program._version
        program._rng_tick += 1
        return np.asarray([program.random_seed, program._rng_salt,
                           program._rng_tick], np.uint32)
    return np.asarray([_step_counter.next(), 0, 0], np.uint32)
