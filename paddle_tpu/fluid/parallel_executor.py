"""ParallelExecutor — multi-chip data-parallel training.

Capability-parity with the reference ParallelExecutor
(`paddle/fluid/framework/parallel_executor.cc:50`,
`python/paddle/fluid/parallel_executor.py:23`), redesigned for XLA SPMD:

  - The reference replicates the op graph per GPU, seeds 1/N loss grads, and
    inserts NCCLAllReduceOpHandle per param-grad into a threaded SSA dataflow
    graph (multi_devices_graph_builder.cc:167).
  - Here the SAME lowered block function is jit-compiled with
    jax.sharding: feed arrays are sharded on the batch axis of a device
    Mesh, persistable state is replicated, and XLA's SPMD partitioner
    inserts the ICI all-reduces where the gradient computation crosses the
    sharded batch dimension. The dataflow overlap the reference got from
    threads, XLA gets from async collectives in one program.

API preserved: ParallelExecutor(use_cuda, loss_name).run(fetch_list, feed).
"""
from __future__ import annotations

import time as _time
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import core
from ..observability import metrics as _metrics, tracing as _tracing
from .enforce import throw_on
from .executor import Scope, _block_io, _lower, _next_seed, global_scope
from .framework import Program, Variable, default_main_program

# per-step latency over the sharded executable. Under SPMD the gradient
# all-reduce is INSIDE the step program (XLA inserts the ICI collectives
# where the grad computation crosses the sharded batch dim), so
# grad_allreduce_step_ms — observed only for runs dispatching a training
# step (loss_name set) — is the collective-inclusive step time, the
# number the reference's per-NCCLAllReduceOpHandle timers added up to.
_m_pe_step_ms = _metrics.histogram("parallel_executor.step_ms")
_m_pe_allreduce_ms = _metrics.histogram(
    "parallel_executor.grad_allreduce_step_ms")
_m_pe_compiles = _metrics.counter("parallel_executor.jit_compiles")
_m_pe_cache_hits = _metrics.counter("parallel_executor.jit_cache_hits")


def _as_name(v) -> str:
    return v.name if isinstance(v, Variable) else str(v)


def _spans_processes(mesh: Mesh) -> bool:
    """True when the mesh includes devices of OTHER processes (multi-host:
    one SPMD program over DCN, reference capability = the trainer fleet)."""
    me = jax.process_index()
    return any(d.process_index != me for d in mesh.devices.flat)


def _global_state_put(mesh: Mesh, arr, spec):
    """Place state every process holds IN FULL onto a cross-process mesh:
    each process contributes the shards its local devices own (params are
    replicated or plan-sharded; either way the full value is available
    host-side, so indexing out the local piece is exact)."""
    sharding = NamedSharding(mesh, spec)
    arr = np.asarray(arr)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx])


class ParallelExecutor:
    def __init__(
        self,
        use_cuda: Optional[bool] = None,
        loss_name: Optional[str] = None,
        main_program: Optional[Program] = None,
        num_threads: Optional[int] = None,
        allow_op_delay: bool = False,
        share_vars_from: Optional["ParallelExecutor"] = None,
        devices: Optional[Sequence[Any]] = None,
        use_tpu: Optional[bool] = None,
        mesh: Optional[Mesh] = None,
        sharding_plan=None,
        collect_cost: bool = False,
    ):
        """`collect_cost`: compile through the AOT path and expose XLA's
        cost analysis of the sharded executable as
        `self.last_cost_analysis` ({"flops", "bytes_accessed"}) — the
        dryrun records these per phase so a communication/remat regression
        shows up as a number, not just a slower wall clock."""
        from ..parallel import ShardingPlan

        self._program = main_program or default_main_program()
        self._loss_name = loss_name
        if mesh is not None:
            if not isinstance(mesh, Mesh):
                # MeshSpec / axes dict / "dp=2,tp=4" string (ISSUE 15):
                # the mesh layer's one coercion rule, built here
                from ..mesh import MeshSpec

                mesh = MeshSpec.coerce(mesh).build(devices=devices)
            self._mesh = mesh
        else:
            from .flags import FLAGS

            if FLAGS["mesh_axes"]:
                # operator-configured default mesh: a run that passes
                # no mesh= still trains sharded per the flag
                from ..mesh import MeshSpec

                self._mesh = MeshSpec.parse(
                    FLAGS["mesh_axes"]).build(devices=devices)
            else:
                devs = (list(devices) if devices is not None
                        else jax.devices())
                self._mesh = Mesh(np.asarray(devs), ("dp",))
        self._plan = sharding_plan or ShardingPlan(batch_axis=self._mesh.axis_names[0])
        self._sharded = int(self._mesh.devices.size) > 1
        if self._sharded:
            from ..mesh import note_mesh

            note_mesh(self._mesh, label="parallel_executor")
        self._scope = (
            share_vars_from._scope if share_vars_from is not None else global_scope()
        )
        self._cache: Dict[Any, Any] = {}
        self._collect_cost = bool(collect_cost)
        self.last_cost_analysis: Optional[Dict[str, float]] = None

    @property
    def device_count(self) -> int:
        return self._mesh.devices.size

    def run(self, fetch_list, feed=None, feed_dict=None, return_numpy: bool = True):
        feed = feed if feed is not None else feed_dict
        feed = feed or {}
        if isinstance(feed, (list, tuple)):
            # reference accepts per-device feed dicts; concat on batch dim
            merged: Dict[str, Any] = {}
            for d in feed:
                for k, v in d.items():
                    merged.setdefault(k, []).append(np.asarray(v))
            feed = {k: np.concatenate(v, axis=0) for k, v in merged.items()}

        program = self._program
        block = program.global_block()
        fetch_names = tuple(_as_name(v) for v in fetch_list)
        mesh = self._mesh

        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

        def _divisible(shape, spec):
            # every sharded dim must divide by its mesh-axis size
            for dim, ax in enumerate(spec):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                size = int(np.prod([axis_sizes.get(a, 1) for a in axes]))
                if dim >= len(shape) or shape[dim] % size != 0:
                    return False
            return True

        def _resolve_spec(name, shape):
            """Plan spec for a state var. Size-1 arrays (scalar optimizer
            accumulators whose names match a param rule) fall back to
            replication; a genuinely indivisible param is a misconfigured
            plan and fails loudly — except under a best_effort plan
            (plan_fsdp's catch-all: real FSDP replicates the odd-width
            biases and class-count tails it cannot split evenly)."""
            spec = self._plan.spec_for(name, len(shape))
            if _divisible(shape, spec):
                return spec
            if int(np.prod(shape, dtype=np.int64)) <= 1:
                return P(*([None] * len(shape)))
            if getattr(self._plan, "best_effort", False):
                return P(*([None] * len(shape)))
            throw_on(
                "sharding plan maps var '%s' (shape %s) to %s, but a "
                "dimension does not divide the mesh axis size %s — fix the "
                "plan rules or the model dims",
                name, tuple(shape), spec, axis_sizes,
                context="ParallelExecutor",
            )

        multiproc = _spans_processes(mesh)
        feed_arrays = {}
        for k, v in feed.items():
            arr = np.asarray(v)
            spec = self._plan.feed_spec(arr.ndim)
            if multiproc:
                # each process feeds its LOCAL batch shard; jax assembles
                # the global array (global batch = concat over processes —
                # the reference trainer fleet's per-trainer minibatches)
                try:
                    feed_arrays[k] = jax.make_array_from_process_local_data(
                        NamedSharding(mesh, spec), arr)
                except (ValueError, TypeError) as e:
                    # replicating a per-process-different feed would be
                    # silently wrong — fail with the fix spelled out
                    throw_on(
                        "feed '%s' local shape %s does not shard over the "
                        "multi-host mesh %s (%s) — pad the local batch or "
                        "use drop_last so every process feeds an equal, "
                        "divisible shard",
                        k, tuple(arr.shape), dict(axis_sizes), e,
                        context="ParallelExecutor",
                    )
                continue
            if not (arr.shape and self._plan.batch_axis
                    and _divisible(arr.shape, spec)):
                # indivisible feeds stay replicated (reference PE pads/splits)
                spec = P(*([None] * arr.ndim))
            feed_arrays[k] = jax.device_put(arr, NamedSharding(mesh, spec))

        feed_sig = tuple(
            sorted((k, tuple(v.shape), str(v.dtype)) for k, v in feed_arrays.items())
        )
        from .flags import FLAGS, trace_flags

        cache_key = (id(program), program._version, feed_sig, fetch_names,
                     trace_flags())
        entry = self._cache.get(cache_key)
        fresh_compile = entry is None
        if entry is not None:
            _m_pe_cache_hits.inc()
        if entry is None:
            _m_pe_compiles.inc()
            state_in, state_out = _block_io(block, set(feed_arrays), self._scope)
            missing = [n for n in state_in if not self._scope.has_var(n)]
            if missing:
                raise RuntimeError(
                    f"vars {missing} not initialized — run the startup program "
                    "with a plain Executor first"
                )
            fn, ro_names, rw_names = _lower(
                block, tuple(feed_arrays), fetch_names, tuple(state_in),
                tuple(state_out),
            )
            def _state_spec(n):
                shape = np.shape(self._scope.find_var(n))  # metadata only
                return NamedSharding(mesh, _resolve_spec(n, shape))

            out_state_shardings = {n: _state_spec(n) for n in state_out}
            jfn = jax.jit(
                fn,
                donate_argnums=(2,),
                out_shardings=(None, out_state_shardings),
            )
            entry = {"jfn": jfn, "ro": ro_names, "rw": rw_names,
                     "state_out": tuple(state_out), "compiled": None,
                     "cost": None, "collectives": None}
            self._cache[cache_key] = entry

        jfn, ro_names, rw_names, state_out = (
            entry["jfn"], entry["ro"], entry["rw"], entry["state_out"])

        def _place(name, x):
            if multiproc:
                if isinstance(x, jax.Array) and not x.is_fully_addressable:
                    return x  # already a global array from a prior step
                return _global_state_put(
                    mesh, x, _resolve_spec(name, np.shape(x)))
            x = jnp.asarray(x)
            target = NamedSharding(mesh, _resolve_spec(name, x.shape))
            if getattr(x, "sharding", None) == target:
                return x
            return jax.device_put(x, target)

        state_ro = {n: _place(n, self._scope.find_var(n)) for n in ro_names}
        state_rw = {n: _place(n, self._scope.find_var(n)) for n in rw_names}
        seed = _next_seed(program)
        from ..parallel import mesh_context

        # emitters that need explicit SPMD (ring attention) see the mesh
        # during tracing, which happens inside this first call
        t0 = _time.perf_counter()
        collectives = None
        with mesh_context(mesh), _tracing.span(
                "parallel_executor.step", devices=int(mesh.devices.size),
                program_version=program._version) as _step_span:
            if self._collect_cost or self._sharded:
                # AOT path: sharded runs always lower explicitly so the
                # compiled program's COLLECTIVES can be counted exactly
                # (mesh.collectives.* — the number a communication
                # regression moves; wall clocks on a contended host
                # cannot carry that evidence), collect_cost additionally
                # records XLA's flop/byte analysis
                if entry["compiled"] is None:
                    compiled = jfn.lower(
                        feed_arrays, state_ro, state_rw, seed).compile()
                    if self._sharded:
                        # count from the COMPILED text: the SPMD
                        # partitioner inserts collectives after
                        # StableHLO, so the lowered form has none yet
                        from ..mesh import note_sharded_compile

                        try:
                            hlo = compiled.as_text()
                        except Exception:  # pragma: no cover - backend
                            hlo = ""
                        entry["collectives"] = note_sharded_compile(hlo)
                    entry["compiled"] = compiled
                    if self._collect_cost:
                        from ..jax_compat import cost_analysis_dict

                        ca = cost_analysis_dict(compiled)
                        entry["cost"] = {
                            "flops": float(ca.get("flops", -1.0)),
                            "bytes_accessed": float(
                                ca.get("bytes accessed", -1.0)),
                        }
                self.last_cost_analysis = entry["cost"]
                collectives = entry["collectives"]
                fetches, new_state = entry["compiled"](
                    feed_arrays, state_ro, state_rw, seed)
            else:
                fetches, new_state = jfn(feed_arrays, state_ro, state_rw,
                                         seed)
            if self._sharded:
                from ..mesh import sharded_step_counter

                sharded_step_counter().inc()
                if collectives:
                    # the span carries the compiled program's collective
                    # census, so a trace shows what each step ships
                    # over ICI without a device profiler
                    _step_span.set_arg(
                        "collectives", int(sum(collectives.values())))
        step_ms = (_time.perf_counter() - t0) * 1e3
        _m_pe_step_ms.observe(step_ms)
        if self._loss_name:  # a training step: includes the grad all-reduce
            _m_pe_allreduce_ms.observe(step_ms)
        for n, v in new_state.items():
            self._scope.set_var(n, v)
        if return_numpy:
            from .selected_rows import is_selected_rows

            out = [f if is_selected_rows(f) else np.asarray(f)
                   for f in fetches]
            if FLAGS["autotune"] and not fresh_compile:
                # same per-shape step log the single-device executor
                # feeds (ISSUE 8). Logged AFTER the numpy conversion —
                # np.asarray is the device barrier; timing the bare
                # jfn() return would persist async-DISPATCH latency as
                # the step cost. Compile runs excluded; return_numpy=False runs
                # have no barrier, so they are not logged at all.
                from ..autotune.measure import note_step_timing

                try:
                    note_step_timing(
                        "parallel_executor.step", program, feed,
                        (_time.perf_counter() - t0) * 1e3)
                except Exception:
                    pass
            return out
        return list(fetches)

    def bcast_params(self):
        """Parity with reference bcast_params (parallel_executor.py:149):
        re-replicate scope params over the mesh (cross-process meshes go
        through the local-shard contribution path, like run())."""
        mesh = self._mesh
        multiproc = _spans_processes(mesh)
        with _tracing.span("parallel_executor.bcast_params",
                           devices=int(mesh.devices.size)):
            self._bcast_params_body(mesh, multiproc)

    def _bcast_params_body(self, mesh, multiproc):
        for name in list(self._scope.var_names()):
            v = self._scope.find_var(name)
            if multiproc:
                if isinstance(v, jax.Array) and not v.is_fully_addressable:
                    continue  # already global
                self._scope.set_var(
                    name,
                    _global_state_put(mesh, v, P(*([None] * np.ndim(v)))),
                )
                continue
            arr = jnp.asarray(v)
            self._scope.set_var(
                name,
                jax.device_put(arr, NamedSharding(mesh, P(*([None] * arr.ndim)))),
            )
