"""paddle_tpu.observability — tracing ring buffer, chrome-trace export,
metrics registry, and the instrumentation wired through the executor,
RPC, parameter-server, and reader layers (ISSUE 1)."""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.observability import metrics, tracing


@pytest.fixture(autouse=True)
def _clean_observability():
    """Each test starts with tracing off+empty and a zeroed registry, and
    leaves the process the same way."""
    tracing.trace_disable()
    tracing.trace_reset()
    metrics.reset_metrics()
    yield
    tracing.trace_disable()
    tracing.trace_reset()
    metrics.reset_metrics()


# --- tracing -----------------------------------------------------------


def test_spans_nest_correctly_across_threads():
    tracing.trace_enable()
    with tracing.span("parent", step=7):
        with tracing.span("child"):
            time.sleep(0.001)

    def worker():
        with tracing.span("worker_span"):
            time.sleep(0.001)

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    events = {e["name"]: e for e in tracing.trace_events()}
    parent, child, worker_ev = (
        events["parent"], events["child"], events["worker_span"])
    # child interval nests inside parent, same thread
    assert parent["ts"] <= child["ts"]
    assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"]
    assert child["tid"] == parent["tid"]
    # the worker thread's span carries its own tid
    assert worker_ev["tid"] != parent["tid"]
    assert parent["args"]["step"] == 7
    # trace context rides along: same-thread child joins the parent's
    # trace; the worker thread's root span starts its own
    assert child["args"]["trace_id"] == parent["args"]["trace_id"]
    assert child["args"]["parent_span_id"] == parent["args"]["span_id"]
    assert worker_ev["args"]["trace_id"] != parent["args"]["trace_id"]


def test_chrome_trace_json_roundtrip(tmp_path):
    tracing.trace_enable()
    with tracing.span("a"):
        with tracing.span("b"):
            pass
    path = tracing.trace_export(str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())
    assert isinstance(doc["traceEvents"], list)
    # one process_name metadata event + the two spans
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(metas) == 1 and metas[0]["name"] == "process_name"
    assert len(spans) == 2
    for ev in spans:
        assert isinstance(ev["ts"], float) and ev["ts"] >= 0
        assert isinstance(ev["dur"], float) and ev["dur"] >= 0
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
    # shard-alignment anchors for `timeline merge` (ISSUE 3)
    other = doc["otherData"]
    assert other["pid"] == os.getpid()
    assert other["wall_epoch_us"] > 0
    assert "rpc_clock_offset_us" in other
    # directory path gets <dir>/trace.json (old profile_path contract)
    d = tmp_path / "out"
    d.mkdir()
    assert tracing.trace_export(str(d)) == str(d / "trace.json")


def test_ring_buffer_drops_oldest_and_counts():
    tracing.trace_enable(buffer_size=16)
    for i in range(40):
        with tracing.span(f"s{i}"):
            pass
    events = tracing.trace_events()
    assert len(events) == 16
    assert events[0]["name"] == "s24"  # oldest 24 dropped
    assert tracing.dropped_spans() == 24
    tracing.trace_enable(buffer_size=65536)  # restore default capacity


def test_disabled_tracing_records_nothing_and_is_noop():
    assert not tracing.trace_enabled()
    s = tracing.span("never")
    with s:
        pass
    # the shared null span: no allocation per call site
    assert s is tracing.span("never_either")
    assert tracing.trace_events() == []


# --- the profiler's clock (ISSUE 27) -------------------------------------


@pytest.mark.parametrize("ring", [False, True])
def test_span_lands_on_the_profilers_clock(profiler_session, ring):
    """While a jax.profiler session collects, span() is also a
    TraceAnnotation of the same name and args — ring on or off; with the
    ring on it records there too, as ever."""
    if ring:
        tracing.trace_enable()
    profiler_session.start()
    with tracing.span("obs.profiled", k=1) as outer:
        assert outer.live
        outer.set_arg("late", 7)        # set after the span opened
        with tracing.span("obs.profiled.child"):
            time.sleep(0.001)
    outer_ev, child_ev = profiler_session.stop(prefix="obs.profiled")
    assert outer_ev["name"] == "obs.profiled"
    assert outer_ev["args"] == {"k": 1, "late": 7}
    assert child_ev["name"] == "obs.profiled.child"
    assert child_ev["line"] == outer_ev["line"]      # one thread's line
    assert outer_ev["start"] <= child_ev["start"] \
        and child_ev["end"] <= outer_ev["end"]
    assert child_ev["end"] - child_ev["start"] >= 1_000_000   # ns
    ring_names = [e["name"] for e in tracing.trace_events()]
    assert ring_names == (["obs.profiled.child", "obs.profiled"]
                          if ring else [])
    if ring:
        child, parent = tracing.trace_events()
        assert parent["args"]["k"] == 1 and parent["args"]["late"] == 7
        assert child["args"]["parent_span_id"] == parent["args"]["span_id"]


def test_span_is_the_shared_noop_with_no_session_and_the_ring_off(
        profiler_session):
    """Neither clock wants the span: the one shared object, before a
    session, and again after it has stopped."""
    assert not tracing.trace_enabled()
    null = tracing.span("obs.nobody", k=1)
    assert null is tracing.span("obs.nobody_else") and not null.live
    profiler_session.start()
    assert tracing.span("obs.somebody") is not null
    profiler_session.stop()
    assert tracing.span("obs.nobody", k=1) is null
    assert tracing.trace_events() == []


def test_fluid_op_types_are_scopes_of_the_lowered_program():
    """exec_op_descs emits each Fluid op under a jax.named_scope of its
    type: the lowered program's op names (and so a device trace's) say
    which Fluid op an operation belongs to."""
    import paddle_tpu.fluid as fluid

    main, startup, loss = _build_sgd_program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        jfn, args = exe.lowered(main, feed={"x": np.ones((2, 4),
                                                           np.float32)},
                                fetch_list=[loss], scope=scope)
    text = jfn.lower(*args).as_text(debug_info=True)
    types = {op.desc.type for op in main.global_block().ops}
    named = {t for t in types if f"jit(fn)/{t}/" in text}
    # an op that emits no operation (assign, a folded constant) leaves
    # no name; every op that computes does
    assert {"mul", "elementwise_add", "mean", "mean_grad", "mul_grad",
            "elementwise_add_grad", "sgd"} <= named, (named, types)


# --- metrics -----------------------------------------------------------


def test_counter_gauge_basognostics():
    c = metrics.counter("t.hits")
    c.inc()
    c.inc(4)
    assert c.value() == 5
    assert metrics.counter("t.hits") is c  # find-or-create caches
    g = metrics.gauge("t.depth")
    g.set(3.5)
    assert metrics.snapshot(prefix="t.")["t.depth"] == 3.5
    with pytest.raises(TypeError):
        metrics.gauge("t.hits")  # kind mismatch is an error, not a clobber


def test_histogram_percentiles_on_known_distribution():
    h = metrics.histogram("t.lat")
    for v in range(1, 101):  # 1..100, uniform
        h.observe(float(v))
    v = h.value()
    assert v["count"] == 100 and v["min"] == 1.0 and v["max"] == 100.0
    assert v["avg"] == pytest.approx(50.5)
    assert v["p50"] == pytest.approx(50.0, abs=1.0)
    assert v["p95"] == pytest.approx(95.0, abs=1.0)
    assert v["p99"] == pytest.approx(99.0, abs=1.0)


def test_histogram_reservoir_bounds_memory():
    h = metrics.histogram("t.big", reservoir=64)
    for v in range(10000):
        h.observe(float(v))
    assert h.value()["count"] == 10000
    assert len(h._vals) == 64
    # reservoir percentiles stay in the observed range and ordered
    v = h.value()
    assert 0 <= v["p50"] <= v["p95"] <= v["p99"] <= 9999


def test_counters_work_with_tracing_disabled():
    """The zero-cost-path contract: metrics are independent of the trace
    recorder — counting while tracing is off neither fails nor records
    spans."""
    assert not tracing.trace_enabled()
    c = metrics.counter("t.cold")
    for _ in range(1000):
        c.inc()
    assert c.value() == 1000
    assert tracing.trace_events() == []


def test_prometheus_text_format():
    metrics.counter("t.reqs").inc(3)
    metrics.gauge("t.qps").set(1.5)
    h = metrics.histogram("t.ms")
    h.observe(10.0)
    text = metrics.prometheus_text()
    assert "# TYPE t_reqs counter" in text
    assert "t_reqs 3" in text
    assert "# TYPE t_qps gauge" in text
    assert '# TYPE t_ms summary' in text
    assert 't_ms{quantile="0.5"} 10.0' in text
    assert "t_ms_count 1" in text


# --- instrumentation through the stack ---------------------------------


def _build_sgd_program():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        y = layers.fc(input=x, size=3)
        loss = layers.mean(y)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def test_executor_run_under_profiler_exports_trace(tmp_path, capsys):
    """The ISSUE acceptance criterion: profiler(profile_path=...) around a
    3-step Executor.run loop exports chrome-trace JSON with executor step
    + reader spans, and the registry reports jit compiles=1, cache
    hits=2 for the repeated program."""
    import paddle_tpu.fluid as fluid

    main, startup, loss = _build_sgd_program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        metrics.reset_metrics()
        path = str(tmp_path / "trace.json")
        with fluid.profiler.profiler(profile_path=path):
            for _ in range(3):
                exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                        fetch_list=[loss])
    snap = metrics.snapshot()
    assert snap["executor.jit_compiles"] == 1
    assert snap["executor.jit_cache_hits"] == 2
    assert snap["executor.step_ms"]["count"] == 3
    doc = json.loads(open(path).read())
    names = [e["name"] for e in doc["traceEvents"]]
    assert names.count("executor.step") == 3
    assert "executor.reader" in names  # the reader pre-pass span
    # profiler() leaves tracing the way it found it
    assert not tracing.trace_enabled()
    capsys.readouterr()  # swallow the profiler table


def test_feed_signature_miss_counter():
    import paddle_tpu.fluid as fluid

    main, startup, loss = _build_sgd_program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        metrics.reset_metrics()
        exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                fetch_list=[loss])
        exe.run(main, feed={"x": np.ones((8, 4), np.float32)},
                fetch_list=[loss])  # new batch shape: feed-sig miss
    snap = metrics.snapshot()
    assert snap["executor.jit_compiles"] == 2
    assert snap["executor.feed_sig_cache_miss"] == 1


def test_record_event_straddling_stop_profiler_is_counted(capsys):
    """Satellite fix: a RecordEvent that begins inside the profile but
    ends after stop_profiler() must still land in the table (enable-state
    captured at __enter__, not checked at __exit__)."""
    from paddle_tpu.fluid import profiler as prof

    prof.start_profiler()
    ev = prof.RecordEvent("straddler")
    ev.__enter__()
    prof.stop_profiler()
    ev.__exit__(None, None, None)
    assert "straddler" in prof._events
    assert prof._events["straddler"][0] == 1
    # and start_profiler resets aggregation state like the reference
    prof.start_profiler()
    assert "straddler" not in prof._events
    prof.stop_profiler()
    capsys.readouterr()


def test_rpc_client_server_metrics_and_error_logging(caplog):
    import logging

    from paddle_tpu.distributed.rpc import RpcClient, RpcServer

    def ok(x):
        return {"echo": x}

    def boom():
        raise ValueError("intentional")

    server = RpcServer({"ok": ok, "boom": boom})
    addr = server.serve()
    client = RpcClient(addr)
    try:
        out = client.call("ok", np.arange(6, dtype=np.float32))
        assert np.allclose(out["echo"], np.arange(6))
        with caplog.at_level(logging.ERROR, logger="paddle_tpu.rpc"):
            with pytest.raises(RuntimeError, match="intentional"):
                client.call("boom")
        # server-side log names the method and the peer (satellite)
        assert any("boom" in r.message and "127.0.0.1" in r.message
                   for r in caplog.records)
    finally:
        client.close()
        server.shutdown()
    snap = metrics.snapshot()
    assert snap["rpc.client.bytes_out"] > 0
    assert snap["rpc.client.bytes_in"] > 0
    assert snap["rpc.server.bytes_in"] > 0
    assert snap["rpc.server.errors"] == 1
    assert snap["rpc.client.errors"] == 1
    assert snap["rpc.client.ok.ms"]["count"] == 1
    assert snap["rpc.server.boom.ms"]["count"] == 1


def test_reader_throughput_gauge():
    from paddle_tpu.fluid.readers import BatchReader, HostReader

    class Tiny(HostReader):
        def __init__(self):
            self.n = 0

        def read_next(self):
            if self.n >= 40:
                raise StopIteration
            self.n += 1
            return (np.zeros((3,), np.float32),)

        def reset(self):
            self.n = 0

    r = BatchReader(Tiny(), batch_size=8)
    for _ in range(5):
        r.read_next()
    snap = metrics.snapshot()
    assert snap["reader.batches"] == 5
    assert snap["reader.records"] == 40
    assert snap["reader.records_per_sec"] > 0


def test_set_flags_buffer_resize_keeps_session_alive():
    """Resizing trace_buffer mid-profile must not flip the enable bit
    (and must actually apply the new capacity)."""
    from paddle_tpu.fluid.flags import FLAGS, set_flags

    old_cap = tracing.buffer_capacity()
    tracing.trace_enable()  # profiler-style session; FLAGS["trace"] False
    try:
        set_flags({"trace_buffer": 128})
        assert tracing.trace_enabled()  # session survived
        assert tracing.buffer_capacity() == 128
        with tracing.span("after_resize"):
            pass
        assert [e["name"] for e in tracing.trace_events()] == ["after_resize"]
    finally:
        set_flags({"trace_buffer": old_cap, "trace": False})
        FLAGS["trace"] = False


def test_stop_profiler_restores_tracing_state(capsys):
    from paddle_tpu.fluid import profiler as prof

    assert not tracing.trace_enabled()
    prof.start_profiler()
    assert tracing.trace_enabled()
    prof.stop_profiler()
    assert not tracing.trace_enabled()  # recorder not left on forever
    # ...but a pre-existing session is left running
    tracing.trace_enable()
    prof.start_profiler()
    prof.stop_profiler()
    assert tracing.trace_enabled()
    capsys.readouterr()


# --- timeline CLI ------------------------------------------------------


def test_timeline_selftest_cli():
    """The tier-1 lint step: a broken recorder/exporter fails here fast."""
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.observability.timeline",
         "--selftest"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "timeline selftest ok" in proc.stdout


def test_timeline_summary_of_exported_trace(tmp_path, capsys):
    tracing.trace_enable()
    for _ in range(3):
        with tracing.span("alpha"):
            pass
    with tracing.span("beta"):
        pass
    path = tracing.trace_export(str(tmp_path / "t.json"))
    from paddle_tpu.observability import timeline

    assert timeline.main([path, "--top", "5"]) == 0
    out = capsys.readouterr().out
    assert "alpha" in out and "beta" in out
    assert "4 spans" in out


# --- trace-context propagation (ISSUE 3) --------------------------------


def test_span_trace_context_parent_child_and_roots():
    tracing.trace_enable()
    with tracing.span("root_a") as a:
        with tracing.span("kid") as k:
            assert k.trace_id == a.trace_id
            assert k.parent_id == a.span_id
    with tracing.span("root_b") as b:
        pass
    assert b.trace_id != a.trace_id  # each root starts its own trace
    assert tracing.wire_context() is None  # no open span -> no header


def test_wire_context_and_adopt_roundtrip():
    tracing.trace_enable()
    with tracing.span("client_side"):
        wire = tracing.wire_context("flow-1")
    assert wire["f"] == "flow-1" and "t" in wire and "s" in wire
    with tracing.adopt(wire), tracing.span("server_side") as s:
        assert s.trace_id == wire["t"]
        assert s.parent_id == wire["s"]
    # adoption is scoped: after the with, new roots are fresh traces
    with tracing.span("later") as later:
        assert later.trace_id != wire["t"]
    # disabled: wire_context yields nothing, adopt is a no-op
    tracing.trace_disable()
    assert tracing.wire_context() is None
    with tracing.adopt(wire):
        pass


def test_rpc_trace_propagation_client_server_flow():
    """The tentpole acceptance shape, in-process: a traced RPC's client
    span and server handler span share a trace_id, the server span's
    parent is the client span, and a flow start/finish pair with one id
    links them for Perfetto's arrow."""
    from paddle_tpu.distributed.rpc import RpcClient, RpcServer

    tracing.trace_enable()
    server = RpcServer({"poke": lambda: {"ok": 1}})
    addr = server.serve()
    client = RpcClient(addr)
    try:
        client.call("poke")
    finally:
        client.close()
        server.shutdown()
    evs = tracing.trace_events()
    cl = [e for e in evs if e["name"] == "rpc.client.poke"]
    sv = [e for e in evs if e["name"] == "rpc.server.poke"]
    assert len(cl) == 1 and len(sv) == 1, [e["name"] for e in evs]
    assert cl[0]["args"]["trace_id"] == sv[0]["args"]["trace_id"]
    assert sv[0]["args"]["parent_span_id"] == cl[0]["args"]["span_id"]
    starts = [e for e in evs if e.get("ph") == "s"]
    ends = [e for e in evs if e.get("ph") == "f"]
    assert len(starts) == 1 and len(ends) == 1
    assert starts[0]["id"] == ends[0]["id"]
    # the clock handshake fed an offset estimate (same host: ~0)
    assert tracing.clock_offset_us() is not None
    # the handshake stamp never leaks into results (popped client-side)


def test_rpc_frames_clean_when_tracing_disabled():
    """No tracing -> no __trace__ header, no server timestamp stamp; the
    handler sees exactly its declared arguments."""
    from paddle_tpu.distributed.rpc import RpcClient, RpcServer

    seen = {}

    def echo(*args):
        seen["args"] = args
        return list(args)

    assert not tracing.trace_enabled()
    server = RpcServer({"echo": echo})
    addr = server.serve()
    client = RpcClient(addr)
    try:
        out = client.call("echo", 1, "two")
    finally:
        client.close()
        server.shutdown()
    assert out == [1, "two"] and seen["args"] == (1, "two")
    assert tracing.trace_events() == []


def test_master_rpc_trace_propagation():
    from paddle_tpu.distributed.master import MasterClient, MasterService

    tracing.trace_enable()
    svc = MasterService(chunks_per_task=1, lease_timeout=5.0)
    addr = svc.serve()
    try:
        cli = MasterClient(addr)
        cli.set_dataset(["s1", "s2"])
        task = cli.get_task()
        assert task is not None
        cli.close()
    finally:
        svc.shutdown()
    evs = tracing.trace_events()
    cl = [e for e in evs if e["name"] == "master.client.get_task"]
    sv = [e for e in evs if e["name"] == "master.get_task"]
    assert cl and sv
    assert cl[0]["args"]["trace_id"] == sv[0]["args"]["trace_id"]
    assert sv[0]["args"]["parent_span_id"] == cl[0]["args"]["span_id"]


def test_dropped_spans_gauge_tracks_ring_overflow():
    tracing.trace_enable(buffer_size=16)
    for i in range(40):
        with tracing.span(f"d{i}"):
            pass
    assert tracing.dropped_spans() == 24
    assert metrics.snapshot()["tracing.dropped_spans"] == 24
    assert "tracing_dropped_spans 24" in metrics.prometheus_text()
    tracing.trace_enable(buffer_size=65536)


def test_reset_all_isolation_helper():
    metrics.counter("iso.c").inc(5)
    tracing.trace_enable()
    with tracing.span("iso"):
        pass
    metrics.reset_all()
    assert metrics.counter("iso.c").value() == 0
    assert tracing.trace_events() == []  # ring cleared too
    assert tracing.dropped_spans() == 0
    # the gauge line survives (registered, zeroed) — /metrics always
    # shows span loss explicitly, even as 0
    assert "tracing_dropped_spans 0" in metrics.prometheus_text()


# --- debug server (ISSUE 3) ---------------------------------------------


def test_debug_server_endpoints_on_ephemeral_port():
    import urllib.request

    from paddle_tpu.observability.debug_server import DebugServer

    metrics.counter("dbg.hits").inc(3)
    srv = DebugServer()
    srv.add_status("demo", lambda: {"n": np.int64(7), "xs": (1, 2)})
    srv.add_status("broken", lambda: 1 / 0)
    host, port = srv.start()
    try:
        def get(path):
            return urllib.request.urlopen(
                f"http://{host}:{port}{path}", timeout=10).read().decode()

        assert get("/healthz").strip() == "ok"
        body = get("/metrics")
        assert "dbg_hits 3" in body
        assert "tracing_dropped_spans" in body
        st = json.loads(get("/statusz"))
        assert st["pid"] == os.getpid()
        assert st["demo"] == {"n": 7, "xs": [1, 2]}  # numpy/tuple coerced
        assert "ZeroDivisionError" in st["broken"]["error"]
        assert "flags" in st and "matmul_precision" in st["flags"]
        assert "jax" in st
        tz = json.loads(get("/tracez"))
        assert tz["enabled"] is False and tz["recent"] == []
        # 404 names the endpoints
        import urllib.error

        with pytest.raises(urllib.error.HTTPError) as ei:
            get("/nope")
        assert ei.value.code == 404
    finally:
        srv.stop()


# --- timeline merge CLI (ISSUE 3) ---------------------------------------


def test_timeline_merge_cli_roundtrip(tmp_path, capsys):
    from paddle_tpu.observability import timeline

    tracing.trace_enable()
    with tracing.span("work.a"):
        pass
    shard1 = tracing.trace_export(str(tmp_path / "trace-1.json"))
    tracing.trace_reset()
    with tracing.span("work.b"):
        pass
    shard2 = tracing.trace_export(str(tmp_path / "trace-2.json"))
    out = str(tmp_path / "merged.json")
    assert timeline.main(["merge", "-o", out, shard1, shard2]) == 0
    txt = capsys.readouterr().out
    assert "merged 2 shard(s)" in txt
    doc = json.loads(open(out).read())
    names = [e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert "work.a" in names and "work.b" in names
    assert all(e["ts"] >= 0 for e in doc["traceEvents"] if "ts" in e)
    assert len(doc["otherData"]["merged_shards"]) == 2
    # same-pid shards get distinct display pids so Perfetto keeps tracks
    pids = {e["pid"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert len(pids) == 2


def test_timeline_merge_missing_shard_is_an_error(tmp_path, capsys):
    from paddle_tpu.observability import timeline

    tracing.trace_enable()
    with tracing.span("only"):
        pass
    shard = tracing.trace_export(str(tmp_path / "trace-1.json"))
    rc = timeline.main(["merge", "-o", str(tmp_path / "m.json"),
                        shard, str(tmp_path / "gone.json")])
    assert rc == 2
    assert "merge failed" in capsys.readouterr().err


# --- XLA cost accounting (ISSUE 3) --------------------------------------


def test_compile_stats_report_and_gauges():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.executor import (compile_report,
                                           reset_compile_report)
    from paddle_tpu.fluid.flags import set_flags

    main, startup, loss = _build_sgd_program()
    scope = fluid.Scope()
    reset_compile_report()
    set_flags({"compile_stats": "auto"})  # conftest turns it off suite-wide
    try:
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                    fetch_list=[loss])
    finally:
        set_flags({"compile_stats": False})
    rep = compile_report()
    assert rep, "compile_stats 'auto' records every jit-cache miss"
    last = rep[-1]
    assert last["flops"] and last["flops"] > 0
    assert last["bytes_accessed"] and last["bytes_accessed"] > 0
    assert "memory" not in last  # 'auto' never pays the second compile
    snap = metrics.snapshot()
    assert snap["executor.compile.flops"] == last["flops"]
    assert snap["executor.compile.bytes_accessed"] == last["bytes_accessed"]


def test_compile_stats_full_mode_memory_analysis():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.executor import (compile_report,
                                           reset_compile_report)
    from paddle_tpu.fluid.flags import set_flags

    main, startup, loss = _build_sgd_program()
    scope = fluid.Scope()
    reset_compile_report()
    set_flags({"compile_stats": "full"})
    try:
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            exe.run(main, feed={"x": np.ones((3, 4), np.float32)},
                    fetch_list=[loss])
    finally:
        set_flags({"compile_stats": False})
    rep = compile_report()
    assert rep
    mem = rep[-1]["memory"]
    assert mem["argument_size_in_bytes"] > 0
    assert "temp_size_in_bytes" in mem
    assert rep[-1]["compile_ms"] >= 0


def test_compile_stats_off_records_nothing():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.executor import (compile_report,
                                           reset_compile_report)

    main, startup, loss = _build_sgd_program()
    scope = fluid.Scope()
    reset_compile_report()
    assert fluid.flags.FLAGS["compile_stats"] is False  # conftest default
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                fetch_list=[loss])
    assert compile_report() == []
