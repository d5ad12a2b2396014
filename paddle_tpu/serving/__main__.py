"""CLI driver for the serving subsystem.

    python -m paddle_tpu.serving --selftest
        In-process end-to-end proof (no external network, no datasets):
        builds two versions of a tiny model, then exercises the bucketed
        batcher (jit-compile bound + batch-invariance), the RPC
        server/client path, an atomic hot-swap, the overload rejection
        path, the DECODE path (ISSUE 6: paged-KV continuous
        batching — warmed slot/width ladder, zero churn compiles, page
        exhaustion refusal, RPC generate + decoder hot-swap), the
        ISSUE 13 layer (prefix-cache hits prefill only the suffix;
        demand reservation + preempt/restore completes an over-
        committed pool with reference-equal tokens), and the ISSUE 14
        layer (speculative decoding: draft-propose + chunked-verify
        emits bitwise the non-speculative tokens — greedy AND seeded
        sampling — in fewer target steps, zero post-warm compiles,
        every rollback page returned).
        Exit-nonzero on any failure — wired into tools/check.py as the
        serving smoke.

    python -m paddle_tpu.serving --serve --load m=/path/to/model_dir
        Operator mode: start a ServingServer on the backend JAX gives
        (the TPU where there is one), load the named model directories,
        print the address and the device, and serve until interrupted.
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile


def _force_cpu():
    """The selftest (and only the selftest) pins the CPU: it proves
    control flow at toy sizes and must not take a chip from a server.
    Pinned before any backend initialization, the same way
    tests/conftest.py and the analysis CLI do."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")


def make_model_dir(dirname: str, scale: float = 1.0, feature_dim: int = 8,
                   classes: int = 3):
    """Build + save a tiny softmax model with DETERMINISTIC,
    scale-distinct parameters (so two builds with different `scale` are
    observably different model versions). Returns (dirname, probe
    input, reference output) — the reference computed by the framework
    itself, for later equality checks against the serving path."""
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers, unique_name
    from paddle_tpu.fluid.framework import Parameter, Program, program_guard

    main, startup, scope = Program(), Program(), fluid.Scope()
    with fluid.scope_guard(scope):
        with program_guard(main, startup), unique_name.guard():
            x = layers.data(name="x", shape=[feature_dim], dtype="float32")
            pred = layers.fc(input=x, size=classes, act="softmax")
        exe = fluid.Executor()
        exe.run(startup)
        rng = np.random.RandomState(7)
        for var in sorted(main.list_vars(), key=lambda v: v.name):
            if isinstance(var, Parameter):
                vals = rng.uniform(-1, 1, size=tuple(var.shape)) * scale
                scope.set_var(var.name, jnp.asarray(vals.astype(np.float32)))
        fluid.io.save_inference_model(dirname, ["x"], [pred], exe, main)
        probe = np.random.RandomState(11).rand(4, feature_dim).astype(
            np.float32)
        (ref,) = exe.run(main, feed={"x": probe}, fetch_list=[pred])
    return dirname, probe, ref


def run_selftest(verbose: bool = True) -> int:
    import numpy as np

    from concurrent.futures import ThreadPoolExecutor

    from paddle_tpu.observability import metrics as _metrics
    from . import (InferenceEngine, ServerOverloaded, ServingClient,
                   ServingServer)

    def say(msg):
        if verbose:
            print(f"  {msg}")

    failures = []

    def check(ok, what):
        say(("ok  " if ok else "FAIL") + f" {what}")
        if not ok:
            failures.append(what)

    with tempfile.TemporaryDirectory() as tmp:
        d1, probe, ref1 = make_model_dir(os.path.join(tmp, "v1"), scale=1.0)
        d2, _, ref2 = make_model_dir(os.path.join(tmp, "v2"), scale=-1.0)

        # -- 1. bucketed batching bounds the jit cache -------------------
        jc = _metrics.counter("executor.jit_compiles")
        base = jc.value()
        eng = InferenceEngine.from_inference_dir(
            d1, name="selftest", buckets=[1, 2, 4], max_wait_ms=1.0)
        warm_compiles = jc.value() - base
        check(warm_compiles <= 3,
              f"warmup compiles {warm_compiles} <= ladder length 3")
        sizes = [1, 2, 3, 4, 1, 3, 2, 4, 1, 1]
        rng = np.random.RandomState(0)
        reqs = [rng.rand(b, 8).astype(np.float32) for b in sizes]
        with ThreadPoolExecutor(max_workers=6) as pool:
            outs = list(pool.map(lambda a: eng.infer({"x": a}), reqs))
        check(all(o[0][0].shape[0] == a.shape[0]
                  for o, a in zip(outs, reqs)),
              "every request got its own rows back")
        check(jc.value() - base <= 3,
              f"mixed arrival pattern stayed inside the ladder "
              f"({jc.value() - base} compiles)")
        # batch invariance: one 4-row request == 4 single-row requests
        (whole, _) = eng.infer({"x": probe})
        singles = [eng.infer({"x": probe[i:i + 1]})[0][0]
                   for i in range(probe.shape[0])]
        check(np.allclose(np.concatenate(singles), whole[0], atol=1e-5),
              "batching is result-invariant (padding sliced off)")
        check(np.allclose(whole[0], ref1, atol=1e-5),
              "engine output matches the framework reference")
        eng.stop()

        # -- 2. server / client / hot-swap / overload --------------------
        srv = ServingServer()
        addr = srv.serve()
        cli = ServingClient(addr)
        try:
            cli.load_model("m", d1, buckets=[1, 2, 4], max_wait_ms=1.0)
            h = cli.health()
            check(h.get("ok") and "m" in h.get("models", []),
                  "health reports the loaded model")
            out, v = cli.infer("m", {"x": probe})
            check(v == 1 and np.allclose(out[0], ref1, atol=1e-5),
                  "RPC infer serves v1")
            cli.load_model("m", d2, buckets=[1, 2, 4], max_wait_ms=1.0)
            out, v = cli.infer("m", {"x": probe})
            check(v == 2 and np.allclose(out[0], ref2, atol=1e-5),
                  "hot-swap flipped to v2 atomically")
            listed = cli.list_models()
            check(listed.get("m", {}).get("version") == 2,
                  "list_models shows the new version")

            # overload: tighten the admission bound, park the scheduler
            # on its batching timer (long enough that a contended host
            # still lands the flood inside the window), and flood —
            # extras must be refused IMMEDIATELY with ServerOverloaded,
            # not queued forever
            cli.load_model("m", d2, version=3, buckets=[1, 2, 4],
                           max_queue=1, max_wait_ms=1200.0)
            ok_n = over_n = 0

            def fire(i):
                nonlocal ok_n, over_n
                try:
                    cli2 = ServingClient(addr)
                    try:
                        cli2.infer("m", {"x": probe[:1]},
                                   deadline_ms=30000.0)
                        ok_n += 1
                    finally:
                        cli2.close()
                except ServerOverloaded:
                    over_n += 1

            with ThreadPoolExecutor(max_workers=8) as pool:
                list(pool.map(fire, range(8)))
            check(over_n > 0 and ok_n > 0,
                  f"overload sheds load ({ok_n} served, {over_n} refused)")
            check(_metrics.counter("serving.overloads").value() >= over_n,
                  "serving.overloads counted the rejections")
        finally:
            cli.close()
            srv.shutdown()

        # -- 3. decode: paged KV + continuous batching (ISSUE 6) ---------
        from . import DecodeEngine, DecoderSpec

        spec = DecoderSpec(vocab=32, d_model=16, n_layers=1, n_heads=2,
                           n_kv_heads=1, seed=3)
        deng = DecodeEngine(spec, name="dec", slots=[1, 2], page_size=4,
                            num_pages=24, max_seq_len=8)
        try:
            n_shapes = (len(deng.slot_ladder)
                        * len(deng.table_width_ladder)
                        * len(deng.chunk_ladder))
            check(len(deng.stats()["compiled_shapes"]) == n_shapes,
                  f"decode warm compiled the full ladder ({n_shapes} "
                  "shapes)")
            dc = _metrics.counter("serving.decode.compiles")
            base = dc.value()
            rng = np.random.RandomState(0)
            reqs = [deng.submit(
                rng.randint(0, 32, size=1 + int(rng.randint(4))),
                max_new_tokens=1 + int(rng.randint(4)))
                for _ in range(8)]
            ok = all(r.ev.wait(120) and r.error is None for r in reqs)
            check(ok, "ragged sequence churn all completed")
            check(dc.value() == base,
                  "churn performed 0 new decode compiles")
            check(deng.cache.allocator.stats()["pages_used"] == 0,
                  "every KV page returned to the pool")
            a = deng.generate([1, 2, 3], max_new_tokens=4)
            b = deng.generate([1, 2, 3], max_new_tokens=4)
            check(a["tokens"] == b["tokens"], "greedy decode deterministic")
            try:
                held = deng.cache.allocator.alloc(9999, 92)  # drain pool
                deng.submit([1, 2, 3, 4], max_new_tokens=4)
                check(False, "page exhaustion refused")
            except ServerOverloaded:
                check(True, "page exhaustion refused (ServerOverloaded)")
                deng.cache.allocator.free(9999)
        finally:
            deng.stop()

        # -- 4. chunked prefill (ISSUE 10): token-budget mixed steps ----
        ceng = DecodeEngine(spec, name="chunked", slots=[2], page_size=4,
                            num_pages=24, max_seq_len=20,
                            prefill_chunk=4)
        try:
            steps = _metrics.counter("serving.decode.steps")
            base = steps.value()
            prompt = list(range(12))
            out = ceng.generate(prompt, max_new_tokens=3)
            # steps-to-first-token bound: ceil(12/4) = 3, not 12
            check(out["steps_to_first_token"] == 3,
                  f"12-token prompt prefilled in "
                  f"{out['steps_to_first_token']} steps (== ceil(12/4))")
            check(steps.value() - base == 3 + 2,
                  "total steps = ceil(P/chunk) + (new - 1)")
            # mixed step: a decoding sequence co-rides a fresh prompt's
            # prefill chunks and never stalls behind them
            a = ceng.submit([5], max_new_tokens=6)
            b = ceng.submit(prompt, max_new_tokens=2)
            ok = a.ev.wait(120) and b.ev.wait(120) and \
                a.error is None and b.error is None
            check(ok and len(a.result["tokens"]) == 6
                  and len(b.result["tokens"]) == 2,
                  "mixed prefill+decode step completed both sequences")
            check(_metrics.counter(
                      "serving.decode.prefill_tokens").value() > 0,
                  "prefill token budget accounted "
                  "(serving.decode.prefill_tokens)")
            # chunking is engine-internal: greedy tokens identical with
            # chunking off (the PR 6 one-token-per-step behavior)
            ueng = DecodeEngine(spec, name="unchunked", slots=[2],
                                page_size=4, num_pages=24,
                                max_seq_len=20, prefill_chunk=1)
            try:
                u = ueng.generate(prompt, max_new_tokens=3)
                check(u["tokens"] == out["tokens"]
                      and u["steps_to_first_token"] == 12,
                      "greedy tokens identical with chunking on vs off "
                      "(12 steps unchunked, 3 chunked)")
            finally:
                ueng.stop()
        finally:
            ceng.stop()

        # -- 5. prefix caching + preemption (ISSUE 13) -------------------
        peng = DecodeEngine(spec, name="prefix", slots=[2], page_size=4,
                            num_pages=24, max_seq_len=20,
                            prefill_chunk=4, prefix_cache=True)
        try:
            prompt12 = list(range(12))
            cold = peng.generate(prompt12, max_new_tokens=3)
            check(cold["cached_tokens"] == 0
                  and cold["steps_to_first_token"] == 3,
                  "cold prompt prefilled in ceil(12/4) steps")
            # shared 8-token prefix, fresh suffix: prefill = the suffix
            warm = peng.generate(prompt12[:8] + [20, 21, 22, 23],
                                 max_new_tokens=3)
            check(warm["cached_tokens"] >= 8
                  and warm["steps_to_first_token"] == 1,
                  f"shared-prefix request mapped "
                  f"{warm['cached_tokens']} cached tokens, "
                  "first token in ceil(suffix/4) = 1 step")
            st = peng.cache.allocator.stats()
            check(st["pages_used"] == 0 and st["prefix_pages"] > 0,
                  "freed shared pages retained reclaimable "
                  f"({st['prefix_pages']} cached, 0 live)")
            cold2 = DecodeEngine(spec, name="prefix_cold", slots=[2],
                                 page_size=4, num_pages=24,
                                 max_seq_len=20, prefill_chunk=4,
                                 prefix_cache=False)
            try:
                ref = cold2.generate(prompt12[:8] + [20, 21, 22, 23],
                                     max_new_tokens=3)
                check(ref["tokens"] == warm["tokens"],
                      "cache-hit tokens identical to a cold engine's")
            finally:
                cold2.stop()
        finally:
            peng.stop()
        # demand reservation + preempt/restore: a pool far too small
        # for the worst case still completes everything, tokens equal
        # the unpreempted reference
        preempts = _metrics.counter("serving.kv.preemptions")
        base_pre = preempts.value()
        deng2 = DecodeEngine(spec, name="demand", slots=[4], page_size=4,
                             num_pages=13, max_seq_len=44,
                             prefill_chunk=4, prefix_cache=False,
                             reservation="demand")
        try:
            reqs = [deng2.submit([1 + i], max_new_tokens=30)
                    for i in range(4)]
            ok = all(r.ev.wait(240) and r.error is None for r in reqs)
            check(ok and preempts.value() > base_pre,
                  f"undersized pool completed via preempt+restore "
                  f"({preempts.value() - base_pre} preemptions)")
            check(deng2.cache.allocator.stats()["pages_used"] == 0,
                  "every page (spilled included) returned to the pool")
            wide = DecodeEngine(spec, name="demand_ref", slots=[4],
                                page_size=4, num_pages=64,
                                max_seq_len=44, prefill_chunk=4,
                                prefix_cache=False,
                                reservation="worst_case")
            try:
                sample = wide.generate([1], max_new_tokens=30)
                check(sample["tokens"] == reqs[0].result["tokens"],
                      "preempted tokens bitwise equal unpreempted "
                      "reference")
            finally:
                wide.stop()
        finally:
            deng2.stop()

        # -- 6. speculative decoding (ISSUE 14) --------------------------
        sspec = DecoderSpec(vocab=32, d_model=16, n_layers=1, n_heads=2,
                            n_kv_heads=1, seed=3)
        sdraft = DecoderSpec(vocab=32, d_model=8, n_layers=1, n_heads=1,
                             n_kv_heads=1, seed=3)
        ts = _metrics.counter("serving.decode.target_steps")
        s_off = DecodeEngine(sspec, name="spec_off", slots=[1],
                             page_size=4, num_pages=16, max_seq_len=20,
                             prefill_chunk=4)
        try:
            base = ts.value()
            ref = s_off.generate([4, 9, 1], max_new_tokens=12)
            off_steps = ts.value() - base
        finally:
            s_off.stop()
        dc = _metrics.counter("serving.decode.compiles")
        s_on = DecodeEngine(sspec, name="spec_on", slots=[1],
                            page_size=4, num_pages=16, max_seq_len=20,
                            prefill_chunk=4, draft_spec=sdraft,
                            spec_k=3)
        try:
            base_c = dc.value()
            base = ts.value()
            out = s_on.generate([4, 9, 1], max_new_tokens=12)
            on_steps = ts.value() - base
            check(out["tokens"] == ref["tokens"],
                  "speculative tokens bitwise equal non-speculative "
                  "(greedy)")
            check(on_steps < off_steps,
                  f"speculation used fewer target steps "
                  f"({on_steps} < {off_steps})")
            check(out["spec_proposed"] > 0
                  and out["accept_rate"] is not None,
                  f"accept_rate reported "
                  f"({out['accept_rate']}, {out['spec_proposed']} "
                  "proposed)")
            # before the fresh off-engine below warms ITS ladder into
            # the same process-global counter
            check(dc.value() == base_c,
                  "speculative rounds performed 0 post-warm compiles")
            s_off2 = DecodeEngine(sspec, name="spec_off2", slots=[1],
                                  page_size=4, num_pages=16,
                                  max_seq_len=20, prefill_chunk=4)
            try:
                a = s_off2.generate([7, 2], max_new_tokens=10,
                                    temperature=0.9, top_k=8, seed=11)
                b = s_on.generate([7, 2], max_new_tokens=10,
                                  temperature=0.9, top_k=8, seed=11)
                check(a["tokens"] == b["tokens"],
                      "seeded-sampled tokens identical with "
                      "speculation on vs off")
            finally:
                s_off2.stop()
            check(s_on.cache.allocator.stats()["pages_used"] == 0,
                  "rejected-suffix rollback returned every page")
        finally:
            s_on.stop()

        # -- 7. typed workloads (ISSUE 20) -------------------------------
        from .workloads import TokenMaskSpec, parse_workload, run_workload

        weng = DecodeEngine(spec, name="workloads", slots=[1, 2],
                            page_size=4, num_pages=64, max_seq_len=32,
                            prefill_chunk=4, prefix_cache=True,
                            embeddings=True)
        try:
            wl_shapes = len(weng.stats()["compiled_shapes"])
            # constrained decode: output in the mask's language, ends
            # when the automaton exhausts
            mask = TokenMaskSpec.regex("5 ( 7 | 9 ) 11")
            c1 = weng.generate([1, 2], max_new_tokens=8, mask=mask)
            check(len(c1["tokens"]) == 3 and c1["tokens"][0] == 5
                  and c1["tokens"][1] in (7, 9) and c1["tokens"][2] == 11,
                  f"constrained decode stayed in the mask language "
                  f"({c1['tokens']})")
            # batch-composition independence: same (seed, mask, prompt)
            # under concurrent load, bitwise-identical tokens
            cs1 = weng.generate([1, 2], max_new_tokens=8,
                                mask=mask.to_dict(), temperature=0.9,
                                top_k=8, seed=5)
            bg = [weng.submit([9, 9, int(i)], max_new_tokens=6)
                  for i in range(3)]
            cs2 = weng.generate([1, 2], max_new_tokens=8,
                                mask=mask.to_dict(), temperature=0.9,
                                top_k=8, seed=5)
            check(all(r.ev.wait(120) for r in bg)
                  and cs2["tokens"] == cs1["tokens"],
                  "constrained sampling batch-composition-independent "
                  "(idle == loaded, bitwise)")
            # embeddings: zero decode slots consumed
            dreq = _metrics.counter("serving.decode.requests")
            base_dreq = dreq.value()
            embeds = [weng.submit_embed(list(range(2 + i)))
                      for i in range(4)]
            ok = all(e.ev.wait(120) and e.error is None for e in embeds)
            live_g = _metrics.gauge(
                "serving.decode.live_slots.workloads.v1")
            check(ok and all(
                len(e.result["embedding"]) == spec.d_model
                and len(e.result["logprobs"]) == len(e.prompt) - 1
                for e in embeds),
                "embeddings pooled d_model dims + per-token logprobs")
            check(dreq.value() == base_dreq and live_g.value() == 0,
                  "embeddings completed with decode live_slots "
                  "untouched (zero slots, zero decode requests)")
            # beam: page sharing proven by counters, tokens by equality
            bres = run_workload(weng, {
                "kind": "beam", "prompt": [3, 1, 4, 1, 5, 9, 2, 6],
                "k": 3, "max_new_tokens": 5})
            check(bres["kind"] == "beam" and len(bres["beams"]) == 3
                  and bres["shared_prompt_pages"] > 0
                  and all(c > 0 for c in bres["cached_tokens"]),
                  f"beam children shared prompt pages "
                  f"({bres['shared_prompt_pages']} refcounted, "
                  f"{bres['cached_tokens']} cached tokens)")
            inds = [weng.generate([3, 1, 4, 1, 5, 9, 2, 6, b[0]],
                                  max_new_tokens=4)["tokens"]
                    for b in bres["beams"]]
            check(all(b[1:] == ind
                      for b, ind in zip(bres["beams"], inds)),
                  "temp-0 beams bitwise equal independent decodes")
            # dispatch layer: unknown kinds refuse before any engine work
            try:
                parse_workload({"kind": "nope", "prompt": [1]})
                check(False, "unknown workload kind refused")
            except ValueError:
                check(True, "unknown workload kind refused (ValueError)")
            check(len(weng.stats()["compiled_shapes"]) == wl_shapes,
                  "workload mix performed 0 post-warm compiles")
            check(weng.cache.allocator.stats()["pages_used"] == 0,
                  "workload mix returned every KV page")
        finally:
            weng.stop()

        # decode over RPC with a hot-swap
        srv2 = ServingServer()
        addr2 = srv2.serve()
        cli2 = ServingClient(addr2)
        try:
            cli2.load_decoder("dec", spec.to_dict(), slots=[1, 2],
                              page_size=4, num_pages=16, max_seq_len=8)
            out = cli2.generate("dec", [3, 1], max_new_tokens=4)
            check(out["version"] == 1 and len(out["tokens"]) == 4,
                  "RPC generate serves the decoder")
            cli2.load_decoder("dec", spec.to_dict(), slots=[1, 2],
                              page_size=4, num_pages=16, max_seq_len=8)
            out2 = cli2.generate("dec", [3, 1], max_new_tokens=4)
            check(out2["version"] == 2 and out2["tokens"] == out["tokens"],
                  "decoder hot-swap flipped with identical tokens")
            # streaming generate (ISSUE 12): same tokens, incrementally
            s = cli2.generate("dec", [3, 1], max_new_tokens=4,
                              stream=True)
            check(list(s) == out["tokens"]
                  and s.result["prompt_len"] == 2,
                  "streamed tokens equal buffered (greedy)")
            # checkpoint deploy (ISSUE 12): save the spec'd decoder,
            # redeploy from the manifest, tokens bitwise identical
            from paddle_tpu.checkpoint import save_decoder_checkpoint

            ckdir = os.path.join(tmp, "dec_ck")
            save_decoder_checkpoint(ckdir, spec)
            cli2.load_decoder("dec_ck", checkpoint_dir=ckdir,
                              slots=[1, 2], page_size=4, num_pages=16,
                              max_seq_len=8)
            out3 = cli2.generate("dec_ck", [3, 1], max_new_tokens=4)
            check(out3["tokens"] == out["tokens"],
                  "checkpoint_dir deploy serves bitwise the same model")
            # typed workloads over RPC (ISSUE 20): one "workload"
            # method, kind-dispatched server-side
            cli2.load_decoder("wl", spec.to_dict(), slots=[1, 2],
                              page_size=4, num_pages=32, max_seq_len=16,
                              prefix_cache=True, embeddings=True)
            from .workloads import TokenMaskSpec as _TMS

            wc = cli2.constrained("wl", [1, 2],
                                  _TMS.regex("5 ( 7 | 9 ) 11"),
                                  max_new_tokens=6)
            check(wc["kind"] == "constrained"
                  and wc["tokens"][0] == 5 and wc["tokens"][-1] == 11,
                  "RPC constrained workload decoded in-language")
            we = cli2.embed("wl", [1, 2, 3, 4])
            check(len(we["embedding"]) == spec.d_model
                  and len(we["logprobs"]) == 3,
                  "RPC embed workload returned pooled states + "
                  "logprobs")
            wb = cli2.beam("wl", [3, 1, 4, 1, 5, 9], k=2,
                           max_new_tokens=4)
            check(len(wb["beams"]) == 2
                  and wb["shared_prompt_pages"] > 0,
                  "RPC beam workload shared prompt pages")
        finally:
            cli2.close()
            srv2.shutdown()

    if failures:
        print(f"serving selftest: {len(failures)} FAILURE(S): {failures}")
        return 1
    print("serving selftest: OK")
    return 0


def serve(host: str, port: int, loads) -> int:
    """Operator mode, on the backend JAX gives: nothing here pins a
    platform, and the printed line says which one serves."""
    import time

    import jax

    from . import InferenceEngine, ServingServer

    dev = jax.devices()[0]
    srv = ServingServer()
    host, port = srv.serve(host, port)
    for spec in loads:
        name, _, dirname = spec.partition("=")
        if not dirname:
            print(f"bad --load {spec!r} (want NAME=DIR)")
            srv.shutdown()
            return 2
        eng = srv.registry.deploy(
            name,
            lambda d=dirname, n=name:
                InferenceEngine.from_inference_dir(d, name=n))
        print(f"loaded {name} v{eng.version} from {dirname}")
    print(f"serving on {host}:{port} platform={dev.platform} "
          f"device_kind={dev.device_kind!r} "
          f"devices={len(jax.devices())} (ctrl-c to stop)", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        srv.shutdown()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m paddle_tpu.serving")
    ap.add_argument("--selftest", action="store_true",
                    help="run the in-process end-to-end selftest")
    ap.add_argument("--serve", action="store_true",
                    help="start a ServingServer")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--load", action="append", default=[],
                    metavar="NAME=DIR",
                    help="model(s) to load at startup (repeatable)")
    args = ap.parse_args(argv)

    if args.serve:
        return serve(args.host, args.port, args.load)
    # default: selftest
    _force_cpu()
    return run_selftest()


if __name__ == "__main__":
    sys.exit(main())
