#!/usr/bin/env python
"""Chaos soak: run the kill-and-drop cluster scenario under
randomized-but-SEEDED fault plans, and print the reproducing seed on
failure. ``--fleet`` instead runs the ISSUE 17 FLEET soak: a live
control plane (controller + autoscale policy + replica launcher) with
real replica SUBPROCESSES under traffic, real SIGKILLs mid-rollout and
mid-stream, poisoned intents, and a cache-aware scale-down — evidence
lands in a JSON file (``--out``), assertions are counter/state-based,
never wall-clock.

Each trial derives a fault spec from its trial seed — response-frame
drops on push_grad, client-side delays, a connection refusal — exports
it via PADDLE_TPU_FAULTS, and runs the scenario test
(tests/test_fault_tolerance.py::test_chaos_scenario_under_env_plan) in
a fresh subprocess. The scenario's invariants hold for EVERY plan this
generator emits: the training pass completes (no deadlock), final
params equal the fault-free run (no lost or double-applied gradients),
the dead trainer is evicted, and the server's dedup hits equal the
client's retransmissions.

    python tools/chaos_soak.py --trials 20 --seed 42

A failing trial prints::

    SOAK_FAIL seed=<trial seed>
    REPRO: PADDLE_TPU_FAULTS='<spec>' python -m pytest \
        tests/test_fault_tolerance.py::test_chaos_scenario_under_env_plan

The generator caps faults below the client's retry budget (3 retries =
4 attempts): at most 3 drops total means even the worst-case clustering
of drops on one logical call still leaves a surviving attempt — the
soak probes ORDERING and TIMING bugs, not budget exhaustion (which is a
documented failure mode, not a bug).
"""
from __future__ import annotations

import argparse
import glob
import os
import random
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCENARIO = ("tests/test_fault_tolerance.py"
            "::test_chaos_scenario_under_env_plan")


def make_spec(seed: int) -> str:
    """Seeded random plan over the scenario's fault surface. The
    scenario makes ~8 push_grad calls (+retransmits) and a handful of
    connects; indices range over that window."""
    rng = random.Random(seed)
    parts = [f"seed={seed}"]
    refuse = rng.random() < 0.5
    # total-budget math per logical call (4 attempts): worst case is all
    # drops clustering on one call's transmissions PLUS the refusal on
    # its re-dial, so with a refusal emitted drops cap at 2 — >=2 drops
    # still satisfies the acceptance bar either way
    n_drops = 2 if refuse else rng.randint(2, 3)
    drops = sorted(rng.sample(range(0, 10), n_drops))
    parts.append("drop@recv.push_grad:" + ",".join(map(str, drops)))
    if refuse:
        parts.append(f"refuse@connect:{rng.randint(0, 2)}")
    if rng.random() < 0.5:
        d = round(rng.uniform(0.01, 0.1), 3)
        parts.append(f"delay@call.push_grad:{rng.randint(0, 7)}={d}")
    return ";".join(parts)


def run_trial(seed: int, verbose: bool = False,
              trace_dir: str | None = None) -> bool:
    spec = make_spec(seed)
    env = dict(os.environ)
    env["PADDLE_TPU_FAULTS"] = spec
    env["PADDLE_TPU_CHAOS"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    trial_dir = None
    if trace_dir:
        # every process of the trial (pytest + any workers it spawns)
        # records spans and exports a per-process shard at exit
        # (tracing's PADDLE_TPU_TRACE_DIR atexit hook) — kept only on
        # failure, merged so the repro spec arrives WITH its timeline
        trial_dir = os.path.join(os.path.abspath(trace_dir),
                                 f"seed{seed}")
        os.makedirs(trial_dir, exist_ok=True)
        env["PADDLE_TPU_TRACE"] = "1"
        env["PADDLE_TPU_TRACE_DIR"] = trial_dir
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", SCENARIO, "-q", "-s",
         "-p", "no:cacheprovider"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    ok = proc.returncode == 0
    print(f"trial seed={seed} spec={spec!r} "
          f"{'OK' if ok else 'FAIL'} ({time.time() - t0:.1f}s)",
          flush=True)
    if not ok or verbose:
        print(proc.stdout[-6000:])
        print(proc.stderr[-3000:], file=sys.stderr)
    if not ok:
        print(f"SOAK_FAIL seed={seed}")
        print(f"REPRO: PADDLE_TPU_FAULTS='{spec}' PADDLE_TPU_CHAOS=1 "
              f"python -m pytest {SCENARIO}")
        if trial_dir:
            _dump_traces(trial_dir)
    elif trial_dir:
        shutil.rmtree(trial_dir, ignore_errors=True)
    return ok


def _dump_traces(trial_dir: str):
    """Merge the failing trial's per-process shards next to the repro
    spec (best effort: a missing merger must not mask the SOAK_FAIL)."""
    shards = sorted(glob.glob(os.path.join(trial_dir, "trace-*.json")))
    if not shards:
        print(f"TRACES: none exported under {trial_dir} "
              "(process died before atexit?)")
        return
    merged = os.path.join(trial_dir, "merged_trace.json")
    print(f"TRACES: {len(shards)} shard(s) in {trial_dir}")
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.observability.timeline",
             "merge", "-o", merged] + shards,
            cwd=REPO, capture_output=True, text=True, timeout=120)
    except Exception as e:  # the shards are already the evidence — a
        # broken/slow merger must not abort the remaining trials
        print(f"TIMELINE: merge failed: {type(e).__name__}: {e}")
        return
    if proc.returncode == 0:
        print(f"TIMELINE: {merged} (open in https://ui.perfetto.dev)")
    else:
        print(f"TIMELINE: merge failed: {proc.stderr.strip()[-500:]}")


# ---------------------------------------------------------------------------
# Fleet soak (ISSUE 17): controller + autoscale policy + launcher + N
# replica SUBPROCESSES under live traffic, with REAL SIGKILLs.
#
# The choreography (every gate is a state predicate, never a sleep-for):
#   1. policy BOOTSTRAPS an empty fleet (min_replicas) — the launcher
#      spawns real `python -m paddle_tpu.fleet --replica` processes
#   2. v1 deploys by checkpoint-dir through the signed intent log
#      (canary -> gate -> durable intent); the under-floor policy grows
#      the fleet to 2 with no operator action
#   3. live traffic (token-verified against an out-of-fleet reference
#      server) pushes fleet free pages under the floor -> policy scales
#      to 3; the new replica converges v1 from the LOG, not an operator
#      (phases 4-6 then pace the traffic and pin min_replicas=3 — the
#      rollout-guard pattern: live-but-light load plus a capacity floor
#      while the fleet is deliberately being shot at)
#   4. SIGKILL the replica serving an in-flight token stream: the
#      stream must splice token-identically on a survivor; the launcher
#      must resurrect the corpse under the same replica id
#   5. roll v2 and SIGKILL a not-yet-rolled replica MID-ROLLOUT: the
#      durable intent converges it anyway after resurrection
#   6. poison the log (unsigned / tampered / out-of-allowlist intents
#      pointing at a REAL loadable checkpoint): every member refuses
#      typed, the applied watermark still passes the poison, and the
#      ghost model appears NOWHERE; a signed remediation unload then
#      lets compaction shrink the log to O(live models)
#   7. traffic stops -> policy drains the COLDEST replica (least
#      cached-token mass) and the launcher stops it; survivors hold
#
# Acceptance: zero dropped and zero corrupted requests end to end
# (typed sheds are the only tolerated non-answer), >=2 crash-restarts,
# scale-up AND cache-aware scale-down with no operator action.
# ---------------------------------------------------------------------------


class SoakFail(AssertionError):
    """A fleet-soak gate failed (timeout or broken invariant)."""


def _wait_until(pred, deadline_s: float, what: str, poll: float = 0.1):
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline_s:
        v = pred()
        if v:
            return v
        time.sleep(poll)
    raise SoakFail(f"timeout ({deadline_s:.0f}s) waiting for: {what}")


class _TrafficStats:
    """Thread-safe tallies; the soak's zero-drop ledger."""

    def __init__(self):
        import threading

        self.mu = threading.Lock()
        self.offered = 0
        self.completed = 0
        self.shed = 0
        self.dropped = 0
        self.corrupted = 0
        self.details: list = []

    def note(self, field: str, detail: str | None = None):
        with self.mu:
            setattr(self, field, getattr(self, field) + 1)
            if detail and len(self.details) < 8:
                self.details.append(detail)

    def snapshot(self) -> dict:
        with self.mu:
            return {"offered": self.offered, "completed": self.completed,
                    "shed": self.shed, "dropped": self.dropped,
                    "corrupted": self.corrupted,
                    "details": list(self.details)}


def run_fleet_soak(seed: int, smoke: bool, out: str | None,
                   verbose: bool = False) -> int:
    """The ISSUE 17 fleet soak. Returns 0 iff every check passed;
    evidence JSON is written to ``out`` (or BENCH_SESSION_r14.json)
    either way."""
    import json
    import tempfile
    import threading

    if REPO not in sys.path:  # `python tools/chaos_soak.py` from anywhere
        sys.path.insert(0, REPO)
    # a CPU soak by construction: this parent initialises a backend
    # (it builds and saves the decoder) and THEN spawns replica
    # processes. A chip belongs to one process, so on a TPU host the
    # children would fail or hang behind the parent — the soak tests
    # the control plane at toy sizes, and says which platform it ran on
    os.environ["JAX_PLATFORMS"] = "cpu"
    work = tempfile.mkdtemp(prefix="fleet_soak_")
    os.environ["PADDLE_TPU_FLEET_KEY"] = f"soak-key-{seed}"
    os.environ["PADDLE_TPU_FLEET_ALLOW"] = work

    from paddle_tpu.checkpoint import save_decoder_checkpoint
    from paddle_tpu.distributed.rpc import RpcClient
    from paddle_tpu.fleet import (FleetController, FleetPolicy,
                                  FleetRouter, ReplicaLauncher,
                                  RolloutDriver, RolloutError,
                                  decoder_artifact)
    from paddle_tpu.fleet import auth as fleet_auth
    from paddle_tpu.observability import metrics as metrics_mod
    from paddle_tpu.serving import (DecoderSpec, ServerOverloaded,
                                    ServingClient, ServingServer)
    from paddle_tpu.serving.decode import build_decoder_params

    rng = random.Random(seed)
    MAX_NEW = 12
    DEC_KW = dict(slots=[2], page_size=4, num_pages=28, max_seq_len=24,
                  prefill_chunk=4, max_queue=8, prefix_cache=True)
    N_WORKERS = 4 if smoke else 6
    COOLDOWN = 16 if smoke else 24  # policy ticks (interval 0.25s)
    spec1 = DecoderSpec(vocab=32, d_model=16, n_layers=1, n_heads=2,
                        n_kv_heads=1, seed=5)
    spec1b = DecoderSpec(vocab=32, d_model=16, n_layers=1, n_heads=2,
                         n_kv_heads=1, seed=6)

    checks: list = []
    evidence: dict = {"bench": "fleet_soak", "seed": seed,
                      "platform": "cpu", "smoke": bool(smoke),
                      "phases": {}}

    def check(name: str, ok, detail=""):
        checks.append({"name": name, "ok": bool(ok),
                       "detail": str(detail)})
        tag = "ok" if ok else "FAIL"
        print(f"  [{tag}] {name}" + (f" ({detail})" if detail else ""),
              flush=True)
        if not ok:
            raise SoakFail(f"{name}: {detail}")

    def ctr(name: str) -> int:
        return int(metrics_mod.counter(name).value())

    # -- setup: checkpoints + out-of-fleet reference tokens ---------------
    ck1 = os.path.join(work, "ck_v1")
    ck2 = os.path.join(work, "ck_v2")
    save_decoder_checkpoint(ck1, spec1, step=1)
    save_decoder_checkpoint(ck2, spec1,
                            params=build_decoder_params(spec1b), step=2)

    prompts = []
    prng = random.Random(seed * 7 + 1)
    for _ in range(6):
        fam = [prng.randrange(1, 32) for _ in range(6)]
        for _ in range(3):
            prompts.append(fam + [prng.randrange(1, 32)])
    stream_prompt = [prng.randrange(1, 32) for _ in range(7)]

    print(f"fleet soak: seed={seed} smoke={smoke} workdir={work}",
          flush=True)
    print("fleet soak: computing reference tokens (v1 + v2)...",
          flush=True)
    refs: dict = {}
    ref_srv = ServingServer()
    try:
        ref_srv.serve("127.0.0.1", 0)
        ref_cli = ServingClient(ref_srv.address)
        ref_cli.load_decoder("ref1", checkpoint_dir=ck1, **DEC_KW)
        ref_cli.load_decoder("ref2", checkpoint_dir=ck2, **DEC_KW)
        for p in prompts + [stream_prompt]:
            for ver, name in ((1, "ref1"), (2, "ref2")):
                refs[(tuple(p), ver)] = list(ref_cli.generate(
                    name, p, max_new_tokens=MAX_NEW)["tokens"])
        ref_cli.close()
    finally:
        ref_srv.shutdown(drain=False)
    check("reference versions diverge",
          any(refs[(tuple(p), 1)] != refs[(tuple(p), 2)]
              for p in prompts),
          "v1 and v2 checkpoints must answer differently somewhere")

    # -- the control plane ------------------------------------------------
    # lease 20s: a replica mid-jax-compile can hold the GIL long enough
    # to starve its beat thread for many seconds — a tighter lease
    # evicts healthy-but-busy joiners and the fleet ladders through
    # auto-N ids forever (each eviction makes the policy backfill, each
    # backfill adds compile load, which starves more beats). 20s also
    # outlives a SIGKILL victim's ~10-15s reboot, so the corpse
    # re-registers under its old id before the lease lapses
    ctl = FleetController(lease_ttl=20.0)
    ctl.serve("127.0.0.1", 0)
    launcher = ReplicaLauncher(ctl.address, poll_interval=0.1,
                               grace=10.0, backoff=0.3, start=True)
    # margin 1.25: the dead band (survivors keep 50 pages) admits the
    # post-traffic drain (two idle survivors hold 54) but blocks drains
    # off transient heartbeat lulls while traffic runs
    policy = FleetPolicy(ctl, interval=0.25, beats=3, cooldown=COOLDOWN,
                         free_page_floor=40, headroom_floor=2,
                         margin=1.25, min_replicas=1, max_replicas=3,
                         start=True)
    router = FleetRouter(ctl.address, scrape_ttl=0.05, replica_ttl=0.25)
    drv = RolloutDriver(ctl.address)
    stats = _TrafficStats()
    stop_traffic = threading.Event()
    # per-request worker throttle (mutable cell, read each iteration):
    # phases 1-3 run the workers HOT to push free pages under the floor;
    # the chaos phases pace them so replica reboots and their double
    # jax compiles (v1 then v2 from the log) get host CPU — traffic
    # stays live through both SIGKILLs, it just stops saturating
    pace = [0.0]
    workers: list = []
    rc = 1

    def view():
        return ctl.policy_view()

    def loaded(st, version=None):
        """Replica has model 'm' (at `version`, if given)."""
        load = st.get("load")
        if not load or "m" not in load.get("models", {}):
            return False
        return version is None or load["models"]["m"] >= version

    def fleet_atleast(n: int, version=None):
        """>=n replicas, EVERY live one serving model 'm' (at
        `version` if given). At-least, not exactly: a SIGKILLed
        replica's lease can expire before its ~10s process reboot
        re-registers, so the policy may legitimately backfill a
        replacement first — the drain path shrinks the fleet back
        inside bounds once the corpse rejoins."""
        v = view()
        ok = (len(v) >= n
              and all(loaded(st, version) for st in v.values()))
        return v if ok else None

    def worker(idx: int):
        wrng = random.Random(seed * 1000 + idx)
        while not stop_traffic.is_set():
            p = wrng.choice(prompts)
            stats.note("offered")
            try:
                out = router.generate("m", p, max_new_tokens=MAX_NEW)
                toks = list(out["tokens"])
                if toks in (refs[(tuple(p), 1)], refs[(tuple(p), 2)]):
                    stats.note("completed")
                else:
                    stats.note("corrupted",
                               f"prompt={p} got={toks}")
            except ServerOverloaded:
                stats.note("shed")
            except Exception as e:
                stats.note("dropped", f"{type(e).__name__}: {e}")
            time.sleep(pace[0] + wrng.uniform(0.0, 0.01))

    try:
        # -- phase 1: bootstrap — empty fleet to min_replicas -------------
        print("fleet soak: phase 1 — policy bootstraps the fleet",
              flush=True)
        _wait_until(lambda: any(st["load"] is not None
                                for st in view().values()),
                    90, "first replica spawned + heartbeating")
        check("bootstrap spawned a replica with NO operator action",
              ctr("fleet.scale.up_intents") >= 1
              and ctr("fleet.launcher.spawns") >= 1)
        evidence["phases"]["bootstrap"] = {
            "replicas": sorted(view()),
            "up_intents": ctr("fleet.scale.up_intents")}

        # -- phase 2: v1 rollout by checkpoint dir through the log --------
        print("fleet soak: phase 2 — v1 checkpoint rollout + growth to 2",
              flush=True)
        canary = sorted(view())[0]
        r1 = drv.rollout(
            "m", decoder_artifact(checkpoint_dir=ck1, **DEC_KW),
            version=1, canary=canary,
            probe=lambda cli: cli.generate("m", prompts[0],
                                           max_new_tokens=2))
        check("v1 canary rollout converged", r1["converged"],
              f"summary={r1}")
        # idle free pages (27/replica) sit under the 40-page floor at
        # n=1, so the policy must grow the fleet to 2 unprompted; the
        # new replica converges v1 from the intent log
        _wait_until(lambda: fleet_atleast(2, version=1), 120,
                    "fleet at 2 replicas, both serving v1 from the log")
        check("under-floor scale-up; joiner converged v1 from the LOG",
              ctr("fleet.scale.up_intents") >= 2)
        evidence["phases"]["v1"] = {
            "rollout": {k: r1[k] for k in ("version", "canary",
                                           "deployed", "converged")},
            "replicas": sorted(view())}

        # -- phase 3: traffic pressure scales the fleet to 3 --------------
        print("fleet soak: phase 3 — live traffic scales the fleet to 3",
              flush=True)
        for i in range(N_WORKERS):
            t = threading.Thread(target=worker, args=(i,), daemon=True,
                                 name=f"soak-traffic-{i}")
            t.start()
            workers.append(t)
        grown = _wait_until(lambda: fleet_atleast(3), 180,
                            "traffic-driven scale-up to 3 replicas")
        check("traffic scaled the fleet to 3",
              ctr("fleet.scale.up_intents") >= 3)
        _wait_until(lambda: stats.snapshot()["completed"] >= 20, 60,
                    "traffic flowing (20 verified completions)")
        evidence["phases"]["scale_up"] = {
            "replicas": sorted(grown),
            "up_intents": ctr("fleet.scale.up_intents"),
            "traffic": stats.snapshot()}
        # chaos window: pace the traffic (live, not saturating) and PIN
        # the capacity floor at 3 — the classic rollout guard. Paced
        # traffic legitimately shows instantaneous-idle heartbeat
        # snapshots (pages are held only while a request is in flight),
        # and the dead band cannot tell a between-requests lull from a
        # genuinely idle fleet — min_replicas=3 makes mid-chaos drains
        # structurally impossible; phase 7 lowers the floor and the
        # policy drains autonomously
        pace[0] = 0.12
        policy.min_replicas = 3

        # -- phase 4: SIGKILL mid-stream ----------------------------------
        print("fleet soak: phase 4 — SIGKILL the replica serving a "
              "live token stream", flush=True)
        want = refs[(tuple(stream_prompt), 1)]
        resumed = killed_rid = None
        t_kill = None

        def reregistered(rid, t0):
            """The rid RE-registered after t0 — the 20s lease keeps a
            SIGKILLed replica's STALE table entry (old endpoint, old
            load summary) visible long past the kill, so 'present and
            loaded' alone would pass while the resurrected process is
            still booting and the next phase would dial a dead port."""
            with ctl._mu:
                st = ctl._replicas.get(rid)
                return st is not None and st["registered_at"] > t0

        for attempt in range(3):
            resumes0 = ctr("fleet.stream.resumes")
            try:
                fs = router.generate("m", stream_prompt,
                                     max_new_tokens=MAX_NEW,
                                     stream=True)
                got = []
                it = iter(fs)
                for _ in range(4):
                    got.append(next(it))
                rid = fs.replica
                t_kill = time.time()
                pid = launcher.kill_replica(rid)
                for t in it:
                    got.append(t)
            except ServerOverloaded:
                time.sleep(1.0)
                continue
            check(f"stream tokens identical across the kill "
                  f"(attempt {attempt})", got == want,
                  f"rid={rid} pid={pid} got={got} want={want}")
            if pid is not None and ctr("fleet.stream.resumes") > resumes0:
                resumed, killed_rid = True, rid
                break
            # stream finished before the SIGKILL landed — try again
        check("mid-stream SIGKILL spliced onto a survivor", resumed,
              "no token-verified resume in 3 attempts")
        check("stream moved off the corpse", fs.replica != killed_rid,
              f"still on {killed_rid}")
        _wait_until(
            lambda: (launcher.stats()["replicas"]
                     .get(killed_rid, {}).get("alive")
                     and reregistered(killed_rid, t_kill)
                     and loaded(view().get(killed_rid, {}))),
            120, f"launcher resurrected {killed_rid} and it re-converged")
        check("launcher crash-restarted the SIGKILLed replica",
              ctr("fleet.launcher.restarts") >= 1)
        evidence["phases"]["mid_stream_kill"] = {
            "victim": killed_rid,
            "stream_resumes": ctr("fleet.stream.resumes"),
            "restarts": ctr("fleet.launcher.restarts")}

        # -- phase 5: v2 rollout with a SIGKILL mid-rollout ---------------
        print("fleet soak: phase 5 — v2 rollout, SIGKILL mid-rollout",
              flush=True)
        seq0 = ctl._fleet_status()["intent_seq"]
        canary2 = sorted(view())[0]
        roll_out: dict = {}

        def _roll():
            try:
                roll_out["summary"] = drv.rollout(
                    "m", decoder_artifact(checkpoint_dir=ck2, **DEC_KW),
                    version=2, canary=canary2,
                    probe=lambda cli: cli.generate(
                        "m", prompts[0], max_new_tokens=2))
            except RolloutError as e:
                # a kill racing the roll may interrupt the driver —
                # the durable intent still converges the fleet
                roll_out["error"] = str(e)

        rt = threading.Thread(target=_roll, daemon=True,
                              name="soak-rollout")
        rt.start()
        # generous: the canary deploy is a fresh jax compile on a
        # possibly just-rebooted replica, under live (paced) traffic.
        # A finished rollout thread also ends the wait, so a canary
        # abort fails FAST with the driver's actual error in evidence
        _wait_until(lambda: (ctl._fleet_status()["intent_seq"] > seq0
                             or roll_out),
                    210, "durable v2 intent appended")
        check("durable v2 intent appended",
              ctl._fleet_status()["intent_seq"] > seq0,
              f"rollout outcome={roll_out}")
        st = ctl._fleet_status()
        lagging = sorted(
            rid for rid, s in st["replicas"].items()
            if rid != canary2
            and (s["applied_seq"] or 0) < st["intent_seq"])
        target = (rng.choice(lagging) if lagging else
                  rng.choice(sorted(r for r in st["replicas"]
                                    if r != canary2)))
        pid2 = launcher.kill_replica(target)
        check("mid-rollout SIGKILL landed on a not-yet-rolled replica",
              pid2 is not None, f"target={target}")
        rt.join(timeout=180)
        check("rollout driver finished", not rt.is_alive(),
              f"outcome={roll_out}")
        _wait_until(lambda: fleet_atleast(3, version=2), 210,
                    "ALL 3 replicas at v2 (incl. the resurrected one, "
                    "converged from the durable intent)")
        check("corpse resurrected AND converged v2 from the log",
              ctr("fleet.launcher.restarts") >= 2)
        evidence["phases"]["mid_rollout_kill"] = {
            "victim": target, "rollout": roll_out,
            "restarts": ctr("fleet.launcher.restarts")}

        # -- phase 6: poisoned intents ------------------------------------
        print("fleet soak: phase 6 — poisoned intents refused fleet-wide",
              flush=True)
        # over-the-wire refusal (controller-side, counted in-process)
        refused0 = ctr("fleet.auth.refused")
        ctl_cli = RpcClient(ctl.address)
        try:
            ctl_cli.call("add_intent", "load_decoder", "ghost",
                         {"checkpoint_dir": ck1})
            check("unsigned append refused at the controller", False)
        except RuntimeError as e:
            check("unsigned append refused at the controller",
                  "intent refused (unsigned)" in str(e), str(e))
        finally:
            ctl_cli.close()
        check("controller refusal counted",
              ctr("fleet.auth.refused") > refused0)
        # member-side: inject poison DIRECTLY into the log (a spoofed
        # controller). The unsigned/tampered poisons name a REAL,
        # allowlisted, loadable checkpoint — only the signature check
        # stands between them and a live 'ghost' model on every replica.
        evil = {"checkpoint_dir": "/etc/fleet-soak-evil", "version": 1}
        evil.update(fleet_auth.signed_fields("load_decoder", "ghost",
                                             dict(evil)))
        poisons = [
            {"action": "load_decoder", "model": "ghost",
             "payload": {"checkpoint_dir": ck1, "version": 1}},
            {"action": "load_decoder", "model": "ghost",
             "payload": {"checkpoint_dir": ck1, "version": 1},
             "nonce": fleet_auth.make_nonce(), "sig": "0" * 64},
            {"action": "load_decoder", "model": "ghost",
             "payload": {k: evil[k] for k in
                         ("checkpoint_dir", "version")},
             "nonce": evil["nonce"], "sig": evil["sig"]},
        ]
        with ctl._mu:
            for rec in poisons:
                ctl._next_seq += 1
                rec["seq"] = ctl._next_seq
                rec["at"] = time.time()
                ctl._intents.append(rec)
            poison_max = ctl._next_seq
        # signed remediation: unload the ghost -> compaction can later
        # drop the whole poisoned episode below the watermark
        fields = fleet_auth.signed_fields("unload_model", "ghost", {})
        seq_fix = int(ctl._add_intent(
            "unload_model", "ghost", {}, fields["nonce"],
            fields["sig"])["seq"])
        _wait_until(
            lambda: all((st["applied_seq"] or 0) >= seq_fix
                        for st in view().values()),
            90, "applied watermark passed the poison (no member wedged)")
        ghost_hosts = [rid for rid, st in view().items()
                       if st["load"]
                       and "ghost" in st["load"]["models"]]
        check("every member refused the poison (ghost model NOWHERE)",
              not ghost_hosts, f"ghost live on {ghost_hosts}")
        _wait_until(
            lambda: ctl._fleet_status()["intent_log_len"] <= 2, 60,
            "compaction shrank the log to O(live models)")
        st6 = ctl._fleet_status()
        check("compaction kept the log O(live models) past the poison",
              st6["intent_log_len"] <= 2
              and st6["intent_seq"] >= poison_max
              and ctr("fleet.intents.compacted") > 0,
              f"len={st6['intent_log_len']} seq={st6['intent_seq']}")
        evidence["phases"]["poison"] = {
            "poison_seqs": [p["seq"] for p in poisons],
            "remediation_seq": seq_fix,
            "intent_log_len": st6["intent_log_len"],
            "intent_seq": st6["intent_seq"],
            "compacted": ctr("fleet.intents.compacted"),
            "auth_refused": ctr("fleet.auth.refused")}

        # -- phase 7: cache-aware scale-down ------------------------------
        print("fleet soak: phase 7 — traffic stops; policy drains the "
              "COLDEST replica", flush=True)
        traffic_final = None
        stop_traffic.set()
        for t in workers:
            t.join(timeout=30)
        traffic_final = stats.snapshot()
        downs0 = ctr("fleet.scale.down_intents")
        # the chaos window is over: lower the pinned capacity floor and
        # let the policy decide the fleet is oversized on its own
        policy.min_replicas = 1
        drain_view = _wait_until(
            lambda: next(
                ((v, rid) for v in [view()]
                 for rid, s in v.items() if s["draining"]), None),
            120, "policy started draining a replica")
        dv, draining_rid = drain_view
        coldest = min(
            (rid for rid, s in dv.items() if s["load"]),
            key=lambda rid: (dv[rid]["load"]["cached_tokens"], rid))
        check("drain victim is the COLDEST replica (cache-aware, "
              "deterministic)", draining_rid == coldest,
              f"drained={draining_rid} coldest={coldest} cached="
              f"{ {r: s['load']['cached_tokens'] for r, s in dv.items() if s['load']} }")
        _wait_until(
            lambda: (ctr("fleet.scale.down_intents") > downs0
                     and len(view()) == 2
                     and draining_rid not in view()
                     and not launcher.stats()["replicas"]
                     .get(draining_rid, {}).get("alive")),
            150, "drained replica unregistered + process stopped")
        time.sleep(3.0)  # dwell: margin dead band must hold at n=2
        check("survivors hold at 2 (dead band, no flap)",
              len(view()) == 2 and ctr("fleet.launcher.stops") >= 1)
        evidence["phases"]["scale_down"] = {
            "victim": draining_rid,
            "cached_tokens": {r: s["load"]["cached_tokens"]
                              for r, s in dv.items() if s["load"]},
            "down_intents": ctr("fleet.scale.down_intents"),
            "launcher_stops": ctr("fleet.launcher.stops")}

        # -- the ledger ---------------------------------------------------
        check("traffic ledger balances (zero dropped, zero corrupted)",
              traffic_final["dropped"] == 0
              and traffic_final["corrupted"] == 0
              and traffic_final["completed"] >= 20
              and (traffic_final["completed"] + traffic_final["shed"]
                   == traffic_final["offered"]),
              f"{traffic_final}")
        check("two real SIGKILLs, two resurrections",
              ctr("fleet.launcher.restarts") >= 2)
        rc = 0
    except SoakFail as e:
        print(f"SOAK_FAIL seed={seed}: {e}", flush=True)
        evidence["failure"] = str(e)
    except Exception as e:  # noqa: BLE001 - evidence must still land
        print(f"SOAK_FAIL seed={seed}: {type(e).__name__}: {e}",
              flush=True)
        evidence["failure"] = f"{type(e).__name__}: {e}"
    finally:
        stop_traffic.set()
        try:
            policy.stop()
            launcher.stop()
            router.close()
            ctl.shutdown()
        except Exception:
            pass
        os.environ.pop("PADDLE_TPU_FLEET_KEY", None)
        os.environ.pop("PADDLE_TPU_FLEET_ALLOW", None)
        shutil.rmtree(work, ignore_errors=True)

    evidence["traffic"] = stats.snapshot()
    evidence["checks"] = checks
    evidence["metrics"] = {
        k: v for k, v in metrics_mod.snapshot(skip_zero=True).items()
        if k.startswith(("fleet.", "rpc.server.dedup"))}
    evidence["ok"] = rc == 0
    out_path = out or os.path.join(REPO, "BENCH_SESSION_r14.json")
    with open(out_path, "w") as f:
        json.dump(evidence, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"fleet soak: {'OK' if rc == 0 else 'FAILED'} — evidence in "
          f"{out_path}", flush=True)
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trials", type=int, default=10)
    ap.add_argument("--seed", type=int, default=None,
                    help="base seed (default: time-derived, printed)")
    ap.add_argument("--verbose", action="store_true")
    ap.add_argument("--trace-dir", default=None,
                    help="dump per-process trace shards + a merged "
                         "Perfetto timeline here for FAILING trials "
                         "(passing trials clean up after themselves)")
    ap.add_argument("--fleet", action="store_true",
                    help="run the ISSUE 17 fleet soak (control plane + "
                         "replica subprocesses + real SIGKILLs) instead "
                         "of the trainer chaos trials")
    ap.add_argument("--smoke", action="store_true",
                    help="fleet soak: lighter traffic + shorter "
                         "cooldowns (CI lane); same 3-replica "
                         "choreography and the same assertions")
    ap.add_argument("--out", default=None,
                    help="fleet soak: evidence JSON path (default: "
                         "BENCH_SESSION_r14.json at the repo root)")
    args = ap.parse_args(argv)
    if args.fleet:
        return run_fleet_soak(
            args.seed if args.seed is not None else 7,
            smoke=args.smoke, out=args.out, verbose=args.verbose)
    base = args.seed if args.seed is not None else int(time.time()) % 100000
    print(f"chaos soak: {args.trials} trials, base seed {base}")
    failures = 0
    for i in range(args.trials):
        if not run_trial(base + i, verbose=args.verbose,
                         trace_dir=args.trace_dir):
            failures += 1
    print(f"chaos soak done: {args.trials - failures}/{args.trials} OK")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
