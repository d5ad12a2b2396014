"""Guards that keep the chip honest: nothing on the operator entry points
may substitute the CPU for it without saying so, chip_smoke.py refuses to
pass off-chip, and the compile cache is placed from outside or at one
fixed path under the checkout."""
import os
import subprocess
import sys
import time

import jax

import paddle_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_the_cpu_before_compiling():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.time()
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=120)
    assert r.returncode != 0
    assert time.time() - t0 < 60
    assert "platform=cpu" in r.stdout              # says what it found
    assert "needs a TPU" in r.stderr and "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout and "CHECK" not in r.stdout


def test_executor_raises_when_the_requested_backend_is_absent():
    """core.default_place() takes the backend JAX gives; a backend that
    cannot initialize raises instead of becoming the CPU with a
    warning."""
    code = ("import paddle_tpu.fluid as fluid\n"
            "try:\n"
            "    fluid.Executor()\n"
            "except RuntimeError as e:\n"
            "    print('RAISED', e)\n")
    env = dict(os.environ, JAX_PLATFORMS="tpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=120)
    assert "RAISED" in r.stdout and "tpu" in r.stdout, r.stdout + r.stderr


def _calls(monkeypatch, module, *names):
    seen = []
    for n in names:
        monkeypatch.setattr(
            module, n,
            lambda *a, _n=n, **k: seen.append(_n) or 0)
    return seen


def test_serve_does_not_pin_the_cpu_but_selftest_does(monkeypatch):
    from paddle_tpu.serving import __main__ as m

    seen = _calls(monkeypatch, m, "_force_cpu", "serve", "run_selftest")
    assert m.main(["--serve"]) == 0
    assert seen == ["serve"]
    del seen[:]
    assert m.main(["--selftest"]) == 0
    assert seen == ["_force_cpu", "run_selftest"]


def test_replica_does_not_pin_the_cpu_but_selftest_does(monkeypatch):
    from paddle_tpu.fleet import __main__ as m

    seen = _calls(monkeypatch, m, "_force_cpu", "run_replica",
                  "run_controller", "run_selftest")
    assert m.main(["--replica", "--controller-addr", "127.0.0.1:1"]) == 0
    assert m.main(["--controller"]) == 0
    assert seen == ["run_replica", "run_controller"]
    del seen[:]
    assert m.main([]) == 0
    assert seen == ["_force_cpu", "run_selftest"]


def test_compile_cache_is_placed_from_outside_or_under_the_checkout(
        monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        # placed from outside: JAX reads the variable, code sets nothing
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert paddle_tpu.place_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir is None
        # not placed: one fixed path under the checkout
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(REPO, ".jax_cache")
        assert paddle_tpu.place_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
