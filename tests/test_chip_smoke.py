"""What ``chip_smoke.py`` and the driver's chip check lean on, as far as a
CPU sandbox can hold it: without a TPU the smoke fails and prints no
result, asking for the TPU is an error and not a silent CPU run, a
utilization against an unknown device's peak is an error, and importing
the package does not touch a JAX backend (a launcher parent that did
would hold the chip its children need)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env():
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PADDLE_TPU_", "PADDLE_TRAINER"))}
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
    return env


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_chip_smoke_fails_without_a_tpu(argv):
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py"),
                        *argv], env=_env(), cwd=REPO, capture_output=True,
                       text=True, timeout=120)
    assert r.returncode != 0, r.stdout + r.stderr
    assert '"ok": true' not in r.stdout
    assert "set_device('tpu')" in r.stderr, r.stderr[-2000:]


def test_set_device_tpu_raises_on_a_cpu_only_backend():
    import paddle_tpu as paddle
    for name in ("tpu", "tpu:0", "tpu:3", "xla"):
        with pytest.raises(RuntimeError, match="0 such device"):
            paddle.set_device(name)
    # an index past what is attached is an error too, on any platform
    with pytest.raises(RuntimeError, match="8 such device"):
        paddle.set_device("cpu:8")
    assert paddle.set_device("cpu:1").device_id == 1
    paddle.set_device("cpu")
    with pytest.raises(ValueError):
        paddle.set_device("gpu")


def test_peak_flops_is_a_table_and_an_unknown_kind_raises(monkeypatch):
    from paddle_tpu.observability.metrics import peak_flops
    assert peak_flops("TPU v5 lite") == 197e12
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "1e15")   # retired override
    assert peak_flops("TPU v5 lite") == 197e12
    for kind in ("cpu", "", "TPU v5 lite pod", "v5"):
        with pytest.raises(KeyError, match="no published peak"):
            peak_flops(kind)


def test_importing_the_package_initialises_no_backend():
    code = """
import paddle_tpu
import paddle_tpu.distributed.launch.main
import paddle_tpu.serving.fleet.router
import paddle_tpu.serving.fleet.remote
import paddle_tpu.jit
paddle_tpu.jit.use_compile_cache(".")
from jax._src import xla_bridge
assert not xla_bridge.backends_are_initialized(), xla_bridge._backends
print("NO-BACKEND")
"""
    r = subprocess.run([sys.executable, "-c", code], env=_env(), cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0 and "NO-BACKEND" in r.stdout, \
        r.stdout + r.stderr


def test_bench_without_a_chip_exits_nonzero_and_prints_no_row():
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=_env(), cwd=REPO, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == "", r.stdout
    assert "measures on a TPU" in r.stderr


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    import jax

    from paddle_tpu.jit import use_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        assert use_compile_cache(str(tmp_path)) == "/placed/outside"
        assert jax.config.jax_compilation_cache_dir == was  # nothing set
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        monkeypatch.chdir(tmp_path)
        want = os.path.join(str(tmp_path), ".jax_cache")
        assert use_compile_cache(".") == want       # resolved, not relative
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
