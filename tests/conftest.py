"""Test harness: run everything on a virtual 8-device CPU mesh so multi-chip
sharding paths are exercised without TPU hardware (reference precedent: the
fake custom-device plugin, SURVEY §4 'fake backends')."""
import os

# Tests run on the CPU, on eight virtual devices; the chip is reached through
# chip_smoke.py. Set before jax is imported, whatever the caller's env says.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running multiprocess/chaos test "
        "(deselected by the tier-1 `-m 'not slow'` run)")
    assert jax.devices()[0].platform == "cpu", "tests must run on CPU mesh"
    assert len(jax.devices()) == 8, "expected 8 virtual CPU devices"


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle
    paddle.seed(2024)
    np.random.seed(2024)
    # fleet topology is module-global state: a mesh left by one test must
    # not leak into the next (tests that need one call fleet.init)
    from paddle_tpu.distributed import topology as _topo
    _topo._hcg = None
    # snapshot every other process-wide knob a test can tweak — default
    # dtype, the flag registry (+ its NaN-check mirror), the in-process
    # fault spec — and restore after the test. This is what turned the
    # alphabetical full run's order-dependent failure cluster (ROADMAP
    # "suite health": leaks surfacing near test_incubate_nn_layers/
    # test_inference_ptq) into a guarantee rather than luck: a test that
    # forgets its own cleanup can no longer poison its successors.
    from paddle_tpu.core import dispatch as _dispatch
    from paddle_tpu.core import dtype as _dtype
    from paddle_tpu.distributed import fault as _fault
    from paddle_tpu.framework import flags as _flags
    saved_dtype = _dtype._default_dtype
    saved_flags = {k: f.value for k, f in _flags._registry.items()}
    saved_nan_check = _dispatch._check_nan_inf
    saved_nan_window = _dispatch._nan_window
    saved_fault_env = os.environ.get("PADDLE_TPU_FAULTS")
    saved_fault_entries = _fault._entries
    saved_kernels_env = os.environ.get("PADDLE_TPU_KERNELS")
    yield
    _dtype._default_dtype = saved_dtype
    for k, v in saved_flags.items():
        if k in _flags._registry:
            _flags._registry[k].value = v
    _dispatch._check_nan_inf = saved_nan_check
    _dispatch._nan_window = saved_nan_window
    _dispatch._nan_pending.clear()
    # the Pallas demotion-gate verdict cache is process-global: a test
    # that records a verdict (or forces PADDLE_TPU_KERNELS) must not
    # steer kernel selection for its successors
    from paddle_tpu.ops.pallas import _common as _pallas_gate
    _pallas_gate._reset_state()
    if os.environ.get("PADDLE_TPU_KERNELS") != saved_kernels_env:
        if saved_kernels_env is None:
            os.environ.pop("PADDLE_TPU_KERNELS", None)
        else:
            os.environ["PADDLE_TPU_KERNELS"] = saved_kernels_env
    # the flight recorder is process-wide too: drop back to the (disabled)
    # env-gated default so an enabled recorder/desync mode can't leak
    from paddle_tpu.distributed import flight_recorder as _flight
    _flight._reset_state()
    # control-plane replication writer ids (claim-key namespace for the
    # WAL's exactly-once adds) restart per test: deterministic op ids,
    # and no claim collisions against a recycled store port
    from paddle_tpu.distributed import tcp_store as _tcp_store
    _tcp_store._reset_replication_state()
    # grad-sync hooks (overlap engine's bucket schedulers) are a process-
    # global registry on the autograd walk: a test that attached one (or
    # leaked a DataParallel with comm_overlap=True) must not keep firing
    # collectives in its successors' backwards
    from paddle_tpu.core import autograd as _autograd
    try:
        _autograd._grad_sync_hooks.clear()
    except AttributeError:
        pass  # a test monkeypatched the registry with a stand-in
    # same for the observability planes (metrics registry, trace buffer):
    # a test that enables them must not leak histograms/spans into — or
    # slow down — its successors
    from paddle_tpu.observability import metrics as _obs_metrics
    from paddle_tpu.observability import telemetry as _obs_telemetry
    from paddle_tpu.observability import tracing as _obs_tracing
    _obs_metrics._reset_state()
    _obs_tracing._reset_state()
    _obs_telemetry._active = None
    if os.environ.get("PADDLE_TPU_FAULTS") != saved_fault_env:
        if saved_fault_env is None:
            os.environ.pop("PADDLE_TPU_FAULTS", None)
        else:
            os.environ["PADDLE_TPU_FAULTS"] = saved_fault_env
    _fault._entries = saved_fault_entries
    # so is the preemption flag: a test that delivered SIGTERM to this
    # process and left it set makes every later lineage'd fit on the same
    # xdist worker save-and-exit 75 at its first poll (which files share a
    # worker varies from run to run, so the victim passed alone)
    _fault._preempt_event.clear()
    # tpu-lint summary-DB cache (ISSUE 15 --changed-only): a test that
    # pointed PADDLE_TPU_LINT_CACHE at a scratch DB must not let it
    # steer the next test's scan — un-setting the var is the isolation
    # (the file itself may be an operator's warm cache: never deleted)
    from paddle_tpu.tools.analyze import summary as _lint_summary
    _lint_summary.reset_cache_state()
    os.environ.pop("PADDLE_TPU_LINT_CACHE", None)
