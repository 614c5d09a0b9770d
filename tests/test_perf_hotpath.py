"""Hot-path perf round (ISSUE 7): structural regression tests.

Wall-clock assertions are flaky on shared CI hosts, so every guarantee here
is asserted STRUCTURALLY instead: dict-lookup/import counts via monkeypatched
hooks, retrace counts via side-effect counters, host-sync counts via the
fit loop's single fetch funnel. A reintroduced per-op import, per-op
retrace, or per-step blocking fetch fails these tests deterministically.
"""
import builtins
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.core import dispatch
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.jit import to_static

import jax
import jax.numpy as jnp


# ------------------------------------------------ dispatch fast path


def test_taped_op_constant_time_noop(monkeypatch):
    """With metrics/trace/profiler off, one taped eager op performs ≤1
    compiled-callable cache lookup and ZERO imports or metrics-registry
    resolutions (ISSUE satellite: the flight-recorder-disabled test's
    counting style, not wall clock)."""
    x = paddle.to_tensor(np.random.randn(64).astype("float32"),
                         stop_gradient=False)
    y = paddle.to_tensor(np.random.randn(64).astype("float32"))
    for _ in range(3):
        (x * y)  # warm: resolve lazies, seen-set, compile the callable

    lookups = []

    class CountingDict(dict):
        def get(self, k, default=None):
            lookups.append(k)
            return dict.get(self, k, default)

        def __getitem__(self, k):
            lookups.append(k)
            return dict.__getitem__(self, k)

    counting = CountingDict(dispatch._jit_cache)
    monkeypatch.setattr(dispatch, "_jit_cache", counting)

    imports = []
    real_import = builtins.__import__

    def counting_import(name, *a, **k):
        imports.append(name)
        return real_import(name, *a, **k)

    def boom():
        raise AssertionError("metrics registry re-resolved on the fast path")

    import gc
    monkeypatch.setattr(dispatch, "_resolve_op_metrics", boom)
    gc.disable()  # a GC finalizer firing mid-op imports on ITS own path,
    gc.collect()  # which would count against the dispatch path unfairly
    builtins.__import__ = counting_import
    try:
        r = x * y
    finally:
        # plain assignment: monkeypatch.setattr itself imports (inspect)
        builtins.__import__ = real_import
        gc.enable()
    assert isinstance(r, Tensor) and not r.stop_gradient
    assert imports == [], f"taped op imported: {imports}"
    assert len(lookups) <= 1, f"taped op did {len(lookups)} cache lookups"


TRACE_COUNT = {"n": 0}


def _counting_mul(a, b):
    # references module globals only — a closure cell over a mutable
    # would (correctly) make the fwd uncacheable
    TRACE_COUNT["n"] += 1
    return jnp.multiply(a, b)


def test_compiled_callable_cache_no_retrace():
    """Second call at the same (op, shape/dtype/device) must NOT re-trace;
    a dtype change must. Counted with a side-effect counter in the fwd —
    the trace runs python, the cached executable does not."""
    dispatch._reset_jit_cache()
    TRACE_COUNT["n"] = 0
    x32 = paddle.to_tensor(np.ones(32, "float32"))
    y32 = paddle.to_tensor(np.ones(32, "float32"))
    out = [dispatch.apply("ph_mul", _counting_mul, [x32, y32])
           for _ in range(4)]
    # call 1: seen-set (direct eager run), call 2: jit trace, 3-4: cached
    assert TRACE_COUNT["n"] == 2, TRACE_COUNT
    np.testing.assert_allclose(out[-1].numpy(), np.ones(32, "float32"))
    # dtype change retraces exactly once (jax keys on avals internally)
    xi = paddle.to_tensor(np.ones(32, "int32"))
    yi = paddle.to_tensor(np.ones(32, "int32"))
    dispatch.apply("ph_mul", _counting_mul, [xi, yi])
    dispatch.apply("ph_mul", _counting_mul, [xi, yi])
    assert TRACE_COUNT["n"] == 3, TRACE_COUNT
    # shape change retraces once too, then caches
    x8 = paddle.to_tensor(np.ones(8, "float32"))
    dispatch.apply("ph_mul", _counting_mul, [x8, x8])
    dispatch.apply("ph_mul", _counting_mul, [x8, x8])
    assert TRACE_COUNT["n"] == 4, TRACE_COUNT


def test_compiled_callable_cache_device_move():
    """The cached callable must follow a device change, not pin the first
    placement (jax re-lowers per placement under the same wrapper)."""
    dispatch._reset_jit_cache()

    def fwd(a, b):
        return jnp.add(a, b)

    d0, d1 = jax.devices()[0], jax.devices()[1]
    a0 = paddle.to_tensor(jax.device_put(jnp.ones(16), d0))
    r0 = dispatch.apply("ph_add_dev", fwd, [a0, a0])
    r0 = dispatch.apply("ph_add_dev", fwd, [a0, a0])  # cached now
    a1 = paddle.to_tensor(jax.device_put(jnp.ones(16), d1))
    r1 = dispatch.apply("ph_add_dev", fwd, [a1, a1])
    assert d1 in r1._data.devices(), r1._data.devices()
    np.testing.assert_allclose(r1.numpy(), 2 * np.ones(16, "float32"))
    assert d0 in r0._data.devices()


def test_compiled_callable_scalar_static_baked():
    """Python scalars in the input list become jit statics: the chained
    ``r * 1.0001`` pattern keeps ONE cache entry (no per-value churn for
    the same scalar, no per-op host constant upload)."""
    dispatch._reset_jit_cache()
    x = paddle.to_tensor(np.ones(64, "float32"))
    r = x
    for _ in range(6):
        r = r * 1.0001
    muls = [k for k in dispatch._jit_cache
            if "multiply" in str(k)]
    assert len(muls) == 1, dispatch._jit_cache.keys()
    np.testing.assert_allclose(r.numpy(), 1.0001 ** 6 * np.ones(64),
                               rtol=1e-5)


def test_nan_check_respects_toggle_with_cached_callable():
    """FLAGS_check_nan_inf toggles take effect immediately — the check
    lives OUTSIDE the compiled callable, so the cache entry survives the
    toggle in both directions."""
    x = paddle.to_tensor(np.asarray([1.0, 0.0], np.float32))
    for _ in range(3):
        x / 2.0  # warm + cache the divide callable
    paddle.set_flags({"FLAGS_check_nan_inf": True})
    try:
        with pytest.raises(FloatingPointError, match="divide"):
            x / 0.0
    finally:
        paddle.set_flags({"FLAGS_check_nan_inf": False})
    x / 0.0  # toggled off again: no raise


def test_nan_check_window_batches_the_host_sync():
    """FLAGS_check_nan_inf_window=N defers the blocking flag fetch until N
    results pend; the eventual raise names the first offending op."""
    paddle.set_flags({"FLAGS_check_nan_inf": True,
                      "FLAGS_check_nan_inf_window": 4})
    try:
        bad = paddle.to_tensor(np.asarray([1.0, 0.0], np.float32))
        bad / 0.0                      # pends — no raise yet
        assert len(dispatch._nan_pending) == 1
        bad * 2.0                      # still under the window
        assert len(dispatch._nan_pending) == 2
        with pytest.raises(FloatingPointError, match="divide"):
            dispatch.flush_nan_checks()
        assert not dispatch._nan_pending
        # window fill triggers the flush without an explicit call
        bad / 0.0
        bad * 1.0
        bad * 1.0
        with pytest.raises(FloatingPointError, match="divide"):
            bad * 1.0
    finally:
        paddle.set_flags({"FLAGS_check_nan_inf": False,
                          "FLAGS_check_nan_inf_window": 1})


def test_nan_pending_flushes_at_backward():
    paddle.set_flags({"FLAGS_check_nan_inf": True,
                      "FLAGS_check_nan_inf_window": 64})
    try:
        x = paddle.to_tensor(np.asarray([1.0, 0.0], np.float32),
                             stop_gradient=False)
        bad = x / 0.0
        assert dispatch._nan_pending
        with pytest.raises(FloatingPointError, match="divide"):
            bad.sum().backward()
    finally:
        paddle.set_flags({"FLAGS_check_nan_inf": False,
                          "FLAGS_check_nan_inf_window": 1})


# ------------------------------------------------ fused whole-step path


def _linear_step():
    paddle.seed(7)
    net = nn.Linear(16, 4)
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=net.parameters())

    def train_step(x, y):
        loss = F.mse_loss(net(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return net, opt, to_static(train_step, capture=(net, opt))


def test_fused_step_no_per_step_eager_rng(monkeypatch):
    """A staged step whose trace consumed no randomness must not create
    eager RNG keys per call (2 device launches/step),
    and must not advance the global generator."""
    from paddle_tpu.core import random as prandom
    net, opt, step = _linear_step()
    x = paddle.to_tensor(np.random.randn(8, 16).astype("float32"))
    y = paddle.to_tensor(np.random.randn(8, 4).astype("float32"))
    step(x, y)
    step(x, y)  # fast memo armed
    counter = prandom.default_generator()._counter

    def boom(*a, **k):
        raise AssertionError("eager jax.random key created on the "
                             "steady-state fused-step path")

    monkeypatch.setattr(prandom.Generator, "next_key", boom)
    for _ in range(3):
        loss = step(x, y)
    assert prandom.default_generator()._counter == counter
    assert np.isfinite(float(loss.numpy()))


STEP_PHASES = ["step.gather", "step.launch", "step.rebind"]


def _xy():
    rng = np.random.RandomState(1)
    return (paddle.to_tensor(rng.randn(8, 16).astype("float32")),
            paddle.to_tensor(rng.randn(8, 4).astype("float32")))


def test_fused_step_records_train_step_and_its_phases():
    """Tracing ON, a whole-step function records one ``train_step`` a call,
    numbered in order, and inside it ``step.gather`` / ``step.launch`` /
    ``step.rebind`` tiling it, each with the thread's CPU time; the first
    call's trace, lowering and compile show inside its ``step.launch``."""
    from paddle_tpu.observability import tracing
    net, opt, step = _linear_step()
    x, y = _xy()
    buf = tracing.start()
    try:
        losses = [float(step(x, y).numpy()) for _ in range(4)]
    finally:
        tracing.stop()
    assert losses[-1] < losses[0]
    events = [e for e in buf.events if e.get("cat") == "step"]
    steps = [e for e in events if e["name"] == "train_step"]
    assert [e["args"] for e in steps] == [{"step": i} for i in range(4)]
    eps = 1.0                            # us: float rounding of ts + dur
    for st in steps:
        inside = [e for e in events if e["name"] != "train_step"
                  and e["args"]["step"] == st["args"]["step"]]
        assert [e["name"] for e in inside] == STEP_PHASES
        assert abs(inside[0]["ts"] - st["ts"]) <= eps
        for a, b in zip(inside, inside[1:]):
            assert abs(b["ts"] - (a["ts"] + a["dur"])) <= eps
        assert abs(inside[-1]["ts"] + inside[-1]["dur"]
                   - (st["ts"] + st["dur"])) <= eps
        for e in inside + [st]:
            assert 0.0 <= e["cpu_us"] <= e["dur"] + 100.0
    # the time between two steps is the caller's: in no span
    for a, b in zip(steps, steps[1:]):
        assert b["ts"] >= a["ts"] + a["dur"] - eps
    launch0 = next(e for e in events if e["name"] == "step.launch")
    jit = [e for e in buf.events if e["name"].startswith("jit.")
           and launch0["ts"] <= e["ts"] + eps
           and e["ts"] + e["dur"] <= launch0["ts"] + launch0["dur"] + eps]
    assert {"jit.trace", "jit.lower", "jit.compile"} <= \
        {e["name"] for e in jit}
    assert all(e["args"]["fun_name"] for e in jit)


@pytest.mark.parametrize("profile", [False, True],
                         ids=["all-off", "profile-only"])
def test_fused_step_gate_off_structurally_zero_overhead(monkeypatch,
                                                        profile):
    """Buffer OFF: the step neither reads the thread's CPU clock nor
    records anything, and with no profile running it allocates no phase
    either (one gate a step is the whole budget). While a profile is being
    taken the phases go to the annotation alone (the benchmark's training
    runner starts the profiler and not the buffer)."""
    import time
    from paddle_tpu.jit import api
    from paddle_tpu.observability import tracing
    net, opt, step = _linear_step()
    x, y = _xy()
    step(x, y)
    step(x, y)                           # fast memo armed
    assert not tracing.enabled()
    log, calls = [], {"thread_time_ns": 0, "add": 0, "phase": 0}

    class Ann:
        is_enabled = staticmethod(lambda: profile)

        def __init__(self, name, **kw):
            self.name = name
            log.append((name, kw))

        def __enter__(self):
            pass

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    def count(key, inner=None):
        def h(*a, **k):
            calls[key] += 1
            return inner(*a, **k) if inner else 0
        return h

    monkeypatch.setattr(api, "_Annotation", Ann)
    monkeypatch.setattr(tracing, "_ANNOTATION", Ann)
    monkeypatch.setattr(time, "thread_time_ns", count("thread_time_ns"))
    monkeypatch.setattr(tracing.TraceBuffer, "add", count("add"))
    monkeypatch.setattr(tracing, "phase", count("phase", tracing.phase))
    n0 = step._steps_run
    for _ in range(2):
        assert np.isfinite(float(step(x, y).numpy()))
    assert calls["thread_time_ns"] == calls["add"] == 0
    if not profile:
        assert calls["phase"] == 0 and log == []
        return
    assert calls["phase"] == 2 * 4       # the step and its three phases
    opened = [e for e in log if e[0] != "exit"]
    assert opened == [(name, {"step": n0 + i}) for i in range(2)
                      for name in ["train_step"] + STEP_PHASES]
    assert [e[1] for e in log if e[0] == "exit"] == \
        [name for _ in range(2) for name in STEP_PHASES + ["train_step"]]


def test_fused_step_rng_step_keys_advance():
    """A dropout step consumes randomness: consecutive steps must use
    DIFFERENT keys (the uint32 spec advances the generator), and two
    identically-seeded runs stay bit-identical."""
    def run():
        paddle.seed(11)
        net = nn.Sequential(nn.Linear(16, 16), nn.Dropout(0.5),
                            nn.Linear(16, 4))
        opt = paddle.optimizer.SGD(learning_rate=1e-2,
                                   parameters=net.parameters())

        def train_step(x, y):
            loss = F.mse_loss(net(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        step = to_static(train_step, capture=(net, opt))
        rng = np.random.RandomState(3)
        x = paddle.to_tensor(rng.randn(8, 16).astype("float32"))
        y = paddle.to_tensor(rng.randn(8, 4).astype("float32"))
        return [float(step(x, y).numpy()) for _ in range(4)]

    a, b = run(), run()
    assert a == b, "seeded fused-step runs must be bit-identical"
    assert len(set(a)) > 1, "per-step keys must differ (dropout varies)"


def test_fused_step_fast_path_matches_slow_path():
    """Parameters after N fast-path steps equal a fresh staged run's (the
    memoized dispatch is the same compiled program, same donation)."""
    def run(n):
        net, opt, step = _linear_step()
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randn(8, 16).astype("float32"))
        y = paddle.to_tensor(rng.randn(8, 4).astype("float32"))
        for _ in range(n):
            step(x, y)
        return net.weight.numpy()

    np.testing.assert_allclose(run(5), run(5), rtol=0, atol=0)


def test_fused_step_tracks_lr_schedule():
    """The learning rate rides the compiled program as a traced input —
    an lr change between steps takes effect WITHOUT retracing."""
    net, opt, step = _linear_step()
    x = paddle.to_tensor(np.random.randn(8, 16).astype("float32"))
    y = paddle.to_tensor(np.random.randn(8, 4).astype("float32"))
    step(x, y)
    step(x, y)
    w0 = net.weight.numpy().copy()
    opt.set_lr(0.0)  # frozen optimizer: params must stop moving
    step(x, y)
    w1 = net.weight.numpy()
    delta = float(np.abs(w1 - w0).max())
    # AdamW at lr=0 still applies zero update; weight decay is lr-scaled
    assert delta == 0.0, delta
    assert len(step._cache) == 1, "lr change must not retrace"


def test_fused_step_invalidate_rediscovers_state():
    net, opt, step = _linear_step()
    x = paddle.to_tensor(np.random.randn(8, 16).astype("float32"))
    y = paddle.to_tensor(np.random.randn(8, 4).astype("float32"))
    step(x, y)
    assert step._state_cache is not None and step._fast_step
    step.invalidate()
    assert step._state_cache is None and not step._fast_step
    loss = step(x, y)  # re-walks, re-memoizes, still correct
    assert np.isfinite(float(loss.numpy()))
    assert step._fast_step


# ------------------------------------------------ fit loop host syncs


def _fit_model():
    net = nn.Linear(16, 4)
    model = paddle.Model(net)
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=net.parameters())
    model.prepare(optimizer=opt, loss=nn.MSELoss())
    return model


def _ds(n_batches=12, bs=4):
    from paddle_tpu.io import Dataset
    X = np.random.RandomState(42).randn(n_batches * bs, 16).astype("float32")
    Y = X @ np.random.RandomState(7).randn(16, 4).astype("float32")

    class DS(Dataset):
        def __getitem__(self, i):
            return X[i], Y[i]

        def __len__(self):
            return len(X)

    return DS()


def test_fit_bounded_host_syncs_per_step(monkeypatch):
    """ISSUE satellite: the eager/fused train LOOP issues a BOUNDED number
    of blocking host syncs — counted structurally through the fit loop's
    single fetch funnel (Model._fetch_scalar / _fetch_scalars), so the
    110→27 steps/s class of regression (a reintroduced per-step fetch)
    is caught without wall-clock flakiness."""
    from paddle_tpu.hapi.model import Model
    scalar_fetches = {"n": 0}
    batch_fetches = {"n": 0}
    real_scalar = Model._fetch_scalar
    real_batch = Model._fetch_scalars

    def count_scalar(loss):
        scalar_fetches["n"] += 1
        return real_scalar(loss)

    def count_batch(losses):
        batch_fetches["n"] += 1
        return real_batch(losses)

    monkeypatch.setattr(Model, "_fetch_scalar", staticmethod(count_scalar))
    monkeypatch.setattr(Model, "_fetch_scalars", staticmethod(count_batch))
    model = _fit_model()
    steps = 12
    hist = model.fit(_ds(steps), batch_size=4, epochs=1, shuffle=False,
                     verbose=0, loss_fetch_every=4)
    # fetch cadence 4 over 12 steps -> 3 scalar fetches (steps 0,4,8) and
    # ONE stacked epoch-end fetch for the lazy remainder
    assert scalar_fetches["n"] == 3, scalar_fetches
    assert batch_fetches["n"] == 1, batch_fetches
    assert scalar_fetches["n"] + batch_fetches["n"] < steps
    assert len(hist["loss"]) == 1 and np.isfinite(hist["loss"][0])


def test_fit_amortized_history_matches_per_step_fetch():
    """Epoch means are EXACT under the amortized fetch — identical to a
    strict per-step fetch run (same seed, same order)."""
    def run(fetch_every):
        paddle.seed(5)
        model = _fit_model()
        return model.fit(_ds(8), batch_size=4, epochs=2, shuffle=False,
                         verbose=0, loss_fetch_every=fetch_every)

    h1, h50 = run(1), run(50)
    np.testing.assert_allclose(h1["loss"], h50["loss"], rtol=1e-6)


def test_fit_metrics_attached_keeps_per_step_fetch():
    """User metrics read host values each step — the lazy path must not
    engage (accuracy accumulation needs the synced outputs)."""
    from paddle_tpu.hapi.model import Model
    net = nn.Linear(16, 4)
    model = paddle.Model(net)
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=net.parameters())
    from paddle_tpu.metric import Accuracy
    model.prepare(optimizer=opt, loss=nn.CrossEntropyLoss(),
                  metrics=Accuracy())
    from paddle_tpu.io import Dataset
    X = np.random.RandomState(0).randn(16, 16).astype("float32")
    Y = np.random.RandomState(1).randint(0, 4, 16).astype("int64")

    class DS(Dataset):
        def __getitem__(self, i):
            return X[i], Y[i]

        def __len__(self):
            return 16

    hist = model.fit(DS(), batch_size=4, epochs=1, verbose=0, shuffle=False)
    assert np.isfinite(hist["loss"][0])


def test_engine_fit_amortized_history_exact():
    from paddle_tpu.distributed.auto_parallel import Engine
    def run(fetch_every):
        paddle.seed(9)
        net = nn.Linear(16, 4)
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=net.parameters())
        eng = Engine(model=net, loss=nn.MSELoss(), optimizer=opt)
        rng = np.random.RandomState(0)
        data = [(paddle.to_tensor(rng.randn(8, 16).astype("float32")),
                 paddle.to_tensor(rng.randn(8, 4).astype("float32")))
                for _ in range(6)]
        return run_hist(eng, data, fetch_every)

    def run_hist(eng, data, fetch_every):
        return eng.fit(data, epochs=1, loss_fetch_every=fetch_every)

    h1, h10 = run(1), run(10)
    assert all(isinstance(v, float) for v in h10)
    np.testing.assert_allclose(h1, h10, rtol=1e-6)


def test_telemetry_split_degrades_gracefully_amortized():
    """With metrics on and the amortized fetch, every step still observes
    the full split (sync_ms=0 between fetches) and step_time_ms stays
    wall-clock exact — MFU/tokens-per-sec remain honest."""
    from paddle_tpu.observability import metrics
    reg = metrics.enable()
    try:
        paddle.seed(5)
        model = _fit_model()
        model.fit(_ds(12), batch_size=4, epochs=1, shuffle=False,
                  verbose=0, loss_fetch_every=4)
        snap = reg.snapshot()
        assert snap["counters"]["steps_total"] == 12
        for h in ("step_time_ms", "compute_ms", "sync_ms", "data_wait_ms"):
            assert snap["histograms"][h]["count"] == 12, h
    finally:
        metrics.disable()


# ------------------------------------------------ kernel demotion gate


def test_kernels_env_modes(monkeypatch):
    from paddle_tpu.ops.pallas import _common as gate
    gate._reset_state()
    sig = gate.shape_sig(np.zeros((128, 128), np.float32))
    monkeypatch.setenv("PADDLE_TPU_KERNELS", "xla")
    assert gate.pallas_default("rms_norm", sig) is False
    monkeypatch.setenv("PADDLE_TPU_KERNELS", "pallas")
    assert gate.pallas_default("rms_norm", sig) is True
    monkeypatch.setenv("PADDLE_TPU_KERNELS", "auto")
    # auto with NO measured verdict: demoted, never promoted on faith
    assert gate.pallas_default("rms_norm", sig) is False
    gate.record_verdict("rms_norm", sig, {"backend": "pallas",
                                          "xla_ms": 2.0, "pallas_ms": 1.0,
                                          "reason": "measured win"})
    assert gate.pallas_default("rms_norm", sig) is True
    monkeypatch.setenv("PADDLE_TPU_KERNELS", "bogus")
    with pytest.raises(ValueError, match="PADDLE_TPU_KERNELS"):
        gate.kernels_mode()


def test_gate_nearest_verdict_band():
    from paddle_tpu.ops.pallas import _common as gate
    gate._reset_state()
    big = gate.shape_sig(np.zeros((1024, 256), np.float32))
    gate.record_verdict("fused_adamw", big,
                        {"backend": "pallas", "xla_ms": 2.0,
                         "pallas_ms": 1.0, "reason": "win"})
    near = gate.shape_sig(np.zeros((512, 256), np.float32))      # 2x off
    far = gate.shape_sig(np.zeros((16, 16), np.float32))         # ~1000x
    other_dtype = gate.shape_sig(np.zeros((1024, 256), np.int32))
    assert gate.pallas_default("fused_adamw", near,
                               allow_nearest=True) is True
    assert gate.pallas_default("fused_adamw", far,
                               allow_nearest=True) is False
    assert gate.pallas_default("fused_adamw", other_dtype,
                               allow_nearest=True) is False
    assert gate.pallas_default("fused_adamw", near) is False  # exact-only


def test_ab_gate_records_and_reports():
    from paddle_tpu.ops.pallas import _common as gate
    gate._reset_state()
    a = jnp.ones((64, 64), jnp.float32)

    row = gate.ab_gate("rms_norm", lambda x: x * 2.0, lambda x: x * 2.0,
                       (a,), repeats=2)
    # off-TPU (CPU mesh) the Pallas leg is skipped and XLA wins by default
    assert row["backend"] == "xla" and "TPU" in row["reason"]
    rep = gate.gate_report()
    assert len(rep) == 1 and "rms_norm[64x64:float32]" in rep
    sig = gate.shape_sig(a)
    assert gate.get_verdict("rms_norm", sig)["backend"] == "xla"


@pytest.mark.parametrize("pallas_ok", [True, False])
def test_ab_gate_on_tpu_failure_is_recorded_not_demoted(monkeypatch, capsys,
                                                        pallas_ok):
    """On a TPU a Pallas leg that raises is a defect, not a measured loss:
    the row says ``failed``, stderr says so once per (kernel, shape), XLA
    serves. A leg that runs is timed and carries no such flag."""
    from paddle_tpu.ops.pallas import _common as gate
    gate._reset_state()
    monkeypatch.setattr(gate, "on_tpu", lambda: True)
    a = jnp.ones((64, 64), jnp.float32)

    def pallas_fn(x):
        if not pallas_ok:
            raise NotImplementedError("Mosaic refused this block shape")
        return x * 2.0

    for _ in range(2):
        row = gate.ab_gate("rms_norm", lambda x: x * 2.0, pallas_fn, (a,),
                           repeats=2)
    err = capsys.readouterr().err
    if pallas_ok:
        assert "failed" not in row and row["pallas_ms"] is not None
        assert err == ""
        return
    assert row["failed"] is True and row["backend"] == "xla"
    assert "Mosaic refused" in row["reason"]
    assert err.count("PALLAS KERNEL FAILED ON TPU") == 1   # loud, once
    assert [r.get("failed") for r in gate.gate_report().values()] == [True]
    # the kernel is off the default path, and visibly so
    assert gate.pallas_default("rms_norm", gate.shape_sig(a)) is False


def test_on_tpu_does_not_swallow_a_backend_error(monkeypatch):
    from paddle_tpu.ops.pallas import _common as gate

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(gate.jax, "devices", boom)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        gate.on_tpu()


def test_ab_gate_rejects_tracers():
    from paddle_tpu.ops.pallas import _common as gate

    def f(x):
        gate.ab_gate("rms_norm", lambda a: a, lambda a: a, (x,))
        return x

    with pytest.raises(Exception, match="concrete"):
        jax.jit(f)(jnp.ones(4))


def test_optimizer_fused_auto_consults_gate(monkeypatch):
    """AdamW auto mode (use_fused=None) demotes the Pallas fused update
    unless the gate has a measured win; explicit use_fused=True wins."""
    from paddle_tpu.ops.pallas import _common as gate
    gate._reset_state()
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-2,
        parameters=nn.Linear(4, 4).parameters())
    w = jnp.ones((256, 256), jnp.float32)
    monkeypatch.setenv("PADDLE_TPU_KERNELS", "auto")
    # pretend single-chip TPU (the CPU mesh has 8 devices, which the
    # multi-chip guard would veto before the gate is consulted)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda *a, **k: 1)
    opt.use_fused = None
    opt._FUSED_MIN_SIZE = 1
    assert opt._fused_ok(w, w) is False  # no verdict: demoted
    gate.record_verdict("fused_adamw", gate.shape_sig(w),
                        {"backend": "pallas", "xla_ms": 2.0,
                         "pallas_ms": 1.0, "reason": "win"})
    assert opt._fused_ok(w, w) is True
    monkeypatch.setenv("PADDLE_TPU_KERNELS", "xla")
    assert opt._fused_ok(w, w) is False  # global demotion
    opt.use_fused = True                 # explicit user override wins
    assert opt._fused_ok(w, w) is True


def test_serving_backend_falls_back_to_kernels_env(monkeypatch):
    from paddle_tpu.serving.ragged_attention import resolve_backend
    monkeypatch.delenv("PADDLE_TPU_SERVING_ATTN", raising=False)
    monkeypatch.setenv("PADDLE_TPU_KERNELS", "xla")
    assert resolve_backend() == "xla"
    monkeypatch.setenv("PADDLE_TPU_SERVING_ATTN", "pallas")
    assert resolve_backend() == "pallas"  # serving knob stays the override


def test_static_scalar_signed_zero_not_collided():
    """+0.0 and -0.0 compare equal, so jax.jit's static keying alone would
    share one traced program between them; the (type, repr) wrapper key
    must keep them apart (x / -0.0 → -inf, not +inf)."""
    dispatch._reset_jit_cache()
    x = paddle.to_tensor(np.ones(4, np.float32))
    for _ in range(3):
        rp = x / 0.0
    rn = x / -0.0
    assert np.all(np.isposinf(rp.numpy()))
    assert np.all(np.isneginf(rn.numpy())), rn.numpy()


def test_closure_const_type_not_collided():
    """Same lambda code with c=2 (int) vs c=2.0 (float) must compile two
    programs — eager dtype promotion differs for int operands."""
    dispatch._reset_jit_cache()

    def scale_by(c):
        return lambda a: a * c

    xi = paddle.to_tensor(np.ones(8, np.int32))
    for _ in range(3):
        ri = dispatch.apply("tpscale", scale_by(2), [xi])
    rf = dispatch.apply("tpscale", scale_by(2.0), [xi])
    assert str(ri.dtype) == "int32", ri.dtype
    assert "float" in str(rf.dtype), rf.dtype


def test_gate_unmeasured_defaults():
    """No verdict + auto: flash_attention (incumbent winner) keeps
    serving; the BENCH_r05 losers stay demoted. A measured loss flips the
    incumbent off."""
    from paddle_tpu.ops.pallas import _common as gate
    gate._reset_state()
    os.environ["PADDLE_TPU_KERNELS"] = "auto"
    sig = gate.shape_sig(np.zeros((8, 128, 4, 64), np.float32),
                         np.zeros((8, 128, 4, 64), np.float32))
    assert gate.pallas_default("flash_attention", sig,
                               allow_nearest=True) is True
    for losing in ("fused_adamw", "rms_norm", "layer_norm",
                   "paged_attention"):
        assert gate.pallas_default(losing, sig) is False, losing
    gate.record_verdict("flash_attention", sig,
                        {"backend": "xla", "xla_ms": 1.0, "pallas_ms": 2.0,
                         "reason": "xla beat pallas at this shape"})
    assert gate.pallas_default("flash_attention", sig) is False


def test_gate_nearest_is_rank_agnostic():
    """Bench measures fused AdamW on a flat (N,) vector; real params are
    2-D — the nearest verdict must bridge ranks at similar total size."""
    from paddle_tpu.ops.pallas import _common as gate
    gate._reset_state()
    flat = gate.shape_sig(np.zeros((1024 * 256,), np.float32))
    gate.record_verdict("fused_adamw", flat,
                        {"backend": "pallas", "xla_ms": 2.0,
                         "pallas_ms": 1.0, "reason": "win"})
    two_d = gate.shape_sig(np.zeros((512, 512), np.float32))
    assert gate.pallas_default("fused_adamw", two_d,
                               allow_nearest=True) is True


def test_fused_step_retraces_on_structural_edit():
    """Growing a captured module mid-training must retrace (the Layer
    structural version guards the cached state walk) — the new parameters
    train instead of the old program silently replaying without them."""
    paddle.seed(7)
    net = nn.Sequential(nn.Linear(16, 16))
    opt = paddle.optimizer.SGD(learning_rate=1e-1,
                               parameters=net.parameters())

    def train_step(x, y):
        loss = F.mse_loss(net(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = to_static(train_step, capture=(net, opt))
    x = paddle.to_tensor(np.random.randn(8, 16).astype("float32"))
    y16 = paddle.to_tensor(np.random.randn(8, 16).astype("float32"))
    step(x, y16)
    step(x, y16)  # fast memo armed
    n_keys = len(step._cache)
    net.add_sublayer("grown", nn.Linear(16, 16))
    # the structural guard's job: retrace + state re-walk so the grown
    # layer joins the forward (optimizer coverage of new params is the
    # user's move, as eagerly)
    loss_after = float(step(x, y16).numpy())
    assert len(step._cache) > n_keys, "structural edit did not retrace"
    assert len(step._state_cache[0]) == 4, "state walk missed new params"
    assert np.isfinite(loss_after)


def test_forward_staging_retraces_on_structural_edit():
    """The structural-version guard must cover FORWARD staging too (not
    just the whole-step fast memo): a sublayer added after staging joins
    the compiled forward, matching eager."""
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(4, 4))
    net.eval()
    staged = to_static(net.forward)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    for _ in range(3):
        before = staged(x)
    net.add_sublayer("grown", nn.Linear(4, 4))
    after = staged(x)
    assert not np.allclose(after.numpy(), before.numpy())
    np.testing.assert_allclose(after.numpy(), net(x).numpy(), rtol=1e-6)
