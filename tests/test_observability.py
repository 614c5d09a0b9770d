"""Run telemetry (paddle_tpu/observability): metrics registry, per-step
fit telemetry, collective latency histograms off the flight-recorder
ring, Perfetto span export + xplane merge, and the launcher's cross-rank
straggler run report.

Acceptance anchors (ISSUE 5):
* disabled = constant-time no-ops (asserted like the flight-recorder
  disabled test);
* PADDLE_TPU_METRICS=1 emits parseable per-rank JSONL with step_time_ms,
  tokens_per_sec, mfu_pct, data_wait_ms and per-collective histograms,
  and a 2-worker launcher run prints a report naming the slowest rank;
* the trace export of one training step loads with step/fwd/bwd/opt
  spans nested correctly and merges with an xplane device trace.
"""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed import flight_recorder as flight
from paddle_tpu.io import Dataset
from paddle_tpu.observability import metrics, report, telemetry, tracing

WORKERS = os.path.join(os.path.dirname(__file__), "workers")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

sys.path.insert(0, WORKERS)
from ft_markers import free_port  # noqa: E402


def _linear_ds(n_batches=6, bs=4):
    X = np.random.RandomState(42).randn(n_batches * bs, 16) \
        .astype("float32")
    Y = X @ np.random.RandomState(7).randn(16, 4).astype("float32")

    class DS(Dataset):
        def __getitem__(self, i):
            return X[i], Y[i]

        def __len__(self):
            return len(X)

    return DS()


def _fit_linear(epochs=2, callbacks=None, verbose=0):
    net = nn.Linear(16, 4)
    model = paddle.Model(net)
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=net.parameters())
    model.prepare(optimizer=opt, loss=nn.MSELoss())
    model.fit(_linear_ds(), batch_size=4, epochs=epochs, shuffle=False,
              verbose=verbose, callbacks=callbacks)
    return model


# ------------------------------------------------------------ disabled path

def test_metrics_disabled_is_noop():
    """Acceptance: with metrics off every hook is a constant-time no-op —
    no registry, no histogram, no trace buffer, no telemetry callback in
    fit, and the collective hot path records nothing."""
    assert metrics.get_registry() is None
    assert metrics.counter("x") is None
    assert metrics.gauge("x") is None
    assert metrics.histogram("x") is None
    metrics.observe("x", 1.0)       # must not throw
    assert metrics.flush() is None
    assert not tracing.enabled()
    with tracing.span("nope"):
        pass                        # disabled span yields immediately
    t = paddle.to_tensor(np.ones((8, 2), "float32"))
    dist.all_reduce(t)              # full collective path, metrics off
    assert metrics.get_registry() is None
    assert flight.get_recorder() is None
    _fit_linear(epochs=1)
    assert metrics.get_registry() is None
    assert telemetry._active is None


def test_telemetry_hooks_noop_without_active_callback():
    telemetry.mark_sync_begin()     # no active clock: returns immediately
    assert telemetry.maybe_telemetry_callback() is None


# ------------------------------------------------------------- metrics core

def test_counter_gauge_histogram_and_keys():
    reg = metrics.enable()
    c = reg.counter("steps_total")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = reg.gauge("mfu_pct", stage="train")
    g.set(41.5)
    assert g.key == "mfu_pct{stage=train}"
    h = reg.histogram("lat_us", kind="all_reduce", group="world:1")
    for v in (1.5, 3.0, 3.0, 1000.0):
        h.observe(v)
    d = h.to_dict()
    assert d["count"] == 4 and d["min"] == 1.5 and d["max"] == 1000.0
    assert sum(d["counts"]) == 4
    # same (name, labels) -> same child; label order irrelevant
    assert reg.histogram("lat_us", group="world:1",
                         kind="all_reduce") is h
    name, labels = metrics.parse_metric_key(h.key)
    assert name == "lat_us"
    assert labels == {"kind": "all_reduce", "group": "world:1"}
    # quantiles: p50 inside the bucket holding the two 3.0s
    p50 = metrics.hist_quantile(d, 0.5)
    assert 1.5 <= p50 <= 4.0
    assert metrics.hist_quantile(d, 0.99) >= 500.0
    assert metrics.hist_mean(d) == pytest.approx((1.5 + 3 + 3 + 1000) / 4)
    assert metrics.hist_quantile({"count": 0, "bounds": [], "counts": []},
                                 0.5) is None


def test_exp_buckets_shape():
    b = metrics.exp_buckets(1.0, 2.0, 5)
    assert b == [1.0, 2.0, 4.0, 8.0, 16.0]


def test_jsonl_snapshot_roundtrip(tmp_path):
    reg = metrics.enable(out_dir=str(tmp_path), interval_s=0, rank=3)
    reg.counter("steps_total").inc(2)
    reg.histogram("step_time_ms").observe(12.0)
    assert reg.flush() == str(tmp_path / "metrics.3.jsonl")
    reg.counter("steps_total").inc()
    reg.flush()
    lines = [json.loads(l) for l in
             open(tmp_path / "metrics.3.jsonl").read().splitlines()]
    assert len(lines) == 2
    assert lines[0]["rank"] == 3
    assert lines[0]["counters"]["steps_total"] == 2
    assert lines[1]["counters"]["steps_total"] == 3  # cumulative
    assert lines[1]["histograms"]["step_time_ms"]["count"] == 1


def test_metrics_env_gate(monkeypatch, tmp_path):
    monkeypatch.setenv("PADDLE_TPU_METRICS", "1")
    monkeypatch.setenv("PADDLE_TPU_WORKERLOG_DIR", str(tmp_path))
    monkeypatch.setenv("PADDLE_TPU_METRICS_INTERVAL_S", "0")
    metrics._reset_state()
    flight._reset_state()
    reg = metrics.get_registry()
    assert reg is not None and reg.out_dir == str(tmp_path)
    # metrics-on implies a recorder: latency histograms need the ring
    assert flight.get_recorder() is not None


# -------------------------------------- collective latency off the recorder

def test_collective_latency_histograms_from_recorder():
    reg = metrics.enable()
    flight.enable(capacity=16)
    t = paddle.to_tensor(np.ones((8, 2), "float32"))
    dist.all_reduce(t)
    dist.all_reduce(t)
    dist.barrier()
    snap = reg.snapshot()
    hists = snap["histograms"]
    ar = [k for k in hists if "kind=all_reduce" in k
          and k.startswith("collective_latency_us")]
    assert ar and hists[ar[0]]["count"] == 2
    assert hists[ar[0]]["sum"] > 0
    assert any("kind=barrier" in k for k in hists)
    # wire volume: 8*2 f32 = 64 bytes per all_reduce
    assert snap["counters"][
        "collective_bytes_total{kind=all_reduce}"] == 128


def test_async_stream_op_completes_histogram_at_wait():
    """Async (sync_op=False) stream collectives stay *issued* until
    wait(); the latency observation happens at wait, covering the whole
    issue→wait window."""
    reg = metrics.enable()
    flight.enable(capacity=16)
    t = paddle.to_tensor(np.ones((8, 2), "float32"))
    task = dist.stream.all_reduce(t, sync_op=False)
    key = "collective_latency_us{group=world:"

    def _stream_count(s):
        return sum(h["count"] for k, h in s["histograms"].items()
                   if "kind=stream.all_reduce" in k)

    before = _stream_count(reg.snapshot())
    task.wait()
    after = _stream_count(reg.snapshot())
    assert (before, after) == (0, 1), (before, after, key)


# ----------------------------------------------------------- fit telemetry

def test_fit_telemetry_metrics_and_jsonl(tmp_path):
    reg = metrics.enable(out_dir=str(tmp_path), interval_s=0)
    _fit_linear(epochs=2)
    snap = reg.snapshot()
    assert snap["counters"]["steps_total"] == 12
    assert snap["counters"]["tokens_total"] == 48  # 12 steps x bs 4
    for h in ("step_time_ms", "data_wait_ms", "compute_ms", "sync_ms"):
        assert snap["histograms"][h]["count"] == 12, h
    assert snap["gauges"]["tokens_per_sec"] > 0
    # the CPU has no published peak to be a fraction of: no MFU is written
    assert "mfu_pct" not in snap["gauges"]
    # TelemetryCallback.on_train_end flushed the JSONL
    lines = open(tmp_path / "metrics.0.jsonl").read().splitlines()
    assert lines and json.loads(lines[-1])["counters"]["steps_total"] == 12
    # the active clock was cleared on train end
    assert telemetry._active is None


def test_engine_fit_telemetry():
    from paddle_tpu.distributed.auto_parallel import Engine
    reg = metrics.enable()
    net = nn.Linear(16, 4)
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=net.parameters())
    eng = Engine(model=net, loss=nn.MSELoss(), optimizer=opt)
    rng = np.random.RandomState(0)
    data = [(paddle.to_tensor(rng.randn(8, 16).astype("float32")),
             paddle.to_tensor(rng.randn(8, 4).astype("float32")))
            for _ in range(4)]
    hist = eng.fit(data, epochs=2)
    assert len(hist) == 8
    snap = reg.snapshot()
    assert snap["counters"]["steps_total"] == 8
    assert snap["histograms"]["step_time_ms"]["count"] == 8
    assert "mfu_pct" not in snap["gauges"]      # CPU: no peak, no MFU


def test_fit_error_path_clears_telemetry_clock(tmp_path):
    """A fit that raises mid-epoch must still clear the module-global
    telemetry clock and flush the last window (finally path)."""
    from paddle_tpu.hapi.callbacks import Callback
    reg = metrics.enable(out_dir=str(tmp_path), interval_s=0)
    net = nn.Linear(16, 4)
    model = paddle.Model(net)
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=net.parameters())
    model.prepare(optimizer=opt, loss=nn.MSELoss())

    class Boom(Callback):
        def on_train_batch_end(self, step, logs=None):
            if step >= 2:
                raise RuntimeError("injected mid-epoch failure")

    with pytest.raises(RuntimeError, match="injected mid-epoch failure"):
        model.fit(_linear_ds(), batch_size=4, epochs=1, shuffle=False,
                  verbose=0, callbacks=[Boom()])
    assert telemetry._active is None
    # the completed steps before the failure were flushed
    lines = open(tmp_path / "metrics.0.jsonl").read().splitlines()
    assert json.loads(lines[-1])["counters"]["steps_total"] >= 1


def test_progbar_shows_ips_and_step_ms(capsys):
    from paddle_tpu.hapi.callbacks import ProgBarLogger
    _fit_linear(epochs=1, verbose=1,
                callbacks=[ProgBarLogger(log_freq=1, verbose=1)])
    out = capsys.readouterr().out
    assert "ips:" in out and "step_ms:" in out
    assert "loss:" in out


# ----------------------------------------------------------------- tracing

def test_trace_pipeline_step_spans_nested(tmp_path):
    """Acceptance: the Perfetto export of one training step has host
    spans step/fwd/bwd/opt nested correctly (+ pipeline micro-batch
    events from the ring)."""
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "pp_degree": 2,
                               "sharding_degree": 1, "sep_degree": 1,
                               "mp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    reg = metrics.enable()
    flight.enable(capacity=64)
    tracing.start(path=str(tmp_path / "trace.0.json"))

    paddle.seed(0)
    layers = [nn.Linear(12, 24), nn.Linear(24, 8), nn.Linear(8, 4)]
    model = fleet.PipelineLayer(layers, num_stages=2,
                                loss_fn=lambda o, y:
                                paddle.mean((o - y) ** 2))
    pipe = fleet.PipelineParallel(model, num_micro_batches=4)
    opt = paddle.optimizer.SGD(learning_rate=0.01,
                               parameters=model.parameters())
    rng = np.random.RandomState(1)
    x = paddle.to_tensor(rng.randn(8, 12).astype("float32"))
    y = paddle.to_tensor(rng.randn(8, 4).astype("float32"))
    pipe.train_batch((x, y), opt)
    path = tracing.stop()
    doc = json.load(open(path))
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    assert {"step", "fwd", "bwd", "opt"} <= set(by_name), sorted(by_name)
    step = by_name["step"][0]
    s0, s1 = step["ts"], step["ts"] + step["dur"]
    eps = 1.0  # µs slack for clock granularity
    for name in ("fwd", "bwd", "opt"):
        for e in by_name[name]:
            assert e["ts"] >= s0 - eps and \
                e["ts"] + e["dur"] <= s1 + eps, (name, e, step)
    # 4 micro-batches x 2 stages, forward and backward each
    assert len(by_name["fwd"]) == 8 and len(by_name["bwd"]) == 8
    # ring-fed pipeline events kept their own category
    assert any(e.get("cat") == "pipeline" for e in evs)
    # metrics-side: pipe-group entries are COMPUTE — they land in the
    # pipeline_latency_us family, never in the collective table
    hists = reg.snapshot()["histograms"]
    assert any(k.startswith("pipeline_latency_us")
               and "kind=pp_forward" in k for k in hists), hists.keys()
    assert not any(k.startswith("collective_latency_us")
                   and "group=pipe" in k for k in hists)


def test_trace_collective_events_from_ring(tmp_path):
    flight.enable(capacity=16)
    tracing.start(path=str(tmp_path / "t.json"))
    t = paddle.to_tensor(np.ones((8, 2), "float32"))
    with tracing.span("step"):
        dist.all_reduce(t)
    path = tracing.stop()
    doc = json.load(open(path))
    colls = [e for e in doc["traceEvents"]
             if e.get("cat") == "collective"]
    assert colls and colls[0]["name"] == "all_reduce"
    steps = [e for e in doc["traceEvents"] if e.get("name") == "step"]
    assert steps
    # the collective happened inside the step span
    s = steps[0]
    assert s["ts"] - 1.0 <= colls[0]["ts"] \
        and colls[0]["ts"] + colls[0]["dur"] <= s["ts"] + s["dur"] + 1.0


def test_merge_host_trace_with_xplane_device_trace(tmp_path):
    """Acceptance: tools/merge_profiles merges the host-span export with
    an xplane-derived device trace into one multi-lane timeline."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.profiler.xplane import parse_xplane

    tracing.start(path=str(tmp_path / "host.json"))
    with tracing.span("step"):
        with tracing.span("fwd"):
            pass
    host = tracing.stop()

    @jax.jit
    def f(a):
        return jnp.tanh(a @ a).sum()

    a = jnp.ones((64, 64))
    f(a)  # compile outside the trace
    logdir = str(tmp_path / "xp")
    jax.profiler.start_trace(logdir)
    for _ in range(3):
        r = f(a)
    np.asarray(r)
    jax.profiler.stop_trace()
    if not parse_xplane(logdir):
        pytest.skip("jax CPU profiler emitted no device-execution trace "
                    f"events on jax {jax.__version__}")

    from paddle_tpu.tools.merge_profiles import main as merge_main
    out = str(tmp_path / "merged.json")
    assert merge_main([host, logdir, "-o", out]) == 0
    doc = json.load(open(out))
    pids = {e.get("pid") for e in doc["traceEvents"]
            if e.get("ph") == "X"}
    assert pids == {0, 1}  # host lane + device lane
    lanes = {e["args"]["name"] for e in doc["traceEvents"]
             if e.get("name") == "process_name"}
    assert any(l.startswith("device:") for l in lanes), lanes
    assert any(e.get("ph") == "X" and e["pid"] == 1
               for e in doc["traceEvents"])  # device events survived


# ---------------------------------------------------------------- report

def _fake_snap(rank, seq, step_ms_samples, mfu=None):
    h = metrics.Histogram("step_time_ms")
    for v in step_ms_samples:
        h.observe(v)
    ch = metrics.Histogram("collective_latency_us{group=g,kind=all_reduce}")
    for v in (100.0, 200.0, 400.0):
        ch.observe(v)
    snap = {"ts": 1.0 + seq, "rank": rank, "seq": seq,
            "counters": {"steps_total": len(step_ms_samples)},
            "gauges": {"tokens_per_sec": 1000.0 / (rank + 1)},
            "histograms": {
                "step_time_ms": h.to_dict(),
                "collective_latency_us{group=g,kind=all_reduce}":
                    ch.to_dict()}}
    if mfu is not None:
        snap["gauges"]["mfu_pct"] = mfu
    return snap


def test_report_names_slowest_rank_and_percentiles(tmp_path):
    per_rank = {
        0: [_fake_snap(0, 1, [10.0] * 4, mfu=40.0)],
        1: [_fake_snap(1, 1, [30.0] * 4, mfu=20.0)],
    }
    for rank, snaps in per_rank.items():
        with open(tmp_path / f"metrics.{rank}.jsonl", "w") as f:
            for s in snaps:
                f.write(json.dumps(s) + "\n")
    loaded = report.read_rank_snapshots(str(tmp_path))
    assert set(loaded) == {0, 1}
    rep = report.build_run_report(loaded)
    assert rep["slowest_rank"] == 1
    assert rep["ranks"][0]["steps"] == 4
    assert rep["ranks"][0]["mfu_pct"] == 40.0
    coll = rep["collectives"]["all_reduce|g"]
    assert coll["count"] == 6  # merged across both ranks
    assert coll["p50_us"] <= coll["p99_us"]
    text = report.format_run_report(rep)
    assert "slowest rank 1" in text
    assert "all_reduce|g" in text


def test_report_straggler_windows():
    """Per-window slowest-rank attribution from cumulative snapshots:
    rank 1 is slow only in the second window."""
    h0a = metrics.Histogram("s")
    h1a = metrics.Histogram("s")
    for v in (10.0, 10.0):
        h0a.observe(v)
        h1a.observe(v)
    # window 2: rank 0 stays at 10ms, rank 1 jumps to 50ms

    def snap(rank, hist):
        return {"ts": 0, "rank": rank, "seq": 0,
                "counters": {}, "gauges": {},
                "histograms": {"step_time_ms": hist.to_dict()}}

    s0_1 = snap(0, h0a)
    s1_1 = snap(1, h1a)
    for v in (10.0, 10.0):
        h0a.observe(v)
    for v in (50.0, 50.0):
        h1a.observe(v)
    s0_2 = snap(0, h0a)
    s1_2 = snap(1, h1a)
    rep = report.build_run_report({0: [s0_1, s0_2], 1: [s1_1, s1_2]})
    assert rep["straggler_windows"].get(1, 0) >= 1
    assert rep["slowest_rank"] == 1


def test_report_cli_json(tmp_path, capsys):
    with open(tmp_path / "metrics.0.jsonl", "w") as f:
        f.write(json.dumps(_fake_snap(0, 1, [5.0])) + "\n")
    assert report.main([str(tmp_path), "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["ranks"]["0"]["steps"] == 1  # json keys stringify
    assert report.main([str(tmp_path / "empty"), "--json"]) == 0


# ------------------------------------------------- fleet metric reducers

def test_fleet_metrics_reducers_direct():
    """Satellite: the distributed reducers get direct unit tests (single
    controller: local stats over the mesh ARE global)."""
    fm = fleet.metrics
    np.testing.assert_allclose(fm.sum(np.array([1.0, 2.0])),
                               [1.0, 2.0])
    np.testing.assert_allclose(fm.sum(paddle.to_tensor(
        np.array([3.0], "float32"))), [3.0])
    np.testing.assert_allclose(fm.max(np.array([5.0, 1.0])), [5.0, 1.0])
    np.testing.assert_allclose(fm.min(np.array([5.0, 1.0])), [5.0, 1.0])
    assert fm.sum(2.5) == 2.5


def test_fleet_metrics_auc_mae_rmse_acc():
    fm = fleet.metrics
    # perfect separation: positives all above, negatives all below
    assert fm.auc([0.0, 10.0], [10.0, 0.0]) == pytest.approx(1.0)
    # identical distributions: chance
    assert fm.auc([5.0, 5.0], [5.0, 5.0]) == pytest.approx(0.5)
    # no positives: degenerate -> 0.5
    assert fm.auc([0.0, 0.0], [1.0, 1.0]) == 0.5
    assert fm.mae(10.0, 4.0) == pytest.approx(2.5)
    assert fm.rmse(16.0, 4.0) == pytest.approx(2.0)
    assert fm.acc(3.0, 4.0) == pytest.approx(0.75)


# ----------------------------------------------------- profiler satellite

def test_profiler_summary_dict_memory_fields():
    """Satellite: peak_bytes/live_bytes surface through a public field."""
    import gc
    prof = paddle.profiler.Profiler(timer_only=True, profile_memory=True)
    prof.start()
    x = paddle.to_tensor(np.random.RandomState(0)
                         .randn(128, 128).astype("float32"))
    y = x @ x
    del y
    gc.collect()
    prof.step()
    prof.stop()
    d = prof.summary_dict()
    assert d["peak_bytes"] >= 128 * 128 * 4
    assert d["live_bytes"] <= d["peak_bytes"]
    assert prof.peak_bytes == d["peak_bytes"]
    assert prof.live_bytes == d["live_bytes"]
    assert d["mem_events"] >= 1 and d["steps"] == 1
    assert "matmul" in d["mem_table"]


# ----------------------------------------------------- dispatch histogram

def test_eager_dispatch_histogram_gated():
    reg = metrics.enable()
    x = paddle.to_tensor(np.ones(64, "float32"))
    for _ in range(3):
        x = x * 1.0
    h = reg.histogram("eager_dispatch_us")
    assert h.count >= 3
    n = h.count
    metrics.disable()
    x = x * 1.0  # must not observe anymore
    assert h.count == n


# ------------------------------------------------- launcher smoke (2-rank)

def _clean_env(extra=None):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PADDLE_TPU_", "PADDLE_TRAINER"))}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p and p != REPO])
    env.update(extra or {})
    return env


def test_launcher_two_worker_metrics_and_run_report(tmp_path):
    """Acceptance: a 2-worker elastic launcher run with metrics on emits
    parseable per-rank metrics JSONL and the launcher prints an
    aggregated run report naming the slowest rank. Rank 1 sleeps 300 ms
    a step: the report ranks by mean step time over eight steps, the
    first of which compiles (0.4 s alone, 1-2 s beside five loaded xdist
    workers, and not the same on both ranks), so the sleeps have to
    outweigh a compile's spread and not only a step."""
    log_dir = str(tmp_path / "logs")
    env = _clean_env({
        "PADDLE_TPU_METRICS": "1",
        "PADDLE_TPU_METRICS_INTERVAL_S": "0",
        "PADDLE_TPU_TM_SLEEP_RANK": "1:300",
        "PADDLE_TPU_TM_BATCHES": "4",
    })
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--np", "2:2", "--master", f"127.0.0.1:{free_port()}",
         "--elastic_port", str(free_port()), "--log_dir", log_dir,
         os.path.join(WORKERS, "telemetry_worker.py")],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    # per-rank JSONL: parseable, with the acceptance keys
    for rank in (0, 1):
        path = os.path.join(log_dir, f"metrics.{rank}.jsonl")
        assert os.path.exists(path), os.listdir(log_dir)
        lines = [json.loads(l) for l in open(path).read().splitlines()]
        assert lines, f"rank {rank} wrote no snapshots"
        last = lines[-1]
        assert last["rank"] == rank
        for h in ("step_time_ms", "data_wait_ms"):
            assert last["histograms"][h]["count"] >= 8, (rank, h)
        assert last["gauges"]["tokens_per_sec"] > 0
        assert "mfu_pct" not in last["gauges"]  # CPU: no peak, no MFU
        assert any(k.startswith("collective_latency_us")
                   for k in last["histograms"]), last["histograms"].keys()
    # the launcher aggregated and named the straggler
    assert "[telemetry] run report (2 rank(s))" in r.stderr, r.stderr
    assert "slowest rank 1" in r.stderr, r.stderr


@pytest.mark.slow
def test_node_coordinator_metrics_run_report(tmp_path):
    """Heavier multi-node variant: a --nnodes 1:2 coordinator job with
    metrics on ends with the aggregated cross-rank run report."""
    log_dir = str(tmp_path / "logs")
    env = _clean_env({
        "PADDLE_TPU_METRICS": "1",
        "PADDLE_TPU_METRICS_INTERVAL_S": "0",
        "PADDLE_TPU_TM_SLEEP_RANK": "1:300",
        "PADDLE_TPU_TM_BATCHES": "4",
    })
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nnodes", "1:2", "--nproc_per_node", "1",
         "--master", f"127.0.0.1:{free_port()}",
         "--elastic_port", str(free_port()), "--elastic_ttl", "3",
         "--log_dir", log_dir,
         os.path.join(WORKERS, "telemetry_worker.py")],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "[telemetry] run report (2 rank(s))" in r.stderr, r.stderr
    assert "slowest rank 1" in r.stderr, r.stderr


def test_report_straggler_windows_timestamp_aligned():
    """ISSUE 6 satellite: windows are keyed by wall-clock bucket, not
    snapshot index. Rank 1 flushes one EXTRA early snapshot (startup
    probe), which under index alignment shifted all its later windows by
    one — blaming rank 1 for windows where rank 0 was the real
    straggler. With ts bucketing the rank-0 spike at t=20 is attributed
    to rank 0 and rank 1 is never the straggler."""
    bounds = [1e9]

    def snap(rank, ts, count, total_ms):
        return {"ts": ts, "rank": rank, "seq": 0, "counters": {},
                "gauges": {},
                "histograms": {"step_time_ms": {
                    "bounds": bounds, "counts": [count, 0],
                    "count": count, "sum": total_ms,
                    "min": 1.0, "max": 1e3}}}

    # rank 0 flushes at t=10,20,30; window means 5, 100 (spike), 5
    r0 = [snap(0, 10.0, 2, 10.0), snap(0, 20.0, 4, 210.0),
          snap(0, 30.0, 6, 220.0)]
    # rank 1 adds an extra flush at t=5 (mean 1000 warmup), then steady
    # 4ms windows at the same wall times as rank 0
    r1 = [snap(1, 5.0, 1, 1000.0), snap(1, 10.0, 3, 1008.0),
          snap(1, 20.0, 5, 1016.0), snap(1, 30.0, 7, 1024.0)]
    rep = report.build_run_report({0: r0, 1: r1})
    # every 2-rank bucket blames rank 0 (5>4, 100>4, 5>4); the t=5
    # warmup bucket has one rank and is skipped
    assert rep["straggler_windows"] == {0: 3}, rep["straggler_windows"]


def test_report_straggler_single_bucket_merge():
    """A rank double-flushing inside one bucket is averaged, not
    double-counted."""
    bounds = [1e9]

    def snap(rank, ts, count, total_ms):
        return {"ts": ts, "rank": rank, "seq": 0, "counters": {},
                "gauges": {},
                "histograms": {"step_time_ms": {
                    "bounds": bounds, "counts": [count, 0],
                    "count": count, "sum": total_ms,
                    "min": 1.0, "max": 1e3}}}

    r0 = [snap(0, 10.0, 2, 20.0), snap(0, 20.0, 4, 40.0)]
    r1 = [snap(1, 10.0, 2, 10.0), snap(1, 10.4, 3, 15.0),
          snap(1, 20.0, 5, 25.0)]
    rep = report.build_run_report({0: r0, 1: r1})
    # rank 0 mean 10ms per window vs rank 1 5ms -> rank 0 in each bucket
    assert rep["straggler_windows"] == {0: 2}, rep["straggler_windows"]


# ------------------------------------------------ dynamic_flops fallback

def test_flops_bare_layer_counts():
    """ISSUE 6 satellite (PR-5 leftover): a bare leaf layer used as the
    whole net gets hooked (named_sublayers never yields the net itself;
    it used to count 0 and telemetry read MFU=0)."""
    from paddle_tpu.hapi.dynamic_flops import flops
    assert flops(nn.Linear(8, 4), [2, 8]) == 2 * 8 * 4
    assert flops(nn.Linear(8, 4), [-1, 8]) == 8 * 4


def test_telemetry_6n_tokens_fallback_no_table_model():
    """A model with NO table-registered leaves falls back to the
    6*N_params*tokens estimate instead of leaving MFU at 0."""
    class AllCustom(nn.Layer):
        def __init__(self):
            super().__init__()
            self.w = self.create_parameter(shape=[8, 4])

        def forward(self, x):
            return paddle.matmul(x.astype("float32"), self.w)

    reg = metrics.enable(out_dir=None, interval_s=0)
    try:
        net = AllCustom()
        cb = telemetry.TelemetryCallback()
        cb.set_model(net)
        cb.on_train_begin()
        x = paddle.to_tensor(np.zeros((2, 8), dtype="int64"))
        cb.batch_ready(x)   # int [2, 8] input -> 16 tokens
        assert cb.flops_per_step == 6 * 32 * 16
        cb._peak = 197e12   # as on a v5e; the CPU's kind has no peak
        cb.on_train_batch_end(0)
        assert reg.gauge("mfu_pct").value > 0
    finally:
        metrics.disable()
