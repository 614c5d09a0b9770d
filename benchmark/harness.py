"""The harness: finds a cell's files by the names in ``BENCHMARK.json``,
runs the cell once, and assembles the one result line. Nothing here knows
a cell, a configuration or a per-layer metric by name.

Layout (``Layout.data`` is ``benchmark/`` in the repo; tests point it at a
temporary directory to add files without editing any):

    BENCHMARK.json                      metrics, cells, run length
    <data>/configs/<config>.json        sizes, source, reduced, assumed
    <data>/workloads/<cell>.json        the traffic mix, as parameters
    <data>/layer_metrics/<metric>.py    read(run) -> number or None
    benchmark/models/<family>.py        builds the system under test
"""
import dataclasses
import importlib
import importlib.util
import json
import os
import statistics
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def say(msg):
    """Information on an earlier line; never the result."""
    print(f"[bench] {msg}", flush=True)


class Refused(SystemExit):
    """The run may not produce a result (no chip, too few chips, files
    missing). Exit code 2, nothing printed under a metric's name."""

    def __init__(self, why):
        print(f"[bench] refused: {why}", file=sys.stderr, flush=True)
        super().__init__(2)


# --------------------------------------------------------------- files

@dataclasses.dataclass
class Layout:
    bench_json: str = os.path.join(ROOT, "BENCHMARK.json")
    data: str = HERE
    checkout: str = ROOT     # compile cache, kernel verdicts, traces

    def load_json(self, *parts):
        path = os.path.join(self.data, *parts)
        try:
            with open(path) as f:
                return json.load(f)
        except FileNotFoundError:
            raise Refused(f"{path} does not exist") from None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    workload: dict            # the traffic mix file
    config: dict              # the configuration file
    end_to_end: list          # BENCHMARK.json entries this cell reports
    per_layer: list
    layout: Layout


def _reports(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name, layout):
    try:
        with open(layout.bench_json) as f:
            bench = json.load(f)
    except FileNotFoundError:
        raise Refused(f"{layout.bench_json} does not exist") from None
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise Refused(f"no workload {name!r} in BENCHMARK.json (has "
                      f"{[w['name'] for w in bench['workloads']]})")
    workload = layout.load_json("workloads", name + ".json")
    config = layout.load_json("configs", entry["config"] + ".json")
    for key, said in (("name", name), ("config", entry["config"]),
                      ("chips", entry["chips"])):
        if workload[key] != said:
            raise Refused(f"workloads/{name}.json says {key} "
                          f"{workload[key]!r}, BENCHMARK.json {said!r}")
    return Cell(name=name, chips=int(entry["chips"]), workload=workload,
                config=config,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)],
                layout=layout)


def load_family(config):
    return importlib.import_module(f"benchmark.models.{config['family']}")


def load_runner(kind):
    """A cell's ``kind`` is the module that runs it: ``benchmark/<kind>.py``
    with ``run(run, family, tracer, t_process)`` and ``KEYS``."""
    try:
        return importlib.import_module("benchmark." + kind)
    except ModuleNotFoundError as e:
        if e.name != "benchmark." + kind:
            raise
        raise Refused(f"no runner benchmark/{kind}.py for kind "
                      f"{kind!r}") from None


# keys of every traffic mix's file: what the harness reads, and the prose
CELL_KEYS = {"name", "config", "chips", "kind", "why", "who"}


def check_keys(where, data, known):
    """Refuse a key that no code reads: a parameter that is written and
    silently ignored would change nothing and claim it had. ``known`` maps
    a group (``""`` is the top level) to the keys it may hold."""
    for group, allowed in known.items():
        sub = data if group == "" else data.get(group)
        unknown = sorted(set(sub) - set(allowed)) if isinstance(
            sub, dict) else []
        if unknown:
            raise Refused(f"{where}: {group or 'top-level'} key(s) "
                          f"{unknown} are read by no code (known: "
                          f"{sorted(allowed)})")


def load_reader(metric_name, layout):
    """``layer_metrics/<metric>.py`` -> its module (needs ``read``)."""
    path = os.path.join(layout.data, "layer_metrics", metric_name + ".py")
    if not os.path.exists(path):
        raise Refused(f"per-layer metric {metric_name!r} has no reader at "
                      f"{path}")
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + metric_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -------------------------------------------------------------- device

def require_tpu(chips):
    """-> jax's devices; refuses anything but enough TPU chips."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise Refused(f"jax found no backend: {e}") from None
    if devs[0].platform != "tpu":
        raise Refused(f"jax's default backend is {devs[0].platform!r}, not "
                      "a TPU: this benchmark measures the chip or nothing")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, {len(devs)} attached")
    return devs


def device_report(devs, chips):
    peaks = []
    for d in devs[:chips]:
        peaks.append(int((d.memory_stats() or {}).get("peak_bytes_in_use",
                                                      0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks)}


def place_caches(layout):
    """The persistent compile cache and the kernel gate's verdict file,
    both at fixed paths inside the checkout (the path is part of the
    cache's key). Where JAX_COMPILATION_CACHE_DIR is set, that directory
    is used and nothing is set in code."""
    import jax
    import paddle_tpu as paddle
    cache = paddle.jit.use_compile_cache(layout.checkout)
    # every program, however quick to compile, so that a second run finds
    # all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    verdicts = os.path.join(cache, "kernel_verdicts.json")
    os.environ["PADDLE_TPU_KERNELS_CACHE"] = verdicts
    say(f"compile cache {cache}; kernel verdicts {verdicts}")
    return cache


# ----------------------------------------------------------------- run

@dataclasses.dataclass
class Run:
    """What one run collected; per-layer readers take what they need."""
    cell: Cell
    seed: int
    seconds: float
    chips: int
    device_kind: str
    setup_s: float = 0.0
    window_s: float = 0.0           # measured window, host clock
    window_wall: tuple = (0.0, float("inf"))   # the same, on time.time()
    e2e: dict = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    correct: bool = False
    # every number ``correct`` compared: short name -> (value, limit)
    compared: dict = dataclasses.field(default_factory=dict)
    # training
    step_s: list = dataclasses.field(default_factory=list)
    losses: list = dataclasses.field(default_factory=list)
    tokens_per_step: int = 0
    flops_per_token: float = 0.0
    # serving
    requests: list = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)
    # device trace (``--trace 1`` only): trace_reduce.Summary or None
    trace: object = None
    pallas_ops: set = dataclasses.field(default_factory=set)


def within(run, name, value, limit):
    """``value <= limit``, noted under ``name`` for the result line's
    ``compared`` and the last lines of standard error."""
    run.compared[name] = (float(value), float(limit))
    return value <= limit


def pct(values, q):
    """The q-th percentile by linear interpolation (numpy's default)."""
    import numpy as np
    return float(np.percentile(np.asarray(values, dtype=float), q))


def host_use():
    """CPU seconds the process has used so far, (user, kernel). A run, or a
    set-up phase, that is slower on the same work shows here whether the
    host worked more or waited more."""
    import resource
    u = resource.getrusage(resource.RUSAGE_SELF)
    return (u.ru_utime, u.ru_stime)


def median(values):
    return float(statistics.median(values))


class Tracer:
    """Device trace of a stretch of the window, in a traced run only."""

    def __init__(self, cell, on):
        self.on = bool(on)
        self.dir = os.path.join(cell.layout.checkout, ".bench_trace",
                                cell.name)
        self.started = None
        self.host_s = None
        self._lock = threading.Lock()

    def start(self):
        if not self.on:
            return
        import shutil
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # the host loop is not slowed
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.started = time.perf_counter()

    def stop(self):
        """Idempotent, and safe from a helper thread: a second caller waits
        for the first to finish writing the trace."""
        with self._lock:
            if self.started is None or self.host_s is not None:
                return
            import jax
            asked = time.perf_counter() - self.started
            jax.profiler.stop_trace()
            self.host_s = asked

    def due(self, stretch_s):
        return self.started is not None and self.host_s is None and \
            time.perf_counter() - self.started >= stretch_s

    def summary(self, pallas_ops=()):
        if self.host_s is None:
            return None
        from . import trace_reduce
        path = trace_reduce.find_xplane(self.dir)
        if path is None:
            say(f"no .xplane.pb under {self.dir}")
            return None
        t0 = time.perf_counter()
        s = trace_reduce.summarize(trace_reduce.load(path),
                                   pallas_ops=pallas_ops)
        say(f"trace {path} ({os.path.getsize(path) / 2**20:.1f} MiB) "
            f"reduced in {time.perf_counter() - t0:.1f} s; traced stretch "
            f"{self.host_s:.2f} s by the host clock")
        return s


def result_line(run, devs, trace_on):
    """The contract's last line, as a dict."""
    cell = run.cell
    metrics = {}
    if not trace_on:
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                value = run.setup_s
            else:
                value = run.e2e.get(m["name"])
            if value is None:
                raise RuntimeError(
                    f"cell {cell.name} declares end-to-end metric "
                    f"{m['name']!r} but its kind "
                    f"{cell.workload['kind']!r} did not produce it (has "
                    f"{sorted(run.e2e)})")
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        took = []
        for m in cell.per_layer:
            t0 = time.perf_counter()
            value = load_reader(m["name"], cell.layout).read(run)
            took.append((time.perf_counter() - t0, m["name"]))
            if value is None:
                say(f"per-layer metric {m['name']}: nothing to read, left "
                    "out")
                continue
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        # the first reader that asks for the host plane pays its parse
        say(f"per-layer readers: {sum(t for t, _ in took):.2f} s in all; "
            "slowest " + ", ".join(f"{name} {t:.2f} s" for t, name in
                                   sorted(took, reverse=True)[:3]))
    device = device_report(devs, run.chips)
    out = {"correct": bool(run.correct), "attempted": int(run.attempted),
           "failed": int(run.failed), "metrics": metrics, "device": device}
    if trace_on and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.top_ops[:10],
                            "idle_gaps": run.trace.top_gaps[:10]}
    # last: each number ``correct`` compared, beside its limit
    out["compared"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in run.compared.items()}
    return out


def run_cell(name, seed, seconds, trace, layout=None, device_check=None,
             t_process=None):
    """Run one cell once; -> the result line as a dict. ``device_check``
    is ``require_tpu`` unless a test steers the harness onto the CPU."""
    t_process = time.perf_counter() if t_process is None else t_process
    layout = layout or Layout()
    cell = load_cell(name, layout)
    try:
        import paddle_tpu  # noqa: F401
    except ImportError as e:
        raise Refused(f"the program under test is not here: {e}") from None
    t_imported = time.perf_counter()
    devs = (device_check or require_tpu)(cell.chips)
    say(f"imports and devices ready {time.perf_counter() - t_process:.1f} s "
        f"after the process started (the devices "
        f"{time.perf_counter() - t_imported:.1f} s of it); "
        f"{sum(host_use()):.1f} s of CPU")
    say(f"cell {cell.name}: config {cell.config['name']} on {cell.chips} "
        f"chip(s); device {devs[0].platform} {devs[0].device_kind!r} x "
        f"{len(devs)}; seed {seed}, window {seconds} s, trace {int(trace)}")
    place_caches(layout)
    runner = load_runner(cell.workload["kind"])
    fam = load_family(cell.config)
    check_keys(f"workloads/{name}.json", cell.workload,
               dict(runner.KEYS, **{"": runner.KEYS[""] | CELL_KEYS}))
    check_keys(f"configs/{cell.config['name']}.json", cell.config,
               fam.CONFIG_KEYS)
    run = Run(cell=cell, seed=int(seed), seconds=float(seconds),
              chips=cell.chips, device_kind=devs[0].device_kind)
    runner.run(run, fam, Tracer(cell, trace), t_process)
    return result_line(run, devs, bool(trace))
