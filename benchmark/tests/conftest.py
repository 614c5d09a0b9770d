"""The benchmark's own tests: run by hand (``python -m pytest
benchmark/tests -q``), not part of the tier-1 suite. They rehearse every
cell's code path on the CPU at a tiny size through the same harness
functions the command uses. The command itself still refuses a CPU; these
tests steer the harness with a ``device_check`` of their own, which no
option of the program or of the command can do.
"""
import json
import os
import shutil

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

# the real cell whose metrics each tiny cell takes over
STANDS_FOR = {
    "tiny-train.steps": "gpt3-1p3b-train.pretrain-2k",
    "tiny-train4.steps": "gpt3-13b-train.hybrid-mp2-sh2",
    "tiny-serve.closed": "gpt3-1p3b-serve.decode-sat",
    "tiny-serve.open": "gpt3-1p3b-serve.chat-short",
}


def tiny_benchmark(real):
    """The real BENCHMARK.json with its cells replaced by the tiny ones:
    the same metrics, the same readers."""
    data = os.path.join(HERE, "data")
    workloads, configs = [], {}
    for name in STANDS_FOR:
        with open(os.path.join(data, "workloads", name + ".json")) as f:
            w = json.load(f)
        workloads.append({"name": name, "config": w["config"],
                          "traffic": name.split(".", 1)[1],
                          "chips": w["chips"], "why": w["why"]})
        configs[w["config"]] = {
            "name": w["config"], "source": "tiny sizes, CPU rehearsal",
            "file": f"configs/{w['config']}.json", "reduced": [],
            "why": "CPU rehearsal"}
    back = {v: k for k, v in STANDS_FOR.items()}
    out = dict(real, workloads=workloads, configs=list(configs.values()))
    for sec in ("end_to_end", "per_layer"):
        out[sec] = []
        for m in real[sec]:
            m = dict(m)
            if "workloads" in m:
                m["workloads"] = [back[w] for w in m["workloads"]
                                  if w in back]
            out[sec].append(m)
    return out


@pytest.fixture()
def layout(tmp_path, monkeypatch):
    """A temporary benchmark directory: the tiny configurations and mixes,
    the real per-layer readers, and a BENCHMARK.json made from the real
    one. Tests add files to it and edit none."""
    from benchmark import harness, peaks
    data = tmp_path / "benchmark"
    shutil.copytree(os.path.join(HERE, "data"), data)
    shutil.copytree(os.path.join(BENCH, "layer_metrics"),
                    data / "layer_metrics")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    bench_json = tmp_path / "BENCHMARK.json"
    bench_json.write_text(json.dumps(tiny_benchmark(real)))
    # the CPU has no published peak; a test lends it the v5e's so that the
    # readers run. Nothing read here is a device number.
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    return harness.Layout(bench_json=str(bench_json), data=str(data),
                          checkout=str(tmp_path))


def cpu_devices(chips):
    import jax
    devs = jax.devices()
    assert devs[0].platform == "cpu" and len(devs) >= chips
    return devs
