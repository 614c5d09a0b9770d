"""The ``afmoe`` family's cell end to end on the CPU at a tiny size (kind
``closed_loop_logits``, window and full layers over two page groups), and
``trinity-large-serve.json`` held to its source's keys. ``conftest.py``'s
tiny benchmark knows the first four cells only; this file adds its own to
a copy, as ``test_new_kinds.py`` does."""
import json
import os

import pytest

from benchmark import harness

from .conftest import cpu_devices

CELL, REAL = "tiny-afmoe.closed", "trinity-large-serve.mixed-long"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture()
def layout3(layout):
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(layout.bench_json) as f:
        tiny = json.load(f)
    w = layout.load_json("workloads", CELL + ".json")
    tiny["workloads"].append({"name": CELL, "config": w["config"],
                              "traffic": "closed", "chips": 1,
                              "why": "CPU rehearsal"})
    for sec in ("end_to_end", "per_layer"):
        for m, r in zip(tiny[sec], real[sec]):
            assert m["name"] == r["name"]
            if REAL in r.get("workloads", ()):
                m["workloads"].append(CELL)
    with open(layout.bench_json, "w") as f:
        json.dump(tiny, f)
    return layout


def _declared(layout, section):
    with open(layout.bench_json) as f:
        bench = json.load(f)
    return {m["name"]: m for m in bench[section]
            if "workloads" not in m or CELL in m["workloads"]}


def test_end_to_end_line(layout3):
    line = harness.run_cell(CELL, seed=3000000019, seconds=2.0, trace=False,
                            layout=layout3, device_check=cpu_devices)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == set(_declared(layout3, "end_to_end"))


def test_traced_line(layout3):
    line = harness.run_cell(CELL, seed=11, seconds=2.0, trace=True,
                            layout=layout3, device_check=cpu_devices)
    declared = _declared(layout3, "per_layer")
    host_side = {n for n, m in declared.items()
                 if m["source"] != "device_trace"}
    assert {"window_held_pct.serve", "experts_idle_pct.serve"} <= host_side
    assert host_side <= set(line["metrics"]) <= set(declared)
    assert line["correct"] is True
    # prompts of 40-100 tokens against a window of 12 and a chunk of 16:
    # the window layers give most of a long request's pages back
    assert 0.0 < line["metrics"]["window_held_pct.serve"]["value"] < 80.0
    assert 0.0 <= line["metrics"]["experts_idle_pct.serve"]["value"] <= 100.0


def test_real_files_agree_with_benchmark_json_and_the_source():
    layout = harness.Layout()
    with open(layout.bench_json) as f:
        bench = json.load(f)
    cell = harness.load_cell(REAL, layout)
    entry = next(w for w in bench["workloads"] if w["name"] == REAL)
    assert cell.workload["config"] == entry["config"] and cell.chips == 1
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    fam = harness.load_family(cell.config)
    runner = harness.load_runner(cell.workload["kind"])
    harness.check_keys(REAL, cell.workload, dict(
        runner.KEYS, **{"": runner.KEYS[""] | harness.CELL_KEYS}))
    harness.check_keys(cell.config["name"], cell.config, fam.CONFIG_KEYS)
    c = next(c for c in bench["configs"]
             if c["name"] == "trinity-large-serve")
    cfg = cell.config
    assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
    assert cfg["reduced"] == ["num_layers", "experts_held", "vocab_size",
                              "max_seq_len"]
    # the traffic ISSUE 33 names
    wl = cell.workload
    assert (wl["clients"], wl["requests_per_client"]) == (32, 24)
    assert wl["prompt_len"] == {"dist": "log_uniform", "min": 512,
                                "max": 16384}
    assert wl["output_len"] == {"dist": "uniform", "min": 512, "max": 2048}
    assert wl["prompt_len"]["max"] + wl["output_len"]["max"] \
        == cfg["max_seq_len"] == wl["correct"]["reference_pad"]
    # what is run differs from the source only where `reduced` says so
    kinds, dense = fam.layers_run(cfg)
    assert kinds == ["sliding_attention"] * 4 + ["full_attention"]
    assert dense == 1 and cfg["layers_run"] == [0, 8, 9, 10, 11]
    assert cfg["experts_held"] == [0, 32] and cfg["num_experts"] == 256
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    e = cfg["engine"]
    assert e["num_pages"] == 32 * 18432 // e["page_size"] + 1
    assert e["window_pages"] == 32 * 20 + 1 and e["prefix_cache"] is False
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Large-Preview")
    assert cfg["source"].startswith(row["source_url"])
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == {"vocab_size"}
    m = fam.model_config(cfg)
    assert (m.hidden_size, m.num_heads, m.num_kv_heads, m.head_dim) \
        == (3072, 48, 8, 128)
    assert (m.sliding_window, m.intermediate_size, m.moe_intermediate_size,
            m.num_experts, m.num_experts_per_tok, m.num_shared_experts,
            m.route_scale) == (4096, 12288, 3072, 256, 4, 1, 2.448)
    for metric in bench["per_layer"]:
        if REAL in metric.get("workloads", ()):
            mod = harness.load_reader(metric["name"], layout)
            assert mod.LAYER == metric["layer"]
            assert mod.MOVES == metric["moves"]
