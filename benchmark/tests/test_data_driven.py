"""A cell, a configuration and a per-layer metric are each added by new
files and new entries alone: no file that is there is edited."""
import json
import os

from benchmark import harness

from .conftest import cpu_devices

BOUNDS = {"train_tokens_per_s": 0.015, "serve_tokens_per_s": 0.08,
          "itl_p99_ms": 0.05, "ttft_p90_ms": 0.1, "setup_s": 0.1}


def test_add_config_cell_and_metric(layout):
    data = layout.data
    # a new configuration: the tiny one, one layer deeper
    with open(os.path.join(data, "configs", "tiny-serve.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny-serve-deep", num_layers=3)
    with open(os.path.join(data, "configs", "tiny-serve-deep.json"),
              "w") as f:
        json.dump(cfg, f)
    # a new traffic mix: parameters only
    mix = {"name": "tiny-serve-deep.long-out", "config": "tiny-serve-deep",
           "chips": 1, "kind": "closed_loop", "clients": 2,
           "requests_per_client": 300, "stagger_first": False,
           "prompt_len": {"dist": "fixed", "value": 6},
           "output_len": {"dist": "uniform", "min": 8, "max": 12},
           "warm_requests": 1, "warm_output_tokens": 4, "ramp_timeout_s": 60, "trace_seconds": 0.5,
           "why": "test", "who": "test",
           "correct": {"sample": 2, "logit_gap_abs": 0.0625,
                       "logit_gap_reason": "as the real cells",
                       "reference_pad": 64}}
    with open(os.path.join(data, "workloads", mix["name"] + ".json"),
              "w") as f:
        json.dump(mix, f)
    # a new per-layer metric: a reader of its own
    with open(os.path.join(data, "layer_metrics", "rounds_per_token.py"),
              "w") as f:
        f.write('LAYER = "serving round"\nMOVES = "itl_p99_ms"\n\n\n'
                'def read(run):\n'
                '    c = run.counters\n'
                '    return c["steps"] / max(1, c["decode_tokens"])\n')
    # and the entries
    with open(layout.bench_json) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-serve-deep", "source": "test",
                             "file": "configs/tiny-serve-deep.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": mix["name"],
                               "config": "tiny-serve-deep",
                               "traffic": "long-out", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("serve_tokens_per_s", "itl_p99_ms"):
            m["workloads"].append(mix["name"])
    bench["per_layer"].append({"name": "rounds_per_token", "unit": "1",
                               "better": "lower",
                               "source": "program_counter",
                               "layer": "serving round",
                               "moves": "itl_p99_ms",
                               "workloads": [mix["name"]]})
    with open(layout.bench_json, "w") as f:
        json.dump(bench, f)

    line = harness.run_cell(mix["name"], 11, 1.0, False, layout=layout,
                            device_check=cpu_devices)
    assert set(line["metrics"]) == {"serve_tokens_per_s", "itl_p99_ms",
                                    "setup_s"}
    assert line["correct"] is True
    line = harness.run_cell(mix["name"], 11, 1.0, True, layout=layout,
                            device_check=cpu_devices)
    assert set(line["metrics"]) == {"rounds_per_token"}
    assert line["metrics"]["rounds_per_token"]["value"] > 0


def test_real_files_agree_with_benchmark_json():
    """Every name in the real BENCHMARK.json finds its files, every
    reader declares the layer and the end-to-end metric the entry gives,
    and no configuration changes a width it does not own up to."""
    layout = harness.Layout()
    with open(layout.bench_json) as f:
        bench = json.load(f)
    e2e = {m["name"] for m in bench["end_to_end"]}
    # the bounds as PR 38 and PR 40 set them (PERF.md section 2 has the runs)
    assert {m["name"]: m["bound"] for m in bench["end_to_end"]} == BOUNDS
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"], layout)
        assert cell.workload["config"] == w["config"]
        assert cell.workload["chips"] == w["chips"] == cell.chips
        assert cell.config["name"] == w["config"]
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        harness.load_family(cell.config)
    for c in bench["configs"]:
        with open(os.path.join(harness.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        pub = cfg["published"]
        # a width the source states is run as stated; which widths a
        # source states is the family's (the GPT table gives heads x
        # head size = hidden and an unreduced context; the three MoE
        # configurations' own tests hold them to their sources'
        # config.json: test_new_kinds, test_afmoe_cell, test_kda_cell)
        for key in ("hidden_size", "num_heads", "head_dim",
                    "intermediate_size", "max_seq_len"):
            if key in pub or cfg["family"] == "gpt":
                assert cfg[key] == pub[key], (c["name"], key)
        if cfg["family"] == "gpt":
            assert cfg["hidden_size"] == cfg["num_heads"] * cfg["head_dim"]
        changed = {k for k in pub if k in cfg and cfg[k] != pub[k]}
        assert changed <= set(cfg["reduced"]) | set(cfg["assumed"])
    for m in bench["per_layer"]:
        mod = harness.load_reader(m["name"], layout)
        assert mod.LAYER == m["layer"] and mod.MOVES == m["moves"]
        assert m["moves"] in e2e
