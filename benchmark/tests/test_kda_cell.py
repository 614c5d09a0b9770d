"""The ``kda_mla_moe`` family's cell end to end on the CPU at a tiny size
(kind ``closed_loop_logits``, three layers with a state a request and one
with latent pages), and ``ling-3p0-flash-serve.json`` held to its source's
keys. ``conftest.py``'s tiny benchmark knows the first four cells only;
this file adds its own to a copy, as ``test_afmoe_cell.py`` does."""
import json
import os

import pytest

from benchmark import harness

from .conftest import cpu_devices

CELL, REAL = "tiny-kda.closed", "ling-3p0-flash-serve.reason-wide"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {"kda_roofline_pct.serve", "state_read_pct.serve"}


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
    assert NEW <= set(declared)
    host_side = {n for n, m in declared.items()
                 if m["source"] != "device_trace"}
    assert {"state_read_pct.serve", "experts_idle_pct.serve"} <= host_side
    assert host_side <= set(line["metrics"]) <= set(declared)
    assert line["correct"] is True
    # three state layers of 4 x 16 x 16 float32 read and written a row
    # against one latent layer's 24 values a cached token at contexts of
    # 40-120: the state is most of what a round moves
    assert 50.0 < line["metrics"]["state_read_pct.serve"]["value"] < 100.0


def test_the_new_readers_read_nothing_of_a_program_without_a_state(layout):
    """The parent, or another family: no span carries ``state_rows`` and no
    configuration names ``kda`` layers; the readers return None and do not
    raise."""
    run = harness.Run(cell=harness.load_cell("tiny-serve.closed", layout),
                      seed=0, seconds=1.0, chips=1, device_kind="cpu")
    run.spans = [{"name": "decode_round", "ph": "X", "ts": 0.0, "dur": 1.0,
                  "args": {"round": 0, "row_lens": [1], "kv_lens": [5],
                           "kv_rows": 5}}]
    for name in NEW:
        assert harness.load_reader(name, layout).read(run) is None


def test_real_files_agree_with_benchmark_json_and_the_source():
    layout = harness.Layout()
    with open(layout.bench_json) as f:
        bench = json.load(f)
    cell = harness.load_cell(REAL, layout)
    entry = next(w for w in bench["workloads"] if w["name"] == REAL)
    assert cell.workload["config"] == entry["config"] and cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "itl_p99_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    # since PR 38 the latent reader counts the latent layers and reads
    # this cell too (one of its seven layers)
    assert NEW | {"mla_roofline_pct.serve"} <= names
    fam = harness.load_family(cell.config)
    runner = harness.load_runner(cell.workload["kind"])
    harness.check_keys(REAL, cell.workload, dict(
        runner.KEYS, **{"": runner.KEYS[""] | harness.CELL_KEYS}))
    harness.check_keys(cell.config["name"], cell.config, fam.CONFIG_KEYS)
    c = next(c for c in bench["configs"]
             if c["name"] == "ling-3p0-flash-serve")
    cfg = cell.config
    assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
    assert cfg["reduced"] == ["num_layers", "experts_held", "vocab_size",
                              "max_seq_len"]
    assert len(c["source"]) <= 200 and len(entry["why"]) <= 200 \
        and len(c["why"]) <= 200
    # the traffic ISSUE 35 names
    wl = cell.workload
    assert (wl["clients"], wl["requests_per_client"]) == (128, 12)
    assert wl["prompt_len"] == {"dist": "log_uniform", "min": 256,
                                "max": 16384}
    assert wl["output_len"] == {"dist": "uniform", "min": 1024, "max": 4096}
    assert wl["prompt_len"]["max"] + wl["output_len"]["max"] \
        == cfg["max_seq_len"] == wl["correct"]["reference_pad"]
    assert (wl["correct"]["sample"], wl["correct"]["positions"]) == (3, 64)
    # what is run differs from the source only where `reduced` says so
    kinds, dense = fam.layers_run(cfg)
    assert kinds == ["kda"] * 6 + ["mla"] and dense == 1
    assert cfg["layers_run"] == [1, 6, 7, 8, 9, 10, 11]
    assert cfg["experts_held"] == [0, 32] and cfg["num_experts"] == 512
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    e = cfg["engine"]
    assert e["max_slots"] == wl["clients"] == 128
    assert e["num_pages"] == 128 * 20480 // e["page_size"] + 1
    assert e["prefill_token_budget"] == 2 * e["prefill_chunk"]
    assert e["token_pads"][-1] == 128 + e["prefill_token_budget"]
    assert e["prefix_cache"] is False
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash-VL")
    assert cfg["source"].startswith(row["source_url"])
    changed = {k for k, v in row["config"].items() if cfg.get(k, "-") != v}
    assert changed == {"vocab_size"}
    m = fam.model_config(cfg)
    assert (m.hidden_size, m.num_heads, m.head_dim, m.conv_taps,
            m.kda_lower_bound) == (2560, 32, 128, 4, -5.0)
    assert (m.q_lora_rank, m.kv_lora_rank, m.qk_nope_head_dim,
            m.qk_rope_head_dim, m.v_head_dim) == (None, 512, 128, 64, 128)
    assert (m.intermediate_size, m.moe_intermediate_size,
            m.n_routed_experts, m.num_experts_per_tok, m.n_shared_experts,
            m.n_group, m.topk_group, m.routed_scaling_factor) \
        == (6144, 768, 512, 8, 1, 8, 4, 2.5)
    # the family refuses what it does not build
    for key, bad in (("score_function", "softmax"), ("use_mla_nope", True),
                     ("use_nGPT", True), ("value_norm", True),
                     ("up_proj_norm", True), ("scale_router_input", True),
                     ("norm_topk_prob", False)):
        with pytest.raises(ValueError, match=key):
            fam.model_config(dict(cfg, **{key: bad}))
    clamp = list(cfg["expert_swiglu_limit_list"])
    clamp[11] = 4
    with pytest.raises(ValueError, match="no clamp"):
        fam.model_config(dict(cfg, expert_swiglu_limit_list=clamp))
    for metric in bench["per_layer"]:
        if REAL in metric.get("workloads", ()):
            mod = harness.load_reader(metric["name"], layout)
            assert mod.LAYER == metric["layer"]
            assert mod.MOVES == metric["moves"]


def test_the_state_probe_reads_three_references_in_one_run(layout3, capsys):
    """``tools/probe_state.py``: the cell's own comparison, then the same
    positions against the reference with a bfloat16 state and on 8-bit
    weights. The tiny cell's limits call the 8-bit weights NOT correct; a
    bfloat16 state beside a bfloat16 program they cannot tell (PERF.md
    section 6, PR 35)."""
    from benchmark.tools import probe_state
    probe_state.main(["--workload", CELL, "--seed", "3000000019",
                      "--seconds", "2"], layout=layout3,
                     device_check=cpu_devices)
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["probe"] is True and line["correct"] is True
    called = line["lowered_called_correct"]
    assert set(called) == {"state in bfloat16", "weights in 8-bit floats"}
    assert called["weights in 8-bit floats"] is False
