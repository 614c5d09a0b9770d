"""Every cell's code path, end to end, on the CPU at a tiny size."""
import json

import pytest

from benchmark import harness

from .conftest import STANDS_FOR, cpu_devices

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _declared(layout, cell, section):
    with open(layout.bench_json) as f:
        bench = json.load(f)
    return {m["name"] for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", sorted(STANDS_FOR))
def test_end_to_end_line(layout, cell):
    """Untraced run: exactly the contract's keys, the cell's end-to-end
    metrics and no other, the system held to gpt_ref.py."""
    line = harness.run_cell(cell, seed=3000000019, seconds=1.0, trace=False,
                            layout=layout, device_check=cpu_devices)
    assert set(line) == RESULT_KEYS
    assert set(line["device"]) == DEVICE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == _declared(layout, cell, "end_to_end")
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    # last, each number ``correct`` compared beside its limit
    assert list(line)[-1] == "compared" and line["compared"]
    for c in line["compared"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    json.dumps(line)


@pytest.mark.parametrize("cell", sorted(STANDS_FOR))
def test_traced_line(layout, cell):
    """Traced run: per-layer metrics only; those whose source is the
    device trace find no TPU plane on the CPU and are left out, the rest
    are there."""
    line = harness.run_cell(cell, seed=7, seconds=1.0, trace=True,
                            layout=layout, device_check=cpu_devices)
    assert RESULT_KEYS <= set(line) <= RESULT_KEYS | {"breakdown"}
    with open(layout.bench_json) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    declared = _declared(layout, cell, "per_layer")
    host_side = {n for n in declared
                 if per_layer[n]["source"] != "device_trace"}
    assert host_side <= set(line["metrics"]) <= declared
    assert line["correct"] is True


def test_same_seed_same_inputs(layout):
    from benchmark import traffic
    cell = harness.load_cell("tiny-serve.open", layout)
    a = traffic.open_loop_schedule(cell.workload, 256, 5, 4.0)
    b = traffic.open_loop_schedule(cell.workload, 256, 5, 4.0)
    c = traffic.open_loop_schedule(cell.workload, 256, 2 ** 31 + 11, 4.0)
    assert a == b and a != c
    # another seed: other tokens, the same sizes at the same due times

    def shape(s):
        return [(t, len(r["prompt"]), r["max_new_tokens"]) for t, r in s]
    assert shape(a) == shape(c)


def test_unread_key_is_refused(layout, tmp_path):
    """A parameter that no code reads is refused, not silently ignored."""
    import os
    path = os.path.join(layout.data, "workloads", "tiny-serve.open.json")
    with open(path) as f:
        mix = json.load(f)
    mix["shared_prefix"] = 1024
    with open(path, "w") as f:
        json.dump(mix, f)
    with pytest.raises(SystemExit) as e:
        harness.run_cell("tiny-serve.open", 1, 1.0, False, layout=layout,
                         device_check=cpu_devices)
    assert e.value.code == 2


def test_refuses_without_tpu(layout):
    """The default device check is the one the command uses."""
    with pytest.raises(SystemExit) as e:
        harness.run_cell("tiny-train.steps", 1, 1.0, False, layout=layout)
    assert e.value.code == 2


def test_wrong_precision_fails_the_reference(layout):
    """The tolerance is tight enough to catch a layer computed in a lower
    precision than the configuration states: round one block's MLP weights
    to 4 mantissa bits after the reference has read the model."""
    import jax.numpy as jnp
    import numpy as np
    from benchmark.models import gpt as fam
    from benchmark.reference import gpt_ref
    cell = harness.load_cell("tiny-train.steps", layout)
    model = fam.build_model(cell.config, 3)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 256, (2, 64)).astype("int32")
    labels = rng.integers(0, 256, (2, 64)).astype("int32")
    weights = fam.reference_weights(model)
    good = gpt_ref.loss(weights, ids, labels)
    w = weights["blocks"][0]["w1"]
    scale = 2.0 ** (jnp.floor(jnp.log2(jnp.abs(w.astype(jnp.float32))
                                       + 1e-30)) - 1)
    weights["blocks"][0]["w1"] = (jnp.round(w.astype(jnp.float32) / scale)
                                  * scale).astype(w.dtype)
    bad = gpt_ref.loss(weights, ids, labels)
    # the loss moves by more than the tolerance the real cells are held to
    real = harness.load_cell("gpt3-1p3b-train.pretrain-2k", harness.Layout())
    assert abs(good - bad) > real.workload["correct"]["first_loss_abs"]
