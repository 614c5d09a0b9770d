"""The recompute policy that keeps what the backward can hold
(``fleet/recompute.py`` ``keep_name`` / ``choose_keep`` / ``recompute(...,
keep=)`` and ``models/gpt.py`` ``_keep_plan``): the chooser as a pure
function, the kept program against full recompute bit for bit, and the CPU
(no memory reported) keeping nothing."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.models import (GPTForCausalLM, GPTPretrainingCriterion,
                               gpt as gpt_mod, gpt_13b, gpt_1p3b, gpt_tiny)
from paddle_tpu.observability import tracing

# fleet re-exports the function under the module's own name
rc = importlib.import_module("paddle_tpu.distributed.fleet.recompute")
GIB = 2 ** 30
MIB = 2 ** 20
ORDER = ("attn_res", "fc1", "attn_out")


def _cell(name):
    """-> (config, tokens on a device, model degree, bytes a v5e holds
    before the step: the compiler's count of the cell's arguments)."""
    if name == "pretrain-2k":        # gpt3-1p3b-train: 2 x 2048 on one chip
        cfg = gpt_1p3b()
        cfg.num_layers = 16
        return cfg, 2 * 2048, 1, int(11.90 * GIB)
    cfg = gpt_13b()                  # gpt3-13b-train: mp 2 x sharding 2
    cfg.num_layers = 8
    return cfg, 2 * 2048, 2, int(10.42 * GIB)


# ------------------------------------------------------------ the chooser
@pytest.mark.parametrize("budget", [0, -1, 15 * MIB])
def test_chooser_keeps_nothing_without_room(budget):
    sizes = {"attn_res": 16 * MIB, "fc1": 64 * MIB}
    assert rc.choose_keep(budget, sizes, 4) == [()] * 4


def test_chooser_fills_in_order_of_worth_and_stops_at_the_budget():
    sizes = {"attn_res": 16, "fc1": 64, "attn_out": 17}
    # 4 x 16 + 2 x 64 = 192; the third fc1 does not fit, and nothing after
    # it is tried although an attn_out would
    keep = rc.choose_keep(192 + 20, sizes, 4)
    assert keep == [("attn_res", "fc1"), ("attn_res", "fc1"),
                    ("attn_res",), ("attn_res",)]
    # every name everywhere once the budget holds them all
    assert rc.choose_keep(4 * 97, sizes, 4) == [ORDER] * 4
    assert rc.choose_keep(4 * 97 - 1, sizes, 4)[-1] == ORDER[:2]


@pytest.mark.parametrize("cell,mib", [
    ("pretrain-2k", {"attn_res": 16, "fc1": 64, "attn_out": 16.25}),
    ("hybrid-mp2-sh2", {"attn_res": 40, "fc1": 80, "attn_out": 20.3125}),
])
def test_block_bytes_are_a_devices_share(cell, mib):
    cfg, tokens, model_deg, _ = _cell(cell)
    sizes = gpt_mod.block_keep_bytes(cfg, tokens, 2, model_deg)
    assert list(sizes) == list(ORDER)
    assert {n: b / MIB for n, b in sizes.items()} == mib


@pytest.mark.parametrize("cell", ["pretrain-2k", "hybrid-mp2-sh2"])
def test_benchmark_cells_keep_what_a_v5e_has_free(cell):
    """The two training cells at a v5e's 15.75 GiB: the sets the chooser
    takes stay inside the budget, and the budget inside what the compile
    for a described chip showed the step can spare (PERF.md section 6)."""
    cfg, tokens, model_deg, held = _cell(cell)
    sizes = gpt_mod.block_keep_bytes(cfg, tokens, 2, model_deg)
    free = int(15.75 * GIB) - held
    budget = free - gpt_mod.step_reserve_bytes(cfg, tokens, 2, model_deg)
    keep = rc.choose_keep(budget, sizes, cfg.num_layers)
    spent = sum(sizes[n] for k in keep for n in k)
    assert 0 < spent <= budget
    assert all(k == ORDER[:len(k)] for k in keep)
    assert all(len(a) >= len(b) for a, b in zip(keep, keep[1:]))
    if cell == "hybrid-mp2-sh2":
        # everything fits: 8 x 140.3 MiB against 2.86 GiB
        assert keep == [ORDER] * 8
    else:
        # 0.66 GiB: the residual in every block, fc1 in the first six
        assert keep == [ORDER[:2]] * 6 + [ORDER[:1]] * 10
    # a device filled to the brim recomputes everything, as before
    assert rc.choose_keep(budget - free, sizes, cfg.num_layers) == \
        [()] * cfg.num_layers


# ------------------------------------------- recompute with a keep set
def _tiny(recompute=True):
    paddle.seed(7)
    cfg = gpt_tiny(recompute=recompute)
    model = GPTForCausalLM(cfg)
    model.train()
    return model, GPTPretrainingCriterion(cfg)


def _ids():
    return paddle.to_tensor(
        np.random.RandomState(0).randint(0, 256, (2, 64)).astype("int32"))


def _grads(monkeypatch, recompute, free):
    monkeypatch.setattr(gpt_mod, "device_free_bytes", lambda: free)
    model, crit = _tiny(recompute)
    loss = crit(model(_ids()), _ids())
    loss.backward()
    plans = list(model.gpt._keep_plans.values())
    return (float(loss.numpy()),
            [np.asarray(p.grad.numpy()) for p in model.parameters()],
            plans[0][0] if plans else None)


def test_kept_gradients_equal_full_recompute_bit_for_bit(monkeypatch):
    loss_0, plain, _ = _grads(monkeypatch, False, None)
    loss_f, full, keep_f = _grads(monkeypatch, True, None)
    loss_k, kept, keep_k = _grads(monkeypatch, True, GIB)
    # the residual everywhere, fc1 in the first block only
    part = gpt_mod.step_reserve_bytes(gpt_tiny(), 2 * 64, 4) \
        + 2 * 32768 + 131072 + 1000
    loss_p, some, keep_p = _grads(monkeypatch, True, part)
    assert keep_f == [(), ()] and keep_k == [ORDER, ORDER]
    assert keep_p == [("attn_res", "fc1"), ("attn_res",)]
    assert loss_f == loss_k == loss_p
    # without recompute each op is a program of its own (and a cached,
    # jitted one from its second use on): the same mathematics, fused
    # differently
    np.testing.assert_allclose(loss_0, loss_f, rtol=1e-6)
    for a, b, c, d in zip(plain, full, kept, some):
        assert np.array_equal(b, c) and np.array_equal(b, d)
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def _lowered_backward(keep):
    """StableHLO, with source locations, of one GPT block's gradients
    through ``recompute(block, x, keep=keep)`` and the program's own tape,
    as the whole step stages them."""
    paddle.seed(1)
    block = gpt_mod.GPTBlock(gpt_tiny())
    params = list(block.parameters())

    def grads(arrs, x):
        saved = [p._data for p in params]
        for p, a in zip(params, arrs):
            p._data = a
        try:
            out = fleet.recompute(block, paddle.to_tensor(x), keep=keep)
            out.sin().sum().backward()
            return [p.grad._data for p in params]
        finally:
            for p, a in zip(params, saved):
                p._data, p._grad = a, None

    x = jnp.ones((2, 64, 64), jnp.float32)
    return jax.jit(grads).lower([p._data for p in params], x).as_text(
        debug_info=True)


def _remat_dots(text):
    """``dot_general`` ops of the text whose location (an alias, defined
    at the end of the text) lies in a ``rematted_computation``."""
    import re
    lines = text.splitlines()
    remat = {m.group(1) for m in (re.match(r"(#loc\d+) = ", l)
                                  for l in lines)
             if m and "rematted_computation" in m.string}
    return sum(m.group(1) in remat for m in (
        re.search(r"stablehlo\.dot_general .* loc\((#loc\d+)\)$", l)
        for l in lines) if m)


def test_kept_program_runs_fewer_products_a_second_time():
    """A block's second forward holds the qkv, output and fc1 products
    and the two of the attention (fc2's output is not needed); keeping
    ``attn_res`` takes the output projection out, ``fc1`` its own."""
    full = _remat_dots(_lowered_backward(()))
    kept = _remat_dots(_lowered_backward(("attn_res", "fc1")))
    res = _remat_dots(_lowered_backward(("attn_res",)))
    assert (full, res, kept) == (5, 4, 3)


def test_default_call_tags_and_saves_nothing():
    x = paddle.to_tensor(np.ones((2, 4), "float32"))
    assert rc.keep_name(x, "fc1") is x          # outside a recompute call
    lin = paddle.nn.Linear(4, 4)

    def block(t):
        return rc.keep_name(lin(t), "fc1") * 2.0

    def residuals(**kw):
        jaxpr = jax.make_jaxpr(lambda a: jax.vjp(
            lambda b: fleet.recompute(
                block, paddle.to_tensor(b), **kw)._data, a)[0])(x._data)
        return str(jaxpr)
    assert "name=fc1" not in residuals()
    assert "name=fc1" not in residuals(keep=("attn_res",))
    assert "name=fc1" in residuals(keep=("fc1",))
    assert rc._keeping == ()


# ------------------------------------------------------- the model's plan
def test_cpu_reports_no_memory_and_keeps_nothing():
    assert rc.device_free_bytes() is None
    events = tracing.start()
    try:
        model, crit = _tiny()
        crit(model(_ids()), _ids()).backward()
    finally:
        tracing.stop()
    found = [e for e in events.events if e["name"] == "recompute.keep"]
    assert len(found) == 1 and found[0]["cat"] == "step"
    args = found[0]["args"]
    assert args["names"] == [] and args["bytes"] == 0
    assert args["budget"] == 0 and args["layers"] == 2
    assert list(args["name_bytes"]) == list(ORDER)


def test_plan_is_decided_once_a_shape_and_recorded_each_trace(monkeypatch):
    asked = []

    def probe():
        asked.append(1)
        return GIB

    monkeypatch.setattr(gpt_mod, "device_free_bytes", probe)
    model, crit = _tiny()
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())

    def train_step(ids):
        loss = crit(model(ids), ids)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(train_step, capture=(model, opt),
                                full_graph=True)
    events = tracing.start()
    try:
        kept = [float(step(_ids()).numpy()) for _ in range(3)]
        step.compiled_text()           # lowers the same step again
    finally:
        tracing.stop()
    assert asked == [1]
    found = [e["args"] for e in events.events
             if e["name"] == "recompute.keep"]
    assert found and all(f == found[0] for f in found)
    assert found[0]["names"] == list(ORDER)
    assert found[0]["blocks"] == dict.fromkeys(ORDER, 2)
    assert found[0]["bytes"] == 2 * sum(found[0]["name_bytes"].values())

    # the same three steps under full recompute: every loss bit-equal
    monkeypatch.setattr(gpt_mod, "device_free_bytes", lambda: None)
    model, crit = _tiny()
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    step = paddle.jit.to_static(train_step, capture=(model, opt),
                                full_graph=True)
    assert [float(step(_ids()).numpy()) for _ in range(3)] == kept
    assert kept[2] < kept[0]
