"""HLO-level verification of ZeRO/TP sharding (VERDICT r2 #4).

Parity tests prove math; these compile the staged train step and assert on
the optimized per-device HLO so a silently-degraded sharding (replicated
state + all-reduce everywhere) cannot pass. Reference behavior being
matched: group_sharded_stage2/3 reduce-scatter + gather-on-use semantics.

Note: the all-reduce+dynamic-slice -> reduce-scatter fusion pass runs on
TPU but not in the CPU SPMD pipeline, so tests accept either form while
asserting the essential property — per-device-sharded update math. On the
TPU the fused form is not spelled ``reduce-scatter(`` either: the slice is
fused with the all-reduce into a ``kind=kCustom`` fusion that calls a
computation named ``%all-reduce-scatter.N`` (the benchmark's hybrid step
compiled for a described v5e 2x2, ISSUE 32), so a count of
``reduce-scatter(`` in a TPU program's text reads 0 where the scatter is
there.
"""
import re

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.fleet.sharding import (
    DygraphShardingOptimizer, group_sharded_parallel,
)
from paddle_tpu.jit import to_static


@pytest.fixture(autouse=True, scope="module")
def _reset_hcg_after_module():
    yield
    from paddle_tpu.distributed.topology import _set_hcg
    _set_hcg(None)  # don't leak this module's meshes into other test files


def _fleet(dp=1, mp=1, sharding=1):
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": dp, "pp_degree": 1,
                               "sharding_degree": sharding, "sep_degree": 1,
                               "mp_degree": mp}
    return fleet.init(is_collective=True, strategy=strategy)


def _staged_step(model, opt, x, y):
    def train_step(xb, yb):
        loss = F.mse_loss(model(xb), yb)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = to_static(train_step, capture=(model, opt))
    step(x, y)
    step(x, y)
    return step


def test_zero2_update_math_is_sharded():
    """Stage-1/2: optimizer state update runs on 1/N-shaped shards and the
    param re-gathers — not replicated state + all-reduce."""
    hcg = _fleet(dp=8)
    paddle.seed(0)
    m = nn.Linear(64, 64)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=m.parameters())
    opt = DygraphShardingOptimizer(opt, group=hcg.get_data_parallel_group())
    rng = np.random.RandomState(0)
    x = dist.shard_batch(
        paddle.to_tensor(rng.randn(16, 64).astype("float32")),
        hcg.get_data_parallel_group())
    y = dist.shard_batch(
        paddle.to_tensor(rng.randn(16, 64).astype("float32")),
        hcg.get_data_parallel_group())
    step = _staged_step(m, opt, x, y)
    txt = step.compiled_text()
    # per-device shard of the [64,64] Adam moments is [8,64]
    assert "f32[8,64]" in txt, "optimizer state update is not sharded"
    # grads must reach the shard: reduce-scatter (TPU) or
    # all-reduce + the sharded update shapes (CPU pipeline)
    assert ("reduce-scatter" in txt) or ("all-reduce" in txt)
    # updated param is re-gathered for the next forward
    assert "all-gather" in txt, "no param re-gather found"


def test_zero3_param_shards_gather_on_use():
    """Stage-3: parameters live sharded; the forward gathers on use."""
    hcg = _fleet(dp=8)
    paddle.seed(0)
    m = nn.Linear(64, 64)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=m.parameters())
    m, opt = group_sharded_parallel(m, opt, level="p_g_os",
                                    group=hcg.get_data_parallel_group())
    w = m.weight._data
    assert "data" in str(w.sharding.spec), w.sharding  # lives sharded
    rng = np.random.RandomState(0)
    x = dist.shard_batch(
        paddle.to_tensor(rng.randn(16, 64).astype("float32")),
        hcg.get_data_parallel_group())
    y = dist.shard_batch(
        paddle.to_tensor(rng.randn(16, 64).astype("float32")),
        hcg.get_data_parallel_group())
    step = _staged_step(m, opt, x, y)
    txt = step.compiled_text()
    assert "all-gather" in txt, "stage-3 forward must gather params on use"
    # program inputs carry the shard, not the full param: [8,64] not [64,64]
    entry = [ln for ln in txt.splitlines() if "ENTRY" in ln]
    assert entry and "f32[8,64]" in entry[0], entry
    # and the update math stays sharded
    assert "f32[8,64]" in txt


def test_tp_matmul_does_not_allgather_weight():
    """TP column-parallel: the sharded weight is consumed in place — no
    all-gather materialising the full [64,512] weight anywhere."""
    hcg = _fleet(mp=8)
    paddle.seed(0)
    m = fleet.ColumnParallelLinear(64, 512, gather_output=False)
    opt = paddle.optimizer.SGD(learning_rate=1e-3,
                               parameters=m.parameters())
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(8, 64).astype("float32"))
    y = paddle.to_tensor(rng.randn(8, 512).astype("float32"))

    def train_step(xb, yb):
        out = m(xb)
        loss = F.mse_loss(out, yb)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = to_static(train_step, capture=(m, opt))
    step(x, y)
    step(x, y)
    txt = step.compiled_text()
    for line in txt.splitlines():
        if "all-gather" in line and re.search(r"f32\[64,512\]", line):
            raise AssertionError(f"full weight all-gathered: {line.strip()}")


def test_hybrid_clip_grad_matches_single_device_norm():
    """HybridParallelClipGrad under mp=2 x dp=4 clips to the same result as
    plain ClipGradByGlobalNorm on one device (reference:
    hybrid_parallel_optimizer.py:44)."""
    rng = np.random.RandomState(3)
    xw = rng.randn(16, 32).astype("float32")
    yw = rng.randn(16, 8).astype("float32")

    def run(parallel):
        if parallel:
            _fleet(dp=4, mp=2)
        else:
            _fleet(dp=8)
        paddle.seed(11)
        m = nn.Linear(32, 8)
        clip = paddle.nn.ClipGradByGlobalNorm(clip_norm=0.01)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=m.parameters(),
                                   grad_clip=clip)
        if parallel:
            opt = fleet.HybridParallelOptimizer(opt)
            assert isinstance(opt._inner_opt._grad_clip,
                              fleet.HybridParallelClipGrad)
        loss = F.mse_loss(m(paddle.to_tensor(xw)), paddle.to_tensor(yw))
        loss.backward()
        opt.step()
        return m.weight.numpy()

    np.testing.assert_allclose(run(True), run(False), rtol=1e-6)


def test_topology_rank_accessors_single_controller():
    hcg = _fleet(dp=2, mp=2)
    assert hcg.get_data_parallel_rank() == 0
    assert hcg.get_model_parallel_rank() == 0
    assert hcg.get_stage_id() == 0
    assert hcg.get_sharding_parallel_rank() == 0


def test_zero_preserves_tp_sharding():
    """Review r3 finding: ZeRO hooks must MERGE the sharding axis with a TP
    param's existing 'model'-axis dims, not replace them (replacement would
    all-gather every TP weight each step)."""
    hcg = _fleet(dp=2, mp=2, sharding=2)
    paddle.seed(0)
    m = fleet.ColumnParallelLinear(64, 256, gather_output=False)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=m.parameters())
    opt = DygraphShardingOptimizer(
        opt, group=hcg.get_sharding_parallel_group())
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(8, 64).astype("float32"))
    y = paddle.to_tensor(rng.randn(8, 256).astype("float32"))
    step = _staged_step(m, opt, x, y)
    # after two real steps, the weight must still carry its 'model' dim
    spec = str(m.weight._data.sharding.spec)
    assert "model" in spec, spec
    # and the moments carry BOTH axes (model from TP, sharding from ZeRO)
    mom = opt._inner._accumulators["moment1"][id(m.weight)]
    mspec = str(mom.sharding.spec)
    assert "model" in mspec and "sharding" in mspec, mspec


def test_bf16_masters_stay_sharded_and_the_step_compiles_once(caplog):
    """The mp x sharding path ``chip_smoke.py --chips 4`` runs, tiny: with
    bf16 params and f32 masters (``multi_precision``) the ZeRO out-hook
    used to regather the MASTER over the sharding axis along with the
    param, so after one step the largest optimizer state was no longer
    split — and, like the norm weights and beta_pow scalars that started
    on one device, it came back in another layout than it went in, which
    cost a second compile of the whole step."""
    from jax.sharding import NamedSharding
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
    hcg = _fleet(dp=2, mp=2, sharding=2)
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=1,
                    num_heads=4, max_seq_len=32, dropout=0.0,
                    tensor_parallel=True)
    model = paddle.amp.decorate(models=GPTForCausalLM(cfg), level="O2",
                                dtype="bfloat16")
    crit = GPTPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters(),
                                 multi_precision=True)
    model = fleet.distributed_model(model)
    assert all(isinstance(p._data.sharding, NamedSharding)
               for p in model.parameters())     # norms, wpe: on the mesh
    opt = DygraphShardingOptimizer(
        opt, group=hcg.get_sharding_parallel_group())
    ids = dist.shard_batch(paddle.to_tensor(
        np.random.RandomState(0).randint(0, 256, (4, 32)).astype("int32")),
        hcg.get_sharding_parallel_group())

    def train_step(x):
        with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
            loss = crit(model(x), x)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = to_static(train_step, capture=(model, opt), full_graph=True)

    def layouts():
        params, _, slots, _, _ = step._state_cached()
        return [p._data.sharding for p in params] + \
            [cont[k].sharding for cont, k in slots]

    import jax
    import logging
    with jax.log_compiles(), caplog.at_level(logging.WARNING, logger="jax"):
        step(ids)
        after_one = layouts()
        step(ids)
        step(ids)
    compiles = [r for r in caplog.records
                if r.getMessage().startswith("Compiling jit(pure)")]
    assert len(compiles) == 1, [r.getMessage()[:80] for r in compiles]
    w = model.gpt.h[0].attn.qkv_proj.weight
    master = opt._inner._master_weights[id(w)]
    assert master.dtype == np.float32
    mspec = str(master.sharding.spec)
    assert "model" in mspec and "sharding" in mspec, mspec
    assert all(s.data.size * 4 == master.size
               for s in master.addressable_shards)
    # the param the forward reads is regathered over 'sharding' only
    assert "sharding" not in str(w._data.sharding.spec)
    assert after_one == layouts()


def test_tp_embedding_draws_the_plain_embedding_s_weights():
    """Same seed, same weights — a tensor-parallel model can be held to
    its single-device twin (reference mp_layers.py:47 passes no
    initializer of its own either)."""
    _fleet(dp=4, mp=2)
    paddle.seed(7)
    plain = nn.Embedding(64, 16)
    paddle.seed(7)
    tp = fleet.VocabParallelEmbedding(64, 16)
    np.testing.assert_array_equal(plain.weight.numpy(), tp.weight.numpy())
    assert "model" in str(tp.weight._data.sharding.spec)


def test_grad_accumulation_adds_no_extra_sync():
    """VERDICT r3 weak #5 (no_sync): the TPU-native grad-accumulation
    pattern — micro-batches scanned INSIDE one backward (scan_loop) — must
    emit the same number of gradient all-reduces as a single-microbatch
    step (one per parameter at the update), which is the reference's
    no_sync + boundary-sync contract (parallel.py:202). Proven on
    optimized HLO. Naive per-microbatch backwards each carry their own
    reduce (linear => same math, more comms) — that gap is exactly why
    the scan pattern is the supported one."""
    import re

    from paddle_tpu.jit import scan_loop

    def build(n_micro):
        paddle.seed(0)
        model = nn.Linear(16, 8)
        model = dist.DataParallel(model)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())

        def step(xs, ys):
            # xs/ys: [n_micro, B, ...] — accumulate the loss over
            # microbatches inside ONE backward via lax.scan
            if n_micro == 1:
                loss = F.mse_loss(model(xs[0]), ys[0])
            else:
                def body(i, acc):
                    xb = xs.index_select(i, axis=0).squeeze(0)
                    yb = ys.index_select(i, axis=0).squeeze(0)
                    return acc + F.mse_loss(model(xb), yb)

                total = scan_loop(
                    body, paddle.zeros([], "float32"), n_steps=n_micro)
                loss = total / float(n_micro)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        sf = to_static(step, capture=(model, opt))
        rng = np.random.RandomState(0)
        xs = paddle.to_tensor(rng.randn(n_micro, 8, 16).astype("float32"))
        ys = paddle.to_tensor(rng.randn(n_micro, 8, 8).astype("float32"))
        sf(xs, ys)
        return sf.compiled_text()

    def n_grad_syncs(hlo):
        """all-reduce INSTRUCTIONS carrying a non-scalar payload (param
        grads); the scalar loss-total reduce is not a gradient sync."""
        n = 0
        for line in hlo.splitlines():
            if not re.search(r"= .* all-reduce(?:-start)?\(", line):
                continue
            # split at the OP, not the instruction name (%all-reduce.N)
            result_type = re.split(r" all-reduce(?:-start)?\(", line)[0]
            result_type = result_type.split("=", 1)[-1]
            if re.search(r"f32\[\d", result_type):
                n += 1
        return n

    one = n_grad_syncs(build(1))
    four = n_grad_syncs(build(4))
    assert one >= 1  # the sanity floor: grads DO sync
    assert four == one, (
        f"scan accumulation multiplied gradient syncs: {one} -> {four}")
