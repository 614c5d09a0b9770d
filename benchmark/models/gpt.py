"""Model builder for configurations whose ``family`` is ``gpt``: everything
that turns a configuration file into the system under test, through the
program's normal entry points (``import paddle_tpu as paddle``). The recipe
is the one ``chip_smoke.py`` proved on the chip (PR 24), copied and not
imported: O2 bfloat16 + AdamW master weights in one donated ``to_static``
step; ``ServingEngine`` on its default ragged path.

A family module offers: ``setup_parallel``, ``build_model``,
``reference_weights``, ``make_train_step``, ``make_engine``,
``train_flops_per_token``, ``reference`` (the plain reference module).
"""
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                               GPTPretrainingCriterion)

from .. import flops
from ..reference import gpt_ref as reference  # noqa: F401  (family API)

# keys of a configuration's file: the sizes and settings read here, and
# (second line of each group) the prose that says where they come from
CONFIG_KEYS = {
    "": {"name", "family", "hidden_size", "num_heads", "head_dim",
         "intermediate_size", "vocab_size", "max_seq_len", "num_layers",
         "train", "parallel", "engine",
         "source", "published", "reduced", "reduced_why", "assumed",
         "deployment", "parameters"},
    "train": {"recompute", "lr",
              "recipe"},
    "parallel": {"mp_degree", "sharding_degree",
                 "layout"},
    "engine": {"page_size", "num_pages", "max_slots", "prefill_chunk",
               "prefix_cache", "max_queue",
               "why"},
}


def setup_parallel(cfg):
    """``cfg['parallel']`` (fleet ``hybrid_configs`` degrees) -> the hybrid
    communicate group, or None for one device."""
    par = cfg.get("parallel")
    if not par:
        return None
    from paddle_tpu.distributed import fleet
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": 1, "pp_degree": 1, "sep_degree": 1,
        "sharding_degree": int(par["sharding_degree"]),
        "mp_degree": int(par["mp_degree"])}
    return fleet.init(is_collective=True, strategy=strategy)


def gpt_config(cfg, hcg=None):
    train = cfg.get("train") or {}
    if cfg["hidden_size"] != cfg["num_heads"] * cfg["head_dim"]:
        raise ValueError(f"{cfg['name']}: hidden_size is not num_heads x "
                         "head_dim")
    return GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_seq_len=cfg["max_seq_len"], dropout=0.0,
        recompute=bool(train.get("recompute", False)),
        tensor_parallel=hcg is not None)


def build_model(cfg, seed, hcg=None):
    """Random weights from the seed through the layers' own default
    initializers (tensor-parallel layers draw what plain ones draw), cast
    to bfloat16, which is the type both training (O2) and serving hold."""
    paddle.seed(int(seed) % (2 ** 31))
    model = paddle.amp.decorate(models=GPTForCausalLM(gpt_config(cfg, hcg)),
                                level="O2", dtype="bfloat16")
    if hcg is not None:
        from paddle_tpu.distributed import fleet
        model = fleet.distributed_model(model)
    return model


def reference_weights(model):
    """The model's arrays under the names ``gpt_ref`` wants. No copy: the
    reference reads the very arrays the program computes with."""
    def a(p):
        return p._data

    def lin(layer):
        return a(layer.weight), a(layer.bias)

    g = model.gpt
    blocks = []
    for b in g.h:
        wqkv, bqkv = lin(b.attn.qkv_proj)
        wo, bo = lin(b.attn.out_proj)
        w1, b1 = lin(b.mlp.fc1)
        w2, b2 = lin(b.mlp.fc2)
        blocks.append({"ln1": lin(b.ln_1), "wqkv": wqkv, "bqkv": bqkv,
                       "wo": wo, "bo": bo, "ln2": lin(b.ln_2),
                       "w1": w1, "b1": b1, "w2": w2, "b2": b2})
    return {"wte": a(g.wte.weight), "wpe": a(g.wpe.weight),
            "ln_f": lin(g.ln_f), "blocks": blocks,
            "num_heads": model.config.num_heads}


def make_train_step(model, cfg, hcg=None):
    """-> (step, place): ``step(ids, labels)`` runs forward, backward and
    AdamW as ONE donated program and returns the loss tensor;
    ``place(np_array)`` puts a host batch where the step wants it."""
    train = cfg["train"]
    crit = GPTPretrainingCriterion(model.config)
    opt = paddle.optimizer.AdamW(learning_rate=float(train["lr"]),
                                 parameters=model.parameters(),
                                 multi_precision=True)
    group = None
    if hcg is not None:
        from paddle_tpu.distributed.fleet.sharding import \
            DygraphShardingOptimizer
        group = hcg.get_sharding_parallel_group()
        opt = DygraphShardingOptimizer(opt, group=group)

    def train_step(x, y):
        with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
            loss = crit(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    # full_graph: a step that cannot be staged is an error, not an eager run
    step = paddle.jit.to_static(train_step, capture=(model, opt),
                                full_graph=True)

    def place(arr):
        t = paddle.to_tensor(np.ascontiguousarray(arr))
        if group is not None:
            from paddle_tpu.distributed import shard_batch
            t = shard_batch(t, group)
        return t

    return step, place


def make_engine(model, cfg):
    """The engine as a deployment builds it: ragged path, attention
    backend left to the start-up gate."""
    from paddle_tpu.serving import ServingEngine
    e = cfg["engine"]
    model.eval()
    return ServingEngine(model, page_size=int(e["page_size"]),
                         num_pages=int(e["num_pages"]),
                         max_slots=int(e["max_slots"]),
                         prefill_chunk=int(e["prefill_chunk"]),
                         prefix_cache=bool(e.get("prefix_cache", True)),
                         max_queue=int(e.get("max_queue", 256)))


def train_flops_per_token(cfg):
    return flops.gpt_train_flops_per_token(
        cfg["hidden_size"], cfg["intermediate_size"], cfg["num_layers"],
        cfg["vocab_size"], cfg["max_seq_len"])
