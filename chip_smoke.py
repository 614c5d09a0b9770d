#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

    python chip_smoke.py            # one TPU chip, one process
    python chip_smoke.py --chips 4  # the hybrid-parallel path and its twin

Default run, three phases through ``import paddle_tpu as paddle``:

* **device** — ``paddle.set_device('tpu')`` (raises without a TPU).
* **train** — GPT-3 1.3B widths (hidden 2048, 16 heads x 128, ffn 8192,
  vocab 50304, sequence 2048, dropout 0), depth cut to fit one 16 GB chip;
  bf16 O2 + AdamW master weights in the one donated ``to_static`` step.
  Five steps on one seeded batch: finite falling losses, the Pallas flash
  kernel in the compiled step, first-step loss against the same step under
  the XLA attention reference.
* **serve** — the same widths at the full 24 layers in bf16 in a
  ``ServingEngine`` over a deployment-sized page pool: eight greedy
  requests, prompts of 16..1024 tokens, once per attention backend
  (Pallas ragged kernel, XLA reference); token agreement, and the dense
  forward as judge where the two part.

``--chips 4`` runs nothing of the above but the device phase: it trains
the depth-cut model three steps unsharded on one device, then under
``fleet.init`` with mp_degree=2 x sharding_degree=2 and
``DygraphShardingOptimizer``, and compares.

Any failed check or exception exits non-zero at once and prints no result.
The last stdout line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Every figure on the earlier lines (compile seconds, step milliseconds,
bytes) is information from one run of a smoke script — not a benchmark
metric.
"""
import argparse
import contextlib
import gc
import json
import os
import sys
import time

import jax
import jaxlib
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet, shard_batch
from paddle_tpu.distributed.fleet.sharding import DygraphShardingOptimizer
from paddle_tpu.models import (GPTForCausalLM, GPTPretrainingCriterion,
                               gpt_1p3b)
from paddle_tpu.models.gpt import GPTAttention
from paddle_tpu.ops.pallas._common import KERNELS_ENV, gate_report
from paddle_tpu.serving import ServingEngine

SEED = 0
GIB = 2 ** 30

# ---- sizes. Widths are gpt_1p3b's published ones and are never cut; only
# depth is, and only for training. Depth, batch and pool were read off the
# TPU compiler's memory_analysis() for a described v5e chip (15.75 GiB
# usable) before the first chip run: the train step at depth 16, batch 2
# with per-block recompute is 14.2 GiB (11.9 GiB of donated state) and
# depth 17 is 14.9 GiB; without recompute depth 12 is 13.2 GiB, and the
# XLA-attention twin, which keeps f32 [B, 16, S, S] scores for the
# backward, does not fit at any depth worth running. A serving round at
# 128 tokens is 8.5 GiB on the Pallas kernel and 14.5 GiB on the XLA
# reference, whose page gather takes 6 GiB beside the 6 GiB pool.
SIZES = dict(
    seq=2048,
    train_layers=16,     # of 24, with per-block recompute
    train_batch=2,
    train_steps=5,
    hybrid_steps=3,
    serve_pages=2048,    # x 16 tokens x 192 KiB/token = 6 GiB of KV pool
    page_size=16,
    slots=8,
    prefill_chunk=64,
    prompt_lens=(16, 48, 100, 200, 333, 512, 777, 1024),
    new_tokens=32,
)
LR = 2e-4                # GPT-3 1.3B's published learning rate

# ---- stated tolerances (bf16 compute, f32 loss)
# first-step loss, Pallas flash vs XLA reference attention: the loss is an
# f32 mean over B*S tokens of bf16 logits, so per-element bf16 rounding
# (2^-8 relative) averages out far below this bound
LOSS_TOL = 0.02
# per-step loss, 2x2 hybrid-parallel vs one device, over three optimizer
# steps: partial sums reduce in another order and the difference compounds
# through the updates
HYBRID_LOSS_TOL = 0.05
# the top logits of a randomly initialised model sit near 1.2, where
# adjacent bf16 values are 2^-7 apart: eight such steps. A token that is
# wrong, not rounded differently, sits a logit sigma (0.3) below the top
LOGIT_TOL = 0.0625
# devices' bytes_in_use under the 2x2 mesh: max over min
EVEN_RATIO = 1.25


def say(msg):
    print(msg, flush=True)


def check(ok, what):
    if not ok:
        print(f"[smoke] FAILED: {what}", file=sys.stderr, flush=True)
        sys.exit(1)
    say(f"[smoke] ok: {what}")


def has_kernel(hlo_text):
    return "tpu_custom_call" in hlo_text


def mem(device=None):
    return paddle.device.memory_stats(device)


def say_mem(label):
    m = mem()
    say(f"[smoke] memory at {label}: bytes_in_use="
        f"{m['bytes_in_use'] / GIB:.3f} GiB peak_bytes_in_use="
        f"{m['peak_bytes_in_use'] / GIB:.3f} GiB (information)")


def release():
    """Between phases and between twins: what the finished run held on the
    device goes before the next one builds its own."""
    gc.collect()
    jax.clear_caches()
    gc.collect()


@contextlib.contextmanager
def kernels(mode):
    """The repo's own kernel selection (ops/pallas/_common.py): ``auto``
    serves the Pallas flash kernel, ``xla`` demotes every kernel to its
    XLA reference."""
    saved = os.environ.get(KERNELS_ENV)
    os.environ[KERNELS_ENV] = mode
    try:
        yield
    finally:
        if saved is None:
            os.environ.pop(KERNELS_ENV, None)
        else:
            os.environ[KERNELS_ENV] = saved


# ------------------------------------------------------------------ device

def device_phase(chips):
    paddle.set_device("tpu")
    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu", f"jax.devices()[0].platform == 'tpu' "
                               f"(got {d.platform!r})")
    check(len(devs) >= chips, f"{chips} chip(s) needed, {len(devs)} attached")
    say(f"[smoke] jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"device_kind={d.device_kind!r} count={len(devs)} "
        f"hbm_limit={mem()['bytes_limit'] / GIB:.2f} GiB")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ------------------------------------------------------------------- model

def gpt_config(num_layers, **kw):
    """gpt_1p3b at its published widths; training recomputes each block in
    the backward (serving ignores the flag)."""
    cfg = gpt_1p3b(max_seq_len=SIZES["seq"], dropout=0.0, recompute=True,
                   **kw)
    assert (cfg.hidden_size, cfg.num_heads, cfg.intermediate_size,
            cfg.vocab_size) == (2048, 16, 8192, 50304)
    cfg.num_layers = num_layers     # depth is the only cut
    return cfg


def build_model(cfg):
    """Random weights from the seed (the layers' default initializers; the
    tensor-parallel layers draw what the plain ones draw), cast to bf16."""
    paddle.seed(SEED)
    return paddle.amp.decorate(models=GPTForCausalLM(cfg), level="O2",
                               dtype="bfloat16")


def seeded_batch(batch, vocab):
    rng = np.random.RandomState(SEED)
    ids = rng.randint(0, vocab, (batch, SIZES["seq"])).astype("int32")
    labels = rng.randint(0, vocab, (batch, SIZES["seq"])).astype("int32")
    return ids, labels


def train_run(label, cfg, steps, distribute=None):
    """``steps`` steps of the one donated program on one seeded batch.
    ``distribute(model, opt, ids, labels)`` returns the four as the fleet
    API wraps and places them. -> (losses, optimized HLO text, model,
    optimizer)."""
    model = build_model(cfg)
    crit = GPTPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=LR,
                                 parameters=model.parameters(),
                                 multi_precision=True)
    ids, labels = (paddle.to_tensor(a) for a in seeded_batch(
        SIZES["train_batch"], cfg.vocab_size))
    if distribute is not None:
        model, opt, ids, labels = distribute(model, opt, ids, labels)

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
    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        losses.append(float(step(ids, labels).numpy()))
        times.append(time.perf_counter() - t0)
    say(f"[smoke] {label}: losses {' '.join(f'{l:.4f}' for l in losses)}")
    say(f"[smoke] {label}: first step (compile included) {times[0]:.1f} s"
        + (f", later steps {np.median(times[1:]) * 1e3:.0f} ms median"
           if steps > 1 else "") + " (information)")
    check(all(np.isfinite(l) for l in losses), f"{label}: every loss finite")
    return losses, step.compiled_text(), model, opt


# ------------------------------------------------------------------- train

def train_phase():
    L, B = SIZES["train_layers"], SIZES["train_batch"]
    cfg = gpt_config(L)
    h = cfg.hidden_size
    n_params = (cfg.vocab_size + SIZES["seq"]) * h + 2 * h \
        + L * (3 * h * h + h * h + 2 * h * cfg.intermediate_size
               + 9 * h + cfg.intermediate_size)
    say(f"[smoke] train: hidden {h} heads {cfg.num_heads} ffn "
        f"{cfg.intermediate_size} vocab {cfg.vocab_size} seq {SIZES['seq']}; "
        f"depth {L} of 24, batch {B} — {n_params / 1e9:.3f}e9 parameters "
        f"x 16 B (bf16 weight and gradient, f32 master and two moments) = "
        f"{16 * n_params / 1e9:.1f} GB of state; with each block "
        f"recomputed in the backward, activations and the [{B}, "
        f"{SIZES['seq']}, {cfg.vocab_size}] logits take 2.3 GiB more by the "
        f"compiler's count, and depth {L + 1} would leave under 1 GiB of "
        f"the chip's 15.75")
    with kernels("auto"):
        losses, text, model, opt = train_run(
            "train/pallas", cfg, SIZES["train_steps"])
    check(losses[-1] < losses[0],
          f"train: loss fell, {losses[0]:.4f} -> {losses[-1]:.4f}")
    check(has_kernel(text),
          "train: the compiled step contains the Pallas flash kernel "
          "(tpu_custom_call)")
    say_mem("end of train/pallas")
    del model, opt, text
    release()
    say_mem("train/pallas released")

    with kernels("xla"):
        ref, ref_text, model, opt = train_run("train/xla", cfg, 1)
    check(not has_kernel(ref_text),
          "train/xla: the reference step has no Pallas kernel")
    check(abs(losses[0] - ref[0]) <= LOSS_TOL,
          f"train: first-step loss, Pallas {losses[0]:.5f} vs XLA attention "
          f"{ref[0]:.5f}: |diff| {abs(losses[0] - ref[0]):.5f} <= "
          f"{LOSS_TOL} (stated bf16 tolerance)")
    del model, opt, ref_text
    release()
    say_mem("train phase released")


# ------------------------------------------------------------------- serve

def serve_run(model, prompts, attn_backend):
    """One engine, warmed, eight requests submitted shortest first so that
    the short ones decode in the rounds in which the long ones still
    prefill. -> (backend that served, tokens per request)."""
    t0 = time.perf_counter()
    eng = ServingEngine(model, page_size=SIZES["page_size"],
                        num_pages=SIZES["serve_pages"],
                        max_slots=SIZES["slots"],
                        prefill_chunk=SIZES["prefill_chunk"],
                        attn_backend=attn_backend)
    backend = eng.attn_backend
    if eng.attn_ab is not None:
        say(f"[smoke] serve: startup A/B gate at the round shape: "
            f"{eng.attn_ab} (information)")
        check(not eng.attn_ab.get("failed"),
              "serve: the Pallas ragged kernel compiled and ran in the gate")
    label = f"serve/{backend}"
    say(f"[smoke] {label}: pool {eng.kv.nbytes() / GIB:.2f} GiB "
        f"({SIZES['serve_pages']} pages x {SIZES['page_size']} tokens), "
        f"{SIZES['slots']} slots, prefill chunk {SIZES['prefill_chunk']}")
    pads = eng.warm_ragged()
    say(f"[smoke] {label}: warm_ragged compiled token pads {pads} in "
        f"{time.perf_counter() - t0:.1f} s from engine start (information)")
    text = eng.compiled_text()
    if backend == "pallas":
        check(has_kernel(text), f"{label}: the round program contains the "
                                "Pallas ragged kernel (tpu_custom_call)")
    else:
        check(not has_kernel(text),
              f"{label}: the reference round program has no Pallas kernel")
    t0 = time.perf_counter()
    reqs = [eng.submit(p, max_new_tokens=SIZES["new_tokens"])
            for p in prompts]
    rounds = eng.run_until_idle()
    tokens = [r.result(timeout=0) for r in reqs]
    st = eng.stats()
    say(f"[smoke] {label}: {rounds} rounds in "
        f"{time.perf_counter() - t0:.1f} s, {st['decode_tokens']} decode + "
        f"{st['prefill_chunk_tokens']} prefill tokens, peak pool occupancy "
        f"{st['kv_occupancy_peak_pct']}%, evictions {st['evictions']}, "
        f"programs {st['distinct_programs']} (information)")
    check(all(len(t) == SIZES["new_tokens"] for t in tokens),
          f"{label}: all {len(reqs)} requests finished with "
          f"{SIZES['new_tokens']} tokens")
    # mixed rounds: fewer rounds than prefill rounds + decode rounds run
    # apart would take
    chunks = sum(-(-len(p) // SIZES["prefill_chunk"]) for p in prompts)
    check(rounds < chunks + len(prompts) * SIZES["new_tokens"]
          and st["decode_tokens"] > 0,
          f"{label}: prefill and decode shared rounds")
    say_mem(f"end of {label}")
    eng.close()
    return backend, tokens


def judge(model, prompts, runs):
    """The two runs' tokens are identical; where they first part, the
    dense forward of the same model (its own flash/XLA attention, no
    pages) must put both candidates within LOGIT_TOL of its top logit.
    Requests that never part are judged at their last token, so the paged
    path is also held to the dense one."""
    (name_a, toks_a), (name_b, toks_b) = runs
    same = 0
    for i, (p, a, b) in enumerate(zip(prompts, toks_a, toks_b)):
        at = next((j for j in range(len(a)) if a[j] != b[j]), None)
        same += at is None
        j = len(a) - 1 if at is None else at
        ctx = list(p) + a[:j]
        ids = np.zeros((1, SIZES["seq"]), "int64")
        ids[0, :len(ctx)] = ctx   # right padding is causal-safe
        with paddle.no_grad():
            row = model(paddle.to_tensor(ids))[0, len(ctx) - 1] \
                .astype("float32").numpy()
        gaps = [float(row.max() - row[t]) for t in (a[j], b[j])]
        where = "never part; judged at the last token" if at is None else \
            f"first part at token {j} ({name_a} {a[j]} vs {name_b} {b[j]})"
        check(max(gaps) <= LOGIT_TOL,
              f"serve: request {i} (prompt {len(p)}): {where}; dense "
              f"logit gaps to the top {gaps[0]:.4f} / {gaps[1]:.4f} <= "
              f"{LOGIT_TOL} (stated bf16 tolerance)")
    say(f"[smoke] serve: {same} of {len(prompts)} requests token-identical "
        f"over all {SIZES['new_tokens']} tokens between {name_a} and "
        f"{name_b}")


def serve_phase():
    cfg = gpt_config(24)
    model = build_model(cfg)
    model.eval()
    say_mem("serve model built (24 layers, bf16)")
    rng = np.random.RandomState(SEED + 1)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
               for n in SIZES["prompt_lens"]]
    # first engine as a deployment builds it: attn_backend unset, so the
    # startup A/B gate times both kernels and picks; the second engine is
    # forced onto whichever backend the gate did not pick
    first = serve_run(model, prompts, None)
    release()
    say_mem(f"serve/{first[0]} released")
    other = "xla" if first[0] == "pallas" else "pallas"
    second = serve_run(model, prompts, other)
    check({first[0], second[0]} == {"pallas", "xla"},
          "serve: one run on the Pallas ragged kernel, one on the XLA "
          "reference")
    release()
    say_mem(f"serve/{second[0]} released")
    judge(model, prompts, (first, second))
    del model
    release()
    say_mem("serve phase released")


# ------------------------------------------------------------- four chips

def hybrid_phase():
    L, steps = SIZES["train_layers"], SIZES["hybrid_steps"]
    say(f"[smoke] hybrid: depth {L} of 24, batch {SIZES['train_batch']}, "
        f"{steps} steps, one device then mp_degree=2 x sharding_degree=2")
    with kernels("auto"):
        ref, _, model, opt = train_run("hybrid/one-device", gpt_config(L),
                                       steps)
    del model, opt
    release()

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "pp_degree": 1,
                               "sharding_degree": 2, "sep_degree": 1,
                               "mp_degree": 2}
    hcg = fleet.init(is_collective=True, strategy=strategy)
    mesh = hcg.mesh
    say(f"[smoke] hybrid: mesh {dict(mesh.shape)}")
    sharding_group = hcg.get_sharding_parallel_group()
    check(GPTAttention._sharded_impl_override is None,
          "hybrid: no test override stands in for the sharded attention")

    def distribute(model, opt, ids, labels):
        return (fleet.distributed_model(model),
                DygraphShardingOptimizer(opt, group=sharding_group),
                shard_batch(ids, sharding_group),
                shard_batch(labels, sharding_group))

    with kernels("auto"):
        losses, text, model, opt = train_run(
            "hybrid/mp2xsharding2", gpt_config(L, tensor_parallel=True),
            steps, distribute)
    diffs = [abs(a - b) for a, b in zip(losses, ref)]
    check(max(diffs) <= HYBRID_LOSS_TOL,
          f"hybrid: per-step |loss diff| to one device "
          f"{' '.join(f'{d:.5f}' for d in diffs)} <= {HYBRID_LOSS_TOL} "
          "(stated tolerance)")
    attn = model.gpt.h[0].attn
    check(has_kernel(text) and attn._sharded_fa is not None,
          "hybrid: attention ran the shard-mapped Pallas flash kernel "
          "(_sharded_flash built it; tpu_custom_call in the step)")
    say("[smoke] hybrid: collectives in the step: " + ", ".join(
        f"{op} x{text.count(op + '(') + text.count(op + '-start(')}"
        for op in ("all-reduce", "all-gather", "reduce-scatter",
                   "all-to-all", "collective-permute")) + " (information)")

    # a tensor-parallel weight: two column halves, each on the two devices
    # (one per sharding rank) of its 'model' coordinate
    w = attn.qkv_proj.weight._data
    cols = w.shape[1] // 2
    for m in range(2):
        want = {d.id for d in mesh.devices[0, 0, :, 0, m]}
        got = {s.device.id for s in w.addressable_shards
               if s.index[1] == slice(m * cols, (m + 1) * cols)}
        check(got == want and all(
            s.data.shape == (w.shape[0], cols) for s in w.addressable_shards),
            f"hybrid: qkv weight columns [{m * cols}:{(m + 1) * cols}] sit "
            f"on devices {sorted(want)}")
    # optimizer state of that weight: split over 'sharding' on top of the
    # tensor-parallel split, a quarter on each device
    mom = opt.state_dict()[f"{attn.qkv_proj.weight.name}_moment1"]._data
    spec = [a for ax in mom.sharding.spec for a in (
        ax if isinstance(ax, tuple) else (ax,)) if a]
    check({"sharding", "model"} <= set(spec) and all(
        s.data.size * 4 == mom.size for s in mom.addressable_shards),
        f"hybrid: AdamW moment of the qkv weight is split {mom.sharding.spec}"
        ", a quarter per device")
    say_mem("end of hybrid (device 0)")
    used = [mem(d)["bytes_in_use"] for d in jax.devices()[:4]]
    check(max(used) <= EVEN_RATIO * min(used),
          "hybrid: bytes_in_use about even over the four devices: "
          + " ".join(f"{u / GIB:.3f}" for u in used)
          + f" GiB (max/min <= {EVEN_RATIO})")


# -------------------------------------------------------------------- main

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the hybrid-parallel train step and "
                         "its one-device twin, on four chips")
    args = ap.parse_args()
    cache = paddle.jit.use_compile_cache(
        os.path.dirname(os.path.abspath(__file__)))
    say(f"[smoke] compile cache: {cache}")
    device = device_phase(args.chips)
    if args.chips == 4:
        hybrid_phase()
    else:
        train_phase()
        serve_phase()
    failed = [k for k, row in gate_report().items() if row.get("failed")]
    check(not failed, f"no Pallas kernel failed in an A/B gate {failed}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
