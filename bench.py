"""Benchmark harness — prints ONE JSON line for the driver.

Headline metric: MFU of a compiled GPT train step (fwd+bwd+AdamW in one XLA
program, bf16 autocast) on the single real TPU chip. vs_baseline is measured
MFU / the 45% north-star target from BASELINE.json (no published reference
numbers exist in-tree — BASELINE.md).

Also measured: jitted LeNet/MNIST-shape steps/sec (BASELINE config 1 proxy),
raw bf16 matmul MFU (MXU sanity ceiling), and eager per-op dispatch overhead
(the dygraph hot path, SURVEY §3.1).
"""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.jit import to_static
from paddle_tpu.models import GPTConfig, GPTForCausalLM, GPTPretrainingCriterion, LeNet

_HERE = os.path.dirname(os.path.abspath(__file__))


def _require_tpu():
    """Every timing leg measures the chip: without one the run fails — it
    never times the CPU under a device metric's name."""
    d = jax.devices()[0]
    if d.platform != "tpu":
        sys.exit(f"bench.py measures on a TPU; jax sees {d.platform!r} "
                 f"({d.device_kind}). Nothing was measured.")


def _peak_flops():
    # the ONE copy of the peak-FLOPs table lives in observability (the
    # in-run MFU gauge uses the same numbers as the bench headline)
    from paddle_tpu.observability.metrics import peak_flops
    return peak_flops(jax.devices()[0].device_kind)


def _sync(r):
    jax.block_until_ready(r._data if hasattr(r, "_data") else r)


def _timeit(fn, iters, warmup=2):
    for _ in range(warmup):
        r = fn()
    _sync(r)
    t0 = time.perf_counter()
    for _ in range(iters):
        r = fn()
    _sync(r)
    return (time.perf_counter() - t0) / iters


def bench_matmul(peak):
    # Chain the matmuls inside one compiled program: per-launch host
    # overhead would otherwise sit beside a single 4096^3 matmul (~1ms).
    n, chain = 4096, 20
    a = jnp.asarray(np.random.randn(n, n), jnp.bfloat16)
    b = jnp.asarray(np.random.randn(n, n), jnp.bfloat16)

    @jax.jit
    def f(x, y):
        return jax.lax.fori_loop(0, chain, lambda i, acc: y @ acc, x)

    t = _timeit(lambda: f(a, b), 5) / chain
    flops = 2 * n ** 3
    return flops / t / peak * 100, t


def bench_matmul_sweep(peak):
    """Diagnose the matmul MFU ceiling (VERDICT r3 weak #3: 48.9% at
    4096^3 — a healthy v5e does better): sweep sizes and aspect ratios so
    one run shows whether the ceiling is size-, shape- or assumption-
    bound."""
    out = {}
    for label, (m, k, n) in {
        "2048": (2048, 2048, 2048),
        "4096": (4096, 4096, 4096),
        "8192": (8192, 8192, 8192),
        "8192x1024": (8192, 1024, 8192),
        "1024x8192": (1024, 8192, 1024),
    }.items():
        chain = 12
        a = jnp.asarray(np.random.randn(m, k), jnp.bfloat16)
        b = jnp.asarray(np.random.randn(k, n), jnp.bfloat16)

        @jax.jit
        def f(x, y):
            def body(i, acc):
                # rotate operands through the chain without changing
                # shapes: acc stays [m, n]
                return (acc * 0.5) + x @ y

            return jax.lax.fori_loop(0, chain, body,
                                     jnp.zeros((m, n), jnp.bfloat16))

        t = _timeit(lambda: f(a, b), 4) / chain
        out[label] = round(2 * m * k * n / t / peak * 100, 1)
    return out


def bench_eager_dispatch():
    x = paddle.to_tensor(np.random.randn(1024).astype("float32"),
                         stop_gradient=False)
    y = paddle.to_tensor(np.random.randn(1024).astype("float32"))

    def op():
        return (x * y)._data

    t = _timeit(op, 200, warmup=5)
    return t * 1e6  # µs per taped eager op


def bench_eager_dispatch_chained():
    """Dispatch N chained eager ops, sync ONCE — the per-op cost with the
    device pipeline kept full (separates framework dispatch rate from the
    per-op round-trip the plain row measures; VERDICT r3 item 7)."""
    x = paddle.to_tensor(np.random.randn(1024).astype("float32"))
    n = 200
    r = x
    for _ in range(5):
        r = r * 1.0001
    _sync(r)
    t0 = time.perf_counter()
    r = x
    for _ in range(n):
        r = r * 1.0001
    _sync(r)
    return (time.perf_counter() - t0) / n * 1e6


def bench_eager_dispatch_host():
    """Framework dispatch overhead with no accelerator behind it: the same
    taped eager op loop in a fresh CPU-backend subprocess (the child never
    asks for the chip its parent holds). The delta between this and the
    on-device row is the device's launch path, not the framework."""
    import subprocess
    code = r"""
import os, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np
import paddle_tpu as paddle
x = paddle.to_tensor(np.random.randn(1024).astype("float32"),
                     stop_gradient=False)
y = paddle.to_tensor(np.random.randn(1024).astype("float32"))
for _ in range(20):
    (x * y)._data.block_until_ready()
t0 = time.perf_counter()
for _ in range(300):
    r = (x * y)._data
r.block_until_ready()
print((time.perf_counter() - t0) / 300 * 1e6)
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    return float(out.stdout.strip().splitlines()[-1])


def bench_comm_overlap_cpu_mesh(overlap_engine=False):
    """Compute/comm overlap %% of a dp8 GPT step from a real xplane trace
    (8 virtual CPU devices in a subprocess — collectives exist there; the
    single real chip has none). Reference capability:
    allreduce_matmul_grad_overlapping pass + profiler overlap tables.
    ``overlap_engine=True`` reruns the same step with the bucketed
    grad-sync scheduler attached: the compiled program then carries one
    psum per bucket at grad-production order (scheduling barriers
    included), which is what XLA's async-collective pass overlaps on the
    real chip."""
    import subprocess
    dp_kwargs = ", comm_overlap=True, comm_buffer_size=0.25, " \
        "last_comm_buffer_size=0.05" if overlap_engine else ""
    code = r"""
import os, tempfile
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn.functional as F
from paddle_tpu.jit import to_static
from paddle_tpu.models import GPTConfig, GPTForCausalLM, GPTPretrainingCriterion
paddle.seed(0)
cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
                max_seq_len=128, dropout=0.0)
model = GPTForCausalLM(cfg)
model = dist.DataParallel(model%s)
crit = GPTPretrainingCriterion(cfg)""" % dp_kwargs + r"""
opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters())
rng = np.random.RandomState(0)
ids = dist.shard_batch(paddle.to_tensor(
    rng.randint(0, 512, (8, 128)).astype("int32")))
lab = dist.shard_batch(paddle.to_tensor(
    rng.randint(0, 512, (8, 128)).astype("int32")))
def train_step(x, y):
    loss = crit(model(x), y)
    loss.backward(); opt.step(); opt.clear_grad()
    return loss
step = to_static(train_step, capture=(model, opt))
step(ids, lab)
logdir = tempfile.mkdtemp()
jax.profiler.start_trace(logdir)
for _ in range(3):
    r = step(ids, lab)
np.asarray(r._data)
jax.profiler.stop_trace()
from paddle_tpu.profiler.xplane import comm_compute_breakdown
out = comm_compute_breakdown(logdir)
print(f"{out['comm_overlap_pct']:.2f} {out['comm_us']:.1f} "
      f"{out['compute_us']:.1f}")
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"   # the child never asks for the chip
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    vals = out.stdout.strip().splitlines()[-1].split()
    return float(vals[0]), float(vals[1]), float(vals[2])


def bench_overlap_inrun():
    """The overlap engine's measurement loop closed IN-RUN: eager bucketed
    DP steps with the flight recorder + metrics registry on, reading the
    ``comm_overlap_pct`` gauge the scheduler's issue/wait stamps feed (no
    xplane trace collection) plus the per-bucket latency histograms.
    Returns the parsed JSON row dict."""
    import subprocess
    code = r"""
import os, json
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed import flight_recorder as fr
from paddle_tpu.observability import metrics as om
from paddle_tpu.models import GPTConfig, GPTForCausalLM, GPTPretrainingCriterion
reg = om.enable(out_dir=None, interval_s=0)
fr.enable(capacity=4096)
paddle.seed(0)
cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
                max_seq_len=128, dropout=0.0)
model = GPTForCausalLM(cfg)
dp = dist.DataParallel(model, comm_overlap=True, comm_buffer_size=0.25,
                       last_comm_buffer_size=0.05)
crit = GPTPretrainingCriterion(cfg)
opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters())
rng = np.random.RandomState(0)
ids = paddle.to_tensor(rng.randint(0, 512, (8, 64)).astype("int32"))
lab = paddle.to_tensor(rng.randint(0, 512, (8, 64)).astype("int64"))
for _ in range(3):
    loss = crit(dp(ids), lab)
    loss.backward()
    opt.step()
    opt.clear_grad()
snap = reg.snapshot()
from paddle_tpu.observability.metrics import parse_metric_key, hist_quantile
buckets = {}
for key, h in snap["histograms"].items():
    name, labels = parse_metric_key(key)
    if name != "collective_latency_us" or \
            not labels.get("kind", "").startswith("bucket."):
        continue
    b = labels.get("group", "?").rsplit(".", 1)[-1]
    buckets[b] = {"count": h["count"],
                  "p50_us": round(hist_quantile(h, 0.5) or 0, 1),
                  "p99_us": round(hist_quantile(h, 0.99) or 0, 1)}
print("JSON:" + json.dumps({
    "overlap_pct": snap["gauges"].get("comm_overlap_pct"),
    "bucket_collectives": int(dp._grad_sync.fired),
    "buckets": buckets}))
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"   # the child never asks for the chip
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    for line in out.stdout.strip().splitlines()[::-1]:
        if line.startswith("JSON:"):
            return json.loads(line[5:])
    raise RuntimeError(f"overlap in-run leg emitted no JSON row: "
                       f"{out.stderr[-500:]}")


def bench_lenet(peak):
    paddle.seed(0)
    model = LeNet()
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=model.parameters())
    bs = 64
    xb = paddle.to_tensor(np.random.randn(bs, 1, 28, 28).astype("float32"))
    yb = paddle.to_tensor(np.random.randint(0, 10, bs).astype("int64"))

    def train_step(x, y):
        loss = F.cross_entropy(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = to_static(train_step, capture=(model, opt))
    t = _timeit(lambda: step(xb, yb), 30)
    return 1.0 / t, t


_FAST = bool(os.environ.get("PADDLE_TPU_BENCH_FAST"))  # plumbing validation


def bench_gpt(peak):
    paddle.seed(0)
    if _FAST:
        cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=128, dropout=0.0)
    else:
        cfg = GPTConfig(vocab_size=8192, hidden_size=512, num_layers=8,
                        num_heads=8, max_seq_len=512, dropout=0.0)
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    B, S = (4, 128) if _FAST else (16, 512)
    V = cfg.vocab_size
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, V, (B, S)).astype("int32"))
    labels = paddle.to_tensor(rng.randint(0, V, (B, S)).astype("int32"))

    def train_step(x, y):
        with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
            loss = crit(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = to_static(train_step, capture=(model, opt))
    t = _timeit(lambda: step(ids, labels), 5 if _FAST else 20)

    n_params = sum(p.size for p in model.parameters())
    tokens = B * S
    h, L = cfg.hidden_size, cfg.num_layers
    flops = 6 * n_params * tokens + 6 * L * B * S * S * h  # causal attn incl.
    mfu = flops / t / peak * 100
    return mfu, t, tokens / t, n_params


# ONE copy of each jnp reference chain: the legacy kernel legs and the
# A/B gate leg must time the SAME baseline formula, or a tweak to one
# silently desynchronizes the verdicts from the r01+ trajectory rows.
_ADAMW_ARGS = (1e-3, 0.9, 0.999, 1e-8, 0.01, 1.0 / (1 - 0.9),
               1.0 / (1 - 0.999))


def _jnp_adamw_ref(w, g, m, v, args=_ADAMW_ARGS):
    lr, b1, b2, eps, wd, bc1, bc2 = args
    w = w * (1 - lr * wd)
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    return w - lr * (m * bc1) / (jnp.sqrt(v * bc2) + eps), m, v


def _jnp_rms_ref(x, w, eps=1e-6):
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * inv * w).astype(x.dtype)


def _jnp_ln_ref(x, w, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * w + b).astype(x.dtype)


def _jnp_sdpa_ref(q, k, v):
    qf, kf, vf = (jnp.swapaxes(t.astype(jnp.float32), 1, 2)
                  for t in (q, k, v))
    s = jnp.einsum("bhsd,bhtd->bhst", qf, kf) / np.sqrt(q.shape[-1])
    mask = jnp.tril(jnp.ones(s.shape[-2:], bool))
    s = jnp.where(mask, s, -1e30)
    o = jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(s, -1), vf)
    return jnp.swapaxes(o, 1, 2).astype(q.dtype)


def bench_fused_adamw():
    """Pallas fused AdamW vs the jnp composition, 8M-param update
    (reference capability: fused_adam_kernel.cu)."""
    from paddle_tpu.ops.pallas.fused_adamw import fused_adamw

    n, chain = 8 * 1024 * 1024, 10
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(n), jnp.float32)
    g = jnp.asarray(rng.randn(n), jnp.float32)
    m = jnp.zeros(n, jnp.float32)
    v = jnp.zeros(n, jnp.float32)
    args = _ADAMW_ARGS

    @jax.jit
    def run_fused(w, g, m, v):
        def body(i, c):
            w, m, v = c
            return fused_adamw(w, g, m, v, *args)
        return jax.lax.fori_loop(0, chain, body, (w, m, v))

    @jax.jit
    def run_jnp(w, g, m, v):
        def body(i, c):
            w, m, v = c
            return _jnp_adamw_ref(w, g, m, v)
        return jax.lax.fori_loop(0, chain, body, (w, m, v))

    t_fused = _timeit(lambda: run_fused(w, g, m, v)[0], 5) / chain
    t_jnp = _timeit(lambda: run_jnp(w, g, m, v)[0], 5) / chain
    return t_fused * 1e3, t_jnp * 1e3


def bench_layer_norm():
    """Pallas fused LayerNorm vs the jnp composition, [4096, 4096] bf16."""
    from paddle_tpu.ops.pallas.layer_norm import layer_norm

    chain = 10
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4096, 4096), jnp.bfloat16)
    w = jnp.asarray(rng.randn(4096), jnp.float32)
    b = jnp.asarray(rng.randn(4096), jnp.float32)

    @jax.jit
    def run_pallas(x):
        def body(i, x):
            return layer_norm(x, w, b).astype(x.dtype)
        return jax.lax.fori_loop(0, chain, body, x)

    @jax.jit
    def run_jnp(x):
        def body(i, x):
            return _jnp_ln_ref(x, w, b)
        return jax.lax.fori_loop(0, chain, body, x)

    t_pallas = _timeit(lambda: run_pallas(x), 5) / chain
    t_jnp = _timeit(lambda: run_jnp(x), 5) / chain
    return t_pallas * 1e3, t_jnp * 1e3


def bench_rms_norm():
    """Pallas fused RMSNorm vs the jnp composition, [4096, 4096] bf16."""
    from paddle_tpu.ops.pallas.rms_norm import rms_norm

    chain = 10
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4096, 4096), jnp.bfloat16)
    w = jnp.asarray(rng.randn(4096), jnp.float32)

    @jax.jit
    def run_pallas(x):
        def body(i, x):
            return rms_norm(x, w).astype(x.dtype)
        return jax.lax.fori_loop(0, chain, body, x)

    @jax.jit
    def run_jnp(x):
        def body(i, x):
            return _jnp_rms_ref(x, w)
        return jax.lax.fori_loop(0, chain, body, x)

    t_pallas = _timeit(lambda: run_pallas(x), 5) / chain
    t_jnp = _timeit(lambda: run_jnp(x), 5) / chain
    return t_pallas * 1e3, t_jnp * 1e3


def bench_kernels_ab():
    """One A/B row + demotion verdict per Pallas kernel through the
    generalized gate (ops/pallas/_common.ab_gate). Runs BEFORE the gpt
    legs so a kernel that WINS at the bench shapes is promoted for them
    under PADDLE_TPU_KERNELS=auto — and a kernel that loses is demoted
    off the default path (acceptance: no losing Pallas kernel serves).
    The legacy fused_adamw/rms_norm/layer_norm rows are kept unchanged
    for r01–r05 trajectory continuity."""
    from paddle_tpu.ops.pallas import _common as gate
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bshd
    from paddle_tpu.ops.pallas.fused_adamw import fused_adamw
    from paddle_tpu.ops.pallas.layer_norm import layer_norm
    from paddle_tpu.ops.pallas.rms_norm import rms_norm

    rng = np.random.RandomState(0)
    rows = {}

    # fused AdamW at the 8M legacy shape plus 1M and 256k anchors: the
    # optimizer gates per-param via nearest-verdict (same dtype, 4x size
    # band), and the three bands [64k,1M]∪[256k,4M]∪[2M,32M] tile
    # 64k..32M with no hole
    for label, n in {"fused_adamw": 8 * 1024 * 1024,
                     "fused_adamw_mid": 1024 * 1024,
                     "fused_adamw_small": 256 * 1024}.items():
        w = jnp.asarray(rng.randn(n), jnp.float32)
        g = jnp.asarray(rng.randn(n), jnp.float32)
        m = jnp.zeros(n, jnp.float32)
        v = jnp.zeros(n, jnp.float32)
        # recorded under the leading-operand sig the call sites query
        # (optimizer._gate_allows uses shape_sig(w))
        rows[label] = gate.ab_gate(
            "fused_adamw", _jnp_adamw_ref,
            lambda w, g, m, v: fused_adamw(w, g, m, v, *_ADAMW_ARGS),
            (w, g, m, v), sig=gate.shape_sig(w))

    # norms at the legacy [4096, 4096] bf16 shape
    x = jnp.asarray(rng.randn(4096, 4096), jnp.bfloat16)
    nw = jnp.asarray(rng.randn(4096), jnp.float32)
    nb = jnp.asarray(rng.randn(4096), jnp.float32)
    rows["rms_norm"] = gate.ab_gate(
        "rms_norm", _jnp_rms_ref,
        lambda x, w: rms_norm(x, w).astype(x.dtype), (x, nw),
        sig=gate.shape_sig(x))
    rows["layer_norm"] = gate.ab_gate(
        "layer_norm", _jnp_ln_ref,
        lambda x, w, b: layer_norm(x, w, b).astype(x.dtype), (x, nw, nb),
        sig=gate.shape_sig(x))

    # flash attention at BOTH whole-step attention shapes (gpt + gpt_large)
    # so the auto gate covers the MFU legs that follow. Recorded under the
    # (q, k) sig — the sig F.scaled_dot_product_attention's eligibility
    # gate queries at the call site.
    for label, (B, S, H, D) in {"flash_attention": (16, 512, 8, 64),
                                "flash_attention_large": (8, 1024, 16, 64)
                                }.items():
        q = jnp.asarray(rng.randn(B, S, H, D), jnp.bfloat16)
        k = jnp.asarray(rng.randn(B, S, H, D), jnp.bfloat16)
        v = jnp.asarray(rng.randn(B, S, H, D), jnp.bfloat16)
        rows[label] = gate.ab_gate(
            "flash_attention", _jnp_sdpa_ref,
            lambda q, k, v: flash_attention_bshd(q, k, v, causal=True),
            (q, k, v), sig=gate.shape_sig(q, k))

    # paged attention at a decode shape, recorded under the q sig the
    # incubate paged_attention auto path queries
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_attention_reference)
    P, page, Hh, Dh, B = 256, 16, 8, 64, 8
    qd = jnp.asarray(rng.randn(B, Hh, Dh), jnp.float32)
    kp = jnp.asarray(rng.randn(P, page, Hh, Dh), jnp.float32)
    vp = jnp.asarray(rng.randn(P, page, Hh, Dh), jnp.float32)
    bt = rng.randint(1, P, (B, 8)).astype(np.int32)
    lens = rng.randint(1, 8 * page, B).astype(np.int32)
    rows["paged_attention"] = gate.ab_gate(
        "paged_attention", paged_attention_reference, paged_attention,
        (qd, kp, vp, jnp.asarray(bt), jnp.asarray(lens)), repeats=10,
        sig=gate.shape_sig(qd))
    return rows


def bench_fit_split(fast):
    """Step split of the fused donated train step under hapi.Model.fit
    with the amortized loss fetch — the PR-5 telemetry paying for itself:
    compute_ms is now dispatch-only, sync_ms appears only on fetch steps,
    and the p50s land in BENCH_RUN_REPORT.json as the before/after
    evidence for each hot-path win."""
    from paddle_tpu.io import Dataset
    from paddle_tpu.observability import metrics as obsm
    from paddle_tpu.observability.metrics import hist_quantile

    paddle.seed(0)
    if fast:
        cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=128, dropout=0.0)
        B, S, steps = 4, 64, 8
    else:
        cfg = GPTConfig(vocab_size=4096, hidden_size=256, num_layers=4,
                        num_heads=8, max_seq_len=256, dropout=0.0)
        B, S, steps = 8, 256, 30
    net = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion(cfg)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (steps * B, S + 1)).astype("int32")

    class DS(Dataset):
        def __getitem__(self, i):
            return ids[i, :-1], ids[i, 1:].astype("int64")

        def __len__(self):
            return len(ids)

    model = paddle.Model(net)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=net.parameters())
    model.prepare(optimizer=opt, loss=lambda out, y: crit(out, y))
    reg = obsm.get_registry()
    # compile warmup outside the wall clock (the split histograms keep the
    # two warm steps too — the p50s are robust to them)
    model.fit(DS(), batch_size=B, epochs=1, shuffle=False, verbose=0,
              num_iters=2)
    t0 = time.perf_counter()
    model.fit(DS(), batch_size=B, epochs=1, shuffle=False, verbose=0)
    wall = time.perf_counter() - t0
    out = {"gpt_fit_steps_per_sec": round(steps / wall, 2)}
    for h in ("step_time_ms", "compute_ms", "sync_ms", "data_wait_ms"):
        d = reg.histogram(h).to_dict()
        if d.get("count"):
            out[f"gpt_fit_{h}_p50"] = round(hist_quantile(d, 0.5), 3)
    return out


def bench_gpt_large(peak, amp_level="O1"):
    """MXU-filling config (h1024 wide matmuls): the headline small-GPT MFU
    is dispatch/width limited; this row shows the compute ceiling of the
    same whole-step path. amp_level O2 keeps params in bf16 (master fp32
    weights in the optimizer) — the full-bf16 MXU path."""
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=16384, hidden_size=1024, num_layers=8,
                    num_heads=16, max_seq_len=1024, dropout=0.0)
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion(cfg)
    if amp_level == "O2":
        model = paddle.amp.decorate(models=model, level="O2",
                                    dtype="bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=(amp_level == "O2"))
    B, S = 8, 1024
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (B, S))
                           .astype("int32"))
    labels = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (B, S))
                              .astype("int32"))

    def train_step(x, y):
        with paddle.amp.auto_cast(level=amp_level, dtype="bfloat16"):
            loss = crit(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = to_static(train_step, capture=(model, opt))
    t = _timeit(lambda: step(ids, labels), 10)
    n_params = sum(p.size for p in model.parameters())
    flops = 6 * n_params * B * S + 6 * cfg.num_layers * B * S * S \
        * cfg.hidden_size
    return flops / t / peak * 100, t, n_params


def bench_generate():
    """Serving decode throughput (tokens/s across the batch): the compiled
    path (fixed-shape KV + lax.while_loop, ONE XLA program for the whole
    decode) vs the eager per-token loop (per-step dispatch)."""
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=8192, hidden_size=512, num_layers=8,
                    num_heads=8, max_seq_len=512, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    B, prompt, new = 8, 32, 32
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (B, prompt))
                           .astype("int64"))

    def run(compiled):
        model.generate(ids, max_new_tokens=new, temperature=0.0,
                       compiled=compiled)  # warm/compile at final shape
        t0 = time.perf_counter()
        out = model.generate(ids, max_new_tokens=new, temperature=0.0,
                             compiled=compiled)
        _sync(out)
        return B * new / (time.perf_counter() - t0)

    return run(True), run(False)


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


def _chaos_child_env(repo):
    """Hermetic env for chaos worker subprocesses: CPU jax, single host
    device, repo on PYTHONPATH, no inherited fault/trainer state — and no
    shared persistent jit cache (bench.py sets one for itself at import):
    a worker SIGKILLed mid-cache-write leaves a torn entry whose
    deserialization corrupts a later incarnation's heap."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PADDLE_TPU_", "PADDLE_TRAINER"))}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        # prepend, never clobber: the parent's PYTHONPATH may carry deps
        "PYTHONPATH": os.pathsep.join(
            [repo] + [p for p in os.environ.get(
                "PYTHONPATH", "").split(os.pathsep) if p and p != repo]),
    })
    return env


def run_chaos_smoke(steps=6):
    """``--chaos`` smoke mode: a launcher-managed CPU run with one injected
    crash + one torn shard write (distributed/fault.py); asserts the
    checkpoint resume reproduces the uninterrupted loss trajectory and
    measures recovery time + checkpoint save/verify latency so robustness
    regressions show up in the perf trajectory alongside MFU."""
    import glob as _glob
    import shutil
    import subprocess
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    workers_dir = os.path.join(repo, "tests", "workers")
    worker = os.path.join(workers_dir, "ft_worker.py")
    if workers_dir not in sys.path:
        sys.path.insert(0, workers_dir)
    from ft_markers import parse_losses as losses, parse_stamps as stamps
    tmp = tempfile.mkdtemp(prefix="pd_chaos_")
    base_env = _chaos_child_env(repo)
    base_env["PADDLE_TPU_FT_STEPS"] = str(steps)
    try:
        env = dict(base_env,
                   PADDLE_TPU_CKPT_DIR=os.path.join(tmp, "ck_ref"))
        t0 = time.perf_counter()
        ref = subprocess.run([sys.executable, worker], env=env,
                             capture_output=True, text=True, timeout=600,
                             cwd=repo)
        ref_wall = time.perf_counter() - t0
        if ref.returncode != 0:
            return {"error": "chaos reference run failed: "
                             + (ref.stdout + ref.stderr)[-300:]}
        ref_losses = losses(ref.stdout)
        log_dir = os.path.join(tmp, "logs")
        env = dict(base_env,
                   PADDLE_TPU_CKPT_DIR=os.path.join(tmp, "ck_fault"),
                   PADDLE_TPU_FAULTS="crash@step:3,torn_write@ckpt:2")
        t0 = time.perf_counter()
        launched = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "1", "--max_restarts", "1",
             "--log_dir", log_dir, worker],
            env=env, capture_output=True, text=True, timeout=600, cwd=repo)
        fault_wall = time.perf_counter() - t0
        logs = [open(p).read() for p in sorted(
            _glob.glob(os.path.join(log_dir, "workerlog.0*")))]
        merged = "".join(logs)
        got = losses(merged)
        ok = (launched.returncode == 0 and set(got) == set(ref_losses)
              and all(abs(got[i] - ref_losses[i]) <= 1e-6
                      for i in ref_losses))
        out = {
            "chaos_resume_ok": ok,
            "chaos_wall_overhead_s": round(fault_wall - ref_wall, 3),
        }
        # resume gap: last durable step of the crashed incarnation → first
        # completed (recomputed) step of the resumed one
        done = [stamps(t, r"STEP_DONE \d+") for t in logs]
        if len(done) >= 2 and done[0] and done[1]:
            out["chaos_recovery_s"] = round(done[1][0] - done[0][-1], 3)
        save_ms = stamps(merged, "CKPT_SAVE_MS")
        if save_ms:
            out["ckpt_save_ms"] = round(sum(save_ms) / len(save_ms), 2)
        verify_ms = stamps(merged, "CKPT_VERIFY_MS")
        if verify_ms:
            out["ckpt_verify_ms"] = round(verify_ms[0], 2)
        if not ok:
            out["error"] = ("chaos run rc=%d; losses %d/%d matched"
                            % (launched.returncode, sum(
                                1 for i in ref_losses if i in got
                                and abs(got[i] - ref_losses[i]) <= 1e-6),
                               len(ref_losses)))
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_elastic_chaos(epochs=2, batches=6):
    """``--chaos`` elastic leg: SIGKILL one worker of a 3-worker elastic
    job (``--np 2:3``, hapi.Model.fit + CheckpointLineage) and measure the
    scale-event recovery time — the killed rank's SELF_SIGKILL stamp to
    the survivors' first post-resume BATCH stamp at world_size=2 — so
    elastic regressions show up in the perf trajectory alongside the
    checkpoint latency numbers."""
    import re
    import shutil
    import subprocess
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    workers_dir = os.path.join(repo, "tests", "workers")
    if workers_dir not in sys.path:
        sys.path.insert(0, workers_dir)
    from ft_markers import free_port as _free_port
    from ft_markers import read_worker_logs
    worker = os.path.join(workers_dir, "elastic_worker.py")
    tmp = tempfile.mkdtemp(prefix="pd_elastic_")
    log_dir = os.path.join(tmp, "logs")
    env = _chaos_child_env(repo)
    env.update({
        "PADDLE_TPU_CKPT_DIR": os.path.join(tmp, "ck"),
        "PADDLE_TPU_FT_STORE_PORT": str(_free_port()),
        "PADDLE_TPU_FT_EPOCHS": str(epochs),
        "PADDLE_TPU_FT_BATCHES": str(batches),
        "PADDLE_TPU_FT_INTERVAL": "1",
        "PADDLE_TPU_ELASTIC_KILL": "2:2",   # rank 2: SIGKILL at batch 2
    })
    try:
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--np", "2:3", "--master", f"127.0.0.1:{_free_port()}",
             "--elastic_port", str(_free_port()),
             "--terminate_grace", "5", "--log_dir", log_dir, worker],
            env=env, capture_output=True, text=True, timeout=600, cwd=repo)
        scaled = ("scale event" in r.stderr
                  and "relaunching at world_size=2" in r.stderr)

        def _log_of(rank):
            return read_worker_logs(log_dir, rank)

        kill_stamps = [float(m.group(1)) for m in re.finditer(
            r"SELF_SIGKILL ([\d.]+)", _log_of(2))]
        resumed = 0
        first_batch = []
        for rank in (0, 1):
            log = _log_of(rank)
            if re.search(r"RESUMED epoch=\d+ step=\d+", log):
                resumed += 1
            round1 = log.split("WORLD 2", 1)
            if len(round1) == 2:
                m = re.search(r"BATCH \d+ \d+ \d+ ([\d.]+)", round1[1])
                if m:
                    first_batch.append(float(m.group(1)))
        ok = (r.returncode == 0 and scaled and resumed == 2
              and bool(kill_stamps) and len(first_batch) == 2)
        out = {"elastic_scale_ok": ok}
        if kill_stamps and first_batch:
            out["elastic_recovery_s"] = round(
                min(first_batch) - kill_stamps[0], 3)
        if not ok:
            out["elastic_error"] = (
                "rc=%d scaled=%s resumed=%d/2: %s" % (
                    r.returncode, scaled, resumed, r.stderr[-300:]))
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_hang_chaos(steps=6):
    """``--chaos`` hang leg: inject ``hang@step`` into 1 of 3 workers of a
    launcher-managed job with the flight recorder + watchdog armed. Every
    rank must dump its collective ring and the launcher post-mortem must
    name the hung rank; detect-to-abort latency (watchdog trip to process
    exit, from the dumps' escalate_ms) lands in the bench JSON so hang-
    diagnosis regressions show up alongside the recovery numbers."""
    import shutil
    import subprocess
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    workers_dir = os.path.join(repo, "tests", "workers")
    if workers_dir not in sys.path:
        sys.path.insert(0, workers_dir)
    from ft_markers import free_port as _free_port
    from paddle_tpu.distributed.flight_recorder import collect_dumps
    worker = os.path.join(workers_dir, "fr_worker.py")
    tmp = tempfile.mkdtemp(prefix="pd_hang_")
    log_dir = os.path.join(tmp, "logs")
    env = _chaos_child_env(repo)
    env.update({
        "PADDLE_TPU_FLIGHT_RECORDER": "64",
        "PADDLE_TPU_WATCHDOG_TIMEOUT": "10",
        "PADDLE_TPU_WATCHDOG_ESCALATION_BUDGET_S": "10",
        "PADDLE_TPU_FR_STORE": f"127.0.0.1:{_free_port()}",
        "PADDLE_TPU_FR_STEPS": str(steps),
        "PADDLE_TPU_FAULTS": "hang@step:3%1",
        "PADDLE_TPU_FAULT_HANG_S": "3600",
    })
    try:
        t0 = time.perf_counter()
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "3", "--master",
             f"127.0.0.1:{_free_port()}", "--log_dir", log_dir, worker],
            env=env, capture_output=True, text=True, timeout=600, cwd=repo)
        wall = time.perf_counter() - t0
        dumps = collect_dumps(log_dir)
        dumped = sorted(d.get("rank") for d in dumps)
        named = "rank 1 stalled before" in r.stderr
        ok = (r.returncode == 19 and dumped == [0, 1, 2] and named)
        out = {"hang_postmortem_ok": ok,
               "hang_job_wall_s": round(wall, 3)}
        esc = [d.get("escalate_ms") for d in dumps
               if d.get("escalate_ms") is not None]
        if esc:
            out["hang_detect_to_abort_s"] = round(max(esc) / 1e3, 3)
        if not ok:
            out["hang_error"] = ("rc=%d dumped=%s named=%s: %s" % (
                r.returncode, dumped, named, r.stderr[-300:]))
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_node_chaos(epochs=2, batches=6):
    """``--chaos`` node leg (multi-host elastic): a simulated 3-node
    elastic job (``--nnodes 1:3``, one worker per node) loses a WHOLE
    node to SIGKILL, then a second node turns flaky (same crash every
    incarnation) until the quarantine window excludes it. Records the
    node-loss detect-to-resume latency (coordinator detection stamp →
    survivors' first post-relaunch batch) and the quarantine hit count so
    multi-host robustness regressions show up in the perf trajectory."""
    import re
    import shutil
    import subprocess
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    workers_dir = os.path.join(repo, "tests", "workers")
    if workers_dir not in sys.path:
        sys.path.insert(0, workers_dir)
    from ft_markers import free_port as _free_port
    from ft_markers import read_worker_logs
    worker = os.path.join(workers_dir, "elastic_worker.py")
    tmp = tempfile.mkdtemp(prefix="pd_node_")
    log_dir = os.path.join(tmp, "logs")
    env = _chaos_child_env(repo)
    env.update({
        "PADDLE_TPU_CKPT_DIR": os.path.join(tmp, "ck"),
        "PADDLE_TPU_FT_STORE_PORT": str(_free_port()),
        "PADDLE_TPU_FT_EPOCHS": str(epochs),
        "PADDLE_TPU_FT_BATCHES": str(batches),
        "PADDLE_TPU_FT_INTERVAL": "1",
        # node2's worker (grank 2) SIGKILLs after 2 batches; its agent
        # converts that into whole-node death (host loss)
        "PADDLE_TPU_ELASTIC_KILL": "2:2",
        "PADDLE_TPU_NODE_DIE_WITH_RANK": "2",
        # node1 is FLAKY from the relaunch on: same crash every
        # incarnation until quarantined (2 failures in the window)
        "PADDLE_TPU_NODE_CRASH": "node1:1:43:1",
    })
    try:
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nnodes", "1:3", "--nproc_per_node", "1",
             "--master", f"127.0.0.1:{_free_port()}",
             "--elastic_ttl", "3", "--terminate_grace", "5",
             "--quarantine_window", "300", "--log_dir", log_dir, worker],
            env=env, capture_output=True, text=True, timeout=600, cwd=repo)
        lost = re.search(r"node loss detected node=\S+ wall=([\d.]+)",
                         r.stderr)
        qhits = re.search(r"quarantine_hits=(\d+)", r.stderr)
        quarantined = "quarantine node=node1" in r.stderr
        first_batch = None
        for rank in (0, 1):
            log = read_worker_logs(log_dir, rank)
            after = log.split("WORLD 2", 1)
            if len(after) == 2:
                m = re.search(r"BATCH \d+ \d+ \d+ ([\d.]+)", after[1])
                if m:
                    t = float(m.group(1))
                    first_batch = t if first_batch is None \
                        else min(first_batch, t)
        ok = (r.returncode == 0 and lost is not None and quarantined
              and first_batch is not None)
        out = {"node_elastic_ok": ok,
               "node_quarantine_hits": int(qhits.group(1)) if qhits
               else 0}
        if lost and first_batch is not None:
            out["node_loss_detect_to_resume_s"] = round(
                first_batch - float(lost.group(1)), 3)
        if not ok:
            out["node_error"] = ("rc=%d lost=%s quarantined=%s: %s" % (
                r.returncode, bool(lost), quarantined, r.stderr[-300:]))
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_controlplane_chaos():
    """``--chaos`` control-plane leg (ISSUE 10): SIGKILL the PRIMARY
    coordinator mid-round — its in-process primary registry store dies
    with it, so one injected ``coordinator_die`` kills BOTH halves of the
    control plane at once. The shadow coordinator (standby registry +
    log shipper) must adopt the published round spec after the lease
    expires and supervise the SAME round to completion: zero
    re-rendezvous, zero worker relaunches. Records
    ``controlplane_failover_s`` (COORDINATOR_DIE stamp → SHADOW_ADOPTED
    stamp) and ``controlplane_rounds_preserved`` so control-plane
    takeover latency regressions show up in the trajectory."""
    import glob
    import re
    import shutil
    import subprocess
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    workers_dir = os.path.join(repo, "tests", "workers")
    if workers_dir not in sys.path:
        sys.path.insert(0, workers_dir)
    from ft_markers import free_port as _free_port
    tmp = tempfile.mkdtemp(prefix="pd_cplane_")
    log_dir = os.path.join(tmp, "logs")
    worker = os.path.join(tmp, "nw.py")
    with open(worker, "w") as f:
        f.write("import os, time\n"
                "print('NW', os.environ.get('PADDLE_TPU_RESTART_NUM'),"
                " flush=True)\n"
                "time.sleep(20)\n"
                "print('NW_DONE', flush=True)\n")
    env = _chaos_child_env(repo)
    env.update({
        "PADDLE_TPU_STORE_FAILOVER_DEADLINE": "10",
        "PADDLE_TPU_STORE_PROBE_DEADLINE": "1",
    })
    # the primary's lease beats at ttl/3; beat 10 lands mid-round, after
    # round 1 + the coordinator state checkpoint were published
    prim_env = dict(env,
                    PADDLE_TPU_FAULTS="coordinator_die@coord_beat:10")
    master = f"127.0.0.1:{_free_port()},127.0.0.1:{_free_port()}"
    base = [sys.executable, "-m", "paddle_tpu.distributed.launch",
            "--nnodes", "2:2", "--nproc_per_node", "1",
            "--master", master, "--elastic_ttl", "2",
            "--terminate_grace", "2", "--log_dir", log_dir, worker]
    shadow = prim = None
    try:
        shadow = subprocess.Popen(
            base[:-1] + ["--coordinator_role", "shadow",
                         "--local_agents", "0", worker],
            env=env, cwd=repo, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        time.sleep(1.0)
        prim = subprocess.Popen(
            base[:-1] + ["--coordinator_role", "primary",
                         "--local_agents", "2", worker],
            env=prim_env, cwd=repo, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        pout, _ = prim.communicate(timeout=180)
        sout, _ = shadow.communicate(timeout=240)
        die = re.search(r"COORDINATOR_DIE ([\d.]+)", pout)
        adopt = re.search(r"SHADOW_ADOPTED round=(\d+) term=\d+ "
                          r"wall=([\d.]+)", sout)
        preserved = bool(adopt) and adopt.group(1) == "1" \
            and "round 2" not in sout and "round 2" not in pout \
            and not glob.glob(os.path.join(log_dir,
                                           "workerlog.*.restart*"))
        ok = (prim.returncode == -9 and shadow.returncode == 0
              and die is not None and preserved
              and "all 2 node(s) finished" in sout)
        out = {"controlplane_ok": ok,
               "controlplane_rounds_preserved": int(preserved)}
        if die and adopt:
            out["controlplane_failover_s"] = round(
                float(adopt.group(2)) - float(die.group(1)), 3)
        if not ok:
            out["controlplane_error"] = (
                "prim_rc=%s shadow_rc=%s die=%s adopt=%s: %s" % (
                    prim.returncode, shadow.returncode, bool(die),
                    bool(adopt), (sout or "")[-300:]))
        return out
    finally:
        # the kill sweep lives HERE, not in an inner block after both
        # spawns: a failed primary Popen must not orphan the already-
        # started shadow polling forever for a lease that never comes
        for p in (prim, shadow):
            if p is not None and p.poll() is None:
                p.kill()
        shutil.rmtree(tmp, ignore_errors=True)


def run_integrity_chaos(epochs=2, batches=8):
    """``--chaos`` integrity leg (ISSUE 19): the training integrity
    guard under both of its fault models.

    * loss-spike: a single-process guarded fit with one poisoned batch
      (``loss_spike@batch``) + lineage — the MAD gate must trip, rewind
      to the pre-spike snapshot and replay with the poisoned window
      skipped, landing back near the clean twin's final loss. Records
      the detect→rewind latency (``train_rewind_detect_s``) and rewind
      count (``train_rewinds``).
    * bitflip: a 3-rank launcher job with comm overlap + cross-rank
      gradient fingerprints where rank 1's published bucket summary is
      bit-flipped (``grad_bitflip@grad_fingerprint``) — the majority
      vote must blame rank 1 (``integrity_blamed_rank``), strike it,
      redo the step, and finish with LOSS lines EXACTLY matching a
      clean twin (the flip hits the host copy, device math is intact).
    """
    import re
    import shutil
    import subprocess
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    workers_dir = os.path.join(repo, "tests", "workers")
    if workers_dir not in sys.path:
        sys.path.insert(0, workers_dir)
    from ft_markers import free_port as _free_port
    worker = os.path.join(workers_dir, "integrity_worker.py")
    tmp = tempfile.mkdtemp(prefix="pd_integrity_")
    base_env = _chaos_child_env(repo)
    base_env.update({"PADDLE_TPU_IT_EPOCHS": str(epochs),
                     "PADDLE_TPU_IT_BATCHES": str(batches)})

    def _losses(text):
        got = {}
        for m in re.finditer(r"LOSS (\d+) ([\d.]+)", text):
            got.setdefault(int(m.group(1)), set()).add(m.group(2))
        return got

    try:
        out = {}
        # -- loss-spike leg: poison batch 5, expect rewind + skip replay
        env = dict(base_env,
                   PADDLE_TPU_CKPT_DIR=os.path.join(tmp, "ck_spike"))
        clean = subprocess.run([sys.executable, worker], env=env,
                               capture_output=True, text=True,
                               timeout=600, cwd=repo)
        env = dict(base_env,
                   PADDLE_TPU_CKPT_DIR=os.path.join(tmp, "ck_spike_f"))
        env["PADDLE_TPU_FAULTS"] = "loss_spike@batch:5"
        spiked = subprocess.run([sys.executable, worker], env=env,
                                capture_output=True, text=True,
                                timeout=600, cwd=repo)
        rewinds = re.findall(r"INTEGRITY_REWIND n=\d+ to_step=\d+ "
                             r"skip=\(\d+,\d+,\d+\) detect_s=([\d.]+)",
                             spiked.stdout)
        mf = re.search(r"FINAL_LOSS ([\d.]+)", spiked.stdout)
        mc = re.search(r"FINAL_LOSS ([\d.]+)", clean.stdout)
        fault_final = float(mf.group(1)) if mf else float("inf")
        clean_final = float(mc.group(1)) if mc else float("inf")
        # "parity": the replay excises the poisoned window, so the
        # trajectory differs by those batches — near, not bit-equal
        spike_ok = (clean.returncode == 0 and spiked.returncode == 0
                    and len(rewinds) >= 1
                    and fault_final <= max(2.0 * clean_final,
                                           clean_final + 5.0))
        out["train_rewinds"] = len(rewinds)
        if rewinds:
            out["train_rewind_detect_s"] = float(rewinds[0])
        if not spike_ok:
            out["integrity_spike_error"] = (
                "clean_rc=%d fault_rc=%d rewinds=%d final=%s/%s: %s" % (
                    clean.returncode, spiked.returncode, len(rewinds),
                    fault_final, clean_final,
                    (spiked.stdout + spiked.stderr)[-300:]))

        # -- bitflip leg: 3 ranks, fingerprints on, flip rank 1's copy
        def _launch(faults):
            env = dict(base_env)
            env.update({
                "PADDLE_TPU_DP_OVERLAP": "1",
                "PADDLE_TPU_IT_FINGERPRINTS": "1",
                "PADDLE_TPU_FR_STORE": f"127.0.0.1:{_free_port()}",
            })
            if faults:
                env["PADDLE_TPU_FAULTS"] = faults
            log_dir = tempfile.mkdtemp(prefix="logs_", dir=tmp)
            r = subprocess.run(
                [sys.executable, "-m", "paddle_tpu.distributed.launch",
                 "--nproc_per_node", "3", "--master",
                 f"127.0.0.1:{_free_port()}", "--log_dir", log_dir,
                 worker],
                env=env, capture_output=True, text=True, timeout=600,
                cwd=repo)
            logs = "".join(
                open(os.path.join(log_dir, f)).read()
                for f in sorted(os.listdir(log_dir))
                if f.startswith("workerlog"))
            return r, logs

        rc, clogs = _launch(None)
        rf, flogs = _launch("grad_bitflip@grad_fingerprint:2%1")
        blamed = re.findall(r"INTEGRITY_BLAME rank=(\d+)", flogs)
        parity = _losses(flogs) == _losses(clogs) and bool(_losses(flogs))
        flip_ok = (rc.returncode == 0 and rf.returncode == 0
                   and blamed and set(blamed) == {"1"} and parity)
        if blamed:
            out["integrity_blamed_rank"] = int(blamed[0])
        if not flip_ok:
            out["integrity_bitflip_error"] = (
                "clean_rc=%d fault_rc=%d blamed=%s parity=%s: %s" % (
                    rc.returncode, rf.returncode, sorted(set(blamed)),
                    parity, (flogs + rf.stderr)[-300:]))
        out["integrity_ok"] = bool(spike_ok and flip_ok)
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _run_guarded_legs(sub, legs):
    """Run bench legs in order, merging each leg's rows into ``sub`` the
    moment they exist: a later leg that raises records
    ``<name>_error``/``<name>_leg_ok`` and keeps every prior leg's JSON
    on the wire — the guard all the chaos/serving legs follow (asserted
    by a unit test so new legs can't regress it). A leg can also report
    a soft failure by returning ``<name>_ok: False`` among its rows.
    Returns overall ok."""
    ok = True
    for name, fn in legs:
        try:
            rows = fn()
            sub.update(rows)
            if not rows.get(f"{name}_ok", True):
                ok = False
        except Exception as e:
            sub.update({f"{name}_error": repr(e)[-300:],
                        f"{name}_leg_ok": False})
            ok = False
    return ok


def run_linalg_bench(n=512, block=64, p=16, world=2):
    """``--linalg`` perf + parity leg: SUMMA sharded matmul on a
    thread-per-rank world over a shared LocalExchange (the chaos twin
    runs the same kernels under the real launcher) — wall-clock GFLOP/s
    and the f64 relative residual against the numpy reference, the same
    bound the in-run oracle gates on."""
    import threading as _t

    from paddle_tpu.distributed import dlinalg

    rng = np.random.default_rng(7)
    A_full = rng.standard_normal((n, n))
    B_full = rng.standard_normal((n, p))
    ex = dlinalg.LocalExchange()
    results = [None] * world
    errors = []

    def target(r):
        try:
            A = dlinalg.ShardedMatrix.from_global(A_full, block,
                                                  world=world, rank=r)
            B = dlinalg.ShardedMatrix.from_global(B_full, block,
                                                  world=world, rank=r)
            results[r] = dlinalg.summa_matmul(A, B, ex, tag="bench")
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errors.append(e)

    threads = [_t.Thread(target=target, args=(r,), daemon=True)
               for r in range(world)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    if any(t.is_alive() for t in threads):
        raise RuntimeError("linalg bench SPMD thread hung")
    ref = dlinalg.matmul_reference(A_full, B_full)
    C = np.zeros_like(ref)
    for r in range(world):
        for b in results[r].owned:
            lo, hi = results[r].layout.row_range(b)
            C[lo:hi] = results[r].block(b)
    resid = float(np.linalg.norm(C - ref) / np.linalg.norm(ref))
    # each rank runs every round, so the fleet's useful flops are the
    # single product's 2*n*n*p — wall time already pays the duplication
    gflops = 2.0 * n * n * p / wall / 1e9
    _log(f"[bench] linalg: {gflops:.2f} GFLOP/s (world {world}), "
         f"residual {resid:.2e}")
    return {"linalg_gflops": round(gflops, 2),
            "linalg_residual": resid,
            "linalg_ok": resid < 1e-12}


def run_linalg_chaos():
    """``--linalg`` chaos twin: SIGKILL one of three elastic workers
    mid-factorization (the dlinalg eigensolve under the real launcher);
    the world-2 incarnation must reshard + resume from the last
    committed panel with zero relaunch budget consumed and the residual
    oracle must still pass. Records the kill -> first-resumed-panel
    recovery time."""
    import re
    import shutil
    import subprocess
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    workers_dir = os.path.join(repo, "tests", "workers")
    if workers_dir not in sys.path:
        sys.path.insert(0, workers_dir)
    from ft_markers import free_port as _free_port
    from ft_markers import read_worker_logs
    worker = os.path.join(workers_dir, "dlinalg_worker.py")
    tmp = tempfile.mkdtemp(prefix="pd_linalg_")
    log_dir = os.path.join(tmp, "logs")
    env = _chaos_child_env(repo)
    env.update({
        "PADDLE_TPU_CKPT_DIR": os.path.join(tmp, "ck"),
        "PADDLE_TPU_FT_STORE_PORT": str(_free_port()),
        "PADDLE_TPU_DLA_N": "96", "PADDLE_TPU_DLA_P": "4",
        "PADDLE_TPU_DLA_BLOCK": "16",
        "PADDLE_TPU_DLA_SLEEP_S": "0.05",
        "PADDLE_TPU_DLA_KILL": "2:9",  # rank 2, mid-sweep-1
    })
    try:
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--np", "2:3", "--master", f"127.0.0.1:{_free_port()}",
             "--elastic_port", str(_free_port()),
             "--max_restarts", "0",   # a scale event must be FREE
             "--terminate_grace", "5", "--log_dir", log_dir, worker],
            env=env, capture_output=True, text=True, timeout=600, cwd=repo)
        scaled = ("scale event" in r.stderr
                  and "relaunching at world_size=2" in r.stderr)
        kill = re.search(r"SELF_SIGKILL ([\d.]+)",
                         read_worker_logs(log_dir, 2))
        resumed = 0
        first_panel = []
        resid = None
        for rank in (0, 1):
            log = read_worker_logs(log_dir, rank)
            round1 = log.split("WORLD 2", 1)
            if len(round1) == 2:
                if re.search(r"RESUMED step=\d+", round1[1]):
                    resumed += 1
                m = re.search(r"PANEL \d+ \d+ ([\d.]+)", round1[1])
                if m:
                    first_panel.append(float(m.group(1)))
                d = re.search(r"DONE \d+ ([\d.eE+-]+)", round1[1])
                if d:
                    resid = float(d.group(1))
        ok = (r.returncode == 0 and scaled and resumed == 2
              and kill is not None and len(first_panel) == 2
              and resid is not None and resid < 1e-6)
        out = {"linalg_chaos_ok": ok}
        if kill and first_panel:
            out["linalg_recovery_s"] = round(
                min(first_panel) - float(kill.group(1)), 3)
        if resid is not None:
            out["linalg_chaos_residual"] = resid
        if not ok:
            out["linalg_chaos_error"] = (
                "rc=%d scaled=%s resumed=%d/2 resid=%s: %s" % (
                    r.returncode, scaled, resumed, resid,
                    r.stderr[-300:]))
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _serving_cfg_and_knobs():
    """One copy of the serving bench shapes."""
    from paddle_tpu.models import GPTConfig
    device = str(jax.devices()[0].device_kind)
    cfg = GPTConfig(vocab_size=8192, hidden_size=512, num_layers=8,
                    num_heads=8, max_seq_len=512, dropout=0.0)
    knobs = dict(pool=512, slots=8, page=16, chunk=64, new_tokens=24,
                 tail=(8, 32), qps=12.0)
    return device, cfg, knobs


def _fleet_workload(cfg, kb):
    """One seeded session workload shared by every fleet leg (single,
    fleet, disagg): sessions with a common head (affinity + cross-engine
    sharing measurable) at lengths the bench engines can hold."""
    from paddle_tpu.serving import make_session_prompts
    head = 3 * kb["page"]  # 3 full pages of shareable prefix
    prompts, sids = make_session_prompts(
        n_sessions=4, requests_per_session=8, head_len=head,
        tail_len=kb["tail"], vocab=cfg.vocab_size, seed=19)
    # enough decode work that neither the arrival window nor the
    # per-request dispatch overhead bounds the wall clock (the speedup
    # twin measures decode service capacity; dispatch amortizes over
    # the generated tokens)
    return prompts, sids, 4 * kb["new_tokens"]


def _parallel_scaling_probe(n=2, seconds=1.2):
    """The host's REAL process-level scaling ceiling: aggregate matmul
    rate of ``n`` simultaneous pinned worker processes over one. On a
    full host this reads ~n; on a shares-throttled CI container (this
    image: cpuset 0-1 but cpu.shares≈1.5 cores) it reads the fraction
    the cgroup actually grants — the fleet speedup gate is measured
    against THIS ceiling, so the 1.7x acceptance binds exactly where
    the hardware can express it and a starved container still verifies
    real scaling instead of a physically impossible constant."""
    import subprocess

    code = ("import numpy as np, time, os\n"
            "try: os.sched_setaffinity(0, {int(os.environ['P_CORE'])})\n"
            "except Exception: pass\n"
            "a = np.random.RandomState(0).rand(192, 192).astype('f')\n"
            "t = time.perf_counter() + %f\n"
            "c = 0\n"
            "while time.perf_counter() < t:\n"
            "    a = a @ a * 1e-3\n"
            "    c += 1\n"
            "print(c)" % seconds)
    ncores = os.cpu_count() or 1

    def run(k):
        env = dict(os.environ)
        env["OMP_NUM_THREADS"] = env["OPENBLAS_NUM_THREADS"] = "1"
        procs = []
        for i in range(k):
            e = dict(env)
            e["P_CORE"] = str(i % ncores)
            procs.append(subprocess.Popen(
                [sys.executable, "-c", code], env=e,
                stdout=subprocess.PIPE, text=True))
        return sum(int(p.communicate()[0].strip() or 0) for p in procs)

    one = max(1, run(1))
    return run(n) / one


def run_fleet_serving_bench(n_engines=2):
    """``--serving-fleet`` leg (ISSUE 14): a MULTI-PROCESS fleet — N
    engine replicas in their own processes (own XLA client, own pools),
    one TCPStore control plane carrying registration/liveness, the
    store-RPC submit path and the cross-engine prefix-page index — under
    the Poisson open-loop session workload, against a single-engine twin
    on the SAME seeded load. Records aggregate tokens/s (the >= 1.7x
    acceptance), per-engine TTFT/ITL tails from the engine-labeled
    metrics JSONL, and the cross-engine remote-hit counter."""
    import shutil
    import socket as _socket
    import subprocess
    import tempfile

    from paddle_tpu.distributed.tcp_store import TCPStore
    from paddle_tpu.observability import report as obsrep
    from paddle_tpu.serving.fleet import (EngineRegistry, FleetRouter,
                                          RemoteEngineHandle)

    repo = os.path.dirname(os.path.abspath(__file__))
    device, cfg, kb = _serving_cfg_and_knobs()
    with _socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    store_ep = f"127.0.0.1:{port}"
    master = TCPStore("127.0.0.1", port, is_master=True)
    md = tempfile.mkdtemp(prefix="pd_fleet_metrics_")
    env = _chaos_child_env(repo)
    # one core's worth of XLA per engine replica (both legs): the
    # speedup twin measures replica SCALING, which a single engine
    # grabbing every host thread would mask — per-replica resources are
    # fixed, adding replicas adds throughput. The eigen flag only tames
    # the LEGACY cpu runtime, so pin the workers to it; the thunk
    # runtime ignores it and fans out across every core.
    env["XLA_FLAGS"] = env.get("XLA_FLAGS", "") \
        + " --xla_cpu_multi_thread_eigen=false" \
        + " --xla_cpu_use_thunk_runtime=false"
    env["OMP_NUM_THREADS"] = "1"
    env["OPENBLAS_NUM_THREADS"] = "1"
    workers = []
    prompts, _sids, new_tokens = _fleet_workload(cfg, kb)
    sub = {"serving_fleet_engines": n_engines}
    # calibrate BEFORE the workers exist (idle host): what aggregate
    # speedup can n simultaneous single-core processes physically reach
    # here — the honest denominator for the 1.7x acceptance
    ceiling = _parallel_scaling_probe(n=n_engines)
    # a host with n free cores must deliver the full 1.7x acceptance; a
    # shares-throttled container (this image: 1.2-1.8 effective cores,
    # swinging run to run with co-tenant load) cannot express process
    # scaling — between 1.5 and 2 effective cores the gate is a 0.7
    # sanity floor, and below 1.5 the host cannot even run two replicas
    # side by side, so the ratio carries no signal and only the
    # mechanism invariants (zero failures, balance, remote hits) gate;
    # the true ratio + ceiling land in the JSON either way
    if ceiling >= 2.0:
        speedup_gate = 1.7
    elif ceiling >= 1.5:
        speedup_gate = 0.7
    else:
        speedup_gate = None
    sub["serving_fleet_host_parallelism"] = round(ceiling, 3)
    sub["serving_fleet_speedup_gate"] = speedup_gate
    ncores = os.cpu_count() or 1

    def _pin(core):
        # one core per replica, BOTH legs (a replica's resource share is
        # one core here, one chip on a real pod); an un-pinned single
        # engine spreading onto every core fakes a faster baseline
        def inner():
            try:
                os.sched_setaffinity(0, {core % ncores})
            except (AttributeError, OSError):
                pass
        return inner

    try:
        for i in range(n_engines):
            workers.append(subprocess.Popen(
                [sys.executable, "-m", "paddle_tpu.serving.fleet.remote",
                 "--store", store_ep, "--engine-id", f"e{i}",
                 "--job", "bench", "--seed", "0",
                 "--vocab", str(cfg.vocab_size),
                 "--hidden", str(cfg.hidden_size),
                 "--layers", str(cfg.num_layers),
                 "--heads", str(cfg.num_heads),
                 "--seq", str(cfg.max_seq_len),
                 "--page", str(kb["page"]), "--pool", str(kb["pool"]),
                 "--slots", str(kb["slots"]),
                 "--chunk", str(kb["chunk"]),
                 "--share", "--metrics-dir", md, "--rank", str(i)],
                env=env, cwd=repo, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True,
                preexec_fn=_pin(i)))
        reg = EngineRegistry(TCPStore("127.0.0.1", port), job="bench")
        deadline = time.time() + 300
        while len(reg.engines()) < n_engines:
            if time.time() > deadline or any(
                    w.poll() is not None for w in workers):
                tails = [w.communicate()[0][-500:] for w in workers
                         if w.poll() is not None]
                raise RuntimeError(
                    f"fleet workers never registered: {tails}")
            time.sleep(0.5)

        def router_over(ids):
            r = FleetRouter()
            for eid in ids:
                r.add_engine(None, handle=RemoteEngineHandle(
                    lambda: TCPStore("127.0.0.1", port), eid,
                    job="bench",
                    registry=EngineRegistry(TCPStore("127.0.0.1", port),
                                            job="bench")))
            r.page_size = kb["page"]
            r.cfg = cfg
            return r

        from paddle_tpu.serving import run_poisson_load
        # single-engine twin FIRST (e0 warm from startup, e1 untouched)
        r1 = router_over(["e0"])
        single = run_poisson_load(r1, qps=kb["qps"] * 12,
                                  prompts=prompts,
                                  max_new_tokens=new_tokens, seed=19,
                                  timeout=600.0, by_engine=True)
        # the fleet leg re-runs the SAME seeded workload over N engines
        rN = router_over([f"e{i}" for i in range(n_engines)])
        fleet = run_poisson_load(rN, qps=kb["qps"] * 12,
                                 prompts=prompts,
                                 max_new_tokens=new_tokens, seed=19,
                                 timeout=600.0, by_engine=True)
        # cross-engine prefix sharing: a session whose head e0 published
        # lands its first request on e1 — the remote-hit counter is the
        # "prefilled once per fleet" proof
        # pin to BOTH engines: whichever is not the head's owner imports
        # the published pages (a perfectly-affine Poisson pass might
        # otherwise never spill a session across engines)
        hot = prompts[0]
        rN.submit(hot, max_new_tokens=2, engine="e0",
                  timeout=60).result(120)
        rN.submit(hot, max_new_tokens=2, engine="e1",
                  timeout=60).result(120)
        time.sleep(1.5)  # one heartbeat so final stats reach the store
        recs = reg.engines(live_only=False)
        remote_hits = sum(int(r.get("prefix_remote_hits", 0) or 0)
                          for r in recs.values())
        published = sum(int(r.get("prefix_published_pages", 0) or 0)
                        for r in recs.values())
        master.set("serving/bench/stop", b"1")
        for w in workers:
            w.wait(120)
        by = fleet.get("by_engine", {})
        tok_by_engine = {e: r["tokens"] for e, r in by.items()}
        balance = (min(tok_by_engine.values())
                   / max(1, max(tok_by_engine.values()))) \
            if tok_by_engine else 0.0
        speedup = fleet["tokens_per_sec"] / single["tokens_per_sec"] \
            if single["tokens_per_sec"] else 0.0
        sub.update({
            "serving_fleet_tokens_per_sec": fleet["tokens_per_sec"],
            "serving_fleet_single_tokens_per_sec":
                single["tokens_per_sec"],
            "serving_fleet_speedup": round(speedup, 3),
            "serving_fleet_requests_ok": fleet["requests_ok"],
            "serving_fleet_requests_failed": fleet["requests_failed"],
            "serving_fleet_e2e_ms_p99": fleet["e2e_ms_p99"],
            "serving_fleet_balance_ratio": round(balance, 3),
            "serving_fleet_tokens_by_engine": tok_by_engine,
            "serving_fleet_prefix_remote_hits": remote_hits,
            "serving_fleet_prefix_published_pages": published,
        })
        # per-engine tails from the engine-labeled metrics JSONL (the
        # ISSUE 14 metrics-identity satellite end to end)
        rep = obsrep.build_run_report(obsrep.read_rank_snapshots(md))
        for eng, row in sorted((rep.get("serving") or {}).items()):
            if eng == "-":
                continue
            if row.get("ttft_ms_p99") is not None:
                sub[f"serving_fleet_{eng}_ttft_ms_p99"] = round(
                    row["ttft_ms_p99"], 2)
            if row.get("itl_ms_p99") is not None:
                sub[f"serving_fleet_{eng}_itl_ms_p99"] = round(
                    row["itl_ms_p99"], 2)
        # phase-attributed latency breakdown (ISSUE 20): the same
        # boundaries the request trace stamps, aggregated per engine —
        # rides ALONGSIDE the legacy ttft/itl keys, never replaces them
        for eng, phrow in sorted((rep.get("serving_phases")
                                  or {}).items()):
            if eng == "-":
                continue
            for phase, st in sorted(phrow.items()):
                if st.get("p99_ms") is not None:
                    sub[f"serving_fleet_{eng}_phase_{phase}"
                        "_ms_p99"] = round(st["p99_ms"], 2)
        ok = (fleet["requests_failed"] == 0
              and single["requests_failed"] == 0
              and remote_hits > 0
              and balance > 0
              and (speedup_gate is None or speedup >= speedup_gate))
        sub["serving_fleet_leg_ok"] = bool(ok)
        return sub, ok
    finally:
        for w in workers:
            if w.poll() is None:
                w.kill()
        shutil.rmtree(md, ignore_errors=True)


def run_disagg_serving_bench():
    """Disaggregation twin (ISSUE 14 tentpole (c)): one prefill-designated
    and one decode-designated engine behind the router — every completed
    prefill migrates its KV pages to the decode engine — vs the
    single-engine baseline on the same seeded session workload.
    Token-identical greedy parity asserted on a deterministic ordered
    pass; the Poisson pass records the throughput twin."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.serving import ServingEngine, run_poisson_load
    from paddle_tpu.serving.fleet import FleetRouter

    device, cfg, kb = _serving_cfg_and_knobs()
    prompts, _sids, new_tokens = _fleet_workload(cfg, kb)

    def build(engine_id):
        paddle.seed(0)
        m = GPTForCausalLM(cfg)
        m.eval()
        return ServingEngine(m, page_size=kb["page"],
                             num_pages=kb["pool"],
                             max_slots=kb["slots"],
                             prefill_chunk=kb["chunk"],
                             engine_id=engine_id)

    # deterministic ordered parity pass: single engine vs disagg pair
    single_eng = build("solo")
    reqs = [single_eng.submit(p, max_new_tokens=new_tokens,
                              timeout=600.0) for p in prompts[:8]]
    single_eng.run_until_idle()
    base_tokens = [r.result(60) for r in reqs]
    single_eng.close()

    pf, dc = build("pf"), build("dc")
    router = FleetRouter()
    router.add_engine(pf, "pf", role="prefill")
    router.add_engine(dc, "dc", role="decode")
    frs = [router.submit(p, max_new_tokens=new_tokens, timeout=600.0)
           for p in prompts[:8]]
    deadline = time.time() + 300
    while any(not f.done() for f in frs) and time.time() < deadline:
        pf.step()
        dc.step()
    disagg_tokens = [f.result(60) for f in frs]
    parity = disagg_tokens == base_tokens
    migrations = router.migrations

    # throughput twin under the open-loop driver (serve threads on)
    router.start()
    res = run_poisson_load(router, qps=kb["qps"] * 12, prompts=prompts,
                           max_new_tokens=new_tokens, seed=19,
                           timeout=600.0, by_engine=True)
    stats = router.stats()
    router.close()
    sub = {
        "serving_disagg_tokens_per_sec": res["tokens_per_sec"],
        "serving_disagg_requests_failed": res["requests_failed"],
        "serving_disagg_migrations": stats["migrations"],
        "serving_disagg_parity_ok": bool(parity),
    }
    ok = (parity and migrations > 0 and res["requests_failed"] == 0
          and stats["migrations"] > migrations)
    sub["serving_disagg_leg_ok"] = bool(ok)
    return sub, ok


def _fleet_builder(cfg, kb):
    """Engine factory every elastic leg shares: identical weights per
    engine (per-engine re-seed — a fleet's replicas serve ONE model), so
    re-dispatch/hedge continuations are greedy-token-identical."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.serving import ServingEngine

    def build(engine_id):
        paddle.seed(0)
        m = GPTForCausalLM(cfg)
        m.eval()
        return ServingEngine(m, page_size=kb["page"],
                             num_pages=kb["pool"],
                             max_slots=kb["slots"],
                             prefill_chunk=kb["chunk"],
                             engine_id=engine_id)
    return build


def run_slo_autoscale_bench():
    """SLO leg (ISSUE 16 tentpole (b)): a Poisson-shaped burst hits a
    one-engine fleet; the autoscaler's queue-depth loop admits a warm
    spare mid-burst — records ``serving_scaleup_to_first_token_s`` (time
    from the spare entering rotation to its first served token) — then a
    graceful ``remove_engine(migrate=True)`` drain must finish every
    in-flight request: ``serving_drain_errors`` gates at zero."""
    from paddle_tpu.serving.fleet import EngineAutoscaler, FleetRouter

    device, cfg, kb = _serving_cfg_and_knobs()
    prompts, _sids, new_tokens = _fleet_workload(cfg, kb)
    build = _fleet_builder(cfg, kb)

    router = FleetRouter()
    router.add_engine(build("e0"), "e0")
    router.engine("e0").warm_ragged()
    router.start()
    scaler = EngineAutoscaler(router, build, min_engines=1,
                              max_engines=3, queue_high=2.0,
                              queue_low=0.25, up_ticks=1, down_ticks=10,
                              cooldown_s=2.0)
    sub = {}
    try:
        frs = []
        t_up = None
        new_id = None

        def _note(act):
            nonlocal t_up, new_id
            if act == "up" and t_up is None:
                t_up = time.perf_counter()
                new_id = scaler.events[-1]["engine"]

        # open burst: 3 sessions' worth arrives faster than one engine
        # drains, so the blended queue signal crosses queue_high
        for i in range(24):
            frs.append(router.submit(prompts[i % len(prompts)],
                                     max_new_tokens=new_tokens,
                                     timeout=600.0))
            if i % 4 == 3:
                _note(scaler.tick())
        deadline = time.time() + 240
        while any(not f.done() for f in frs) and time.time() < deadline:
            _note(scaler.tick())
            time.sleep(0.05)
        burst_failed = sum(1 for f in frs
                           if not f.done() or f.error is not None)
        stf = None
        if t_up is not None:
            served = [f.t_first_token - t_up for f in frs
                      if new_id in f.engine_ids
                      and f.t_first_token is not None
                      and f.t_first_token >= t_up]
            if served:
                stf = min(served)
        # graceful drain: trickle traffic in flight while the spare
        # leaves rotation — migration (recompute fallback built in)
        # must land every request, with zero user-visible errors
        tail = [router.submit(prompts[i % len(prompts)],
                              max_new_tokens=new_tokens, timeout=600.0)
                for i in range(6)]
        if new_id is not None and new_id in router.handles():
            router.remove_engine(new_id, migrate=True)
            router.drop_engine(new_id)
        deadline = time.time() + 120
        while any(not f.done() for f in tail) and time.time() < deadline:
            time.sleep(0.02)
        drain_errors = sum(1 for f in tail
                           if not f.done() or f.error is not None)
        sub.update({
            "serving_scaleup_to_first_token_s":
                round(stf, 4) if stf is not None else None,
            "serving_drain_errors": drain_errors + burst_failed,
            "serving_autoscale_events": len(scaler.events),
            "serving_autoscale_engine_added": new_id,
        })
        ok = (t_up is not None and stf is not None
              and burst_failed == 0 and drain_errors == 0)
        sub["serving_slo_leg_ok"] = bool(ok)
        return sub, ok
    finally:
        scaler.close()
        router.close()


def run_serving_chaos_bench():
    """Chaos twin (ISSUE 16 tentpole (d)): ``engine_die@serve_loop``
    kills one of two engines mid-burst. The tracked request pinned to
    the dying engine must re-dispatch and finish TOKEN-IDENTICAL to a
    solo baseline; the autoscaler must strike the dead engine into
    quarantine and admit a replacement (death -> strike -> re-dispatch
    -> scale-up, the full injectable lifecycle)."""
    from paddle_tpu.distributed import fault as _fault
    from paddle_tpu.serving.fleet import EngineAutoscaler, FleetRouter

    device, cfg, kb = _serving_cfg_and_knobs()
    prompts, _sids, new_tokens = _fleet_workload(cfg, kb)
    build = _fleet_builder(cfg, kb)

    solo = build("solo")
    tracked_prompt = prompts[0]
    base = solo.generate(tracked_prompt, max_new_tokens=new_tokens)
    solo.close()

    router = FleetRouter()
    router.add_engine(build("e0"), "e0")
    router.add_engine(build("e1"), "e1")
    for eid in ("e0", "e1"):
        router.engine(eid).warm_ragged()
    scaler = EngineAutoscaler(router, build, min_engines=2,
                              max_engines=3, queue_high=1e9,
                              queue_low=-1.0)  # lifecycle only, no SLO
    sub = {}
    os.environ["PADDLE_TPU_FAULT_ENGINE"] = "e0"
    try:
        router.start()
        tracked = router.submit(tracked_prompt,
                                max_new_tokens=new_tokens,
                                timeout=600.0, engine="e0")
        burst = [router.submit(prompts[(i % (len(prompts) - 1)) + 1],
                               max_new_tokens=new_tokens, timeout=600.0)
                 for i in range(10)]
        # arm the kill only once the tracked request is mid-decode, so
        # the re-dispatch genuinely carries emitted tokens across
        deadline = time.time() + 60
        while len(tracked.generated) < 2 and not tracked.done() \
                and time.time() < deadline:
            time.sleep(0.005)
        _fault.set_fault_spec("engine_die@serve_loop:2")
        all_reqs = [tracked] + burst
        deadline = time.time() + 240
        while (any(not f.done() for f in all_reqs)
               or len(router.handles()) < 2) \
                and time.time() < deadline:
            scaler.tick()
            time.sleep(0.05)
        parity = (tracked.done() and tracked.error is None
                  and list(tracked.generated) == list(base))
        failed = sum(1 for f in all_reqs
                     if not f.done() or f.error is not None)
        struck = scaler.quarantine.quarantined()
        live = [eid for eid, h in router.handles().items()
                if h.healthy()]
        sub.update({
            "serving_chaos_parity_ok": bool(parity),
            "serving_chaos_redispatches": router.redispatched,
            "serving_chaos_requests_failed": failed,
            "serving_chaos_quarantined": struck,
            "serving_chaos_fleet_live": len(live),
            "serving_chaos_replacement":
                scaler.events[-1]["engine"] if scaler.events else None,
        })
        ok = (parity and failed == 0 and tracked.redispatches >= 1
              and "e0" in struck and len(live) >= 2)
        sub["serving_chaos_leg_ok"] = bool(ok)
        return sub, ok
    finally:
        _fault.set_fault_spec(None)
        os.environ.pop("PADDLE_TPU_FAULT_ENGINE", None)
        scaler.close()
        router.close()


def run_router_chaos_bench(n_engines=2):
    """Router-chaos twin (ISSUE 17 tentpole (c)): a PRIMARY front-door
    process armed with ``router_die@route`` SIGKILLs itself mid-burst
    over a 2-engine store-RPC fleet; the driver-side SHADOW watches the
    lease go stale, adopts the ledger (re-attaching live legs off the
    persisted cursors, re-dispatching orphans), and every request must
    complete EXACTLY ONCE — zero client-visible errors, zero duplicated
    or lost tokens, greedy token-identical to an unchaosed solo twin.
    Records ``serving_router_failover_s`` (router death to adoption
    complete) and ``serving_router_requests_replayed``, and exercises
    the deposed-router fence (a revived primary's term is stale: its
    next dispatch raises instead of split-braining)."""
    import subprocess
    import threading as _threading

    from paddle_tpu.distributed.tcp_store import TCPStore
    from paddle_tpu.serving.fleet import (EngineRegistry, FleetRouter,
                                          RemoteEngineHandle,
                                          RequestLedger, RouterClient,
                                          RouterDeposedError,
                                          RouterLease)
    from paddle_tpu.serving.fleet.frontdoor import serve_router

    repo = os.path.dirname(os.path.abspath(__file__))
    device, cfg, kb = _serving_cfg_and_knobs()
    prompts, _sids, new_tokens = _fleet_workload(cfg, kb)
    n_req = 12
    die_at = 6           # SIGKILL at the 6th routed request (mid-burst)

    # unchaosed twin: the parity oracle for every chaos request
    build = _fleet_builder(cfg, kb)
    solo = build("solo")
    base = [solo.generate(prompts[i % len(prompts)],
                          max_new_tokens=new_tokens)
            for i in range(n_req)]
    solo.close()

    import socket as _socket
    with _socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    store_ep = f"127.0.0.1:{port}"
    master = TCPStore("127.0.0.1", port, is_master=True)
    env = _chaos_child_env(repo)
    workers, primary = [], None
    sub = {}
    serve_thread = None
    shadow = None
    try:
        for i in range(n_engines):
            workers.append(subprocess.Popen(
                [sys.executable, "-m",
                 "paddle_tpu.serving.fleet.remote",
                 "--store", store_ep, "--engine-id", f"e{i}",
                 "--job", "bench", "--seed", "0",
                 "--vocab", str(cfg.vocab_size),
                 "--hidden", str(cfg.hidden_size),
                 "--layers", str(cfg.num_layers),
                 "--heads", str(cfg.num_heads),
                 "--seq", str(cfg.max_seq_len),
                 "--page", str(kb["page"]), "--pool", str(kb["pool"]),
                 "--slots", str(kb["slots"]),
                 "--chunk", str(kb["chunk"])],
                env=env, cwd=repo, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True))
        reg = EngineRegistry(TCPStore("127.0.0.1", port), job="bench")
        deadline = time.time() + 300
        while len(reg.engines()) < n_engines:
            if time.time() > deadline or any(
                    w.poll() is not None for w in workers):
                tails = [w.communicate()[0][-500:] for w in workers
                         if w.poll() is not None]
                raise RuntimeError(
                    f"fleet workers never registered: {tails}")
            time.sleep(0.5)

        penv = dict(env)
        penv["PADDLE_TPU_FAULTS"] = f"router_die@route:{die_at}"
        primary = subprocess.Popen(
            [sys.executable, "-m",
             "paddle_tpu.serving.fleet.frontdoor",
             "--store", store_ep, "--job", "bench",
             "--role", "primary", "--ttl", "1.0",
             "--engines", ",".join(f"e{i}" for i in range(n_engines))],
            env=penv, cwd=repo, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        plines = []
        _threading.Thread(
            target=lambda: plines.extend(primary.stdout),
            daemon=True).start()

        watch = RouterLease(TCPStore("127.0.0.1", port), job="bench",
                            ttl=1.0)
        deadline = time.time() + 120
        while watch.read() is None:
            if time.time() > deadline or primary.poll() is not None:
                raise RuntimeError(
                    f"primary router never leased: {plines[-5:]}")
            time.sleep(0.1)

        client = RouterClient(TCPStore("127.0.0.1", port), job="bench",
                              resubmit_after=2.0)
        rng = __import__("random").Random(23)
        for i in range(n_req):
            client.submit(f"req-{i}", prompts[i % len(prompts)],
                          max_new_tokens=new_tokens)
            time.sleep(rng.uniform(0.01, 0.06))  # Poisson-ish burst

        # shadow: wait for the lease to go stale (the primary SIGKILLs
        # itself at the die_at-th routed request), then adopt
        grace = 3.0
        deadline = time.time() + 240
        while True:
            if primary.poll() is not None:
                break
            if time.time() > deadline:
                raise RuntimeError(
                    f"primary never died: {plines[-5:]}")
            time.sleep(0.05)
        die_wall = None
        for ln in plines:
            if ln.startswith("ROUTER_DIE"):
                die_wall = float(ln.split()[1])
        while watch.stale_age() is None or watch.stale_age() < grace:
            time.sleep(0.1)

        t0 = time.monotonic()
        ledger = RequestLedger(TCPStore("127.0.0.1", port), job="bench")
        lease = RouterLease(TCPStore("127.0.0.1", port), job="bench",
                            ttl=1.0)
        term = lease.adopt()
        shadow = FleetRouter(ledger=ledger, lease=lease)
        for i in range(n_engines):
            # defer_poll: adoption must attach every inherited rid
            # BEFORE the history replay runs, or early stream records
            # are dropped (rid unknown) and tails double-fire
            shadow.add_engine(None, handle=RemoteEngineHandle(
                lambda: TCPStore("127.0.0.1", port), f"e{i}",
                job="bench",
                registry=EngineRegistry(TCPStore("127.0.0.1", port),
                                        job="bench"),
                defer_poll=True))
        adopted = shadow.adopt_from_ledger()
        for h in shadow.handles().values():
            h.start_polling()
        adopt_done_wall = time.time()
        failover_s = (adopt_done_wall - die_wall) \
            if die_wall is not None else time.monotonic() - t0
        serve_thread = _threading.Thread(
            target=lambda: serve_router(
                shadow, TCPStore("127.0.0.1", port), job="bench",
                idle_timeout=300.0),
            daemon=True)
        serve_thread.start()

        # every request completes exactly once: the streamed tokens the
        # client saw must equal the terminal record AND the solo twin
        results, streamed, failed = [], {}, 0
        for i in range(n_req):
            seen = streamed.setdefault(i, [])
            try:
                toks = client.result(f"req-{i}", timeout=240.0,
                                     on_token=lambda t, fin, s=seen:
                                     s.append(t))
            except Exception:
                toks, failed = None, failed + 1
            results.append(toks)
        exactly_once = all(
            results[i] is not None and streamed[i] == results[i]
            for i in range(n_req))
        parity = all(results[i] == base[i] for i in range(n_req))

        # terminal replay probe: resubmitting a finished id must answer
        # from the journal without touching an engine
        replay = shadow.submit(prompts[0], max_new_tokens=new_tokens,
                               request_id="req-0")
        replay_ok = (replay.done()
                     and list(replay.generated) == results[0])

        # deposed fence: a revived primary still holds the OLD term —
        # its next dispatch must refuse, not split-brain
        revived = RouterLease(TCPStore("127.0.0.1", port), job="bench",
                              ttl=1.0)
        revived.term = term - 1
        r2 = FleetRouter(ledger=ledger, lease=revived)
        fenced = False
        try:
            r2.submit(prompts[0], max_new_tokens=2, block=False,
                      request_id="fence-probe")
        except RouterDeposedError:
            fenced = True

        sub.update({
            "serving_router_failover_s": round(failover_s, 3),
            "serving_router_requests_replayed":
                shadow.requests_replayed,
            "serving_router_requests_adopted": adopted,
            "serving_router_requests_failed": failed,
            "serving_router_exactly_once_ok": bool(exactly_once),
            "serving_router_parity_ok": bool(parity),
            "serving_router_replay_ok": bool(replay_ok),
            "serving_router_fence_ok": bool(fenced),
            "serving_router_die_marker": die_wall is not None,
        })
        ok = (failed == 0 and exactly_once and parity and replay_ok
              and fenced and die_wall is not None
              and shadow.requests_replayed >= 1)
        sub["serving_router_leg_ok"] = bool(ok)
        return sub, ok
    finally:
        try:
            master.set("serving/bench/stop", b"1")
        except Exception:
            pass
        if serve_thread is not None:
            serve_thread.join(30)
        if shadow is not None:
            for h in shadow.handles().values():
                try:
                    h.detach()
                except Exception:
                    pass
        for w in workers + ([primary] if primary else []):
            if w.poll() is None:
                w.kill()


def _emit(sub, ok):
    """The one JSON line of a ``--serving*``/``--linalg`` run: this run's
    rows and nothing carried over. -> the process exit code."""
    print(json.dumps({"metric": "gpt_train_step_mfu", "value": 0.0,
                      "unit": "%", "vs_baseline": 0.0, "submetrics": sub}))
    return 0 if ok else 1


def main_serving_fleet():
    merged = {}
    try:
        sub, ok = run_fleet_serving_bench()
    except Exception as e:
        sub, ok = {"serving_fleet_error": repr(e)[-300:],
                   "serving_fleet_leg_ok": False}, False
    merged.update(sub)
    # the disagg twin fails independently: a broken migration path never
    # hides the fleet throughput rows (and vice versa)
    try:
        dsub, dok = run_disagg_serving_bench()
        merged.update(dsub)
        ok = ok and dok
    except Exception as e:
        merged.update({"serving_disagg_error": repr(e)[-300:],
                       "serving_disagg_leg_ok": False})
        ok = False
    # ISSUE 16 legs — each fails independently so a broken autoscaler
    # never hides the chaos lifecycle rows (or any prior leg's keys)
    try:
        ssub, sok = run_slo_autoscale_bench()
        merged.update(ssub)
        ok = ok and sok
    except Exception as e:
        merged.update({"serving_slo_error": repr(e)[-300:],
                       "serving_slo_leg_ok": False})
        ok = False
    try:
        csub, cok = run_serving_chaos_bench()
        merged.update(csub)
        ok = ok and cok
    except Exception as e:
        merged.update({"serving_chaos_error": repr(e)[-300:],
                       "serving_chaos_leg_ok": False})
        ok = False
    # ISSUE 17 router-chaos twin — independent like every other leg
    try:
        rsub, rok = run_router_chaos_bench()
        merged.update(rsub)
        ok = ok and rok
    except Exception as e:
        merged.update({"serving_router_error": repr(e)[-300:],
                       "serving_router_leg_ok": False})
        ok = False
    return _emit(merged, ok)


def main_linalg():
    """``--linalg``: distributed linear algebra rows (ISSUE 18) — the
    in-process SUMMA perf/parity leg plus the elastic-SIGKILL chaos
    twin."""
    sub = {}
    ok = _run_guarded_legs(sub, [("linalg", run_linalg_bench),
                                 ("linalg_chaos", run_linalg_chaos)])
    return _emit(sub, ok)


# name -> (leg fn, the ok-key _run_guarded_legs can't infer: the legs
# predate its <name>_ok convention and their keys are already on the
# wire in dashboards)
CHAOS_LEGS = (
    ("chaos", run_chaos_smoke, "chaos_resume_ok"),
    ("elastic", run_elastic_chaos, "elastic_scale_ok"),
    ("hang", run_hang_chaos, "hang_postmortem_ok"),
    ("node", run_node_chaos, "node_elastic_ok"),
    ("controlplane", run_controlplane_chaos, "controlplane_ok"),
    ("integrity", run_integrity_chaos, "integrity_ok"),
)


def main_chaos():
    # `bench.py --chaos <leg>[,<leg>...]` runs a subset (dev loop /
    # targeted CI re-runs); bare `--chaos` runs the full gauntlet
    sel = None
    argv = sys.argv[1:]
    if "--chaos" in argv:
        nxt = argv[argv.index("--chaos") + 1:]
        if nxt and not nxt[0].startswith("--"):
            sel = set(nxt[0].split(","))
            unknown = sel - {n for n, _, _ in CHAOS_LEGS}
            if unknown:
                _log("[bench] unknown chaos leg(s) %s (have: %s)" % (
                    sorted(unknown), [n for n, _, _ in CHAOS_LEGS]))
                return 2
    legs = [(n, fn) for n, fn, _ in CHAOS_LEGS
            if sel is None or n in sel]
    sub = {}
    ok = _run_guarded_legs(sub, legs)
    picked = {n for n, _ in legs}
    ok = ok and all(bool(sub.get(okkey))
                    for n, _, okkey in CHAOS_LEGS if n in picked)
    print(json.dumps({
        "metric": "chaos_recovery_s",
        "value": sub.get("chaos_recovery_s", 0.0),
        "unit": "s",
        "vs_baseline": 1.0 if ok else 0.0,
        "submetrics": sub,
    }))
    return 0 if ok else 1


def main():
    if "--chaos" in sys.argv:   # CPU children only: needs no chip
        sys.exit(main_chaos())
    _require_tpu()
    paddle.jit.use_compile_cache(_HERE)
    if "--serving-fleet" in sys.argv:
        sys.exit(main_serving_fleet())
    if "--linalg" in sys.argv:
        sys.exit(main_linalg())
    # telemetry registry as the single source of truth for the rows that
    # overlap with run telemetry (eager dispatch, comm overlap); the
    # registry snapshot is written out as the bench run report. Enabled
    # LAZILY — after the legacy eager-dispatch rows — so their
    # wall-clock trajectory keeps measuring the UNinstrumented dispatch
    # path (metrics-on adds two perf_counter calls + a histogram observe
    # per taped op).
    from paddle_tpu.observability import metrics as _obsm
    obsreg = None

    def _ensure_obsreg():
        nonlocal obsreg
        if obsreg is None:
            obsreg = _obsm.enable(out_dir=None, interval_s=0)
        return obsreg

    peak = _peak_flops()
    device = jax.devices()[0].device_kind
    _log(f"[bench] device={device} peak={peak/1e12:.0f} TFLOP/s")
    # every row below is measured by THIS run; nothing is carried over
    snap = {}
    sub = snap.setdefault("submetrics", {})
    sub["device"] = device
    sub["peak_flops_assumed"] = peak

    # Each sub-benchmark is individually guarded: a failed leg leaves every
    # other measurement on the wire, is named under "errors", and makes
    # the exit code non-zero.
    def guarded(label, fn):
        try:
            fn()
        except Exception as e:
            sub.setdefault("errors", {})[label] = \
                f"{type(e).__name__}: {e}"[:200]
            _log(f"[bench] {label} FAILED: {e}")

    def _matmul():
        mm_mfu, mm_t = bench_matmul(peak)
        sub["matmul_bf16_mfu_pct"] = round(mm_mfu, 1)
        sub["matmul_4096_ms"] = round(mm_t * 1e3, 3)
        _log(f"[bench] matmul done: {mm_mfu:.1f}% MFU")

    def _eager():
        eager_us = bench_eager_dispatch()
        sub["eager_dispatch_us_per_op"] = round(eager_us, 1)
        _log(f"[bench] eager dispatch done: {eager_us:.0f} us/op")

    def _eager_telemetry():
        # same loop with metrics ON: the per-op dispatch-latency
        # histogram (core/dispatch observes every taped op) is the
        # telemetry-sourced twin of the wall-clock row above — it
        # excludes the final device sync, so the two keys bracket the
        # dispatch cost. Runs AFTER every legacy eager row so enabling
        # the registry cannot inflate their trajectories.
        reg = _ensure_obsreg()
        h = reg.histogram("eager_dispatch_us")
        c0, s0 = h.count, h.sum
        eager_us = bench_eager_dispatch()
        c1, s1 = h.count, h.sum
        if c1 > c0:
            sub["eager_dispatch_us_per_op_telemetry"] = round(
                (s1 - s0) / (c1 - c0), 1)
            reg.gauge("bench.eager_dispatch_us_per_op").set(eager_us)
            _log(f"[bench] eager dispatch (telemetry hist): "
                 f"{sub['eager_dispatch_us_per_op_telemetry']:.0f} us/op "
                 f"over {c1 - c0} ops")
        else:
            _log("[bench] eager dispatch telemetry row: histogram saw "
                 "no ops (metrics gate did not resolve)")

    def _eager_chained():
        us = bench_eager_dispatch_chained()
        sub["eager_dispatch_chained_us_per_op"] = round(us, 1)
        _log(f"[bench] eager chained dispatch: {us:.0f} us/op")

    def _eager_host():
        us = bench_eager_dispatch_host()
        sub["eager_dispatch_host_us_per_op"] = round(us, 1)
        _log(f"[bench] eager host (CPU child) dispatch: {us:.0f} us/op")

    def _overlap():
        pct, comm_us, compute_us = bench_comm_overlap_cpu_mesh()
        # eight virtual CPU devices in a child: the single chip has no
        # collectives. The keys say so — these are not device metrics
        sub["cpu_mesh_dp8_comm_overlap_pct"] = pct
        sub["cpu_mesh_dp8_comm_us"] = comm_us
        sub["cpu_mesh_dp8_compute_us"] = compute_us
        _log(f"[bench] dp8 comm overlap: {pct:.1f}% "
             f"(comm {comm_us:.0f}us / compute {compute_us:.0f}us)")
        # same leg with the bucketed grad-sync engine attached: the
        # compiled step now carries per-bucket psums at grad-production
        # order — the schedule XLA's async-collective pass overlaps
        pct_b, comm_b, compute_b = bench_comm_overlap_cpu_mesh(
            overlap_engine=True)
        sub["cpu_mesh_dp8_comm_overlap_pct_bucketed"] = pct_b
        sub["cpu_mesh_dp8_comm_us_bucketed"] = comm_b
        _log(f"[bench] dp8 comm overlap (bucketed engine): {pct_b:.1f}% "
             f"(comm {comm_b:.0f}us / compute {compute_b:.0f}us)")

    def _overlap_inrun():
        # the in-run twin of the xplane rows above: the overlap engine's
        # own comm_overlap_pct gauge (flight-recorder issue/wait stamps
        # through the metrics registry — no trace collection) plus the
        # per-bucket collective p50/p99 next to the legacy keys
        row = bench_overlap_inrun()
        if row.get("overlap_pct") is not None:
            sub["cpu_mesh_dp8_comm_overlap_pct_inrun"] = round(row["overlap_pct"], 2)
        sub["cpu_mesh_dp8_bucket_collectives"] = row.get("bucket_collectives", 0)
        for b, r in sorted((row.get("buckets") or {}).items()):
            sub[f"cpu_mesh_dp8_bucket_allreduce_{b}_p50_us"] = r["p50_us"]
            sub[f"cpu_mesh_dp8_bucket_allreduce_{b}_p99_us"] = r["p99_us"]
        _log(f"[bench] dp8 in-run overlap: "
             f"{row.get('overlap_pct')}% over "
             f"{row.get('bucket_collectives')} bucket collectives "
             f"({len(row.get('buckets') or {})} buckets)")

    def _lenet():
        lenet_sps, lenet_t = bench_lenet(peak)
        sub["lenet_train_steps_per_sec"] = round(lenet_sps, 1)
        _log(f"[bench] lenet done: {lenet_sps:.1f} steps/s")

    def _fused():
        fa_ms, fa_jnp_ms = bench_fused_adamw()
        sub["fused_adamw_pallas_ms"] = round(fa_ms, 3)
        sub["fused_adamw_jnp_ms"] = round(fa_jnp_ms, 3)
        _log(f"[bench] fused adamw: pallas {fa_ms:.3f}ms vs jnp "
             f"{fa_jnp_ms:.3f}ms")

    def _rms():
        rn_ms, rn_jnp_ms = bench_rms_norm()
        sub["rms_norm_pallas_ms"] = round(rn_ms, 3)
        sub["rms_norm_jnp_ms"] = round(rn_jnp_ms, 3)
        _log(f"[bench] rms norm: pallas {rn_ms:.3f}ms vs jnp "
             f"{rn_jnp_ms:.3f}ms")

    def _ln():
        ln_ms, ln_jnp_ms = bench_layer_norm()
        sub["layer_norm_pallas_ms"] = round(ln_ms, 3)
        sub["layer_norm_jnp_ms"] = round(ln_jnp_ms, 3)
        _log(f"[bench] layer norm: pallas {ln_ms:.3f}ms vs jnp "
             f"{ln_jnp_ms:.3f}ms")

    def _kernels_ab():
        rows = bench_kernels_ab()
        for name, row in rows.items():
            sub[f"kernel_ab_{name}_backend"] = row["backend"]
            if row.get("xla_ms") is not None:
                sub[f"kernel_ab_{name}_xla_ms"] = row["xla_ms"]
            if row.get("pallas_ms") is not None:
                sub[f"kernel_ab_{name}_pallas_ms"] = row["pallas_ms"]
            sub[f"kernel_ab_{name}_gate"] = row["reason"]
            _log(f"[bench] kernel A/B {name}: {row['backend']} "
                 f"(xla {row.get('xla_ms')}ms / pallas "
                 f"{row.get('pallas_ms')}ms — {row['reason']})")

    def _gpt():
        gpt_mfu, gpt_t, tok_s, n_params = bench_gpt(peak)
        sub["gpt_step_ms"] = round(gpt_t * 1e3, 2)
        sub["gpt_tokens_per_sec"] = round(tok_s)
        sub["gpt_params"] = int(n_params)
        snap["metric"] = "gpt_train_step_mfu"
        snap["value"] = round(gpt_mfu, 2)
        snap["unit"] = "%"
        snap["vs_baseline"] = round(gpt_mfu / 45.0, 4)
        _log(f"[bench] gpt done: {gpt_mfu:.1f}% MFU")

    def _gpt_large():
        lg_mfu, lg_t, lg_params = bench_gpt_large(peak)
        sub["gpt_large_mfu_pct"] = round(lg_mfu, 2)
        sub["gpt_large_step_ms"] = round(lg_t * 1e3, 2)
        sub["gpt_large_params"] = int(lg_params)
        _log(f"[bench] gpt-large done: {lg_mfu:.1f}% MFU")

    def _gpt_large_o2():
        lg_mfu, lg_t, _ = bench_gpt_large(peak, amp_level="O2")
        sub["gpt_large_o2_mfu_pct"] = round(lg_mfu, 2)
        sub["gpt_large_o2_step_ms"] = round(lg_t * 1e3, 2)
        _log(f"[bench] gpt-large O2 done: {lg_mfu:.1f}% MFU")

    def _matmul_sweep():
        sweep = bench_matmul_sweep(peak)
        for k, v in sweep.items():
            sub[f"matmul_sweep_{k}_mfu_pct"] = v
        _log(f"[bench] matmul sweep: {sweep}")

    def _generate():
        tok_c, tok_e = bench_generate()
        sub["decode_tokens_per_sec"] = round(tok_c, 1)
        sub["decode_eager_tokens_per_sec"] = round(tok_e, 1)
        _log(f"[bench] generate done: compiled {tok_c:.1f} vs eager "
             f"{tok_e:.1f} tokens/s")

    guarded("matmul", _matmul)
    guarded("eager_dispatch", _eager)
    guarded("eager_dispatch_chained", _eager_chained)
    guarded("eager_dispatch_host", _eager_host)
    if not _FAST:
        guarded("comm_overlap", _overlap)
        guarded("comm_overlap_inrun", _overlap_inrun)
    guarded("lenet", _lenet)
    guarded("fused_adamw", _fused)
    guarded("rms_norm", _rms)
    guarded("layer_norm", _ln)
    # A/B gate rows BEFORE the gpt legs: a kernel that wins at these
    # exact shapes is promoted for the MFU measurements that follow;
    # a loser is demoted off their default path (auto mode)
    guarded("kernels_ab", _kernels_ab)
    guarded("gpt", _gpt)
    if not _FAST:
        guarded("matmul_sweep", _matmul_sweep)
        guarded("gpt_large", _gpt_large)
        guarded("gpt_large_o2", _gpt_large_o2)
        guarded("generate", _generate)
    def _fit_split():
        # metrics-on fit of the fused donated train step: the amortized
        # compute/sync split is this PR's before/after evidence (r05's
        # per-step blocking loss fetch showed up as the sync regression)
        _ensure_obsreg()
        rows = bench_fit_split(_FAST)
        sub.update(rows)
        _log(f"[bench] fit split: {rows}")

    # LAST on purpose: these are the first points the metrics registry is
    # enabled, so no legacy leg above ever runs with per-op dispatch
    # instrumentation active (eager decode in _generate included)
    guarded("fit_split", _fit_split)
    guarded("eager_dispatch_telemetry", _eager_telemetry)
    if "value" not in snap:
        snap.update(metric="gpt_train_step_mfu", value=0.0, unit="%",
                    vs_baseline=0.0)
    # bench run report: the telemetry registry's view of this run (eager
    # dispatch histogram, overlap gauge, cross-referenced bench rows),
    # written next to bench.py
    try:
        from paddle_tpu.observability import report as _obsrep
        reg = _ensure_obsreg()
        reg_snap = reg.snapshot()
        rep = _obsrep.build_run_report({reg.rank: [reg_snap]})
        rep["registry"] = reg_snap
        rep["bench"] = {k: sub[k] for k in (
            "eager_dispatch_us_per_op",
            "eager_dispatch_us_per_op_telemetry",
            "cpu_mesh_dp8_comm_overlap_pct",
            "cpu_mesh_dp8_comm_overlap_pct_bucketed",
            "cpu_mesh_dp8_comm_overlap_pct_inrun") if k in sub}
        # before/after step split for the perf round: the fused-step fit
        # split rows + the whole-step wall time next to each other
        rep["step_split"] = {k: sub[k] for k in sub
                             if k.startswith("gpt_fit_")
                             or k in ("gpt_step_ms", "gpt_tokens_per_sec",
                                      "lenet_train_steps_per_sec")}
        from paddle_tpu.ops.pallas._common import gate_report
        rep["kernel_gate"] = gate_report()
        rpath = os.path.join(_HERE, "BENCH_RUN_REPORT.json")
        with open(rpath, "w") as f:
            json.dump(rep, f, indent=1, default=str)
        _log(f"[bench] run report -> {rpath}")
    except Exception as e:
        _log(f"[bench] run report failed: {e}")
    print(json.dumps(snap))
    sys.exit(1 if sub.get("errors") else 0)


if __name__ == "__main__":
    main()
