"""Op micro-benchmark CLI (reference: tools/ci_op_benchmark.sh — clone op
benchmarks, time ops, diff against a baseline via
tools/check_op_benchmark_result.py; here self-contained).

    python -m paddle_tpu.tools.op_benchmark --op matmul \
        --shapes 512x512,512x512 --dtype float32 --repeat 50
    python -m paddle_tpu.tools.op_benchmark --op relu --shapes 1024 \
        --baseline old.json --threshold 0.05

Prints one JSON line per op; with --baseline, exits 1 when an op got
slower than the threshold (the CI gate semantics of
check_op_benchmark_result.py).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

__all__ = ["benchmark_op", "compare", "main"]


def _parse_shapes(spec):
    shapes = []
    for part in spec.split(","):
        part = part.strip()
        shapes.append([int(d) for d in part.split("x")] if part else [])
    return shapes


def benchmark_op(op_name, shapes, dtype="float32", repeat=50, warmup=5,
                 seed=0):
    """Time one eager op on the current device; returns a result dict."""
    import numpy as np

    import paddle_tpu as paddle
    fn = getattr(paddle, op_name, None)
    if fn is None:
        import paddle_tpu.nn.functional as F
        fn = getattr(F, op_name, None)
    if fn is None:
        raise SystemExit(f"unknown op '{op_name}' (looked in paddle.* "
                         "and paddle.nn.functional.*)")
    rng = np.random.RandomState(seed)
    # feed exactly the op's required positional arity (a unary op given
    # two --shapes must not receive a stray tensor as its name= kwarg)
    import inspect
    try:
        params = list(inspect.signature(fn).parameters.values())
        required = len([p for p in params
                        if p.default is inspect.Parameter.empty
                        and p.kind in (p.POSITIONAL_ONLY,
                                       p.POSITIONAL_OR_KEYWORD)])
        shapes = shapes[:max(required, 1)]
    except (TypeError, ValueError):
        pass
    args = [paddle.to_tensor(rng.rand(*s).astype(dtype) + 0.1)
            for s in shapes]
    for _ in range(warmup):
        out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn(*args)
    _sync(out)
    us = (time.perf_counter() - t0) / repeat * 1e6
    import jax
    return {"op": op_name, "shapes": shapes, "dtype": dtype,
            "repeat": repeat, "us_per_call": round(us, 2),
            "device": jax.devices()[0].device_kind}


def _sync(out):
    import jax
    t = out[0] if isinstance(out, (tuple, list)) else out
    jax.block_until_ready(t._data)


def compare(results, baseline, threshold=0.05):
    """Reference: tools/check_op_benchmark_result.py — report ops slower
    than baseline by more than threshold; returns the regressions."""
    base = {r["op"]: r for r in baseline}
    regressions = []
    for r in results:
        b = base.get(r["op"])
        if b is None:
            continue
        ratio = r["us_per_call"] / max(b["us_per_call"], 1e-9)
        if ratio > 1.0 + threshold:
            regressions.append({"op": r["op"], "ratio": round(ratio, 3),
                                "now_us": r["us_per_call"],
                                "baseline_us": b["us_per_call"]})
    return regressions


def main(argv=None):
    ap = argparse.ArgumentParser(prog="paddle_tpu.tools.op_benchmark")
    ap.add_argument("--op", action="append", required=True,
                    help="op name (repeatable)")
    ap.add_argument("--shapes", default="256x256",
                    help="comma-separated DxD shapes, one per op input")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--repeat", type=int, default=50)
    ap.add_argument("--baseline", default=None,
                    help="json file of prior results to diff against")
    ap.add_argument("--threshold", type=float, default=0.05)
    ap.add_argument("--out", default=None, help="write results json here")
    args = ap.parse_args(argv)

    shapes = _parse_shapes(args.shapes)
    results = [benchmark_op(op, shapes, args.dtype, args.repeat)
               for op in args.op]
    for r in results:
        print(json.dumps(r))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f)
    if args.baseline:
        with open(args.baseline) as f:
            regs = compare(results, json.load(f), args.threshold)
        if regs:
            print(json.dumps({"regressions": regs}), file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
