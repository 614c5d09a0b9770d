"""Shared Pallas kernel scaffolding + the kernel demotion gate.

**The standing kernel rule (ROADMAP item 1):** a Pallas kernel serves on
the default path only where it MEASURABLY beats its XLA counterpart at the
exact shape, on the real chip. BENCH_r05 showed all three fused kernels
(AdamW, rms_norm, layer_norm) losing to plain jnp/XLA on the v5e — the
gate gives every kernel the serving attention's A/B mechanism:

* ``PADDLE_TPU_KERNELS=xla|pallas|auto`` (default ``auto``) — ``xla``
  demotes every kernel, ``pallas`` forces every eligible kernel (still
  TPU-only; interpret mode is an emulator, not a measurement), ``auto``
  consults the verdict cache.
* :func:`ab_gate` times the jitted XLA reference against the Pallas kernel
  at one exact shape and caches the verdict per ``(kernel, shape sig)``.
  ``bench.py``'s kernels leg (and the serving engine at startup) run it
  eagerly and record one A/B row per kernel in the snapshot JSON.
* :func:`pallas_default` is the cheap per-call-site query: under ``auto``
  with no measured verdict it answers **False** — unmeasured kernels are
  demoted, never promoted on faith. Measurement never happens implicitly
  inside user code or under tracing (you cannot time a tracer).

Verdicts are process-local by default; :func:`nearest_verdict` lets size-
polymorphic callers (the fused optimizer sweeping many param shapes) reuse
a same-dtype/same-rank verdict within a 4x size band. Set
``PADDLE_TPU_KERNELS_CACHE=<path>`` to persist verdicts ACROSS processes
as JSON (PR-7 follow-up c): the file is loaded lazily on the first verdict
query (in-memory measurements win over file rows), merged and atomically
re-saved on every :func:`record_verdict` — so a bench run warms the cache
and later user jobs start with measured verdicts instead of the
demote-unproven default.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

__all__ = ["pad_rows_to_grid", "kernels_mode", "on_tpu", "shape_sig",
           "pallas_default", "ab_gate", "record_verdict", "get_verdict",
           "nearest_verdict", "gate_report", "save_verdicts",
           "KERNELS_ENV", "KERNELS_CACHE_ENV"]

KERNELS_ENV = "PADDLE_TPU_KERNELS"
KERNELS_CACHE_ENV = "PADDLE_TPU_KERNELS_CACHE"
_MODES = ("xla", "pallas", "auto")

# (kernel name, shape sig) -> {"backend", "xla_ms", "pallas_ms", "reason"}
_verdicts: dict = {}
_cache_loaded = False
# (kernel, sig) pairs whose on-TPU Pallas failure was already printed
_failures_logged: set = set()

# auto-mode behavior when NO verdict (exact or nearest) exists for a shape.
# flash_attention is the incumbent winner (it carried the MFU headline
# before the gate existed and was never among BENCH_r05's losers), so an
# unmeasured process keeps serving it — demotion needs a measured LOSS.
# The kernels BENCH_r05 caught losing on-chip (fused AdamW, rms_norm,
# layer_norm) plus paged_attention (the serving engine measures at
# startup anyway) stay demoted until a measured win promotes them.
_UNMEASURED_DEFAULT = {"flash_attention": True}


def _reset_state():
    """Drop every cached A/B verdict (tests) and forget whether the
    persistent cache file was loaded."""
    global _cache_loaded
    _verdicts.clear()
    _failures_logged.clear()
    _cache_loaded = False


# ----------------------------------------------- cross-process persistence

def _sig_to_json(sig):
    return [[list(s), d] for s, d in sig]


def _sig_from_json(j):
    return tuple((tuple(int(x) for x in s), str(d)) for s, d in j)


def _load_cache():
    """Lazy one-shot load of ``PADDLE_TPU_KERNELS_CACHE``. File rows never
    override verdicts measured in THIS process (fresher hardware truth)."""
    global _cache_loaded
    if _cache_loaded:
        return
    _cache_loaded = True
    path = os.environ.get(KERNELS_CACHE_ENV)
    if not path or not os.path.exists(path):
        return
    try:
        with open(path) as f:
            rows = json.load(f)
        for r in rows:
            _verdicts.setdefault((r["kernel"], _sig_from_json(r["sig"])),
                                 r["row"])
    except Exception as e:
        import sys
        print(f"[kernels] {KERNELS_CACHE_ENV}={path}: load failed "
              f"({type(e).__name__}: {e}); starting from empty verdicts",
              file=sys.stderr, flush=True)


def save_verdicts(path=None):
    """Merge the in-memory verdicts into the cache file and atomically
    replace it (tmp + ``os.replace`` — a concurrent reader never sees a
    torn file). Rows already on disk for other shapes survive. Returns
    the path, or None when no cache is configured.

    Called on every :func:`record_verdict` by design: verdicts arrive
    only from explicit measurement (a bench leg, serving startup —
    dozens per process at most, never a hot loop), the file is KB-scale,
    and saving immediately means a crash mid-sweep keeps everything
    measured so far."""
    path = path or os.environ.get(KERNELS_CACHE_ENV)
    if not path:
        return None
    merged: dict = {}
    try:
        if os.path.exists(path):
            with open(path) as f:
                for r in json.load(f):
                    merged[(r["kernel"], _sig_from_json(r["sig"]))] = \
                        r["row"]
    except Exception:
        pass  # a corrupt file is replaced wholesale
    merged.update(_verdicts)
    rows = [{"kernel": k, "sig": _sig_to_json(s), "row": row}
            for (k, s), row in sorted(merged.items(), key=str)]
    try:
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(rows, f, indent=1)
        os.replace(tmp, path)
    except Exception as e:
        import sys
        print(f"[kernels] {KERNELS_CACHE_ENV}={path}: save failed "
              f"({type(e).__name__}: {e})", file=sys.stderr, flush=True)
        return None
    return path


def kernels_mode() -> str:
    """Resolve the global kernel-selection knob."""
    mode = (os.environ.get(KERNELS_ENV) or "auto").lower()
    if mode not in _MODES:
        raise ValueError(
            f"{KERNELS_ENV}={mode!r}: pick from {_MODES}")
    return mode


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def shape_sig(*arrays):
    """Exact-shape signature: ((shape, dtype), ...) over the operands that
    determine the kernel's grid. Works on tracers (shape/dtype are
    static)."""
    return tuple((tuple(a.shape), str(a.dtype)) for a in arrays)


def get_verdict(kernel, sig):
    _load_cache()
    return _verdicts.get((kernel, sig))


def record_verdict(kernel, sig, row):
    _load_cache()
    _verdicts[(kernel, sig)] = row
    if os.environ.get(KERNELS_CACHE_ENV):
        save_verdicts()


def nearest_verdict(kernel, sig, size_band=4.0):
    """A measured verdict for the same kernel whose leading operand has the
    same dtype and a total size within ``size_band``x — the fused optimizer
    sweeps param shapes and re-timing every one would cost more than it
    saves. Rank is deliberately NOT matched: the elementwise/row-tiled
    kernels care about total element count (bench measures fused AdamW on
    a flat 8M vector, real params are 2-D; norm call sites see [B, S, H]
    activations against a 2-D bench verdict)."""
    _load_cache()
    if not sig:
        return None
    want_shape, want_dtype = sig[0]
    want_size = 1
    for d in want_shape:
        want_size *= max(int(d), 1)
    best = None
    for (k, s), row in _verdicts.items():
        if k != kernel or not s:
            continue
        shape, dtype = s[0]
        if dtype != want_dtype:
            continue
        size = 1
        for d in shape:
            size *= max(int(d), 1)
        ratio = size / want_size if want_size else float("inf")
        if 1.0 / size_band <= ratio <= size_band:
            if best is None or abs(ratio - 1.0) < best[0]:
                best = (abs(ratio - 1.0), row)
    return best[1] if best else None


def pallas_default(kernel, sig, allow_nearest=False):
    """Should this call site take the Pallas path? ``xla`` → never;
    ``pallas`` → always (the caller still owns TPU-eligibility);
    ``auto`` → a measured win at this (or, optionally, a nearby) shape,
    falling back to the kernel's unmeasured default (incumbent winners
    keep serving; measured losers and unproven kernels demote). One env
    read + one dict lookup on the no-verdict path."""
    mode = kernels_mode()
    if mode == "pallas":
        return True
    if mode == "xla":
        return False
    _load_cache()
    row = _verdicts.get((kernel, sig))
    if row is None and allow_nearest:
        row = nearest_verdict(kernel, sig)
    if row is None:
        return _UNMEASURED_DEFAULT.get(kernel, False)
    return row.get("backend") == "pallas"


def _time_jitted(fn, args, repeats):
    out = fn(*args)           # compile + warm
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e3


def ab_gate(kernel, xla_fn, pallas_fn, args, repeats=10, record=True,
            sig=None):
    """Time the jitted XLA reference vs the Pallas kernel at this exact
    shape and cache the verdict. Off-TPU the Pallas leg is skipped
    (interpret mode measures the emulator, not the chip) and XLA wins by
    default. A Pallas leg that raises ON the TPU is not a measured loss:
    the row carries ``"failed": True``, the error goes to stderr once per
    (kernel, shape), and XLA serves — callers that must not run degraded
    (``chip_smoke.py``) check the flag in :func:`gate_report`.
    ``sig`` overrides the recorded signature — it must match what the
    kernel's call site queries (e.g. flash attention gates on (q, k)
    while the timing needs (q, k, v)).
    -> ``{"backend", "xla_ms", "pallas_ms", "reason"[, "failed"]}``."""
    for a in args:
        if isinstance(a, jax.core.Tracer):
            raise RuntimeError(
                f"ab_gate({kernel!r}) needs concrete operands — it cannot "
                "time a tracer; run it eagerly (bench kernels leg, serving "
                "warmup) before compiling the consumer")
    if sig is None:
        sig = shape_sig(*args)
    mode = kernels_mode()
    row = {"backend": "xla", "xla_ms": None, "pallas_ms": None,
           "reason": "xla reference"}
    if mode in ("xla", "pallas"):
        row["backend"] = mode
        row["reason"] = f"forced by {KERNELS_ENV}={mode}"
        # NOT recorded: a forced row is policy, not a measurement — if it
        # entered the verdict cache, flipping the env back to auto in the
        # same process would serve an untimed kernel as if it had won
        return row
    xla_ms = _time_jitted(jax.jit(xla_fn), args, repeats)
    row["xla_ms"] = round(xla_ms, 4)
    if not on_tpu():
        row["reason"] = "pallas requires TPU (interpret-only here)"
        if record:
            record_verdict(kernel, sig, row)
        return row
    try:
        pallas_ms = _time_jitted(jax.jit(pallas_fn), args, repeats)
    except Exception as e:
        row["failed"] = True
        row["reason"] = f"pallas FAILED on tpu: {type(e).__name__}: {e}"[:160]
        if (kernel, sig) not in _failures_logged:
            _failures_logged.add((kernel, sig))
            import sys
            print(f"[kernels] PALLAS KERNEL FAILED ON TPU — {kernel} at "
                  f"{sig}: {type(e).__name__}: {e}\n[kernels] serving the "
                  "XLA reference instead; this is a defect, not an A/B "
                  "verdict", file=sys.stderr, flush=True)
        if record:
            record_verdict(kernel, sig, row)
        return row
    row["pallas_ms"] = round(pallas_ms, 4)
    if pallas_ms < xla_ms:
        row["backend"] = "pallas"
        row["reason"] = "pallas beat xla at this shape"
    else:
        row["reason"] = "xla beat pallas at this shape"
    if record:
        record_verdict(kernel, sig, row)
    return row


def gate_report():
    """Every cached verdict, keyed ``kernel[shapes]`` — the bench snapshot
    embeds this so each round records which kernels were demoted where."""
    _load_cache()
    out = {}
    for (kernel, sig), row in sorted(_verdicts.items(), key=str):
        label = ",".join("x".join(map(str, s)) + f":{d}" for s, d in sig)
        out[f"{kernel}[{label}]"] = row
    return out


def pad_rows_to_grid(x2, block_rows):
    """Pad a [R, H] operand so R divides the row-block size.

    Row-tiled kernels must not fall back to one giant [R, H] block when R
    is not divisible (a single block must fit VMEM, ~16 MB); padding the
    grid and slicing the output back is the safe general form. Returns
    (padded, R, br): the original row count and the block size to use.
    """
    R, H = x2.shape
    br = min(block_rows, R)
    pad = (-R) % br
    if pad:
        x2 = jnp.concatenate([x2, jnp.zeros((pad, H), x2.dtype)], axis=0)
    return x2, R, br
