"""host-sync (HS) — blocking device→host fetches on designated hot paths.

The perf arc (PR 7-9) bought the hot paths their throughput precisely by
REMOVING host syncs: one dict lookup + one pjit call per taped op, bucketed
collectives awaited only at backward end, `loss_fetch_every`-amortized loss
fetches, host-side sampling batched per decode round.  A stray ``.numpy()``/
``.item()``/``block_until_ready``/``device_get`` on one of these paths
re-serializes host and device and silently costs ~1 ms per occurrence.

Designation: the built-in :data:`HOT_PATHS` table (paths relative to the
package root, optionally narrowed to function qualnames) plus an in-file
``# tpu-lint: hot-path`` marker for new hot files.  Deliberate syncs (the
amortized flush, the designed sampling fetch) carry suppressions with
reasons — that is the documentation of WHY the sync is allowed.
"""
from __future__ import annotations

import ast

from .engine import Finding, dotted, enclosing_function, terminal_name

FAMILY = "host-sync"

RULES = {
    "HS001": ("error", "blocking fetch on a designated hot path"),
    "HS002": ("warning", "potential host transfer on a designated hot path"),
}

# path (relative to the paddle_tpu package root) -> None for the whole file,
# or a set of function qualnames (the hot region within the file)
HOT_PATHS = {
    "core/dispatch.py": None,
    "serving/scheduler.py": {
        "ContinuousBatchingScheduler.schedule",
        "ContinuousBatchingScheduler.ensure_decode_capacity",
        "ContinuousBatchingScheduler.complete_step",
        # request-trace hook sites (ISSUE 20): stamped inside the
        # scheduling/finish path, so they must never block or transfer
        "ContinuousBatchingScheduler._trace_admit",
        "ContinuousBatchingScheduler._evict",
        "ContinuousBatchingScheduler.readmit",
        "GenerationRequest.finish",
        "GenerationRequest._trace_terminal",
    },
    "serving/engine.py": {
        "ServingEngine.step",
        "ServingEngine._step_ragged",
        "ServingEngine._serve_loop",
        "ServingEngine.snapshot_kv",
        "ServingEngine.adopt_request",
        "ServingEngine._finish_prompt",
    },
    # request-trace buffer feeds (ISSUE 20): called from the scheduler
    # round, the serve loop and the router dispatch path
    "observability/tracing.py": {
        "TraceBuffer.add",
        "TraceBuffer.req_add",
        "TraceBuffer.req_finish",
        "req_event",
        "finish_request",
        "mint_context",
    },
    # fleet migration path (router.py designates itself whole-file via
    # the in-file hot-path marker)
    "serving/fleet/disagg.py": {
        "migrate_request",
        "drain_active",
    },
    "distributed/overlap.py": {
        "BucketedGradSync.on_grad_ready",
        "BucketedGradSync.on_backward_begin",
        "BucketedGradSync.on_backward_end",
        "BucketedGradSync._fire",
    },
    # integrity guard per-step hooks (ISSUE 19): run inside the guarded
    # fit loop / backward walk, so any blocking fetch is step latency
    "distributed/integrity.py": {
        "TrainingGuard.observe_loss",
        "TrainingGuard.maybe_poison",
        "GradFingerprints.on_bucket",
        "GradFingerprints.verify",
    },
    "jit/api.py": {
        "StaticFunction.__call__",
        "StaticFunction._exec_whole_step",
    },
}

_BLOCKING = {"numpy", "item", "block_until_ready", "device_get"}
_TRANSFER_CHAINS = {"np.asarray", "numpy.asarray", "np.array", "numpy.array"}


def _hot_qualnames(ctx):
    """None = file not designated; set() = whole file; else the qualnames."""
    spec = HOT_PATHS.get(ctx.pkg_relpath) if ctx.pkg_relpath else None
    if ctx.hot_file:
        return set()
    if ctx.pkg_relpath in HOT_PATHS:
        return set() if spec is None else set(spec)
    return None


def _in_hot_region(ctx, node, hot) -> str:
    """The hot qualname covering ``node``, or "" when outside."""
    fn = enclosing_function(node)
    if not hot:  # whole file designated
        while fn is not None and isinstance(fn, ast.Lambda):
            fn = enclosing_function(fn)
        return ctx.qualnames.get(fn, "<module>") if fn is not None \
            else "<module>"
    while fn is not None:
        q = ctx.qualnames.get(fn)
        if q is not None and q in hot:
            return q
        fn = enclosing_function(fn)
    return ""


def run(ctx):
    hot = _hot_qualnames(ctx)
    if hot is None:
        return []
    findings = []
    for node in ctx.nodes:
        if not isinstance(node, ast.Call):
            continue
        t = terminal_name(node.func)
        chain = dotted(node.func)
        rule = None
        if t in _BLOCKING:
            rule, sev = "HS001", "error"
            what = f"`{chain or t}()`" if chain else f"`.{t}()`"
            msg = (f"blocking fetch {what} on hot path '%s' — serializes "
                   "host and device on the per-step path")
            hint = ("amortize it (loss_fetch_every pattern), batch it per "
                    "round, or move it off the hot path; if this sync IS "
                    "the designed completion point, suppress with the "
                    "reason")
        elif chain in _TRANSFER_CHAINS:
            rule, sev = "HS002", "warning"
            msg = (f"`{chain}(...)` on hot path '%s' — a device operand "
                   "makes this a blocking device→host copy")
            hint = ("keep device values on device; if the operand is "
                    "host-only numpy, suppress with that reason")
        if rule is None:
            continue
        region = _in_hot_region(ctx, node, hot)
        if not region:
            continue
        findings.append(Finding(
            file=ctx.relpath, line=node.lineno, col=node.col_offset,
            rule=rule, family=FAMILY, severity=sev,
            message=msg % region, hint=hint, source_line=ctx.src(node)))
    return findings
