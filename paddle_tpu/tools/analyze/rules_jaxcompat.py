"""jax-compat (JC) — the deprecated jax spellings the installed jax dropped.

This repo runs on one installation (jax 0.9): ``jax.shard_map`` with the
``check_vma=`` kwarg, ``jax.lax.pcast`` and ``jax.enable_x64`` are all
top-level there. The ``jax.experimental`` spellings they replaced
(``jax.experimental.shard_map``, ``check_rep=``,
``jax.experimental.enable_x64``) are gone or rejected on it, so code that
uses them fails at import or at the first sharded call — on the chip,
where it costs the most to find. These rules catch them at lint time.
"""
from __future__ import annotations

import ast

from .engine import Finding, dotted, terminal_name

FAMILY = "jax-compat"

RULES = {
    "JC001": ("error", "direct jax.experimental.shard_map import"),
    "JC002": ("error", "check_rep= passed to shard_map (removed kwarg)"),
    "JC003": ("error", "direct jax.experimental enable_x64 import"),
}

def run(ctx):
    findings = []
    for node in ctx.nodes:
        if isinstance(node, ast.ImportFrom) and node.module:
            if node.module.startswith("jax.experimental.shard_map"):
                findings.append(Finding(
                    file=ctx.relpath, line=node.lineno, col=node.col_offset,
                    rule="JC001", family=FAMILY, severity="error",
                    message="`jax.experimental.shard_map` is the "
                            "deprecated spelling — the installed jax "
                            "ships `jax.shard_map`",
                    hint="use `from jax import shard_map`",
                    source_line=ctx.src(node)))
            elif node.module == "jax.experimental" and any(
                    a.name == "enable_x64" for a in node.names):
                findings.append(Finding(
                    file=ctx.relpath, line=node.lineno, col=node.col_offset,
                    rule="JC003", family=FAMILY, severity="error",
                    message="`jax.experimental.enable_x64` is the "
                            "deprecated spelling — the installed jax "
                            "ships `jax.enable_x64`",
                    hint="use `jax.enable_x64`",
                    source_line=ctx.src(node)))
        elif isinstance(node, ast.Attribute) \
                and node.attr in ("shard_map", "enable_x64"):
            # the terminal attr gates the (comparatively pricey) chain walk
            if dotted(node).startswith("jax.experimental.shard_map"):
                findings.append(Finding(
                    file=ctx.relpath, line=node.lineno, col=node.col_offset,
                    rule="JC001", family=FAMILY, severity="error",
                    message="attribute use of the deprecated "
                            "`jax.experimental.shard_map`",
                    hint="use `jax.shard_map` / `from jax import shard_map`",
                    source_line=ctx.src(node)))
            elif dotted(node) == "jax.experimental.enable_x64":
                findings.append(Finding(
                    file=ctx.relpath, line=node.lineno, col=node.col_offset,
                    rule="JC003", family=FAMILY, severity="error",
                    message="attribute use of the deprecated "
                            "`jax.experimental.enable_x64`",
                    hint="use `jax.enable_x64`",
                    source_line=ctx.src(node)))
        elif isinstance(node, ast.Call) \
                and terminal_name(node.func) == "shard_map":
            for kw in node.keywords:
                if kw.arg == "check_rep":
                    findings.append(Finding(
                        file=ctx.relpath, line=kw.value.lineno,
                        col=kw.value.col_offset,
                        rule="JC002", family=FAMILY, severity="error",
                        message="`check_rep=` is the removed kwarg — "
                                "`jax.shard_map` rejects it with a "
                                "TypeError",
                        hint="pass `check_vma=`",
                        source_line=ctx.src(node)))
    return findings
