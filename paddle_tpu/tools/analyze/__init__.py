"""tpu-lint — framework-native static analysis for paddle_tpu (ISSUE 12,
project-wide two-pass analysis since ISSUE 15).

Eight pure-AST rule families catch, before a run, the bug classes the
runtime machinery diagnoses after one:

* ``collective-order`` (CO) — collectives under rank-/data-/exception-
  dependent control flow (the desync exit-21 class), including the
  interprocedural CO005 through the project call graph;
* ``trace-purity`` (TP) — side effects baked into traced/cached programs
  (the stale `_jit_cache` replay class);
* ``host-sync`` (HS) — blocking fetches on designated hot paths;
* ``jax-compat`` (JC) — deprecated ``jax.experimental`` spellings the
  installed jax dropped;
* ``donation`` (DN) — reads of buffers already donated to a jitted call;
* ``locks`` (LK) — ABBA lock order, blocking calls under contended
  locks, signal/atexit-reachable acquisitions;
* ``store-keys`` (SK) — the distributed/keyspace.py key protocol;
* ``bounded-compile`` (RC) — the serving compile-count contract.

CLI::

    python -m paddle_tpu.tools.analyze                 # scan, gate on baseline
    python -m paddle_tpu.tools.analyze --changed-only  # pre-commit loop
    python -m paddle_tpu.tools.analyze --update-baseline
    python -m paddle_tpu.tools.analyze path/to/file.py --no-baseline

Exit codes: 0 clean vs baseline, 7 new findings, 2 usage error.  The CLI
never imports jax (``paddle_tpu/__init__`` skips framework init for this
boot shape), so a full-tree scan is parse-time only.

This package must stay importable with NOTHING but the stdlib — no jax, no
paddle_tpu framework modules.
"""
from .engine import (  # noqa: F401
    EXIT_NEW_FINDINGS, FAMILIES, Finding, all_rules, analyze_file,
    analyze_paths, diff_against_baseline, finding_key, fingerprint,
    format_finding, iter_py_files, load_baseline, package_root,
    save_baseline,
)

DEFAULT_BASELINE = __file__.rsplit("/", 1)[0] + "/baseline.json"
