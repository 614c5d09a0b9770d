"""A census of the ``PADDLE_TPU_*`` environment names, which only falls.

ROADMAP C5: every such name is a setting somebody must know about, and
each behaviour switch doubles the configurations tests and benchmarks
have to cover. The list below is every distinct name read or mentioned
under ``paddle_tpu/``, in ``bench.py`` and in ``chip_smoke.py``. Taking
a name out of the code takes it out of the list in the same change;
putting one in fails here first.
"""
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"PADDLE_TPU_[A-Z0-9_]+")

CENSUS = [
    "PADDLE_TPU_AGENT_ORPHAN_S",
    "PADDLE_TPU_BENCH_FAST",
    "PADDLE_TPU_CKPT_DIR",
    "PADDLE_TPU_COORDINATOR",
    "PADDLE_TPU_COORD_LEASE_GRACE_S",
    "PADDLE_TPU_DESYNC_CHECK",
    "PADDLE_TPU_DESYNC_TIMEOUT_S",
    "PADDLE_TPU_DLA_BLOCK",
    "PADDLE_TPU_DLA_KILL",
    "PADDLE_TPU_DLA_N",
    "PADDLE_TPU_DLA_P",
    "PADDLE_TPU_DLA_SLEEP_S",
    "PADDLE_TPU_DP_OVERLAP",
    "PADDLE_TPU_DP_QUANT",
    "PADDLE_TPU_ELASTIC_JOB_ID",
    "PADDLE_TPU_ELASTIC_KILL",
    "PADDLE_TPU_ELASTIC_NAME",
    "PADDLE_TPU_ELASTIC_NP",
    "PADDLE_TPU_ELASTIC_STORE",
    "PADDLE_TPU_ELASTIC_TTL",
    "PADDLE_TPU_FAULTS",
    "PADDLE_TPU_FAULT_AGENT_STALL_S",
    "PADDLE_TPU_FAULT_COMMIT_STALL_S",
    "PADDLE_TPU_FAULT_ENGINE",
    "PADDLE_TPU_FAULT_ENGINE_STALL_S",
    "PADDLE_TPU_FAULT_HANG_S",
    "PADDLE_TPU_FAULT_LEDGER",
    "PADDLE_TPU_FAULT_ROUTER_STALL_S",
    "PADDLE_TPU_FAULT_SLOW_IO_S",
    "PADDLE_TPU_FAULT_SPIKE_SCALE",
    "PADDLE_TPU_FAULT_SWEEP_STALL_S",
    "PADDLE_TPU_FLIGHT_RECORDER",
    "PADDLE_TPU_FR_DUMP_DIR",
    "PADDLE_TPU_FR_STEPS",
    "PADDLE_TPU_FR_STORE",
    "PADDLE_TPU_FT_BATCHES",
    "PADDLE_TPU_FT_EPOCHS",
    "PADDLE_TPU_FT_INTERVAL",
    "PADDLE_TPU_FT_STEPS",
    "PADDLE_TPU_FT_STORE_PORT",
    "PADDLE_TPU_INIT_DEADLINE",
    "PADDLE_TPU_INIT_RETRIES",
    "PADDLE_TPU_INTEGRITY_TIMEOUT_S",
    "PADDLE_TPU_IT_BATCHES",
    "PADDLE_TPU_IT_EPOCHS",
    "PADDLE_TPU_IT_FINGERPRINTS",
    "PADDLE_TPU_JOB_ID",
    "PADDLE_TPU_KERNELS",
    "PADDLE_TPU_KERNELS_CACHE",
    "PADDLE_TPU_LINT_BOOT",
    "PADDLE_TPU_LINT_CACHE",
    "PADDLE_TPU_METRICS",
    "PADDLE_TPU_METRICS_DIR",
    "PADDLE_TPU_METRICS_INTERVAL_S",
    "PADDLE_TPU_NNODES",
    "PADDLE_TPU_NODE_AGENT",
    "PADDLE_TPU_NODE_CRASH",
    "PADDLE_TPU_NODE_DIE_WITH_RANK",
    "PADDLE_TPU_NODE_ID",
    "PADDLE_TPU_NODE_RANK",
    "PADDLE_TPU_NUM_PROCESSES",
    "PADDLE_TPU_PREEMPT_COMMIT_TIMEOUT_S",
    "PADDLE_TPU_PROCESS_ID",
    "PADDLE_TPU_RESTART_NUM",
    "PADDLE_TPU_SERVING_ATTN",
    "PADDLE_TPU_SERVING_DRAIN_S",
    "PADDLE_TPU_STORE_CONNECT_DEADLINE",
    "PADDLE_TPU_STORE_FAILOVER_DEADLINE",
    "PADDLE_TPU_STORE_INCARNATION",
    "PADDLE_TPU_STORE_PROBE_DEADLINE",
    "PADDLE_TPU_STORE_REPLICATION",
    "PADDLE_TPU_TP_CHUNKS",
    "PADDLE_TPU_TRACE",
    "PADDLE_TPU_TRACE_",
    "PADDLE_TPU_TRACE_PATH",
    "PADDLE_TPU_TRACE_SAMPLE",
    "PADDLE_TPU_TRACE_SLOW_MS",
    "PADDLE_TPU_WATCHDOG_ESCALATION_BUDGET_S",
    "PADDLE_TPU_WATCHDOG_TIMEOUT",
    "PADDLE_TPU_WORKERLOG_DIR",
]


def _names_in_tree():
    files = [os.path.join(REPO, "bench.py"),
             os.path.join(REPO, "chip_smoke.py")]
    for root, _, names in os.walk(os.path.join(REPO, "paddle_tpu")):
        files += [os.path.join(root, n) for n in names if n.endswith(".py")]
    found = set()
    for path in files:
        with open(path, encoding="utf-8") as f:
            found.update(NAME.findall(f.read()))
    return found


def test_census_only_falls():
    assert CENSUS == sorted(set(CENSUS))
    found = _names_in_tree()
    new = sorted(found - set(CENSUS))
    assert not new, (
        f"new environment name(s) {new}: ROADMAP C5 — \"A behaviour "
        "switch with one value in use becomes a constant; a switch that "
        "picks between code paths goes the way of C3 and C4 [...] no PR "
        "raises [the count] without the two-callers argument\": two "
        "callers or workloads that exist at the parent commit, tests and "
        "examples not counted, that need different values. Addresses, "
        "paths and fault specs are deployment settings; with that "
        "argument made in the PR, add the name to CENSUS.")
    gone = sorted(set(CENSUS) - found)
    assert not gone, (
        f"{gone} left the code: take them out of CENSUS too, so the "
        "count that PERF.md section 7 and ROADMAP C5 record falls with "
        "the code")
