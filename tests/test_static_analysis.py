"""tpu-lint (ISSUE 12): per-rule fixtures, suppression/baseline semantics,
the tier-1 self-scan against the committed baseline, and the CLI contract
(exit 7 on new findings, no jax import, <10s full-tree scan)."""
import json
import os
import subprocess
import sys
import time

import pytest

from paddle_tpu.tools.analyze import (DEFAULT_BASELINE, EXIT_NEW_FINDINGS,
                                      analyze_file, analyze_paths,
                                      diff_against_baseline, load_baseline,
                                      package_root, save_baseline)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "tpu_lint")


def rules_of(path):
    return [f.rule for f in analyze_file(os.path.join(FIXTURES, path))]


# ---- per-rule fixtures ------------------------------------------------------

def test_collective_order_fixtures():
    assert rules_of("collective_violate.py") == [
        "CO001", "CO001", "CO002", "CO003", "CO004"]
    # ranked p2p, no_sync guard, partial-bucket flush: all sanctioned
    assert rules_of("collective_ok.py") == []


def test_trace_purity_fixtures():
    assert rules_of("purity_violate.py") == [
        "TP001", "TP002", "TP003", "TP004"]
    assert rules_of("purity_ok.py") == []


def test_host_sync_fixtures():
    # file designated hot by the `# tpu-lint: hot-path` marker
    assert rules_of("hostsync_violate.py") == ["HS001", "HS002", "HS001"]
    # loss_fetch_every-amortized fetch rides on a reasoned suppression
    assert rules_of("hostsync_ok.py") == []


def test_jax_compat_fixtures():
    assert rules_of("jaxcompat_violate.py") == ["JC001", "JC003", "JC002"]
    assert rules_of("jaxcompat_ok.py") == []


def test_donation_fixtures():
    assert rules_of("donation_violate.py") == ["DN001", "DN002"]
    assert rules_of("donation_ok.py") == []


# ---- ISSUE 15 project-level families ---------------------------------------

def test_locks_fixtures():
    # ABBA order (both conflicting sites), store round-trip under the
    # scheduler lock, lock in a signal-reachable function
    assert rules_of("locks_violate.py") == \
        ["LK001", "LK001", "LK002", "LK003"]
    # consistent order, _store_lock serialization idiom, flag-only
    # handler, reasoned ok[LK002]
    assert rules_of("locks_ok.py") == []


def test_lk001_catches_one_line_multi_item_with_abba(tmp_path):
    # review-hardening: `with a, b:` vs `with b, a:` is the same ABBA
    # deadlock as the nested spelling — earlier items of one multi-item
    # With are held for the later ones
    fs = _scan_source(tmp_path, (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self.a_lock = threading.Lock()\n"
        "        self.b_lock = threading.Lock()\n"
        "    def p1(self):\n"
        "        with self.a_lock, self.b_lock:\n"
        "            return 1\n"
        "    def p2(self):\n"
        "        with self.b_lock, self.a_lock:\n"
        "            return 2\n"))
    assert [f.rule for f in fs] == ["LK001", "LK001"]


def test_sk001_ignores_docstrings_and_bare_string_statements(tmp_path):
    # review-hardening: documenting the key layout must not trip the
    # gate — only strings that can reach the wire count
    fs = _scan_source(tmp_path, (
        '"""serving/<job>/eng/<id> is the per-engine prefix layout."""\n'
        "def layout():\n"
        '    """elastic/<job>/coord holds the lease."""\n'
        '    "pshare/<job>/pg/<h> payload"\n'
        "    return None\n"))
    assert fs == []


def test_lk002_interprocedural_not_masked_by_unlocked_lexical_op(tmp_path):
    # review-hardening: a function with an UNLOCKED blocking op used to
    # be exempt from the interprocedural check entirely — the lock-held
    # call to a blocking helper in the same function went unflagged
    fs = _scan_source(tmp_path, (
        "import threading\n"
        "class Eng:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def helper(self, store):\n"
        "        return store.get('k')\n"
        "    def round(self, store):\n"
        "        store.get('warm')\n"          # unlocked: fine
        "        with self._lock:\n"
        "            self.helper(store)\n"))   # held: must flag
    assert [f.rule for f in fs] == ["LK002"]
    assert fs[0].callpath == ["Eng.round", "Eng.helper"]


def test_storekeys_fixtures():
    assert rules_of("storekeys_violate.py") == ["SK001", "SK003"]
    assert rules_of("storekeys_ok.py") == []


def test_storekeys_cross_subsystem_write():
    # SK002 needs the PROJECT view: two files in different subsystems
    # writing the same key root — neither file is wrong alone
    fs = analyze_paths([os.path.join(FIXTURES, "sk2")])
    by_file = {}
    for f in fs:
        by_file.setdefault(os.path.basename(f.file), []).append(f.rule)
    assert sorted(by_file) == ["roster.py", "rounds.py"]
    for rules in by_file.values():
        assert "SK002" in rules


def test_compile_fixtures():
    assert rules_of("compile_violate.py") == ["RC001", "RC002"]
    # accounted install + keepalive-pinned id key (reasoned suppression)
    assert rules_of("compile_ok.py") == []


def test_interprocedural_collective_across_files():
    # CO005: the helper issues the collective in one file, the
    # rank-gated call lives in another — invisible to any per-file scan
    fs = analyze_paths([os.path.join(FIXTURES, "xproc_co")])
    assert [(os.path.basename(f.file), f.rule) for f in fs] == \
        [("caller_violate.py", "CO005")]
    # the finding carries the resolved witness chain to the issue site
    assert fs[0].callpath == ["maybe_sync", "sync_grads", "_reduce_all"]
    assert fs[0].qualname == "maybe_sync"


# ---- suppression semantics --------------------------------------------------

def _scan_source(tmp_path, source):
    p = tmp_path / "snippet.py"
    p.write_text(source)
    return analyze_file(str(p))


def test_reasoned_suppression_suppresses(tmp_path):
    fs = _scan_source(tmp_path, (
        "def f(rank, x):\n"
        "    if rank == 0:\n"
        "        # tpu-lint: ok[CO001] every rank computes rank==0 False-"
        "identically here\n"
        "        dist.broadcast(x, src=0)\n"))
    assert [f.rule for f in fs] == []


def test_family_slug_suppression(tmp_path):
    fs = _scan_source(tmp_path, (
        "def f(rank, x):\n"
        "    if rank == 0:\n"
        "        dist.broadcast(x, src=0)  "
        "# tpu-lint: ok[collective-order] sanctioned for this test\n"))
    assert [f.rule for f in fs] == []


def test_bare_suppression_is_finding_and_does_not_suppress(tmp_path):
    fs = _scan_source(tmp_path, (
        "def f(rank, x):\n"
        "    if rank == 0:\n"
        "        dist.broadcast(x, src=0)  # tpu-lint: ok[CO001]\n"))
    assert sorted(f.rule for f in fs) == ["CO001", "SUP001"]


def test_stale_suppression_flagged(tmp_path):
    fs = _scan_source(tmp_path, (
        "def f(x):\n"
        "    return x  # tpu-lint: ok[CO001] nothing here anymore\n"))
    assert [f.rule for f in fs] == ["SUP002"]


def test_suppression_inside_string_literal_ignored(tmp_path):
    fs = _scan_source(tmp_path, (
        'DOC = "example: # tpu-lint: ok[CO001] reason"\n'))
    assert fs == []  # no SUP002: not a real comment token


def test_unparseable_file_reports_parse001(tmp_path):
    fs = _scan_source(tmp_path, "def broken(:\n")
    assert [f.rule for f in fs] == ["PARSE001"]


# ---- baseline ratchet -------------------------------------------------------

def test_baseline_ratchet_roundtrip(tmp_path):
    viol = tmp_path / "v.py"
    viol.write_text("def f(rank, x):\n"
                    "    if rank == 0:\n"
                    "        dist.broadcast(x, src=0)\n")
    findings = analyze_paths([str(viol)])
    assert len(findings) == 1
    bl = tmp_path / "baseline.json"
    save_baseline(str(bl), findings)
    # the pre-existing finding rides...
    new, old = diff_against_baseline(analyze_paths([str(viol)]),
                                     load_baseline(str(bl)))
    assert new == [] and len(old) == 1
    # ...the fingerprint survives line drift (comment shifts it down)...
    viol.write_text("# a new leading comment\n" + viol.read_text())
    new, old = diff_against_baseline(analyze_paths([str(viol)]),
                                     load_baseline(str(bl)))
    assert new == [] and len(old) == 1
    # ...and a second, genuinely new finding fails the ratchet
    viol.write_text(viol.read_text() +
                    "\n\ndef g(rank, y):\n"
                    "    if rank == 1:\n"
                    "        dist.all_reduce(y)\n")
    new, old = diff_against_baseline(analyze_paths([str(viol)]),
                                     load_baseline(str(bl)))
    assert len(new) == 1 and len(old) == 1


def test_baseline_refuses_bare_suppressions(tmp_path):
    snip = tmp_path / "s.py"
    snip.write_text("x = 1  # tpu-lint: ok[CO001]\n")
    with pytest.raises(ValueError, match="SUP001"):
        save_baseline(str(tmp_path / "b.json"), analyze_paths([str(snip)]))


# ---- the committed tree ----------------------------------------------------

def test_self_scan_no_new_findings_vs_committed_baseline():
    t0 = time.perf_counter()
    findings = analyze_paths([package_root()])
    elapsed = time.perf_counter() - t0
    new, _old = diff_against_baseline(findings,
                                      load_baseline(DEFAULT_BASELINE))
    assert new == [], "new tpu-lint findings vs committed baseline:\n" + \
        "\n".join(f"{f.file}:{f.line}: {f.rule} {f.message}" for f in new)
    # in-process scan must stay WELL under the tier-1 headroom; the CLI
    # acceptance bound (<10s incl. boot) is asserted in the CLI test below
    assert elapsed < 30.0, f"self-scan took {elapsed:.1f}s"


def test_critical_families_have_zero_baseline_entries():
    # ISSUE 12 acceptance: collective-order, host-sync and donation end
    # with ZERO baseline entries; ISSUE 15 extends the same bar to the
    # locks / store-keys / bounded-compile families (sanctioned sites use
    # reasoned suppressions instead of riding the ratchet)
    with open(DEFAULT_BASELINE) as fh:
        entries = json.load(fh)["entries"]
    critical = [e for e in entries
                if e["rule"].startswith(("CO", "HS", "DN",
                                         "LK", "SK", "RC"))]
    assert critical == []


def test_analyzer_modules_never_import_jax():
    import ast
    adir = os.path.join(package_root(), "tools", "analyze")
    for name in sorted(os.listdir(adir)):
        if not name.endswith(".py"):
            continue
        tree = ast.parse(open(os.path.join(adir, name)).read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                mods = [node.module or ""]
            else:
                continue
            for m in mods:
                assert not (m == "jax" or m.startswith("jax.")), \
                    f"{name} imports {m} — the analyzer must stay pure-AST"


# ---- CLI contract -----------------------------------------------------------

def _run_cli(*args, env_extra=None):
    env = dict(os.environ)
    env.pop("PADDLE_TPU_LINT_BOOT", None)
    if env_extra:
        env.update(env_extra)
    return subprocess.run(
        [sys.executable, "-m", "paddle_tpu.tools.analyze", *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)


def test_cli_clean_fast_and_jax_free():
    t0 = time.perf_counter()
    res = _run_cli("--assert-no-jax")
    wall = time.perf_counter() - t0
    assert res.returncode == 0, res.stdout + res.stderr
    # --assert-no-jax exits 2 if jax sneaks into the process, so rc 0 also
    # proves the boot guard skipped framework init
    assert "0 new vs baseline" in res.stdout
    assert wall < 10.0, f"CLI scan took {wall:.1f}s (acceptance: <10s)"


def test_family_filter_does_not_invent_stale_suppressions():
    # review-hardening: a collective-order-only scan must not flag the
    # tree's reasoned host-sync suppressions as stale (their rules never
    # ran, so staleness is not judgeable)
    findings = analyze_paths([package_root()],
                             families={"collective-order"})
    assert [f for f in findings if f.rule == "SUP002"] == []


def test_dn001_skips_mutually_exclusive_branch(tmp_path):
    fs = _scan_source(tmp_path, (
        "import jax\n"
        "def f(train_step, x, use_fast):\n"
        "    step = jax.jit(train_step, donate_argnums=(0,))\n"
        "    if use_fast:\n"
        "        y = step(x)\n"
        "    else:\n"
        "        y = x + 1\n"  # never executes after the donating call
        "    return y\n"))
    assert [f.rule for f in fs] == []


def test_cli_rejects_bad_family_and_partial_baseline_update():
    assert _run_cli("--families", "hostsync").returncode == 2  # typo
    res = _run_cli("--families", "collective-order", "--update-baseline")
    assert res.returncode == 2  # partial scan must never rewrite baseline
    assert "PARTIAL" in res.stderr


def test_cli_exits_7_on_injected_violation():
    res = _run_cli(os.path.join("tests", "fixtures", "tpu_lint",
                                "collective_violate.py"))
    assert res.returncode == EXIT_NEW_FINDINGS, res.stdout + res.stderr
    assert "CO001" in res.stdout


# ---- --changed-only + summary DB cache (ISSUE 15) ---------------------------

_HELPER_BODY = ("import dist\n"
                "\n"
                "def sync_grads(x):\n"
                "    dist.all_reduce(x)\n"
                "    return x\n")


def _write_xproc(tmp_path):
    helper = tmp_path / "helper.py"
    helper.write_text(_HELPER_BODY)
    caller = tmp_path / "caller.py"
    caller.write_text("from helper import sync_grads\n"
                      "\n"
                      "def maybe(x, rank):\n"
                      "    if rank == 0:\n"
                      "        sync_grads(x)\n")
    return helper, caller


def test_changed_only_reuses_cached_summaries(tmp_path):
    from paddle_tpu.tools.analyze.engine import analyze_paths
    helper, caller = _write_xproc(tmp_path)
    db = str(tmp_path / "db.json")
    full = analyze_paths([str(tmp_path)], db_path=db, persist_db=True)
    assert [f.rule for f in full] == ["CO005"]
    # tamper: drop the collective from helper.py but KEEP mtime+size, so
    # the cache reads as fresh — the scoped scan must still report CO005
    # from the STALE summary (proof the DB, not the file, fed pass 1)
    st = os.stat(helper)
    neutered = _HELPER_BODY.replace("    dist.all_reduce(x)\n",
                                    "    pass  # no colls x\n")
    assert len(neutered) == len(_HELPER_BODY)
    helper.write_text(neutered)
    os.utime(helper, (st.st_atime, st.st_mtime))
    scoped = analyze_paths([str(tmp_path)], changed={str(caller)},
                           db_path=db)
    assert [f.rule for f in scoped] == ["CO005"]


def test_changed_only_mtime_invalidation_rebuilds_summary(tmp_path):
    from paddle_tpu.tools.analyze.engine import analyze_paths
    helper, caller = _write_xproc(tmp_path)
    db = str(tmp_path / "db.json")
    analyze_paths([str(tmp_path)], db_path=db, persist_db=True)
    # a REAL edit (new mtime) must silently re-summarize the unchanged-
    # scoped file: the interprocedural finding disappears with the
    # collective even though only caller.py is in the changed set
    helper.write_text("def sync_grads(x):\n    return x\n")
    scoped = analyze_paths([str(tmp_path)], changed={str(caller)},
                           db_path=db)
    assert scoped == []


def test_changed_only_corrupt_db_is_silent_full_rebuild(tmp_path):
    from paddle_tpu.tools.analyze.engine import analyze_paths
    helper, caller = _write_xproc(tmp_path)
    db = tmp_path / "db.json"
    db.write_text("{definitely not json")
    scoped = analyze_paths([str(tmp_path)], changed={str(caller)},
                           db_path=str(db))
    assert [f.rule for f in scoped] == ["CO005"]  # rebuilt, never crashed


def test_changed_only_reports_parse_error_in_changed_file(tmp_path):
    # a syntax error in a CHANGED file is exactly what the pre-commit
    # loop exists to catch — scoping must not filter PARSE001 away
    from paddle_tpu.tools.analyze.engine import analyze_paths
    broken = tmp_path / "broken.py"
    broken.write_text("def nope(:\n")
    scoped = analyze_paths([str(tmp_path)], changed={str(broken)},
                           db_path=str(tmp_path / "db.json"))
    assert [f.rule for f in scoped] == ["PARSE001"]


def test_changed_only_scopes_reported_findings(tmp_path):
    # a finding in an UNCHANGED file must not be reported by the scoped
    # scan (it is not new work for the pre-commit loop)
    from paddle_tpu.tools.analyze.engine import analyze_paths
    bad = tmp_path / "bad.py"
    bad.write_text("def f(rank, x):\n"
                   "    if rank == 0:\n"
                   "        dist.broadcast(x, src=0)\n")
    clean = tmp_path / "clean.py"
    clean.write_text("def g(x):\n    return x\n")
    db = str(tmp_path / "db.json")
    assert len(analyze_paths([str(tmp_path)], db_path=db,
                             persist_db=True)) == 1
    scoped = analyze_paths([str(tmp_path)], changed={str(clean)},
                           db_path=db)
    assert scoped == []


def test_cli_changed_only_json_schema_and_speed():
    # warm the summary DB, then assert the pre-commit contract: a scoped
    # scan against the warm DB is sub-2s (timed in-process with a FIXED
    # one-file changed set — the CLI twin would ride on whatever git
    # happens to say is dirty) and the --json schema carries the
    # machine-readable fields
    from paddle_tpu.tools.analyze.engine import analyze_paths
    analyze_paths([package_root()], persist_db=True)
    t0 = time.perf_counter()
    analyze_paths([package_root()],
                  changed={"paddle_tpu/serving/scheduler.py"})
    scoped = time.perf_counter() - t0
    assert scoped < 2.0, f"warm scoped scan took {scoped:.2f}s"
    res = _run_cli("--changed-only", "--json")
    assert res.returncode in (0, EXIT_NEW_FINDINGS), res.stdout + res.stderr
    data = json.loads(res.stdout)
    assert data["schema"] == 2
    assert data["changed_only"] is True


def test_explicit_path_scan_never_shrinks_summary_db(tmp_path):
    # review-hardening: `--changed-only <subdir>` used to persist a DB
    # holding only the subtree's summaries (save_db replaces the file
    # map), silently evicting ~200 cached entries and breaking the next
    # scoped run's sub-2s contract — explicit-path runs must not persist
    from paddle_tpu.tools.analyze.summary import load_db
    db = str(tmp_path / "db.json")
    env = {"PADDLE_TPU_LINT_CACHE": db}
    assert _run_cli(env_extra=env).returncode in (0, EXIT_NEW_FINDINGS)
    full = len(load_db(db))
    assert full > 100
    sub = os.path.join("paddle_tpu", "serving")
    assert _run_cli("--changed-only", sub,
                    env_extra=env).returncode in (0, EXIT_NEW_FINDINGS)
    assert len(load_db(db)) == full


def test_cli_json_exit7_and_schema_on_injected_violation():
    import re
    res = _run_cli("--json", os.path.join("tests", "fixtures", "tpu_lint",
                                          "locks_violate.py"))
    assert res.returncode == EXIT_NEW_FINDINGS, res.stdout + res.stderr
    data = json.loads(res.stdout)
    rules = [f["rule"] for f in data["new"]]
    assert rules == ["LK001", "LK001", "LK002", "LK003"]
    for f in data["new"]:
        assert re.fullmatch(r"[0-9a-f]{12}", f["fingerprint"])
        for field in ("qualname", "callpath", "family", "severity",
                      "source_line", "line", "col"):
            assert field in f


# ---- regression: the three real findings the first scan surfaced -----------

def test_check_vma_is_the_installed_shard_map_kwarg():
    # the serving shard_map wrapper + ops/pallas/flash_attention.py passed check_rep=
    # straight through; the fix passes check_vma= — prove the call shape
    # JC002 steers to works on the installed jax
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices("cpu")[:1]), ("x",))
    f = jax.shard_map(lambda a: a * 2, mesh=mesh, in_specs=(P(),),
                      out_specs=P(), check_vma=False)
    out = f(jax.numpy.arange(4.0))
    assert out.tolist() == [0.0, 2.0, 4.0, 6.0]


def test_fixed_files_scan_clean_for_jax_compat():
    for rel in ("serving/ragged_attention.py",
                "ops/pallas/flash_attention.py"):
        path = os.path.join(package_root(), rel)
        fs = [f for f in analyze_file(path) if f.family == "jax-compat"]
        assert fs == [], f"{rel} regressed: {[f.rule for f in fs]}"
