"""Fault-tolerant distributed linear algebra (distributed/dlinalg):
numpy-parity for SUMMA matmul / TSQR / blocked QR / the subspace-sweep
eigensolver, bit-identical resume from mid-iteration, the numerical-
correctness oracle turning injected corruption into a loud error, and
the fault/keyspace/preemption satellites of ISSUE 18.

The multi-rank fast tier simulates SPMD with one thread per rank over a
shared LocalExchange — same code path as the chaos workers minus the
process boundary (tests/test_dlinalg_chaos.py runs the real launcher).
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu.distributed as dist
from paddle_tpu.distributed import dlinalg, fault, keyspace
from paddle_tpu.distributed.dlinalg import (
    BlockCyclicLayout, ExchangeTimeout, LocalExchange, ShardedMatrix,
    StoreExchange, SubspaceEigensolver, SweepSpec, OracleViolation,
    ResidualOracle, blocked_qr, matmul_reference, qr_reference,
    summa_matmul, tsqr,
)

WORKERS = os.path.join(os.path.dirname(__file__), "workers")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_fault_state(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_FAULTS", raising=False)
    monkeypatch.delenv("PADDLE_TPU_FAULT_LEDGER", raising=False)
    fault.set_fault_spec(None)
    yield
    fault.set_fault_spec(None)


def run_spmd(world, fn, timeout=120):
    """Run ``fn(rank, exchange)`` on one thread per rank over a shared
    LocalExchange; returns the per-rank results (re-raises the first
    failure)."""
    ex = LocalExchange()
    results = [None] * world
    errors = []

    def target(r):
        try:
            results[r] = fn(r, ex)
        except BaseException as e:  # noqa: BLE001 - surfaced below
            errors.append((r, e))

    threads = [threading.Thread(target=target, args=(r,), daemon=True)
               for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
        assert not t.is_alive(), "SPMD thread hung"
    if errors:
        raise errors[0][1]
    return results


# ---------------------------------------------------------------- layout

def test_block_cyclic_layout_ownership():
    lay = BlockCyclicLayout(100, 16, world=3)
    assert lay.n_blocks == 7
    assert [lay.owner(b) for b in range(7)] == [0, 1, 2, 0, 1, 2, 0]
    assert lay.blocks_of(0) == (0, 3, 6)
    assert lay.row_range(6) == (96, 100)  # ragged tail block
    assert lay.block_nrows(6) == 4
    # every row is covered exactly once
    rows = [r for b in range(lay.n_blocks)
            for r in range(*lay.row_range(b))]
    assert rows == list(range(100))
    with pytest.raises(ValueError):
        BlockCyclicLayout(0, 16)
    with pytest.raises(ValueError):
        BlockCyclicLayout(100, 16, world=0)


def test_layout_reshard_is_metadata_only():
    """The block COUNT is world-independent: resharding changes only
    ownership, and reshard_moves names exactly the blocks that move."""
    old = BlockCyclicLayout(100, 16, world=3)
    new = old.reshard(2)
    assert new.n_blocks == old.n_blocks
    moves = old.reshard_moves(new)
    for b, old_owner, new_owner in moves:
        assert old.owner(b) == old_owner != new.owner(b) == new_owner
    moved = {b for b, _, _ in moves}
    for b in range(old.n_blocks):
        assert (b in moved) == (old.owner(b) != new.owner(b))
    with pytest.raises(ValueError):
        old.reshard_moves(BlockCyclicLayout(100, 8, world=2))


def test_sharded_matrix_round_trip():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((50, 7))
    m = ShardedMatrix.from_global(a, 8, world=1, rank=0)
    assert np.array_equal(m.to_global(), a)
    # sharded across a world: each rank holds exactly its blocks
    shards = [ShardedMatrix.from_global(a, 8, world=3, rank=r)
              for r in range(3)]
    for r, m in enumerate(shards):
        assert set(m.blocks) == set(m.layout.blocks_of(r))
        for b in m.owned:
            lo, hi = m.layout.row_range(b)
            assert np.array_equal(m.block(b), a[lo:hi])
    with pytest.raises(ValueError):
        shards[0].set_block(1, np.zeros((8, 7)))  # rank 1's block
    with pytest.raises(ValueError):
        shards[0].set_block(0, np.zeros((3, 7)))  # wrong shape


# ---------------------------------------------------------------- matmul

def test_summa_matmul_parity_world3():
    rng = np.random.default_rng(1)
    a, b = rng.standard_normal((60, 40)), rng.standard_normal((40, 9))
    ref = matmul_reference(a, b)

    def body(rank, ex):
        A = ShardedMatrix.from_global(a, 16, world=3, rank=rank)
        B = ShardedMatrix.from_global(b, 16, world=3, rank=rank)
        C = summa_matmul(A, B, ex)
        return C.gather_global(ex, "c")

    for got in run_spmd(3, body):
        assert np.allclose(got, ref, atol=1e-12)
        # f64 accumulation in global block order: parity is BITWISE vs
        # the single-rank run of the same kernel
    solo = summa_matmul(ShardedMatrix.from_global(a, 16),
                        ShardedMatrix.from_global(b, 16),
                        LocalExchange()).to_global()
    assert np.array_equal(solo, got)


def test_summa_matmul_xla_backend_parity():
    rng = np.random.default_rng(2)
    a, b = rng.standard_normal((24, 16)), rng.standard_normal((16, 5))
    C = summa_matmul(ShardedMatrix.from_global(a, 8),
                     ShardedMatrix.from_global(b, 8),
                     LocalExchange(), backend="xla")
    # xla runs at the session dtype (f32 unless x64): tolerance parity
    assert np.allclose(C.to_global(), matmul_reference(a, b),
                       rtol=1e-5, atol=1e-4)


def test_summa_resume_mid_round_bit_identical():
    """stop_round checkpoints a partial product; resuming with the saved
    C and start_round reproduces the uninterrupted result BITWISE."""
    rng = np.random.default_rng(3)
    a, b = rng.standard_normal((40, 40)), rng.standard_normal((40, 6))
    A = ShardedMatrix.from_global(a, 8)
    B = ShardedMatrix.from_global(b, 8)
    full = summa_matmul(A, B, LocalExchange()).to_global()
    part = summa_matmul(A, B, LocalExchange(), stop_round=2)
    resumed = summa_matmul(A, B, LocalExchange(), start_round=2, C=part)
    assert np.array_equal(resumed.to_global(), full)


def test_freivalds_oracle_passes_and_catches_corruption():
    rng = np.random.default_rng(4)
    a, b = rng.standard_normal((30, 20)), rng.standard_normal((20, 4))
    A = ShardedMatrix.from_global(a, 8)
    B = ShardedMatrix.from_global(b, 8)
    C = summa_matmul(A, B, LocalExchange())
    oracle = ResidualOracle()
    oracle.freivalds_matmul(A, B, C, LocalExchange(), "fv_ok")
    C.block(0)[0, 0] += 1e-3  # silent corruption
    with pytest.raises(OracleViolation) as ei:
        oracle.freivalds_matmul(A, B, C, LocalExchange(), "fv_bad")
    assert ei.value.what == "matmul_freivalds"
    assert any(w == "matmul_freivalds" for w, _ in oracle.history)


# ---------------------------------------------------------------- QR

def test_tsqr_parity_and_replicated_r():
    rng = np.random.default_rng(5)
    y = rng.standard_normal((70, 6))
    qref, rref = qr_reference(y)

    def body(rank, ex):
        Y = ShardedMatrix.from_global(y, 16, world=3, rank=rank)
        Q, R = tsqr(Y, ex)
        return Q.gather_global(ex, "q"), R

    out = run_spmd(3, body)
    # R is replicated bit-identically (every rank factors the same
    # stacked bytes); Q/R match the sign-fixed numpy reference
    assert np.array_equal(out[0][1], out[1][1])
    assert np.array_equal(out[1][1], out[2][1])
    for q, r in out:
        assert np.allclose(r, rref, atol=1e-12)
        assert np.allclose(q, qref, atol=1e-12)
        assert np.allclose(q.T @ q, np.eye(6), atol=1e-13)
        assert np.allclose(q @ r, y, atol=1e-12)


def test_blocked_qr_parity_and_resume():
    rng = np.random.default_rng(6)
    a = rng.standard_normal((64, 12))
    qref, rref = qr_reference(a)

    def body_full(rank, ex):
        mine = ShardedMatrix.from_global(a, 8, world=2, rank=rank)
        return blocked_qr(mine, ex, panel_cols=4,
                          oracle=ResidualOracle())

    full = run_spmd(2, body_full)
    # parity vs the sign-fixed reference (assemble from both ranks)
    got = np.zeros((64, 12))
    for q, _ in full:
        for b in q.owned:
            lo, hi = q.layout.row_range(b)
            got[lo:hi] = q.block(b)
    assert np.allclose(got, qref, atol=1e-11)
    assert np.array_equal(full[0][1], full[1][1])  # replicated R
    assert np.allclose(full[0][1], rref, atol=1e-11)

    # resume: capture the state committed after panel 1, restart at 2
    # (interrupt by raising from on_panel — the chaos model minus the
    # process boundary)
    class _Stop(Exception):
        pass

    saved = {}

    def body_first_half(rank, ex):
        mine = ShardedMatrix.from_global(a, 8, world=2, rank=rank)

        def cap(j, Q, R):
            saved[rank] = ({b: Q.block(b).copy() for b in Q.owned},
                           R.copy())
            if j == 1:
                raise _Stop()
        try:
            blocked_qr(mine, ex, panel_cols=4, on_panel=cap)
        except _Stop:
            pass

    run_spmd(2, body_first_half)

    def body_resume(rank, ex):
        mine = ShardedMatrix.from_global(a, 8, world=2, rank=rank)
        blocks, R = saved[rank]
        Q0 = ShardedMatrix(mine.layout, 12, rank, blocks=blocks)
        return blocked_qr(mine, ex, panel_cols=4, start_panel=2,
                          Q=Q0, R=R.copy(), oracle=ResidualOracle())

    resumed = run_spmd(2, body_resume)
    # bit-identical continuation: projections read only committed state
    for rank in (0, 1):
        assert np.array_equal(resumed[rank][1], full[rank][1])
        for b in resumed[rank][0].owned:
            assert np.array_equal(resumed[rank][0].block(b),
                                  full[rank][0].block(b))


def test_blocked_qr_oracle_catches_injected_corruption():
    fault.set_fault_spec("panel_corrupt@linalg_panel:2")
    rng = np.random.default_rng(7)
    a = rng.standard_normal((32, 8))
    A = ShardedMatrix.from_global(a, 8)
    with pytest.raises(OracleViolation):
        blocked_qr(A, LocalExchange(), panel_cols=4,
                   oracle=ResidualOracle())


# ---------------------------------------------------------------- sweeps

def _test_matrix(n, p, seed=11):
    rng = np.random.default_rng(seed)
    V, _ = np.linalg.qr(rng.standard_normal((n, n)))
    d = np.concatenate([np.linspace(p + 1.0, 2.0, p),
                        np.sort(rng.uniform(0.0, 0.05, n - p))[::-1]])
    return (V * d) @ V.T


def test_subspace_eigensolver_matches_numpy():
    n, p = 48, 3
    a = _test_matrix(n, p)
    A = ShardedMatrix.from_global(a, 8)
    spec = SweepSpec(n, p, block_rows=8, tol=1e-9, max_sweeps=60)
    solver = SubspaceEigensolver(A, spec, LocalExchange())
    theta, X, converged = solver.run()
    assert converged
    ref = np.linalg.eigvalsh(a)[::-1][:p]
    assert np.allclose(theta, ref, rtol=1e-8)
    # Ritz vectors: A X ~= X diag(theta)
    assert np.allclose(a @ X, X * theta, atol=1e-6)
    assert solver.residual_history[-1] < 1e-9


def test_subspace_eigensolver_world_parity():
    """Within one world every rank ends BIT-IDENTICAL (rank-ordered
    deterministic reductions + replicated host eigh); across world
    sizes the answer agrees to round-off (TSQR stacks rows per rank, so
    f64 association — not the result — depends on the world)."""
    n, p = 48, 3
    a = _test_matrix(n, p)
    spec = dict(block_rows=8, tol=1e-9, max_sweeps=60)
    solo = SubspaceEigensolver(
        ShardedMatrix.from_global(a, 8), SweepSpec(n, p, **spec),
        LocalExchange())
    t1, x1, c1 = solo.run()

    def body(rank, ex):
        A = ShardedMatrix.from_global(a, 8, world=3, rank=rank)
        s = SubspaceEigensolver(A, SweepSpec(n, p, **spec), ex)
        return s.run()

    out = run_spmd(3, body)
    for theta, X, converged in out:
        assert converged == c1
        # cross-rank: bitwise; cross-world: exact answer, f64 round-off
        assert np.array_equal(theta, out[0][0])
        assert np.array_equal(X, out[0][1])
        assert np.allclose(theta, t1, rtol=1e-12)
        assert np.allclose(X, x1, atol=1e-9)


def test_subspace_eigensolver_resume_bit_identical(tmp_path):
    """Interrupt mid-sweep (after a committed panel), restore from the
    lineage in a NEW solver, finish: theta/X match the uninterrupted run
    bitwise and the residual history is stitched, not restarted."""
    n, p = 48, 3
    a = _test_matrix(n, p)

    def fresh(lineage=None):
        A = ShardedMatrix.from_global(a, 8)
        spec = SweepSpec(n, p, block_rows=8, tol=1e-9, max_sweeps=60,
                         checkpoint_panels=True)
        return SubspaceEigensolver(A, spec, LocalExchange(),
                                   lineage=lineage)

    base = fresh()
    t_ref, x_ref, c_ref = base.run()

    lineage = fault.CheckpointLineage(str(tmp_path / "ck"))

    class _Boom(Exception):
        pass

    def bomb(s, b):
        if s == 2 and b == 1:
            raise _Boom()

    victim = fresh(lineage)
    assert victim.restore() is None  # nothing saved yet
    with pytest.raises(_Boom):
        victim.run(on_panel=bomb)

    heir = fresh(lineage)
    step = heir.restore()
    assert step is not None and heir.sweep == 2 and heir.panel == 2
    t2, x2, c2 = heir.run()
    assert c2 == c_ref
    assert np.array_equal(t2, t_ref)
    assert np.array_equal(x2, x_ref)
    assert heir.residual_history == base.residual_history

    # seed mismatch = different problem: restore must refuse, loudly
    A = ShardedMatrix.from_global(a, 8)
    other = SubspaceEigensolver(
        A, SweepSpec(n, p, block_rows=8, seed=99, checkpoint_panels=True),
        LocalExchange(), lineage=lineage)
    with pytest.raises(ValueError, match="RNG spec"):
        other.restore()


def test_subspace_eigensolver_oracle_catches_corruption():
    fault.set_fault_spec("panel_corrupt@linalg_panel:3")
    n, p = 48, 3
    A = ShardedMatrix.from_global(_test_matrix(n, p), 8)
    solver = SubspaceEigensolver(
        A, SweepSpec(n, p, block_rows=8, max_sweeps=10), LocalExchange())
    with pytest.raises(OracleViolation) as ei:
        solver.run()
    assert "panel_residual" in ei.value.what


# ---------------------------------------------------------------- fault

def test_dlinalg_fault_kinds_parse_and_validate():
    es = fault.parse_fault_spec(
        "panel_corrupt@linalg_panel:2,sweep_stall@linalg_sweep:1,"
        "panel_corrupt:1")
    assert [e.key() for e in es] == [
        "panel_corrupt@linalg_panel:2", "sweep_stall@linalg_sweep:1",
        "panel_corrupt:1"]
    # wildcard cooperative kinds only fire at their honored site
    assert es[2].matches("linalg_panel", None)
    assert not es[2].matches("step", None)
    # pinned to a site that can't enact them: rejected at PARSE time
    with pytest.raises(ValueError):
        fault.parse_fault_spec("panel_corrupt@route:1")
    with pytest.raises(ValueError):
        fault.parse_fault_spec("sweep_stall@step:1")


def test_sweep_stall_executes_bounded_sleep(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FAULT_SWEEP_STALL_S", "0.2")
    fault.set_fault_spec("sweep_stall@linalg_sweep:1")
    t0 = time.monotonic()
    # executed kind (like slow_io): the sleep happens HERE, no caller
    # cooperation needed, so maybe_inject returns None
    assert fault.maybe_inject("linalg_sweep") is None
    assert time.monotonic() - t0 >= 0.2
    # trigger burned: the next sweep boundary is clean
    t0 = time.monotonic()
    assert fault.maybe_inject("linalg_sweep") is None
    assert time.monotonic() - t0 < 0.1


def test_exit_causes_audit():
    """Satellite: every EXIT_* constant has a human cause in EXIT_CAUSES
    and the codes are pairwise distinct (the launcher's failure summary
    and the chaos tests both key on them)."""
    codes = {name: getattr(fault, name) for name in dir(fault)
             if name.startswith("EXIT_") and name != "EXIT_CAUSES"
             and isinstance(getattr(fault, name), int)}
    assert len(set(codes.values())) == len(codes), codes
    for name, rc in codes.items():
        assert rc in fault.EXIT_CAUSES, f"{name} has no EXIT_CAUSES entry"
        assert fault.EXIT_CAUSES[rc].strip()
    assert fault.EXIT_ORACLE == 47
    assert "oracle" in fault.describe_exit(fault.EXIT_ORACLE)


def test_preemption_scope_installs_and_restores():
    """Satellite: the scoped SIGTERM watcher restores the previous
    disposition/callback/flag on exit, and nests."""
    seen = []
    prev = signal.getsignal(signal.SIGTERM)
    with fault.preemption_scope() as scope:
        assert scope.installed
        assert not scope.preempted()
        os.kill(os.getpid(), signal.SIGTERM)
        for _ in range(100):
            if scope.preempted():
                break
            time.sleep(0.01)
        assert scope.preempted()
        # nested scope sees a clean slate-restoring stack
        with fault.preemption_scope(on_preempt=lambda: seen.append(1)):
            pass
        assert scope.preempted()  # outer flag survived the inner scope
    assert not fault.preempted()  # scope exit cleared the flag it owned
    assert signal.getsignal(signal.SIGTERM) == prev
    assert not seen  # inner callback never fired


@pytest.mark.slow
def test_sigterm_mid_sweep_saves_and_exits_75(tmp_path):
    """Satellite regression: SIGTERM a single-process sweep mid-run →
    verified snapshot on disk + EXIT_PREEMPT, and a rerun RESUMES from
    it and converges to the right answer."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PADDLE_TPU_", "PADDLE_TRAINER"))}
    env.update({
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO,
        "PYTHONUNBUFFERED": "1",
        "PADDLE_TPU_CKPT_DIR": str(tmp_path / "ck"),
        "PADDLE_TPU_DLA_N": "64", "PADDLE_TPU_DLA_P": "3",
        "PADDLE_TPU_DLA_BLOCK": "8",
        "PADDLE_TPU_DLA_SLEEP_S": "0.2",
    })
    proc = subprocess.Popen(
        [sys.executable, os.path.join(WORKERS, "dlinalg_worker.py")],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, cwd=REPO)
    lines = []
    for line in proc.stdout:
        lines.append(line)
        if line.startswith("PANEL"):
            proc.send_signal(signal.SIGTERM)
            break
    out_rest, err = proc.communicate(timeout=120)
    lines.append(out_rest)
    assert proc.returncode == fault.EXIT_PREEMPT, \
        f"rc={proc.returncode}\n{''.join(lines)}\n{err}"

    # the snapshot it left is VERIFIED loadable (not torn)
    lineage = fault.CheckpointLineage(str(tmp_path / "ck"))
    lay = dlinalg.BlockCyclicLayout(64, 8, world=1)
    target = {"sweep": 0, "panel": 0, "seed": 0, "world": 0,
              "resid_history": [], "theta": None, "Q": None,
              "Y": {f"b{b}": None for b in lay.blocks_of(0)}}
    step = lineage.load_latest(target)
    assert step is not None and step >= 1

    # rerun: resumes (not FRESH) and converges to the true spectrum
    r = subprocess.run(
        [sys.executable, os.path.join(WORKERS, "dlinalg_worker.py")],
        env={**env, "PADDLE_TPU_DLA_SLEEP_S": "0"}, capture_output=True,
        text=True, timeout=300, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "RESUMED step=" in r.stdout and "FRESH" not in r.stdout
    assert "DONE" in r.stdout
    theta_err = float(r.stdout.split("THETA_ERR ")[1].split()[0])
    assert theta_err < 1e-6


# ---------------------------------------------------------------- keyspace

def test_keyspace_builders_round_trip():
    """Satellite: every public builder produces its documented spelling
    (the wire bytes are the protocol — a drifted spelling silently
    splits the namespace)."""
    cases = {
        keyspace.wal_entry(7): "__wal/7",
        keyspace.wal_claim("op1"): "__wal/claim/op1",
        keyspace.wal_result("op1"): "__wal/result/op1",
        keyspace.wal_cursor(2): "__wal/cursor/2",
        keyspace.fence_promo(3): "__fence/promo/e3",
        keyspace.elastic_job("j"): "elastic/j",
        keyspace.elastic_node("j"): "elastic/j/node",
        keyspace.elastic_coord("j"): "elastic/j/coord",
        keyspace.fleet_registry("j"): "serving/j",
        keyspace.fleet_engine_rpc("j", "e1"): "serving/j/eng/e1",
        keyspace.fleet_engine_stream("j", "e1"): "serving/j/eng/e1/stream",
        keyspace.fleet_quarantine("j"): "serving/j/quarantine",
        keyspace.fleet_autoscale("j"): "serving/j/autoscale",
        keyspace.fleet_ledger("j"): "serving/j/ledger",
        keyspace.fleet_router("j"): "serving/j/router",
        keyspace.page_share("j"): "pshare/j",
        keyspace.rpc_worker("w"): "rpc/worker/w",
        keyspace.rpc_rank(4): "rpc/rank/4",
        keyspace.dlinalg_job("j"): "dlinalg/j",
        keyspace.dlinalg_panels("j"): "dlinalg/j/panel",
        keyspace.dlinalg_solver("j"): "dlinalg/j/solver",
    }
    for got, want in cases.items():
        assert got == want
    # __all__ is the audit surface: every builder above is exported
    for name in ("dlinalg_job", "dlinalg_panels", "dlinalg_solver"):
        assert name in keyspace.__all__
    # every dlinalg key is registry scope (no ``__`` prefix): it must
    # ride the FailoverStore WAL, not skip it
    for k in (keyspace.dlinalg_job("j"), keyspace.dlinalg_panels("j"),
              keyspace.dlinalg_solver("j")):
        assert not k.startswith("__")


# ---------------------------------------------------------------- exchange

def test_store_exchange_round_trip_and_timeout():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    store = dist.TCPStore("127.0.0.1", port, is_master=True, timeout=15)
    try:
        ex = StoreExchange(store, job="t")
        arr = np.arange(12, dtype=np.float64).reshape(3, 4) / 7.0
        ex.publish("i0/s0/x", arr)
        got = ex.fetch("i0/s0/x", timeout=5)
        assert got.dtype == np.float64 and np.array_equal(got, arr)
        # non-f64 dtypes survive the pack/unpack header too
        ex.publish("i0/s0/y", np.array([[1, 2]], dtype=np.int32))
        assert ex.fetch("i0/s0/y").dtype == np.int32
        # keys live under the keyspace builders (SK rules)
        raw = store.get(keyspace.dlinalg_panels("t") + "/i0/s0/x")
        assert raw is not None
        with pytest.raises(ExchangeTimeout):
            ex.fetch("i0/s0/missing", timeout=0.3)
        ex.barrier("done", 1, timeout=5)
        # reduce_sum over one rank is the identity
        assert np.array_equal(
            ex.reduce_sum("i0/s0/r", 0, 1, arr), arr)
    finally:
        store.stop_server()


def test_local_exchange_poll_hook_aborts_blocked_fetch():
    """The poll hook runs while a fetch waits — a preempted rank blocked
    on a dead peer's panel still drains instead of hanging."""
    ex = LocalExchange()

    class _Drain(Exception):
        pass

    calls = []

    def poll():
        calls.append(1)
        if len(calls) >= 3:
            raise _Drain()

    ex.poll = poll
    with pytest.raises(_Drain):
        ex.fetch("never", timeout=10)
    assert len(calls) >= 3


# ---------------------------------------------------------------- bench

def test_bench_guarded_legs_keep_prior_json():
    """bench.py leg guard (``--linalg`` satellite): a later leg that
    raises must record its error rows WITHOUT dropping any prior leg's
    JSON, and a leg's soft ``<name>_ok: False`` must fail the run while
    keeping every row — so new bench legs can't regress the
    keep-prior-legs contract. Run in a subprocess: bench.py imports the
    whole framework under its own environment."""
    code = """
import json
import bench

sub = {}
ok = bench._run_guarded_legs(sub, [
    ("good", lambda: {"linalg_gflops": 1.5}),
    ("bad", lambda: (_ for _ in ()).throw(ValueError("later leg"))),
    ("soft", lambda: {"soft_ok": False, "soft_rows": 2}),
])
print("GUARD " + json.dumps({"ok": ok, "sub": sub}))
"""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PADDLE_TPU_", "PADDLE_TRAINER"))}
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
                "PYTHONPATH": REPO})
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    line = [l for l in r.stdout.splitlines() if l.startswith("GUARD ")]
    assert line, r.stdout
    out = json.loads(line[0][len("GUARD "):])
    assert out["ok"] is False
    # the raising middle leg kept the first leg's rows on the wire...
    assert out["sub"]["linalg_gflops"] == 1.5
    assert out["sub"]["bad_leg_ok"] is False
    assert "later leg" in out["sub"]["bad_error"]
    # ...and the legs after it still ran and reported
    assert out["sub"]["soft_rows"] == 2
