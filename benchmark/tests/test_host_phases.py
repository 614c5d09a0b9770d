"""``host_phases`` and the five readers that stand on it (PR 37), on
traces and span lists small enough to work out by hand (a hole in the
serve thread's tiling, a collection nested in a phase, a compile inside
the window, a step with no program), on the tiny CPU cells' own profiles
for the host side of the path, and ``BENCHMARK.json``'s new entries
against the files they name."""
import json
import os
import random
import sys
import types

import pytest

from benchmark import harness, host_phases, host_trace, trace_reduce

from .conftest import ROOT, cpu_devices
from .test_host_trace import (SERVE_CFG, _program, _reader, _run,
                              count_unions, program_ops)

NEW = ("unattributed_idle_pct.serve", "round_host_cpu_ms.serve",
       "step_host_ms.train", "step_gap_ms.train", "setup_lower_s")


# --------------------------------------------------- BENCHMARK.json's entries

def test_new_entries_name_existing_cells_and_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    entries = [m for m in bench["per_layer"] if m["name"] in NEW]
    # the five, at the end of the list, in the issue's order
    assert [m["name"] for m in bench["per_layer"][-5:]] == list(NEW)
    assert [m["name"] for m in entries] == list(NEW)
    for m in entries:
        reader = harness.load_reader(m["name"], harness.Layout())
        assert callable(reader.read)
        assert (reader.LAYER, reader.MOVES) == (m["layer"], m["moves"])
        assert m["workloads"] and set(m["workloads"]) <= set(cells)
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:     # each reports what the metric moves
            assert "workloads" not in moved or cell in moved["workloads"]
        want = {"serve": "-serve", "train": "-train"}.get(
            m["name"].rsplit(".", 1)[-1])
        if want:
            assert m["workloads"] == [c for c in cells if want in
                                      cells[c]["config"]]
        else:
            assert m["workloads"] == list(cells)
    # four of them read layers the benchmark already names, letter for letter
    assert {m["layer"] for m in entries} - layers == \
        {"programs (trace, lower, compile)"}


# ------------------------------------------- the serve thread, tiled or not

def _tiled_round(n, start, end, turn_end, cuts):
    """One round's annotations as the program records them since PR 37:
    six phases from their seven boundaries ``cuts`` tiling
    ``[start, end)``, then the ``serve.turn`` up to ``turn_end``."""
    assert cuts[0] == start and cuts[-1] == end
    return [("decode_round", start, end - start,
             {"round": n, "pad": 8, "tokens": 1, "row_lens": 1,
              "kv_lens": 9 + n})] + \
        [(name, a, b - a, {"round": n}) for name, a, b in
         zip(host_phases.ROUND_PHASES, cuts, cuts[1:])] + \
        [("serve.turn", end, turn_end - end)]


def _tiled_planes(hole):
    """Stretch [1000, 9000) as ``test_host_trace.serve_run``'s: programs
    at [1000, 3000), [3400, 5400), [7000, 9000), idle in [3000, 3400) and
    [5400, 7000). The serve thread is tiled from 400 on, but for ``hole``
    ns that the turn after round 1 ends early (the thread was in no
    span)."""
    return {
        "/device:TPU:0": {
            "XLA Modules": [("jit_round_step(1)", s, 2000)
                            for s in (1000, 3400, 7000)],
            "XLA Ops": _program(1000) + _program(3400) + _program(7000)},
        "/host:CPU": {
            "serve":
                [("serve.turn", 400, 100)]
                + _tiled_round(0, 500, 3100, 3150,
                               [500, 600, 700, 1000, 3020, 3070, 3100])
                + _tiled_round(1, 3150, 5500, 5600 - hole,
                               [3150, 3200, 3250, 3400, 5420, 5470, 5500])
                + [("serve.idle_wait", 5600, 1000),
                   ("serve.turn", 6600, 100)]
                + _tiled_round(2, 6700, 9100, 9150,
                               [6700, 6750, 6800, 7000, 9020, 9070, 9100]),
            "another-thread": [("serve.turn", 0, 9000)]},
    }


@pytest.mark.parametrize("hole", [0, 60])
def test_unattributed_idle_by_hand(tmp_path, capsys, hole):
    """Tiled, no idle instant is under no span; a hole of 60 ns in the
    thread's tiling inside a device gap is 60 ns unattributed of a stretch
    of 8000. ``host_trace`` takes the new names by their prefixes."""
    run = _run(tmp_path, _tiled_planes(hole), SERVE_CFG)
    ht = host_trace.of_run(run)
    by = {k: round(v * 1e9) for k, v in host_trace.idle_by_phase(ht).items()}
    # [3000, 3400): fetch 20, emit 50, account 30, turn 50, then round 1's
    #   schedule 50, assemble 50, launch 150.
    # [5400, 7000): fetch 20, emit 50, account 30, turn 100 (less the
    #   hole), the wait 1000, turn 100, schedule 50, assemble 50, launch 200
    want = {"round.fetch": 40, "round.emit": 100, "round.account": 60,
            "serve.turn": 250 - hole, "serve.idle_wait": 1000,
            "round.schedule": 100, "round.assemble": 100,
            "round.launch": 350}
    if hole:
        want["unattributed"] = hole
    assert by == want and sum(by.values()) == 2000
    assert _reader("unattributed_idle_pct.serve").read(run) == \
        pytest.approx(100 * hole / 8000)
    out = capsys.readouterr().out
    assert f"serve.turn {(250 - hole) / 1e9:.4f} s" in out
    assert "round.account 0.0000 s" in out and "over 3 rounds" in out
    _reader("host_bound_idle_pct.serve").read(run)
    assert f"cover {100 * (2000 - hole) / 2000:.2f} %" in \
        capsys.readouterr().out


def test_the_parents_trace_gives_the_new_readers_nothing(tmp_path):
    """A program without ``serve.turn`` (five phases a round, the thread
    not tiled) is the parent's: nothing to read, nothing raised."""
    planes = _tiled_planes(0)
    planes["/host:CPU"] = {"serve": [
        ev for ev in planes["/host:CPU"]["serve"]
        if ev[0] not in ("serve.turn", "round.account")]}
    run = _run(tmp_path, planes, SERVE_CFG)
    assert host_trace.idle_by_phase(host_trace.of_run(run))["unattributed"]
    run.step_s = []
    for name in NEW[:4]:
        assert _reader(name).read(run) is None
    run.trace = None                    # ... and an untraced run's
    for name in NEW[:4]:
        assert _reader(name).read(run) is None


# ----------------------------------------------- the serving round's CPU

def _ev(name, ts_ms, dur_ms, cpu_ms=None, tid=7, **args):
    e = {"name": name, "ph": "X", "tid": tid, "ts": 1e3 * ts_ms,
         "dur": 1e3 * dur_ms, "cat": "serving"}
    if cpu_ms is not None:
        e["cpu_us"] = 1e3 * cpu_ms
    if args:
        e["args"] = args
    return e


def _span_round(n, start_ms, fetch_ms, turn_cpu_ms, slow=0.0):
    """A round as the buffer holds it: schedule 0.1, assemble 0.2 (+
    ``slow``, all of it on the CPU), launch 1.0 (0.9 of it on the CPU),
    fetch ``fetch_ms`` (0.1 on the CPU), emit 0.2, account 0.1, then a
    turn of 0.1 ms with ``turn_cpu_ms`` on the CPU."""
    durs = [0.1, 0.2 + slow, 1.0, fetch_ms, 0.2, 0.1]
    cpus = [0.1, 0.2 + slow, 0.9, 0.1, 0.2, 0.1]
    out, t = [], start_ms
    for name, d, c in zip(host_phases.ROUND_PHASES, durs, cpus):
        out.append(_ev(name, t, d, c, round=n))
        t += d
    out.append(_ev("decode_round", start_ms, t - start_ms, sum(cpus),
                   round=n, pad=8, tokens=1))
    out.append(_ev("serve.turn", t, 0.1, turn_cpu_ms))
    return out, t + 0.1


def test_round_host_cpu_by_hand(capsys):
    """Three rounds inside the window and one cut by its opening. A round
    is 1.6 ms of CPU + its turn's; the second is slow on the CPU in
    ``round.assemble``, the third waits long in ``round.fetch`` (wall, not
    CPU); a collection nests in the second's ``round.emit``, another runs
    on another thread, a compile ends inside the window."""
    spans, t = [], 990.0
    for n, (fetch, turn_cpu, slow) in enumerate(
            [(10.0, 0.1, 0.0), (10.0, 0.1, 0.0), (10.0, 0.05, 2.0),
             (30.0, 0.1, 0.0)]):
        evs, t = _span_round(n, t, fetch, turn_cpu, slow)
        spans += evs
    emit2 = next(e for e in spans if e["name"] == "round.emit"
                 and e["args"]["round"] == 2)
    spans += [
        dict(_ev("host.gc", emit2["ts"] / 1e3 + 0.05, 0.1, generation=1,
                 collected=3), cat="host"),
        dict(_ev("host.gc", 1010.0, 0.4, tid=9, generation=2, collected=0),
             cat="host"),
        dict(_ev("host.gc", 900.0, 5.0, generation=2, collected=0),
             cat="host"),                        # before the window
        dict(_ev("jit.compile", 1020.0, 3.0, tid=9,
                 fun_name="jit(round_step)"), cat="jit"),
        _ev("moe.route", 1000.0, 0.0, round=1, layers=[[1, 2, 3]]),
    ]
    got = host_phases.round_host_cpu(spans, (1.0, 2.0))
    assert got["cpu_ms"] == pytest.approx([1.7, 3.65, 1.7])
    wall, cpu, n = got["by_phase"]["round.assemble"]
    assert (wall, cpu, n) == (pytest.approx(0.2),
                              pytest.approx((0.2 + 2.2 + 0.2) / 3), 3)
    wall, cpu, n = got["by_phase"]["round.fetch"]
    assert (wall, cpu, n) == (pytest.approx(10.0), pytest.approx(0.1), 3)
    assert got["by_phase"]["decode_round"][2] == 3
    assert got["by_phase"]["serve.turn"] == \
        (pytest.approx(0.1), pytest.approx(0.25 / 3), 3)
    assert [(name, rnd) for name, rnd, _, _ in got["longest"]] == \
        [("round.fetch", 3), ("round.fetch", 1), ("round.fetch", 2)]
    assert got["longest"][0][2:] == (pytest.approx(30.0),
                                     pytest.approx(0.1))
    assert sorted(e["dur"] for e in got["stalls"]) == [100.0, 400.0, 3000.0]

    run = types.SimpleNamespace(spans=spans, window_wall=(1.0, 2.0))
    # the mean: the chip's host reads a thread's CPU clock in 10 ms ticks
    assert _reader("round_host_cpu_ms.serve").read(run) == \
        pytest.approx((1.7 + 3.65 + 1.7) / 3)
    out = capsys.readouterr().out
    assert "over 3 rounds" in out
    assert "round.fetch 10.000 / 0.100" in out
    assert "round.fetch of round 3 30.000 ms wall, 0.100 ms CPU" in out
    assert "host.gc inside the window: 2 (generation 1: 1, generation 2: " \
        "1), 0.50 ms in all" in out
    assert "0.400 ms generation 2 collected 0 on the thread 9" in out
    assert "0.100 ms generation 1 collected 3 on the serve thread" in out
    assert "jit.compile jit(round_step) 3.0 ms on the thread 9" in out
    # the parent's records carry no cpu_us: nothing to read
    for e in spans:
        e.pop("cpu_us", None)
    assert host_phases.round_host_cpu(spans, (1.0, 2.0)) is None
    assert _reader("round_host_cpu_ms.serve").read(run) is None


# ------------------------------------ the training step against the device

def _train_step(n, start, cuts, end):
    """``train_step`` n over ``[start, end)`` and its three phases from
    their boundaries."""
    assert cuts[0] == start and cuts[-1] == end
    return [("train_step", start, end - start, {"step": n})] + \
        [(name, a, b - a, {"step": n}) for name, a, b in
         zip(host_phases.STEP_PHASES, cuts, cuts[1:])]


def _step_ops(start, dur=2000, hole=0):
    ops = [("%fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop", start,
            dur // 2 - hole)]
    return ops + [("%fusion.2 = bf16[8]{0} fusion(%p), kind=kLoop",
                   start + dur // 2, dur - dur // 2)]


@pytest.fixture()
def no_slack(monkeypatch):
    """The hand-made steps lie 3000 ns apart where real ones lie 200 ms:
    the 2 ms a real join allows the clocks would swallow them."""
    monkeypatch.setattr(host_phases, "SLACK_NS", 0.0)


@pytest.fixture()
def train_run(tmp_path, no_slack):
    """Two chips, four steps recorded on the host, three programs a chip:
    step 2's launch found no program (it lies where none starts before
    step 3's launch). Programs of 2000 ns at 1000, 4000 and 13000 on chip
    0, 20 ns later on chip 1; chip 0's first program has an idle hole of
    100 ns inside. The host: gather 100, launch 200, rebind 100 a step."""
    host = _train_step(0, 700, [700, 800, 1000, 1100], 1100) \
        + _train_step(1, 3700, [3700, 3800, 4000, 4100], 4100) \
        + _train_step(2, 9000, [9000, 9100, 9300, 9400], 9400) \
        + _train_step(3, 12700, [12700, 12800, 13000, 13100], 13100)
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit_pure(1)", s, 2000)
                            for s in (1000, 4000, 13000)],
            "XLA Ops": _step_ops(1000, hole=100) + _step_ops(4000)
            + _step_ops(13000)},
        "/device:TPU:1": {
            "XLA Modules": [("jit_pure(1)", s + 20, 2000)
                            for s in (1000, 4000, 13000)],
            "XLA Ops": _step_ops(1020) + _step_ops(4020)
            + _step_ops(13020)},
        "/host:CPU": {
            "main": host + [("PjitFunction(pure)", 810, 150)],
            "another-thread": [("step.launch", 0, 15000, {"step": 0})]},
    }
    run = _run(tmp_path, planes, {})
    run.step_s = [0.003] * 4
    return run


def test_steps_join_their_programs_by_order(train_run, capsys):
    st = host_phases.steps_of_run(train_run)
    assert (st.t0, st.t1) == (1000.0, 15020.0) and len(st.chips) == 2
    assert [s.stats["step"] for s in st.steps] == [0, 1, 2, 3]
    assert len(st.phases) == 12          # the other thread's is not a step's
    for chip, shift in zip(st.chips, (0, 20)):
        assert [(s.stats["step"], p0) for s, (p0, _) in
                host_phases.step_programs(st, chip)] == \
            [(0, 1000.0 + shift), (1, 4000.0 + shift), (3, 13000.0 + shift)]
    # steps 0 -> 1: idle [3000, 4000) on chip 0, [3020, 4020) on chip 1;
    # 1 -> 3 is not one step to the next
    assert host_phases.step_gaps_ms(st) == {
        "/device:TPU:0": [pytest.approx(1e-3)],
        "/device:TPU:1": [pytest.approx(1e-3)]}
    assert st.joined == {"/device:TPU:0": (4, 3, 3),
                         "/device:TPU:1": (4, 3, 3)}
    assert _reader("step_gap_ms.train").read(train_run) == \
        pytest.approx(1e-3)
    out = capsys.readouterr().out
    assert "/device:TPU:0 4 / 3 / 3" in out
    assert "clocks: the runtime recorded no enqueue" in out
    # every step's three phases lie inside the stretch but step 0's gather
    # and launch, which end before the first op: 400 ns a step
    assert host_phases.step_host_ms(st)[0] == [pytest.approx(4e-4)] * 3
    assert _reader("step_host_ms.train").read(train_run) == \
        pytest.approx(4e-4)
    out = capsys.readouterr().out
    assert "over 3 steps" in out and "step.launch 0.000" in out


def test_device_idle_by_step_phase_by_hand(train_run):
    """Chip 0 is idle in [1450, 1550) (inside its first program),
    [3000, 4000), [6000, 13000) and [15000, 15020); chip 1 in
    [1000, 1020), [3020, 4020), [6020, 13020): 8120 and 8020 ns."""
    st = host_phases.steps_of_run(train_run)
    by = {k: round(v * 1e9, 1)
          for k, v in host_phases.step_idle_by_phase(st).items()}
    # chip 0: [1450, 1550) between steps 100. [3000, 4000): between 700,
    #   gather 100, launch 200. [6000, 13000): between 3000 + 3300,
    #   step 2's gather 100, launch 200, rebind 100, step 3's gather 100,
    #   launch 200. [15000, 15020): after the last step 20.
    # chip 1: [1000, 1020) step 0's rebind 20. [3020, 4020): between 680,
    #   gather 100, launch 200, rebind 20. [6020, 13020): between 2980 +
    #   3300, 100, 200, 100, 100, 200, step 3's rebind 20.
    assert by == {"between steps": (7100 + 6960) / 2,
                  "step.gather": 300.0, "step.launch": 600.0,
                  "step.rebind": (100 + 160) / 2, "unattributed": 10.0}
    assert sum(by.values()) == (8120 + 8020) / 2


def test_step_clocks_apart_are_brought_together(tmp_path, no_slack):
    """The device's clock 30 ns behind the host's: each program is
    enqueued 5 ns before it starts (inside its ``step.launch``) and heard
    of 5 ns after it ends, so the lag lies between 25 and 35 and the
    steps are moved by 30 onto the device's clock."""
    lag = 30
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit_pure(1)", s - lag, 2000)
                            for s in (1000, 4000)],
            "XLA Ops": [(n, s - lag, d) for n, s, d in
                        _step_ops(1000) + _step_ops(4000)]},
        "/host:CPU": {
            "main": _train_step(0, 700, [700, 800, 1000, 1100], 1100)
            + _train_step(1, 3700, [3700, 3800, 4000, 4100], 4100),
            "the-runtime's-queue": [("DoEnqueueProgram", 980, 15),
                                    ("DoEnqueueProgram", 3980, 15)],
            "the-runtime's-waiter": [
                ("tpu::System::Execute=>Done", 3005, 4),
                ("tpu::System::Execute=>Done", 6005, 4)]},
    }
    run = _run(tmp_path, planes, {})
    run.step_s = [0.003] * 2
    st = host_phases.steps_of_run(run)
    assert st.lag_bounds == (25.0, 35.0) and st.lag_ns == 30.0
    assert [s.start for s in st.steps] == [670.0, 3670.0]
    assert host_phases.step_gaps_ms(st) == \
        {"/device:TPU:0": [pytest.approx(1e-3)]}


# ---------------------------------------------------------- the compile log

LOG = [  # (t_end, kind, fun_name, seconds)
    (10.5, "jit.trace", "kernel", 0.5),            # inside the next one
    (11.0, "jit.trace", "round_step", 2.0),        # [9, 11)
    (12.0, "jit.lower", "jit(round_step)", 1.0),   # [11, 12)
    (15.0, "jit.compile", "jit(round_step)", 3.0),
    (20.0, "jit.trace", "round_step", 1.0),        # [19, 20)
    (21.0, "jit.compile", "jit(round_step)", 0.5),
    (40.0, "jit.lower", "jit(_where)", 0.25),      # inside the window
    (95.0, "jit.compile", "jit(reference)", 9.0),  # after it
]


def test_compile_split_by_hand():
    got = host_phases.compile_split(LOG, t_open=30.0, t_close=81.0)
    assert got["lower_s"] == pytest.approx(4.0)    # the union, not 4.5
    assert got["lower_sum_s"] == pytest.approx(4.5)
    assert got["compile_s"] == pytest.approx(3.5)
    assert got["events"] == 6
    assert got["costliest"][:2] == [("jit(round_step)", 4.5, 3),
                                    ("round_step", 3.0, 2)]
    assert got["inside"] == [LOG[6]]


def test_setup_lower_reader(monkeypatch, capsys):
    """Under the command the window opens at ``__main__.T_PROCESS +
    setup_s``; a compile that ended inside the window is printed; with no
    anchor or no compile log there is nothing to read."""
    import time
    from paddle_tpu.observability import tracing
    monkeypatch.setattr(tracing, "compile_log", lambda: list(LOG))
    monkeypatch.setattr(sys.modules["__main__"], "T_PROCESS", 5.0,
                        raising=False)
    run = types.SimpleNamespace(setup_s=25.0, window_s=51.0,
                                window_wall=(0.0, float("inf")))
    reader = _reader("setup_lower_s")
    assert reader.read(run) == pytest.approx(4.0)
    out = capsys.readouterr().out
    assert "(6 events)" in out and "under way 4.00 s (plain sum 4.50 s" in out
    assert "compiling or loading from the cache 3.50 s" in out
    assert "jit(round_step) 4.50 s x3; round_step 3.00 s x2" in out
    assert "1, the longest: jit.lower jit(_where) 0.250 s at +10.00 s" in out
    assert "jit(reference)" not in out.split("inside the window")[1]
    # a serving run says when its window closed, on time.time()
    skew = time.time() - time.perf_counter()
    run.window_wall = (30.0 + skew, 39.0 + skew)
    assert reader.read(run) == pytest.approx(4.0)
    assert "inside the window (expected none): none" in \
        capsys.readouterr().out
    monkeypatch.delattr(sys.modules["__main__"], "T_PROCESS")
    assert reader.read(run) is None                # no anchor
    monkeypatch.setattr(sys.modules["__main__"], "T_PROCESS", 5.0,
                        raising=False)
    monkeypatch.delattr(tracing, "compile_log")    # the parent's program
    assert reader.read(run) is None


# ---------------------------------- the host side of the path, for real

def test_the_tiny_serving_cell_is_tiled_in_its_profile(layout):
    """A traced run of the tiny closed-loop cell on the CPU: the profile's
    serve thread holds six phases a round and ``serve.turn`` between the
    rounds, tiling it; the buffer's records give ``round_host_cpu_ms.serve``
    (a CPU's number: plumbing, never a device metric) and the compile log
    ``setup_lower_s``; no jit event ended inside the window."""
    from jax.profiler import ProfileData
    line = harness.run_cell("tiny-serve.closed", seed=11, seconds=1.0,
                            trace=True, layout=layout,
                            device_check=cpu_devices)
    assert line["metrics"]["round_host_cpu_ms.serve"]["value"] > 0
    assert line["metrics"]["setup_lower_s"]["value"] > 0
    assert "unattributed_idle_pct.serve" not in line["metrics"]
    path = trace_reduce.find_xplane(
        os.path.join(layout.checkout, ".bench_trace", "tiny-serve.closed"))
    serve = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == host_trace.HOST_PLANE:
            for ln in plane.lines:
                evs = sorted((e.start_ns, e.start_ns + e.duration_ns,
                              e.name, dict(e.stats)) for e in ln.events
                             if host_trace._is_phase(e.name))
                if any(e[2] == "decode_round" for e in evs):
                    serve.append(evs)
    assert len(serve) == 1
    tiles = [e for e in serve[0] if not e[2].startswith("round.")]
    assert {e[2] for e in tiles} >= {"decode_round", "serve.turn"}
    holes = [b[0] - a[1] for a, b in zip(tiles, tiles[1:])]
    # the annotations take their own readings of the profiler's clock
    assert max(holes) < 1e6 and sum(h for h in holes if h > 0) < \
        0.02 * (tiles[-1][1] - tiles[0][0])
    for start, end, name, stats in tiles:
        if name != "decode_round" or "pad" not in stats:
            continue
        inside = [e[2] for e in serve[0] if e[2].startswith("round.")
                  and e[3].get("round") == stats["round"]]
        assert inside == list(host_phases.ROUND_PHASES)


def test_the_tiny_training_cell_records_its_steps(layout, capsys):
    """A traced run of the tiny training cell on the CPU: the profiler is
    on and the buffer is not, and the profile's main thread holds one
    ``train_step`` a step of the stretch, numbered in order, with its three
    phases; ``step_host_ms.train`` reads them with no TPU plane,
    ``step_gap_ms.train`` has no device to read."""
    line = harness.run_cell("tiny-train.steps", seed=5, seconds=1.0,
                            trace=True, layout=layout,
                            device_check=cpu_devices)
    assert line["metrics"]["step_host_ms.train"]["value"] > 0
    assert line["metrics"]["setup_lower_s"]["value"] > 0
    assert "step_gap_ms.train" not in line["metrics"]
    assert "compile events that ended inside the window (expected none): " \
        "none" in capsys.readouterr().out
    path = trace_reduce.find_xplane(
        os.path.join(layout.checkout, ".bench_trace", "tiny-train.steps"))
    st = host_phases.load_steps(path)
    assert st.chips == [] and len(st.steps) >= 3
    numbers = [s.stats["step"] for s in st.steps]
    assert numbers == list(range(numbers[0], numbers[0] + len(numbers)))
    for s in st.steps:
        inside = [p for p in st.phases if p.stats["step"] == s.stats["step"]]
        assert [p.name for p in inside] == list(host_phases.STEP_PHASES)
        assert s.start <= inside[0].start and inside[-1].end <= s.end


# -------------------------------------------- step gaps in linear time (PR 40)

def old_step_gaps_ms(st):
    """``step_gaps_ms`` as it was until PR 40, kept as the oracle: a
    ``subtract`` against every op of the chip for each gap."""
    out = {}
    for chip in st.chips:
        busy = [(s, e) for _, s, e in chip.ops]
        pairs = host_phases.step_programs(st, chip)
        gaps = []
        for (s0, (_, e0)), (s1, (p1, _)) in zip(pairs, pairs[1:]):
            if s1.stats["step"] == s0.stats["step"] + 1 and p1 > e0:
                gaps.append(trace_reduce.measure(
                    trace_reduce.subtract([(e0, p1)], busy)) / 1e6)
        if gaps:
            out[chip.name] = gaps
    return out


def random_steps(rng, monkeypatch, steps, chips=2):
    """A StepTrace with its joins made: programs of 150-250 ms with nested
    ops, back to back, overlapping or some ms apart; a step number skipped
    now and then. ``step_programs`` is made to return the joins."""
    pairs, t, n = [], 1e6 + rng.random(), 0
    for _ in range(steps):
        p0 = t + rng.choice([0.0, -rng.uniform(0, 1e6), rng.uniform(0, 5e6)])
        p1 = p0 + rng.uniform(150e6, 250e6)
        pairs.append((host_trace.Span("train_step", p0 - 3e6, p1, {"step": n}),
                      p0, p1))
        t, n = p1, n + (2 if rng.random() < 0.05 else 1)
    chip_list, joins = [], {}
    for c in range(chips):
        ops = []
        for _, p0, p1 in pairs:
            ops += program_ops(rng, p0 + 7.3 * c, p1 + 7.3 * c, 30)
        ops.sort(key=lambda e: (e[1], -e[2]))
        chip_list.append(host_trace.Chip(f"/device:TPU:{c}", ops, []))
        joins[chip_list[-1].name] = [(s, (p0 + 7.3 * c, p1 + 7.3 * c))
                                     for s, p0, p1 in pairs]
    monkeypatch.setattr(host_phases, "step_programs",
                        lambda st, chip: joins[chip.name])
    return host_phases.StepTrace(chip_list, [s for s, _, _ in pairs], [],
                                 chip_list[0].ops[0][1], t)


@pytest.mark.parametrize("seed", range(6))
def test_step_gaps_are_the_old_formulas_number_for_number(seed, monkeypatch):
    st = random_steps(random.Random(seed), monkeypatch, steps=60)
    gaps = host_phases.step_gaps_ms(st)
    assert gaps == old_step_gaps_ms(st)         # equal, not approximately
    assert set(gaps) == {c.name for c in st.chips}


def test_step_gaps_merge_the_op_list_once_a_chip(monkeypatch):
    calls = count_unions(monkeypatch)
    for steps in (8, 200):
        st = random_steps(random.Random(steps), monkeypatch, steps=steps)
        calls.clear()
        assert host_phases.step_gaps_ms(st)
        assert len(calls) == 2
