"""``host_trace`` and the five readers that stand on it, on traces small
enough to work out by hand, on a recorded cut of a real v5e traced run of
``decode-sat`` (host plane included), and on the tiny CPU cell's own
profile for the host side of the path."""
import os
import random
import shutil
import statistics
import time
import types

import pytest

from benchmark import harness, host_phases, host_trace, trace_reduce

from .conftest import cpu_devices

HERE = os.path.dirname(os.path.abspath(__file__))
KERNEL = ' = bf16[8]{0} custom-call(%q), custom_call_target=\\"tpu_custom_call\\"'
V5E = {"flops": 197e12, "bytes": 819e9}     # benchmark/peaks.py


def _xspace(planes):
    """{plane: {line: [(name, start_ns, dur_ns[, {stat: value}])]}} ->
    serialized XSpace (``test_trace_reduce._xspace``'s form, with the
    annotations' stats)."""
    from jax.profiler import ProfileData
    text = []
    for pname, lines in planes.items():
        names = sorted({ev[0] for evs in lines.values() for ev in evs})
        stats = sorted({k for evs in lines.values() for ev in evs
                        for k in (ev[3] if len(ev) > 3 else {})})
        ids = {n: i + 1 for i, n in enumerate(names)}
        sids = {n: i + 1 for i, n in enumerate(stats)}
        text.append(f'planes {{ name: "{pname}"')
        for i, (lname, evs) in enumerate(lines.items()):
            text.append(f'  lines {{ id: {i + 1} name: "{lname}" '
                        'timestamp_ns: 0')
            for ev in evs:
                n, s, d = ev[:3]
                st = "".join(
                    f' stats {{ metadata_id: {sids[k]} '
                    + (f'str_value: "{v}"' if isinstance(v, str)
                       else f'int64_value: {v}') + ' }'
                    for k, v in (ev[3] if len(ev) > 3 else {}).items())
                text.append(f'    events {{ metadata_id: {ids[n]} offset_ps: '
                            f'{s * 1000} duration_ps: {d * 1000}{st} }}')
            text.append('  }')
        for n, i in ids.items():
            text.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" }} }}')
        for n, i in sids.items():
            text.append(f'  stat_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" }} }}')
        text.append('}')
    return ProfileData.text_proto_to_serialized_xspace("\n".join(text))


def _run(tmp_path, planes, config, workload=None, cell="hand.cell"):
    """A ``harness.Run`` whose traced stretch is the hand-built trace."""
    where = tmp_path / ".bench_trace" / cell
    where.mkdir(parents=True)
    (where / "hand.xplane.pb").write_bytes(_xspace(planes))
    layout = harness.Layout(checkout=str(tmp_path))
    c = harness.Cell(name=cell, chips=1, workload=workload or {},
                     config=config, end_to_end=[], per_layer=[],
                     layout=layout)
    return harness.Run(cell=c, seed=1, seconds=1.0, chips=1,
                       device_kind="TPU v5 lite",
                       trace=types.SimpleNamespace())     # a traced run


def _reader(name):
    return harness.load_reader(name, harness.Layout())


def _round(n, start, end, tokens, row_lens, kv_lens, phases):
    """One round's annotations: the ``decode_round`` and its five phases
    from their boundaries ``phases`` (six instants)."""
    names = ["round.schedule", "round.assemble", "round.launch",
             "round.fetch", "round.emit"]
    return [("decode_round", start, end - start,
             {"round": n, "pad": 8, "tokens": tokens, "row_lens": row_lens,
              "kv_lens": kv_lens})] + \
        [(name, a, b - a, {"round": n})
         for name, a, b in zip(names, phases, phases[1:])]


def _program(start, kernel_a=1000, gap=0):
    """One round's program of 2000 ns from ``start``: a fusion, the
    kernel in two layers (an idle ``gap`` between them), a copy."""
    s = start
    return [("%fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop", s, 400),
            ("%ragged_paged_attention.1" + KERNEL, s + 400, kernel_a),
            ("%ragged_paged_attention.2" + KERNEL, s + 400 + kernel_a + gap,
             1500 - kernel_a - gap),
            ("%copy-done.1 = bf16[8]{0} copy-done(%c)", s + 1900, 100)]


SERVE_CFG = {"num_layers": 2, "num_heads": 2, "head_dim": 4}


@pytest.fixture()
def serve_run(tmp_path):
    """One chip, stretch [1000, 9000). Three rounds' programs at
    [1000, 3000), [3400, 5400), [7000, 9000); the first has 100 ns of
    idle between its two kernel calls, so the device is idle in
    [2400, 2500), [3000, 3400) and [5400, 7000): 2100 of 8000 ns. The
    serve thread runs three rounds and, between the second and the third,
    waits 1000 ns for work; a second host thread must be ignored."""
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit_rstep(1)", 1000, 2000),
                            ("jit_rstep(1)", 3400, 2000),
                            ("jit_rstep(1)", 7000, 2000)],
            "XLA Ops": _program(1000, gap=100) + _program(3400)
            + _program(7000)},
        "/host:CPU": {
            "whatever-the-profiler-calls-it":
                _round(0, 500, 3100, 5, "1 4", "9 4",
                       [500, 600, 700, 1000, 3020, 3090])
                + _round(1, 3150, 5500, 2, "1 1", "10 5",
                         [3150, 3200, 3250, 3400, 5420, 5490])
                + [("serve.idle_wait", 5600, 1000)]
                + _round(2, 6700, 9100, 1, 1, 11,
                         [6700, 6750, 6800, 7000, 9020, 9090])
                + [("PjitFunction(rstep)", 720, 200)],
            "another-thread": [("round.emit", 0, 9000),
                               ("serve.idle_wait", 0, 9000)]},
    }
    return _run(tmp_path, planes, SERVE_CFG)


def test_gap_attribution_by_hand(serve_run):
    ht = host_trace.of_run(serve_run)
    assert (ht.t0, ht.t1) == (1000.0, 9000.0) and len(ht.chips) == 1
    # the serve thread is the line with the decode_round annotations
    assert len(ht.serve) == 19
    assert host_trace.idle_gaps(ht.chips[0], ht.t0, ht.t1) == \
        [[2400.0, 2500.0], [3000.0, 3400.0], [5400.0, 7000.0]]
    by = {k: round(v * 1e9) for k, v in host_trace.idle_by_phase(ht).items()}
    # [2400, 2500): round 0 is in its fetch.
    # [3000, 3400): fetch 20, emit 70, the round's own tail 10, nothing
    #   50, then round 1's schedule 50, assemble 50, launch 150.
    # [5400, 7000): fetch 20, emit 70, tail 10, nothing 100, the wait
    #   1000, nothing 100, round 2's schedule 50, assemble 50, launch 200.
    assert by == {"round.fetch": 140, "round.emit": 140, "decode_round": 20,
                  "unattributed": 250, "round.schedule": 100,
                  "round.assemble": 100, "round.launch": 350,
                  "serve.idle_wait": 1000}
    assert sum(by.values()) == 2100


def test_clocks_apart_are_brought_together(tmp_path, capsys):
    """The same three rounds with the device's clock 30 ns behind the
    host's, and the runtime's own events to find that out: each program
    is enqueued 5 ns before it really starts and heard of 5 ns after it
    really ends, so the lag lies between 25 and 35 and 30 is applied; the
    attribution is then the one worked out above."""
    lag = 30

    def device(evs):
        return [(n, s - lag, d) for n, s, d in evs]

    planes = {
        "/device:TPU:0": {
            "XLA Modules": device([("jit_rstep(1)", 1000, 2000),
                                   ("jit_rstep(1)", 3400, 2000),
                                   ("jit_rstep(1)", 7000, 2000)]),
            "XLA Ops": device(_program(1000, gap=100) + _program(3400)
                              + _program(7000))},
        "/host:CPU": {
            "serve":
                _round(0, 500, 3100, 5, "1 4", "9 4",
                       [500, 600, 700, 1000, 3020, 3090])
                + _round(1, 3150, 5500, 2, "1 1", "10 5",
                         [3150, 3200, 3250, 3400, 5420, 5490])
                + [("serve.idle_wait", 5600, 1000)]
                + _round(2, 6700, 9100, 1, 1, 11,
                         [6700, 6750, 6800, 7000, 9020, 9090]),
            "the-runtime's-queue": [("DoEnqueueProgram", 980, 15),
                                    ("DoEnqueueProgram", 3380, 15),
                                    ("DoEnqueueProgram", 6980, 15)],
            "the-runtime's-waiter": [
                ("tpu::System::Execute=>Done", 3005, 4),
                ("tpu::System::Execute=>Done", 5405, 4),
                ("tpu::System::Execute=>Done", 9005, 4)]},
    }
    run = _run(tmp_path, planes, SERVE_CFG)
    ht = host_trace.of_run(run)
    assert ht.lag_bounds == (25.0, 35.0) and ht.lag_ns == 30.0
    assert (ht.t0, ht.t1) == (970.0, 8970.0)    # the device's clock
    by = {k: round(v * 1e9) for k, v in host_trace.idle_by_phase(ht).items()}
    assert by == {"round.fetch": 140, "round.emit": 140, "decode_round": 20,
                  "unattributed": 250, "round.schedule": 100,
                  "round.assemble": 100, "round.launch": 350,
                  "serve.idle_wait": 1000}
    assert host_trace.round_gaps_ms(ht) == [pytest.approx(400e-6)]
    _reader("host_bound_idle_pct.serve").read(run)
    assert "runs 0.000 to 0.000 ms behind" in capsys.readouterr().out


def test_serving_readers_by_hand(serve_run, capsys):
    # idle 2100 of 8000 ns, 1000 of it while the loop waited for work
    assert _reader("host_bound_idle_pct.serve").read(serve_run) == \
        pytest.approx(100 * 1100 / 8000)
    out = capsys.readouterr().out
    assert "serve.idle_wait 0.0000" in out and "unattributed" in out
    assert "clocks: the runtime recorded no enqueue" in out
    assert f"cover {100 * 1850 / 2100:.2f} %" in out
    # round 0 -> 1: 400 ns with work pending; 1 -> 2 holds the wait
    assert host_trace.round_gaps_ms(host_trace.of_run(serve_run)) == \
        [pytest.approx(400e-6)]
    assert _reader("round_gap_ms").read(serve_run) == pytest.approx(400e-6)
    # the rounds' need by flops.ragged, heads 2 x 4, bf16: a row of n
    # tokens ending at context kv sees n (kv - n) + n (n + 1) / 2 keys,
    # 4 H D flops each, and reads kv keys and values, n queries and
    # writes n outputs of H D 2 bytes. Round 0, rows (1, 9) and (4, 4):
    # 9 + 10 keys = 608 flops, (18 + 2 + 8 + 8) x 16 = 576 bytes.
    # Round 1, rows (1, 10) and (1, 5): 15 keys = 480 flops,
    # (20 + 2 + 10 + 2) x 16 = 544 bytes. Round 2, row (1, 11):
    # 352 flops, (22 + 2) x 16 = 384 bytes. All memory-bound; two layers.
    least = 2 * (576 + 544 + 384) / V5E["bytes"]
    assert 608 / V5E["flops"] < 576 / V5E["bytes"]
    # the kernel's events: 1000 + 400, 1000 + 500, 1000 + 500 ns
    assert _reader("attn_roofline_pct.serve").read(serve_run) == \
        pytest.approx(100 * least / 4400e-9)


def test_a_round_cut_by_the_stretch_is_left_out(tmp_path):
    """The program in flight when the trace began has no ``round.launch``
    on record; the one whose round closes after the trace is joined but
    its kernel calls count only if the program is whole."""
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit_rstep(1)", 0, 1500),
                            ("jit_rstep(1)", 3400, 2000)],
            # of the first program only the tail is on record
            "XLA Ops": _program(-500)[2:] + _program(3400)},
        "/host:CPU": {"t": _round(1, 3150, 5500, 2, "1 1", "10 5",
                                  [3150, 3200, 3250, 3400, 5420, 5490])},
    }
    run = _run(tmp_path, planes, SERVE_CFG)
    ht = host_trace.of_run(run)
    assert [r.stats["round"] for r, _ in
            host_trace.round_programs(ht, ht.chips[0])] == [1]
    assert host_trace.round_gaps_ms(ht) == []
    assert _reader("round_gap_ms").read(run) is None
    assert _reader("attn_roofline_pct.serve").read(run) == \
        pytest.approx(100 * 2 * 544 / V5E["bytes"] / 1500e-9)


TRAIN_CFG = {"num_layers": 1, "num_heads": 4, "head_dim": 4,
             "max_seq_len": 8,
             "parallel": {"mp_degree": 2, "sharding_degree": 2}}


def _step(start, scale):
    """One step's ops: forward, the recomputed forward and the two
    backward kernels, ``scale`` x 100 ns each (the dkv twice that)."""
    s, d = start, 100 * scale
    return [("%fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop", s, 50),
            ("%flash_attention_fwd.1" + KERNEL, s + 100, d),
            ("%flash_attention_fwd.2" + KERNEL, s + 1000, d),
            ("%flash_attention_bwd_dq.1" + KERNEL, s + 2000, d),
            ("%flash_attention_bwd_dkv.1" + KERNEL, s + 3000, 2 * d)]


def test_training_roofline_by_hand(tmp_path):
    """Two chips, two whole steps each and a third cut by the end of the
    stretch on chip 0's module line; chip 1's kernels take twice as long.
    A program that runs no kernel is no step."""
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit_pure(7)", 0, 4000),
                            ("jit_pure(7)", 4000, 4000),
                            ("jit_convert(3)", 8000, 20),
                            ("jit_pure(7)", 8100, 4000000)],
            "XLA Ops": _step(0, 1) + _step(4000, 1)
            + [("%convert.1 = f32[] convert(%l)", 8000, 20)]
            + _step(8100, 1)[:2]},
        "/device:TPU:1": {
            "XLA Modules": [("jit_pure(7)", 0, 4000),
                            ("jit_pure(7)", 4000, 4000)],
            "XLA Ops": _step(0, 2) + _step(4000, 2)},
    }
    run = _run(tmp_path, planes, TRAIN_CFG,
               workload={"batch": {"sequences": 2}})
    # one device's share: 2 / 2 sequences, 4 / 2 heads, S 8, D 4, bf16.
    # pairs = 1 x 2 x 8 x 9 / 2 = 72. Forward: 4 D pairs = 1152 flops,
    # 4 x (1 x 2 x 8 x 4) x 2 + 64 = 576 bytes; backward: 10 D pairs =
    # 2880 flops, 8 x 64 x 2 + 128 = 1152 bytes: both memory-bound
    assert 2880 / V5E["flops"] < 1152 / V5E["bytes"]
    a_step = (576 + 1152) / V5E["bytes"]
    # chip 0: 500 ns of kernels a step, chip 1: 1000; two whole steps
    want = (100 * 2 * a_step / 1000e-9 + 100 * 2 * a_step / 2000e-9) / 2
    assert _reader("attn_roofline_pct.train").read(run) == \
        pytest.approx(want)


def test_a_program_without_names_gives_nothing(tmp_path):
    """The parent of the PR that named the kernels and annotated the
    round: anonymous kernels, no annotation. Every reader returns None
    and none raises."""
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit_rstep(1)", 1000, 2000)],
            "XLA Ops": [("%rstep.31" + KERNEL, 1000, 1000),
                        ("%checkpoint.74" + KERNEL, 2000, 1000)]},
        "/host:CPU": {"python": [("PjitFunction(rstep)", 0, 100)]},
    }
    run = _run(tmp_path, planes, dict(SERVE_CFG, max_seq_len=8),
               workload={"batch": {"sequences": 2}})
    ht = host_trace.of_run(run)
    assert ht.serve == [] and host_trace.idle_by_phase(ht) is None
    for name in ("attn_roofline_pct.train", "attn_roofline_pct.serve",
                 "round_gap_ms", "host_bound_idle_pct.serve",
                 "padded_token_pct.serve"):
        assert _reader(name).read(run) is None
    # and without a device trace at all
    run.trace = None
    assert host_trace.of_run(run) is None


def test_recorded_decode_sat_rounds(tmp_path):
    """A cut of a real v5e traced run of ``decode-sat`` (PR 27,
    ``tools/trace_cut.py``): a few decode-only rounds at the 32-token pad,
    the chip's programs and ops, the serve thread's annotations with
    their stats as the profiler wrote them, and the runtime's own events
    that bound the lag between the two clocks."""
    cell = harness.load_cell("gpt3-1p3b-serve.decode-sat", harness.Layout())
    cell.layout = harness.Layout(checkout=str(tmp_path))
    where = tmp_path / ".bench_trace" / cell.name
    where.mkdir(parents=True)
    shutil.copy(os.path.join(HERE, "data", "v5e-decode-sat-host.xplane.pb"),
                where)
    run = harness.Run(cell=cell, seed=1, seconds=1.0, chips=1,
                      device_kind="TPU v5 lite",
                      trace=types.SimpleNamespace())
    ht = host_trace.of_run(run)
    chip, = ht.chips
    assert len(chip.modules) == 4 and len(chip.ops) == 5884
    assert len(ht.serve) == 24                  # four rounds, five phases
    assert all(name.startswith("jit_rstep(") for name, _, _ in chip.modules)
    # the device's clock ran 1.47 to 1.97 ms behind the host's in this trace:
    # uncorrected, the programs start before they were enqueued
    low, up = ht.lag_bounds
    assert 1.0e6 < low <= ht.lag_ns <= up < 2.0e6
    joined = host_trace.round_programs(ht, chip)
    assert [r.stats["round"] for r, _ in joined] == [75, 76, 77, 78]
    for r, prog in joined:
        assert (r.stats["pad"], r.stats["tokens"]) == (32, 32)
        rows = host_trace.ints(r.stats["row_lens"])
        kv = host_trace.ints(r.stats["kv_lens"])
        assert rows == [1] * 32 and len(kv) == 32 and min(kv) > 50
        # one kernel call a layer, 2.14 ms each; the program 54.7 ms of a
        # 58.0-59.2 ms round
        calls = [o for o in chip.ops if host_trace.RAGGED in o[0]
                 and prog[0] <= o[1] <= prog[1]]
        assert len(calls) == cell.config["num_layers"] == 24
        assert host_trace.kernel_ns(chip, host_trace.RAGGED, prog) / 1e6 \
            == pytest.approx(51.39, abs=0.03)
        assert (prog[1] - prog[0]) / 1e6 == pytest.approx(54.73, abs=0.03)
        assert 58.0 < (r.end - r.start) / 1e6 < 59.2
    # every row one token further than the round before
    assert host_trace.ints(joined[1][0].stats["kv_lens"]) == \
        [k + 1 for k in host_trace.ints(joined[0][0].stats["kv_lens"])]
    gaps = host_trace.round_gaps_ms(ht)
    assert gaps == pytest.approx([3.445693, 4.003831, 3.679789])
    assert gaps == old_round_gaps_ms(ht)
    assert _reader("round_gap_ms").read(run) == statistics.median(gaps)
    by = host_trace.idle_by_phase(ht)
    idle = sum(by.values())
    # on one clock the launch holds most of the device's idle time, the
    # fetch's tail the next most; a closed loop never waits for work
    assert by["round.launch"] > by["round.fetch"] > by["round.assemble"]
    assert 0.55 < by["round.launch"] / idle < 0.75
    assert by["unattributed"] / idle < 0.1
    assert "serve.idle_wait" not in by
    # so all of the idle is the host's; trace_reduce reads the same idle
    share = _reader("host_bound_idle_pct.serve").read(run)
    assert 4.0 < share < 6.0
    assert share == pytest.approx(trace_reduce.summarize(trace_reduce.load(
        trace_reduce.find_xplane(str(where)))).idle_pct)
    # 32 rows of 50-160 tokens of context need 24-27 MB a layer, 30 us at
    # 819 GB/s, of a kernel that takes 2.14 ms whatever it is given
    assert 1.45 < _reader("attn_roofline_pct.serve").read(run) < 1.6


def test_padded_token_share(tmp_path):
    run = _run(tmp_path, {}, SERVE_CFG)
    run.window_wall = (10.0, 20.0)

    def span(name, ts_s, dur_s, **args):
        return {"name": name, "ph": "X", "ts": ts_s * 1e6,
                "dur": dur_s * 1e6, "args": args}

    run.spans = [span("decode_round", 9.9, 0.2, pad=128, tokens=1),
                 span("decode_round", 11.0, 0.1, pad=32, tokens=20),
                 span("round.launch", 11.0, 0.1, round=3),
                 span("decode_round", 12.0, 0.1, pad=128, tokens=76),
                 span("decode_round", 19.95, 0.1, pad=8, tokens=1)]
    # the two rounds that end inside the window: 96 of 160 tokens valid
    assert _reader("padded_token_pct.serve").read(run) == \
        pytest.approx(40.0)


def test_annotations_of_the_tiny_cell_reach_the_profile(layout):
    """The host side of the path, for real: a traced run of the tiny
    closed-loop cell on the CPU leaves a profile whose ``/host:CPU`` plane
    holds the serve thread's ``decode_round`` annotations with their
    stats and inside them the phases ``host_phases.ROUND_PHASES`` lists
    (six since PR 37). (No TPU plane: the four
    trace-sourced readers return None here, ``test_cells`` checks.)"""
    from jax.profiler import ProfileData
    line = harness.run_cell("tiny-serve.closed", seed=11, seconds=1.0,
                            trace=True, layout=layout,
                            device_check=cpu_devices)
    assert 0 <= line["metrics"]["padded_token_pct.serve"]["value"] < 100
    path = trace_reduce.find_xplane(
        os.path.join(layout.checkout, ".bench_trace", "tiny-serve.closed"))
    serve = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == host_trace.HOST_PLANE:
            for ln in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in ln.events
                       if host_trace._is_phase(e.name)]
                if any(e[0] == "decode_round" for e in evs):
                    serve.append(evs)
    assert len(serve) == 1                      # one serve thread
    rounds = [e for e in serve[0] if e[0] == "decode_round"]
    assert len(rounds) > 5
    for name, start, end, stats in rounds:
        assert {"round", "pad", "tokens", "row_lens", "kv_lens"} <= \
            set(stats)
        rows = host_trace.ints(stats["row_lens"])
        assert sum(rows) == stats["tokens"] <= stats["pad"]
        assert len(host_trace.ints(stats["kv_lens"])) == len(rows)
        inside = [e for e in serve[0] if e[0].startswith("round.")
                  and e[3].get("round") == stats["round"]]
        assert [e[0] for e in inside] == list(host_phases.ROUND_PHASES)
        assert start <= inside[0][1] and inside[-1][2] <= end


# ------------------------------------------- round gaps in linear time (PR 40)

def old_round_gaps_ms(ht):
    """``round_gaps_ms`` as it was until PR 40, kept as the oracle: a
    ``subtract`` against every op of the chip for each gap (which merges
    the whole op list again) and a scan of every wait for each gap."""
    waits = [(s.start, s.end) for s in ht.serve
             if s.name == host_trace.IDLE_WAIT]
    out = []
    for chip in ht.chips:
        busy = [(s, e) for _, s, e in chip.ops]
        joined = host_trace.round_programs(ht, chip)
        for (r0, (_, e0)), (r1, (s1, _)) in zip(joined, joined[1:]):
            if r1.stats["round"] != r0.stats["round"] + 1 or s1 <= e0 \
                    or any(a < s1 and b > e0 for a, b in waits):
                continue
            out.append(trace_reduce.measure(
                trace_reduce.subtract([(e0, s1)], busy)) / 1e6)
    return out


def program_ops(rng, p0, p1, n):
    """``n`` ops of a program at float times: one op over the whole
    program holding the rest (nested), some of them of no length, and
    now and then a copy that runs on past the program's end."""
    ops = [("%while.1", p0, p1)]
    cuts = sorted(rng.uniform(p0, p1) for _ in range(n - 1))
    for i, (a, b) in enumerate(zip([p0] + cuts, cuts + [p1])):
        if i == n - 1:
            break
        ops.append((f"%fusion.{i}", a, a if rng.random() < 0.05 else b))
    if rng.random() < 0.2:
        ops[-1] = ("%copy-done.1", ops[-1][1],
                   p1 + rng.uniform(0, 2e6))
    return ops


def random_serving(rng, rounds, ops_a_round=20, chips=2):
    """A HostTrace of ``chips`` chips with its joins made (what
    ``round_programs`` would return), so that only the gaps are read:
    programs back to back, with no gap, overlapping the one before or a
    few ms apart; a round number skipped now and then; a foreign op in
    some gaps; ``serve.idle_wait`` spans that cover a gap, reach into it
    from either side, or lie between rounds apart from any gap."""
    serve, progs, t, n = [], [], 1e6 + rng.random(), 100
    for _ in range(rounds):
        gap = rng.choice([0.0, -rng.uniform(0, 1e6), rng.uniform(0, 4e6)])
        p0 = t + gap
        p1 = p0 + rng.uniform(2e6, 9e6)
        progs.append((n, p0, p1))
        serve.append(host_trace.Span(host_trace.ROUND, p0 - 1e6, p1 + 5e5,
                                     {"round": n}))
        if rng.random() < 0.3:
            a = rng.uniform(t - 2e6, t + 4e6)
            serve.append(host_trace.Span(host_trace.IDLE_WAIT, a,
                                         a + rng.uniform(0, 3e6), {}))
        t, n = p1, n + (2 if rng.random() < 0.05 else 1)
    serve.sort(key=lambda s: (s.start, -s.end))
    chip_list, joins = [], {}
    for c in range(chips):
        shift = c * 13.7
        ops = []
        for k, (_, p0, p1) in enumerate(progs):
            ops += program_ops(rng, p0 + shift, p1 + shift, ops_a_round)
            if k and rng.random() < 0.1:     # a foreign op in the gap
                a = progs[k - 1][2] + shift
                ops.append(("%fusion.99", a + 10.5, a + 20.25))
        ops.sort(key=lambda e: (e[1], -e[2]))
        chip = host_trace.Chip(f"/device:TPU:{c}", ops, [])
        chip_list.append(chip)
        rnds = [s for s in serve if s.name == host_trace.ROUND]
        joins[chip.name] = [(r, (p0 + shift, p1 + shift))
                            for r, (_, p0, p1) in zip(rnds, progs)]
    ht = host_trace.HostTrace(chip_list, serve, chip_list[0].ops[0][1],
                              max(e for c in chip_list for _, _, e in c.ops))
    ht.joins = joins
    return ht


@pytest.mark.parametrize("seed", range(8))
def test_round_gaps_are_the_old_formulas_number_for_number(seed):
    ht = random_serving(random.Random(seed), rounds=120)
    gaps = host_trace.round_gaps_ms(ht)
    assert gaps == old_round_gaps_ms(ht)        # equal, not approximately
    # some pairs are read, the rest left out (overlapping or touching
    # programs, a wait, a round number skipped); two chips
    assert 0 < len(gaps) < 2 * 119


def count_unions(monkeypatch):
    """-> the list that ``trace_reduce.union`` appends to at each call."""
    calls, union = [], trace_reduce.union
    monkeypatch.setattr(trace_reduce, "union",
                        lambda intervals: calls.append(1) or union(intervals))
    return calls


def test_the_op_list_is_merged_once_a_chip(monkeypatch):
    calls = count_unions(monkeypatch)
    for rounds in (10, 300):
        ht = random_serving(random.Random(rounds), rounds=rounds, chips=2)
        calls.clear()
        assert host_trace.round_gaps_ms(ht)
        assert len(calls) == 2


def test_two_thousand_rounds_of_five_hundred_ops_in_seconds():
    """PR 39's engine gave 846 rounds in an 8 s stretch; the old reader
    took minutes there. 2,000 rounds of 500 ops: well under 5 s here."""
    ht = random_serving(random.Random(7), rounds=2000, ops_a_round=500,
                        chips=1)
    assert len(ht.chips[0].ops) > 1_000_000
    t0 = time.perf_counter()
    gaps = host_trace.round_gaps_ms(ht)
    took = time.perf_counter() - t0
    assert len(gaps) > 300 and took < 5.0, took
