"""``host_trace.round_programs`` joins a round to its program by order,
on hand-made traces of an engine that launches round n + 1 while round n
runs: the engine ROADMAP A5 (1) asks for, which the rule "the program that
starts nearest to the launch's close" cannot read. Times are in ns at a
real scale (programs of 6-10 ms, a host that takes 0.9 ms to launch)."""
import pytest

from benchmark import host_trace

from .test_host_trace import KERNEL, SERVE_CFG, _reader, _run

MS = 1_000_000
FIRST, ROUNDS = 100, 20
LAG = 700_000                   # the device's clock behind the host's


def _ops(start, dur):
    """A program's ops: a fusion, then the kernel to the program's end."""
    return [("%fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop", start,
             dur // 4),
            ("%ragged_paged_attention.1" + KERNEL, start + dur // 4,
             dur - dur // 4)]


def overlapped(foreign="jit_logits_at(7)", cut_right=True,
               drop_program=None):
    """-> (planes, truth): ``truth`` maps a round's number to its
    program's (start, end) on the device's clock, ``gaps`` the true idle
    between consecutive rounds' programs in ms.

    The device runs the rounds' programs back to back (0.10-0.20 ms
    apart). The host launches round k 1.5 ms after round k - 1's program
    began, so the program that starts nearest to that launch's close is
    round k - 1's, 2.4 ms before it; round k's own starts 3.6-7.6 ms
    after. It fetches round k after it has launched round k + 1. A
    program of round 99 is on record whose launch is not (in flight when
    the trace began); a foreign program (the reference's forward) runs
    between rounds 109 and 110; round 120's launch is on record and its
    program is not (the trace ended)."""
    mods, ops, host, enq, done = [], [], [], [], []
    truth, gaps = {}, []
    t = 5 * MS
    # round 99's program, its launch before the trace began
    mods.append(("jit_rstep(11)", t, 7 * MS))
    ops += _ops(t, 7 * MS)
    prev_start, prev_end = t, t + 7 * MS
    for k in range(FIRST, FIRST + ROUNDS):
        dur = (6 + (k * 7) % 5) * MS
        gap = 100_000 + 50_000 * (k % 3)
        start = prev_end + gap
        if foreign and k == FIRST + 10:
            # the reference's forward, 0.5 ms, between two rounds
            mods.append((foreign, prev_end + 40_000, 500_000))
            ops.append(("%fusion.9 = f32[8]{0} fusion(%p), kind=kLoop",
                        prev_end + 40_000, 500_000))
            start = prev_end + 540_000 + gap - 40_000
        a = prev_start + 1_500_000           # round k's launch opens
        stats = {"round": k, "pad": 32, "tokens": 32,
                 "row_lens": " ".join(["1"] * 32),
                 "kv_lens": " ".join([str(200 + k)] * 32)}
        host.append(("decode_round", a - 300_000, 1_250_000, stats))
        host.append(("round.launch", a, 900_000, {"round": k}))
        enq.append(("DoEnqueueProgram", a + 500_000, 100_000))
        if k != drop_program:
            mods.append((f"jit_rstep({11 + k % 2})", start, dur))
            ops += _ops(start, dur)
        truth[k] = (start, start + dur)
        if k > FIRST:
            gaps.append(gap / 1e6)
        # round k - 1 is fetched once round k is launched; the fetch
        # returns 1.0 ms after the program's end
        if k > FIRST:
            host.append(("round.fetch", a + 1_000_000,
                         prev_end + 1_000_000 - (a + 1_000_000),
                         {"round": k - 1}))
            done.append(("tpu::System::Execute=>Done", prev_end + 300_000,
                         5_000))
        prev_start, prev_end = start, start + dur
    last = FIRST + ROUNDS
    a = prev_start + 1_500_000
    if cut_right:
        host.append(("decode_round", a - 300_000, 1_250_000,
                     {"round": last, "pad": 32, "tokens": 32,
                      "row_lens": "1", "kv_lens": "9"}))
        host.append(("round.launch", a, 900_000, {"round": last}))
    host.append(("round.fetch", a + 1_000_000,
                 prev_end + 1_000_000 - (a + 1_000_000),
                 {"round": last - 1}))
    done.append(("tpu::System::Execute=>Done", prev_end + 300_000, 5_000))

    def device(evs):
        return [(n, s - LAG, d) for n, s, d in evs]

    planes = {
        "/device:TPU:0": {"XLA Modules": device(mods),
                          "XLA Ops": device(ops)},
        "/host:CPU": {"serve": host, "the-runtime's-queue": enq,
                      "the-runtime's-waiter": done}}
    truth = {k: (s - LAG, e - LAG) for k, (s, e) in truth.items()}
    return planes, truth, gaps


def _nearest(ht, chip):
    """The rule this module had until PR 38."""
    launches = host_trace._by_round(ht, host_trace.LAUNCH)
    out = {}
    for r in ht.serve:
        if r.name == host_trace.ROUND and "pad" in r.stats:
            close = launches[r.stats["round"]].end
            m = min(chip.modules, key=lambda m: abs(m[1] - close))
            if abs(m[1] - close) <= 5e6:
                out[r.stats["round"]] = m[1:]
    return out


def test_overlapped_rounds_join_by_order(tmp_path, capsys):
    planes, truth, gaps = overlapped()
    run = _run(tmp_path, planes, dict(SERVE_CFG, family="gpt",
                                      hidden_size=8, intermediate_size=32,
                                      vocab_size=64))
    ht = host_trace.of_run(run)
    chip, = ht.chips
    assert len(chip.modules) == ROUNDS + 2      # round 99's and a foreign
    joined = host_trace.round_programs(ht, chip)
    assert {r.stats["round"]: p for r, p in joined} == truth
    assert [r.stats["round"] for r, _ in joined] == \
        list(range(FIRST, FIRST + ROUNDS))      # not 99, not 120
    # nearness would have given every round but the first the program of
    # the round before it
    near = _nearest(ht, chip)
    assert all(near[k] == truth[k - 1] for k in range(FIRST + 1,
                                                      FIRST + ROUNDS))
    # the true device gaps; between rounds 109 and 110 the foreign
    # program's 0.5 ms are busy, not idle
    assert host_trace.round_gaps_ms(ht) == pytest.approx(gaps)
    assert _reader("round_gap_ms").read(run) == pytest.approx(0.15)
    # the clocks: every program was enqueued 4-9 ms before it started, so
    # the lower bound is loose; the upper (heard of 0.3 ms after its end)
    # is not, and the lag applied lies within 0.5 ms of the truth
    low, up = ht.lag_bounds
    assert up == LAG + 300_000 and low < LAG - 3 * MS
    assert abs(ht.lag_ns - LAG) <= 500_000
    # the readers that stand on the join read every round
    assert _reader("attn_roofline_pct.serve").read(run) > 0
    assert 0 < _reader("round_mfu_pct.serve").read(run) < 100
    out = capsys.readouterr().out
    assert "in 20 rounds" in out and "in 20 whole rounds" in out
    assert "do NOT join" not in out


def test_without_the_runtimes_events_the_join_is_the_same(tmp_path):
    planes, truth, gaps = overlapped()
    del planes["/host:CPU"]["the-runtime's-queue"]
    del planes["/host:CPU"]["the-runtime's-waiter"]
    ht = host_trace.of_run(_run(tmp_path, planes, SERVE_CFG))
    assert ht.lag_bounds is None and ht.lag_ns == 0.0
    assert {r.stats["round"]: p for r, p in
            host_trace.round_programs(ht, ht.chips[0])} == truth
    assert host_trace.round_gaps_ms(ht) == pytest.approx(gaps)


def test_a_count_that_does_not_agree_gives_no_join(tmp_path, capsys):
    """The program between rounds 109 and 110 is one of the rounds' own
    jitted function (a warm-up launched by another thread) and no round's:
    between the first and the last joined pair a program is left over, so
    the chip gives no join and says so, and the readers read nothing
    rather than a neighbour's numbers."""
    planes, truth, _ = overlapped(foreign="jit_rstep(13)")
    run = _run(tmp_path, planes, SERVE_CFG)
    ht = host_trace.of_run(run)
    assert host_trace.round_programs(ht, ht.chips[0]) == []
    assert host_trace.round_gaps_ms(ht) == []
    for name in ("round_gap_ms", "attn_roofline_pct.serve",
                 "round_mfu_pct.serve"):
        assert _reader(name).read(run) is None
    out = capsys.readouterr().out
    assert "do NOT join by order" in out
    assert "1 program(s) no launch" in out
    assert "22 of 22 programs candidates" in out


def test_a_join_that_is_off_by_a_round_contradicts_the_clocks(tmp_path,
                                                              capsys):
    """Round 107's program is missing from the record (a dropped event).
    On two clocks 3 ms of slack let rounds 100-107 take the programs of
    rounds 99-106, and no count shows it (round 99's program, in flight
    when the trace began, makes up the number); but then a program starts
    before the runtime enqueued it by more than it ends before the
    runtime heard of it, and the join is given up."""
    planes, truth, _ = overlapped(drop_program=FIRST + 7)
    run = _run(tmp_path, planes, SERVE_CFG)
    ht = host_trace.of_run(run)
    low, up = ht.lag_bounds
    assert low > up + 500_000
    assert host_trace.round_programs(ht, ht.chips[0]) == []
    assert _reader("round_gap_ms").read(run) is None
    out = capsys.readouterr().out
    assert out.count("the bounds contradict each other") == 1   # said once


def test_a_sequential_engine_with_a_foreign_program_near_a_launch(tmp_path,
                                                                  capsys):
    """Every round waits for its program (today's engine), and a foreign
    program ends 0.3 ms before a round's own starts: two programs start
    within 5 ms of that launch's close, so that round is no anchor; the
    others are, the foreign program's name is none of theirs, and the
    join is the right one."""
    mods, ops, host = [], [], []
    t, truth = 2 * MS, {}
    for k in range(5):
        a = t                                    # the launch opens
        start, dur = a + 800_000, 12 * MS
        if k == 2:
            mods.append(("jit_logits_at(7)", a - 200_000, 700_000))
            ops.append(("%fusion.9 = f32[8]{0} fusion(%p), kind=kLoop",
                        a - 200_000, 700_000))
        mods.append(("jit_rstep(3)", start, dur))
        ops += _ops(start, dur)
        truth[k] = (start, start + dur)
        host += [("decode_round", a - 300_000, dur + 2_500_000,
                  {"round": k, "pad": 8, "tokens": 1, "row_lens": 1,
                   "kv_lens": 9 + k}),
                 ("round.launch", a, 900_000, {"round": k}),
                 ("round.fetch", a + 900_000, dur + 900_000, {"round": k})]
        t = start + dur + 2 * MS
    planes = {"/device:TPU:0": {"XLA Modules": mods, "XLA Ops": ops},
              "/host:CPU": {"serve": host}}
    ht = host_trace.of_run(_run(tmp_path, planes, SERVE_CFG))
    assert {r.stats["round"]: p for r, p in
            host_trace.round_programs(ht, ht.chips[0])} == truth
    assert "do NOT join" not in capsys.readouterr().out


def test_the_latent_reader_counts_the_latent_layers(tmp_path, capsys):
    """One round whose program holds the latent kernel: a configuration
    that names its layers' kinds is counted by its ``mla`` layers (one of
    seven here), one that does not by ``num_layers`` as before; a count
    that would read over 100 % raises."""
    kernel = "%mla_ragged_attention.1" + KERNEL
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit_rstep(1)", 2 * MS, 4 * MS)],
            "XLA Ops": [("%fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop",
                         2 * MS, MS), (kernel, 3 * MS, 2000),
                        ("%copy-done.1 = bf16[8]{0} copy-done(%c)",
                         6 * MS - 100, 100)]},
        "/host:CPU": {"serve": [
            ("decode_round", MS, 6 * MS,
             {"round": 5, "pad": 8, "tokens": 2, "row_lens": "1 1",
              "kv_lens": "40 24", "latent_rows": 64}),
            ("round.launch", MS + 1000, MS // 2, {"round": 5}),
            ("round.fetch", 2 * MS, 4 * MS + MS // 2, {"round": 5})]}}
    cfg = {"kv_lora_rank": 8, "qk_rope_head_dim": 4,
           "num_attention_heads": 2, "num_layers": 7}
    whole = _reader("mla_roofline_pct.serve").read(
        _run(tmp_path / "a", planes, cfg, cell="a.cell"))
    kinds = ["kda"] * 6 + ["mla"]
    one = _reader("mla_roofline_pct.serve").read(
        _run(tmp_path / "b", planes, dict(cfg, layer_types_run=kinds),
             cell="b.cell"))
    assert 0 < one < 100 and whole == pytest.approx(7 * one)
    assert "in 1 latent layer(s)" in capsys.readouterr().out
    with pytest.raises(RuntimeError, match="count of latent layers"):
        _reader("mla_roofline_pct.serve").read(
            _run(tmp_path / "c", planes, dict(cfg, num_layers=7000),
                 cell="c.cell"))
