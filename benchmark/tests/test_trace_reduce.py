"""The trace reduction, on a trace small enough to check by hand and on a
recorded one cut from a real v5e trace of a benchmark cell."""
import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def _xspace(planes):
    """{plane: {line: [(name, start_ns, dur_ns)]}} -> serialized XSpace,
    through the text form jax's ProfileData parses."""
    from jax.profiler import ProfileData
    text = []
    for pname, lines in planes.items():
        names = sorted({n for evs in lines.values() for n, _, _ in evs})
        ids = {n: i + 1 for i, n in enumerate(names)}
        text.append(f'planes {{ name: "{pname}"')
        for i, (lname, evs) in enumerate(lines.items()):
            text.append(f'  lines {{ id: {i + 1} name: "{lname}" '
                        'timestamp_ns: 1000')
            for n, s, d in evs:
                text.append(f'    events {{ metadata_id: {ids[n]} offset_ps: '
                            f'{s * 1000} duration_ps: {d * 1000} }}')
            text.append('  }')
        for n, i in ids.items():
            text.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" }} }}')
        text.append('}')
    return ProfileData.text_proto_to_serialized_xspace("\n".join(text))


@pytest.fixture()
def hand_trace(tmp_path):
    """Two chips over 1000 ns. Chip 0: a while [0, 400) holding
    fusion.1 [0, 100) and the kernel custom-call.7 [100, 300); an
    all-reduce.3 [500, 700) alone; fusion.2 [800, 1000). Chip 1: an
    all-gather.1 [0, 200) under which fusion.1 [100, 200) runs on a second
    op line, then nothing until fusion.2 [900, 1000). A host plane that
    must be ignored."""
    planes = {
        "/device:TPU:0": {"XLA Ops": [
            ("%while.1 = (s32[]) while(%t), body=%b", 0, 400),
            ("%fusion.1 = bf16[8]{0:T(8,128)} fusion(%p), kind=kLoop", 0, 100),
            ('%custom-call.7 = bf16[8]{0} custom-call(%q), '
             'custom_call_target=\\"tpu_custom_call\\"', 100, 200),
            ("all-reduce.3", 500, 200), ("fusion.2", 800, 200)]},
        "/device:TPU:1": {"XLA Ops": [
            ("all-gather.1", 0, 200), ("fusion.1", 100, 100),
            ("fusion.2", 900, 100)],
            "Steps": [("step", 0, 1000)]},
        "/host:CPU": {"python": [("busy-host", 0, 1000)]},
    }
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_xspace(planes))
    return str(path)


def test_by_hand(hand_trace):
    devices = tr.load(hand_trace)
    assert [d.name for d in devices] == ["/device:TPU:0", "/device:TPU:1"]
    assert devices[0].pallas == {"custom-call.7"}     # from its own text
    s = tr.summarize(devices)
    assert s.devices == 2
    assert s.window_s == pytest.approx(1000e-9)
    # busy: chip 0 400 + 200 + 200 = 800; chip 1 200 + 100 = 300
    assert s.busy_s == pytest.approx(550e-9)
    assert s.idle_pct == pytest.approx(45.0)
    # collectives: 200 on each chip; on chip 1 half runs under fusion.1
    assert s.collective_pct == pytest.approx(20.0)
    assert s.collective_exposed_pct == pytest.approx(15.0)
    # the kernel: 200 of 1100 busy ns
    assert s.pallas_pct_of_busy == pytest.approx(100 * 200 / 1100)
    ops = dict(s.top_ops)
    # self time, mean over the chips; the while keeps only what its
    # children do not cover: 400 - 100 - 200
    assert ops["while"] == pytest.approx(100e-9 / 2)
    assert ops["pallas:custom-call"] == pytest.approx(200e-9 / 2)
    assert ops["fusion"] == pytest.approx((100 + 200 + 100 + 100) * 1e-9 / 2)
    assert ops["all-gather"] == pytest.approx(100e-9 / 2)
    gaps = dict(s.top_gaps)
    assert gaps["after fusion"] == pytest.approx(700e-9 / 2)
    assert gaps["after while"] == pytest.approx(100e-9 / 2)
    assert gaps["after all-reduce"] == pytest.approx(100e-9 / 2)


def test_nothing_on_the_device_is_nothing(tmp_path):
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(_xspace({"/host:CPU": {"python": [("x", 0, 10)]}}))
    assert tr.summarize(tr.load(str(path))) is None


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert tr.measure([(0, 2), (1, 3), (5, 7)]) == 5
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [[0, 2], [3, 5]]
    assert tr.subtract([(0, 4), (6, 8)], []) == [[0, 4], [6, 8]]
    assert tr.base_name("all-reduce-start.12") == "all-reduce-start"
    assert tr.instruction_name(
        "%fusion.13 = bf16[50304,2048]{1,0:T(8,128)(2,1)} fusion(%a), "
        "kind=kOutput") == "fusion.13"
    assert tr.instruction_name("jit_pure(98)") == "jit_pure(98)"
    assert tr.is_collective("all-gather-done.3") and \
        not tr.is_collective("fusion.9")


@pytest.mark.parametrize("seed", range(6))
def test_idle_within_is_subtract_to_the_bit(seed):
    """``idle_within`` on the merged list against PR 39's formula,
    ``measure(subtract([(a, b)], busy))``, over nested, touching,
    zero-length and overlapping intervals at float times: equal, not
    approximately equal."""
    import random
    rng = random.Random(4000 + seed)
    busy = []
    for _ in range(300):
        s = rng.uniform(0, 1e6)
        e = s + rng.choice([0.0, rng.uniform(0, 50), rng.uniform(0, 5000)])
        busy.append((s, e))
        if rng.random() < 0.3:          # a child inside, or one touching
            busy.append(((s + e) / 2, e) if rng.random() < 0.5
                        else (e, e + rng.choice([0.0, 7.25])))
    merged = tr.union(busy)
    cuts = [rng.uniform(-1e4, 1.1e6) for _ in range(400)] + \
        [x for iv in merged[:20] for x in iv]
    for _ in range(600):
        a, b = rng.choice(cuts), rng.choice(cuts)
        for lo, hi in ((a, b), (b, a), (a, a)):
            assert tr.idle_within(merged, lo, hi) == \
                tr.measure(tr.subtract([(lo, hi)], busy))
    assert tr.idle_within([], 2.0, 5.5) == 3.5
    assert tr.idle_within(tr.union([(0, 2), (2, 3), (5, 12)]), 0, 10) == 2


def test_pallas_instructions_from_hlo_text():
    text = '''
  %fusion.3 = bf16[8]{0} fusion(%p), kind=kLoop
  %custom-call.7 = bf16[2,16,2048,128]{3,2,1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", operand_layout_constraints={}
  ROOT custom-call.9 = f32[4]{0} custom-call(x), custom_call_target="tpu_custom_call"
  %custom-call.11 = f32[4]{0} custom-call(x), custom_call_target="Sharding"
'''
    assert tr.pallas_instructions(text) == {"custom-call.7",
                                            "custom-call.9"}


# recorded cuts of real v5e traces of this benchmark's cells (PR 26): the
# device plane, the op line's first 600 events. name -> (Pallas kernels
# among the instructions, their share of busy time in %, the op kind that
# takes most self time, window in ms)
RECORDED = {
    "v5e-train-step": (12, 52.253, "pallas:checkpoint", 56.157334),
    "v5e-serve-rounds": (8, 98.238, "pallas:rstep", 69.698479),
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_trace(name):
    kernels, pallas_pct, top, window_ms = RECORDED[name]
    devices = tr.load(os.path.join(HERE, "data", name + ".xplane.pb"))
    assert len(devices) == 1 and len(devices[0].ops) == 600
    # event names are whole instruction texts; the reduction knows an op
    # by its instruction name and a kernel by its custom-call target
    assert all(" = " not in n and not n.startswith("%")
               for n, _, _ in devices[0].ops)
    assert len(devices[0].pallas) == kernels
    s = tr.summarize(devices)
    assert s.window_s * 1e3 == pytest.approx(window_ms)
    assert 0 < s.busy_s <= s.window_s and 0 <= s.idle_pct < 1
    assert s.pallas_pct_of_busy == pytest.approx(pallas_pct, abs=1e-3)
    assert s.top_ops[0][0] == top
    assert s.collective_pct == s.collective_exposed_pct == 0
    # self times partition the busy time of a single op line
    assert sum(v for _, v in s.top_ops) == pytest.approx(s.busy_s, rel=1e-6)
    assert len(s.top_gaps) <= 10
