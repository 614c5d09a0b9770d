"""What the host did, by named phase, read from what the program records
since PR 37 (``paddle_tpu/observability/tracing.py`` lists the names):

* the serve thread's buffer records (``run.spans``): ``decode_round`` with
  its six ``round.*`` phases, ``serve.idle_wait`` and ``serve.turn``, which
  tile the thread's time, each with ``cpu_us`` (the thread's CPU time
  between the record's two instants) beside ``dur``; ``host.gc`` and
  ``jit.trace`` / ``jit.lower`` / ``jit.compile`` events nested in them;
* the training step's annotations on ``/host:CPU`` of a traced run's
  ``.xplane.pb``: ``train_step`` (stat ``step``) with ``step.gather``,
  ``step.launch``, ``step.rebind`` inside it. The thread is the host line
  that holds ``train_step``, whatever the profiler calls it;
* ``tracing.compile_log()``: ``(t_end, kind, fun_name, seconds)`` of every
  trace, lowering and compile since the log began, ``t_end`` on
  ``time.perf_counter()``.

``host_trace.py`` reads the serving round against the device and is left
as it is: its ``labelled`` / ``idle_by_phase`` take ``serve.turn`` and
``round.account`` by their prefixes. Here: the CPU side of the serving
round, the training step against the device, and the compile log.

* a step's program: the steps of the stretch and the ``XLA Modules``
  events of a chip, both in order, joined one to one: a step's program is
  the first not yet taken that starts after the step's ``step.launch``
  opened (2 ms of slack for the clocks) and before the next step's did.
  The counts are printed; a step with no program is left out.
* the clocks: as ``host_trace`` does for a round, from the runtime's own
  host events: a program starts after the ``DoEnqueueProgram`` inside its
  ``step.launch`` and ends before the next ``tpu::System::Execute=>Done``.
  The lag applied is the middle of the largest lower and the smallest
  upper bound; without those events the clocks are taken as one.

A program that lacks the spans (the parent of the PR that added them)
gives nothing to read: every function here then returns ``None`` or an
empty result and never raises. Cost: as ``host_trace``'s (since PR 40),
O((ops + spans) log) in the stretch; a chip's op list is merged once,
never once a step.
"""
import bisect
import dataclasses
import functools
import os
import statistics

from . import host_trace, trace_reduce
from .host_trace import Span

ROUND, TURN, ACCOUNT = "decode_round", "serve.turn", "round.account"
ROUND_PHASES = ("round.schedule", "round.assemble", "round.launch",
                "round.fetch", "round.emit", ACCOUNT)
STALLS = ("host.gc", "jit.trace", "jit.lower", "jit.compile")
TRAIN_STEP, STEP_LAUNCH = "train_step", "step.launch"
STEP_PHASES = ("step.gather", STEP_LAUNCH, "step.rebind")
BETWEEN = "between steps"
SLACK_NS = 2e6


# ------------------------------------------------ the serving round's CPU

def _end(e):
    return e["ts"] + e["dur"]


def round_host_cpu(spans, window_wall):
    """The serve thread's CPU time a round, from the buffer's records.
    -> None where no round of the window carries ``cpu_us``, else a dict:
    ``cpu_ms`` (a value a round: the ``decode_round``'s plus the
    ``serve.turn``'s after it), ``by_phase`` {name: (median wall ms, MEAN
    CPU ms, count)}, ``longest`` [(name, round, wall ms, CPU ms)] (the
    three longest single phases) and ``stalls`` (the ``host.gc`` /
    ``jit.*`` events that lie inside the window, as they are).

    The CPU side is a mean, never a median: the chip's host (gVisor)
    moves a thread's CPU clock in ticks of 10 ms, so one record reads 0 or
    a tick and only a sum over many rounds says what a phase costs (a
    tick falls on a phase in proportion to the CPU time spent in it)."""
    lo, hi = (1e6 * t for t in window_wall)
    rounds = [e for e in spans if e.get("name") == ROUND and lo <= e["ts"]
              and _end(e) <= hi and "cpu_us" in e]
    if not rounds:
        return None
    tid = rounds[0]["tid"]
    mine = [e for e in spans if e.get("tid") == tid and "cpu_us" in e]
    turns = sorted((e for e in mine if e["name"] == TURN),
                   key=lambda e: e["ts"])
    starts = [e["ts"] for e in turns]
    by_phase = {ROUND: rounds}
    cpu_ms = []
    for r in rounds:
        cpu = r["cpu_us"]
        # the turn that opened where this round closed (one reading of
        # the clock for both, so equal up to float rounding)
        i = bisect.bisect_left(starts, _end(r) - 1.0)
        if i < len(turns) and starts[i] <= _end(r) + 1.0:
            cpu += turns[i]["cpu_us"]
            by_phase.setdefault(TURN, []).append(turns[i])
        cpu_ms.append(cpu / 1e3)
    n0, n1 = rounds[0]["args"]["round"], rounds[-1]["args"]["round"]
    phases = [e for e in mine if e["name"] in ROUND_PHASES
              and n0 <= e["args"]["round"] <= n1]
    for e in phases:
        by_phase.setdefault(e["name"], []).append(e)
    longest = sorted(phases + by_phase.get(TURN, []),
                     key=lambda e: -e["dur"])[:3]
    return {
        "cpu_ms": cpu_ms,
        "by_phase": {name: (statistics.median(e["dur"] for e in evs) / 1e3,
                            statistics.mean(e["cpu_us"] for e in evs)
                            / 1e3, len(evs))
                     for name, evs in by_phase.items()},
        "longest": [(e["name"], (e.get("args") or {}).get("round"),
                     e["dur"] / 1e3, e["cpu_us"] / 1e3) for e in longest],
        "stalls": [e for e in spans if e.get("name") in STALLS
                   and lo <= e["ts"] and _end(e) <= hi],
    }


def cpu_lines(got):
    """``round_host_cpu``'s by-phase and longest-phase readings as text."""
    order = (ROUND,) + ROUND_PHASES + (TURN,)
    return [
        "median wall / mean CPU ms by phase: " + ", ".join(
            f"{name} {got['by_phase'][name][0]:.3f} / "
            f"{got['by_phase'][name][1]:.3f}"
            for name in order if name in got["by_phase"]),
        "the three longest single phases: " + "; ".join(
            f"{name} of round {rnd} {wall:.3f} ms wall, {cpu:.3f} ms CPU"
            for name, rnd, wall, cpu in got["longest"])]


def stall_lines(stalls, serve_tid=None, most=12):
    """The window's ``host.gc`` and ``jit.*`` events as text: the
    collections counted by generation, the ``jit.*`` events counted (there
    should be none), the ``most`` longest of either spelled out."""
    gcs = [e for e in stalls if e["name"] == "host.gc"]
    jits = [e for e in stalls if e["name"] != "host.gc"]
    lines = []

    def where(e):
        return "serve thread" if e.get("tid") == serve_tid else \
            f"thread {e.get('tid')}"

    if gcs:
        by_gen = {}
        for e in gcs:
            g = e["args"]["generation"]
            by_gen[g] = by_gen.get(g, 0) + 1
        lines.append(
            f"host.gc inside the window: {len(gcs)} "
            f"({', '.join(f'generation {g}: {n}' for g, n in sorted(by_gen.items()))}), "
            f"{sum(e['dur'] for e in gcs) / 1e3:.2f} ms in all; the longest: "
            + "; ".join(f"{e['dur'] / 1e3:.3f} ms generation "
                        f"{e['args']['generation']} collected "
                        f"{e['args']['collected']} on the {where(e)}"
                        for e in sorted(gcs, key=lambda e: -e["dur"])[:most]))
    else:
        lines.append("host.gc inside the window: none")
    if jits:
        # an inner jit's trace is an event of its own: the longest say what
        # compiled, the count how much came with it
        lines.append(
            f"jit.* events inside the window (expected none): {len(jits)}, "
            f"the longest: " + "; ".join(
                f"{e['name']} {e['args']['fun_name']} "
                f"{e['dur'] / 1e3:.1f} ms on the {where(e)}"
                for e in sorted(jits, key=lambda e: -e["dur"])[:most]))
    else:
        lines.append("jit.* events inside the window: none")
    return lines


# -------------------------------------- the training step against the device

@dataclasses.dataclass
class StepTrace:
    chips: list           # host_trace.Chip, those on which an op ran
    steps: list           # the train_step Spans, sorted by start
    phases: list          # their step.* Spans, sorted by start
    t0: float             # the stretch, as host_trace's; with no chip
    t1: float             # (a CPU rehearsal) the steps' own span
    lag_bounds: tuple = None
    lag_ns: float = 0.0
    joined: dict = dataclasses.field(default_factory=dict)

    @property
    def window_ns(self):
        return self.t1 - self.t0


def _launches(st):
    return {s.stats.get("step"): s for s in st.phases
            if s.name == STEP_LAUNCH}


def step_programs(st, chip):
    """[(train_step Span, (program start, end))] in order: each step that
    has a ``step.launch`` joined to its program on ``chip`` (the module
    doc says how)."""
    launches = _launches(st)
    steps = [s for s in st.steps if s.stats.get("step") in launches]
    out, j = [], 0
    for i, s in enumerate(steps):
        opened = launches[s.stats["step"]].start - SLACK_NS
        nxt = launches[steps[i + 1].stats["step"]].start - SLACK_NS \
            if i + 1 < len(steps) else float("inf")
        while j < len(chip.modules) and chip.modules[j][1] < opened:
            j += 1
        if j < len(chip.modules) and chip.modules[j][1] < nxt:
            out.append((s, chip.modules[j][1:]))
            j += 1
    return out


def _clock_lag(st, enqueued, done):
    """(low, up) in ns, as ``host_trace.clock_lag``: over the steps of
    the first chip, a program starts after the last ``DoEnqueueProgram``
    that closed inside its ``step.launch`` and ends before the first
    ``...=>Done`` that opened after that launch. None where the runtime
    recorded neither."""
    launches = _launches(st)
    lows, ups = [], []
    for s, (p0, p1) in step_programs(st, st.chips[0]):
        launch = launches[s.stats["step"]]
        i = bisect.bisect_right(enqueued, launch.end) - 1
        if i >= 0 and enqueued[i] >= launch.start:
            lows.append(enqueued[i] - p0)
        j = bisect.bisect_left(done, launch.end)
        if j < len(done):
            ups.append(done[j] - p1)
    return (max(lows), min(ups)) if lows and ups else None


@functools.lru_cache(maxsize=1)
def load_steps(path):
    """-> StepTrace, or None where the program recorded no ``train_step``.
    ``chips`` (``host_trace.load``'s) is empty where no op ran on any chip
    (the CPU rehearsals): the host's side can still be read. Parsed once
    per process however many readers ask."""
    from jax.profiler import ProfileData
    steps, phases, enqueued, done = [], [], [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != host_trace.HOST_PLANE:
            continue
        for line in plane.lines:
            mine = []
            for ev in line.events:
                s = float(ev.start_ns)
                if ev.name == TRAIN_STEP or ev.name in STEP_PHASES:
                    mine.append(Span(ev.name, s, s + float(ev.duration_ns),
                                     dict(ev.stats)))
                elif ev.name == host_trace.ENQUEUED:
                    enqueued.append(s + float(ev.duration_ns))
                elif ev.name == host_trace.DONE:
                    done.append(s)
            if any(s.name == TRAIN_STEP for s in mine):
                steps += [s for s in mine if s.name == TRAIN_STEP]
                phases += [s for s in mine if s.name != TRAIN_STEP]
    if not steps:
        return None
    steps.sort(key=lambda s: s.start)
    phases.sort(key=lambda s: s.start)
    ht = host_trace.load(path)
    if ht is None:
        return StepTrace([], steps, phases, steps[0].start, steps[-1].end)
    st = StepTrace(ht.chips, steps, phases, ht.t0, ht.t1)
    st.lag_bounds = _clock_lag(st, sorted(enqueued), sorted(done))
    if st.lag_bounds is not None:
        low, up = st.lag_bounds
        st.lag_ns = (low + up) / 2 if low <= up else low
        for s in steps + phases:        # onto the device's clock
            s.start -= st.lag_ns
            s.end -= st.lag_ns
    return st


def steps_of_run(run):
    """The traced run's StepTrace; None where the run left no profile (an
    untraced run) or the profile holds no ``train_step``."""
    if not run.step_s:          # not a training run: whose profile is it
        return None
    path = trace_reduce.find_xplane(os.path.join(
        run.cell.layout.checkout, ".bench_trace", run.cell.name))
    return None if path is None else load_steps(path)


def step_host_ms(st):
    """([ms a step], {phase: median ms}): ``step.gather`` + ``step.launch``
    + ``step.rebind`` of every step whose three phases lie inside the
    stretch."""
    by_step = {}
    for p in st.phases:
        if p.start >= st.t0 - SLACK_NS and p.end <= st.t1 + SLACK_NS:
            by_step.setdefault(p.stats.get("step"), {})[p.name] = \
                (p.end - p.start) / 1e6
    whole = [d for d in by_step.values() if len(d) == len(STEP_PHASES)]
    return ([sum(d.values()) for d in whole],
            {name: statistics.median(d[name] for d in whole)
             for name in STEP_PHASES} if whole else {})


def step_gaps_ms(st):
    """{chip name: [ms]}: device idle between the end of one step's
    program and the start of the next step's, and what the join found:
    ``st.joined`` {chip name: (steps, programs, joined)}. The chip's busy
    list is merged once, as ``host_trace.round_gaps_ms`` does."""
    out = {}
    for chip in st.chips:
        merged = trace_reduce.union((s, e) for _, s, e in chip.ops)
        pairs = step_programs(st, chip)
        st.joined[chip.name] = (len(st.steps), len(chip.modules),
                                len(pairs))
        gaps = []
        for (s0, (_, e0)), (s1, (p1, _)) in zip(pairs, pairs[1:]):
            if s1.stats["step"] == s0.stats["step"] + 1 and p1 > e0:
                gaps.append(trace_reduce.idle_within(merged, e0, p1) / 1e6)
        if gaps:
            out[chip.name] = gaps
    return out


def step_labelled(st):
    """The training thread's time as disjoint labelled intervals, sorted:
    every ``step.*`` phase as it is, of each ``train_step`` the part none
    of them covers, and the caller's time between one step and the
    next."""
    leaves = [(p.start, p.end, p.name) for p in st.phases]
    bare = trace_reduce.subtract([(s.start, s.end) for s in st.steps],
                                 [(s, e) for s, e, _ in leaves])
    between = [(a.end, b.start, BETWEEN)
               for a, b in zip(st.steps, st.steps[1:]) if b.start > a.end]
    return sorted(leaves + between + [(s, e, TRAIN_STEP) for s, e in bare])


def step_idle_by_phase(st):
    """{label: seconds} of device idle time inside the stretch, mean over
    the chips (``host_trace.attribute``: what no interval covers is
    ``unattributed``)."""
    intervals = step_labelled(st)
    total = {}
    for chip in st.chips:
        gaps = host_trace.idle_gaps(chip, st.t0, st.t1)
        for label, ns in host_trace.attribute(gaps, intervals).items():
            total[label] = total.get(label, 0.0) + ns / len(st.chips) / 1e9
    return total


# ---------------------------------------------------------- the compile log

def compile_split(log, t_open, t_close):
    """The compile log against the window ``(t_open, t_close]`` on
    ``time.perf_counter()``. -> dict: ``lower_s`` (the seconds before the
    window opened in which a ``jit.trace`` or a ``jit.lower`` was under
    way: the UNION of the events' intervals, because an inner jit's trace
    lies inside its caller's), ``lower_sum_s`` (their plain sum),
    ``compile_s`` (the same union for ``jit.compile``), ``costliest``
    [(fun_name, seconds, events)] by summed seconds over all three kinds,
    ``events`` (how many ended before the window) and ``inside`` (the
    events that ended inside it, as they are)."""
    before = [e for e in log if e[0] <= t_open]
    lower = [e for e in before if e[1] in ("jit.trace", "jit.lower")]
    by_fun = {}
    for _, _, fun, s in before:
        tot = by_fun.setdefault(fun, [0.0, 0])
        tot[0] += s
        tot[1] += 1

    def under_way(events):
        return trace_reduce.measure([(t - s, t) for t, _, _, s in events])

    return {
        "lower_s": under_way(lower),
        "lower_sum_s": sum(e[3] for e in lower),
        "compile_s": under_way([e for e in before
                                if e[1] == "jit.compile"]),
        "costliest": sorted(((f, s, n) for f, (s, n) in by_fun.items()),
                            key=lambda x: -x[1])[:10],
        "events": len(before),
        "inside": [e for e in log if t_open < e[0] <= t_close],
    }
