"""The host's phases laid against the device's idle time, from the one
``.xplane.pb`` of a traced run: what ``trace_reduce.py`` cannot say
because it reads the device planes alone.

The program (``paddle_tpu/observability/tracing.py`` lists the names)
records the serving round and its phases as profiler annotations, which
land on ``/host:CPU`` on the same clock as the device's ops:

* ``decode_round`` with stats ``round``, ``pad``, ``tokens``, ``row_lens``,
  ``kv_lens`` (lists as space-separated text);
* inside it, one after the other, ``round.schedule``, ``round.assemble``,
  ``round.launch``, ``round.fetch``, ``round.emit``, each with ``round``;
* ``serve.idle_wait`` while the serve loop has nothing pending.

The serve thread is the host line that holds the ``decode_round``
annotations, whatever the profiler calls it. Kernels are found by their
instruction name, ``flash_attention*`` and ``ragged_paged_attention*``
(``pallas_call(name=...)`` in the program; a jax transform may wrap the
name, ``jvp_flash_attention_fwd_``, so the name is looked for anywhere in
the instruction's).

* stretch: from the first op's start to the last op's end over all chips,
  as in ``trace_reduce``; a chip's idle gaps are the stretch minus the
  union of its op intervals.
* attribution: each gap is shared out among the phases of the serve
  thread that overlap it. The phases of one thread never overlap; the part
  of a ``decode_round`` that none of its phases covers goes to
  ``decode_round`` itself, time no span covers to ``unattributed``.
* a round's program: the ``XLA Modules`` event that starts nearest to the
  close of that round's ``round.launch`` (programs lie tens of ms apart).
* the clocks: the device planes' clock was found to run behind the host
  plane's by 0.3 to 1.3 ms, another amount in every trace (v5e, PR 27: a
  program "started" before the runtime had enqueued it). Causality
  bounds the lag from both sides with two of the runtime's own host
  events: a program starts after its ``DoEnqueueProgram`` (inside
  ``round.launch``) and ends before its ``tpu::System::Execute=>Done``
  (inside ``round.fetch``). The lag is taken as the middle of the largest
  lower and the smallest upper bound over the rounds, and the serve
  thread's spans are moved onto the device's clock by it; without those
  events (another runtime) the clocks are taken as one, and the
  ``[bench]`` line says so.

A program that lacks the annotations or the kernel names (the parent of
the PR that added them) gives nothing to read: every function here then
returns ``None`` or an empty result and never raises.
"""
import bisect
import dataclasses
import functools
import os

from . import trace_reduce

HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
ROUND, LAUNCH, IDLE_WAIT = "decode_round", "round.launch", "serve.idle_wait"
FETCH = "round.fetch"
# the runtime's own host events that bracket a program's life on the device
ENQUEUED, DONE = "DoEnqueueProgram", "tpu::System::Execute=>Done"
PHASE_PREFIXES = ("round.", "serve.")
UNATTRIBUTED = "unattributed"
FLASH, RAGGED = "flash_attention", "ragged_paged_attention"


@dataclasses.dataclass
class Span:
    name: str
    start: float          # ns, the file's clock
    end: float
    stats: dict


@dataclasses.dataclass
class Chip:
    name: str
    ops: list             # (instruction name, start, end), sorted by start
    modules: list         # (program name, start, end), sorted by start

    @functools.cached_property
    def starts(self):
        return [o[1] for o in self.ops]


@dataclasses.dataclass
class HostTrace:
    chips: list           # those on which an op ran
    serve: list           # the serve thread's Spans, sorted by start
    t0: float             # the stretch
    t1: float
    # by how much the device's clock runs behind the host's, ns: the
    # bounds found (None without the runtime's events), the value applied
    lag_bounds: tuple = None
    lag_ns: float = 0.0

    @property
    def window_ns(self):
        return self.t1 - self.t0


def _is_phase(name):
    return name == ROUND or name.startswith(PHASE_PREFIXES)


@functools.lru_cache(maxsize=1)
def load(path):
    """-> HostTrace, or None where no op ran on any chip. Parsed once per
    process however many readers ask."""
    from jax.profiler import ProfileData
    chips, serve, enqueued, done = [], [], [], []
    for plane in ProfileData.from_file(path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            ops, modules, names = [], [], {}
            for line in plane.lines:
                if line.name in trace_reduce.OP_LINES:
                    for ev in line.events:
                        text = ev.name
                        name = names.get(text)
                        if name is None:
                            name = names[text] = \
                                trace_reduce.instruction_name(text)
                        s = float(ev.start_ns)
                        ops.append((name, s, s + float(ev.duration_ns)))
                elif line.name == MODULE_LINE:
                    for ev in line.events:
                        s = float(ev.start_ns)
                        modules.append((ev.name, s,
                                        s + float(ev.duration_ns)))
            if ops:
                ops.sort(key=lambda e: (e[1], -e[2]))
                modules.sort(key=lambda e: e[1])
                chips.append(Chip(plane.name, ops, modules))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans = []
                for ev in line.events:
                    s = float(ev.start_ns)
                    if _is_phase(ev.name):
                        spans.append(Span(ev.name, s,
                                          s + float(ev.duration_ns),
                                          dict(ev.stats)))
                    elif ev.name == ENQUEUED:
                        enqueued.append(s + float(ev.duration_ns))
                    elif ev.name == DONE:
                        done.append(s)
                if any(s.name == ROUND for s in spans):
                    serve += spans
    if not chips:
        return None
    chips.sort(key=lambda c: c.name)
    serve.sort(key=lambda s: (s.start, -s.end))
    ht = HostTrace(chips, serve,
                   min(c.ops[0][1] for c in chips),
                   max(e for c in chips for _, _, e in c.ops))
    ht.lag_bounds = clock_lag(ht, sorted(enqueued), sorted(done))
    if ht.lag_bounds is not None:
        low, up = ht.lag_bounds
        ht.lag_ns = (low + up) / 2 if low <= up else low
        for s in serve:         # onto the device's clock
            s.start -= ht.lag_ns
            s.end -= ht.lag_ns
    return ht


def of_run(run):
    """The traced run's HostTrace; None without a device trace (an
    untraced run, or no TPU plane as in the CPU rehearsals)."""
    if run.trace is None:
        return None
    layout = run.cell.layout
    path = trace_reduce.find_xplane(
        os.path.join(layout.checkout, ".bench_trace", run.cell.name))
    return None if path is None else load(path)


# ---------------------------------------------------------- idle, by phase

def idle_gaps(chip, t0, t1):
    """The chip's idle intervals inside the stretch, sorted."""
    return trace_reduce.subtract([(t0, t1)],
                                 [(s, e) for _, s, e in chip.ops])


def labelled(spans):
    """The serve thread's spans as disjoint labelled intervals, sorted:
    every phase as it is, and of each ``decode_round`` the part that none
    of its phases covers."""
    leaves = [(s.start, s.end, s.name) for s in spans if s.name != ROUND]
    rounds = [(s.start, s.end) for s in spans if s.name == ROUND]
    bare = trace_reduce.subtract(rounds, [(s, e) for s, e, _ in leaves])
    return sorted(leaves + [(s, e, ROUND) for s, e in bare])


def attribute(gaps, intervals):
    """{label: ns} of the gaps' time by the labelled interval that covers
    it; what none covers is ``unattributed``. ``intervals`` are disjoint
    and sorted (``labelled``)."""
    ends = [e for _, e, _ in intervals]
    out = {}
    for gs, ge in gaps:
        left = ge - gs
        i = bisect.bisect_right(ends, gs)
        while i < len(intervals) and intervals[i][0] < ge:
            s, e, label = intervals[i]
            part = min(e, ge) - max(s, gs)
            if part > 0:
                out[label] = out.get(label, 0.0) + part
                left -= part
            i += 1
        if left > 0:
            out[UNATTRIBUTED] = out.get(UNATTRIBUTED, 0.0) + left
    return out


def idle_by_phase(ht):
    """{label: seconds} of device idle time inside the stretch, mean over
    the chips; None where the program recorded no round."""
    if not ht.serve:
        return None
    intervals = labelled(ht.serve)
    total = {}
    for chip in ht.chips:
        for label, ns in attribute(idle_gaps(chip, ht.t0, ht.t1),
                                   intervals).items():
            total[label] = total.get(label, 0.0) + ns / len(ht.chips) / 1e9
    return total


# ------------------------------------------------------ rounds and programs

def ints(value):
    """A stat written as space-separated text, read back as numbers (the
    profiler hands a one-number text back as a number)."""
    if isinstance(value, str):
        return [int(x) for x in value.split()]
    return [int(value)]


def _by_round(ht, name):
    return {s.stats.get("round"): s for s in ht.serve if s.name == name}


def round_programs(ht, chip):
    """[(decode_round Span, (program start, end))] in the order of the
    rounds: every round that says what it launched, joined to the program
    that launch caused on ``chip``: the one that starts nearest to the
    launch's close, and within 5 ms of it (no clock is that far off, and
    no two programs lie that close)."""
    launches = _by_round(ht, LAUNCH)
    starts = [m[1] for m in chip.modules]
    out = []
    for r in ht.serve:
        launch = launches.get(r.stats.get("round"))
        if r.name != ROUND or "pad" not in r.stats or launch is None \
                or not starts:
            continue
        i = bisect.bisect_left(starts, launch.end)
        i = min((j for j in (i - 1, i) if 0 <= j < len(starts)),
                key=lambda j: abs(starts[j] - launch.end))
        if abs(starts[i] - launch.end) <= 5e6:
            out.append((r, chip.modules[i][1:]))
    return out


def clock_lag(ht, enqueued, done):
    """(low, up) in ns: the device's clock runs behind the host's by at
    least ``low`` (no program starts before the runtime enqueued it) and
    at most ``up`` (none ends after the runtime heard of its end), over the
    rounds of the first chip. ``enqueued`` holds the close of every
    ``DoEnqueueProgram``, ``done`` the opening of every ``...=>Done``,
    both sorted; a round's own are those inside its ``round.launch`` and
    its ``round.fetch`` (host events all, so one clock). None where the
    runtime recorded neither."""
    launches, fetches = _by_round(ht, LAUNCH), _by_round(ht, FETCH)
    lows, ups = [], []
    for r, (p0, p1) in round_programs(ht, ht.chips[0]):
        n = r.stats["round"]
        i = bisect.bisect_right(enqueued, launches[n].end) - 1
        if i >= 0 and enqueued[i] >= launches[n].start:
            lows.append(enqueued[i] - p0)
        if n in fetches:
            j = bisect.bisect_left(done, fetches[n].start)
            if j < len(done) and done[j] <= fetches[n].end:
                ups.append(done[j] - p1)
    return (max(lows), min(ups)) if lows and ups else None


def round_gaps_ms(ht):
    """Device idle, in ms, between the end of one round's program and the
    start of the next round's, for every such pair in which the serve loop
    never waited for work (no ``serve.idle_wait`` overlaps the gap)."""
    waits = [(s.start, s.end) for s in ht.serve if s.name == IDLE_WAIT]
    out = []
    for chip in ht.chips:
        busy = [(s, e) for _, s, e in chip.ops]
        joined = round_programs(ht, chip)
        for (r0, (_, e0)), (r1, (s1, _)) in zip(joined, joined[1:]):
            if r1.stats["round"] != r0.stats["round"] + 1 or s1 <= e0 \
                    or any(a < s1 and b > e0 for a, b in waits):
                continue
            out.append(trace_reduce.measure(
                trace_reduce.subtract([(e0, s1)], busy)) / 1e6)
    return out


def kernel_ns(chip, kernel, span):
    """Summed duration of the chip's op events whose instruction name
    holds ``kernel`` and that lie inside ``span`` (start, end)."""
    lo = bisect.bisect_left(chip.starts, span[0])
    hi = bisect.bisect_right(chip.starts, span[1])
    return sum(e - s for name, s, e in chip.ops[lo:hi]
               if kernel in name and e <= span[1])


def inside(ht, span):
    """Whether a program's (start, end) lies wholly inside the stretch. A
    program's event opens a little before its first op and closes a
    little after its last, hence the slack."""
    slack = 1e6
    return span[0] >= ht.t0 - slack and span[1] <= ht.t1 + slack


def whole_programs(ht, chip, kernel):
    """The chip's programs that lie wholly inside the stretch and run
    ``kernel``: [((start, end), kernel ns)]."""
    out = []
    for _, s, e in chip.modules:
        ns = kernel_ns(chip, kernel, (s, e)) if inside(ht, (s, e)) else 0
        if ns:
            out.append(((s, e), ns))
    return out
