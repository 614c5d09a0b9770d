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
* a round's program: the chip runs programs in the order they were
  launched, so rounds and programs are joined *by order* under two bounds
  of causality (a program starts no earlier than its ``round.launch``
  opened and ends no later than the ``round.fetch`` of the same round
  closed), whether or not a round waits for its program before the next
  is launched. ``round_programs`` says how, and what is checked.
* the clocks: the device planes' clock was found to run behind the host
  plane's by 0.3 to 1.3 ms, another amount in every trace (v5e, PR 27: a
  program "started" before the runtime had enqueued it). Causality
  bounds the lag from both sides with two of the runtime's own host
  events: a program starts after its ``DoEnqueueProgram`` (inside
  ``round.launch``) and ends before its ``tpu::System::Execute=>Done``
  (inside ``round.fetch``). The lag is taken as the middle of the largest
  lower and the smallest upper bound over the rounds (and no further than
  0.5 ms under the upper one: where programs queue behind one another the
  lower bound is loose), and the serve thread's spans are moved onto the
  device's clock by it; without those events (another runtime) the clocks
  are taken as one, and the ``[bench]`` line says so.

A program that lacks the annotations or the kernel names (the parent of
the PR that added them) gives nothing to read: every function here then
returns ``None`` or an empty result and never raises.

Cost (since PR 40): O((ops + spans) log) in the stretch, as
``trace_reduce`` says. A chip's op list is merged once however many rounds
ask (``round_gaps_ms``, ``idle_gaps``); a program's ops are found by
bisecting ``Chip.starts``; the waits a gap may overlap by bisection.
Nothing is repeated per round over the whole op list.
"""
import bisect
import collections
import dataclasses
import functools
import itertools
import os

from . import trace_reduce
from .harness import say

HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
ROUND, LAUNCH, IDLE_WAIT = "decode_round", "round.launch", "serve.idle_wait"
FETCH = "round.fetch"
# the runtime's own host events that bracket a program's life on the device
ENQUEUED, DONE = "DoEnqueueProgram", "tpu::System::Execute=>Done"
PHASE_PREFIXES = ("round.", "serve.")
UNATTRIBUTED = "unattributed"
# a program "near" a launch's close (no clock is that far off), and what
# the two clocks may differ by before and after they are brought together
NEAR_NS, SLACK_APART_NS, SLACK_ONE_CLOCK_NS = 5e6, 3e6, 1e6
LAG_WIDTH_NS = 1e6
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
    # round_programs' answers by chip, made once
    joins: dict = dataclasses.field(default_factory=dict)

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
        # a program that queues behind another starts long after it was
        # enqueued: the lower bound is then loose and the middle wrong, so
        # the lag is taken no further under the upper bound than the two
        # lay apart where every round waited for its program (0.3-0.5 ms)
        ht.lag_ns = max((low + up) / 2, up - LAG_WIDTH_NS / 2) \
            if low <= up else low
        for s in serve:         # onto the device's clock
            s.start -= ht.lag_ns
            s.end -= ht.lag_ns
        ht.joins.clear()        # made on two clocks; make them on one
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


def _stem(program):
    """``jit_rstep(1205857459157590206)`` -> ``jit_rstep``: one jitted
    function's programs differ in the number alone (one a token pad)."""
    return program.split("(", 1)[0]


def round_programs(ht, chip):
    """[(decode_round Span, (program start, end))] in the order of the
    rounds: every round that says what it launched, joined to the program
    that launch caused on ``chip``. The join goes by order, whether or not
    a round's program has ended before the next round is launched:

    * anchors: a launch made with the device idle (the round before it
      had been fetched) at whose close exactly one program starts within
      ``NEAR_NS``, that program near no other such launch's close: the
      rule this module had until PR 38, which every round but the first
      of an engine that fetches before it launches again meets. A program
      that is no round's (the reference's forward, a warm-up) is told by
      its name: only programs of the anchors' jitted functions are
      candidates (of the most frequent one where nothing anchors);
    * from the last round back, a round takes the latest candidate not yet
      taken that ends before the round's ``round.fetch`` closed and starts
      after its ``round.launch`` opened, each with the slack the clocks
      need. A launch or a program cut by the stretch's edge finds no
      partner and is left out;
    * checked: between the first and the last joined pair no launch and no
      candidate is left over, and every anchor is joined to its program.
      Where that fails the chip gives NO join and a ``[bench]`` line says
      so: the seven readers that stand on this then read nothing, rather
      than a wrong round's numbers."""
    if chip.name not in ht.joins:
        ht.joins[chip.name] = _join(ht, chip)
    return ht.joins[chip.name]


def _join(ht, chip):
    launches, fetches = _by_round(ht, LAUNCH), _by_round(ht, FETCH)
    # a round whose fetch is not on record (cut by the stretch's edge) has
    # no upper bound and takes no part
    rounds = [r for r in ht.serve if r.name == ROUND and "pad" in r.stats
              and r.stats.get("round") in launches
              and r.stats.get("round") in fetches]
    mods = chip.modules
    if not rounds or not mods:
        return []
    if ht.lag_bounds is not None and \
            ht.lag_bounds[0] - ht.lag_bounds[1] > LAG_WIDTH_NS / 2:
        # bounds taken from a join on two clocks; a join that is off by a
        # round has programs start before they were enqueued
        say(f"rounds and programs on {chip.name} do NOT join by order: "
            f"joined before the clocks were brought together, a program "
            f"starts {ht.lag_bounds[0] / 1e6:.3f} ms before the runtime "
            f"enqueued it by the lower bound of the clocks' lag and ends "
            f"{ht.lag_bounds[1] / 1e6:.3f} ms before the runtime heard of "
            "it by the upper: the bounds contradict each other, so some "
            "round was joined to another round's program. No round is "
            "joined on this chip")
        return []
    slack = SLACK_APART_NS if ht.lag_bounds is None or not ht.lag_ns \
        else SLACK_ONE_CLOCK_NS
    starts = [m[1] for m in mods]
    near = {}
    for k in range(1, len(rounds)):
        # launched with the device idle: the round before has been fetched
        launch = launches[rounds[k].stats["round"]]
        if fetches[rounds[k - 1].stats["round"]].end > launch.start:
            continue
        lo = bisect.bisect_left(starts, launch.end - NEAR_NS)
        if bisect.bisect_right(starts, launch.end + NEAR_NS) - lo == 1:
            near[k] = lo
    claimed = collections.Counter(near.values())
    anchors = {k: j for k, j in near.items() if claimed[j] == 1}
    stems = {_stem(mods[j][0]) for j in anchors.values()} or {
        collections.Counter(_stem(m[0]) for m in mods).most_common(1)[0][0]}
    cand = [j for j, m in enumerate(mods) if _stem(m[0]) in stems]
    match, c = {}, len(cand) - 1
    for k in range(len(rounds) - 1, -1, -1):
        n = rounds[k].stats["round"]
        while c >= 0 and mods[cand[c]][2] > fetches[n].end + slack:
            c -= 1              # ends too late for this and every earlier
        if c >= 0 and mods[cand[c]][1] >= launches[n].start - slack:
            match[k] = cand[c]
            c -= 1
    if not match:
        return []
    taken = set(match.values())
    lo, hi = min(taken), max(taken)
    left_rounds = [rounds[k].stats["round"]
                   for k in range(min(match), max(match)) if k not in match]
    left_progs = [j for j in cand if lo < j < hi and j not in taken]
    off = [rounds[k].stats["round"] for k, j in anchors.items()
           if match.get(k) != j]
    if left_rounds or left_progs or off:
        say(f"rounds and programs on {chip.name} do NOT join by order: "
            f"between the first and the last joined pair "
            f"{len(left_rounds)} launch(es) found no program (rounds "
            f"{left_rounds[:5]}) and {len(left_progs)} program(s) no launch "
            f"(starting at {[mods[j][1] for j in left_progs[:5]]} ns); "
            f"{len(off)} round(s) whose launch has one program near its "
            f"close joined another (rounds {off[:5]}); {len(rounds)} "
            f"launches, {len(cand)} of {len(mods)} programs candidates. No "
            "round is joined on this chip, so the readers that need the "
            "join read nothing")
        return []
    return [(rounds[k], mods[match[k]][1:]) for k in sorted(match)]


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
    never waited for work (no ``serve.idle_wait`` overlaps the gap). The
    chip's busy list is merged once and each gap read from it, and the
    waits are looked up by bisection: O((ops + rounds) log) in all, where
    a ``subtract`` against every op a gap was quadratic in the rounds
    (PR 39's 846 rounds held a traced run past the driver's limit)."""
    waits = sorted((s.start, s.end) for s in ht.serve if s.name == IDLE_WAIT)
    starts = [a for a, _ in waits]
    reach = list(itertools.accumulate((b for _, b in waits), max))

    def waited(e0, s1):
        # a wait that opened before the gap closed and ended after it opened
        i = bisect.bisect_left(starts, s1)
        return i > 0 and reach[i - 1] > e0

    out = []
    for chip in ht.chips:
        merged = trace_reduce.union((s, e) for _, s, e in chip.ops)
        joined = round_programs(ht, chip)
        for (r0, (_, e0)), (r1, (s1, _)) in zip(joined, joined[1:]):
            if r1.stats["round"] != r0.stats["round"] + 1 or s1 <= e0 \
                    or waited(e0, s1):
                continue
            out.append(trace_reduce.idle_within(merged, e0, s1) / 1e6)
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
