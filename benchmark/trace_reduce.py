"""From a profiler trace (``.xplane.pb``) to numbers, with nothing but
``jax.profiler.ProfileData``. Every PR computes these the same way.

What a TPU trace looks like (read by hand from v5e traces of this
benchmark's cells, PR 26): one plane per chip named ``/device:TPU:<n>``;
on it the line ``XLA Ops`` carries one event per executed HLO instruction
whose name is the instruction's whole text, ``%fusion.13 = bf16[..]
fusion(..), kind=..`` (a Pallas kernel's text holds
``custom_call_target="tpu_custom_call"`` and is named after the jitted
function or ``jax.checkpoint``, ``%rstep.31``, ``%checkpoint.74``, never
after the kernel); ``XLA Modules`` carries one event per program launch,
``Steps`` one per step, ``Async XLA Ops`` the copies in flight beside the
ops. Host threads are the plane ``/host:CPU``. Times are nanoseconds on
one clock per file. An op is known here by its instruction name, the text
before `` = `` without the ``%``.

* busy: the union of the op intervals of a device, so nesting and
  overlapping lines are not counted twice; ``busy_s`` is its mean over the
  devices and ``window_s`` the span from the first op's start to the last
  op's end over all devices.
* time by name: *self* time, an op's duration minus what its nested
  children cover, summed over events and averaged over devices.
* collectives: ops whose name starts with one of ``COLLECTIVES``; the
  exposed part is the time in which no other op runs on that device.
* Pallas: ops whose text, in the trace or in the compiled program handed
  in, says ``custom_call_target="tpu_custom_call"``.

Cost (since PR 40, for every reader of a traced run, ``host_trace`` and
``host_phases`` included): O((ops + spans) log) in the stretch. Nothing is
repeated per round or per step over the whole op list: a chip's busy list
is merged once (``union``) and an interval's idle is read from it by
``idle_within``, never by ``subtract`` against every op again.
"""
import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINES = ("XLA Ops",)
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
TPU_CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=")


def instruction_name(text):
    """``%fusion.13 = bf16[8]{0} fusion(...)`` -> ``fusion.13``; a name
    that is no instruction text is returned as it is."""
    m = _INSTRUCTION.match(text)
    return m.group(1) if m else text


def pallas_instructions(hlo_text):
    """Names of the instructions of an optimized HLO text that are Pallas
    (Mosaic) kernels."""
    return {instruction_name(line) for line in hlo_text.splitlines()
            if TPU_CUSTOM_CALL in line}


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return found[-1] if found else None


@dataclasses.dataclass
class Device:
    name: str
    ops: list          # (name, start_ns, end_ns), sorted by start
    pallas: set        # names of the ops whose text is a Pallas kernel's


def load(path, plane_pattern=DEVICE_PLANE, op_lines=OP_LINES):
    """-> [Device] for the planes that are chips."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane_pattern.match(plane.name):
            continue
        ops, names, pallas = [], {}, set()
        for line in plane.lines:
            if line.name not in op_lines:
                continue
            for ev in line.events:
                text = ev.name
                name = names.get(text)
                if name is None:
                    name = names[text] = instruction_name(text)
                    if TPU_CUSTOM_CALL in text:
                        pallas.add(name)
                s = float(ev.start_ns)
                ops.append((name, s, s + float(ev.duration_ns)))
        ops.sort(key=lambda e: (e[1], -e[2]))
        out.append(Device(plane.name, ops, pallas))
    out.sort(key=lambda d: d.name)
    return out


def union(intervals):
    """Merged, sorted, disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def measure(intervals):
    return sum(e - s for s, e in union(intervals))


def subtract(a, b):
    """The part of the union of ``a`` not covered by the union of ``b``."""
    out, b = [], union(b)
    j = 0
    for s, e in union(a):
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def idle_within(merged, a, b):
    """The ns of ``(a, b)`` that ``merged`` (``union``'s output) does not
    cover, in O(log n + the intervals it meets): ``measure(subtract([(a,
    b)], busy))`` for ``merged == union(busy)`` to the last bit, because it
    makes ``subtract``'s pieces, joins those that touch (where a busy
    interval has no length) as ``measure``'s ``union`` does, and sums them
    in the same order."""
    pieces, cur = [], a

    def piece(s, e):
        if pieces and s <= pieces[-1][1]:
            pieces[-1][1] = max(pieces[-1][1], e)
        else:
            pieces.append([s, e])

    k = bisect.bisect_right(merged, a, key=lambda iv: iv[1])
    while k < len(merged) and merged[k][0] < b:
        if merged[k][0] > cur:
            piece(cur, merged[k][0])
        cur = max(cur, merged[k][1])
        k += 1
    if cur < b:
        piece(cur, b)
    return sum(e - s for s, e in pieces)


def self_times(ops):
    """{name: self ns} for one device's ops (sorted by start, longest
    first on ties): a parent's self time excludes its nested children."""
    total = {}
    stack = []          # [name, end, child_ns, dur]

    def close(item):
        name, _, child, dur = item
        total[name] = total.get(name, 0.0) + max(0.0, dur - child)

    for name, s, e in ops:
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] += min(e, stack[-1][1]) - s
        stack.append([name, e, 0.0, e - s])
    while stack:
        close(stack.pop())
    return total


def is_collective(name):
    return name.startswith(COLLECTIVES)


def base_name(name):
    """``fusion.123`` -> ``fusion``: instruction numbering changes with
    every compile, the kind of op does not."""
    return re.sub(r"[.\d]+$", "", name) or name


@dataclasses.dataclass
class Summary:
    devices: int
    window_s: float
    busy_s: float                 # mean over devices
    idle_pct: float
    collective_pct: float
    collective_exposed_pct: float
    pallas_pct_of_busy: float
    top_ops: list                 # [[name, seconds]], self time, mean
    top_gaps: list                # [[label, seconds]]
    by_name_s: dict               # full-name self seconds, mean


def summarize(devices, pallas_ops=()):
    """-> Summary, or None where no op ran on any device."""
    devices = [d for d in devices if d.ops]
    if not devices:
        return None
    pallas_ops = set(pallas_ops).union(*(d.pallas for d in devices))
    t0 = min(d.ops[0][1] for d in devices)
    t1 = max(e for d in devices for _, _, e in d.ops)
    window = t1 - t0
    n = len(devices)
    busy = coll = exposed = pallas = 0.0
    by_name, gaps = {}, []
    for d in devices:
        spans = [(s, e) for _, s, e in d.ops]
        merged = union(spans)
        busy += sum(e - s for s, e in merged)
        c = [(s, e) for name, s, e in d.ops if is_collective(name)]
        other = [(s, e) for name, s, e in d.ops
                 if not is_collective(name) and base_name(name) != "while"]
        coll += measure(c)
        exposed += sum(e - s for s, e in subtract(c, other))
        st = self_times(d.ops)
        for name, ns in st.items():
            by_name[name] = by_name.get(name, 0.0) + ns / n
            if name in pallas_ops:
                pallas += ns
        edges = [[t0, t0]] + merged + [[t1, t1]]
        before = {e: name for name, _, e in d.ops}
        for (_, e0), (s1, _) in zip(edges, edges[1:]):
            if s1 > e0:
                gaps.append((s1 - e0, d.name, before.get(e0, "start")))
    groups = {}
    for name, ns in by_name.items():
        key = ("pallas:" if name in pallas_ops else "") + base_name(name)
        groups[key] = groups.get(key, 0.0) + ns
    top_ops = sorted(([k, v / 1e9] for k, v in groups.items()),
                     key=lambda kv: -kv[1])
    # gaps are labelled by the op they follow (the host's own spans are
    # not on the profiler's clock yet) and summed per label, mean over
    # devices
    by_label = {}
    for ns, _, prev in gaps:
        label = "after " + base_name(prev)
        by_label[label] = by_label.get(label, 0.0) + ns / n / 1e9
    top_gaps = sorted(([k, v] for k, v in by_label.items()),
                      key=lambda kv: -kv[1])[:10]
    return Summary(
        devices=n, window_s=window / 1e9, busy_s=busy / n / 1e9,
        idle_pct=100.0 * (1.0 - busy / n / window),
        collective_pct=100.0 * coll / n / window,
        collective_exposed_pct=100.0 * exposed / n / window,
        pallas_pct_of_busy=100.0 * pallas / busy if busy else 0.0,
        top_ops=top_ops, top_gaps=top_gaps,
        by_name_s={k: v / 1e9 for k, v in by_name.items()})
