"""Cut a few whole serving rounds out of a traced run's ``.xplane.pb``
into a file small enough to keep under ``benchmark/tests/data/``:

    python3 -m benchmark.tools.trace_cut <dir or .xplane.pb> <out.pb> [first round] [rounds]

Kept: of every chip's plane the ``XLA Modules`` and ``XLA Ops`` events, of
``/host:CPU`` the round and phase annotations with their stats and the
runtime's two events that bound the clocks (``benchmark/host_trace.py``
says which), all inside
the span from the first kept round's opening to the last one's close.
Instruction texts are cut to their first 96 characters, with the Mosaic
custom-call target kept where the text had it; times are moved so that the
cut starts near 0. Everything else in the profile is dropped.
"""
import sys

from benchmark import host_trace, trace_reduce

KEEP = 96


def _text(s):
    return s.replace("\\", "\\\\").replace('"', '\\"')


def _short(name):
    if len(name) <= KEEP:
        return name
    tail = " " + trace_reduce.TPU_CUSTOM_CALL \
        if trace_reduce.TPU_CUSTOM_CALL in name else ""
    return name[:KEEP] + " ..." + tail


def cut(path, first=10, rounds=4):
    """-> the serialized XSpace of ``rounds`` rounds from the ``first``
    one the serve thread has on record."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ht = host_trace.load(path)
    on_record = [s for s in ht.serve if s.name == host_trace.ROUND]
    kept = on_record[first:first + rounds]
    # host_trace has moved the spans onto the device's clock; the file's
    # host events are still on their own
    lo, hi = kept[0].start, kept[-1].end
    span = {True: (lo, hi), False: (lo + ht.lag_ns, hi + ht.lag_ns)}
    out = []
    for plane in data.planes:
        chip = trace_reduce.DEVICE_PLANE.match(plane.name)
        if not chip and plane.name != host_trace.HOST_PLANE:
            continue
        names, stats, lines = {}, {}, []
        for line in plane.lines:
            if chip and line.name not in trace_reduce.OP_LINES + (
                    host_trace.MODULE_LINE,):
                continue
            a, b = span[bool(chip)]
            evs = [ev for ev in line.events
                   if a <= ev.start_ns and ev.start_ns + ev.duration_ns <= b
                   and (chip or host_trace._is_phase(ev.name)
                        or ev.name in (host_trace.ENQUEUED,
                                       host_trace.DONE))]
            if not evs:
                continue
            body = []
            for ev in evs:
                mid = names.setdefault(_short(ev.name), len(names) + 1)
                st = ""
                for k, v in (() if chip else ev.stats):
                    sid = stats.setdefault(k, len(stats) + 1)
                    st += f" stats {{ metadata_id: {sid} " + (
                        f'str_value: "{_text(v)}"' if isinstance(v, str)
                        else f"int64_value: {int(v)}") + " }"
                body.append(
                    f"events {{ metadata_id: {mid} offset_ps: "
                    f"{round((ev.start_ns - lo) * 1000)} duration_ps: "
                    f"{round(ev.duration_ns * 1000)}{st} }}")
            lines.append(f'lines {{ id: {len(lines) + 1} name: '
                         f'"{_text(line.name)}" timestamp_ns: 1000\n'
                         + "\n".join(body) + "\n}")
        meta = [f'event_metadata {{ key: {i} value {{ id: {i} name: '
                f'"{_text(n)}" }} }}' for n, i in names.items()]
        meta += [f'stat_metadata {{ key: {i} value {{ id: {i} name: '
                 f'"{_text(n)}" }} }}' for n, i in stats.items()]
        out.append(f'planes {{ name: "{plane.name}"\n'
                   + "\n".join(lines + meta) + "\n}")
    return ProfileData.text_proto_to_serialized_xspace("\n".join(out))


def main(argv):
    path = argv[1]
    if not path.endswith(".pb"):
        path = trace_reduce.find_xplane(path)
    blob = cut(path, *map(int, argv[3:5]))
    with open(argv[2], "wb") as f:
        f.write(blob)
    print(f"{argv[2]}: {len(blob)} bytes from {path}")


if __name__ == "__main__":
    main(sys.argv)
