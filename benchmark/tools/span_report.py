"""What the serve thread did in an UNTRACED run, from the buffer a run
with ``PADDLE_TPU_TRACE=<path>.json`` in its environment exports at exit:

    python3 -m benchmark.tools.span_report <trace.json> [seconds]

Prints what ``round_host_cpu_ms.serve`` prints in a traced run (median
wall and mean CPU time by phase, the three longest single phases, the
``host.gc`` / ``jit.*`` events), over the last ``seconds`` (default 51)
before the last round closed: a closed loop's window ends where its
engine stops. For a run that read far from its twins (PERF.md section 2)
this says which phase grew, and whether on the CPU or off it; lay it
beside a twin's.
"""
import json
import statistics
import sys

from benchmark import host_phases


def report(events, seconds):
    """-> the lines, or None where the export holds no round."""
    spans = [e for e in events if e.get("ph") == "X"]
    rounds = [e for e in spans if e.get("name") == host_phases.ROUND]
    if not rounds:
        return None
    hi = max(e["ts"] + e["dur"] for e in rounds) / 1e6
    got = host_phases.round_host_cpu(spans, (hi - seconds, hi))
    if got is None:
        return None
    lines = [
        f"{len(got['cpu_ms'])} rounds in the last {seconds:.0f} s; the "
        f"serve thread's CPU a round (round + turn): mean "
        f"{statistics.mean(got['cpu_ms']):.3f} ms, "
        f"{sum(got['cpu_ms']) / 1e3:.2f} s in all"] \
        + host_phases.cpu_lines(got)
    return lines + host_phases.stall_lines(got["stalls"], rounds[0]["tid"])


def main(argv):
    with open(argv[1]) as f:
        doc = json.load(f)
    seconds = float(argv[2]) if len(argv) > 2 else 51.0
    if doc.get("droppedEvents"):
        print(f"the buffer dropped its {doc['droppedEvents']} oldest events")
    for line in report(doc["traceEvents"], seconds) or \
            ["no decode_round with cpu_us in this export"]:
        print(line)


if __name__ == "__main__":
    main(sys.argv)
