"""One cell, once, in a new process:

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Refuses (exit code 2, no result line) without a TPU, with fewer chips than
the cell asks for, or where the program under test is not beside it.
Earlier lines of standard output are information, each starting
``[bench]``; the last line is the result, one JSON object. Its last key,
``compared``, holds each number that ``correct`` compared beside its limit;
the same are the last lines of standard error.
"""
import time

T_PROCESS = time.perf_counter()      # set-up starts here

import argparse  # noqa: E402
import json      # noqa: E402
import sys       # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    from benchmark import harness
    line = harness.run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), t_process=T_PROCESS)
    for name, c in line["compared"].items():
        print(f"[bench] compared {name}: {c['value']!r}, limit "
              f"{c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
