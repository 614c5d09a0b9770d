"""Run one cell of the ``kda_mla_moe`` family as the benchmark runs it and
then hold the logits it emitted, at the very positions ``correct`` read,
against LOWERED references as well, to learn what the cell's limits should
be. What it prints is never the benchmark's number for the cell.

    python3 -m benchmark.tools.probe_state --workload <cell> --seed <n> --seconds <s>

After the cell's own comparison (the float32 reference) come two more, each
on the same requests and positions, so that the three readings of a seed
differ by the reference alone:

* the reference with every head's recurrent state rounded to bfloat16 after
  every token (``kda_mla_moe_ref``'s ``state_dtype``): the nearest precision
  below the one the configuration states for the state;
* the reference on the program's weights rounded to 8-bit floats (e4m3, one
  scale a matrix, layer by layer: ``tools/probe.py``'s rounding): the
  nearest precision below the one it states for the weights.

The last line carries, beside the cell's own result, which of the lowered
references the cell's limits called correct.
"""
import argparse
import json
import sys
import time

T_PROCESS = time.perf_counter()


def main(argv=None, **run_kw):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    from benchmark import closed_loop_logits, harness
    from benchmark.harness import say
    from benchmark.tools import probe

    held = {}
    load_family = harness.load_family

    class Watched:
        """The family, remembering the weights it hands the reference."""

        def __init__(self, fam):
            self._fam = fam

        def __getattr__(self, name):
            return getattr(self._fam, name)

        def reference_weights(self, model):
            held["weights"] = self._fam.reference_weights(model)
            return held["weights"]

    def eight_bit(tree):
        return jax.tree_util.tree_map(
            lambda a: probe.rounded(a, 8) if hasattr(a, "ndim") else a, tree)

    class Layers:
        """The reference's layers, each rounded when it is reached and
        dropped when the next one is (``probe._Layers``, for layers that
        also say their ``kind``)."""

        def __init__(self, layers):
            self.layers = layers

        def __iter__(self):
            return (eight_bit(layer) for layer in self.layers)

    def lowered(weights):
        yield "state in bfloat16", weights, {"state_dtype": "bfloat16"}
        yield "weights in 8-bit floats", {
            k: Layers(v) if isinstance(v, list) else eight_bit(v)
            for k, v in weights.items()}, {}

    check = closed_loop_logits._check_logits
    verdicts = {}

    def check_all(run, ref, tol):
        check(run, ref, tol)
        own = run.correct
        for label, weights, kw in lowered(held["weights"]):
            say(f"probe: the same positions against the reference with its "
                f"{label}")
            other = closed_loop_logits._Remembering(ref.reference,
                                                    tol["positions"])
            for key, (at, _) in ref.rows.items():
                other.rows[key] = (at, np.asarray(ref.reference.logits_at(
                    weights, np.frombuffer(key, np.int32), at, **kw)))
            run.correct = True
            check(run, other, tol)
            verdicts[label] = run.correct
        run.correct = own

    harness.load_family = lambda config: Watched(load_family(config))
    closed_loop_logits._check_logits = check_all
    try:
        line = harness.run_cell(args.workload, args.seed, args.seconds,
                                False, t_process=T_PROCESS, **run_kw)
    finally:
        harness.load_family = load_family
        closed_loop_logits._check_logits = check
    print(json.dumps(dict(line, probe=True, lowered_called_correct=verdicts)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
