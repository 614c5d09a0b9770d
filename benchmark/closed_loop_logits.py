"""Runner for serving cells of kind ``closed_loop_logits``: the closed
loop of ``closed_loop.py``, on an engine that emits each token's own logit
(``engine.emit_logits`` in the configuration), held to the reference by
NUMBERS at many positions and not by the first and last token alone.

``correct`` is serve.py's check (the emitted token within
``logit_gap_abs`` of the reference's top logit, first and last token of
``sample`` requests) AND, for the same requests, the logit the timed path
emitted at the end of prefill and at ``positions`` decode positions spread
evenly over the answer, against the reference's logit for that token from
its full forward of the same tokens:

* the median of the differences is at most ``logit_median_abs`` (what
  rounding in the stated precision gives; a layer in fewer bits moves
  every position and fails this);
* at most ``flip_share`` of the positions differ by more than
  ``logit_abs``, and none by more than ``logit_flip_abs``. A model that
  picks k of many experts from scores a rounding apart picks, at some
  positions, another expert than the float32 reference: such a position
  is off by one expert's output, not by rounding. The share such
  positions may take is a limit of its own, so that an expert that is
  dropped or a latent row in a narrower type, which put many more
  positions off, fail it.

The reference's forward is made once a sampled request: it is asked for
all positions at serve.py's first call and remembered.
"""
import numpy as np

from . import closed_loop, serve
from .harness import median, pct, say, within

KEYS = {"": closed_loop.KEYS[""],
        "correct": closed_loop.KEYS["correct"] | {
            "positions", "logit_median_abs", "logit_abs", "flip_share",
            "logit_flip_abs", "logit_reason"}}


def spread(first, last, n):
    """``first`` (the end of prefill) and up to ``n`` decode positions
    spread evenly over ``first + 1 .. last``, the last among them."""
    decode = np.unique(np.linspace(first + 1, last,
                                   min(n, last - first)).round().astype(int))
    return [int(first)] + [int(p) for p in decode]


class _Remembering:
    """The family's plain reference, asked once a sequence for every
    position the run will want."""

    def __init__(self, reference, positions):
        self.reference, self.positions = reference, int(positions)
        self.rows = {}        # the sequence's bytes -> (positions, logits)

    def logits_at(self, weights, seq, where):
        key = np.asarray(seq).tobytes()
        if key not in self.rows:
            at = spread(where[0], where[-1], self.positions)
            self.rows[key] = (at, np.asarray(
                self.reference.logits_at(weights, seq, at)))
        at, rows = self.rows[key]
        return rows[[at.index(w) for w in where]]


class _Family:
    """The family module with a remembering reference; everything else is
    the module's own."""

    def __init__(self, fam, positions):
        self._fam = fam
        self.reference = _Remembering(fam.reference, positions)

    def __getattr__(self, name):
        return getattr(self._fam, name)


def run(run, fam, tracer, t_process):
    tol = run.cell.workload["correct"]
    fam = _Family(fam, tol["positions"])
    serve.run(run, fam, tracer, t_process, closed_loop._loop, closed=True)
    _check_logits(run, fam.reference, tol)


def _check_logits(run, ref, tol):
    pad = int(tol["reference_pad"])
    off, checked = [], 0
    for s in run.requests:
        p, g = s.req.prompt_ids, s.req.generated
        if s.req.state != "finished" or len(p) + len(g) - 1 > pad:
            continue
        seq = np.zeros(pad, np.int32)
        ctx = list(p) + list(g[:-1])
        seq[:len(ctx)] = ctx
        hit = ref.rows.get(seq.tobytes())
        if hit is None:
            continue
        at, rows = hit
        emitted = s.req.token_logits
        if len(emitted) != len(g):
            say(f"NOT correct: a request emitted {len(g)} tokens and "
                f"{len(emitted)} logits (was it evicted and recomputed, or "
                "the engine built without emit_logits?)")
            run.correct = False
            return
        checked += 1
        for pos, row in zip(at, rows):
            j = pos - (len(p) - 1)             # the token emitted there
            off.append(abs(float(row[g[j]]) - emitted[j]))
    if not off:
        say("NOT correct: no sampled request's logits to compare")
        run.correct = False
        return
    flips = sum(d > float(tol["logit_abs"]) for d in off) / len(off)
    med, worst = median(off), max(off)
    ok = all([
        within(run, "logit_diff_median", med, float(tol["logit_median_abs"])),
        within(run, "logit_diff_flip_share", flips, float(tol["flip_share"])),
        within(run, "logit_diff_largest", worst,
               float(tol["logit_flip_abs"]))])
    say(f"emitted logits against the reference at {len(off)} positions of "
        f"{checked} requests: |difference| median {med:.5f} (limit "
        f"{tol['logit_median_abs']}), p90 {pct(off, 90):.5f}, p99 "
        f"{pct(off, 99):.5f}, largest {worst:.5f} (limit "
        f"{tol['logit_flip_abs']}); share beyond {tol['logit_abs']}: "
        f"{flips:.4f} (limit {tol['flip_share']}) ({tol['logit_reason']}): "
        f"{'correct' if ok else 'NOT correct'}")
    run.correct = bool(run.correct and ok)
