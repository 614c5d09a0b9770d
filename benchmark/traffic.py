"""The one general traffic generator. A traffic mix is a data file of
parameters (``benchmark/workloads/<cell>.json``); this module turns the
parameters and ``--seed`` into the inputs, and nothing here knows a cell
by name.

Steadiness rule: the seed never changes *how much* work a run offers, nor
how it bunches. Every length comes from a fixed stratified set (the
mid-quantiles of the distribution the file names) and every inter-arrival
gap from the stratified quantiles of the exponential law; ``SCHEDULE_SEED``
shuffles them once into one frozen sequence, which is part of the traffic
mix and the same in every run. ``--seed`` draws the token ids and the
weights, nothing else. So a tail such as ``ttft_p90_ms`` is the tail of
that one sequence, not of the arrival law: queueing tails and a closed
loop's mix of stages depend on the bunching, and seeds that shuffled or
even rotated the sequence moved ``serve_tokens_per_s`` by 3.5-4.6 %
between runs where one sequence repeats to 0.4 % (my chip runs, PR 26).
"""
import math

import numpy as np

# the one shuffle of every mix's lengths and gaps; not a parameter
SCHEDULE_SEED = 0


def rng_for(seed, stream, block=0):
    """Independent generator per purpose; ``seed`` may exceed 2**31. A
    closed loop's block k > 0 has generators of its own; block 0's are
    the two-word keys every run to date was drawn from."""
    key = [int(seed) & 0xFFFFFFFFFFFFFFFF, int(stream)]
    return np.random.default_rng(key + [int(block)] if block else key)


# ------------------------------------------------------------- lengths

def _quantile(dist, u):
    kind = dist["dist"]
    lo, hi = dist["min"], dist["max"]
    if kind == "uniform":
        x = lo + u * (hi - lo)
    elif kind == "log_uniform":
        x = math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(round(x))


def longest(dist):
    """The largest length ``dist`` can give."""
    return int(dist["value"]) if dist["dist"] == "fixed" \
        else _quantile(dist, 1.0)


def stratified_lengths(dist, n, rng):
    """n lengths at the mid-quantiles (i + 0.5) / n of ``dist``, shuffled."""
    if dist["dist"] == "fixed":
        return [int(dist["value"])] * n
    vals = [_quantile(dist, (i + 0.5) / n) for i in range(n)]
    rng.shuffle(vals)
    return vals


def stratified_gaps(rate, n, rng):
    """n inter-arrival gaps at the mid-quantiles of Exp(rate), rescaled so
    that they sum to exactly n / rate, shuffled: a Poisson process's gaps
    with the sampling noise of their total removed."""
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    gaps *= (n / rate) / gaps.sum()
    rng.shuffle(gaps)
    return gaps


def prompt_tokens(length, vocab, rng):
    """Uniform ids over the vocabulary: prompts share nothing, so the
    prefix cache finds no hit."""
    return rng.integers(0, vocab, size=length, dtype=np.int64).tolist()


# ------------------------------------------------------------ serving

def request_stream(spec, vocab, seed, n):
    """n requests as dicts ``{prompt, max_new_tokens}`` from the mix's
    ``prompt_len`` and ``output_len`` distributions: the sequence of
    length pairs is the mix's own frozen one, the token ids are the
    seed's."""
    base = rng_for(SCHEDULE_SEED, 1)
    plens = stratified_lengths(spec["prompt_len"], n, base)
    olens = stratified_lengths(spec["output_len"], n, base)
    rng = rng_for(seed, 1)
    return [{"prompt": prompt_tokens(p, vocab, rng), "max_new_tokens": o}
            for p, o in zip(plens, olens)]


def open_loop_schedule(spec, vocab, seed, seconds):
    """Arrivals for ``seconds`` at ``spec['rate_per_s']``: -> list of
    ``(due_s, request)`` with due times relative to the window's start.
    The count is round(rate * seconds) and the due times are the mix's
    own, whatever the seed."""
    n = max(1, int(round(spec["rate_per_s"] * seconds)))
    gaps = stratified_gaps(spec["rate_per_s"], n,
                           rng_for(SCHEDULE_SEED, 2))
    due = np.cumsum(gaps) - gaps[0]         # the first arrival opens it
    reqs = request_stream(spec, vocab, seed, n)
    return [(float(t), r) for t, r in zip(due, reqs) if t < seconds]


def closed_loop_clients(spec, vocab, seed, per_client, block=0):
    """``spec['clients']`` queues of ``per_client`` requests each, dealt
    round-robin from the one sequence, so no client is all-long or
    all-short: one *block* of a closed loop's pool. A client that ends
    its queue of block k goes on into its queue of block k + 1.

    Block 0 is ``request_stream`` over ``clients x per_client`` requests,
    the frozen sequence of every run to date. Block k > 0 is the same
    stratified set of lengths in a further frozen order (generators keyed
    by ``k``: ``request_stream`` stratifies over its ``n``, so a longer
    stream would have changed what block 0 sends); its prompts are
    ``int32`` arrays cut from one draw, not lists, since a pool of many
    blocks holds tens of millions of ids."""
    c = int(spec["clients"])
    n = c * per_client
    if not block:
        reqs = request_stream(spec, vocab, seed, n)
    else:
        base = rng_for(SCHEDULE_SEED, 1, block)
        plens = stratified_lengths(spec["prompt_len"], n, base)
        olens = stratified_lengths(spec["output_len"], n, base)
        ids = rng_for(seed, 1, block).integers(
            0, vocab, size=sum(plens), dtype=np.int32)
        cuts = np.cumsum([0] + plens)
        reqs = [{"prompt": ids[a:b], "max_new_tokens": o}
                for a, b, o in zip(cuts, cuts[1:], olens)]
    return [reqs[i::c] for i in range(c)]


def closed_loop_pool(spec, vocab, seed, per_client, tokens_a_client):
    """Blocks 0, 1, ... of ``closed_loop_clients`` until every client's
    queues hold ``tokens_a_client`` output tokens (a closed loop gives a
    client one token a round, so that is a count of rounds): -> list of
    blocks. What a block holds depends on the mix, the seed and its number
    alone, and how many are made on the mix and ``tokens_a_client``:
    nothing a run observes."""
    c = int(spec["clients"])
    held, blocks = [0] * c, []
    while min(held) < tokens_a_client:
        blocks.append(closed_loop_clients(spec, vocab, seed, per_client,
                                          len(blocks)))
        for i, q in enumerate(blocks[-1]):
            held[i] += sum(r["max_new_tokens"] for r in q)
    return blocks


# ----------------------------------------------------------- training

def zipf_batches(spec, vocab, seq, seed, count):
    """``count`` fresh batches of ``spec['sequences']`` sequences of
    ``seq`` + 1 tokens whose ids follow a Zipf unigram law with exponent
    ``spec['zipf_exponent']`` over a seeded permutation of the vocabulary,
    so the loss has something to learn. -> (ids, labels) int32 arrays
    [count, B, seq]; labels are the next tokens."""
    if spec["tokens"] != "zipf":
        raise ValueError(f"unknown token law {spec['tokens']!r}")
    rng = rng_for(seed, 3)
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks ** -float(spec["zipf_exponent"])
    cdf = np.cumsum(p / p.sum())
    perm = rng.permutation(vocab)
    b = int(spec["sequences"])
    u = rng.random((count, b, seq + 1))
    toks = perm[np.minimum(np.searchsorted(cdf, u), vocab - 1)]
    toks = toks.astype(np.int32)
    return toks[..., :-1], toks[..., 1:]
