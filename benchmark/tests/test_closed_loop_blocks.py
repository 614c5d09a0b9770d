"""A closed loop's pool in blocks (``traffic.closed_loop_clients`` /
``closed_loop_pool``, ``closed_loop._loop``): block 0 is, request for
request and token for token, what every run before PR 38 sent; later
blocks hold the same lengths in another frozen order; a client goes from
one block into the next without a gap or a repeat, on the CPU's tiny
engine as on any other."""
import collections
import hashlib
import json
import os

import numpy as np
import pytest

from benchmark import closed_loop, harness, serve, traffic

from .conftest import BENCH, cpu_devices

# the four real closed-loop cells, and the digest of what the parent's
# ``closed_loop_clients`` (commit 58f9117) returned for two seeds: every
# client's (prompt length, output length) pairs in order and its first
# and last prompt, token for token
CELLS = {
    "gpt3-1p3b-serve.decode-sat": "gpt3-1p3b-serve",
    "kimi-k2p6-serve.reason-long": "kimi-k2p6-serve",
    "trinity-large-serve.mixed-long": "trinity-large-serve",
    "ling-3p0-flash-serve.reason-wide": "ling-3p0-flash-serve",
}
PARENT = {
    ("gpt3-1p3b-serve.decode-sat", 7): "fb98b1b7dfab3958",
    ("gpt3-1p3b-serve.decode-sat", 2 ** 31 + 11): "4f063fec03693a77",
    ("kimi-k2p6-serve.reason-long", 7): "192348d4b35272ca",
    ("kimi-k2p6-serve.reason-long", 2 ** 31 + 11): "ddecf59445a8ed9b",
    ("trinity-large-serve.mixed-long", 7): "2fed4ff164f0a548",
    ("trinity-large-serve.mixed-long", 2 ** 31 + 11): "e4feeba160414913",
    ("ling-3p0-flash-serve.reason-wide", 7): "e2eb0f7082d68cd0",
    ("ling-3p0-flash-serve.reason-wide", 2 ** 31 + 11): "9e46c1c1c1d0777e",
}


def _mix(cell):
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        wl = json.load(f)
    with open(os.path.join(BENCH, "configs", CELLS[cell] + ".json")) as f:
        vocab = json.load(f)["vocab_size"]
    return wl, vocab, int(wl["requests_per_client"])


def _digest(queues):
    h = hashlib.sha256()
    for q in queues:
        h.update(json.dumps([[len(r["prompt"]), r["max_new_tokens"]]
                             for r in q]).encode())
        h.update(json.dumps([list(map(int, q[0]["prompt"])),
                             list(map(int, q[-1]["prompt"]))]).encode())
    return h.hexdigest()[:16]


def _lengths(block):
    return [(len(r["prompt"]), r["max_new_tokens"]) for q in block for r in q]


@pytest.mark.parametrize("cell,seed", sorted(PARENT))
def test_block_0_is_what_the_parent_sent(cell, seed):
    wl, vocab, per = _mix(cell)
    block = traffic.closed_loop_clients(wl, vocab, seed, per)
    assert len(block) == wl["clients"]
    assert all(len(q) == per and isinstance(q[0]["prompt"], list)
               for q in block)
    assert _digest(block) == PARENT[cell, seed]
    # said twice: block 0 by its number is the same call
    assert _digest(traffic.closed_loop_clients(wl, vocab, seed, per, 0)) \
        == PARENT[cell, seed]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_later_block_holds_the_same_lengths_in_another_order(cell):
    wl, vocab, per = _mix(cell)
    b0 = traffic.closed_loop_clients(wl, vocab, 7, per)
    b1 = traffic.closed_loop_clients(wl, vocab, 7, per, 1)
    b2 = traffic.closed_loop_clients(wl, vocab, 7, per, 2)
    for name in ("prompt", "output"):
        i = 0 if name == "prompt" else 1
        sets = [sorted(x[i] for x in _lengths(b)) for b in (b0, b1, b2)]
        assert sets[0] == sets[1] == sets[2], name
    assert _lengths(b0) != _lengths(b1) != _lengths(b2) != _lengths(b0)
    # a block's order is the mix's, its ids the seed's
    other = traffic.closed_loop_clients(wl, vocab, 8, per, 1)
    assert _lengths(other) == _lengths(b1)
    assert not np.array_equal(other[0][0]["prompt"], b1[0][0]["prompt"])
    assert b1[0][0]["prompt"].dtype == np.int32
    assert 0 <= min(r["prompt"].min() for q in b1 for r in q)
    assert max(r["prompt"].max() for q in b1 for r in q) < vocab


def test_two_calls_agree_and_the_count_is_the_mixs():
    wl, vocab, per = _mix("gpt3-1p3b-serve.decode-sat")
    a = traffic.closed_loop_pool(wl, vocab, 11, per, 9000)
    b = traffic.closed_loop_pool(wl, vocab, 11, per, 9000)
    c = traffic.closed_loop_pool(wl, vocab, 12, per, 9000)
    assert len(a) == len(b) == len(c) >= 3
    for x, y in zip(a, b):
        for qx, qy in zip(x, y):
            for rx, ry in zip(qx, qy):
                assert rx["max_new_tokens"] == ry["max_new_tokens"]
                assert np.array_equal(rx["prompt"], ry["prompt"])
    assert [_lengths(x) for x in a] == [_lengths(x) for x in c]
    # every client holds what was asked for, and no block is made in vain
    held = [sum(r["max_new_tokens"] for blk in a for r in blk[i])
            for i in range(wl["clients"])]
    assert min(held) >= 9000
    short = [sum(r["max_new_tokens"] for blk in a[:-1] for r in blk[i])
             for i in range(wl["clients"])]
    assert min(short) < 9000
    # a pool of one block is block 0 alone: nothing a run to date sent
    # depends on how far the pool reaches
    assert _digest(traffic.closed_loop_pool(wl, vocab, 7, per, 1)[0]) == \
        PARENT["gpt3-1p3b-serve.decode-sat", 7]
    assert _digest(a[0]) == _digest(
        traffic.closed_loop_clients(wl, vocab, 11, per))


def test_block_0s_generators_are_the_two_word_keys():
    a = traffic.rng_for(2 ** 31 + 11, 1).integers(0, 1 << 30, 8)
    b = traffic.rng_for(2 ** 31 + 11, 1, 0).integers(0, 1 << 30, 8)
    c = traffic.rng_for(2 ** 31 + 11, 1, 1).integers(0, 1 << 30, 8)
    assert a.tolist() == b.tolist() != c.tolist()
    assert traffic.longest({"dist": "uniform", "min": 4, "max": 16}) == 16
    assert traffic.longest({"dist": "fixed", "value": 9}) == 9


def _two_a_block(layout):
    path = os.path.join(layout.data, "workloads", "tiny-serve.closed.json")
    with open(path) as f:
        mix = json.load(f)
    mix["requests_per_client"] = 2      # the committed file says 400
    with open(path, "w") as f:
        json.dump(mix, f)
    return mix


def test_every_client_passes_its_first_block(layout, monkeypatch, capsys):
    """The tiny engine, two requests a client a block and a window long
    enough that every client ends its first block: no error, tokens
    counted from later blocks, and each client's requests are its queues
    of block 0, 1, 2, ... in order with no gap and no repeat."""
    mix = _two_a_block(layout)
    sent = []
    real = serve.submit

    def submit(eng, r, on_done=None):
        if on_done is not None:         # the loop's, not the warm request
            sent.append((tuple(int(t) for t in r["prompt"]),
                         r["max_new_tokens"]))
        return real(eng, r, on_done=on_done)

    monkeypatch.setattr(serve, "submit", submit)
    line = harness.run_cell("tiny-serve.closed", seed=5, seconds=2.0,
                            trace=False, layout=layout,
                            device_check=cpu_devices)
    out = capsys.readouterr().out
    assert "ran out of requests" not in out
    assert line["correct"] is True and line["failed"] == 0
    n, per = mix["clients"], 2
    assert line["attempted"] == len(sent) > n * per
    cell = harness.load_cell("tiny-serve.closed", layout)
    pool = traffic.closed_loop_pool(mix, cell.config["vocab_size"], 5, per,
                                    10_000)
    where = {}
    for b, block in enumerate(pool):
        for c, q in enumerate(block):
            for j, r in enumerate(q):
                where[tuple(int(t) for t in r["prompt"])] = (
                    c, b * per + j, r["max_new_tokens"])
    seen = collections.defaultdict(list)
    for prompt, out_len in sent:
        c, i, full = where[prompt]
        seen[c].append(i)
        # only a client's very first request is cut (stagger_first)
        assert out_len == full or (i == 0 and out_len < full)
    assert sorted(seen) == list(range(n))
    for c, order in seen.items():
        assert order == list(range(len(order))), c      # no gap, no repeat
        assert len(order) > per                         # into block 1
    entered = int(out.split("so the run entered ")[1].split(" of")[0])
    assert entered == -(-max(len(o) for o in seen.values()) // per) >= 2


def test_the_guard_says_how_many_blocks_were_made(layout, monkeypatch):
    """An engine faster than the floor the pool is made for: the guard
    still speaks, and counts."""
    _two_a_block(layout)
    monkeypatch.setattr(closed_loop, "FLOOR_ROUND_S", 60.0)     # one round
    with pytest.raises(RuntimeError,
                       match=r"ran out of requests after \d+ blocks of 2"):
        harness.run_cell("tiny-serve.closed", seed=5, seconds=2.0,
                         trace=False, layout=layout,
                         device_check=cpu_devices)
