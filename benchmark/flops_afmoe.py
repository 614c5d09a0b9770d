"""Operations and bytes the ``afmoe`` family's attention needs, from shapes
alone (``flops.py`` has ``roofline_seconds``; ``flops_mla_moe.moe_grouped``
counts the expert products this family shares). They count what the
mathematics requires and the bytes a kernel MUST move, not those it happens
to: a row's visible keys and values are counted once however many of its
query blocks read them, and no padding is counted. The same work whatever
implements it.
"""


def windowed_ragged(row_lens, kv_lens, heads, kv_heads, head_dim, window,
                    itemsize=2):
    """One launch of grouped-query attention of one layer that looks back
    ``window`` tokens (the token itself included; ``None``: all the way).
    Row r carries ``row_lens[r]`` query tokens ending at context
    ``kv_lens[r]``; the token at position p sees ``min(p + 1, window)``
    rows. -> (flops, bytes): ``4 * heads * head_dim`` operations a query
    token and visible row (a score and an accumulation); the rows some
    token of the row sees, ``min(kv, window + n - 1)``, read once as keys
    and once as values over ``kv_heads``; queries read and outputs written
    once."""
    flops = nbytes = 0
    for n, kv in zip(row_lens, kv_lens):
        if n <= 0:
            continue
        first = kv - n                  # position of the row's first token
        if window is None or kv <= window:
            visible = n * first + n * (n + 1) // 2
            read = kv
        else:
            # tokens at positions below window - 1 see position + 1 rows,
            # the others a whole window
            short = max(0, min(n, window - 1 - first))
            visible = short * first + short * (short + 1) // 2 \
                + (n - short) * window
            read = min(kv, window + n - 1)
        flops += 4 * heads * head_dim * visible
        nbytes += 2 * read * kv_heads * head_dim * itemsize \
            + 2 * n * heads * head_dim * itemsize
    return flops, nbytes
