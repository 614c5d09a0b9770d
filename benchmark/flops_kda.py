"""Operations and bytes the delta-rule recurrence of a ``kda`` layer needs,
from shapes alone (``flops.py`` has ``roofline_seconds``). They count what
the mathematics requires and the bytes a kernel MUST move, not those it
happens to: the recurrence's own operations a token whatever form (token by
token, or in chunks through the matrix unit) a kernel runs, a row's state
once in and once out however many work items the row is cut into, and no
padding. The same work whatever implements it.
"""


def kda_ragged(row_lens, heads, key_dim, value_dim, state_itemsize=4,
               token_itemsize=4):
    """One launch of the recurrence of one layer. Row r carries
    ``row_lens[r]`` tokens of one request.
    -> (flops, bytes): a token of a head decays the state
    (``key_dim * value_dim`` products), reads it with the key
    (``2 * key_dim * value_dim``), applies the rank-one update (the same)
    and reads it with the query (the same): ``7 * key_dim * value_dim``
    operations. A row's state of ``heads * key_dim * value_dim`` values is
    read once and written once; a token's q, k, decay (``key_dim`` each), v
    (``value_dim``) and step (1) a head are read and its output
    (``value_dim``) written once."""
    flops = nbytes = 0
    for n in row_lens:
        if n <= 0:
            continue
        flops += 7 * heads * key_dim * value_dim * n
        nbytes += 2 * heads * key_dim * value_dim * state_itemsize \
            + n * heads * (3 * key_dim + 2 * value_dim + 1) * token_itemsize
    return flops, nbytes
