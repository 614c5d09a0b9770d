"""Operations and bytes the algorithms need, computed from shapes alone.

These are the yardstick's numerators: model FLOPs per token for
``mfu_pct``, and each attention kernel's operations and bytes for a
roofline share. They count what the mathematics requires — causal
attention counts only the visible half, recomputation in the backward is
NOT counted — so a program that does more work than this does not look
better for it.
"""


def gpt_matmul_params(hidden, ffn, layers, vocab):
    """Weights that take part in a matrix multiplication once per token:
    qkv (3h^2), attention output (h^2), the two MLP matrices (2*h*ffn) in
    every block, and the tied output head (vocab*h). Embedding lookups,
    the position table, biases and norms are not multiplications."""
    return layers * (4 * hidden * hidden + 2 * hidden * ffn) + vocab * hidden


def gpt_train_flops_per_token(hidden, ffn, layers, vocab, seq):
    """Forward plus backward FLOPs per trained token of a dense GPT:
    6 per matmul weight (2 forward, 4 backward) plus causal attention,
    whose forward per sequence and layer is QK^T and PV over the visible
    half, 2 * 2 * hidden * seq*(seq+1)/2, i.e. 2*hidden*(seq+1) a token;
    the backward costs twice the forward."""
    matmul = 6 * gpt_matmul_params(hidden, ffn, layers, vocab)
    attn = 3 * layers * 2 * hidden * (seq + 1)
    return matmul + attn


def flash_fwd(batch, heads, seq, head_dim, itemsize=2):
    """Causal flash attention forward, [B, S, H, D] operands.
    -> (flops, bytes): two matmuls over the visible half; q, k, v read and
    o written once, plus the f32 log-sum-exp row."""
    pairs = batch * heads * seq * (seq + 1) // 2
    flops = 2 * 2 * head_dim * pairs
    nbytes = 4 * batch * heads * seq * head_dim * itemsize \
        + batch * heads * seq * 4
    return flops, nbytes


def flash_bwd(batch, heads, seq, head_dim, itemsize=2):
    """Causal flash attention backward (dq, dk, dv): five matmuls over the
    visible half (recomputed QK^T, dP = dO V^T, dV = P^T dO, dQ = dS K,
    dK = dS^T Q); q, k, v, o, do read and dq, dk, dv written once."""
    pairs = batch * heads * seq * (seq + 1) // 2
    flops = 5 * 2 * head_dim * pairs
    nbytes = 8 * batch * heads * seq * head_dim * itemsize \
        + 2 * batch * heads * seq * 4
    return flops, nbytes


def ragged(row_lens, kv_lens, heads, kv_heads, head_dim, itemsize=2):
    """One ragged paged attention launch. Row r carries ``row_lens[r]``
    query tokens ending at context length ``kv_lens[r]``; token i of the
    row sees kv_lens[r] - row_lens[r] + i + 1 keys.
    -> (flops, bytes): QK^T and PV over the visible keys; each row's K and
    V pages read once, q read and o written once."""
    flops = nbytes = 0
    for n, kv in zip(row_lens, kv_lens):
        if n <= 0:
            continue
        visible = n * (kv - n) + n * (n + 1) // 2
        flops += 2 * 2 * heads * head_dim * visible
        nbytes += 2 * kv * kv_heads * head_dim * itemsize \
            + 2 * n * heads * head_dim * itemsize
    return flops, nbytes


def roofline_seconds(flops, nbytes, peaks):
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
