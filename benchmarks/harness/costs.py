"""Operations and bytes that the measured calls need, computed from shapes.

These are the numerators of every roofline share and MFU the benchmark
reports. They count what the algorithm requires, never what an
implementation happens to execute: recomputation (remat, flash's second
QK^T in the backward) is left out, so a share computed from them cannot
pass 100% unless the time leaves work out.
"""


def causal_window_pairs(seq_len: int, window=None) -> int:
    """(query, key) pairs a causal mask with an optional sliding window keeps:
    query t sees keys (t - window, t], so min(t + 1, window) of them."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    full = window * (window + 1) // 2          # queries 0 .. window-1
    return full + (seq_len - window) * window  # the rest see `window` keys


def flash_fwd_flops(rows: int, heads: int, seq_len: int, head_dim: int,
                    window=None) -> int:
    """Forward attention: QK^T and PV, 2 * head_dim FLOPs each per kept
    (query, key) pair per head."""
    return 4 * head_dim * rows * heads * causal_window_pairs(seq_len, window)


def flash_bwd_flops(rows: int, heads: int, seq_len: int, head_dim: int,
                    window=None) -> int:
    """Backward attention: dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q —
    four matmuls of 2 * head_dim FLOPs per kept pair. The QK^T a flash
    backward recomputes is recomputation and is not counted."""
    return 8 * head_dim * rows * heads * causal_window_pairs(seq_len, window)


def paged_decode_bytes(contexts, kv_heads: int, head_dim: int,
                       page_itemsize: int, window=None) -> int:
    """Bytes one paged-attention decode call must read from the KV pool for
    one layer: K and V of every token each sequence can still see (its
    context, cut to the window). q, the output and the block tables are
    three orders smaller and left out."""
    seen = sum(min(int(c), window) if window else int(c) for c in contexts)
    return 2 * seen * kv_heads * head_dim * page_itemsize


def dense_layer_matmul_params(hidden: int, intermediate: int, heads: int,
                              kv_heads: int, head_dim: int,
                              experts_per_token: int = 1) -> int:
    """Weights one token multiplies in one decoder layer: q, k, v, o
    projections and the gated MLP (three matrices) of every expert the token
    is routed to. Norm scales and the router are left out (under 0.1%)."""
    attn = hidden * head_dim * (2 * heads + 2 * kv_heads)
    mlp = 3 * hidden * intermediate * experts_per_token
    return attn + mlp


def train_flops_per_token(layers: int, hidden: int, intermediate: int,
                          heads: int, kv_heads: int, head_dim: int,
                          vocab: int, seq_len: int, window=None,
                          experts_per_token: int = 1) -> float:
    """FLOPs the forward and backward passes require per trained token:
    6 per matmul weight (2 forward, 4 backward) for the layers and the output
    head, plus attention at its causal window (forward 4d, backward 8d per
    kept pair per head, averaged over the row). The embedding lookup is a
    gather and counts nothing. Recomputation is not counted."""
    weights = layers * dense_layer_matmul_params(
        hidden, intermediate, heads, kv_heads, head_dim, experts_per_token)
    weights += hidden * vocab                      # output head
    pairs_per_token = causal_window_pairs(seq_len, window) / seq_len
    attention = layers * heads * 12 * head_dim * pairs_per_token
    return 6.0 * weights + attention
