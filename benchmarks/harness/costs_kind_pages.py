"""Bytes and operations that paged attention must read and do over a cache
that keeps pages by layer kind where each kind has its own KV heads and its
keys and values their own widths, from shapes, beside ``costs.py``: what the
algorithm requires, never what an implementation executes (no row's padding,
no masked pair, no tile's rounding), so a share computed from them cannot
pass 100% unless the time leaves work out.

A configuration states a kind's shape under ``num_attention_heads``,
``num_key_value_heads``, ``head_dim`` and ``v_head_dim``, a windowed layer's
under the same names with ``swa_`` before them; ``hybrid_layer_pattern`` says
which layers are windowed (1) and ``sliding_window`` how far they see.

``joined`` is what the two readers of these costs share on the trace's side:
the stretches of time that ``costs_latent.ticks_with_device_work``'s
intervals cover together.
"""

from benchmarks.harness import costs_latent

FULL, WINDOW = "full", "window"


def has_kinds(hf: dict) -> bool:
    """Whether the configuration states layer kinds the way this module
    reads them."""
    return "hybrid_layer_pattern" in hf and "swa_num_key_value_heads" in hf


def kind_shape(hf: dict, kind: str) -> dict:
    """{heads, kv_heads, d_k, d_v} of a layer kind."""
    pre = "swa_" if kind == WINDOW else ""
    return {"heads": int(hf[pre + "num_attention_heads"]),
            "kv_heads": int(hf[pre + "num_key_value_heads"]),
            "d_k": int(hf[pre + "head_dim"]),
            "d_v": int(hf[pre + "v_head_dim"])}


def layers_by_kind(hf: dict) -> dict:
    pattern = list(hf["hybrid_layer_pattern"])
    return {FULL: pattern.count(0), WINDOW: pattern.count(1)}


def token_bytes(hf: dict, kind: str, itemsize: int) -> int:
    """K and V of one token in one layer of ``kind``: its own KV heads times
    a key and a value at their own widths (2,560 bytes in a full layer and
    5,120 in a windowed one at 4 and 8 heads of 192 + 128 in bfloat16)."""
    at = kind_shape(hf, kind)
    return at["kv_heads"] * (at["d_k"] + at["d_v"]) * itemsize


def least_pool_token_bytes(hf: dict, itemsize: int) -> int:
    """The full layers' pages alone, no row padded: what a live token costs
    at least (5,120 bytes at two full layers)."""
    return layers_by_kind(hf)[FULL] * token_bytes(hf, FULL, itemsize)


def kind_decode_bytes(ctx_tokens: int, ctx_tokens_windowed: int, hf: dict,
                      itemsize: int) -> int:
    """Bytes one decode step must read from the pools: the full layers' K
    and V of ``ctx_tokens`` (every token the batch's sequences hold), the
    windowed layers' of ``ctx_tokens_windowed`` (each context cut to the
    window). q, the outputs, the sinks and the block tables are three orders
    smaller and left out."""
    kinds = layers_by_kind(hf)
    return kinds[FULL] * token_bytes(hf, FULL, itemsize) * int(ctx_tokens) \
        + kinds[WINDOW] * token_bytes(hf, WINDOW, itemsize) \
        * int(ctx_tokens_windowed)


def windowed_chunk_pairs(start: int, tokens: int, window: int) -> int:
    """(query, key) pairs a chunk of ``tokens`` queries at positions
    ``start ..`` keeps behind a window: query t sees ``min(t + 1, window)``
    keys."""
    first, last = int(start), int(start) + int(tokens)
    short = max(min(last, window) - first, 0)      # queries under the window
    return short * first + short * (short + 1) // 2 \
        + (int(tokens) - short) * window


def kind_prefill_flops(start: int, tokens: int, hf: dict) -> int:
    """Operations one chunk's attention requires over all the layers: each
    row's visible keys (all ``start + t + 1`` in a full layer, at most
    ``sliding_window`` in a windowed one) times the kind's query heads times
    a score over ``d_k`` and a value sum over ``d_v``, 2 a multiply-add."""
    kinds = layers_by_kind(hf)
    pairs = {FULL: costs_latent.chunk_pairs(start, tokens),
             WINDOW: windowed_chunk_pairs(start, tokens,
                                          int(hf["sliding_window"]))}
    total = 0
    for kind, layers in kinds.items():
        at = kind_shape(hf, kind)
        total += layers * pairs[kind] * at["heads"] \
            * 2 * (at["d_k"] + at["d_v"])
    return total


def joined(intervals) -> list:
    """The intervals with every run of touching or overlapping ones made one.
    A tick's interval ends where the host saw the step before it end, and
    with a step in flight the device is by then some milliseconds into the
    next program: its first paged call lies across the boundary, inside the
    ticks' time and inside neither tick. A reader that asks whether ONE
    interval holds a call drops that call of every decode program (half the
    full layers' time in a 13 ms tick; my chip run, PR 41: 83.9% read where
    the calls of the same ticks give 62.7%); asked of the joined stretch, it
    is counted."""
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [tuple(iv) for iv in out]
