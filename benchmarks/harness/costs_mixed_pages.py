"""Bytes that paged attention must read from a cache that keeps pages by
layer kind, from shapes, beside ``costs.py``: what the algorithm requires,
never what an implementation executes, so a share computed from them cannot
pass 100% unless the time leaves work out.

A full layer's decode reads K and V of every token its sequences hold; a
windowed layer's those of the last ``sliding_window`` tokens at most. The
pool's cost a live token follows: a full layer's pages a token, a windowed
layer's a constant a sequence.
"""

from benchmarks.harness import costs

FULL, SLIDING = "full_attention", "sliding_attention"


def layers_by_kind(hf: dict) -> dict:
    """{kind: how many of the configuration's layers are of it}."""
    types = hf["layer_types"]
    return {FULL: types.count(FULL), SLIDING: types.count(SLIDING)}


def token_bytes(hf: dict, layers: int, itemsize: int) -> int:
    """K and V of one token in ``layers`` layers."""
    return 2 * layers * hf["num_key_value_heads"] * hf["head_dim"] * itemsize


def mixed_decode_bytes(ctx_tokens: int, ctx_tokens_windowed: int, hf: dict,
                       itemsize: int) -> int:
    """Bytes one decode step must read from the pools: the full layers' K
    and V of ``ctx_tokens`` (every token the batch's sequences hold), the
    windowed layers' of ``ctx_tokens_windowed`` (each context cut to the
    window). q, the outputs and the block tables are three orders smaller
    and left out."""
    kinds = layers_by_kind(hf)
    per_layer = costs.paged_decode_bytes(
        [1], hf["num_key_value_heads"], hf["head_dim"], itemsize)
    return per_layer * (kinds[FULL] * int(ctx_tokens)
                        + kinds[SLIDING] * int(ctx_tokens_windowed))


def uniform_pool_token_bytes(hf: dict, itemsize: int) -> int:
    """What a live token costs a pool that holds every layer's pages for
    the whole context: 20,480 bytes at five layers of 8 x 128 in bfloat16."""
    return token_bytes(hf, len(hf["layer_types"]), itemsize)


def least_pool_token_bytes(hf: dict, itemsize: int) -> int:
    """The full layers' pages alone: what a live token costs at least, the
    windowed layers' constant a sequence spread over a long context (8,192
    bytes at two full layers)."""
    return token_bytes(hf, layers_by_kind(hf)[FULL], itemsize)
