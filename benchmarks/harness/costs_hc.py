"""Bytes that the mixing of several residual streams round a sublayer
(hyper-connections, ``hc_mult`` streams of ``hidden_size``) needs, from
shapes, beside ``costs.py``: what the algorithm requires, never what an
implementation executes, so a share computed from them cannot pass 100%
unless the time leaves work out.

Per token and sublayer, with ``n`` streams of ``C`` values: before the
sublayer the streams are read (``n C``) and its input written (``C``); after
it the streams and its output are read (``n C + C``) and the new streams
written (``n C``): ``(3 n + 2) C`` values, 100,352 bytes at 4 streams of
3,584 in bfloat16. The projection onto the ``n^2 + 2 n`` coefficients is
``n C (n^2 + 2 n)`` = 344,064 multiply-adds a token a sublayer: 3.5 ns at
the bf16 peak against 122 ns for the bytes at the bandwidth, so the bytes
bound it and the operations are left out, as are ``phi`` itself (0.7 MB a
sublayer, once a step), the Sinkhorn iterations (on 16 values a token) and
the sum of the streams before the head (rows that reach the head only).

``step_tokens`` selects the program's spans the reader of these costs
(``metrics/hc_mix_roofline.py``) counts tokens from, and ``is_mix_op`` the
device operations both readers (``metrics/hc_mix_share.py`` too) call the
mixing's.
"""

from benchmarks.harness import program_events as pe

MIX_SCOPE = "/hc/"
SUBLAYERS_A_LAYER = 2


def is_mix_op(op) -> bool:
    """An operation traced under the program's ``hc/pre``, ``hc/post`` or
    ``hc/head`` scope."""
    return MIX_SCOPE in op.scope


def mix_bytes_per_token_sublayer(hf: dict, itemsize: int) -> int:
    """``(3 n + 2) C`` values of the streams' type."""
    return (3 * hf["hc_mult"] + 2) * hf["hidden_size"] * itemsize


def mix_bytes(tokens: int, hf: dict, itemsize: int) -> int:
    """What a step program of ``tokens`` rows must move for the mixing of
    every sublayer of every layer."""
    return int(tokens) * SUBLAYERS_A_LAYER * hf["num_hidden_layers"] \
        * mix_bytes_per_token_sublayer(hf, itemsize)


def step_tokens(evs, intervals) -> int:
    """Rows that are no padding of the step programs dispatched inside
    ``intervals`` [(start, end)]: ``tokens`` of every ``serve/prefill_chunk``
    and ``batch`` of every ``serve/step_decode`` span that starts in one (a
    chunk that waits for nothing lies in the interval of the tick that next
    waits, ``costs_latent.ticks_with_device_work``)."""
    def inside(t):
        return any(a <= t <= b for a, b in intervals)
    total = 0
    for e in evs:
        if e.name == pe.PREFILL_CHUNK and inside(e.start):
            total += int(e.arg("tokens") or 0)
        elif e.name == pe.STEP_DECODE and inside(e.start):
            total += int(e.arg("batch") or 0)
    return total
