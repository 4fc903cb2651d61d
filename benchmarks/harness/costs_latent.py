"""Operations and bytes that a latent (MLA) cache's attention and a grouped
expert matmul need, from shapes, beside ``costs.py``: what the algorithm
requires, never what an implementation executes, so a share computed from
them cannot pass 100% unless the time leaves work out.

A cached token of one layer is one row of ``kv_lora_rank + qk_rope_head_dim``
values that every query head reads: a decode call's bytes count the row once
(the zero lanes a pool may pad it with are not required, and not counted). A
prefill chunk attends unfolded, over keys and values up-projected per head:
``qk_nope_head_dim + qk_rope_head_dim`` multiply-adds a score and
``v_head_dim`` a value sum. A grouped expert matmul needs three matrices of
every expert that has a row, and three matmuls of every row.

``ticks_with_device_work`` and ``step_counts`` select the program's spans
that the readers of these costs share (``metrics/latent_*_roofline.py``,
``metrics/moe_grouped_roofline.py``, ``metrics/moe_rows_per_touched_expert.py``),
``ends_in`` the kernel calls that the decode rooflines count beside those
spans' bytes (``metrics/paged_kernel_roofline.py``, ``paged_decode_roofline``,
``latent_paged_roofline``, ``mixed_paged_roofline``), and
``is_grouped_expert_op`` the device operations that ``moe_grouped_roofline``
and ``moe_grouped_share`` both call the experts'.
"""

import bisect

from benchmarks.harness import program_events as pe
from benchmarks.harness import trace as tr

#: the args the step programs' counts are put on these spans under
COUNTED_SPANS = (pe.PREFILL_CHUNK, pe.STEP_DECODE)
ROWS, TOUCHED = "expert_rows", "experts_touched"
EXPERT_SCOPE = "/moe/experts"
GROUPED_CALL = "ragged-dot"


def is_grouped_expert_op(op) -> bool:
    """An operation of the routed experts: traced under the program's
    ``moe/experts`` scope (the sort, the gathers, the combine), or XLA:TPU's
    grouped-matmul call, which is named ``ragged-dot-<...>`` and carries its
    own name where its scope would be (my chip run, PR 31: ``tf_op``
    ``ragged-dot-none:``), so the scope alone finds a tenth of the experts'
    time."""
    return EXPERT_SCOPE in op.scope or op.name.startswith(GROUPED_CALL)


def latent_row_bytes(hf: dict, itemsize: int) -> int:
    """One cached token of one layer: ``[ckv ; k_rope]``."""
    return (hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) * itemsize


def latent_decode_bytes(ctx_tokens: int, row_bytes: int) -> int:
    """Bytes one decode call must read from the latent pool for one layer:
    the row of every token its sequences can see. Queries, outputs and block
    tables are three orders smaller and left out."""
    return int(ctx_tokens) * row_bytes


def chunk_pairs(start: int, tokens: int) -> int:
    """(query, key) pairs a causal chunk of ``tokens`` queries at positions
    ``start ..`` keeps: query t sees ``start + t + 1`` keys."""
    return tokens * start + tokens * (tokens + 1) // 2


def latent_prefill_flops(pairs: int, hf: dict) -> int:
    """One layer's unfolded attention over ``pairs`` kept pairs: a score over
    the 192-wide key and a value sum over 128, 2 operations a multiply-add,
    for each head."""
    per_pair = 2 * (hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]
                    + hf["v_head_dim"])
    return per_pair * hf["num_attention_heads"] * int(pairs)


#: (the key that counts a layer's routed experts, the key of one expert's
#: width), by family: DeepSeek's names (JoyAI, Xing4.0, MiMo-V2) and
#: Qwen2-MoE's (Laguna). A further family's names join here.
EXPERT_KEYS = (("n_routed_experts", "moe_intermediate_size"),
               ("num_experts", "moe_intermediate_size"))


def expert_shape(hf: dict):
    """(routed experts a layer holds HERE, one expert's width) under the
    family's own keys, or None where the configuration has no routed
    experts. A configuration that holds a share of a deployment's experts
    states the share under the same key (``reduced`` names it, ``published``
    has the router's width), so the count is of the experts held: the most
    a step can touch a layer, and what its bytes are of."""
    for count, width in EXPERT_KEYS:
        if hf.get(count) and hf.get(width):
            return int(hf[count]), int(hf[width])
    return None


def grouped_expert_flops(rows: int, hf: dict) -> int:
    """``rows`` token-expert pairs through a gated MLP: three matmuls."""
    return 6 * hf["hidden_size"] * expert_shape(hf)[1] * int(rows)


def grouped_expert_bytes(touched: int, hf: dict, itemsize: int) -> int:
    """The three matrices of every expert with at least one row."""
    return 3 * hf["hidden_size"] * expert_shape(hf)[1] * itemsize \
        * int(touched)


def grouped_least_seconds(rows: int, touched: int, hf: dict, itemsize: int,
                          flops_per_s: float, bytes_per_s: float) -> float:
    """The larger of operations over the peak and bytes over the bandwidth."""
    return max(grouped_expert_flops(rows, hf) / flops_per_s,
               grouped_expert_bytes(touched, hf, itemsize) / bytes_per_s)


def ticks_with_device_work(evs, window):
    """[(interval, the tick's ``serve/prefill_chunk`` and ``serve/step_decode``
    spans)] for the ticks that end in a wait for the device
    (``serve/decode_wait``: after a decode batch, or after a prompt's last
    chunk) and whose interval lies wholly inside ``window``. The device runs
    programs in the order they were dispatched and a wait returns after the
    last, so every operation of the programs dispatched since the wait
    before lies between that wait's end and this tick's last: that is the
    interval. It covers a tick of chunks alone that waited for nothing, whose
    counts the program puts on the next span that does wait."""
    a, b = window
    waited = sorted(e.end for e in evs if e.name == pe.DECODE_WAIT)
    out = []
    for _, spans in sorted(pe.by_tick(evs).items()):
        waits = [e for e in spans if e.name == pe.DECODE_WAIT]
        work = [e for e in spans if e.name in COUNTED_SPANS]
        if not waits or not work:
            continue
        first = min(e.start for e in work)
        before = bisect.bisect_right(waited, first)
        lo = waited[before - 1] if before else first
        hi = max(e.end for e in waits)
        if a <= lo and hi <= b:
            out.append(((lo, hi), work))
    return out


def ends_in(ticks):
    """A test ``(end)``: does a device call that ends at ``end`` end inside
    the stretch that the intervals of ``ticks`` (``ticks_with_device_work``'s
    answer) cover, ``lo < end <= hi``? With a step in flight a step's
    programs run while the host is anywhere in its loop, so no host span
    holds a kernel's calls: what holds them is the interval between the
    ends of two waits. The host sees a wait end some hundred microseconds
    after the device began the next program, so a program's first call may
    lie across the edge between two intervals: it is counted whole where it
    ends and never dropped (asked whether ONE interval holds it whole, every
    decode program would lose that call: ``costs_kind_pages.joined``). One
    merge and one bisection a call."""
    stretches = tr.merge(interval for interval, _ in ticks)
    starts = [lo for lo, _ in stretches]

    def test(end: float) -> bool:
        i = bisect.bisect_left(starts, end)
        return i > 0 and end <= stretches[i - 1][1]
    return test


def decode_steps(ticks, *counts):
    """The ``serve/step_decode`` spans among the ticks' work that carry every
    arg of ``counts``: the decode batches whose bytes a decode roofline sets
    against the calls ``ends_in`` finds. With a step in flight a span's
    batch runs in the interval of the tick AFTER the one that stamped it, so
    over a stretch of n ticks the bytes are of steps 1..n and the calls of
    steps 0..n-1: the two ends cancel to within a step's share of the
    stretch, which is the metric's resolution."""
    return [e for _, work in ticks for e in work if e.name == pe.STEP_DECODE
            and all(e.arg(c) is not None for c in counts)]


def step_counts(evs):
    """[(expert_rows, experts_touched)] of the spans that carry both."""
    pairs = [(e.arg(ROWS), e.arg(TOUCHED)) for e in evs
             if e.name in COUNTED_SPANS]
    return [(r, t) for r, t in pairs if r is not None and t is not None]
