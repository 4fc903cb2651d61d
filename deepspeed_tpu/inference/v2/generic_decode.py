"""Generic paged serving loop: policy-parameterized prefill + decode.

Reference analog: ``inference/v2/model_implementations/inference_transformer_base.py``
— the shared ragged forward skeleton that per-arch containers plug into. Here the
skeleton is jitted pure functions over (policy, config) static args; the
policy (``modules.py``) contributes embed/block/unembed and the pool's page
kind (``kv_cache.page_kind``) everything that knows what a page is: where a
step's rows land, their write, and the attention over the pages. The loop
binds the two: each layer's block is handed one ``attend``, which passes what
the block computed on to the kind with the layer and the step's slots.

What a tick dispatches: the reference runs a prompt's chunk and the running
sequences' tokens as one ragged forward a step (FastGen's Dynamic SplitFuse).
Here that is ``prefill_chunk_g`` handed a decode half: the chunk's rows and
the decode batch's go through the embedding, every block and the output head
together, and only ``attend`` tells them apart, by the operands the kind
calls rows. A step of decode rows alone is ``decode_step_g``.

Every phase of a step sits under a ``jax.named_scope`` whose name reaches the
device trace (an operation's ``tf_op``): ``embed`` and ``lm_head`` here,
``attn/kv_write``, ``attn/paged``, the ``attn/latent_*`` scopes and a state
kind's ``ssm/conv``, ``ssm/scan`` and ``ssm/update`` in the kinds, ``attn/qkv``, ``attn/out``, ``mlp``, ``moe/router`` and
``moe/experts`` in the policies; a scope that holds several kinds of work
(``moe/experts``, ``attn/latent_prefill``, ``attn/latent_paged``) is opened
through its leaves (``telemetry/names.py`` ``SERVED_LEAF_SCOPES``). Metadata
only: the compiled program is the same.

A block returns ``(x, counts or None)``; the three step programs return
``(logits, cache, counts)`` (the logits a pair, the chunk's and the decode
rows', where a chunk carries a decode half), the counts one int32 vector of
``telemetry/names.py`` ``STEP_COUNTER_ARGS`` summed over the layers that
count (sums over what a router has anyway), and empty where none does.
``cache_data`` is whatever the kind's pool is (``BlockedKVCache.pool``): the
structure that goes in comes out.
"""

from functools import partial

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.kv_cache import StateKindError, page_kind


def _summed(counted):
    """The layers' counts summed: [len(STEP_COUNTER_ARGS)] int32, or [0]
    where no layer counted."""
    if not counted:
        return jnp.zeros((0,), jnp.int32)
    return sum(counted[1:], counted[0])


def _step_states(params, cache_data, chunk, decode, policy, cfg,
                 block_size: int, attn_impl: str):
    """The shared forward of a step's rows: a chunk's (``chunk``: tokens
    [Tb] bucket-padded, start, block_table [MB], true_len; or None), then a
    decode batch's (``decode``: tokens, positions [B], block_tables [B, MB],
    valid [B]; or None), embedded and taken through every layer TOGETHER. Only
    the ``attend`` a block is handed knows the halves apart: it cuts the
    operands the kind calls rows (``row_operands``) where the chunk's end,
    has the kind write and attend each half with its own slots and tables
    (``attend_chunk``, ``attend_decode``), and joins the outputs. A chunk's
    sequence and the decoding ones are different sequences, so the halves
    write different blocks and slots; padding rows of both write the trash
    block and the pad slot. Returns (per-row states [Tb + B, ...], updated
    cache, the counts handed out, once for all the rows)."""
    spec = policy.cache_spec(cfg)
    kind = page_kind(spec, cache_data)
    tokens, positions, valid, halves = [], [], [], []

    def half(toks, pos, ok, attend_half, *where):
        at = sum(t.shape[0] for t in tokens)
        halves.append((slice(at, at + toks.shape[0]), attend_half, where))
        tokens.append(toks), positions.append(pos), valid.append(ok)

    if chunk is not None:
        toks, start, block_table, true_len = chunk
        tb = toks.shape[0]
        pos = jnp.minimum(start + jnp.arange(tb), spec.max_seq_len - 1)
        ok = jnp.arange(tb) < true_len
        half(toks, pos, ok, kind.attend_chunk,
             kind.chunk_slots(cache_data, block_table, start, pos, ok,
                              block_size), block_table, start)
    if decode is not None:
        toks, pos, block_tables, ok = decode
        pos = jnp.minimum(pos, spec.max_seq_len - 1)
        half(toks, pos, ok, kind.attend_decode,
             kind.decode_slots(cache_data, block_tables, pos, ok, block_size),
             block_tables, pos)

    def joined(parts):
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    safe_pos, valid = joined(positions), joined(valid)

    with jax.named_scope("embed"):
        x = policy.embed(params, joined(tokens), safe_pos, cfg)

    cache = cache_data
    counted = []
    for i in range(spec.num_layers):
        def attend(*computed, i=i, **how):
            nonlocal cache
            n = kind.row_operands(i)
            outs = []
            for rows, attend_half, where in halves:
                mine = computed[:n] if len(halves) == 1 else \
                    tuple(r[rows] for r in computed[:n])
                if outs:
                    # this half writes the pool the half before has read, and
                    # nothing it computes says so: tied to that half's
                    # output, its write is ordered behind the read (left to
                    # itself XLA:TPU copies a pool of scaled pages whole
                    # instead)
                    mine, outs = jax.lax.optimization_barrier((mine, outs))
                out, cache = attend_half(cache, i, *where, attn_impl, *mine,
                                         *computed[n:], **how)
                outs.append(out)
            return joined(outs)
        x, counts = policy.block(params, i, x, attend, safe_pos, cfg, valid)
        if counts is not None:
            counted.append(counts)
    return x, cache, _summed(counted)


@partial(jax.jit, static_argnames=("policy", "cfg", "block_size", "attn_impl"),
         donate_argnames=("cache_data",))
def prefill_chunk_g(params, cache_data, tokens, start, block_table, true_len,
                    decode=None, *, policy, cfg, block_size: int,
                    attn_impl: str = "auto"):
    """One sequence, one chunk, and where ``decode`` is given a decode batch
    in the same forward pass (FastGen's ragged forward of a step, at static
    shapes): every weight is read once for both. tokens: [Tb]
    (bucket-padded); start: the chunk's offset in the sequence; block_table:
    [MB] block ids (trash-padded); true_len: real chunk tokens; decode:
    ``decode_step_g``'s (tokens, positions, block_tables, valid), rows that
    decode nothing not ``valid``. Returns (the chunk's last-token logits [V],
    updated cache_data, counts: module docstring), the logits a pair (the
    chunk's [V], the decode rows' [B, V]) where ``decode`` is given: the
    output head runs once over the chunk's last row and the decode rows."""
    x, cache, counts = _step_states(
        params, cache_data, (tokens, start, block_table, true_len), decode,
        policy, cfg, block_size, attn_impl)
    rows = x[jnp.maximum(true_len - 1, 0)][None]
    if decode is not None:
        rows = jnp.concatenate([rows, x[tokens.shape[0]:]])
    with jax.named_scope("lm_head"):
        logits = policy.unembed(params, rows, cfg)
    return (logits[0] if decode is None else (logits[0], logits[1:])), \
        cache, counts


@partial(jax.jit, static_argnames=("policy", "cfg", "block_size", "attn_impl"),
         donate_argnames=("cache_data",))
def verify_chunk_g(params, cache_data, tokens, start, block_table, true_len,
                   policy, cfg, block_size: int, attn_impl: str = "auto"):
    """Speculative-decoding verifier: the same cache-writing chunk forward
    as ``prefill_chunk_g`` but returns logits for EVERY row ([Tb, V]) — row
    i holds the model's prediction for position ``start + i + 1``, so the
    host accepts the longest proposal prefix whose tokens match the argmax
    chain (draft-free prompt-lookup speculation; no reference analog —
    FastGen has no speculative decoding). Rejected rows' K/V writes land at
    positions beyond the accepted context and are invisible (causal masking
    doubles as the context-length mask) until a later step overwrites them:
    true of pages alone, so a pool some of whose layers keep a recurrent
    state (which has summed every row) is refused by name."""
    if getattr(page_kind(policy.cache_spec(cfg), cache_data), "state", None):
        raise StateKindError(
            "verify_chunk_g writes rows that may be rejected, and a "
            "recurrent state cannot give them up: speculative verification "
            "is not supported over a cache with a state kind")
    x, cache, counts = _step_states(
        params, cache_data, (tokens, start, block_table, true_len), None,
        policy, cfg, block_size, attn_impl)
    with jax.named_scope("lm_head"):
        return policy.unembed(params, x, cfg), cache, counts


@partial(jax.jit, static_argnames=("policy", "cfg", "block_size", "attn_impl"),
         donate_argnames=("cache_data",))
def decode_step_g(params, cache_data, tokens, positions, block_tables, valid,
                  policy, cfg, block_size: int, attn_impl: str = "auto"):
    """Batched single-token decode. tokens/positions/valid: [B] (the rows
    that are batch padding not ``valid``); block_tables: [B, MB]. Returns
    (logits [B, V], updated cache_data, counts: module docstring)."""
    x, cache, counts = _step_states(
        params, cache_data, None, (tokens, positions, block_tables, valid),
        policy, cfg, block_size, attn_impl)
    with jax.named_scope("lm_head"):
        return policy.unembed(params, x, cfg), cache, counts


# compile-event ledger: every XLA compile of the serving step fns emits an
# ``xla/compile`` instant (fn + shape signature + wall ms) and bumps the
# process compile counter (``telemetry/compiles.py`` ``compiles_total``),
# which the benchmark reads around its window: after the warm-up there are
# to be none inside it
from deepspeed_tpu.telemetry.compiles import watch_jit  # noqa: E402
from deepspeed_tpu.telemetry.tracer import get_tracer  # noqa: E402


def _report_kv_alias(fn, name, args, kwargs, out):
    """One ``serve/kv_alias`` instant per compiled step program: whether the
    pool handed in was consumed (``donated``: every leaf reads deleted) and
    how many bytes the program updates in place (``alias_bytes``, of
    ``pool_bytes``). Everything here is free once the call has compiled:
    ``is_deleted`` and ``nbytes`` read no device memory, and lowering the
    same call again (with the pool that came back in the place of the one
    consumed) finds the computation jit has just built, whose ``compile()``
    hands back the executable it holds — no second compile, no load from
    the compile cache, no wait on the device. The pool is the step
    functions' second positional argument and second result."""
    tracer = get_tracer()
    if not tracer.enabled:
        return
    pool_in, pool_out = args[1], out[1]
    stats = fn.lower(args[0], pool_out, *args[2:],
                     **kwargs).compile().memory_analysis()
    tracer.instant(
        "serve/kv_alias", cat="serve", fn=name,
        donated=all(x.is_deleted() for x in jax.tree.leaves(pool_in)),
        alias_bytes=int(stats.alias_size_in_bytes),
        pool_bytes=sum(int(x.nbytes) for x in jax.tree.leaves(pool_out)))


prefill_chunk_g = watch_jit(prefill_chunk_g, "generic_decode.prefill_chunk_g",
                            _report_kv_alias)
verify_chunk_g = watch_jit(verify_chunk_g, "generic_decode.verify_chunk_g",
                           _report_kv_alias)
decode_step_g = watch_jit(decode_step_g, "generic_decode.decode_step_g",
                          _report_kv_alias)
