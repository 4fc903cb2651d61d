"""Generic paged serving loop: policy-parameterized prefill + decode.

Reference analog: ``inference/v2/model_implementations/inference_transformer_base.py``
— the shared ragged forward skeleton that per-arch containers plug into. Here the
skeleton is jitted pure functions over (policy, config) static args; the
policy (``modules.py``) contributes embed/block/unembed and the pool's page
kind (``kv_cache.page_kind``) everything that knows what a page is: where a
step's rows land, their write, and the attention over the pages. The loop
binds the two: each layer's block is handed one ``attend``, which passes what
the block computed on to the kind with the layer and the step's slots.

Every phase of a step sits under a ``jax.named_scope`` whose name reaches the
device trace (an operation's ``tf_op``): ``embed`` and ``lm_head`` here,
``attn/kv_write``, ``attn/paged``, the ``attn/latent_*`` scopes and a state
kind's ``ssm/conv``, ``ssm/scan`` and ``ssm/update`` in the kinds, ``attn/qkv``, ``attn/out``, ``mlp``, ``moe/router`` and
``moe/experts`` in the policies. Metadata only: the compiled program is the
same.

A block returns ``(x, counts or None)``; the three step programs return
``(logits, cache, counts)``, the counts one int32 vector of
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


def _chunk_states(params, cache_data, tokens, start, block_table, true_len,
                  policy, cfg, block_size: int, attn_impl: str):
    """Shared chunk forward: embeds a bucket-padded token chunk, has the page
    kind write each layer's new rows into the pages and attend over the paged
    context, and returns (per-row hidden states [Tb, D], updated cache, the
    counts handed out)."""
    spec = policy.cache_spec(cfg)
    kind = page_kind(spec, cache_data)
    tb = tokens.shape[0]

    positions = start + jnp.arange(tb)
    safe_pos = jnp.minimum(positions, spec.max_seq_len - 1)
    valid = jnp.arange(tb) < true_len
    slots = kind.chunk_slots(cache_data, block_table, start, safe_pos, valid,
                             block_size)

    with jax.named_scope("embed"):
        x = policy.embed(params, tokens, safe_pos, cfg)

    cache = cache_data
    counted = []
    for i in range(spec.num_layers):
        def attend(*computed, i=i, **how):
            nonlocal cache
            out, cache = kind.attend_chunk(cache, i, slots, block_table,
                                           start, attn_impl, *computed, **how)
            return out
        x, counts = policy.block(params, i, x, attend, safe_pos, cfg, valid)
        if counts is not None:
            counted.append(counts)
    return x, cache, _summed(counted)


@partial(jax.jit, static_argnames=("policy", "cfg", "block_size", "attn_impl"),
         donate_argnames=("cache_data",))
def prefill_chunk_g(params, cache_data, tokens, start, block_table, true_len,
                    policy, cfg, block_size: int, attn_impl: str = "auto"):
    """One sequence, one chunk. tokens: [Tb] (bucket-padded); start: the
    chunk's offset in the sequence; block_table: [MB] block ids
    (trash-padded); true_len: real chunk tokens. Returns (last-token logits
    [V], updated cache_data, counts: module docstring)."""
    x, cache, counts = _chunk_states(params, cache_data, tokens, start,
                                     block_table, true_len, policy, cfg,
                                     block_size, attn_impl)
    last = x[jnp.maximum(true_len - 1, 0)]
    with jax.named_scope("lm_head"):
        logits = policy.unembed(params, last[None], cfg)[0]
    return logits, cache, counts


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
    x, cache, counts = _chunk_states(params, cache_data, tokens, start,
                                     block_table, true_len, policy, cfg,
                                     block_size, attn_impl)
    with jax.named_scope("lm_head"):
        return policy.unembed(params, x, cfg), cache, counts


@partial(jax.jit, static_argnames=("policy", "cfg", "block_size", "attn_impl"),
         donate_argnames=("cache_data",))
def decode_step_g(params, cache_data, tokens, positions, block_tables, valid,
                  policy, cfg, block_size: int, attn_impl: str = "auto"):
    """Batched single-token decode. tokens/positions/valid: [B] (the rows
    that are batch padding not ``valid``); block_tables: [B, MB]. Returns
    (logits [B, V], updated cache_data, counts: module docstring)."""
    spec = policy.cache_spec(cfg)
    kind = page_kind(spec, cache_data)

    safe_pos = jnp.minimum(positions, spec.max_seq_len - 1)
    slots = kind.decode_slots(cache_data, block_tables, safe_pos, valid,
                              block_size)

    with jax.named_scope("embed"):
        x = policy.embed(params, tokens, safe_pos, cfg)

    cache = cache_data
    counted = []
    for i in range(spec.num_layers):
        def attend(*computed, i=i, **how):
            nonlocal cache
            out, cache = kind.attend_decode(cache, i, slots, block_tables,
                                            safe_pos, attn_impl, *computed,
                                            **how)
            return out
        x, counts = policy.block(params, i, x, attend, safe_pos, cfg, valid)
        if counts is not None:
            counted.append(counts)

    with jax.named_scope("lm_head"):
        logits = policy.unembed(params, x, cfg)
    return logits, cache, _summed(counted)


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
