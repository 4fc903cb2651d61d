"""Generic paged serving loop: policy-parameterized prefill + decode.

Reference analog: ``inference/v2/model_implementations/inference_transformer_base.py``
— the shared ragged forward skeleton that per-arch containers plug into. Here the
skeleton is jitted pure functions over (policy, config) static args; the
policy (``modules.py``) contributes embed/block/unembed and the loop owns KV
cache writes + the Pallas paged attention (``llama_decode._paged_attn``).

Every phase of a step sits under a ``jax.named_scope`` whose name reaches the
device trace (an operation's ``tf_op``): ``embed``, ``attn/kv_write``,
``attn/paged`` and ``lm_head`` here, ``attn/qkv``, ``attn/out``, ``mlp``,
``moe/router`` and ``moe/experts`` in the policies. Metadata only: the
compiled program is the same.

Over a latent cache (``KVCacheSpec.latent_dim``: one row a token, no heads)
the block hands ``attend`` its queries in two parts, the token's row and the
key-value up-projection, and the loop writes the row (``attn/latent_write``)
and reads it the way its program needs: a chunk attends unfolded over keys
and values up-projected from the gathered rows (``attn/latent_prefill``), a
decode batch folded over the pages themselves (``attn/latent_q``,
``attn/latent_paged``). A block returns ``(x, counts or None)``; the three
step programs return ``(logits, cache, counts)``, the counts one int32 vector
of ``telemetry/names.py`` ``STEP_COUNTER_ARGS`` summed over the layers that
count (sums over what a router has anyway), and empty where none does.
"""

from functools import partial

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.kv_cache import (write_kv, write_kv_scaled,
                                                write_latent)
from deepspeed_tpu.inference.v2.llama_decode import (_latent_paged_attn,
                                                     _latent_prefill_attn,
                                                     _paged_attn)


def _trash_block(pool, spec):
    """The pool's last block, where padding rows are written."""
    return pool.shape[1 if spec.latent_dim else 3] - 1


def _summed(counted):
    """The layers' counts summed: [len(STEP_COUNTER_ARGS)] int32, or [0]
    where no layer counted."""
    if not counted:
        return jnp.zeros((0,), jnp.int32)
    return sum(counted[1:], counted[0])


def _chunk_states(params, cache_data, tokens, start, block_table, true_len,
                  policy, cfg, block_size: int, attn_impl: str):
    """Shared chunk forward: embeds a bucket-padded token chunk, scatters
    each layer's K/V into the pages, attends over the paged context, and
    returns (per-row hidden states [Tb, D], updated cache, the counts handed
    out). ``cache_data`` may be the plain page pool [L, 2, H, NB, bs, D], a
    ``(pages, scales)`` tuple for scaled fp8 pages
    (``BlockedKVCache.scales``), or a latent pool [L, NB, bs, W]."""
    spec = policy.cache_spec(cfg)
    tb = tokens.shape[0]
    mb = block_table.shape[0]
    scaled = isinstance(cache_data, tuple)
    pool = cache_data[0] if scaled else cache_data

    positions = start + jnp.arange(tb)
    safe_pos = jnp.minimum(positions, spec.max_seq_len - 1)
    valid = jnp.arange(tb) < true_len
    tok_block = jnp.where(valid,
                          block_table[jnp.minimum(safe_pos // block_size, mb - 1)],
                          _trash_block(pool, spec))
    tok_off = safe_pos % block_size
    touched = None
    if scaled:
        # pages the chunk's valid tokens can land on: a contiguous table
        # slice (clamp duplicates repeat the same slot — identical updates,
        # safe for write_kv_scaled's requantize scatter). Static worst-case
        # page count: offsets start%bs .. start%bs+tb-1 span up to
        # (tb + bs - 2)//bs + 1 pages — a chunk smaller than a page that
        # crosses a boundary still touches TWO pages (tb//bs+1 missed that)
        touch_idx = jnp.minimum(
            start // block_size +
            jnp.arange((tb + block_size - 2) // block_size + 1), mb - 1)
        touched = block_table[touch_idx]

    with jax.named_scope("embed"):
        x = policy.embed(params, tokens, safe_pos, cfg)

    cache = cache_data
    counted = []
    for i in range(spec.num_layers):
        def attend_latent(q_nope, q_rope, row, w_ukv, scale, i=i):
            nonlocal cache
            with jax.named_scope("attn/latent_write"):
                cache = write_latent(cache, i, row, tok_block, tok_off)
            with jax.named_scope("attn/latent_prefill"):
                return _latent_prefill_attn(q_nope, q_rope, cache, i,
                                            block_table, start, w_ukv, scale,
                                            attn_impl)

        def attend(q, k, v, i=i, window="spec", softcap=None):
            nonlocal cache
            win = spec.window if window == "spec" else window
            if scaled:
                data, scales = cache
                with jax.named_scope("attn/kv_write"):
                    data, scales = write_kv_scaled(
                        data, scales, i, 0, k, tok_block, tok_off, touched)
                    data, scales = write_kv_scaled(
                        data, scales, i, 1, v, tok_block, tok_off, touched)
                cache = (data, scales)
                with jax.named_scope("attn/paged"):
                    return _paged_attn(q[None], data, i, block_table[None],
                                       jnp.asarray(start).reshape(1), win,
                                       attn_impl, softcap=softcap,
                                       scales=scales)[0]
            with jax.named_scope("attn/kv_write"):
                cache = write_kv(cache, i, k, v, tok_block, tok_off)
            with jax.named_scope("attn/paged"):
                return _paged_attn(q[None], cache, i, block_table[None],
                                   jnp.asarray(start).reshape(1), win,
                                   attn_impl, softcap=softcap)[0]
        x, counts = policy.block(
            params, i, x, attend_latent if spec.latent_dim else attend,
            safe_pos, cfg, valid)
        if counts is not None:
            counted.append(counts)
    return x, cache, _summed(counted)


@partial(jax.jit, static_argnames=("policy", "cfg", "block_size", "attn_impl"),
         donate_argnames=("cache_data",))
def prefill_chunk_g(params, cache_data, tokens, start, block_table, true_len,
                    policy, cfg, block_size: int, attn_impl: str = "auto"):
    """One sequence, one bucket-padded chunk; returns (last-token logits [V],
    updated cache_data, counts: module docstring). See llama_decode.prefill_chunk for the argument
    contract — this is the arch-generic version; cache structure in ==
    structure out (plain pool or (pages, scales))."""
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
    doubles as the context-length mask) until a later step overwrites them."""
    x, cache, counts = _chunk_states(params, cache_data, tokens, start,
                                     block_table, true_len, policy, cfg,
                                     block_size, attn_impl)
    with jax.named_scope("lm_head"):
        return policy.unembed(params, x, cfg), cache, counts


@partial(jax.jit, static_argnames=("policy", "cfg", "block_size", "attn_impl"),
         donate_argnames=("cache_data",))
def decode_step_g(params, cache_data, tokens, positions, block_tables, valid,
                  policy, cfg, block_size: int, attn_impl: str = "auto"):
    """Batched single-token decode; returns (logits [B, V], updated
    cache_data, counts: module docstring).
    See llama_decode.decode_step for the argument contract.
    ``cache_data``: plain pool or ``(pages, scales)`` like prefill_chunk_g."""
    spec = policy.cache_spec(cfg)
    mb = block_tables.shape[1]
    scaled = isinstance(cache_data, tuple)
    pool = cache_data[0] if scaled else cache_data

    safe_pos = jnp.minimum(positions, spec.max_seq_len - 1)
    blk = jnp.where(valid,
                    jnp.take_along_axis(
                        block_tables,
                        jnp.minimum(safe_pos // block_size, mb - 1)[:, None],
                        axis=1)[:, 0],
                    _trash_block(pool, spec))
    off = safe_pos % block_size

    with jax.named_scope("embed"):
        x = policy.embed(params, tokens, safe_pos, cfg)

    cache = cache_data
    counted = []
    for i in range(spec.num_layers):
        def attend_latent(q_nope, q_rope, row, w_ukv, scale, i=i):
            nonlocal cache
            with jax.named_scope("attn/latent_write"):
                cache = write_latent(cache, i, row, blk, off)
            return _latent_paged_attn(q_nope, q_rope, cache, i, block_tables,
                                      safe_pos, w_ukv, scale, attn_impl)

        def attend(q, k, v, i=i, window="spec", softcap=None):
            nonlocal cache
            win = spec.window if window == "spec" else window
            if scaled:
                # each token touches exactly its own page (invalid rows all
                # write the trash page with identical per-page updates)
                data, scales = cache
                with jax.named_scope("attn/kv_write"):
                    data, scales = write_kv_scaled(data, scales, i, 0, k,
                                                   blk, off, blk)
                    data, scales = write_kv_scaled(data, scales, i, 1, v,
                                                   blk, off, blk)
                cache = (data, scales)
                with jax.named_scope("attn/paged"):
                    return _paged_attn(q[:, None], data, i, block_tables,
                                       safe_pos, win, attn_impl,
                                       softcap=softcap, scales=scales)[:, 0]
            with jax.named_scope("attn/kv_write"):
                cache = write_kv(cache, i, k, v, blk, off)
            with jax.named_scope("attn/paged"):
                return _paged_attn(q[:, None], cache, i, block_tables,
                                   safe_pos, win, attn_impl,
                                   softcap=softcap)[:, 0]
        x, counts = policy.block(
            params, i, x, attend_latent if spec.latent_dim else attend,
            safe_pos, cfg, valid)
        if counts is not None:
            counted.append(counts)

    with jax.named_scope("lm_head"):
        logits = policy.unembed(params, x, cfg)
    return logits, cache, _summed(counted)


# compile-event ledger: every XLA compile of the serving step fns emits an
# ``xla/compile`` instant (fn + shape signature + wall ms) and bumps the
# process compile counter — bench_serve asserts ZERO compiles inside the
# measured window after warmup (telemetry/compiles.py)
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
