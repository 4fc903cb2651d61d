"""Vocab-parallel cross entropy.

Reference analog: ``deepspeed/sequence/cross_entropy.py``
(``vocab_parallel_cross_entropy`` — CE over a vocab-sharded lm head without
gathering the full logits, Megatron-style).

TPU shape: inside ``shard_map`` over the ``tensor`` axis each device holds
``logits_local [*, V/P]``; the softmax statistics compose across shards with
two psums (max, sum-exp) and the target logit is recovered with a masked local
lookup + psum — the full ``[*, V]`` logits never materialize, which matters
when V is 128k+ and the sequence is long.
"""

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm import mesh as mesh_lib


def _local_vocab_ce(logits_local, labels, axis_name: str):
    """logits_local: [N, V/P] fp32; labels: [N] global vocab ids.
    Returns per-token loss [N] (replicated across the axis)."""
    vp = logits_local.shape[-1]
    rank = jax.lax.axis_index(axis_name)
    lo = rank * vp

    lmax = jax.lax.pmax(jnp.max(logits_local, axis=-1), axis_name)     # [N]
    shifted = logits_local - lmax[..., None]
    sumexp = jax.lax.psum(jnp.sum(jnp.exp(shifted), axis=-1), axis_name)

    local_idx = labels - lo
    in_shard = (local_idx >= 0) & (local_idx < vp)
    safe_idx = jnp.clip(local_idx, 0, vp - 1)
    tgt = jnp.take_along_axis(shifted, safe_idx[..., None], axis=-1)[..., 0]
    tgt = jax.lax.psum(jnp.where(in_shard, tgt, 0.0), axis_name)

    return jnp.log(sumexp) - tgt


def vocab_parallel_cross_entropy(logits, labels, mesh=None,
                                 axis_name: str = "tensor"):
    """logits: [B, S, V] sharded on V over ``axis_name``; labels: [B, S].
    Returns per-token loss [B, S]. Degrades to dense CE when the axis is 1."""
    mesh = mesh or mesh_lib.get_global_mesh()
    if mesh is None or mesh.shape.get(axis_name, 1) == 1:
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]

    def body(logits_l, labels_l):
        b, s, vp = logits_l.shape
        loss = _local_vocab_ce(logits_l.astype(jnp.float32).reshape(b * s, vp),
                               labels_l.reshape(b * s), axis_name)
        return loss.reshape(b, s)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None, axis_name), P()),
        out_specs=P(), check_vma=False)(logits, labels)


def chunked_cross_entropy(hidden, labels, mask, *, kernel=None, embedding=None,
                          chunk_size: int = 1024,
                          soft_cap=None, compute_dtype=jnp.bfloat16,
                          unroll: bool = False):
    """Next-token CE from *hidden states* without materializing [B*S, V] fp32.

    The reference computes full logits and feeds them to torch CE (its fused
    vocab kernel lives in Megatron, not DeepSpeed); on TPU the fp32 logits tensor
    is the single largest HBM temp of a training step (B*S*V*4 bytes — 1 GB at
    B=4, S=2k, V=32k), and it is written + re-read across the fwd/bwd boundary.
    Here the head matmul and the softmax-CE reduction run fused per token-chunk
    under ``jax.checkpoint`` inside a ``lax.scan``: peak logits memory drops to
    ``chunk_size * V`` and the backward recomputes each chunk's logits instead
    of fetching them from HBM (one extra head matmul — ~3% of model FLOPs for
    a 0.7B Llama — traded for ~3 GB of temps).

    hidden: [B, S, H]; labels/mask: [B, S]; exactly one of
    ``kernel`` [H, V] / ``embedding`` [V, H] (tied) supplies the head weights.
    Returns mean CE over masked tokens (same contract as the dense path).
    """
    if (kernel is None) == (embedding is None):
        raise ValueError("pass exactly one of kernel / embedding")
    b, s, h = hidden.shape
    n = b * s
    c = min(chunk_size, n)
    pad = (-n) % c
    xf = hidden.reshape(n, h)
    lf = labels.reshape(n).astype(jnp.int32)
    mf = mask.reshape(n).astype(jnp.float32)
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
        lf = jnp.pad(lf, (0, pad))
        mf = jnp.pad(mf, (0, pad))
    nc = (n + pad) // c
    w = (kernel if kernel is not None else embedding).astype(compute_dtype)
    contract = "ch,hv->cv" if kernel is not None else "ch,vh->cv"

    def body(total, inp):
        xc, lc, mc = inp
        logits = jnp.einsum(contract, xc.astype(compute_dtype), w,
                            preferred_element_type=jnp.float32)
        if soft_cap:
            logits = soft_cap * jnp.tanh(logits / soft_cap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return total + jnp.sum((lse - tgt) * mc), None

    xs = xf.reshape(nc, c, h)
    ls = lf.reshape(nc, c)
    ms = mf.reshape(nc, c)
    ck = jax.checkpoint(body)
    if unroll:
        # unrolled chunk loop: nc is small and static (B*S/chunk ~ 4-16), so
        # XLA sees nc copies of one fused matmul+CE block instead of a
        # scan-of-checkpoint — the structure suspected of the pathological
        # XLA:TPU compile time when this scan nests inside the engine's gas
        # scan (>20 min observed in round 2). Same memory bound:
        # each chunk's logits are rematerialized in the backward.
        total = jnp.zeros((), jnp.float32)
        for i in range(nc):
            total, _ = ck(total, (xs[i], ls[i], ms[i]))
    else:
        total, _ = jax.lax.scan(ck, jnp.zeros((), jnp.float32), (xs, ls, ms))
    return total / jnp.maximum(jnp.sum(mf), 1.0)
