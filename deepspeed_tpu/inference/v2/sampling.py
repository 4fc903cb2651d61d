"""Token sampling for serving: greedy / temperature / top-k / top-p.

Reference analog: the reference's FastGen pipeline samples in MII; the engine
itself shipped argmax. Here sampling is a first-class jitted device-side op so
the serving loop fetches only the sampled token ids ([B] int32, a few bytes)
instead of the full [B, vocab] logits every step.
"""

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    temperature: float = 0.0     # 0 -> greedy argmax
    top_k: int = 0               # 0 -> disabled
    top_p: float = 1.0           # 1 -> disabled
    seed: int = 0


@partial(jax.jit, static_argnames=("cfg",))
def sample_tokens(logits, key, cfg: SamplingConfig):
    """logits: [B, V] fp32 -> [B] int32 sampled token ids (device-side)."""
    with jax.named_scope("sample"):
        return _sample(logits, key, cfg)


@partial(jax.jit, static_argnames=("cfg",))
def sample_into(logits, key, last, rows, cfg: SamplingConfig):
    """``sample_tokens``, with the tokens also left on the device where the
    next step reads them: ``last`` [slots] int32 is a last-token vector by
    sequence slot, ``rows`` [2, B] int32 carries each row's slot
    in ``rows[0]``. Returns (the [B] token ids, the vector with them
    stored)."""
    with jax.named_scope("sample"):
        tokens = _sample(logits, key, cfg)
    return tokens, last.at[rows[0]].set(tokens)


@jax.jit
def feed_tokens(last, rows):
    """The [B] token ids a decode step takes: row ``j`` reads its sequence's
    last token at ``last[rows[0, j]]``, unless the host holds it and sent it
    as ``rows[1, j]`` (>= 0)."""
    return jnp.where(rows[1] >= 0, rows[1], last[rows[0]])


def _sample(logits, key, cfg: SamplingConfig):
    if cfg.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits.astype(jnp.float32) / cfg.temperature
    if cfg.top_k and cfg.top_k > 0:
        kth = jax.lax.top_k(scaled, cfg.top_k)[0][:, -1:]
        scaled = jnp.where(scaled < kth, NEG_INF, scaled)
    if cfg.top_p < 1.0:
        sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative mass >= top_p; the top-1
        # token is kept unconditionally so top_p <= 0 degrades to greedy
        # instead of masking every token
        keep = cum - probs < cfg.top_p
        keep = keep.at[:, 0].set(True)
        cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf),
                         axis=-1, keepdims=True)
        scaled = jnp.where(scaled < cutoff, NEG_INF, scaled)
    return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)


# compile-event ledger: sampler recompiles (a new [B, V] bucket or a new
# SamplingConfig) are real serve-tick stalls too — watched like the step fns
from deepspeed_tpu.telemetry.compiles import watch_jit  # noqa: E402

sample_tokens = watch_jit(sample_tokens, "sampling.sample_tokens")
sample_into = watch_jit(sample_into, "sampling.sample_into")
feed_tokens = watch_jit(feed_tokens, "sampling.feed_tokens")
