"""Paged (blocked) KV cache on device.

Reference analog: ``deepspeed/inference/v2/ragged/kv_cache.py:40``
(``BlockedKVCache``) — a pool of fixed-size KV blocks per layer, reserved through a
``BlockedAllocator``. TPU layout is **head-major**
[kv_heads, num_blocks, block_size, head_dim], so one page of one KV head is a
contiguous (block_size, head_dim) tile — the shape the Pallas paged-attention
kernel DMAs per grid step (``ops/pallas/paged_attention.py``); shard over
``tensor`` on the leading heads dim. Block writes are ``.at[].set`` scatters
inside the jitted step; reads either go through the kernel (block table in
scalar prefetch) or gather a contiguous context window (CPU fallback).
"""

import dataclasses
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2.blocked_allocator import BlockedAllocator


@dataclasses.dataclass
class KVCacheConfig:
    num_layers: int
    num_kv_heads: int
    head_dim: int
    block_size: int = 64
    num_blocks: int = 256
    dtype: any = jnp.bfloat16
    # a latent (MLA) page kind: one plane of ``latent_dim`` values a token
    # with no heads (``num_kv_heads`` and ``head_dim`` then say nothing), its
    # rows padded with zero lanes to ``latent_row_width``
    latent_dim: int = 0


def latent_row_width(latent_dim: int) -> int:
    """A latent page's row as the pool stores it: ``latent_dim`` values and
    zero lanes up to the next multiple of the TPU's 128. A last axis that is
    no multiple of 128 gets a device layout with the block index minor, and
    the kernel, which needs the row minor, then has the whole pool copied in
    and out of every step; the tiled row-major layout pads the row in memory
    anyway, so the lanes cost no bytes that layout would not."""
    return -(-latent_dim // 128) * 128


class LatentPageDtypeError(ValueError):
    """A page dtype that a latent (MLA) pool cannot hold."""


class BlockedKVCache:
    def __init__(self, cfg: KVCacheConfig):
        self.cfg = cfg
        # last block reserved as the trash target for padding-token writes
        # (see llama_decode.py); never handed out by the allocator
        self.allocator = BlockedAllocator(cfg.num_blocks - 1)
        # [L, 2(kv), H_kv, num_blocks, block_size, D] (head-major pages), or
        # for a latent cache one plane [L, num_blocks, block_size, W]
        self.block_axis = 1 if cfg.latent_dim else 3
        if cfg.latent_dim and cfg.dtype == jnp.float8_e4m3fn:
            raise LatentPageDtypeError(
                "fp8 scaled pages are not supported over a latent cache: "
                "their per-(head, page) scales and K and V planes have no "
                "place on one headless plane; use kv_cache_dtype='model'")
        self.data = jnp.zeros(
            (cfg.num_layers, cfg.num_blocks, cfg.block_size,
             latent_row_width(cfg.latent_dim)) if cfg.latent_dim else
            (cfg.num_layers, 2, cfg.num_kv_heads, cfg.num_blocks,
             cfg.block_size, cfg.head_dim), cfg.dtype)
        # fp8 pages carry a per-(layer, k/v, head, page) fp32 scale: stored
        # value = real / scale, grown monotonically as outliers arrive (the
        # whole page is requantized under the new scale on growth). The
        # reference fp quantizer is group-scaled the same way
        # (csrc/fp_quantizer/fp_quantize.cu, group absmax).
        self.scales = (jnp.ones(
            (cfg.num_layers, 2, cfg.num_kv_heads, cfg.num_blocks),
            jnp.float32) if cfg.dtype == jnp.float8_e4m3fn else None)

    @property
    def pool(self):
        """What the step programs take and give back: ``data``, or
        ``(data, scales)`` for scaled fp8 pages. They consume what they are
        given (``generic_decode`` donates it and updates it in place), so
        the pool that comes back is assigned here right after the call:
        ``logits, kv.pool, _ = decode_step_g(params, kv.pool, ...)``. Read the
        pool afresh after a step; an array kept across one is deleted."""
        return self.data if self.scales is None else (self.data, self.scales)

    @pool.setter
    def pool(self, cache) -> None:
        if self.scales is None:
            self.data = cache
        else:
            self.data, self.scales = cache

    @property
    def free_blocks(self) -> int:
        return self.allocator.free_blocks

    def blocks_needed(self, num_tokens: int) -> int:
        return int(np.ceil(num_tokens / self.cfg.block_size))

    def reserve(self, num_blocks: int) -> List[int]:
        """reference: kv_cache.py:144 reserve."""
        return self.allocator.allocate(num_blocks)

    def release(self, blocks: List[int],
                pinned: Optional[Sequence[int]] = None) -> None:
        """Free blocks back to the allocator. ``pinned`` names pages the
        prefix cache still holds readers on (refcount > 0): those are
        skipped ENTIRELY — not freed and, critically, not scale-reset.
        One reader of a shared fp8 page releasing its block list must
        not clobber the surviving readers' scales (a reset would silently
        re-interpret their stored values under the wrong scale)."""
        if pinned:
            keep = set(pinned)
            blocks = [b for b in blocks if b not in keep]
        self.allocator.free(blocks)
        if self.scales is not None and blocks:
            # reset released pages' scales: a page freed by a sequence with
            # outlier K/V must not impose its grown scale (= lost precision)
            # on the next sequence the allocator hands it to
            self.scales = _set_blocks(self.scales, jnp.asarray(blocks), 1.0)

    # ------------------------------------------------------------------
    # host offload tier (serving demotion/promotion; see kv_offload.py)
    # ------------------------------------------------------------------
    def gather_blocks(self, blocks: List[int]
                      ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Copy the listed blocks' pages (and, for fp8, their scales) to
        host ndarrays ``[L, 2, H_kv, len(blocks), bs, D]`` (a latent pool:
        ``[L, len(blocks), bs, W]``). A deliberate
        device->host transfer — demotion runs OFF the per-tick fast path,
        only when the serving tier policy decides to spill."""
        idx = np.asarray(blocks, np.int32)
        data = np.asarray(jnp.take(self.data, idx, axis=self.block_axis))
        scales = (np.asarray(self.scales[:, :, :, idx])
                  if self.scales is not None else None)
        return data, scales

    def scatter_blocks(self, blocks: List[int], data: np.ndarray,
                       scales: Optional[np.ndarray] = None) -> None:
        """Write gathered pages back into (possibly different) blocks —
        the promotion path. fp8 scales are restored alongside the pages,
        so a promoted sequence's quantization state is bit-identical to
        what it was at demotion."""
        idx = jnp.asarray(np.asarray(blocks, np.int32))
        self.data = _set_blocks(self.data, idx,
                                jnp.asarray(data, self.cfg.dtype),
                                self.block_axis)
        if self.scales is not None and scales is not None:
            self.scales = _set_blocks(self.scales, idx,
                                      jnp.asarray(scales, jnp.float32))


@partial(jax.jit, donate_argnums=(0,), static_argnums=(3,))
def _set_blocks(pool, idx, vals, axis: int = 3):
    """``pool[..., idx] = vals`` along the pool's block ``axis`` in place
    (pages or scales; ``vals`` whole blocks or one number). Outside a jit
    the same update writes a second pool and holds both until the first is
    dropped."""
    return pool.at[(slice(None),) * axis + (idx,)].set(vals)


FP8_MAX = 448.0     # float8_e4m3fn max finite; overflow casts become NaN


def cast_to_page_dtype(x, dtype):
    """Cast K/V to the page dtype; fp8 pages clamp to the finite e4m3 range
    first (e4m3 has no inf — out-of-range casts would write NaN and poison
    the page for the rest of the sequence)."""
    if dtype == jnp.float8_e4m3fn:
        x = jnp.clip(x, -FP8_MAX, FP8_MAX)
    return x.astype(dtype)


def write_kv_scaled(cache_data, scales, layer: int, kv: int, vals,
                    block_ids, offsets, touched_pages):
    """Scatter new tokens into fp8 pages under per-(head, page) scales.

    cache_data: [L, 2, H, NB, bs, D] fp8; scales: [L, 2, H, NB] fp32;
    vals: [T, H, D] compute dtype; block_ids/offsets: [T] target slot per
    token; touched_pages: [P] page ids covering ``set(block_ids)`` —
    duplicates are allowed only if they carry identical updates (trash-padded
    table slots / clamped slices satisfy this), because the requantize
    scatter writes them all.

    A new token whose |value| exceeds the page's committed range GROWS the
    page scale (``new = max(old, absmax/448)``) and the whole page is
    requantized under it (one small gather-scale-scatter — pages are
    (bs, D) tiles); pages without outliers keep ratio 1.0 and the fp8→fp32→
    fp8 round-trip is exact. Scales never shrink while a page is live; the
    allocator resets them to 1.0 on release (``BlockedKVCache.release``).
    """
    f32 = jnp.float32
    old_s = scales[layer, kv]                                   # [H, NB]
    absmax = jnp.max(jnp.abs(vals.astype(f32)), axis=-1)        # [T, H]
    page_max = jnp.zeros_like(old_s).at[:, block_ids].max(absmax.T)
    # the trash page (num_blocks-1, where bucket-padding rows land) is never
    # allocated or released, so letting it join the scatter-max would grow
    # its scale monotonically for the cache's lifetime — silent state drift
    # with no output effect (trash slots are always causally masked)
    page_max = page_max.at[:, -1].set(0.0)
    new_s = jnp.maximum(old_s, page_max / FP8_MAX)              # [H, NB]

    # the head is an index like the page and the offset (see ``write_kv``):
    # the pool keeps its layout through both scatters
    heads = jnp.arange(cache_data.shape[2])[None, :]

    # requantize touched pages under the grown scale — predicated: in
    # steady-state decode no scale grows and the full-page read-modify-write
    # would be pure wasted HBM bandwidth in the hot path
    def requant(data):
        pages = touched_pages[:, None]
        old_tile = data[layer, kv, heads, pages]                # [P, H, bs, D]
        ratio = (old_s / new_s)[:, touched_pages].T             # [P, H]
        tile = old_tile.astype(f32) * ratio[..., None, None]
        return data.at[layer, kv, heads, pages].set(tile.astype(data.dtype))

    cache_data = jax.lax.cond(jnp.any(new_s > old_s), requant,
                              lambda data: data, cache_data)
    # write the new tokens under the new scale
    tok_scale = new_s[:, block_ids].T                           # [T, H]
    cache_data = cache_data.at[
        layer, kv, heads, block_ids[:, None], offsets[:, None]].set(
            cast_to_page_dtype(vals.astype(f32) / tok_scale[..., None],
                               cache_data.dtype))
    return cache_data, scales.at[layer, kv].set(new_s)


def write_kv(cache_data, layer: int, k_new, v_new, block_ids, offsets):
    """Scatter new K/V tokens into their page slots, in place where the pool
    is donated (``generic_decode``).

    cache_data: [L, 2, H, NB, bs, D]; k_new/v_new: [T, H, D]; block_ids/
    offsets: [T], the slot of each token. The head is an index like the block
    and the offset, so that one update is one D row of the pool as it lies in
    memory. Written ``[layer, kv, :, block_ids, offsets]`` an update is an
    [H, D] window strided over the heads, and XLA on the TPU then transposes
    the whole pool to token-major before the scatter and back after it, and
    every layer's pages back for the kernel: pool-sized copies, each step.
    """
    heads = jnp.arange(cache_data.shape[2])[None, :]
    blk, off = block_ids[:, None], offsets[:, None]
    for kv, new in enumerate((k_new, v_new)):
        cache_data = cache_data.at[layer, kv, heads, blk, off].set(
            cast_to_page_dtype(new, cache_data.dtype))
    return cache_data


def write_latent(cache_data, layer: int, rows, block_ids, offsets):
    """Scatter new tokens' latent rows into their page slots, in place where
    the pool is donated. cache_data: [L, NB, bs, W]; rows: [T, C] with C <= W
    (zero lanes fill the rest); block_ids/offsets: [T]. One update is one W
    row of the pool as it lies in memory, as in ``write_kv``."""
    pad = cache_data.shape[-1] - rows.shape[-1]
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad)))
    return cache_data.at[layer, block_ids, offsets].set(
        cast_to_page_dtype(rows, cache_data.dtype))
