"""Paged (blocked) KV cache on device, and what a page of it is.

Reference analog: ``deepspeed/inference/v2/ragged/kv_cache.py:40``
(``BlockedKVCache``) — a pool of fixed-size KV blocks per layer, reserved through a
``BlockedAllocator``.

The pool's format is a *page kind*, chosen once from the cache's config and
found again inside a step program from the policy's spec and the pool handed
in (``page_kind``): head-major K and V planes (``_HeadPages``), the same in
fp8 under per-(head, page) scales (``_ScaledHeadPages``), one headless
latent plane (``_LatentPages``), head pages held by LAYER kind
(``_LayerKindPages``: a pool of the full layers' pages and a pool of the
windowed layers', each with its block tables and, where the model says so,
its own KV head count and key and value widths, for a model that mixes the
two; and, beside the pages, the layers that keep no pages at all but what
does not grow with the context, one SLOT a sequence and no block table: the
tail of a causal convolution alone, ``_TailSlots``, or that and a recurrent
state, ``_StateSlots``). A kind owns, and nothing outside this
module knows: the pool's shape and block axis, the trash block, whether the
step programs carry an array or ``(pages, scales)``, the slots a step's rows
land in, their write, the chunk and decode attention over its pages,
Pallas kernel or gather path (``attn_impl``), and which of the operands a
block hands ``attend`` are rows (``row_operands``: what a step of a chunk's
rows and a decode batch's cuts between the two). The served loop
(``generic_decode.py``) hands a kind the step's positions and block tables
and each layer's ``attend`` arguments. A further kind is a class here and
the policy that calls its ``attend``.

Padding rows are written to the trash block (the pool's last, never handed
out by the allocator), so the row write needs no mask; a chunk's rows go in a
page at a time (``write_chunk_pages``: pages that are all padding to the trash
block, the first and the last page's other rows kept under a row mask), a
decode batch's one by one. On the read path
causal masking doubles as padding masking: a gathered position at or past
the context length can never satisfy qpos >= kpos.
"""

import dataclasses
from functools import partial
from typing import Any, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2.blocked_allocator import BlockedAllocator
from deepspeed_tpu.ops import ssm
from deepspeed_tpu.ops.pallas import page_write as _page_write
from deepspeed_tpu.ops.pallas import ssm_update as _ssm_update
from deepspeed_tpu.ops.pallas.paged_attention import (
    chunk_tile_keys, decode_slot_copies, decode_tile_keys,
    paged_attention_pool, paged_attention_reference)
from deepspeed_tpu.telemetry.names import LATENT_KEYS_ARG

FULL, WINDOW, STATE = "full", "window", "state"
#: the kind of a layer that keeps nothing of a sequence (a layer that is an
#: MLP or routed experts alone): no pool, no slot, and its block calls no
#: ``attend``
NONE = "none"


@dataclasses.dataclass(frozen=True)
class HeadPageShape:
    """What a kind of head pages holds of one token in one layer:
    ``num_kv_heads`` keys of ``key_dim`` and as many values of
    ``value_dim``."""
    num_kv_heads: int
    key_dim: int
    value_dim: int

    @property
    def square(self) -> bool:
        """Keys and values of one width: K and V planes of one array."""
        return self.key_dim == self.value_dim


@dataclasses.dataclass(frozen=True)
class StateSlotShape:
    """What a slot of the state kind holds of one sequence in one layer.
    Always the TAIL of the layer's causal convolution: the last ``conv_width
    - 1`` rows of the ``conv_channels`` it looks back on, in the compute
    type. Where ``heads`` > 0 (a Mamba-2 layer) also the recurrent state
    of ``heads`` heads, ``[head_dim, d_state]`` float32 each (a sum over
    every token the sequence has seen, so not the compute type):
    ``scan_block`` is the block a prefill chunk's closed form sums over,
    ``groups`` how many ``B`` and ``C`` a token brings (``conv_channels`` =
    ``heads * head_dim + 2 * groups * d_state``; a head reads its group's).
    With no heads (``tail_only``: a gated short convolution) the slot is the
    tail and nothing else, and no pool of states exists. What follows the
    convolution's taps' sum is the kind's to say (``_TailSlots.activation``)."""
    heads: int
    head_dim: int
    d_state: int
    conv_width: int
    conv_channels: int
    scan_block: int = 256
    groups: int = 1

    @classmethod
    def tail_only(cls, conv_width: int, conv_channels: int):
        """A slot that is a convolution tail alone."""
        return cls(0, 0, 0, conv_width, conv_channels)

    @property
    def recurrent(self) -> bool:
        """The slot holds a recurrent state beside its tail."""
        return self.heads > 0

    @property
    def pack(self) -> int:
        """Heads side by side in the stored state's lanes
        (``ops/pallas/ssm_update.py``)."""
        return _ssm_update.state_pack(self.heads, self.head_dim, self.groups)

    @property
    def stored(self) -> Tuple[int, int, int]:
        """A layer's state as a slot stores it: [heads / pack, d_state,
        pack * head_dim]."""
        return (self.heads // self.pack, self.d_state,
                self.pack * self.head_dim)

    @property
    def tail(self) -> int:
        """Values of a layer's convolution tail: ``conv_width - 1`` rows of
        ``conv_channels``."""
        return (self.conv_width - 1) * self.conv_channels

    @property
    def tail_stored(self) -> Tuple[int, int]:
        """A layer's tail as a slot stores it: the values in their order over
        128 lanes, [104, 128] at a Mamba-2 layer's published 3 x 4,352 and
        [32, 128] at a short convolution's 2 x 2,048, so that a slot is
        whole tiles which a copy can name (``ssm_update.tail_stored``)."""
        return _ssm_update.tail_stored(self.conv_width, self.conv_channels)

    def layer_bytes(self, itemsize: int) -> int:
        """One sequence's bytes in one layer (a tail alone: 8 KB at 2 x
        2,048 bfloat16)."""
        return self.heads * self.head_dim * self.d_state * 4 \
            + self.tail * itemsize


@dataclasses.dataclass
class KVCacheConfig:
    """What a page holds is stated once: a head page ``num_kv_heads`` keys
    and values of ``head_dim`` a token a layer, unless ``kind_pages`` states
    each layer kind's own; a latent page one row of ``latent_dim``."""
    num_layers: int
    num_kv_heads: int
    head_dim: int
    block_size: int = 64
    num_blocks: int = 256
    dtype: any = jnp.bfloat16
    # a latent (MLA) page kind: one plane of ``latent_dim`` values a token
    # with no heads, its rows padded with zero lanes to ``latent_row_width``
    latent_dim: int = 0
    # pages by layer kind: every layer's window (None: a full layer), given
    # where a model mixes full and windowed layers; ``window_blocks`` is the
    # windowed kind's pool (``num_blocks`` the full kind's). Left empty, all
    # layers' pages are of one kind in one pool
    layer_windows: Tuple[Optional[int], ...] = ()
    window_blocks: int = 0
    # {"full": HeadPageShape, "window": HeadPageShape} where the two kinds
    # of layer differ in KV heads, or keys and values in width; None: both
    # kinds hold ``num_kv_heads`` x ``head_dim`` keys and values
    kind_pages: Optional[dict] = None
    # query heads a layer, or ``{"full": n, "window": m}``; None: as many as
    # KV heads (``modules.KVCacheSpec.query_heads``)
    query_heads: Any = None
    # every layer's kind by name (``"full"``, ``"window"``, ``"state"``,
    # ``"none"``) where ``layer_windows`` cannot say it: a model some of whose
    # layers keep a recurrent state in a slot and no pages, or nothing at all
    # (a layer without a mixer that looks back). ``num_layers`` then counts the
    # layers of all kinds; ``state_slot`` is what a slot holds of a layer and
    # ``state_slots`` how many sequences have one (one more is padding's)
    layer_kinds: Tuple[str, ...] = ()
    state_slot: Optional[StateSlotShape] = None
    state_slots: int = 0

    @property
    def page_shape(self) -> HeadPageShape:
        """What a head page holds where no kind states its own."""
        return HeadPageShape(self.num_kv_heads, self.head_dim, self.head_dim)


def latent_row_width(latent_dim: int) -> int:
    """A page's row as a pool stores it where its width need not be whole
    lane tiles (a latent row; a key or a value of a width of its own):
    ``latent_dim`` values and
    zero lanes up to the next multiple of the TPU's 128. A last axis that is
    no multiple of 128 gets a device layout with the block index minor, and
    the kernel, which needs the row minor, then has the whole pool copied in
    and out of every step; the tiled row-major layout pads the row in memory
    anyway, so the lanes cost no bytes that layout would not."""
    return -(-latent_dim // 128) * 128


class LatentPageDtypeError(ValueError):
    """A page dtype that a latent (MLA) pool cannot hold."""


class TwoPageKindsError(NotImplementedError):
    """Something that moves block ids of one pool (prefix reuse, the host
    offload tier, the prefix handoff, fp8 scaled pages) was asked of a cache
    that keeps pages of two kinds."""


class StateKindError(NotImplementedError):
    """Something that takes a sequence's cache to be its pages (prefix reuse,
    the host offload tier, the prefix handoff, fp8 scaled pages, speculative
    verification, whose rejected rows are invisible in pages and are NOT in
    a state that has summed them) was asked of a cache some of whose layers
    keep a recurrent state in a slot."""


def one_window(layer_windows) -> Optional[int]:
    """The window of a model's windowed layers (None where it has none); a
    ``ValueError`` that says so where they have more than one size."""
    sizes = set(layer_windows or ()) - {None}
    if len(sizes) > 1:
        raise ValueError(f"more than one window size {sorted(sizes)}: the "
                         f"cache keeps one windowed kind of page")
    return next(iter(sizes), None)


def windowed_table_blocks(rows: int, window: int, block_size: int) -> int:
    """Entries of a windowed layer's block table for a step of ``rows``
    queries a sequence: the blocks that hold the first query's window and
    the rows themselves, wherever the first falls in its block."""
    return (rows + window - 2) // block_size + 2


def blocks_behind_window(first_query: int, window: int, block_size: int):
    """Leading blocks of a sequence that no query from position
    ``first_query`` on can see through ``window``: key ``j`` is seen by query
    ``t`` only if ``j > t - window``. The host gives these back; a step
    program starts a windowed layer's table at this block (ints or jnp)."""
    lead = (first_query - window + 1) // block_size
    return lead * (lead > 0)


class BlockedKVCache:
    def __init__(self, cfg: KVCacheConfig):
        self.cfg = cfg
        scaled = cfg.dtype == jnp.float8_e4m3fn
        if cfg.layer_kinds or mixes_layer_kinds(cfg.layer_windows):
            self.kind = _LayerKindPages(cfg.layer_windows, cfg.kind_pages,
                                        cfg.layer_kinds, cfg.state_slot)
        else:
            self.kind = _kind_for(cfg.latent_dim, scaled)()
        # the last block is the kind's trash block, the target of
        # padding-token writes: never handed out by the allocator
        self.allocator = BlockedAllocator(cfg.num_blocks - 1)
        # ``scales`` is None unless the kind's pages carry them; with pages
        # by layer kind ``data`` is {"full": pool, "window": pool}, and
        # where some layers keep a state {"full": pool, "state": {"ssm",
        # "conv"}}
        self.data, self.scales = self.kind.new_pool(cfg)
        # the windowed kind's own pool, trash block and allocator, and how
        # many of its blocks sequences have given back from behind their
        # windows; None where all pages are of one kind
        self.window_allocator = None
        self.window_blocks_given_back = 0
        def block_bytes(pool, blocks):
            return sum(int(x.nbytes) for x in jax.tree.leaves(pool)) // blocks
        if self.two_kinds:
            self.window_allocator = BlockedAllocator(cfg.window_blocks - 1)
        # each paged kind's block as its own pool stores it: its heads, its
        # widths, its rows' padding
        pools = self.data if self.by_layer_kind else {FULL: self.data}
        self._block_bytes = {
            kind: block_bytes(pools[kind], blocks)
            for kind, blocks in ((FULL, cfg.num_blocks),
                                 (WINDOW, cfg.window_blocks))
            if kind in pools}
        # what one sequence's slot holds over all the state layers
        self.slot_bytes = 0
        if self.has_state:
            self.slot_bytes = self.kind.kinds.count(STATE) \
                * cfg.state_slot.layer_bytes(jnp.dtype(cfg.dtype).itemsize)

    @classmethod
    def for_spec(cls, spec, kv_cache_dtype: str, block_size: int,
                 num_blocks: int, window_blocks: int = 0,
                 state_slots: int = 0) -> "BlockedKVCache":
        """The cache a policy's ``KVCacheSpec`` asks for, its pages stored as
        the engine's ``kv_cache_dtype`` says: ``"model"`` (the spec's compute
        dtype) or ``"fp8"`` (float8_e4m3 pages under scales). Where the spec
        mixes full and windowed layers, ``num_blocks`` is the full layers'
        pool and ``window_blocks`` the windowed layers'; where some layers
        keep a state, ``state_slots`` sequences have a slot."""
        dtypes = {"model": spec.dtype, "fp8": jnp.float8_e4m3fn}
        if kv_cache_dtype not in dtypes:
            raise ValueError(f"unknown kv_cache_dtype {kv_cache_dtype!r}; "
                             f"one of {sorted(dtypes)}")
        return cls(KVCacheConfig(
            num_layers=spec.num_layers, num_kv_heads=spec.num_kv_heads,
            head_dim=spec.head_dim, block_size=block_size,
            num_blocks=num_blocks, dtype=dtypes[kv_cache_dtype],
            latent_dim=spec.latent_dim,
            layer_windows=tuple(spec.layer_windows or ()),
            window_blocks=window_blocks, kind_pages=spec.kind_pages,
            query_heads=spec.query_heads,
            layer_kinds=tuple(spec.layer_kinds or ()),
            state_slot=spec.state_slot, state_slots=state_slots))

    # ------------------------------------------------------------------
    # pages by layer kind: the windowed pool's host side
    # ------------------------------------------------------------------
    @property
    def by_layer_kind(self) -> bool:
        """The pool, and a step program's tables, are a dict by layer kind."""
        return isinstance(self.kind, _LayerKindPages)

    @property
    def two_kinds(self) -> bool:
        """Pages of two kinds: the windowed layers have a pool of their own."""
        return self.by_layer_kind and WINDOW in self.kind.pages

    @property
    def has_state(self) -> bool:
        """Some layers keep a recurrent state in a slot."""
        return self.by_layer_kind and self.kind.state is not None

    def require_one_kind(self, what: str) -> None:
        """Raise by name where ``what`` moves block ids of one pool and this
        cache keeps two, or takes a sequence's cache to be its pages and
        some layers keep a state."""
        if self.has_state:
            raise StateKindError(
                f"{what} takes a sequence's cache to be its pages, and in "
                f"this cache some layers keep a recurrent state in a slot "
                f"(no pages, no block table, nothing a block id names): not "
                f"supported over it")
        if self.two_kinds:
            raise TwoPageKindsError(
                f"{what} moves block ids of one pool, and this cache keeps "
                f"pages of two kinds (full layers' and windowed layers', each "
                f"with its own block tables): not supported over it")

    @property
    def window_steady_blocks(self) -> int:
        """Most windowed blocks a sequence holds between two steps: those of
        its next query's window."""
        return windowed_table_blocks(1, self.kind.window, self.cfg.block_size)

    def window_step_blocks(self, rows: int) -> int:
        """Most windowed blocks one sequence holds while a step of ``rows``
        of its tokens runs, over what it holds between steps."""
        return windowed_table_blocks(rows, self.kind.window,
                                     self.cfg.block_size) \
            - self.window_steady_blocks

    def blocks_of(self, num_tokens: int) -> int:
        """Blocks that hold ``num_tokens`` tokens of one sequence."""
        return -(-int(num_tokens) // self.cfg.block_size)

    def lacking(self, seq, num_tokens: int) -> Tuple[int, int]:
        """(full, windowed) blocks that ``seq`` (None: a sequence not yet
        admitted) lacks to hold ``num_tokens`` tokens between steps."""
        whole = self.blocks_of(num_tokens)
        full = whole - (len(seq.blocks) if seq is not None else 0)
        if not self.two_kinds:
            return full, 0
        held = len(seq.window_blocks) if seq is not None else 0
        return full, max(min(whole, self.window_steady_blocks) - held, 0)

    def ensure_window(self, seq, up_to_tokens: int) -> None:
        """Windowed blocks for ``seq`` up to ``up_to_tokens`` tokens."""
        need = self.blocks_of(up_to_tokens) - seq.window_base \
            - len(seq.window_blocks)
        if need > 0:
            seq.window_blocks.extend(self.window_allocator.allocate(need))

    def give_back_behind_window(self, seq) -> int:
        """Give back the windowed blocks no query of ``seq`` from its next
        position (``seen_tokens``) on can see. Returns how many."""
        dead = int(blocks_behind_window(seq.seen_tokens, self.kind.window,
                                        self.cfg.block_size)) \
            - seq.window_base
        if dead <= 0:
            return 0
        self.window_allocator.free(seq.window_blocks[:dead])
        del seq.window_blocks[:dead]
        seq.window_base += dead
        self.window_blocks_given_back += dead
        return dead

    def release_window(self, seq) -> None:
        """All of ``seq``'s windowed blocks back to their allocator."""
        self.window_allocator.free(seq.window_blocks)
        seq.window_blocks = []
        seq.window_base = 0

    def window_table(self, seq, first_query: int, rows: int) -> np.ndarray:
        """A windowed layer's block table for a step whose first query of
        ``seq`` is at ``first_query``, ``rows`` queries long: entry ``k`` is
        the block that holds tokens ``(base + k) * block_size ..``, ``base``
        the blocks behind the first query's window (the step program derives
        the same from the position); the trash block where ``seq`` holds
        none."""
        n = windowed_table_blocks(rows, self.kind.window, self.cfg.block_size)
        table = np.full((n,), self.cfg.window_blocks - 1, np.int32)
        skip = int(blocks_behind_window(first_query, self.kind.window,
                                        self.cfg.block_size)) - seq.window_base
        live = seq.window_blocks[skip:skip + n]
        table[:len(live)] = live
        return table

    def _fold_of(self, kind: str) -> dict:
        """What the paged kernel's tile follows in a layer of ``kind``
        (``"full"``, ``"window"``), under the names the kernel's own counts
        take: its KV heads, the query heads it folds over each, the block,
        the rows' widths and the item size."""
        cfg = self.cfg
        at = self.kind.stored(kind, cfg) if self.two_kinds else cfg.page_shape
        heads = cfg.query_heads
        if isinstance(heads, dict):
            heads = heads[kind]
        return dict(hkv=at.num_kv_heads, d=at.key_dim, dv=at.value_dim,
                    group=max((heads or 0) // at.num_kv_heads, 1),
                    bs=cfg.block_size, itemsize=jnp.dtype(cfg.dtype).itemsize)

    def _behind_window(self, positions, rows: int, table_blocks: int):
        """``(positions, table blocks)`` as a layer behind the window is
        handed them for a step of ``rows`` queries a sequence from
        ``positions``: over one pool the full table's; over pages by kind
        the windowed kind's own table, which starts at the block the
        position's window starts in and is as long as the window and the
        rows."""
        if not self.two_kinds:
            return list(positions), table_blocks
        window, bs = self.kind.window, self.cfg.block_size
        return [p - int(blocks_behind_window(p, window, bs)) * bs
                for p in positions], windowed_table_blocks(rows, window, bs)

    def decode_tile_keys(self, contexts, table_blocks: int, window) -> dict:
        """Keys the paged kernel's tiles cover for a decode batch of
        ``contexts`` tokens a sequence over full tables of ``table_blocks``:
        ``tile_keys`` with every context read whole, ``tile_keys_windowed``
        with the layers that have a ``window`` reading theirs (a windowed
        kind's table starts behind the window and is as long as it). Each
        over ``ctx_tokens`` / ``ctx_tokens_windowed`` is the tiles' fill.
        Over a latent pool, which the paged kernel does not read, the keys
        of the latent decode kernel's tiles instead (``latent_tile_keys``,
        ``latent_attention.decode_tile_keys``)."""
        bs = self.cfg.block_size
        if self.cfg.latent_dim:
            from deepspeed_tpu.ops.pallas import latent_attention
            return {"latent_tile_keys": latent_attention.decode_tile_keys(
                contexts, table_blocks, bs)}
        whole = windowed = decode_tile_keys(contexts, table_blocks, bs)
        if window is not None:
            last, mb = self._behind_window([c - 1 for c in contexts], 1,
                                           table_blocks)
            windowed = decode_tile_keys([p + 1 for p in last], mb, bs, window)
        return {"tile_keys": whole, "tile_keys_windowed": windowed}

    def decode_slot_copies(self, contexts, bucket: int, table_blocks: int,
                           window) -> dict:
        """Page copies one layer's paged kernel call issues for a decode
        batch of ``contexts`` padded to ``bucket`` over full tables of
        ``table_blocks``: ``slot_copies`` a call of a layer that walks the
        full table, and ``slot_copies_windowed`` a call of a layer behind
        ``window`` (a windowed kind's over its own table), each from the
        heads and the row widths of the kind's own pages. Over a latent
        pool ``latent_page_copies``, the latent decode kernel's
        (``latent_attention.decode_page_copies``: one a live page, a page
        has no heads and its row is key and value)."""
        if self.cfg.latent_dim:
            from deepspeed_tpu.ops.pallas import latent_attention
            return {"latent_page_copies": latent_attention.decode_page_copies(
                contexts, bucket, table_blocks, self.cfg.block_size)}
        whole = windowed = decode_slot_copies(
            contexts, bucket, mb=table_blocks, **self._fold_of("full"))
        if window is not None:
            last, mb = self._behind_window([c - 1 for c in contexts], 1,
                                           table_blocks)
            windowed = decode_slot_copies(
                [p + 1 for p in last], bucket, mb=mb, window=window,
                **self._fold_of("window"))
        return {"slot_copies": whole, "slot_copies_windowed": windowed}

    def chunk_tile_keys(self, start: int, bucket: int, table_blocks: int,
                        window) -> dict:
        """What the paged kernel reads for one chunk padded to ``bucket``
        rows at positions ``start ..`` over a full table of
        ``table_blocks``: ``tile_keys``, the keys of the tiles a query row
        is multiplied by, summed over the chunk's rows, in a layer that
        reads the context whole, and ``tile_keys_windowed`` in a layer
        behind ``window`` (the visible pairs over each is the tiles' fill);
        ``tile_copies``, the K and V page copies a call of the first issues
        for all its heads. Over a latent pool, which the paged kernel does
        not read, the panels of the latent prefill kernel's grid instead
        (``latent_attention.prefill_panels``: ``latent_panels`` a head a
        layer, ``latent_panels_masked`` of them, ``latent_panels_dead``) and
        beside them ``latent_keys_gathered``, the rows one layer's call
        gathers from the pages and up-projects for the chunk
        (``_latent_prefill_attn``: ``prefill_keys`` of the table's)."""
        if self.cfg.latent_dim:
            from deepspeed_tpu.ops.pallas.latent_attention import (
                prefill_keys, prefill_panels)
            keys = table_blocks * self.cfg.block_size
            return {**prefill_panels(start, bucket, keys),
                    LATENT_KEYS_ARG: prefill_keys(bucket, keys)}
        whole, copies = chunk_tile_keys(start, bucket, mb=table_blocks,
                                        **self._fold_of("full"))
        windowed = whole
        if window is not None:
            (first,), mb = self._behind_window([start], bucket, table_blocks)
            windowed, _ = chunk_tile_keys(first, bucket, mb=mb, window=window,
                                          **self._fold_of("window"))
        return {"tile_keys": whole, "tile_keys_windowed": windowed,
                "tile_copies": copies}

    def chunk_by_page(self, bucket: int) -> bool:
        """Whether a chunk program of ``bucket`` rows writes the chunk's rows
        a page at a time (the kind's static rule, ``_Pages.chunk_by_page``)."""
        return self.kind.chunk_by_page(bucket, self.cfg.block_size)

    def pages_held(self) -> dict:
        """Blocks sequences hold now, by kind, and their bytes, each kind's
        from a block of its own pool (``full_bytes`` over the live tokens is
        what a token costs the full layers, their rows' padding with it;
        ``window_bytes`` is a constant a sequence)."""
        full = self.allocator.total_blocks - self.allocator.free_blocks
        full_bytes = full * self._block_bytes["full"]
        if not self.two_kinds:
            return {"full_blocks": full, "full_bytes": full_bytes,
                    "held_bytes": full_bytes}
        win = self.window_allocator.total_blocks \
            - self.window_allocator.free_blocks
        win_bytes = win * self._block_bytes["window"]
        return {"full_blocks": full, "window_blocks": win,
                "window_blocks_given_back": self.window_blocks_given_back,
                "full_bytes": full_bytes, "window_bytes": win_bytes,
                "held_bytes": full_bytes + win_bytes}

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
        """Free blocks, of both kinds where there are two."""
        if self.window_allocator is not None:
            return self.allocator.free_blocks + \
                self.window_allocator.free_blocks
        return self.allocator.free_blocks

    @property
    def usable_blocks(self) -> int:
        """Blocks sequences can hold (each kind's last is its trash block)."""
        return self.cfg.num_blocks - 1 + (
            self.cfg.window_blocks - 1 if self.two_kinds else 0)

    def blocks_needed(self, num_tokens: int) -> int:
        """Blocks a sequence of ``num_tokens`` tokens holds between steps:
        with two kinds, its full layers' and its windowed layers'."""
        return sum(self.lacking(None, num_tokens))

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
        host ndarrays: the pool's shape with ``len(blocks)`` on its block
        axis. A deliberate
        device->host transfer — demotion runs OFF the per-tick fast path,
        only when the serving tier policy decides to spill."""
        self.require_one_kind("gathering a sequence's blocks (KV offload, "
                              "prefix handoff)")
        idx = np.asarray(blocks, np.int32)
        data = np.asarray(jnp.take(self.data, idx, axis=self.kind.block_axis))
        scales = (np.asarray(self.scales[:, :, :, idx])
                  if self.scales is not None else None)
        return data, scales

    def gathered_blocks(self, data: np.ndarray) -> int:
        """How many blocks a ``gather_blocks`` payload holds."""
        return int(data.shape[self.kind.block_axis])

    def scatter_blocks(self, blocks: List[int], data: np.ndarray,
                       scales: Optional[np.ndarray] = None) -> None:
        """Write gathered pages back into (possibly different) blocks —
        the promotion path. fp8 scales are restored alongside the pages,
        so a promoted sequence's quantization state is bit-identical to
        what it was at demotion."""
        idx = jnp.asarray(np.asarray(blocks, np.int32))
        self.data = _set_blocks(self.data, idx,
                                jnp.asarray(data, self.cfg.dtype),
                                self.kind.block_axis)
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


@partial(jax.jit, static_argnames=("layer",))
def write_kv(cache_data, layer: int, k_new, v_new, block_ids, offsets):
    """Scatter new K/V tokens into their page slots, in place where the pool
    is donated (``generic_decode``). Under a ``jit`` of its own so that the
    two scatters are traced once a layer and a shape in a PROCESS and not
    once a layer in every step program (every chunk program writes a decode
    half's rows too, the same shape in all of them: PERF.md section 6, PR
    45); the layer stays static, so the program XLA sees is the same.

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


class PageSlots(NamedTuple):
    """Where one sequence's chunk lands, a page at a time: the block of each
    page its rows can span (``page_write.page_count`` of them; the trash
    block for a page that is all bucket padding), and the chunk's rows
    ``[lo, hi)`` counted from the first page's first row: ``lo`` the first
    position's offset in its page, ``hi - lo`` the real rows."""
    pages: Any
    lo: Any
    hi: Any


@partial(jax.jit, static_argnames=("attn_impl",))
def write_chunk_pages(pool, layer, rows, slots: PageSlots, attn_impl: str):
    """A chunk's rows into a paged pool one update a (group, page) tile,
    ``[block_size, W]`` contiguous in the pool as it lies, in place where
    the pool is donated: the one write of every pool format a chunk goes
    through. pool: ``[..., NB, bs, W]`` behind whatever leading axes its kind
    gives it, the layer first and the ``n`` groups a layer writes together
    last among them (``[L, 2, H_kv, ...]``: a layer's K heads then its V
    heads, ``n = 2 * H_kv``; a split kind's ``[L, H_kv, ...]``; a latent
    pool's ``[L, ...]``, ``n = 1``); rows: ``[T, n, C]`` with ``C <= W``
    (zero lanes fill the rest). The first page's rows before ``slots.lo``
    and the last's from ``slots.hi`` on keep what they held
    (``ops/pallas/page_write.py``: the Pallas kernel on a TPU, the same in
    ``jax.numpy`` where ``attn_impl`` resolves to ``gather``). Merging the
    leading axes moves nothing (the tiled axes are the last two), so the
    pool keeps its layout, as ``write_kv`` says it must. Under a ``jit`` of
    its own with the layer a VALUE, so that a step program traces it once a
    shape and not once a layer."""
    nb, bs, w = pool.shape[-3:]
    new = _page_write.frames(
        cast_to_page_dtype(_lanes(rows, w), pool.dtype), slots.lo, bs)
    impl = _resolve_impl(attn_impl)
    write = _page_write.write_pages_reference if impl == "gather" else \
        partial(_page_write.write_pages, interpret=impl == "kernel_interpret")
    return write(pool.reshape(-1, nb, bs, w), layer, new,
                 *slots).reshape(pool.shape)


# --- attention over the pages: kernel or gather path --------------------
ATTN_IMPLS = ("auto", "kernel", "kernel_interpret", "gather")


def _resolve_impl(attn_impl: str) -> str:
    """``auto`` to the Pallas kernel on a TPU and the gather path elsewhere."""
    if attn_impl not in ATTN_IMPLS:
        raise ValueError(f"unknown attn_impl {attn_impl!r}; one of {ATTN_IMPLS}")
    if attn_impl == "auto":
        return "kernel" if jax.default_backend() == "tpu" else "gather"
    return attn_impl


def _latent_paged_attn(q_nope, q_rope, pool, layer, block_tables, positions,
                       w_ukv, scale, attn_impl: str):
    """Decode over a latent pool [L, NB, bs, W], one token a sequence, with
    the up-projections folded: ``q~_i = W_uk_i^T q_nope_i`` scores against
    the cached rows themselves and ``W_uv_i`` is applied to the summed rows
    (``ops/pallas/latent_attention.py``). q_nope: [B, H, d_n]; q_rope:
    [B, H, d_r], rotated; w_ukv: [rank, H, d_n + d_v]. Returns [B, H, d_v].
    Kernel against gather path, as ``_HeadPages._read``. The fold sits under
    ``attn/latent_q``, the call and the value unfold behind it under the two
    leaves of ``attn/latent_paged`` (``names.SERVED_LEAF_SCOPES``)."""
    from deepspeed_tpu.ops.pallas.latent_attention import \
        latent_paged_attention_reference
    rank, d_n = w_ukv.shape[0], q_nope.shape[-1]
    with jax.named_scope("attn/latent_q"):
        q = jnp.concatenate(
            [jnp.einsum("bhk,rhk->bhr", q_nope, w_ukv[..., :d_n]), q_rope], -1)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pool.shape[-1] - q.shape[-1])))
    impl = _resolve_impl(attn_impl)
    with jax.named_scope("attn/latent_paged/kernel"):
        if impl == "gather":
            o = latent_paged_attention_reference(
                q, pool[layer], block_tables, positions, scale, rank)
        else:
            # the kernel reads the pool as [L * NB, bs, W]: the layer goes
            # into the tables, so that every layer's call is layer 0's
            o = _latent_paged_call(
                q, pool, block_tables + layer * pool.shape[1], positions,
                scale=float(scale), rank=rank,
                interpret=impl == "kernel_interpret")
    with jax.named_scope("attn/latent_paged/unfold"):
        return jnp.einsum("bhr,rhv->bhv", o, w_ukv[..., d_n:])


@partial(jax.jit, static_argnames=("scale", "rank", "interpret"))
def _latent_paged_call(q, pool, block_tables, positions, *, scale: float,
                       rank: int, interpret: bool):
    """The latent decode kernel over the blocks ``block_tables`` name in the
    pool read as ONE layer of ``L * NB`` blocks. A function of its own under
    ``jit``, and the same for every layer, so that a step program traces it
    and lowers it to Mosaic once and not once a layer (as
    ``paged_attention._paged_call``): every chunk program carries this fold
    for its decode half, and a lowering a layer was a second and a half of
    every such program's first call at five layers."""
    from deepspeed_tpu.ops.pallas.latent_attention import \
        latent_paged_attention
    return latent_paged_attention(q, pool, 0, block_tables, positions, scale,
                                  rank, interpret=interpret)


@partial(jax.jit, static_argnames=("scale", "interpret"))
def _latent_prefill_call(q_nope, q_rope, k_nope, k_rope, v, start, *,
                         scale: float, interpret: bool):
    """The latent prefill kernel, under ``jit`` for ``_latent_paged_call``'s
    reason: it takes no layer, so the layers' calls are one."""
    from deepspeed_tpu.ops.pallas.latent_attention import \
        latent_prefill_attention
    return latent_prefill_attention(q_nope, q_rope, k_nope, k_rope, v, start,
                                    scale, interpret=interpret)


def _latent_prefill_attn(q_nope, q_rope, pool, layer, block_table, start,
                         w_ukv, scale, attn_impl: str):
    """One sequence's chunk over a latent pool, unfolded: the context's rows
    (the chunk's own, already written, among them) are gathered from the
    pages and every head's keys and values up-projected from them, 2 x (192 +
    128) operations a pair a head at the published sizes where the folded
    form costs 2 x (576 + 512). q_nope: [T, H, d_n]; q_rope: [T, H, d_r],
    rotated; block_table: [MB]; start: the chunk's first position. Returns
    [T, H, d_v]. Every operation sits under one of the three leaves of
    ``attn/latent_prefill`` (``names.SERVED_LEAF_SCOPES``: ``gather``,
    ``up_proj``, ``kernel``), opened in the order the operations are made, so
    that a device trace says what the gather and the up-projection of the
    WHOLE bucket cost beside the kernel."""
    from deepspeed_tpu.ops.pallas.latent_attention import (
        latent_prefill_attention_reference, prefill_keys)
    rank, d_n = w_ukv.shape[0], q_nope.shape[-1]
    d_r = q_rope.shape[-1]
    nb, bs = pool.shape[1], pool.shape[2]
    # whole key panels for the kernel: dead slots read the trash page, which
    # no query's horizon reaches
    mb = block_table.shape[0]
    keys = prefill_keys(q_nope.shape[0], mb * bs)
    with jax.named_scope("attn/latent_prefill/gather"):
        table = jnp.pad(block_table, (0, -(-keys // bs) - mb),
                        constant_values=nb - 1)
        rows = pool[layer, table].reshape(-1, pool.shape[-1])[:keys]
        ckv = rows[:, :rank]
    with jax.named_scope("attn/latent_prefill/kernel"):
        q_nope, q_rope = q_nope.transpose(1, 0, 2), q_rope.transpose(1, 0, 2)
    # keys and values each from their own half of the up-projection: one
    # product sliced afterwards is two more copies of the context
    with jax.named_scope("attn/latent_prefill/up_proj"):
        k_nope = jnp.einsum("sr,rhk->hsk", ckv, w_ukv[..., :d_n])
    with jax.named_scope("attn/latent_prefill/gather"):
        k_rope = rows[:, rank:rank + d_r]
    with jax.named_scope("attn/latent_prefill/up_proj"):
        v = jnp.einsum("sr,rhk->hsk", ckv, w_ukv[..., d_n:])
    args = (q_nope, q_rope, k_nope, k_rope, v, start)
    impl = _resolve_impl(attn_impl)
    with jax.named_scope("attn/latent_prefill/kernel"):
        if impl == "gather":
            out = latent_prefill_attention_reference(*args, scale)
        else:
            out = _latent_prefill_call(*args, scale=float(scale),
                                       interpret=impl == "kernel_interpret")
        return out.transpose(1, 0, 2)


# --- the page kinds ------------------------------------------------------
class _Pages:
    """What the kinds share: the trash block and the slots a step's rows land
    in, one (block, offset) a row through the sequence's block table."""
    block_axis: int

    def __init__(self, window=None):
        self.window = window

    def row_operands(self, layer: int) -> int:
        """How many of the operands a block hands ``attend`` hold one row a
        token (q, k, v; a latent kind's q_nope, q_rope, row), leading the
        rest, which are the layer's own whatever their shapes (an
        up-projection, a scale; every keyword: sinks, a window, a softcap).
        A step of a chunk's rows and a decode batch's cuts these, and only
        these, between ``attend_chunk`` and ``attend_decode``."""
        return 3

    def trash_block(self, cache) -> int:
        """The pool's last block, where padding rows are written. The pages
        are the first leaf of what the step programs carry."""
        return jax.tree.leaves(cache)[0].shape[self.block_axis] - 1

    #: whether a chunk's rows go into this kind's pool a page at a time
    by_page = True

    def chunk_by_page(self, rows: int, block_size: int) -> bool:
        """Whether a chunk padded to ``rows`` is written a page at a time
        (``write_chunk_pages``) and not a row at a time: a static property
        of the call. A chunk shorter than a block (speculation's ``k + 1``
        rows, a bucket under a block) would move two pages' tiles for its
        few rows and keeps the row scatter."""
        return self.by_page and rows >= block_size

    def _row_slots(self, cache, block_table, safe_pos, valid,
                   block_size: int):
        """Each row's (block, offset), the trash block for the rows that are
        bucket padding."""
        mb = block_table.shape[0]
        blk = jnp.where(
            valid, block_table[jnp.minimum(safe_pos // block_size, mb - 1)],
            self.trash_block(cache))
        return blk, safe_pos % block_size

    def chunk_slots(self, cache, block_table, start, safe_pos, valid,
                    block_size: int):
        """Where one sequence's chunk lands: ``PageSlots`` where it is
        written a page at a time (``chunk_by_page``), else each row's
        (block, offset); the trash block for what is bucket padding, a whole
        page or a row. block_table: [MB]; start: the chunk's first position,
        anywhere in its block; safe_pos, valid: [T]. The table lookup is the
        same on both paths: entry ``min(position // block_size, MB - 1)``."""
        rows = safe_pos.shape[0]
        if not self.chunk_by_page(rows, block_size):
            return self._row_slots(cache, block_table, safe_pos, valid,
                                   block_size)
        lo = start % block_size
        hi = lo + jnp.sum(valid)
        page = jnp.arange(_page_write.page_count(rows, block_size))
        entry = jnp.minimum(start // block_size + page,
                            block_table.shape[0] - 1)
        return PageSlots(jnp.where(page * block_size < hi, block_table[entry],
                                   self.trash_block(cache)), lo, hi)

    def decode_slots(self, cache, block_tables, safe_pos, valid,
                     block_size: int):
        """Where a decode batch's tokens land, one a sequence. block_tables:
        [B, MB]; safe_pos, valid: [B]."""
        mb = block_tables.shape[1]
        blk = jnp.where(
            valid,
            jnp.take_along_axis(
                block_tables,
                jnp.minimum(safe_pos // block_size, mb - 1)[:, None],
                axis=1)[:, 0],
            self.trash_block(cache))
        return blk, safe_pos % block_size


class _HeadPages(_Pages):
    """K and V planes of head-major pages, ``[L, 2, H_kv, NB, bs, D]``: one
    page of one KV head is a contiguous (bs, D) tile, the shape the paged
    kernel DMAs per grid step, a table entry's page of every head in one
    strided copy for a decode fold (``ops/pallas/paged_attention.py``); shard
    over ``tensor`` on the heads. A block hands its ``attend`` ``(q, k, v,
    window=, softcap=)``: q [N, H, D], k and v [N, H_kv, D], one row a token;
    ``window`` left out is the spec's."""
    block_axis = 3

    @staticmethod
    def empty(layers: int, shape: HeadPageShape, blocks: int,
              block_size: int, dtype):
        """An empty pool of ``layers`` layers' pages of ``shape``."""
        return jnp.zeros((layers, 2, shape.num_kv_heads, blocks, block_size,
                          shape.key_dim), dtype)

    @staticmethod
    def new_pool(cfg: KVCacheConfig):
        """``(pages, scales or None)`` of an empty pool."""
        return _HeadPages.empty(cfg.num_layers, cfg.page_shape,
                                cfg.num_blocks, cfg.block_size,
                                cfg.dtype), None

    def _write(self, cache, layer, k, v, slots):
        return write_kv(cache, layer, k, v, *slots)

    def _write_pages(self, cache, layer, k, v, slots, attn_impl):
        # a layer's K heads then its V heads lie side by side in the pool
        return write_chunk_pages(cache, layer, jnp.concatenate([k, v], 1),
                                 slots, attn_impl)

    def _write_chunk(self, cache, layer, k, v, slots, attn_impl):
        """A chunk's rows by page where its slots say so (``chunk_slots``),
        else by row."""
        if isinstance(slots, PageSlots):
            return self._write_pages(cache, layer, k, v, slots, attn_impl)
        return self._write(cache, layer, k, v, slots)

    def _read(self, pages, layer, q, block_tables, start_pos, attn_impl,
              window="spec", softcap=None, scales=None, sinks=None):
        """q: [B, T, H, D]; kernel or gather reference, both with ``softcap``
        (gemma2), ``sinks`` (a logit a query head in the softmax's
        denominator) and, for fp8 pages, ``scales`` applied per (head, page)
        on load."""
        window = self.window if window == "spec" else window
        impl = _resolve_impl(attn_impl)
        if impl == "gather":
            ks, vs = (scales[layer, 0], scales[layer, 1]) \
                if scales is not None else (None, None)
            return paged_attention_reference(
                q, pages[layer, 0], pages[layer, 1], block_tables, start_pos,
                window=window, softcap=softcap, k_scales=ks, v_scales=vs,
                sinks=sinks)
        # the kernel takes the pool whole: a slice of it is a copy of it
        return paged_attention_pool(
            q, pages, layer, block_tables, start_pos, window=window,
            softcap=softcap, scales=scales, sinks=sinks,
            interpret=impl == "kernel_interpret")

    def attend_chunk(self, cache, layer, slots, block_table, start,
                     attn_impl, q, k, v, **how):
        """Write one chunk's rows, then attend over the sequence's pages from
        position ``start``. Returns (out [T, H, D], the pool)."""
        with jax.named_scope("attn/kv_write"):
            cache = self._write_chunk(cache, layer, k, v, slots, attn_impl)
        with jax.named_scope("attn/paged"):
            return self._read(cache, layer, q[None], block_table[None],
                              jnp.asarray(start).reshape(1), attn_impl,
                              **how)[0], cache

    def attend_decode(self, cache, layer, slots, block_tables, positions,
                      attn_impl, q, k, v, **how):
        """Write one token a sequence, then attend over each sequence's
        pages. Returns (out [B, H, D], the pool). A decode batch's rows lie
        in as many pages as sequences, so they keep the row scatter (on a
        v5e 0.06 ms a pool at 32 rows x 8 heads and 0.20 at 256: PERF.md
        section 6, PR 55)."""
        with jax.named_scope("attn/kv_write"):
            cache = self._write(cache, layer, k, v, slots)
        with jax.named_scope("attn/paged"):
            return self._read(cache, layer, q[:, None], block_tables,
                              positions, attn_impl, **how)[:, 0], cache


class _SplitHeadPages(_HeadPages):
    """Head pages whose keys and values have widths of their own: a pool
    ``{"k": [L, H_kv, NB, bs, W_k], "v": [L, H_kv, NB, bs, W_v]}``, each row
    its ``key_dim`` or ``value_dim`` values and zero lanes up to
    ``latent_row_width`` (a 192-wide key lies in 256 lanes: the device's
    tiled layout pads a row to whole lane tiles whatever the shape says, so
    the shape says it, and a block's bytes are what it costs). The kernel
    scores a q padded with zeros to ``W_k`` at ``key_dim``'s scale and its
    output is cut to ``value_dim``. A block hands its ``attend`` ``(q [N, H,
    key_dim], k [N, H_kv, key_dim], v [N, H_kv, value_dim])``."""
    block_axis = 2

    def __init__(self, window, shape: HeadPageShape):
        super().__init__(window)
        self.shape = shape

    @staticmethod
    def empty(layers: int, shape: HeadPageShape, blocks: int,
              block_size: int, dtype):
        return {name: jnp.zeros((layers, shape.num_kv_heads, blocks,
                                 block_size, latent_row_width(dim)), dtype)
                for name, dim in (("k", shape.key_dim),
                                  ("v", shape.value_dim))}

    def _write(self, cache, layer, k, v, slots):
        # the head is an index like the block and the offset, one update one
        # row of the pool as it lies in memory (``write_kv``)
        blk, off = slots[0][:, None], slots[1][:, None]
        heads = jnp.arange(k.shape[1])[None, :]
        return {name: cache[name].at[layer, heads, blk, off].set(
                    cast_to_page_dtype(_lanes(new, cache[name].shape[-1]),
                                       cache[name].dtype))
                for name, new in (("k", k), ("v", v))}

    def _write_pages(self, cache, layer, k, v, slots, attn_impl):
        return {name: write_chunk_pages(cache[name], layer, new, slots,
                                        attn_impl)
                for name, new in (("k", k), ("v", v))}

    def _read(self, pages, layer, q, block_tables, start_pos, attn_impl,
              window="spec", sinks=None):
        window = self.window if window == "spec" else window
        how = dict(window=window, sinks=sinks,
                   scale=self.shape.key_dim ** -0.5)
        q = _lanes(q, pages["k"].shape[-1])
        impl = _resolve_impl(attn_impl)
        if impl == "gather":
            out = paged_attention_reference(
                q, pages["k"][layer], pages["v"][layer], block_tables,
                start_pos, **how)
        else:
            out = paged_attention_pool(
                q, (pages["k"], pages["v"]), layer, block_tables, start_pos,
                interpret=impl == "kernel_interpret", **how)
        return out[..., :self.shape.value_dim]


def _lanes(x, width: int):
    """``x`` with zero lanes up to ``width`` on its last axis."""
    pad = width - x.shape[-1]
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),)) if pad else x


class _ScaledHeadPages(_HeadPages):
    """Head pages in fp8 under per-(layer, k/v, head, page) fp32 scales
    ``[L, 2, H_kv, NB]``: stored value = real / scale, the scale grown
    monotonically as outliers arrive and the whole page requantized under
    it (``write_kv_scaled``; the reference fp quantizer is group-scaled the
    same way, csrc/fp_quantizer/fp_quantize.cu, group absmax). The step
    programs carry ``(pages, scales)`` and both attention paths dequantize
    on load. A chunk's rows keep the row scatter (``by_page`` False): a
    page's requantisation is tied to the rows that grow its scale, so the
    write is not a move of tiles; no cell of the benchmark runs fp8 pages
    and the row path's cost here is not measured."""
    by_page = False

    @staticmethod
    def new_pool(cfg: KVCacheConfig):
        return _HeadPages.new_pool(cfg)[0], jnp.ones(
            (cfg.num_layers, 2, cfg.num_kv_heads, cfg.num_blocks), jnp.float32)

    def chunk_slots(self, cache, block_table, start, safe_pos, valid,
                    block_size: int):
        # and the pages the chunk's valid tokens can land on: a contiguous
        # table slice (clamp duplicates repeat the same slot — identical
        # updates, safe for write_kv_scaled's requantize scatter). Static
        # worst-case page count: offsets start%bs .. start%bs+tb-1 span up to
        # (tb + bs - 2)//bs + 1 pages — a chunk smaller than a page that
        # crosses a boundary still touches TWO pages (tb//bs+1 missed that)
        blk, off = self._row_slots(cache, block_table, safe_pos, valid,
                                   block_size)
        tb, mb = safe_pos.shape[0], block_table.shape[0]
        touch_idx = jnp.minimum(
            start // block_size +
            jnp.arange(_page_write.page_count(tb, block_size)), mb - 1)
        return blk, off, block_table[touch_idx]

    def decode_slots(self, cache, block_tables, safe_pos, valid,
                     block_size: int):
        # each token touches exactly its own page (invalid rows all write
        # the trash page with identical per-page updates)
        blk, off = super().decode_slots(cache, block_tables, safe_pos, valid,
                                        block_size)
        return blk, off, blk

    def _write(self, cache, layer, k, v, slots):
        data, scales = cache
        for kv, new in enumerate((k, v)):
            data, scales = write_kv_scaled(data, scales, layer, kv, new,
                                           *slots)
        return data, scales

    def _read(self, cache, *args, **how):
        return super()._read(cache[0], *args, scales=cache[1], **how)


class _LatentPages(_Pages):
    """One headless plane ``[L, NB, bs, W]`` of latent (MLA) rows, ``[ckv ;
    k_rope]`` and zero lanes up to ``latent_row_width``: all of a row the key
    of every query head, its leading compressed part the value. A block hands
    its ``attend`` ``(q_nope, q_rope, row, w_ukv, scale)``, one row a token
    and the key-value up-projection; a chunk attends unfolded over keys and
    values up-projected from the gathered rows, a decode batch folded over
    the pages themselves."""
    block_axis = 1

    @staticmethod
    def new_pool(cfg: KVCacheConfig):
        if cfg.dtype == jnp.float8_e4m3fn:
            raise LatentPageDtypeError(
                "fp8 scaled pages are not supported over a latent cache: "
                "their per-(head, page) scales and K and V planes have no "
                "place on one headless plane; use kv_cache_dtype='model'")
        return jnp.zeros((cfg.num_layers, cfg.num_blocks, cfg.block_size,
                          latent_row_width(cfg.latent_dim)), cfg.dtype), None

    def _write_chunk(self, cache, layer, row, slots, attn_impl):
        """A chunk's rows by page where its slots say so (``chunk_slots``:
        a row is its one group's), else by row."""
        if isinstance(slots, PageSlots):
            return write_chunk_pages(cache, layer, row[:, None], slots,
                                     attn_impl)
        return write_latent(cache, layer, row, *slots)

    def attend_chunk(self, cache, layer, slots, block_table, start,
                     attn_impl, q_nope, q_rope, row, w_ukv, scale):
        with jax.named_scope("attn/latent_write"):
            cache = self._write_chunk(cache, layer, row, slots, attn_impl)
        return _latent_prefill_attn(q_nope, q_rope, cache, layer, block_table,
                                    start, w_ukv, scale, attn_impl), cache

    def attend_decode(self, cache, layer, slots, block_tables, positions,
                      attn_impl, q_nope, q_rope, row, w_ukv, scale):
        with jax.named_scope("attn/latent_write"):
            cache = write_latent(cache, layer, row, *slots)
        return _latent_paged_attn(q_nope, q_rope, cache, layer, block_tables,
                                  positions, w_ukv, scale, attn_impl), cache


def mixes_layer_kinds(layer_windows) -> bool:
    """Whether a spec's per-layer windows name full layers and windowed ones
    (then the cache keeps pages by layer kind)."""
    return one_window(layer_windows) is not None \
        and None in set(layer_windows)


class _TailSlots:
    """The layers that keep no pages: what a sequence leaves in one of them
    does not grow with its context, one SLOT a sequence and no block table.
    This class is the kind whose slot is the TAIL of a causal convolution and
    nothing else (``StateSlotShape.tail_only``: a gated short convolution of
    ``K`` taps looks ``K - 1`` rows back and keeps no other state), a pool
    ``{"conv": [L_state, slots + 1, rows, lanes]}`` in the compute type (as
    ``ops/pallas/ssm_update.py`` stores a tail, ``tail_stored``: 8 KB a layer
    a sequence at 2 x 2,048 bfloat16); ``_StateSlots`` below adds a
    recurrent state to it. A sequence's slot is the engine's
    ``SequenceDescriptor.slot``, held from ``create`` to ``pop``; the slot
    past the last is padding's, as the trash block is the pages'. Nobody
    zeroes a slot when a sequence leaves it: a chunk that starts at position
    0 starts from a zero tail, whoever held the slot before. The kind owns
    the tail, the taps' sum (then ``activation``: none here) and the write;
    what is multiplied in before and after the
    convolution, and both projections, are the policy's. A block hands its
    ``attend`` plain arrays: ``x`` [N, C] (one row a token, what the
    convolution runs over) and the convolution's ``kernel`` [C, K]; it gets
    back the convolved rows [N, C] in ``x``'s type."""
    #: what follows the taps' sum, by ``ops/ssm.py`` ``ACTIVATIONS``' name
    activation = None

    def __init__(self, shape: StateSlotShape):
        self.shape = shape

    # a kind is what its slot holds: ``attend_chunk`` and ``attend_decode``
    # run under ``jit`` with the kind static and the LAYER A VALUE, so that a
    # step program of 36 such layers traces and lowers each once and calls it
    # a layer (as ``ssm_update._update_call``; traced a layer, the decode
    # half every chunk program carries was a second of host time a program
    # at 40 layers: PERF.md section 6, PR 45)
    def __hash__(self):
        return hash((type(self), self.shape))

    def __eq__(self, other):
        return type(other) is type(self) and other.shape == self.shape

    def row_operands(self, layer: int) -> int:
        """``x`` (``_Pages.row_operands``)."""
        return 1

    def empty(self, layers: int, slots: int, dtype):
        return {"conv": jnp.zeros(
            (layers, slots + 1) + self.shape.tail_stored, dtype)}

    def _held_tail(self, tails, layer, slot, fresh):
        """The slot's tail [K - 1, C], zeros where the chunk is ``fresh``."""
        return _ssm_update.unpack_tail(
            jnp.where(fresh, 0, tails[layer, slot]),
            self.shape.conv_width, self.shape.conv_channels)

    def _new_tail(self, rows, true_len):
        """The tail a chunk leaves, of ``causal_conv``'s joined ``rows``: cut
        behind the last REAL row, so that bucket padding moves nothing."""
        return jax.lax.dynamic_slice_in_dim(rows, true_len,
                                            self.shape.conv_width - 1)

    def _tail_written(self, tails, layer, slot, tail):
        return tails.at[layer, slot].set(
            _ssm_update.pack_tail(tail, self.shape.tail_stored))

    def _decode_conv(self, tails, layer, slots, attn_impl, x, kernel, bias):
        """One token a row: each row's tail shifted by its token, by the
        Pallas kernel that moves the rows' slots alone (or gather, shift,
        scatter: ``attn_impl``). Returns (the convolved rows [B, C] in ``x``'s
        type, the tails' pool)."""
        impl = _resolve_impl(attn_impl)
        how = {} if impl == "gather" else \
            dict(interpret=impl == "kernel_interpret")
        shift = _ssm_update.ssm_conv_step_reference if impl == "gather" \
            else _ssm_update.ssm_conv_step
        return shift(tails, layer, slots, x, kernel, bias,
                     activation=self.activation, **how)

    @partial(jax.jit, static_argnames=("self", "attn_impl"))
    def attend_chunk(self, cache, layer, slots, attn_impl, x, kernel):
        """One sequence's chunk: the convolution over its rows behind the
        slot's tail, and the new tail written back."""
        slot, valid, fresh = slots
        true_len = jnp.sum(valid)
        with jax.named_scope("conv/shift"):
            conv, rows = ssm.causal_conv(
                x, self._held_tail(cache["conv"], layer, slot, fresh), kernel,
                activation=self.activation)
            return conv.astype(x.dtype), {"conv": self._tail_written(
                cache["conv"], layer, slot, self._new_tail(rows, true_len))}

    @partial(jax.jit, static_argnames=("self", "attn_impl"))
    def attend_decode(self, cache, layer, slots, attn_impl, x, kernel):
        """One token a sequence: each row's tail shifted by its token."""
        with jax.named_scope("conv/shift"):
            conv, tails = self._decode_conv(
                cache["conv"], layer, slots, attn_impl, x, kernel,
                jnp.zeros(x.shape[1:], jnp.float32))
        return conv, {"conv": tails}


class _StateSlots(_TailSlots):
    """A slot that holds a Mamba-2 layer's recurrent state beside the tail
    (``StateSlotShape`` with heads): ``{"ssm": [L_state, slots + 1, G, N, W]
    float32, "conv": [L_state, slots + 1, rows, lanes]}`` (as
    ``ops/pallas/ssm_update.py`` stores a state and a tail, ``stored`` and
    ``tail_stored``), the slots and their bookkeeping ``_TailSlots``'s. A
    chunk that starts at position 0 starts from a zero state and a zero tail.
    The kind owns the tail, the state, the scan or the update, and the
    writes; what a model's parameters are called and how its step is made is
    the policy's. A block hands its ``attend`` plain arrays, as an attention
    layer hands q, k and v: ``xbc`` [N, C] (the mixer's projection before the
    convolution, one row a token), ``step`` [N, H] float32 (> 0: after the
    model's softplus), the convolution's ``kernel`` [C, K] and ``bias`` [C],
    ``a_log`` [H] (``A = -exp(a_log)``) and the skip ``d`` [H]; it gets back
    ``y`` [N, H * P] with the skip term in it."""
    activation = "silu"

    def row_operands(self, layer: int) -> int:
        """``xbc`` and ``step`` (``_Pages.row_operands``)."""
        return 2

    def empty(self, layers: int, slots: int, dtype):
        return {"ssm": jnp.zeros((layers, slots + 1) + self.shape.stored,
                                 jnp.float32),
                **super().empty(layers, slots, dtype)}

    def _split(self, conv):
        at = self.shape
        return ssm.split_conv(conv, at.heads, at.head_dim, at.d_state,
                              at.groups)

    @partial(jax.jit, static_argnames=("self", "attn_impl"))
    def attend_chunk(self, cache, layer, slots, attn_impl, xbc, step, kernel,
                     bias, a_log, d):
        """One sequence's chunk: the convolution over its rows behind the
        slot's tail, the closed-form scan from the slot's state, and both
        written back. Rows that are bucket padding step by 0 and the new
        tail is cut behind the last REAL row, so padding moves nothing."""
        at = self.shape
        slot, valid, fresh = slots
        true_len = jnp.sum(valid)
        with jax.named_scope("ssm/conv"):
            conv, rows = ssm.causal_conv(
                xbc, self._held_tail(cache["conv"], layer, slot, fresh),
                kernel, bias)
            tail = self._new_tail(rows, true_len)
            x, bm, cm = self._split(conv.astype(xbc.dtype))
        # the slot's state in and out by a kernel wherever there is a TPU,
        # whatever ``attn_impl`` says: a slice of the pool invites XLA there
        # to re-lay the whole pool out (``ssm_update.slot_read``)
        impl = _resolve_impl(attn_impl)
        sliced = impl == "gather" and jax.default_backend() != "tpu"
        how = dict(interpret=impl == "kernel_interpret")
        with jax.named_scope("ssm/scan"):
            held = cache["ssm"][layer, slot] if sliced else \
                _ssm_update.slot_read(cache["ssm"], layer, slot, **how)
            s0 = _ssm_update.unpack_state(jnp.where(fresh, 0.0, held),
                                          at.pack)
            y, s = ssm.ssm_chunk_scan(x, jnp.where(valid[:, None], step, 0.0),
                                      a_log, bm, cm, s0, at.scan_block)
            y = y + d.astype(jnp.float32)[:, None] * x
            s = _ssm_update.pack_state(s, at.pack)
            states = cache["ssm"].at[layer, slot].set(s) if sliced else \
                _ssm_update.slot_write(cache["ssm"], layer, slot, s, **how)
            cache = {"ssm": states, "conv": self._tail_written(
                cache["conv"], layer, slot, tail)}
        return y.reshape(y.shape[0], -1).astype(xbc.dtype), cache

    @partial(jax.jit, static_argnames=("self", "attn_impl"))
    def attend_decode(self, cache, layer, slots, attn_impl, xbc, step, kernel,
                      bias, a_log, d):
        """One token a sequence: each row's tail shifted by its token, its
        state read and written once, both by a Pallas kernel that moves the
        rows' slots alone (or gather, update, scatter: ``attn_impl``)."""
        impl = _resolve_impl(attn_impl)
        how = {} if impl == "gather" else \
            dict(interpret=impl == "kernel_interpret")
        with jax.named_scope("ssm/conv"):
            conv, tails = self._decode_conv(cache["conv"], layer, slots,
                                            attn_impl, xbc, kernel, bias)
            x, bm, cm = self._split(conv)
        with jax.named_scope("ssm/update"):
            update = _ssm_update.ssm_update_reference if impl == "gather" \
                else _ssm_update.ssm_update
            y, pool = update(cache["ssm"], layer, slots, x, step, a_log, bm,
                             cm, **how)
            y = y + d.astype(jnp.float32)[:, None] * x
        return y.reshape(y.shape[0], -1).astype(xbc.dtype), \
            {"ssm": pool, "conv": tails}


class _LayerKindPages:
    """A cache held by layer kind, for a model whose layers differ in what
    they keep: ``{"full": [L_full, 2, H_kv, NB, bs, D], "window": [L_window,
    2, H_kv, NB_w, bs, D]}`` for one that mixes full and windowed layers,
    each pool with its own trash block, allocator and block tables (the step
    programs carry ``{"full": table, "window": table}`` likewise);
    ``{"full": ..., "state": {"ssm", "conv"}}`` for one some of whose layers
    keep a recurrent state and a convolution's tail (``_StateSlots``: a slot
    and no block table; the step programs' ``"state"`` entry IS the slot),
    ``{"full": ..., "state": {"conv"}}`` where the tail is all such a layer
    keeps (``_TailSlots``: no pool of states exists); a layer of kind ``"none"``
    keeps nothing and is in no pool. The kinds are data: a
    layer's is ``layer_kinds[l]`` where the spec names them and follows from
    ``layer_windows[l]`` where it does not, and a kind that no layer has
    has no pool. ``kind_pages`` states each paged kind's own KV
    heads and key and value widths where the kinds differ (left out, both
    hold the config's ``num_kv_heads`` x ``head_dim``); a kind whose keys
    and values differ in width keeps them in a pool of two arrays
    (``_SplitHeadPages``). A full layer's table names every block of
    the sequence. A windowed layer's names the blocks from the one that
    holds the first query's window on (``blocks_behind_window``, which host
    and program both derive from the position: nothing else is handed in)
    and is ``windowed_table_blocks`` long whatever the context, so its
    kernel's grid is the window's pages and the blocks behind can be given
    back. Inside, each paged kind is ``_HeadPages`` over its own pool with
    positions counted from its table's first block."""

    def __init__(self, layer_windows, kind_pages=None, layer_kinds=(),
                 state_slot=None):
        self.window = one_window(layer_windows)
        self.kinds = tuple(layer_kinds) or tuple(
            WINDOW if w else FULL for w in layer_windows)
        if set(self.kinds) - {FULL, WINDOW, STATE, NONE} \
                or (STATE in self.kinds) != (state_slot is not None) \
                or (WINDOW in self.kinds) != (self.window is not None):
            raise ValueError(f"layer kinds {sorted(set(self.kinds))} with "
                             f"window {self.window} and state slot "
                             f"{state_slot}: a kind is full, window (behind "
                             f"layer_windows' one window), state (which "
                             f"states what its slot holds) or none")
        # a layer's index in its kind's pool
        self.local = tuple(self.kinds[:i].count(k)
                           for i, k in enumerate(self.kinds))
        self.shapes = kind_pages

        def pages(kind, window):
            shape = kind_pages and kind_pages[kind]
            return _HeadPages(window) if not shape or shape.square \
                else _SplitHeadPages(window, shape)
        self.pages = {kind: pages(kind, window)
                      for kind, window in ((FULL, None), (WINDOW, self.window))
                      if kind in self.kinds}
        self.state = state_slot and (
            _StateSlots if state_slot.recurrent else _TailSlots)(state_slot)

    def shape(self, kind: str, cfg: KVCacheConfig) -> HeadPageShape:
        """What ``kind``'s page holds of a token a layer."""
        return self.shapes[kind] if self.shapes else cfg.page_shape

    def stored(self, kind: str, cfg: KVCacheConfig) -> HeadPageShape:
        """``shape`` with the widths of the rows the pool stores."""
        at = self.shape(kind, cfg)
        return at if at.square else dataclasses.replace(
            at, key_dim=latent_row_width(at.key_dim),
            value_dim=latent_row_width(at.value_dim))

    def new_pool(self, cfg: KVCacheConfig):
        if cfg.dtype == jnp.float8_e4m3fn:
            if self.state is not None:
                raise StateKindError(
                    "fp8 scaled pages are not supported over a cache some of "
                    "whose layers keep a recurrent state in a slot; use "
                    "kv_cache_dtype='model'")
            raise TwoPageKindsError(
                "fp8 scaled pages are not supported over a cache that keeps "
                "pages of two kinds (full layers' and windowed layers'); use "
                "kv_cache_dtype='model'")
        if WINDOW in self.pages and cfg.window_blocks < 2:
            raise ValueError(f"window_blocks {cfg.window_blocks}: the "
                             f"windowed layers' pool needs its trash block "
                             f"and at least one more")
        if self.state is not None and cfg.state_slots < 1:
            raise ValueError(f"state_slots {cfg.state_slots}: the state "
                             f"layers' pool needs a slot a sequence")
        blocks = {FULL: cfg.num_blocks, WINDOW: cfg.window_blocks}
        pool = {kind: pages.empty(
                    self.kinds.count(kind), self.shape(kind, cfg),
                    blocks[kind], cfg.block_size, cfg.dtype)
                for kind, pages in self.pages.items()}
        if self.state is not None:
            pool[STATE] = self.state.empty(self.kinds.count(STATE),
                                           cfg.state_slots, cfg.dtype)
        return pool, None

    def chunk_by_page(self, rows: int, block_size: int) -> bool:
        """What the paged kinds say (they share ``_Pages``' rule)."""
        return any(pages.chunk_by_page(rows, block_size)
                   for pages in self.pages.values())

    def row_operands(self, layer: int) -> int:
        """What the layer's own kind says (``_Pages.row_operands``)."""
        kind = self.kinds[layer]
        return (self.state if kind == STATE
                else self.pages[kind]).row_operands(layer)

    def _behind(self, first_query, block_size: int):
        """Tokens before a windowed table's first block."""
        return blocks_behind_window(first_query, self.window,
                                    block_size) * block_size

    def _slots(self, how: str, cache, tables, first, safe_pos, valid,
               block_size: int, state_slot):
        """A step's slots by kind: the paged kinds' (block, offset) a row
        (the windowed kind's counted from its table's first block), the
        state kind's ``state_slot``."""
        slots = {}
        if FULL in self.pages:
            slots[FULL] = getattr(self.pages[FULL], how)(
                cache[FULL], tables[FULL], *first, safe_pos, valid,
                block_size)
        if WINDOW in self.pages:
            at = first[0] if first else safe_pos
            behind = self._behind(at, block_size)
            slots[WINDOW] = getattr(self.pages[WINDOW], how)(
                cache[WINDOW], tables[WINDOW], *(f - behind for f in first),
                safe_pos - behind, valid, block_size)
            slots["behind"] = behind
        if self.state is not None:
            slots[STATE] = state_slot
        return slots

    def chunk_slots(self, cache, block_table, start, safe_pos, valid,
                    block_size: int):
        return self._slots(
            "chunk_slots", cache, block_table, (start,), safe_pos, valid,
            block_size, self.state and
            (block_table[STATE], valid, jnp.asarray(start) == 0))

    def decode_slots(self, cache, block_tables, safe_pos, valid,
                     block_size: int):
        # a row that is batch padding lands in the slot past the last
        return self._slots(
            "decode_slots", cache, block_tables, (), safe_pos, valid,
            block_size, self.state and jnp.where(
                valid, block_tables[STATE],
                cache[STATE]["conv"].shape[1] - 1))

    def _attend(self, how, cache, layer, slots, tables, positions, *args,
                **kwargs):
        kind = self.kinds[layer]
        if kind == STATE:
            out, pool = getattr(self.state, how)(
                cache[kind], self.local[layer], slots[kind], *args, **kwargs)
            return out, {**cache, kind: pool}
        attend = getattr(self.pages[kind], how)
        if kind == WINDOW:
            with jax.named_scope("attn/window"):
                out, pool = attend(
                    cache[kind], self.local[layer], slots[kind],
                    tables[kind], positions - slots["behind"], *args,
                    **kwargs)
        else:
            with jax.named_scope("attn/full"):
                out, pool = attend(cache[kind], self.local[layer],
                                   slots[kind], tables[kind], positions,
                                   *args, **kwargs)
        return out, {**cache, kind: pool}

    def attend_chunk(self, cache, layer, slots, block_table, start, *args,
                     **how):
        return self._attend("attend_chunk", cache, layer, slots, block_table,
                            start, *args, **how)

    def attend_decode(self, cache, layer, slots, block_tables, positions,
                      *args, **how):
        return self._attend("attend_decode", cache, layer, slots,
                            block_tables, positions, *args, **how)


def _kind_for(latent_dim: int, scaled: bool) -> type:
    if latent_dim:
        return _LatentPages
    return _ScaledHeadPages if scaled else _HeadPages


def page_kind(spec, cache):
    """The kind of the pool a step program was handed, from the policy's
    ``KVCacheSpec`` and the pool's structure: ``(pages, scales)``, pages
    alone, or a pool a layer kind."""
    if isinstance(cache, dict):
        return _LayerKindPages(spec.layer_windows, spec.kind_pages,
                               spec.layer_kinds or (), spec.state_slot)
    return _kind_for(spec.latent_dim, isinstance(cache, tuple))(spec.window)
