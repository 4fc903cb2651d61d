"""The seam between the served loop and what a KV page is
(``inference/v2/kv_cache.py``): each page kind against the allocator, the
slots its writes land in, and the host's gather and scatter of its blocks.
What the kinds compute in a step program is pinned elsewhere
(tests/test_inference_v2.py, test_joyai_flash.py, test_kv_donation.py,
test_step_program_tpu_compile.py); here only the layout contract a further
kind has to meet, read from outside through the pool's documented shape.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.kv_cache import (BlockedKVCache,
                                                 KVCacheConfig, page_kind)
from deepspeed_tpu.inference.v2.modules import KVCacheSpec

L, H, D, NB, BS = 2, 2, 8, 12, 4
RANK, D_R, D_N, D_V = 16, 4, 8, 8          # the latent row is RANK + D_R wide
TRASH = NB - 1
FP8 = jnp.float8_e4m3fn

#: name -> (page dtype, latent_dim, the pool's leaves as documented, the
#: block axis of each, tolerance of a value read back through the pages)
KINDS = {
    "heads": (jnp.float32, 0, [(L, 2, H, NB, BS, D)], [3], 0.0),
    "heads_fp8": (FP8, 0, [(L, 2, H, NB, BS, D), (L, 2, H, NB)], [3, 3],
                  0.07),
    "latent": (jnp.float32, RANK + D_R, [(L, NB, BS, 128)], [1], 0.0),
}


def other_blocks(name, pool, named):
    """Each leaf of the pool without the ``named`` blocks, as float32."""
    rest = np.delete(np.arange(NB), named)
    return [np.take(np.asarray(x, np.float32), rest, axis)
            for x, axis in zip(jax.tree.leaves(pool), KINDS[name][3])]


def make(name):
    dtype, latent_dim, _, _, _ = KINDS[name]
    kv = BlockedKVCache(KVCacheConfig(
        num_layers=L, num_kv_heads=H, head_dim=D, block_size=BS,
        num_blocks=NB, dtype=dtype, latent_dim=latent_dim))
    spec = KVCacheSpec(L, H, D, 64, jnp.float32, None, latent_dim=latent_dim)
    return kv, page_kind(spec, kv.pool)


def rows_of(name, pool, layer):
    """One layer's pages as real values ``[NB, BS, features]``, a token's
    K then V over the heads (or its latent row) flattened."""
    if name == "latent":
        return np.asarray(pool[layer], np.float32)
    pages = pool if name == "heads" else pool[0]
    real = np.asarray(pages[layer], np.float32)            # [2, H, NB, BS, D]
    if name == "heads_fp8":
        real = real * np.asarray(pool[1][layer])[..., None, None]
    return real.transpose(2, 3, 0, 1, 4).reshape(NB, BS, 2 * H * D)


def computed(name, n, seed):
    """What a block hands ``attend`` for ``n`` rows, and each row as
    ``rows_of`` should read it back."""
    key = jax.random.split(jax.random.PRNGKey(seed), 4)
    if name == "latent":
        row = jax.random.normal(key[0], (n, RANK + D_R))
        args = (jax.random.normal(key[1], (n, H, D_N)),
                jax.random.normal(key[2], (n, H, D_R)), row,
                jax.random.normal(key[3], (RANK, H, D_N + D_V)), 0.3)
        return args, np.pad(np.asarray(row), ((0, 0), (0, 128 - RANK - D_R)))
    k = jax.random.normal(key[0], (n, H, D))
    v = jax.random.normal(key[1], (n, H, D))
    if name == "heads_fp8":
        # one outlier beyond the page's committed range, in a row that is no
        # padding: its page's scale grows and the page is requantized under
        # it (the trash page's scale never grows)
        k = k.at[0, 0, 0].set(900.0)
    args = (jax.random.normal(key[2], (n, 2 * H, D)), k, v)
    return args, np.stack([np.asarray(k), np.asarray(v)], 1).reshape(n, -1)


@pytest.mark.parametrize("name", sorted(KINDS))
def test_pool_shape_block_axis_and_trash_block_agree_with_the_allocator(name):
    dtype, _, shapes, axes, _ = KINDS[name]
    kv, kind = make(name)
    assert type(kind) is type(kv.kind)
    leaves = jax.tree.leaves(kv.pool)
    assert [x.shape for x in leaves] == shapes
    assert leaves[0].dtype == dtype
    assert (kv.scales is None) == (len(shapes) == 1)
    assert kind.block_axis == axes[0]
    # the trash block is the pool's last and is never handed out
    assert kind.trash_block(kv.pool) == TRASH == kv.allocator.total_blocks
    assert TRASH not in kv.reserve(kv.free_blocks) and kv.free_blocks == 0


@pytest.mark.parametrize("name", sorted(KINDS))
def test_a_write_lands_in_the_named_slots_and_nowhere_else(name):
    tol = KINDS[name][4]
    kv, kind = make(name)
    # a pool of a value that is no row's, so that a stray write shows; fp8
    # pages hold it under a scale of 0.5
    pool = jax.tree.map(lambda x: jnp.full_like(x, 0.5), kv.pool)
    old = 0.25 if name == "heads_fp8" else 0.5

    def check(pool, before, layer, slots, want, trash_said=True):
        """``slots``: (block, offset) of each row of ``want``; every other
        slot of ``layer`` reads ``old``, every other layer and every page
        not named is bit for bit what it was. Where a chunk went in a page
        at a time nobody says what the trash block holds."""
        expect = np.full((NB, BS, want.shape[-1]), old, np.float32)
        for (b, o), row in zip(slots, want):
            expect[b, o] = row
        rows = rows_of(name, pool, layer)
        if not trash_said:
            rows, expect = rows[:TRASH], expect[:TRASH]
        np.testing.assert_allclose(rows, expect, rtol=tol, atol=tol * 0.25)
        named = sorted({b for b, _ in slots})
        for got, was in zip(other_blocks(name, pool, named),
                            other_blocks(name, before, named)):
            np.testing.assert_array_equal(got, was)
        for got, was in zip(other_blocks(name, pool, []),
                            other_blocks(name, before, [])):
            np.testing.assert_array_equal(np.delete(got, layer, 0),
                                          np.delete(was, layer, 0))

    # a chunk of 6 rows, 5 of them real, from position 6: slots 2-3 of the
    # table's second block, 0-2 of its third; the padding row to the trash
    # where the rows go in one by one (fp8 pages), nowhere where they go in
    # a page at a time (the third block's last row keeps what it held)
    table = jnp.asarray([7, 2, 9, TRASH], jnp.int32)
    start, rows, real = 6, 6, 5
    safe_pos = start + jnp.arange(rows)
    valid = jnp.arange(rows) < real
    slots = kind.chunk_slots(pool, table, start, safe_pos, valid, BS)
    args, want = computed(name, rows, seed=0)
    before = jax.tree.map(jnp.copy, pool)
    out, pool = kind.attend_chunk(pool, 0, slots, table, start, "gather",
                                  *args)
    assert out.shape[0] == rows and np.isfinite(np.asarray(out)[:real]).all()
    by_page = kind.chunk_by_page(rows, BS)
    assert by_page == (name != "heads_fp8")
    check(pool, before, 0,
          [(2, 2), (2, 3), (9, 0), (9, 1), (9, 2)]
          + ([] if by_page else [(TRASH, 3)]), want, trash_said=not by_page)

    # a decode batch of 3, the middle one batch padding: one token each at
    # positions 5 and 8 of their own tables
    tables = jnp.asarray([[3, 4, 5], [TRASH] * 3, [6, 1, 8]], jnp.int32)
    positions = jnp.asarray([5, 0, 8], jnp.int32)
    valid = jnp.asarray([True, False, True])
    slots = kind.decode_slots(pool, tables, positions, valid, BS)
    args, want = computed(name, 3, seed=1)
    before = jax.tree.map(jnp.copy, pool)
    out, pool = kind.attend_decode(pool, 1, slots, tables, positions,
                                   "gather", *args)
    assert out.shape[0] == 3 and np.isfinite(np.asarray(out)[[0, 2]]).all()
    check(pool, before, 1, [(4, 1), (TRASH, 0), (8, 0)], want)


@pytest.mark.parametrize("name", sorted(KINDS))
def test_gather_then_scatter_into_other_blocks_is_bit_identical(name):
    src, _ = make(name)
    dst, _ = make(name)
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    # pages of noise, and scales (the second leaf, where there is one) of 0.5-1.5
    filled = [jax.random.uniform(k, x.shape) + 0.5 if i else
              jax.random.normal(k, x.shape).astype(x.dtype)
              for i, (k, x) in enumerate(zip(keys, jax.tree.leaves(src.pool)))]
    src.pool = filled[0] if len(filled) == 1 else tuple(filled)
    empty = jax.tree.map(jnp.copy, dst.pool)

    data, scales = src.gather_blocks([2, 5, 7])
    assert src.gathered_blocks(data) == 3
    assert (scales is None) == (src.scales is None)
    dst.scatter_blocks([9, 1, 4], data, scales)
    back, back_scales = dst.gather_blocks([9, 1, 4])
    np.testing.assert_array_equal(back.view(np.uint8), data.view(np.uint8))
    if scales is not None:
        np.testing.assert_array_equal(back_scales, scales)
    # and nothing else of the second pool moved
    for got, was in zip(other_blocks(name, dst.pool, [9, 1, 4]),
                        other_blocks(name, empty, [9, 1, 4])):
        np.testing.assert_array_equal(got, was)


# --- pages by layer kind ------------------------------------------------------

def test_windowed_kind_gives_blocks_back_and_refuses_what_moves_one_pools_ids(
        tmp_path):
    """A cache over full and windowed layers (window 6 over blocks of 4): a
    pool a kind with its own trash block and allocator; a windowed layer's
    table starts at the block that holds the first query's window and is as
    long as window + rows need, whatever the context; the blocks behind are
    given back as the sequence advances, and a release makes both allocators
    whole. Prefix reuse, the host offload tier and the prefix handoff move
    block ids of one pool and refuse this cache by name."""
    from deepspeed_tpu.inference.v2.kv_cache import (TwoPageKindsError,
                                                     blocks_behind_window,
                                                     windowed_table_blocks)
    from deepspeed_tpu.inference.v2.ragged_manager import SequenceDescriptor
    windows = (None, 6, 6)
    kv = BlockedKVCache(KVCacheConfig(
        num_layers=3, num_kv_heads=H, head_dim=D, block_size=BS,
        num_blocks=NB, dtype=jnp.float32, layer_windows=windows,
        window_blocks=7))
    spec = KVCacheSpec(3, H, D, 64, jnp.float32, None, layer_windows=windows)
    kind = page_kind(spec, kv.pool)
    assert type(kind) is type(kv.kind) and kv.two_kinds
    assert {k: v.shape for k, v in kv.pool.items()} == {
        "full": (1, 2, H, NB, BS, D), "window": (2, 2, H, 7, BS, D)}
    assert (kv.allocator.total_blocks, kv.window_allocator.total_blocks) \
        == (NB - 1, 6)
    assert kv.usable_blocks == kv.free_blocks == NB - 1 + 6
    # a sequence between steps holds the window's blocks: 6 tokens end in at
    # most three blocks of four
    assert kv.window_steady_blocks == windowed_table_blocks(1, 6, BS) == 3
    assert kv.blocks_needed(40) == 10 + 3 and kv.blocks_needed(5) == 2 + 2
    assert [int(blocks_behind_window(q, 6, BS)) for q in (0, 5, 8, 9, 13)] \
        == [0, 0, 0, 1, 2]

    seq = SequenceDescriptor(uid=1, prompt_tokens=np.zeros(30, np.int32))
    seq.blocks = kv.reserve(3)
    kv.ensure_window(seq, 10)                     # a chunk of 10 from 0
    assert len(seq.window_blocks) == 3 and seq.window_base == 0
    table = kv.window_table(seq, 0, 10)
    # five entries for ten rows behind a window of six; 6 is the trash block
    assert table.tolist() == seq.window_blocks + [6, 6]
    seq.seen_tokens = 10
    assert kv.give_back_behind_window(seq) == 1   # block 0: tokens 0-3
    assert (seq.window_base, len(seq.window_blocks)) == (1, 2)
    assert kv.window_blocks_given_back == 1
    # one token at position 10: its window is tokens 5-10, blocks 1 and 2
    assert kv.window_table(seq, 10, 1).tolist() == seq.window_blocks + [6]
    slots = kind.decode_slots(kv.pool, {
        "full": jnp.asarray([seq.blocks + [TRASH]], jnp.int32),
        "window": jnp.asarray([kv.window_table(seq, 10, 1)])},
        jnp.asarray([10]), jnp.asarray([True]), BS)
    assert int(slots["behind"][0]) == 4
    assert (int(slots["full"][0][0]), int(slots["full"][1][0])) \
        == (seq.blocks[2], 2)
    assert (int(slots["window"][0][0]), int(slots["window"][1][0])) \
        == (seq.window_blocks[1], 2)
    held = kv.pages_held()
    assert (held["full_blocks"], held["window_blocks"]) == (3, 2)
    assert held["held_bytes"] == 3 * kv.data["full"].nbytes // NB \
        + 2 * kv.data["window"].nbytes // 7
    kv.release(seq.blocks)
    kv.release_window(seq)
    assert kv.free_blocks == kv.usable_blocks and seq.window_blocks == []

    for moved in (lambda: kv.gather_blocks([0]),
                  lambda: kv.require_one_kind("the prefix cache")):
        with pytest.raises(TwoPageKindsError, match="two kinds"):
            moved()
    with pytest.raises(TwoPageKindsError, match="fp8"):
        BlockedKVCache(KVCacheConfig(
            num_layers=3, num_kv_heads=H, head_dim=D, block_size=BS,
            num_blocks=NB, dtype=FP8, layer_windows=windows, window_blocks=7))
    # one window for all layers is one kind, as before
    one = BlockedKVCache(KVCacheConfig(
        num_layers=2, num_kv_heads=H, head_dim=D, block_size=BS,
        num_blocks=NB, dtype=jnp.float32, layer_windows=(6, 6)))
    assert not one.two_kinds and one.window_allocator is None
    assert one.blocks_needed(40) == 10 and one.usable_blocks == NB - 1


@pytest.mark.parametrize("shapes", [
    pytest.param({"full": (1, 8, 8), "window": (2, 8, 8)},
                 id="unequal-heads-square"),
    pytest.param({"full": (1, 12, 8), "window": (2, 12, 8)},
                 id="unequal-heads-keys-wider-than-values"),
    pytest.param({"full": (2, 12, 8), "window": (2, 8, 8)},
                 id="one-kind-split-one-square")])
def test_a_kind_states_its_own_heads_and_widths(shapes):
    """Pages by layer kind where each kind has its own KV head count and its
    keys and values their own widths: a square kind keeps K and V planes of
    one array at its own head count, a kind whose key is wider than its value
    a K pool and a V pool, each row padded to whole lane tiles; a write lands
    in the named slot of its own kind's pool at its own widths, the other
    kind's pool does not move, and every byte count is the kind's own."""
    from deepspeed_tpu.inference.v2.kv_cache import (HeadPageShape,
                                                     latent_row_width)
    windows = (None, 6, 6)
    kinds = {k: HeadPageShape(*v) for k, v in shapes.items()}
    kv = BlockedKVCache(KVCacheConfig(
        num_layers=3, num_kv_heads=1, head_dim=12, block_size=BS,
        num_blocks=NB, dtype=jnp.float32, layer_windows=windows,
        window_blocks=7, kind_pages=kinds))
    spec = KVCacheSpec(3, 1, 12, 64, jnp.float32, None,
                       layer_windows=windows, kind_pages=kinds)
    kind = page_kind(spec, kv.pool)
    layers, blocks = {"full": 1, "window": 2}, {"full": NB, "window": 7}
    for name, at in kinds.items():
        pool = kv.pool[name]
        if at.square:
            assert pool.shape == (layers[name], 2, at.num_kv_heads,
                                  blocks[name], BS, at.key_dim)
            per_token = 2 * at.num_kv_heads * at.key_dim
        else:
            assert {k: v.shape for k, v in pool.items()} == {
                "k": (layers[name], at.num_kv_heads, blocks[name], BS, 128),
                "v": (layers[name], at.num_kv_heads, blocks[name], BS, 128)}
            per_token = 2 * at.num_kv_heads * latent_row_width(at.key_dim)
        assert kv._block_bytes[name] == layers[name] * BS * per_token * 4
        assert kind.pages[name].trash_block(pool) == blocks[name] - 1
        assert kv.kind.stored(name, kv.cfg).key_dim == (
            at.key_dim if at.square else 128)
    # a decode batch of two in the second windowed layer (layer 2, its
    # kind's layer 1): one token each at positions 5 and 2
    at = kinds["window"]
    tables = {"full": jnp.asarray([[3, 4], [6, 1]], jnp.int32),
              "window": jnp.asarray([[2, 5, 6], [4, 6, 6]], jnp.int32)}
    positions = jnp.asarray([5, 2], jnp.int32)
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(key[0], (2, 2 * at.num_kv_heads, at.key_dim))
    k = jax.random.normal(key[1], (2, at.num_kv_heads, at.key_dim))
    v = jax.random.normal(key[2], (2, at.num_kv_heads, at.value_dim))
    slots = kind.decode_slots(kv.pool, tables, positions,
                              jnp.asarray([True, True]), BS)
    before = jax.tree.map(jnp.copy, kv.pool)
    out, pool = kind.attend_decode(kv.pool, 2, slots, tables, positions,
                                   "gather", q, k, v)
    assert out.shape == (2, 2 * at.num_kv_heads, at.value_dim)
    for a, b in zip(jax.tree.leaves(pool["full"]),
                    jax.tree.leaves(before["full"])):
        np.testing.assert_array_equal(a, b)
    win = pool["window"]
    k_rows, v_rows = (win[1, 0], win[1, 1]) if at.square else \
        (win["k"][1], win["v"][1])                   # [H, NB_w, BS, W]
    for row, (block, offset) in enumerate([(5, 1), (4, 2)]):
        np.testing.assert_array_equal(
            k_rows[:, block, offset, :at.key_dim], k[row])
        np.testing.assert_array_equal(
            v_rows[:, block, offset, :at.value_dim], v[row])
        assert not np.asarray(k_rows[:, block, offset, at.key_dim:]).any()
    assert int(sum(jnp.count_nonzero(x) for x in jax.tree.leaves(win))) \
        == 2 * at.num_kv_heads * (at.key_dim + at.value_dim)
    # the bytes sequences hold, by kind, from a block of the kind's own pool
    kv.reserve(3)
    kv.window_allocator.allocate(2)
    held = kv.pages_held()
    assert held["full_bytes"] == 3 * kv._block_bytes["full"]
    assert held["window_bytes"] == 2 * kv._block_bytes["window"]
    assert held["held_bytes"] == held["full_bytes"] + held["window_bytes"]
    # a decode call's slot copies follow the kind's own heads and widths
    copies = kv.decode_slot_copies([30, 9], 4, 8, kv.kind.window)
    assert copies["slot_copies"] > 0 and copies["slot_copies_windowed"] > 0


def test_more_than_one_window_size_is_refused_by_name():
    from deepspeed_tpu.inference.v2.kv_cache import (mixes_layer_kinds,
                                                     one_window)
    assert one_window((None, 6, 6)) == 6 and one_window((None, None)) is None
    assert one_window(()) is None and not mixes_layer_kinds((6, 6))
    for ask in (one_window, mixes_layer_kinds):
        with pytest.raises(ValueError, match="more than one window size"):
            ask((None, 6, 8))
    with pytest.raises(ValueError, match="more than one window size"):
        BlockedKVCache(KVCacheConfig(
            num_layers=3, num_kv_heads=H, head_dim=D, block_size=BS,
            num_blocks=NB, dtype=jnp.float32, layer_windows=(None, 6, 8),
            window_blocks=7))


# --- a kind that is a slot and no block table ----------------------------------

@pytest.mark.parametrize("impl", ["gather", "kernel_interpret"])
def test_state_kind_is_a_slot_a_sequence_beside_the_full_layers_pages(impl):
    """A cache over one attention layer and two layers that keep a recurrent
    state: the kinds are data (``layer_kinds``), the full layers' pool is
    what it is in any cache by layer kind and has the one allocator, the
    state kind's pool is ``{"ssm", "conv"}`` with a slot a sequence and one
    more for padding; a step's ``"state"`` table entry IS the slot; a chunk
    that starts at 0 reads nothing of the slot's last holder, a padding row
    of a decode batch lands in the slot past the last; what takes a cache to
    be its pages refuses by name."""
    from deepspeed_tpu.inference.v2.kv_cache import (StateKindError,
                                                     StateSlotShape)
    from deepspeed_tpu.ops.pallas.ssm_update import unpack_state
    kinds = ("state", "full", "state")
    heads, p, n, taps = 4, 8, 16, 4
    channels = heads * p + 2 * n
    at = StateSlotShape(heads, p, n, taps, channels, scan_block=4)
    slots_n = 3
    kv = BlockedKVCache(KVCacheConfig(
        num_layers=3, num_kv_heads=H, head_dim=D, block_size=BS,
        num_blocks=NB, dtype=jnp.float32, layer_kinds=kinds, state_slot=at,
        state_slots=slots_n))
    spec = KVCacheSpec(3, H, D, 64, jnp.float32, None, layer_kinds=kinds,
                       state_slot=at)
    kind = page_kind(spec, kv.pool)
    assert type(kind) is type(kv.kind)
    assert kv.by_layer_kind and kv.has_state and not kv.two_kinds
    assert kv.window_allocator is None and kind.window is None
    assert (at.pack, at.stored, at.tail) == (4, (1, n, 4 * p), 3 * channels)
    # a toy tail fills no lanes: a row a tap (the published 3 x 4,352 are
    # 102 rows of 128 lanes and two of zeros, a whole tile)
    assert at.tail_stored == (3, channels)
    assert StateSlotShape(64, 64, 128, 4, 4352).tail_stored == (104, 128)
    assert jax.tree.map(lambda x: x.shape, kv.pool) == {
        "full": (1, 2, H, NB, BS, D),
        "state": {"ssm": (2, slots_n + 1, 1, n, 4 * p),
                  "conv": (2, slots_n + 1, 3, channels)}}
    assert kv.pool["state"]["ssm"].dtype == jnp.float32
    assert kind.local == (0, 0, 1) and kv.usable_blocks == NB - 1
    assert kv.slot_bytes == 2 * (heads * p * n * 4 + 3 * channels * 4)
    assert kv.blocks_needed(40) == 10 and kv.pages_held()["held_bytes"] == 0

    key = jax.random.split(jax.random.PRNGKey(0), 8)
    w = {"conv_kernel": jax.random.normal(key[0], (channels, taps)) * 0.3,
         "conv_bias": jax.random.normal(key[1], (channels,)) * 0.1,
         "dt_bias": jnp.full((heads,), -1.0),
         "a_log": jnp.log(jnp.linspace(1.0, 8.0, heads)),
         "d": jnp.ones((heads,))}
    # every slot dirty, as its last holder left it
    pool = {**kv.pool, "state": jax.tree.map(
        lambda x: jnp.full_like(x, 0.5), kv.pool["state"])}
    # a chunk of 6 rows, 5 of them real, from position 0, into slot 1
    rows, real = 6, 5
    table = {"full": jnp.asarray([7, 2, TRASH], jnp.int32),
             "state": jnp.int32(1)}
    valid = jnp.arange(rows) < real
    slots = kind.chunk_slots(pool, table, 0, jnp.arange(rows), valid, BS)
    xbc = jax.random.normal(key[2], (rows, channels))
    dt = jax.random.normal(key[3], (rows, heads))
    before = jax.tree.map(jnp.copy, pool)
    # the policy's part: the step after its softplus, the parameters as
    # plain arrays
    step_of = lambda dt: jax.nn.softplus(dt + w["dt_bias"])
    plain = (w["conv_kernel"], w["conv_bias"], w["a_log"], w["d"])
    out, pool = kind.attend_chunk(pool, 2, slots, table, 0, impl, xbc,
                                  step_of(dt), *plain)
    assert out.shape == (rows, heads * p)
    # layer 2 is the state kind's second; slots 0, 2, 3, the other layer and
    # the pages are what they were
    for name in ("ssm", "conv"):
        got, was = pool["state"][name], before["state"][name]
        np.testing.assert_array_equal(got[0], was[0])
        np.testing.assert_array_equal(got[1, [0, 2, 3]], was[1, [0, 2, 3]])
    np.testing.assert_array_equal(pool["full"], before["full"])
    # ... and slot 1 holds what five tokens from a ZERO state leave: the
    # recurrence token by token, and the last three real rows
    from deepspeed_tpu.ops import ssm
    conv, joined = ssm.causal_conv(xbc[:real], jnp.zeros((3, channels)),
                                   w["conv_kernel"], w["conv_bias"])
    x = conv[:, :heads * p].reshape(real, heads, p)
    step = jax.nn.softplus(dt[:real] + w["dt_bias"])
    # one group of B and C: [rows, 1, n]
    y, s = ssm.ssm_token_scan(x, step, w["a_log"],
                              conv[:, None, heads * p:-n], conv[:, None, -n:],
                              jnp.zeros((heads, p, n)))
    np.testing.assert_allclose(
        unpack_state(pool["state"]["ssm"][1, 1], at.pack), s, atol=1e-5)
    np.testing.assert_array_equal(
        pool["state"]["conv"][1, 1].reshape(3, channels), xbc[real - 3:real])
    np.testing.assert_allclose(
        out[:real], (y + x).reshape(real, -1), atol=1e-5)

    # a decode batch of 3, the middle one batch padding, slots 2 and 0
    tables = {"full": jnp.asarray([[3, 4], [TRASH] * 2, [6, 1]], jnp.int32),
              "state": jnp.asarray([2, slots_n, 0], jnp.int32)}
    valid = jnp.asarray([True, False, True])
    slots = kind.decode_slots(pool, tables, jnp.asarray([5, 0, 8]), valid, BS)
    assert slots["state"].tolist() == [2, slots_n, 0]
    # (whatever a padding row's table names, it lands in the last slot)
    assert kind.decode_slots(
        pool, {**tables, "state": jnp.asarray([2, 1, 0], jnp.int32)},
        jnp.asarray([5, 0, 8]), valid, BS)["state"].tolist() == [2, slots_n, 0]
    before = jax.tree.map(jnp.copy, pool)
    out, pool = kind.attend_decode(
        pool, 0, slots, tables, jnp.asarray([5, 0, 8]), impl,
        jax.random.normal(key[4], (3, channels)),
        step_of(jax.random.normal(key[5], (3, heads))), *plain)
    assert out.shape == (3, heads * p)
    for name in ("ssm", "conv"):
        got, was = pool["state"][name], before["state"][name]
        np.testing.assert_array_equal(got[1], was[1])
        np.testing.assert_array_equal(got[0, 1], was[0, 1])
        for slot in (0, 2, slots_n):
            assert not np.array_equal(got[0, slot], was[0, slot])
    # a tail moves on by one row
    np.testing.assert_array_equal(
        pool["state"]["conv"][0, 2].reshape(3, channels)[:2],
        before["state"]["conv"][0, 2].reshape(3, channels)[1:])

    for moved in (lambda: kv.gather_blocks([0]),
                  lambda: kv.require_one_kind("the prefix cache")):
        with pytest.raises(StateKindError, match="recurrent state"):
            moved()
    with pytest.raises(StateKindError, match="fp8"):
        BlockedKVCache(KVCacheConfig(
            num_layers=3, num_kv_heads=H, head_dim=D, block_size=BS,
            num_blocks=NB, dtype=FP8, layer_kinds=kinds, state_slot=at,
            state_slots=slots_n))
    for bad in (dict(layer_kinds=kinds), dict(layer_kinds=("full", "linear")),
                dict(layer_kinds=("full", "window"), state_slot=None)):
        with pytest.raises(ValueError, match="layer kinds"):
            BlockedKVCache(KVCacheConfig(
                num_layers=3, num_kv_heads=H, head_dim=D, block_size=BS,
                num_blocks=NB, dtype=jnp.float32, **bad))
    with pytest.raises(ValueError, match="state_slots"):
        BlockedKVCache(KVCacheConfig(
            num_layers=3, num_kv_heads=H, head_dim=D, block_size=BS,
            num_blocks=NB, dtype=jnp.float32, layer_kinds=kinds,
            state_slot=at))


# --- a chunk's rows by page ----------------------------------------------------
# A chunk of 128 rows over blocks of 64 from any offset in its first block:
# the pool after the page write against the pool after the row scatter.

PBS, BUCKET, PNB, WIN = 64, 128, 9, 100
LENGTHS = {"bucket": BUCKET, "bucket-1": BUCKET - 1, "one": 1,
           "under-a-block": 40}


def _paged(fmt):
    """(the cache, its kind, the layer written, that layer's pool name or
    None, what the layer's block hands ``attend`` for a chunk)."""
    from deepspeed_tpu.inference.v2.kv_cache import HeadPageShape
    key = jax.random.split(jax.random.PRNGKey(5), 5)
    how = dict(num_layers=2, num_kv_heads=H, head_dim=D, block_size=PBS,
               num_blocks=PNB, dtype=jnp.bfloat16)
    spec = dict(latent_dim=0)
    layer, name = 1, None
    if fmt == "latent":
        how["latent_dim"] = spec["latent_dim"] = RANK + D_R
    elif fmt in ("split", "layer-kinds"):
        layer, name = 2, "window"
        how.update(num_layers=3, layer_windows=(None, WIN, WIN),
                   window_blocks=PNB)
        spec["layer_windows"] = how["layer_windows"]
        if fmt == "split":
            how["kind_pages"] = spec["kind_pages"] = {
                "full": HeadPageShape(1, 12, 8),
                "window": HeadPageShape(H, 12, 8)}
    kv = BlockedKVCache(KVCacheConfig(**how))
    kind = page_kind(KVCacheSpec(how["num_layers"], H, D, 4096, jnp.bfloat16,
                                 None, **spec), kv.pool)
    if fmt == "latent":
        args = (jax.random.normal(key[0], (BUCKET, H, D_N)),
                jax.random.normal(key[1], (BUCKET, H, D_R)),
                jax.random.normal(key[2], (BUCKET, RANK + D_R)),
                jax.random.normal(key[3], (RANK, H, D_N + D_V)), 0.3)
    else:
        dk, dv = (12, 8) if fmt == "split" else (D, D)
        args = (jax.random.normal(key[0], (BUCKET, 2 * H, dk)),
                jax.random.normal(key[1], (BUCKET, H, dk)),
                jax.random.normal(key[2], (BUCKET, H, dv)))
    return kv, kind, layer, name, args


def _but_the_trash(pool, kv):
    """Every leaf of ``pool`` without its trash block, as bits."""
    kind = kv.kind
    out = []
    for name, leaves in (pool.items() if kv.by_layer_kind
                         else [(None, pool)]):
        pages = kind.pages[name] if name else kind
        for x in jax.tree.leaves(leaves):
            bits = np.asarray(x).view(np.uint16)
            out.append(np.delete(bits, bits.shape[pages.block_axis] - 1,
                                 pages.block_axis))
    return out


@pytest.mark.parametrize("ends_its_table", [False, True],
                         ids=["table-goes-on", "ends-in-the-last-block"])
@pytest.mark.parametrize("length", sorted(LENGTHS))
@pytest.mark.parametrize("offset", [0, 1, 37, 63])
@pytest.mark.parametrize("fmt", ["heads", "split", "latent", "layer-kinds"])
def test_a_chunk_by_page_leaves_what_the_row_scatter_leaves(
        monkeypatch, fmt, offset, length, ends_its_table):
    """``_HeadPages``, ``_SplitHeadPages`` (a windowed kind of a pool by
    layer kind whose keys are wider than its values), ``_LatentPages`` and a
    square windowed kind behind its window (positions counted from its
    table's first block): a chunk padded to 128 rows from ``offset`` in its
    first block, ``length`` of them real, written a page at a time
    (``attend_chunk``, the ``jax.numpy`` form; then the kernel, interpreted)
    leaves every block but the trash block bit for bit as the row scatter
    leaves it: the first page's rows before the chunk and the last page's
    behind it keep what they held, a page that is all padding goes to the
    trash block, and a table that ends in the chunk's last block is not read
    past its end."""
    from deepspeed_tpu.inference.v2 import kv_cache
    kv, kind, layer, name, args = _paged(fmt)
    real = LENGTHS[length]
    start = 2 * PBS + offset
    # noise everywhere, so that a row that should keep what it held shows
    fill = jax.random.split(jax.random.PRNGKey(11), 8)
    pool = jax.tree.unflatten(
        jax.tree.structure(kv.pool),
        [jax.random.normal(k, x.shape).astype(x.dtype)
         for k, x in zip(fill, jax.tree.leaves(kv.pool))])
    ids = np.random.default_rng(offset).permutation(PNB - 1)

    def table_of(first, entries):
        """``entries`` distinct blocks, cut behind the chunk's last where the
        table ends there."""
        if ends_its_table:
            entries = (first + real - 1) // PBS + 1
        return jnp.asarray(ids[:entries], jnp.int32)
    if name:
        behind = int(kv_cache.blocks_behind_window(start, WIN, PBS)) * PBS
        table = {"full": table_of(start, 6),
                 "window": table_of(start - behind,
                                    kv_cache.windowed_table_blocks(
                                        BUCKET, WIN, PBS))}
    else:
        table = table_of(start, 6)
    safe_pos = start + jnp.arange(BUCKET)
    valid = jnp.arange(BUCKET) < real

    def written(by_page, impl="gather"):
        monkeypatch.setattr(kv_cache._Pages, "by_page", by_page)
        slots = kind.chunk_slots(pool, table, start, safe_pos, valid, PBS)
        mine = slots[name] if name else slots
        assert isinstance(mine, kv_cache.PageSlots) == by_page
        if impl == "gather":
            return kind.attend_chunk(jax.tree.map(jnp.copy, pool), layer,
                                     slots, table, start, impl, *args)[1]
        # the kernel's write alone (the attention over the pages has its own
        # interpreted cases elsewhere)
        pages = kind.pages[name] if name else kind
        at = kind.local[layer] if name else layer
        new = pages._write_chunk(pool[name] if name else pool, at,
                                 *args[-2:] if fmt != "latent" else args[2:3],
                                 mine, impl)
        return {**pool, name: new} if name else new

    by_row = _but_the_trash(written(False), kv)
    for impl in ("gather", "kernel_interpret"):
        for got, want in zip(_but_the_trash(written(True, impl), kv), by_row):
            np.testing.assert_array_equal(got, want)
    # and the write moved something: the chunk's first row is in its slot
    was = _but_the_trash(pool, kv)
    assert any((a != b).any() for a, b in zip(by_row, was))


@pytest.mark.parametrize("program,dtype,rows,by_page", [
    ("prefill_chunk_g", jnp.float32, 32, True),
    ("prefill_chunk_g", jnp.float32, 8, False),      # a bucket under a block
    ("verify_chunk_g", jnp.float32, 5, False),       # speculation's k + 1
    ("prefill_chunk_g", FP8, 32, False),             # scaled pages
    ("verify_chunk_g", FP8, 32, False),
    ("decode_step_g", jnp.float32, 4, False)],
    ids=["chunk", "chunk-under-a-block", "verify-5-rows", "chunk-fp8",
         "verify-fp8", "decode"])
def test_which_step_programs_write_a_page_at_a_time(program, dtype, rows,
                                                    by_page):
    """The page write is in a chunk program whose bucket is a block or more
    over plain pages and in no other: speculation's verifier at ``k + 1``
    rows, a bucket under a block, every program over fp8 scaled pages and the
    decode step are traced as they were, the row scatter and nothing of
    ``write_chunk_pages``. What the engine counts (``chunk_by_page``) is the
    same static rule."""
    from deepspeed_tpu.inference.v2 import generic_decode as gd
    from deepspeed_tpu.inference.v2.modules import policy_for
    from deepspeed_tpu.models.llama import (TINY_LLAMA, LlamaConfig,
                                            LlamaForCausalLM)
    cfg = LlamaConfig(**{**TINY_LLAMA.__dict__, "dtype": jnp.float32,
                         "max_seq_len": 512})
    params = jax.eval_shape(
        lambda key: LlamaForCausalLM(cfg).init(
            key, {"input_ids": np.zeros((1, 8), np.int32)})["params"],
        jax.random.PRNGKey(0))
    policy = policy_for(cfg)
    spec = policy.cache_spec(cfg)
    kv = BlockedKVCache(KVCacheConfig(
        num_layers=spec.num_layers, num_kv_heads=spec.num_kv_heads,
        head_dim=spec.head_dim, block_size=16, num_blocks=16, dtype=dtype))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    if program == "decode_step_g":
        tail = (ints(rows), ints(rows), ints(rows, 4),
                jax.ShapeDtypeStruct((rows,), jnp.bool_))
    else:
        tail = (ints(rows), ints(), ints(4), ints())
        assert kv.chunk_by_page(rows) == by_page
    text = getattr(gd, program).lower(
        params, kv.pool, *tail, policy=policy, cfg=cfg, block_size=16,
        attn_impl="gather").as_text()
    assert ("write_chunk_pages" in text) == by_page
    if not by_page:
        assert "stablehlo.scatter" in text


@pytest.mark.parametrize("pages", ["head_pages", "latent_pages",
                                   "pages_by_layer_kind", "split_head_pages",
                                   "state_slots"])
def test_served_tokens_are_the_row_scatters_over_chunks_at_unaligned_starts(
        monkeypatch, pages):
    """A toy model of each pool format served greedily, a long prompt's
    chunks beside rows that decode (a step of 32 tokens less the decode rows:
    the next chunk starts at 31, 61, ... in blocks of 8): token for token
    what the same engine delivers with every chunk written a row at a time,
    as before PR 55."""
    from deepspeed_tpu.inference.v2 import kv_cache
    from deepspeed_tpu.telemetry.tracer import get_tracer
    from test_fused_step import KINDS, _engine
    from test_step_in_flight import _prompts, _served
    build, options = KINDS[pages]
    cfg, params = build()
    prompts, budgets = _prompts((11, 93, 45), seed=8), (14, 5, 7)
    tracer = get_tracer()
    was = tracer.enabled
    tracer.configure(enabled=True)
    tracer.clear()
    try:
        by_page, _ = _served(_engine(cfg, params, **options), prompts,
                             budgets)
        chunks = [e[7] for e in tracer.events_snapshot()
                  if e[1] == "serve/prefill_chunk"]
    finally:
        tracer.configure(enabled=was)
        tracer.clear()
    assert sum(c["start"] % 8 != 0 for c in chunks) >= 3
    assert all(c["kv_rows_by_page"] == c["tokens"] for c in chunks)
    # the same programs traced anew with the row scatter in every chunk
    monkeypatch.setattr(kv_cache._Pages, "by_page", False)
    jax.clear_caches()
    try:
        by_row, _ = _served(_engine(cfg, params, **options), prompts, budgets)
    finally:
        jax.clear_caches()
    assert by_page == by_row and [len(g) for g in by_page] == list(budgets)
