"""Pallas kernel tests (interpret mode on CPU; same code path compiles on TPU).

Reference analog: tests/unit/ops/* — each native kernel vs a reference
implementation on random tensors.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.flash_attention import attention_reference
from deepspeed_tpu.ops.pallas.flash_attention import pallas_flash_attention
from deepspeed_tpu.ops.pallas.quant import dequantize_int8, quantize_int8
from deepspeed_tpu.ops.pallas.rms_norm import pallas_rms_norm, rms_norm_reference


def qkv(b=2, s=128, h=4, hkv=None, d=32, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    hkv = hkv or h
    return (jnp.asarray(rng.normal(size=(b, s, h, d)), dtype),
            jnp.asarray(rng.normal(size=(b, s, hkv, d)), dtype),
            jnp.asarray(rng.normal(size=(b, s, hkv, d)), dtype))


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_flash_matches_reference(causal):
    q, k, v = qkv()
    out = pallas_flash_attention(q, k, v, causal, 64, 64, True)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_pallas_flash_gqa_unaligned():
    q, k, v = qkv(s=100, h=8, hkv=2)   # padding + GQA index mapping
    out = pallas_flash_attention(q, k, v, True, 64, 64, True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_flash_grad(causal):
    q, k, v = qkv(s=64)

    def loss_p(q, k, v):
        return jnp.sum(pallas_flash_attention(q, k, v, causal, 32, 32, True) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


@pytest.mark.slow
def test_pallas_flash_grad_gqa_unaligned():
    # GQA (in-kernel group accumulation for dk/dv) + q/k padding in backward
    q, k, v = qkv(s=100, h=8, hkv=2)

    def loss_p(q, k, v):
        return jnp.sum(pallas_flash_attention(q, k, v, True, 32, 32, True) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_pallas_flash_grad_weighted_loss():
    # asymmetric cotangent exercises delta = rowsum(dO*O) properly
    q, k, v = qkv(s=64, h=2)
    w = jnp.asarray(np.random.default_rng(9).normal(size=(2, 64, 2, 32)),
                    jnp.float32)

    def loss_p(q, k, v):
        return jnp.sum(w * pallas_flash_attention(q, k, v, True, 32, 32, True))

    def loss_r(q, k, v):
        return jnp.sum(w * attention_reference(q, k, v, causal=True))

    gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_pallas_rms_norm():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 37, 256)), jnp.float32)
    scale = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    out = pallas_rms_norm(x, scale, 1e-5, 64, True)
    ref = rms_norm_reference(x, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_pallas_rms_norm_grad():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(8, 128)), jnp.float32)
    scale = jnp.asarray(1.0 + 0.1 * rng.normal(size=(128,)), jnp.float32)

    def loss_p(x, s):
        return jnp.sum(pallas_rms_norm(x, s, 1e-5, 8, True) ** 3)

    def loss_r(x, s):
        return jnp.sum(rms_norm_reference(x, s) ** 3)

    gp = jax.grad(loss_p, argnums=(0, 1))(x, scale)
    gr = jax.grad(loss_r, argnums=(0, 1))(x, scale)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_int8_quant_roundtrip():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(16, 512)) * 3.0, jnp.float32)
    q, s = quantize_int8(x, interpret=True)
    assert q.dtype == jnp.int8 and s.shape == (16, 1)
    back = dequantize_int8(q, s, dtype=jnp.float32, interpret=True)
    # int8 symmetric: relative error bounded by ~scale/2 = absmax/254
    err = np.abs(np.asarray(back) - np.asarray(x))
    bound = np.abs(np.asarray(x)).max(axis=-1, keepdims=True) / 127
    assert (err <= bound).all()


def test_int8_quant_extremes():
    x = jnp.zeros((4, 128), jnp.float32)
    q, s = quantize_int8(x, interpret=True)
    assert np.allclose(np.asarray(q), 0)
    back = dequantize_int8(q, s, dtype=jnp.float32, interpret=True)
    assert np.allclose(np.asarray(back), 0)


def test_quantized_all_gather(mesh_dp8):
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.ops.pallas.quant import quantized_all_gather
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(16, 128)), jnp.float32)

    def body(x_l):
        return quantized_all_gather(x_l, "data")

    out = jax.jit(lambda v: jax.shard_map(
        body, mesh=mesh_dp8, in_specs=P("data"), out_specs=P(),
        check_vma=False)(v))(x)
    rel = np.abs(np.asarray(out) - np.asarray(x)) / (np.abs(np.asarray(x)).max())
    assert rel.max() < 0.02  # int8 quantization error bound


@pytest.mark.parametrize("window,softcap", [(None, None), (24, None),
                                            (None, 20.0)])
def test_paged_attention_kernel(window, softcap):
    """Paged decode/prefill kernel vs gather reference (GQA, ragged lengths,
    trash-padded tables, sliding window)."""
    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_attention_reference)
    rng = np.random.default_rng(0)
    hkv, nb, bs, d = 2, 16, 16, 32
    kp = jnp.asarray(rng.normal(size=(hkv, nb, bs, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(hkv, nb, bs, d)), jnp.float32)
    # decode: B=3, rep=4
    q = jnp.asarray(rng.normal(size=(3, 1, 8, d)), jnp.float32)
    tables = jnp.asarray(rng.permutation(nb - 1)[:12].reshape(3, 4), jnp.int32)
    start = jnp.asarray([37, 5, 63], jnp.int32)
    out_k = paged_attention(q, kp, vp, tables, start, window=window,
                            softcap=softcap, interpret=True)
    out_r = paged_attention_reference(q, kp, vp, tables, start, window=window,
                                      softcap=softcap)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=2e-5, rtol=2e-5)
    # prefill chunk: B=1, T=24 at offset 16
    q = jnp.asarray(rng.normal(size=(1, 24, 4, d)), jnp.float32)
    tables = jnp.asarray([[3, 7, 1, 9]], jnp.int32)
    start = jnp.asarray([16], jnp.int32)
    out_k = paged_attention(q, kp, vp, tables, start, window=window,
                            softcap=softcap, interpret=True)
    out_r = paged_attention_reference(q, kp, vp, tables, start, window=window,
                                      softcap=softcap)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("pages", ["plain", "fp8-scaled"])
@pytest.mark.parametrize("layer", [0, 2])
def test_paged_attention_over_the_whole_pool(layer, pages):
    """``paged_attention_pool`` finds a layer's pages through its index maps:
    the same floats as the kernel on that layer's K and V pages sliced out."""
    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_attention_pool)
    rng = np.random.default_rng(layer)
    layers, hkv, nb, bs, d = 3, 2, 16, 16, 32
    pool = jnp.asarray(rng.normal(size=(layers, 2, hkv, nb, bs, d)),
                       jnp.float32)
    scales = None
    if pages == "fp8-scaled":
        pool = pool.astype(jnp.float8_e4m3fn)
        scales = jnp.asarray(rng.uniform(0.5, 2.0, size=(layers, 2, hkv, nb)),
                             jnp.float32)
    q = jnp.asarray(rng.normal(size=(3, 1, 8, d)), jnp.float32)
    tables = jnp.asarray(rng.permutation(nb - 1)[:12].reshape(3, 4), jnp.int32)
    start = jnp.asarray([37, 5, 63], jnp.int32)
    whole = paged_attention_pool(q, pool, layer, tables, start, window=24,
                                 scales=scales, interpret=True)
    ks, vs = (scales[layer, 0], scales[layer, 1]) if scales is not None \
        else (None, None)
    sliced = paged_attention(q, pool[layer, 0], pool[layer, 1], tables, start,
                             window=24, k_scales=ks, v_scales=vs,
                             interpret=True)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(sliced))


# What a key tile of several pages can get wrong. Blocks of 16, heads of 32
# and two KV heads unless said: a tile is 8 pages = 128 keys, or the table
# where that is shorter. ``starts``: the queries' first positions, a batch row
# each; ``tile``: the (rows, pages, heads) the kernel's rule has to choose for
# the case to test what it says: a short fold takes a table entry's page of
# every KV head in one copy (PR 40), any other one head over up to 16 pages
# (PR 42). ``small``: the rule's sizes scaled down to these toy shapes (row
# blocks of 64, behind a narrow window of 32, no fold short enough for every
# head), so that a chunk of 256 tokens is cut as one of 4,096 is on the chip.
# ``nan_past``: every page no context reaches holds NaN, the trash block too.
_SMALL = {"_DEFAULT_VMEM_BYTES": 0, "_MAX_ROWS": 64, "_WINDOW_ROWS": 32}


def _case(b, t, h, mb, starts, tile, window=None, softcap=None, fp8=False,
          d=32, hkv=2, trash=True, small=False, nan_past=False):
    return dict(b=b, t=t, h=h, mb=mb, starts=starts, tile=tile,
                window=window, softcap=softcap, fp8=fp8, d=d, hkv=hkv,
                trash=trash, small=small, nan_past=nan_past)


_JOINED_TILE_CASES = {
    # 5 blocks: one step of 8 slots, three of them padding; a context that
    # ends inside the tile, one of a single token, one that fills the table
    "table-5-of-8": _case(3, 1, 8, 5, [37, 0, 79], (8, 8, 2)),
    # 12 blocks: two steps, half of the second padding; a context that ends
    # on the tile's edge, one a key past it
    "table-12-of-16": _case(4, 1, 8, 12, [127, 128, 0, 191], (8, 8, 2)),
    # 20 blocks: three steps; contexts that end on the second tile's edge,
    # one key past it, and in the table's last page
    "table-20-of-24": _case(4, 1, 8, 20, [255, 256, 0, 319], (8, 8, 2)),
    # 6 and 9 query heads a KV head: folds of 8 and 16 rows
    "rep-6": _case(3, 1, 12, 20, [300, 3, 100], (8, 8, 2)),
    "rep-9": _case(3, 1, 18, 20, [300, 3, 100], (16, 8, 2), window=40),
    # a window that spans a tile's edge (keys 201-300 over tiles of 128),
    # one wholly inside a tile, one that ends on an edge
    "window-over-tile-edge": _case(3, 1, 8, 24, [300, 380, 256], (8, 8, 2),
                                   window=100),
    # a chunk that starts inside a tile and ends inside the next
    "chunk-over-tiles": _case(1, 72, 4, 20, [230], (144, 8, 2)),
    "chunk-window-over-tiles": _case(1, 72, 4, 20, [230], (144, 8, 2),
                                     window=50),
    # a fold cut into row blocks (heads of 128: 2,048 rows a block, one q
    # head's whole chunk each) behind a window narrower than a block and
    # wider than a tile of 16 pages
    "chunk-cut-window-under-rows": _case(1, 2048, 4, 132, [50], (2048, 16, 1),
                                         window=300, d=128),
    # ... and cut into blocks that span heads, from position 0
    "chunk-cut-over-heads": _case(1, 512, 16, 32, [0], (2048, 16, 1),
                                  window=300, d=128),
    # a chunk that starts inside a block and inside a tile, cut into four row
    # blocks a q head over a table (40 blocks) that is no whole number of
    # tiles (16 pages): the first blocks stop at their own horizon, tiles
    # before the chunk's end
    "chunk-cut-first-blocks-stop-early": _case(1, 256, 4, 40, [300],
                                               (64, 16, 1), small=True),
    # ... and behind a narrow window each row block reads ONE tile, from the
    # table entry its own window starts in (entries 15 to 29, never 0)
    "chunk-cut-window-tile-of-its-own": _case(1, 256, 4, 40, [300],
                                              (32, 8, 1), window=50,
                                              small=True),
    "chunk-cut-window-softcap-fp8": _case(1, 256, 4, 40, [300], (32, 8, 1),
                                          window=50, softcap=20.0, fp8=True,
                                          small=True),
    # pages that no context reaches hold NaN: a tile that is skipped, a page
    # of a live tile that is not copied, a padding row and a context of one
    # token (its table all trash but entry 0) leak nothing
    "nan-past-decode-dead-rows": _case(4, 1, 8, 20, [300, 0, 0, 170],
                                       (8, 8, 2), nan_past=True),
    "nan-past-heads-8-window": _case(3, 1, 32, 20, [300, 0, 319], (8, 8, 8),
                                     hkv=8, window=100, nan_past=True),
    "nan-past-chunk-cut": _case(1, 256, 4, 40, [300], (64, 16, 1),
                                small=True, nan_past=True),
    "nan-past-chunk-cut-window": _case(1, 256, 4, 40, [300], (32, 8, 1),
                                       window=50, small=True, nan_past=True),
    # fp8 pages: every slot of a tile under its own (head, page) scale
    "fp8-scales-a-slot": _case(3, 1, 8, 20, [300, 0, 319], (8, 8, 2),
                               fp8=True),
    "fp8-chunk-window": _case(1, 72, 4, 20, [230], (144, 8, 2), window=50,
                              fp8=True),
    "softcap": _case(3, 1, 8, 20, [300, 0, 319], (8, 8, 2), softcap=20.0),
    "softcap-chunk-window": _case(1, 72, 4, 20, [230], (144, 8, 2),
                                  window=50, softcap=20.0),
    # eight KV heads a step (the served models'), at the groups of the cells:
    # 4 (Mistral, Mixtral), 6 and 9 (Laguna's full and sliding layers)
    "heads-8-group-4": _case(3, 1, 32, 20, [300, 0, 319], (8, 8, 8), hkv=8),
    "heads-8-group-6": _case(3, 1, 48, 20, [255, 256, 100], (8, 8, 8), hkv=8),
    "heads-8-group-9-window": _case(3, 1, 72, 9, [130, 40, 143], (16, 8, 8),
                                    hkv=8, window=40),
    "heads-8-softcap-window": _case(2, 1, 32, 12, [190, 77], (8, 8, 8),
                                    hkv=8, window=100, softcap=20.0),
    # every (head, page) of a slot of eight heads under its own scale
    "heads-8-fp8-scales": _case(3, 1, 32, 20, [300, 0, 319], (8, 8, 8),
                                hkv=8, fp8=True),
    "heads-8-fp8-window-group-6": _case(2, 1, 48, 12, [191, 64], (8, 8, 8),
                                        hkv=8, fp8=True, window=70),
    # a table that is no whole number of tiles, and a table of random blocks
    # where the cells' are trash-padded: a dead slot's page is another page
    "heads-8-table-5-of-8": _case(3, 1, 32, 5, [37, 0, 79], (8, 8, 8), hkv=8),
    "heads-8-random-table": _case(3, 1, 32, 20, [300, 0, 319], (8, 8, 8),
                                  hkv=8, trash=False),
    # four heads, and the one head that is the kernel as it was
    "heads-4": _case(3, 1, 16, 12, [127, 128, 5], (8, 8, 4), hkv=4),
    "heads-1": _case(3, 1, 4, 12, [127, 128, 5], (8, 8, 1), hkv=1),
    "heads-1-window-fp8": _case(3, 1, 6, 12, [127, 128, 5], (8, 8, 1), hkv=1,
                                window=50, fp8=True),
    # a short chunk of eight KV heads is one row block too: its rows' tokens
    # are reckoned by the same remainder under every head
    "heads-8-short-chunk-window": _case(1, 24, 16, 20, [270], (48, 8, 8),
                                        hkv=8, window=50),
    # a chunk cut into row blocks takes one KV head a step, whatever the
    # heads
    "chunk-cut-of-8-heads-takes-one": _case(1, 1024, 32, 80, [200],
                                            (2048, 16, 1), hkv=8, d=128),
}


@pytest.mark.parametrize("name", sorted(_JOINED_TILE_CASES))
def test_paged_attention_joined_key_tile(name, monkeypatch):
    """The kernel copies several pages of the table into one key tile a
    step: against the gather reference where the table is no whole number of
    tiles, where contexts and windows end inside a tile, at folds of 8 and 16
    rows, where the fold is cut into row blocks (under a window wider than a
    tile, and under a narrow one that gives each block a tile of its own),
    with fp8 pages under different scales, with softcap; where a step takes a
    table entry's page of every KV head at once (8, 4 and 2 heads, groups of
    4, 6 and 9, each head's page under its own scale); and where every page
    the kernel has no business reading holds NaN."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    c = _JOINED_TILE_CASES[name]
    b, t, h, mb, d, hkv = c["b"], c["t"], c["h"], c["mb"], c["d"], c["hkv"]
    nb, bs = 192, 16
    for size, value in (_SMALL if c["small"] else {}).items():
        monkeypatch.setattr(pa, size, value)
    assert pa._tile(h // hkv * t, mb, bs, d, 1 if c["fp8"] else 4, hkv,
                    window=c["window"]) == c["tile"]
    rng = np.random.default_rng(sorted(_JOINED_TILE_CASES).index(name))
    kp = jnp.asarray(rng.normal(size=(hkv, nb, bs, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(hkv, nb, bs, d)), jnp.float32)
    scales = {}
    if c["fp8"]:
        kp, vp = kp.astype(jnp.float8_e4m3fn), vp.astype(jnp.float8_e4m3fn)
        scales = {"k_scales": jnp.asarray(rng.uniform(0.25, 4.0, (hkv, nb)),
                                          jnp.float32),
                  "v_scales": jnp.asarray(rng.uniform(0.25, 4.0, (hkv, nb)),
                                          jnp.float32)}
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    # every row's blocks its own; what lies past a context is the trash block
    tables = np.full((b, mb), nb - 1, np.int32) if c["trash"] \
        else rng.integers(0, nb, (b, mb)).astype(np.int32)
    free = rng.permutation(nb - 1)
    for r, first in enumerate(c["starts"]):
        n = -(-(first + t) // bs)
        assert n <= mb
        tables[r, :n], free = free[:n], free[n:]
    tables = jnp.asarray(tables)
    start = jnp.asarray(c["starts"], jnp.int32)
    how = dict(window=c["window"], softcap=c["softcap"], **scales)
    ref = pa.paged_attention_reference(q, kp, vp, tables, start, **how)
    if c["nan_past"]:
        unreached = jnp.asarray(np.append(free, nb - 1))
        kp, vp = kp.at[:, unreached].set(jnp.nan), \
            vp.at[:, unreached].set(jnp.nan)
    out = pa.paged_attention(q, kp, vp, tables, start, interpret=True, **how)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_paged_tile_follows_the_fold_the_table_and_the_window():
    """``_tile`` at the served shapes (blocks of 64, bfloat16, eight KV heads
    of 128 unless said): a short fold (every decode fold) is its own rows
    over 8 pages or the table and takes every KV head a step where that many
    count to the default VMEM; any other takes one head, 2,048 rows over 16
    pages or the table, whatever the width of its keys; behind a window
    narrow enough for one tile of 16 pages its tile does not widen with the
    full fold's: 256 rows over the smallest tile that holds all they see.
    And the VMEM the call asks for is what its own tile counts to."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    shape = (64, 128, 2, 8)
    assert (pa._MAX_PAGES, pa._MAX_PAGES_CUT, pa._MAX_ROWS,
            pa._WINDOW_ROWS) == (8, 16, 2048, 256)
    assert pa._tile(6, 260, *shape) == (8, 8, 8)        # Laguna, full decode
    assert pa._tile(9, 9, *shape, window=512) == (16, 8, 8)     # ... sliding
    assert pa._tile(4, 64, *shape) == (8, 8, 8)         # Mixtral's decode
    assert pa._tile(4, 32, *shape, window=4096) == (8, 8, 8)    # chat's
    assert pa._tile(4, 64, 64, 128, 1, 8) == (8, 8, 8)  # ... over fp8 pages
    assert pa._tile(4, 4, *shape) == (8, 4, 8)   # a table shorter than a tile
    assert pa._tile(4, 5, *shape) == (8, 8, 8)
    # chunks: Laguna's full, Mixtral's, chat's (one row block of 2,048 rows
    # behind a window wider than any tile), fp8 pages, heads of 256, MiMo's
    # keys of 256 lanes beside values of 128
    assert pa._tile(6 * 4096, 260, *shape) == (2048, 16, 1)
    assert pa._tile(4 * 2048, 64, *shape) == (2048, 16, 1)
    assert pa._tile(4 * 512, 16, *shape, window=4096) == (2048, 16, 1)
    assert pa._tile(4 * 512, 8, *shape, window=4096) == (2048, 8, 1)
    assert pa._tile(4 * 512, 16, 64, 128, 1, 8) == (2048, 16, 1)
    assert pa._tile(8 * 1024, 32, 64, 256, 2, 8) == (2048, 16, 1)
    assert pa._tile(16 * 4096, 390, 64, 256, 2, 4, 128) == (2048, 16, 1)
    # behind a window: Laguna's 512 needs 256 + 511 keys from wherever in a
    # page, 13 pages, one tile of 16; MiMo's 128 one tile of 8; a window of
    # 1,024 fits no tile beside 256 rows and walks the table as a full fold
    assert pa._tile(9 * 4096, 73, *shape, window=512) == (256, 16, 1)
    assert pa._tile(8 * 4096, 67, 64, 256, 2, 8, 128, 128) == (256, 8, 1)
    assert pa._tile(8 * 512, 11, 64, 256, 2, 8, 128, 128) == (256, 8, 1)
    assert pa._tile(4 * 4096, 128, *shape, window=1024) == (2048, 16, 1)
    # every head a step up to the fold that counts to the default VMEM with
    # eight of them (232 rows of 128: 15.97 MiB), one past it; one KV head is
    # a step of one
    assert pa._tile(4, 64, 64, 128, 2) == (8, 8, 1)
    assert pa._tile(4, 64, 64, 128, 2, 2) == (8, 8, 2)
    assert pa._tile(232, 64, *shape) == (232, 8, 8)
    assert pa._tile(240, 64, *shape) == (240, 16, 1)

    # the module's count: a row's q and output twice, its float32
    # accumulator, maximum and sum, a float32 score and probability a key; a
    # key's K and V rows in each of the two buffers the kernel copies into
    def counted(rows, pages, heads=1, d=128, dv=128):
        return heads * (rows * (4 * (d + dv) + 4 * dv + 1024 + 8 * pages * 64)
                        + 4 * pages * 64 * (d + dv))
    for tile, widths in (((2048, 16, 1), (128, 128)),
                         ((2048, 16, 1), (256, 128)),
                         ((232, 8, 8), (128, 128)), ((256, 8, 1), (256, 128))):
        count = pa._vmem_bytes(*tile, 64, *widths, 2)
        assert count == counted(*tile, *widths) <= pa._SCOPED_VMEM_BYTES
        assert pa._vmem_limit(*tile, 64, *widths, 2) \
            == max(count + count // 4, 16 << 20) < 32 << 20
    assert counted(232, 8, 8) <= pa._DEFAULT_VMEM_BYTES < counted(240, 8, 8)
    # what a decode call's seconds divide by: code-mixed's full layer reads
    # a K and a V page a live table entry for all eight heads at once, and
    # a padding row's one; a head a step reads each eight times
    contexts = [16384] * 8 + [3000] * 20
    assert pa.decode_slot_copies(contexts, 32, 8, 260, 64, 128, 2, group=6) \
        == 2 * (8 * 256 + 20 * 47 + 4)
    assert pa.decode_slot_copies(contexts, 32, 1, 260, 64, 128, 2) * 8 \
        == 16 * (8 * 256 + 20 * 47 + 4)
    # a chunk of 4,096 at 8,192 in a full layer of six heads a KV head: two
    # row blocks a head over 10 and 12 tiles of 1,024 keys (the visible pairs
    # over it, 41,945,088, is the tiles' fill: 0.909), 160 + 192 entries a
    # head; behind Laguna's window (its own table of 73 blocks from where the
    # first query's window starts: position 511 of it) sixteen row blocks of
    # 256 a head, each over its one tile of 1,024 keys
    assert pa.chunk_tile_keys(8192, 4096, 6, 8, 192, 64, 128, 2) \
        == (2048 * (10240 + 12288), 2 * (160 + 192) * 6 * 8)
    assert pa.chunk_tile_keys(511, 4096, 9, 8, 73, 64, 128, 2,
                              window=512)[0] == 4096 * 1024


def test_quantized_psum_scatter(mesh_dp8):
    """qgZ reduce-scatter building block: int8-wire sum matches psum_scatter
    within quantization error."""
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.ops.pallas.quant import quantized_psum_scatter
    rng = np.random.default_rng(4)
    # 8 devices, each holding a [16, 64] partial
    parts = jnp.asarray(rng.normal(size=(8, 16, 64)), jnp.float32)

    def body(x_l):
        return quantized_psum_scatter(x_l[0], "data")

    out = jax.jit(lambda v: jax.shard_map(
        body, mesh=mesh_dp8, in_specs=P("data"), out_specs=P("data"),
        check_vma=False)(v))(parts)
    exact = np.asarray(parts).sum(0)               # [16, 64] global sum
    got = np.asarray(out)                          # same, reassembled
    rel = np.abs(got - exact).max() / np.abs(exact).max()
    assert rel < 0.05, rel


def test_all_to_all_quant_reduce_hierarchical(mesh8):
    """Two-level qgZ over (fsdp, data): result matches the exact global sum."""
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.ops.pallas.quant import all_to_all_quant_reduce
    rng = np.random.default_rng(5)
    parts = jnp.asarray(rng.normal(size=(8, 16, 64)), jnp.float32)

    def body(x_l):
        return all_to_all_quant_reduce(x_l[0], "fsdp", outer_axis_name="data")

    out = jax.jit(lambda v: jax.shard_map(
        body, mesh=mesh8, in_specs=P(("data", "fsdp")),
        out_specs=P(("fsdp", "data")), check_vma=False)(v))(parts)
    exact = np.asarray(parts).sum(0)
    got = np.asarray(out)
    rel = np.abs(got - exact).max() / np.abs(exact).max()
    assert rel < 0.05, rel


@pytest.mark.parametrize("fmt", ["e4m3", "e5m2"])
def test_fp8_quant_roundtrip(fmt):
    from deepspeed_tpu.ops.pallas.fp_quant import (
        FP8_FORMATS, dequantize_fp8, quantize_fp8)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(16, 256)) * 5.0, jnp.float32)
    q, s = quantize_fp8(x, fmt=fmt, interpret=True)
    assert q.dtype == FP8_FORMATS[fmt][0] and s.shape == (16, 1)
    back = dequantize_fp8(q, s, dtype=jnp.float32, interpret=True)
    # jnp reference: scale to fmax, cast, cast back. The fp8 cast itself
    # must go through jnp so reference and kernel share XLA's convert
    # rounding — numpy/ml_dtypes rounds a handful of near-tie values one
    # ulp differently on this backend, which is cast-library drift, not a
    # kernel defect
    dt, fmax = FP8_FORMATS[fmt]
    scale = np.maximum(np.abs(np.asarray(x)).max(-1, keepdims=True) / fmax, 1e-12)
    ref = np.asarray(
        jnp.asarray(np.asarray(x) / scale).astype(dt).astype(jnp.float32)
    ) * scale
    np.testing.assert_allclose(np.asarray(back), ref, rtol=1e-6, atol=1e-6)
    # error bound: e4m3 has 3 mantissa bits -> rel err <= 2^-4 per element
    rel = np.abs(np.asarray(back) - np.asarray(x)) / \
        (np.abs(np.asarray(x)) + 1e-3)
    assert rel.max() < (0.07 if fmt == "e4m3" else 0.3)


def test_fp8_selective_dequantize():
    from deepspeed_tpu.ops.pallas.fp_quant import (
        dequantize_fp8, quantize_fp8, selective_dequantize_fp8)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    q, s = quantize_fp8(x, interpret=True)
    rows = jnp.asarray([3, 17, 42], jnp.int32)
    got = selective_dequantize_fp8(q, s, rows, dtype=jnp.float32,
                                   interpret=True)
    full = dequantize_fp8(q, s, dtype=jnp.float32, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(full)[[3, 17, 42]])


def test_fp8_all_gather(mesh_dp8):
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.ops.pallas.fp_quant import quantized_all_gather_fp8
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(16, 128)), jnp.float32)
    out = jax.jit(lambda v: jax.shard_map(
        lambda x_l: quantized_all_gather_fp8(x_l, "data"),
        mesh=mesh_dp8, in_specs=P("data"), out_specs=P(),
        check_vma=False)(v))(x)
    rel = np.abs(np.asarray(out) - np.asarray(x)) / np.abs(np.asarray(x)).max()
    assert rel.max() < 0.07


def test_fp8_matmul_close_to_fp32():
    from deepspeed_tpu.ops.pallas.fp_quant import fp8_matmul, quantize_fp8
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.normal(size=(8, 128)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(128, 64)) * 0.1, jnp.float32)
    # fp8_matmul expects per-K-row scales: quantize_fp8 groups over the last
    # dim, so quantizing b [K, N] directly yields scales [K, 1] as required
    q, s = quantize_fp8(b, interpret=True)
    out = fp8_matmul(a, q, s)
    ref = np.asarray(a) @ np.asarray(b)
    rel = np.abs(np.asarray(out) - ref).max() / np.abs(ref).max()
    assert rel < 0.1, rel


def test_quantized_all_to_all(mesh_dp8):
    """MoE-dispatch int8 all-to-all: permutation semantics match the fp
    all_to_all within quantization error."""
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.ops.pallas.quant import quantized_all_to_all
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)  # 8 rows/device

    def body_q(x_l):
        return quantized_all_to_all(x_l, "data")

    def body_f(x_l):
        return jax.lax.all_to_all(x_l, "data", split_axis=0, concat_axis=0,
                                  tiled=True)

    run = lambda body: np.asarray(jax.jit(lambda v: jax.shard_map(
        body, mesh=mesh_dp8, in_specs=P("data"), out_specs=P("data"),
        check_vma=False)(v))(x))
    got, ref = run(body_q), run(body_f)
    rel = np.abs(got - ref).max() / np.abs(ref).max()
    assert rel < 0.02, rel


def test_quantized_psum_grad(mesh_dp8):
    """quantized_psum's straight-through vjp matches lax.psum's transpose —
    convention regression guard for the calibration documented in
    quant.py:_quantized_psum_bwd (check_vma=False hands dL/dy / w)."""
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.ops.pallas.quant import quantized_psum
    x = jnp.asarray(np.random.default_rng(5).normal(size=(16, 64)), jnp.float32)

    def mk(body):
        f = jax.shard_map(body, mesh=mesh_dp8, in_specs=P("data"),
                          out_specs=P(), axis_names=frozenset({"data"}),
                          check_vma=False)
        return jax.grad(lambda v: jnp.sum(jax.jit(f)(v) ** 2))(x)

    g_ref = mk(lambda xl: jax.lax.psum(xl, "data"))
    g_q = mk(lambda xl: quantized_psum(xl, ("data",)))
    rel = np.abs(np.asarray(g_q) - np.asarray(g_ref)).max() / \
        np.abs(np.asarray(g_ref)).max()
    assert rel < 0.03, rel   # identical up to int8 fwd rounding in g_ref's y


@pytest.mark.slow
def test_quantized_psum_grad_two_axes():
    """Same convention guard over TWO manual axes (the MoE dispatch path
    reduces over composed batch axes): bwd scaling must be 1/(w1*w2)."""
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.comm.mesh import create_mesh
    from deepspeed_tpu.config.config import MeshConfig
    from deepspeed_tpu.ops.pallas.quant import quantized_psum
    mesh = create_mesh(MeshConfig(data=4, fsdp=2))
    x = jnp.asarray(np.random.default_rng(6).normal(size=(16, 64)), jnp.float32)

    def mk(body):
        f = jax.shard_map(body, mesh=mesh, in_specs=P(("data", "fsdp")),
                          out_specs=P(),
                          axis_names=frozenset({"data", "fsdp"}),
                          check_vma=False)
        return jax.grad(lambda v: jnp.sum(jax.jit(f)(v) ** 2))(x)

    g_ref = mk(lambda xl: jax.lax.psum(xl, ("data", "fsdp")))
    g_q = mk(lambda xl: quantized_psum(xl, ("data", "fsdp")))
    rel = np.abs(np.asarray(g_q) - np.asarray(g_ref)).max() / \
        np.abs(np.asarray(g_ref)).max()
    assert rel < 0.03, rel


@pytest.mark.slow
@pytest.mark.parametrize("window", [16, 40])
def test_pallas_flash_sliding_window(window):
    """Sliding-window masking in the flash fwd + both backward kernels
    (mistral-style training on the kernel path; below-window blocks are
    skipped like above-diagonal ones). GQA + unaligned seq included."""
    from deepspeed_tpu.models.llama import _xla_attention
    q, k, v = qkv(s=100, h=8, hkv=2)

    out = pallas_flash_attention(q, k, v, True, 32, 32, True, window)
    ref = _xla_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    def lp(q, k, v):
        return jnp.sum(
            pallas_flash_attention(q, k, v, True, 32, 32, True, window) ** 2)

    def lr(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, causal=True,
                                      window=window) ** 2)

    gp = jax.grad(lp, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# the flash kernels' panel schedule: which panels a grid visits, which body
# each gets (interpret mode; panels small enough that one call holds dead,
# whole, diagonal-crossed and window-edge-crossed panels)
# ---------------------------------------------------------------------------
def _dense_mask(sq, sk, causal, window, shift=0, q_ids=None, k_ids=None):
    """[B or 1, sq, sk] bool by brute force: the contract of ``_mask``."""
    qpos, kpos = np.arange(sq)[:, None], np.arange(sk)[None, :]
    mask = np.ones((sq, sk), bool)
    if causal or window is not None:
        mask &= qpos >= kpos + shift
    if window is not None:
        mask &= kpos > qpos - window
    mask = mask[None]
    if q_ids is not None:
        mask = mask & (np.asarray(q_ids)[:, :, None]
                       == np.asarray(k_ids)[:, None, :])
    return mask


def _dense_attention(q, k, v, mask):
    """Softmax attention under an explicit mask; a row that sees no key
    gives zeros (a ring step's skipped rows), where a softmax over a row of
    NEG_INF would give the mean of the values."""
    h, hkv = q.shape[2], k.shape[2]
    k, v = jnp.repeat(k, h // hkv, axis=2), jnp.repeat(v, h // hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    m = jnp.asarray(mask)[:, None]
    s = jnp.where(m, s, -1e30)
    p = jnp.where(m, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _seg(b, s, seed, segments=3):
    return np.sort(np.random.default_rng(seed).integers(
        0, segments, size=(b, s)), axis=1).astype(np.int32)


#: name -> (s, h, hkv, block_q, block_k, causal, window, shift, segment ids)
_SCHEDULE_CASES = {
    # a window that is no multiple of the panel: dead panels on both sides of
    # the band, whole ones inside, the diagonal and the edge each crossing
    "window-no-multiple": (128, 4, 4, 16, 16, True, 40, 0, None),
    "window-equals-panel": (96, 2, 2, 16, 16, True, 16, 0, None),
    "window-under-a-panel": (96, 2, 2, 16, 16, True, 5, 0, None),
    "rows-taller-than-keys": (128, 2, 2, 32, 16, True, 32, 0, None),
    "keys-wider-than-rows": (128, 2, 2, 16, 32, True, 24, 0, None),
    "unaligned-gqa4-window": (100, 8, 2, 32, 16, True, 40, 0, None),
    "causal-no-window": (96, 2, 2, 16, 32, True, None, 0, None),
    "causal-unaligned-gqa4": (72, 4, 1, 32, 32, True, None, 0, None),
    "strict-band": (64, 2, 2, 16, 16, True, None, 1, None),
    "strict-band-rect": (96, 2, 1, 32, 16, True, None, 1, None),
    "non-causal": (64, 2, 2, 16, 32, False, None, 0, None),
    "non-causal-padded-tail": (72, 2, 1, 16, 32, False, None, 0, None),
    "segments-window": (96, 2, 2, 16, 16, True, 24, 0, "shared"),
    "segments-non-causal": (64, 2, 2, 32, 16, False, None, 0, "shared"),
    "ring-pair-full": (64, 2, 2, 16, 16, False, None, 0, "pair"),
    "ring-pair-strict": (80, 4, 2, 16, 32, True, None, 1, "pair"),
}


def _run_flash(case, q, k, v, w):
    """(out, lse, dq, dk, dv) of the kernels, and the dense mask; ``case`` a
    row of ``_SCHEDULE_CASES``."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    s, _, _, bq, bk, causal, window, shift, seg = case
    b = q.shape[0]
    ids = {None: None, "shared": (_seg(b, s, 1),) * 2,
           "pair": (_seg(b, s, 2), _seg(b, s, 3))}[seg]
    segment_ids = None if ids is None else (
        jnp.asarray(ids[0]) if seg == "shared" else
        tuple(jnp.asarray(i) for i in ids))
    out, lse = fa._pallas_flash_fwd_impl(q, k, v, causal, bq, bk, True,
                                         window, shift, segment_ids)
    grads = fa._pallas_flash_bwd_impl(q, k, v, out, lse, w, causal, bq, bk,
                                      True, window, shift, segment_ids)
    mask = _dense_mask(s, s, causal, window, shift, *(ids or (None, None)))
    return (out, lse) + tuple(grads), mask


@pytest.mark.parametrize("case", sorted(_SCHEDULE_CASES))
def test_flash_panel_schedule_matches_dense(case):
    """Forward, lse and all three gradients of the banded grid with its two
    bodies against dense attention under the brute-force mask."""
    s, h, hkv = _SCHEDULE_CASES[case][:3]
    q, k, v = qkv(b=2, s=s, h=h, hkv=hkv, seed=len(case))
    w = jnp.asarray(np.random.default_rng(5).normal(size=q.shape), jnp.float32)
    (out, lse, dq, dk, dv), mask = _run_flash(_SCHEDULE_CASES[case], q, k, v, w)
    if _SCHEDULE_CASES[case][-1] is None and not _SCHEDULE_CASES[case][-2]:
        causal, window = _SCHEDULE_CASES[case][5:7]
        np.testing.assert_allclose(
            np.asarray(_dense_attention(q, k, v, mask)),
            np.asarray(attention_reference(q, k, v, causal=causal,
                                           window=window)),
            atol=2e-5, rtol=2e-5)
    ref, vjp = jax.vjp(lambda q, k, v: _dense_attention(q, k, v, mask),
                       q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    for name, got, want in zip(("dq", "dk", "dv"), (dq, dk, dv), vjp(w)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4, rtol=2e-4, err_msg=name)
    # lse: a row [B*H, 1, S padded]; rows that see a key hold the logsumexp
    kr = jnp.repeat(k, h // hkv, axis=2)
    sc = np.asarray(jnp.einsum("bqhd,bkhd->bhqk", q, kr)) / np.sqrt(32)
    sees = np.broadcast_to(mask[:, None], sc.shape)
    want = np.log(np.sum(np.where(sees, np.exp(sc), 0.0), axis=-1) + 1e-300)
    got = np.asarray(lse)[:, 0, :s].reshape(2, h, s)
    rows = sees.any(axis=-1)
    np.testing.assert_allclose(got[rows], want[rows], atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kernel,lanes", [
    ("fwd", 1), ("fwd", 128), ("dq", 1), ("dq", 128),
    ("dkv", 1)])    # dKV reads its statistics as rows, never lane-dense
def test_flash_two_bodies_agree_bit_for_bit(kernel, lanes):
    """A panel wholly inside the band may take either body: the unmasked
    one gives what the masked one gives under an all-true mask, bit for bit
    (``where(True, x, ..)`` is ``x``), whether a row block's statistics are
    columns or lane-dense. The bodies as pure functions, op by op: inside a
    kernel the CPU's compiler fuses each body on its own and reorders sums."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    from deepspeed_tpu.ops.pallas.latent_attention import _softmax_update
    rng = np.random.default_rng(3)
    q, k, v, do = (jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
                   for _ in range(4))
    col = lambda x: jnp.tile(jnp.asarray(x, jnp.float32), (1, lanes))
    lse, delta = col(rng.normal(size=(64, 1)) + 3), col(rng.normal(size=(64, 1)))
    if kernel == "fwd":
        s = fa._nt(q, k) * 0.2
        scratch = (col(rng.normal(size=(64, 1))),
                   col(np.abs(rng.normal(size=(64, 1)))),
                   jnp.asarray(rng.normal(size=(64, 32)), jnp.float32))
        bare = _softmax_update(s, None, v, *scratch)
        masked = _softmax_update(s, jnp.ones(s.shape, bool), v, *scratch)
    elif kernel == "dq":
        bare = (fa._dq_panel(q, k, v, do, lse, delta, None, 0.2),)
        masked = (fa._dq_panel(q, k, v, do, lse, delta,
                               jnp.ones((64, 64), bool), 0.2),)
    else:
        bare = fa._dkv_panel(q, k, v, do, lse.T, delta.T, None, 0.2)
        masked = fa._dkv_panel(q, k, v, do, lse.T, delta.T,
                               jnp.ones((64, 64), bool), 0.2)
    for a, b in zip(bare, masked):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


#: (sq, sk, block_q, block_k, causal, window, shift)
_COUNT_CASES = [
    (128, 128, 16, 16, True, 40, 0), (128, 128, 32, 16, True, 32, 0),
    (128, 128, 16, 32, True, 24, 0), (100, 100, 32, 16, True, 40, 0),
    (96, 96, 16, 16, True, 5, 0), (96, 96, 16, 16, True, 16, 0),
    (96, 96, 16, 32, True, None, 0), (64, 64, 16, 16, True, None, 1),
    (96, 96, 32, 16, True, None, 1), (72, 72, 16, 32, False, None, 0),
    (64, 64, 16, 32, False, None, 0), (160, 64, 16, 16, True, 24, 0),
    (64, 160, 32, 16, True, None, 0), (200, 120, 16, 32, True, 48, 1),
    (8192, 8192, 1024, 1024, True, 4096, 0),
    (8192, 8192, 512, 512, True, 4096, 0),
]


@pytest.mark.parametrize("sq,sk,bq,bk,causal,window,shift", _COUNT_CASES)
def test_flash_schedule_counts_equal_brute_force(sq, sk, bq, bk, causal,
                                                 window, shift):
    """Live, whole and dead by the schedule equal a count over the dense
    mask, for the row-major grids (forward, dQ) and the key-major one (dKV);
    a band's live panels are visited once each, and a surplus step names the
    block the step before it named, so nothing is fetched for it."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    sched = fa._schedule(sq, sk, bq, bk, causal, window, shift, False)
    # padded query rows are rows like any other; padded keys are masked
    dense = np.zeros((sched.nq * bq, sched.nk * bk), bool)
    dense[:, :sk] = _dense_mask(sched.nq * bq, sk, causal, window, shift)[0]
    panel = dense.reshape(sched.nq, bq, sched.nk, bk).transpose(0, 2, 1, 3)
    live, whole = panel.any(axis=(2, 3)), panel.all(axis=(2, 3))

    def walk(outer, steps, first, block, panel_of):
        """One grid: ``panel_of(a, b)`` is the panel ``(qi, ki)`` of outer
        block ``a`` at inner block ``b``; ``block(a, j)`` the inner block
        step ``j`` names."""
        seen_live = np.zeros_like(live)
        seen_whole = np.zeros_like(whole)
        dead = 0
        for a in range(outer):
            named = [int(block(a, j)) for j in range(steps)]
            for j in range(steps):
                at = panel_of(a, first(a) + j)
                is_live, is_whole = sched.kind(*at)
                if is_live:
                    assert panel_of(a, named[j]) == at
                    assert not seen_live[at], "a panel visited twice"
                    seen_live[at], seen_whole[at] = True, bool(is_whole)
                else:
                    dead += 1
                    assert j == 0 or named[j] == named[j - 1]
        return seen_live, seen_whole, dead

    for outer, steps, first, block, panel_of in (
            (sched.nq, sched.k_steps, sched.first_k, sched.k_block,
             lambda qi, ki: (qi, ki)),
            (sched.nk, sched.q_steps, sched.first_q, sched.q_block,
             lambda ki, qi: (qi, ki))):
        seen_live, seen_whole, dead = walk(outer, steps, first, block,
                                           panel_of)
        np.testing.assert_array_equal(seen_live, live)
        np.testing.assert_array_equal(seen_whole, whole)
        assert dead == outer * steps - live.sum()


def test_flash_panels_at_the_train_cell():
    """``panels`` at the panel ``flash_attention_auto`` would choose: the
    train cell's call and its brute-force count."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    got = fa.panels(8192, 8192, True, 4096, head_dim=128)
    bq, bk = fa._auto_panel(8192, 8192, 128)
    dense = _dense_mask(8192, 8192, True, 4096)[0]
    panel = dense.reshape(8192 // bq, bq, 8192 // bk, bk)
    live = panel.any(axis=(1, 3))
    assert got["flash_panels"] == live.sum()
    assert got["flash_panels_masked"] == (live & ~panel.all(axis=(1, 3))).sum()
    steps = fa._schedule(8192, 8192, bq, bk, True, 4096, 0, False).k_steps
    assert got["flash_steps_dead"] == live.shape[0] * steps - live.sum()
    # no band, no padded tail: no mask anywhere, no dead step
    assert fa.panels(2048, 2048, False)["flash_panels_masked"] == 0
    assert fa.panels(2048, 2048, False)["flash_steps_dead"] == 0
