"""The Pallas grouped matmul (``ops/pallas/grouped_matmul.py``) in interpret
mode on the CPU against ``jax.lax.ragged_dot``: the rows up to the counts'
sum are compared, the tail is nobody's. Then the tiles it chooses from
static shapes, the visits it is handed, and ``grouped_expert_ffn`` with the
kernel for its matmul.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.grouped_experts import grouped_expert_ffn, softmax_route
from deepspeed_tpu.ops.pallas import grouped_matmul as gmm

_SPARSE = np.random.RandomState(7).randint(0, 4, 256).tolist()   # 0-3 rows

# (m, k, n, counts, tiles): tiles None = what ``tiling`` picks for the shape
CASES = {
    "every-group-some-rows": (64, 32, 48, [10, 6, 30, 18], None),
    "empty-groups": (64, 32, 48, [0, 34, 0, 30], None),
    "empty-groups-first-and-last": (64, 32, 48, [0, 0, 40, 0], None),
    "no-rows-at-all": (64, 32, 48, [0, 0, 0, 0], None),
    "one-group-holds-every-row": (64, 32, 48, [0, 64, 0, 0], None),
    "counts-end-inside-a-tile": (64, 32, 48, [3, 17, 21, 23], (16, 32, 48)),
    "counts-end-on-the-tiles": (64, 32, 48, [16, 32, 0, 16], (16, 32, 48)),
    "fewer-rows-than-m": (64, 32, 48, [5, 0, 9, 7], (16, 32, 48)),
    "one-row": (64, 32, 48, [0, 0, 1, 0], (16, 32, 48)),
    "a-tile-of-five-groups": (64, 32, 48, [2, 3, 1, 4, 30], (16, 32, 48)),
    "256-groups-of-0-to-3-rows": (512, 64, 32, _SPARSE, None),
    "more-groups-than-rows": (16, 32, 16, [1, 0, 1, 0, 0, 1, 1, 0] * 4, None),
    "fewer-rows-than-a-sublane": (6, 32, 16, [1, 0, 2, 0, 0, 1, 1, 0], None),
    "contraction-in-blocks": (64, 256, 128, [10, 0, 30, 20], (16, 128, 128)),
    "columns-in-blocks": (64, 128, 256, [10, 0, 30, 20], (16, 128, 128)),
    "both-in-blocks": (96, 256, 256, [40, 1, 0, 50], (32, 128, 128)),
    "k-and-n-swapped": (96, 256, 256, [40, 1, 0, 50], (32, 256, 128)),
    "runs-of-a-tall-tile": (512, 128, 256, [100, 0, 29, 300, 83],
                            (256, 128, 128)),
    "runs-of-a-tall-tile-whole-matrix": (512, 128, 256, [1, 400, 0, 90, 3],
                                         (256, 128, 256)),
    "rows-no-multiple-of-the-tile": (70, 128, 128, [3, 30, 0, 20],
                                     (16, 128, 128)),
    "last-tile-partly-past-the-rows": (70, 128, 128, [3, 30, 7, 30],
                                       (16, 128, 128)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_equals_ragged_dot_up_to_the_counts(case, dtype):
    m, k, n, counts, tiles = CASES[case]
    e = len(counts)
    kx, kw = jax.random.split(jax.random.PRNGKey(len(case)))
    xs = jax.random.normal(kx, (m, k), dtype)
    w = jax.random.normal(kw, (e, k, n), dtype) * k ** -0.5
    counts = jnp.asarray(counts, jnp.int32)
    live = int(counts.sum())
    # the tail may hold anything on the way in
    xs = xs.at[live:].set(jnp.nan)

    got = gmm.grouped_matmul(xs, w, counts, tiles=tiles, interpret=True)
    want = jax.lax.ragged_dot(xs, w, counts)

    assert got.shape == (m, n) and got.dtype == dtype
    # float32 accumulation and one rounding: a bfloat16 result is the
    # float32 one rounded, to within an ulp where the sums' orders differ
    tol = dict(atol=1e-5, rtol=1e-5) if dtype == jnp.float32 else \
        dict(atol=2 ** -6, rtol=2 ** -7)
    np.testing.assert_allclose(np.asarray(got[:live], np.float32),
                               np.asarray(want[:live], np.float32), **tol)
    assert np.isfinite(np.asarray(got[:live], np.float32)).all()


@pytest.mark.parametrize("m,e,k,n,want", [
    # the three regimes of the served cells (docs/kernels.md)
    (32768, 256, 2048, 768, (128, 2048, 768)),     # docqa chunk: whole experts
    (32768, 256, 768, 2048, (128, 768, 2048)),
    (256, 256, 2048, 768, (16, 2048, 768)),        # docqa decode
    (8, 256, 768, 2048, (8, 768, 2048)),           # one row: one tile of all
])
def test_the_tiles_follow_the_group_from_static_shapes(m, e, k, n, want):
    assert gmm.tiling(m, e, k, n, jnp.bfloat16) == want
    tm, tk, tn = want
    assert k % tk == 0 and (tn == n or tn % 128 == 0)


@pytest.mark.parametrize("m,e,k,n", [(4096, 8, 4096, 14336),
                                     (4096, 8, 14336, 4096),
                                     (64, 8, 4096, 14336)])
def test_a_matrix_too_large_for_vmem_is_cut_into_blocks_under_tall_tiles(
        m, e, k, n):
    tm, tk, tn = gmm.tiling(m, e, k, n, jnp.bfloat16)
    assert tm == min(512, m)       # every visit reads the blocks again
    assert k % tk == 0 and n % tn == 0 and tk % 128 == 0 and tn % 128 == 0
    # double-buffered blocks, and a float32 accumulator where the
    # contraction is cut
    assert 2 * 2 * (tm * tk + tk * tn + tm * tn) + \
        (4 * tm * tn if tk < k else 0) <= gmm._VMEM_BLOCK_BYTES
    # two stacks of weights in one pass (gate and up) get narrower blocks
    tm2, tk2, tn2 = gmm.tiling(m, e, k, n, jnp.bfloat16, weights=2)
    assert (tm2, tk2) == (tm, tk) and tn2 <= tn and n % tn2 == 0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", [
    "every-group-some-rows", "empty-groups", "no-rows-at-all",
    "counts-end-inside-a-tile", "fewer-rows-than-m", "both-in-blocks",
    "runs-of-a-tall-tile", "runs-of-a-tall-tile-whole-matrix",
    "last-tile-partly-past-the-rows"])
def test_gate_and_up_in_one_pass_equal_two_matmuls_and_their_product(
        case, dtype):
    m, k, n, counts, tiles = CASES[case]
    e = len(counts)
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    xs = jax.random.normal(keys[0], (m, k), dtype)
    w_gate = jax.random.normal(keys[1], (e, k, n), dtype) * k ** -0.5
    w_up = jax.random.normal(keys[2], (e, k, n), dtype) * k ** -0.5
    counts = jnp.asarray(counts, jnp.int32)
    live = int(counts.sum())
    xs = xs.at[live:].set(jnp.nan)

    got = gmm.grouped_gate_up(xs, w_gate, w_up, counts, tiles=tiles,
                              interpret=True)
    want = jax.nn.silu(jax.lax.ragged_dot(
        xs, w_gate, counts, preferred_element_type=jnp.float32)) * \
        jax.lax.ragged_dot(xs, w_up, counts,
                           preferred_element_type=jnp.float32)

    assert got.shape == (m, n) and got.dtype == dtype
    # one rounding of the float32 product
    tol = dict(atol=1e-5, rtol=1e-5) if dtype == jnp.float32 else \
        dict(atol=2 ** -7, rtol=2 ** -7)
    np.testing.assert_allclose(np.asarray(got[:live], np.float32),
                               np.asarray(want[:live], np.float32), **tol)


@pytest.mark.parametrize("counts,tm,groups,tiles", [
    ([3, 17, 21, 23], 16, [0, 1, 1, 2, 2, 3, 3], [0, 0, 1, 1, 2, 2, 3]),
    ([0, 34, 0, 30], 16, [1, 1, 1, 3, 3], [0, 1, 2, 2, 3]),
    ([0, 0, 0, 0], 16, [], []),
    ([5, 0, 9, 7], 16, [0, 2, 3, 3], [0, 0, 0, 1]),
])
def test_a_visit_for_each_tile_and_group_with_rows(counts, tm, groups, tiles):
    m = 64
    offsets, group, tile, live = gmm._visits(jnp.asarray(counts, jnp.int32),
                                             m, tm)
    n = int(live[0])
    assert group.shape == tile.shape == (gmm.max_visits(m, len(counts), tm),)
    assert offsets.tolist() == np.concatenate([[0], np.cumsum(counts)]).tolist()
    assert group[:n].tolist() == groups and tile[:n].tolist() == tiles
    # the visits past the live ones name the last live one's blocks
    assert set(group[n:].tolist()) <= {groups[-1] if groups else len(counts) - 1}
    assert set(tile[n:].tolist()) <= {tiles[-1] if tiles else 0}
    assert int(gmm.visited_tile_rows(jnp.asarray(counts, jnp.int32), m, tm)) \
        == n * tm >= sum(counts)


@pytest.mark.parametrize("fused", [False, True],
                         ids=["three-matmuls", "gate-and-up-in-one"])
@pytest.mark.parametrize("masked", [False, True],
                         ids=["all-rows", "rows-left-out"])
@pytest.mark.parametrize("t", [1, 2, 8, 9, 33, 512, 513])
def test_grouped_expert_ffn_with_the_kernel_equals_it_with_ragged_dot(
        t, masked, fused):
    """The rows counts of ``test_softmax_grouped_moe.py``'s test of the two
    forms ``_softmax_moe`` chose between until the kernel made the second
    needless (1, 2, 8 | 9, 33, 512 | 513), now over the two matmuls."""
    e, k, d, f = 8, 2, 16, 24
    keys = jax.random.split(jax.random.PRNGKey(t), 5)
    h = jax.random.normal(keys[0], (t, d))
    experts = {"w_gate": jax.random.normal(keys[2], (e, d, f)) * 0.3,
               "w_up": jax.random.normal(keys[3], (e, d, f)) * 0.3,
               "w_down": jax.random.normal(keys[4], (e, f, d)) * 0.3}
    weights, ids = softmax_route(h, jax.random.normal(keys[1], (d, e)), k, True)
    valid = jnp.arange(t) % 4 != 1 if masked else None

    want, want_rows = grouped_expert_ffn(h, experts, weights, ids, valid)
    got, rows = grouped_expert_ffn(
        h, experts, weights, ids, valid,
        matmul=functools.partial(gmm.grouped_matmul, interpret=True),
        gate_up=functools.partial(gmm.grouped_gate_up, interpret=True)
        if fused else None)

    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_array_equal(rows, want_rows)
    if masked:
        assert not np.asarray(got)[~np.asarray(valid)].any()   # zeros
