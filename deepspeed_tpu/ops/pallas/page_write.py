"""A chunk's rows into a paged pool one page at a time.

A paged pool is ``[G, NB, bs, W]`` behind whatever leading axes its kind
gives it (``inference/v2/kv_cache.py``: ``[L, 2, H_kv, NB, bs, D]`` seen as
``[L * 2 * H_kv, ...]``, a split kind's ``[L, H_kv, NB, bs, W]`` as ``[L *
H_kv, ...]``, a latent pool ``[L, NB, bs, W]`` as it is; merging leading axes
moves nothing, the tiled axes are the last two), and one page of one group is
a ``[bs, W]`` tile contiguous in memory. A chunk of ``T`` consecutive tokens
of one sequence from position ``start`` lies in ``page_count(T, bs)``
consecutive entries of its block table, so its rows go in as that many tiles
a group and not as ``T`` rows a group: XLA's scatter of one row an update
moved 512 bytes an update at 82 ns each (6 GB/s of the chip's 819: PERF.md
section 7, PR 54). Alone on a v5e, 4,096 rows x 8 heads of 256 lanes: the row
scatter 2.76 ms, this kernel with its shift 0.27, the ``jax.numpy`` form
below 0.34 (PERF.md section 6, PR 55).

``frames`` lays the rows out as the pages will hold them, shifted by the
chunk's offset in its first page (one pass of XLA's); ``write_pages`` puts
the frames' tiles where ``pages`` says and keeps, in the first and the last
page, the rows the chunk does not own: a tile's rows outside ``[lo, hi)`` of
the frames' row count are the pool's own, read through the aliased operand.
Everything that is arithmetic on the table or the position is done outside
the kernel (``page_bounds``), which sees two bounds a page.

``write_pages_reference`` is the same in plain ``jax.numpy`` (a gather of the
pages' tiles, the merge, XLA's scatter with a ``[bs, W]`` window), what a
backend without the kernel runs and what the kernel is tested against.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def page_count(rows: int, block_size: int) -> int:
    """Pages that ``rows`` consecutive positions can span wherever the first
    falls in its page (a chunk shorter than a page that crosses a boundary
    still touches two)."""
    return (rows + block_size - 2) // block_size + 1


def frames(rows, lo, block_size: int):
    """``rows`` [T, n, W] as the pages will hold them: [n, P * bs, W] with
    row ``t`` at ``lo + t`` (``lo`` = the first position's offset in its
    page, traced) and zeros around."""
    t, n, w = rows.shape
    out = jnp.zeros((n, page_count(t, block_size) * block_size, w),
                    rows.dtype)
    return jax.lax.dynamic_update_slice(out, rows.transpose(1, 0, 2),
                                        (0, lo, 0))


def page_bounds(lo, hi, pages: int, block_size: int):
    """Each page's own rows ``[lo_j, hi_j)`` of ``block_size``, from the
    chunk's ``[lo, hi)`` in frame rows: [pages] int32 each."""
    first = jnp.arange(pages, dtype=jnp.int32) * block_size
    return (jnp.clip(lo - first, 0, block_size).astype(jnp.int32),
            jnp.clip(hi - first, 0, block_size).astype(jnp.int32))


def _kernel(pages_ref, group_ref, lo_ref, hi_ref, new_ref, old_ref, out_ref):
    del pages_ref, group_ref
    j = pl.program_id(0)
    row = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    mine = (row >= lo_ref[j]) & (row < hi_ref[j])
    out_ref[...] = jnp.where(mine, new_ref[...], old_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def write_pages(pool, group, new, pages, lo, hi, interpret: bool = False):
    """``pool`` [G, NB, bs, W] with the tiles of ``new`` [n, P * bs, W]
    written to blocks ``pages`` [P] of groups ``group * n .. + n``, rows
    ``[lo, hi)`` of the frames alone; in place where the pool is donated.
    Pages named twice (the trash block, for the pages that are all padding)
    end with one of their writes."""
    n, rows, w = new.shape
    bs = pool.shape[2]
    p = rows // bs
    lo_j, hi_j = page_bounds(lo, hi, p, bs)
    tile = pl.BlockSpec((n, None, bs, w),
                        lambda j, pages, group, lo, hi:
                        (group[0], pages[j], 0, 0))
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(p,),
            in_specs=[pl.BlockSpec((n, bs, w),
                                   lambda j, pages, group, lo, hi: (0, j, 0)),
                      tile],
            out_specs=tile),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # operand 5 (after the four prefetched scalars and the frames) is the
        # pool: written where the index map says, left alone elsewhere
        input_output_aliases={5: 0},
        # a page named twice is written in the grid's order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="kv_page_write",
    )(pages.astype(jnp.int32), jnp.asarray(group, jnp.int32).reshape(1),
      lo_j, hi_j, new.astype(pool.dtype), pool)


@jax.jit
def write_pages_reference(pool, group, new, pages, lo, hi):
    """``write_pages`` in plain ``jax.numpy``."""
    n, rows, w = new.shape
    bs = pool.shape[2]
    p = rows // bs
    groups = (group * n + jnp.arange(n))[:, None]
    row = jnp.arange(rows).reshape(p, bs)
    mine = ((row >= lo) & (row < hi))[None, :, :, None]
    tiles = jnp.where(mine, new.astype(pool.dtype).reshape(n, p, bs, w),
                      pool[groups, pages[None, :]])
    return pool.at[groups, pages[None, :]].set(tiles)
