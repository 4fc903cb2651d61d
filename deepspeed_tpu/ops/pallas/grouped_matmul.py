"""Pallas grouped matmul: sorted rows against stacked expert weights.

Reference analog: ``deepspeed/inference/v2/kernels/cutlass_ops/moe_gemm`` (one
grouped GEMM over rows sorted by expert). ``grouped_matmul(xs, w, counts)`` has
the semantics of ``jax.lax.ragged_dot(xs, w, counts)``: ``xs`` [M, K] holds the
rows of group 0, then of group 1, ...; ``w`` is [E, K, N]; ``counts`` [E] int32
says how many rows each group has, ``sum(counts) <= M``. Row ``r`` of group
``g`` comes out as ``xs[r] @ w[g]``, accumulated in float32 and rounded once
to the rows' type. The rows past ``sum(counts)`` (bucket padding) may hold
anything on the way in and hold anything on the way out: the caller zeroes
them (``moe/grouped_experts.py``).

TPU design, after megablox's ``gmm``: the rows are cut into tiles of ``tm``
and the kernel makes one *visit* for every (row tile, group) pair that has
rows, so a tile that straddles a boundary is visited once a group and the
other groups' rows are masked at the store. Which tile and which group a
visit takes is computed from ``counts`` in a few jnp operations
(``_visits``) and handed in by scalar prefetch
(``pltpu.PrefetchScalarGridSpec``), so the BlockSpec index maps dereference
it and the kernel reads the rows' tile and the group's weight block straight
from HBM: no padded copy of the rows, and an expert nobody chose is never
read. The grid is static, (column blocks, ``ceil(M / tm) + min(E, M) - 1``
visits at most, contraction blocks), with the column blocks outermost so that
a tile's visits follow each other and its output block is written back once.
Visits past the live ones are clamped to the last live one (their index maps
name the blocks already there, so nothing is fetched) and skip the compute.
Blocks that do not change between consecutive steps are not fetched again, so
with a whole weight matrix a block every expert is read exactly once whatever
``tm``.

The tiles follow the group and are chosen from static shapes alone
(``tiling``). Where two whole ``[K, N]`` matrices fit ``_VMEM_BLOCK_BYTES``
beside the rows and the output (JoyAI's 3.1 MB experts), a group's weight
block is its matrix, read once, and ``tm`` is the mean rows a group ``M / E``
rounded up to the sublane multiple: a taller tile would multiply more of its
neighbours' rows at every boundary, a shorter one pay more steps. Where they do
not (Mixtral's 117 MB experts) the matrix is cut into ``[tk, tn]`` blocks and
every visit reads the group's blocks again, so the visits are what costs and
``tm`` is the tallest tile, ``_MAX_TILE_ROWS``. A tile taller than
``_SUB_ROWS`` is multiplied in runs of that many rows, and a run without a row
of the visit's group is skipped, so a boundary costs the multiplications of
one run and not of the tile.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the tallest row tile: 512 rows against a [tk, tn] weight block are 512
#: operations a weight byte, twice what a v5e needs to be compute-bound
_MAX_TILE_ROWS = 512
#: rows of one run of a taller tile (an MXU pass of a v5e is 128 rows)
_SUB_ROWS = 128
#: what the double-buffered blocks of one grid step may take of VMEM (a v5e
#: has 128 MiB; the compiler keeps the matmul's float32 result beside them)
_VMEM_BLOCK_BYTES = 40 << 20
_VMEM_LIMIT_BYTES = 100 << 20
#: widest block of a weight matrix too large to be one
_MAX_BLOCK_K = 2048
_MAX_BLOCK_N = 2048


def _largest_block(dim: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``cap``; ``dim`` itself where it is no larger or nothing divides it."""
    if dim <= cap:
        return dim
    for b in range(cap - cap % 128, 0, -128):
        if dim % b == 0:
            return b
    return dim


def tiling(m: int, e: int, k: int, n: int, dtype, weights: int = 1):
    """(tm, tk, tn) for ``[m, k]`` rows on ``weights`` stacks of ``[e, k,
    n]`` weights, from the static shapes alone (module docstring). ``tm`` is
    at most ``m``: one tile of every row."""
    size = jnp.dtype(dtype).itemsize
    sublane = 8 * max(1, 4 // size)

    def fits(tm, tk, tn):
        return (2 * size * (tm * tk + weights * tk * tn + tm * tn)
                + (4 * weights * tm * tn if tk < k else 0)
                ) <= _VMEM_BLOCK_BYTES

    tm = min(pl.cdiv(pl.cdiv(m, e), sublane) * sublane, _MAX_TILE_ROWS, m)
    if fits(tm, k, n):
        return tm, k, n
    tm = min(_MAX_TILE_ROWS, m)
    # a tile of whole rows stays where it is while the columns change and
    # needs no accumulator: taken where it leaves the weights half the room
    tk = k if 4 * size * tm * k <= _VMEM_BLOCK_BYTES else \
        _largest_block(k, _MAX_BLOCK_K)
    tn = _largest_block(n, _MAX_BLOCK_N)
    while not fits(tm, tk, tn) and tn > 128:
        tn = _largest_block(n, tn - 128)
    return tm, tk, tn


def max_visits(m: int, e: int, tm: int) -> int:
    """The grid's visits: every row tile once, and once more for each group
    that can start inside one."""
    return pl.cdiv(m, tm) + min(e, m) - 1


@functools.partial(jax.jit, static_argnames=("m", "tm"))
def _visits(counts, m: int, tm: int):
    """(offsets [E + 1], group [V], tile [V], live [1]) int32 for the
    kernel's ``V = max_visits`` visits: the row each group starts at, the
    group and the row tile of each visit in the order the kernel makes them,
    and how many visits have rows. Visits from ``live`` on repeat the last
    live one."""
    e = counts.shape[0]
    ends = jnp.cumsum(counts)
    first = (ends - counts) // tm
    tiles = jnp.where(counts > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(tiles)
    live = visit_ends[-1]
    v = jnp.minimum(jnp.arange(max_visits(m, e, tm), dtype=jnp.int32),
                    jnp.maximum(live - 1, 0))
    group = jnp.minimum(
        jnp.searchsorted(visit_ends, v, side="right", method="compare_all"),
        e - 1).astype(jnp.int32)
    tile = first[group] + v - (visit_ends - tiles)[group]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets.astype(jnp.int32), group, tile.astype(jnp.int32),
            live.reshape(1).astype(jnp.int32))


def visited_tile_rows(counts, m: int, tm: int):
    """Rows of the tiles the kernel visits for these ``counts``: visits with
    rows x ``tm``. ``sum(counts)`` over it is the tiles' fill."""
    return _visits(counts, m, tm)[3][0] * tm


def _kernel(offsets_ref, group_ref, tile_ref, live_ref, x_ref, *refs,
            tm: int, blocks_k: int, weights: int, epilogue, rows_out: bool):
    w_refs, o_ref, acc_refs = refs[:weights], refs[weights], refs[weights + 1:]
    v, ki = pl.program_id(1), pl.program_id(2)
    sub = _SUB_ROWS if tm % _SUB_ROWS == 0 else tm
    g = group_ref[v]
    lo, hi = offsets_ref[g], offsets_ref[g + 1]

    def _run(j, _=None):
        """Rows [j * sub, (j + 1) * sub) of the tile against the blocks, if
        the visit has rows at all and one of these is the group's."""
        rows = pl.ds(pl.multiple_of(j * sub, sub), sub)
        first = tile_ref[v] * tm + j * sub

        def _store(*accs):
            out = epilogue(*accs)
            at = first + jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
            o_ref[rows, :] = jnp.where(
                (at >= lo) & (at < hi), out,
                o_ref[rows, :].astype(jnp.float32)).astype(o_ref.dtype)

        @pl.when((v < live_ref[0]) & (first < hi) & (first + sub > lo))
        def _some_row_is_the_groups():
            x = x_ref[rows, :]
            # a weight block is [tk, tn], or with ``rows_out`` [tn, tk]: the
            # contraction over both operands' minor axis, which the MXU does
            # as it does the other
            contract = (((1,), (1 if rows_out else 0,)), ((), ()))
            parts = [jax.lax.dot_general(x, w_ref[...], contract,
                                         preferred_element_type=jnp.float32)
                     for w_ref in w_refs]
            if blocks_k == 1:
                _store(*parts)
                return
            # a select and not a branch a step of the contraction: every
            # branch is traced and lowered anew for every step program
            for acc_ref, part in zip(acc_refs, parts):
                acc_ref[rows, :] = jnp.where(ki == 0, part,
                                             acc_ref[rows, :] + part)
            pl.when(ki == blocks_k - 1)(
                lambda: _store(*(acc_ref[rows, :] for acc_ref in acc_refs)))

    if tm == sub:
        _run(0)
    else:           # one traced body for every run: a program holds dozens
        jax.lax.fori_loop(0, tm // sub, _run, None)


def _call(xs, ws, counts, epilogue, name, tiles, interpret,
          rows_out: bool = False):
    """``epilogue`` of the float32 products of the sorted rows ``xs`` with
    each stack of ``ws``, a group at a time, in one pass over the rows.
    ``rows_out``: the stacks are ``[E, N, K]``, a row an output column."""
    m, k = xs.shape
    e, n = ws[0].shape[0], ws[0].shape[1 if rows_out else 2]
    tm, tk, tn = tiles or tiling(m, e, k, n, xs.dtype, len(ws))
    if k % tk:
        raise ValueError(f"a contraction block of {tk} does not divide {k}")
    blocks_k, blocks_n = k // tk, pl.cdiv(n, tn)
    meta = _visits(counts.astype(jnp.int32), m, tm)

    def k_block(v, ki, live_ref):
        # a visit without rows keeps the blocks of the step before it
        return jnp.where(v < live_ref[0], ki, blocks_k - 1)

    if rows_out:
        w_spec = pl.BlockSpec((None, tn, tk),
                              lambda ni, v, ki, off, grp, til, live:
                              (grp[v], ni, k_block(v, ki, live)))
    else:
        w_spec = pl.BlockSpec((None, tk, tn),
                              lambda ni, v, ki, off, grp, til, live:
                              (grp[v], k_block(v, ki, live), ni))
    size = xs.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, blocks_k=blocks_k,
                          weights=len(ws), epilogue=epilogue,
                          rows_out=rows_out),
        out_shape=jax.ShapeDtypeStruct((m, n), xs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(blocks_n, max_visits(m, e, tm), blocks_k),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda ni, v, ki, off, grp, til, live:
                             (til[v], k_block(v, ki, live))),
            ] + [w_spec] * len(ws),
            out_specs=pl.BlockSpec(
                (tm, tn), lambda ni, v, ki, off, grp, til, live:
                (til[v], ni)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] * len(ws)
            if blocks_k > 1 else [],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n * len(ws), transcendentals=0,
            bytes_accessed=size * (blocks_n * m * k + len(ws) * e * k * n
                                   + m * n)),
        interpret=interpret,
        name=name,
    )(*meta, xs, *(w.astype(xs.dtype) for w in ws))


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def grouped_matmul(xs, w, counts, *, tiles=None, interpret: bool = False):
    """``jax.lax.ragged_dot(xs, w, counts)`` for ``xs`` [M, K] sorted by
    group, ``w`` [E, K, N] and ``counts`` [E] int32 with ``sum(counts) <=
    M``; the rows past the sum are not defined (module docstring). ``tiles``
    overrides ``tiling``'s (tm, tk, tn): ``tk`` has to divide ``K``."""
    return _call(xs, (w,), counts, lambda acc: acc, "grouped_matmul", tiles,
                 interpret)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def grouped_gate_up(xs, w_gate, w_up, counts, *, tiles=None,
                    interpret: bool = False):
    """``silu(xs @ w_gate[g]) * (xs @ w_up[g])`` a group, as two
    ``grouped_matmul``s and the product would give it, in one pass over the
    rows: a tile of rows is read once for both weights, and the two float32
    products meet in VMEM and leave as one ``[M, N]`` array rounded once
    (the two alone are rounded each, then multiplied)."""
    return _call(xs, (w_gate, w_up), counts,
                 lambda gate, up: jax.nn.silu(gate) * up,
                 "grouped_matmul_gate_up", tiles, interpret)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def grouped_relu2_in(xs, w_in, counts, *, tiles=None, interpret: bool = False):
    """``relu(xs @ w_in[g].T) ** 2`` a group: an UNGATED expert's first
    product and its activation (``mlp_hidden_act: relu2``), the float32
    product squared in VMEM and rounded once. ``w_in`` is ``[E, N, K]``, a
    row a hidden unit (``moe/grouped_experts.py`` says why), so the kernel
    contracts the minor axis of both operands; ``grouped_matmul``'s body
    under another epilogue otherwise. Where the expert's width ``N`` is no
    multiple of 128 (Nemotron-H's 1,856) and a whole matrix is the block
    (``tiling``), ``N`` is a full dimension of every block it is in."""
    return _call(xs, (w_in,), counts,
                 lambda acc: jnp.square(jnp.maximum(acc, 0.0)),
                 "grouped_matmul_relu2_in", tiles, interpret, rows_out=True)
