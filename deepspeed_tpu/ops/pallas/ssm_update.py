"""One decode step of a selective state-space layer (Mamba-2) over states
kept in slots: ``S <- a S + (dt x) (x) B``, ``y = S C``, each live slot's
state read once and written once, in place.

A pool of states is ``[L, slots, G, N, W]`` float32: layer, slot, a group of
``pack`` heads, the state's ``d_state`` rows, and ``W = pack * head_dim``
lanes that hold the group's heads side by side (``state_pack``: two heads of
64 fill the TPU's 128 lanes). With the heads' values in the lanes and the
state's rows in the sublanes, everything a token brings is a broadcast the
vector unit has (``a`` and ``dt x`` one value a lane, down the sublanes; ``B``
and ``C`` one value a sublane, across the lanes) and ``y`` is a sum down the
sublanes, plain adds of whole registers. The other way round (``d_state`` in
the lanes) every head's ``y`` is a reduction ACROSS lanes, a rotate-and-add
ladder a register, and the kernel is bound by that and not by the bytes.

``B`` and ``C`` come ``[B, groups, N]``: the heads of a pack read one group's
(``state_pack`` packs no heads of two groups), packs ``g * packs / groups ..``
group ``g``'s, one group where a model has one.

``ssm_update`` is the Pallas kernel: the rows' slots and the layer arrive by
scalar prefetch (as ``paged_attention.py`` takes its tables), the pool whole
with ``input_output_aliases`` so that only the tiles of the rows' slots move,
a grid over (row, tile of groups). ``ssm_update_reference`` is the same step
in plain ``jax.numpy`` (gather the rows' states, update, scatter): the numerics
oracle of the kernel's tests, and the path where there is no TPU. Rows that
are batch padding name the pool's last slot, which no sequence holds.

``ssm_conv_step`` is the step before it, the same way: each row's convolution
tail (the last ``K - 1`` rows of the layer's convolution input, in the cache's
type) read, shifted by the row's token and written, by copies the kernel
issues itself out of and into the rows' slots of a pool that stays in HBM
whole; ``ssm_conv_step_reference`` is its oracle. What follows the taps' sum
is the caller's to say (``activation``: ``"silu"``, a Mamba-2 layer's, or
None, a gated short convolution's, whose slot is such a tail and nothing
else), so the same kernel moves the tails of both.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.ssm import ACTIVATIONS

LANES = 128
#: groups of heads a grid step updates: [GROUP_TILE, d_state, W] float32 is
#: 1 MiB at 16 x 128 x 128, in and out and double-buffered 4 MiB of VMEM
GROUP_TILE = 16


def state_pack(heads: int, head_dim: int, groups: int = 1) -> int:
    """Heads whose values lie side by side in a state's lanes: of one group
    of ``B`` and ``C``."""
    return math.gcd(heads // groups, max(LANES // head_dim, 1))


def pack_state(state, pack: int):
    """[..., H, P, N] as the pool stores it, [..., H / pack, N, pack * P]."""
    *lead, h, p, n = state.shape
    s = state.reshape(*lead, h // pack, pack, p, n)
    return jnp.moveaxis(s, -1, -3).reshape(*lead, h // pack, n, pack * p)


def unpack_state(stored, pack: int):
    """The inverse of ``pack_state``: [..., G, N, pack * P] -> [..., H, P, N]."""
    *lead, g, n, w = stored.shape
    s = stored.reshape(*lead, g, n, pack, w // pack)
    return jnp.moveaxis(s, -3, -1).reshape(*lead, g * pack, w // pack, n)


def _lanes_of(v, pack: int, head_dim: int):
    """[B, H] a value a head -> [B, G, W], the value in each of the head's
    lanes."""
    b, h = v.shape
    return jnp.repeat(v.reshape(b, h // pack, pack), head_dim, axis=-1)


def _operands(x, dt, a_log, pack: int):
    """What both paths feed on, float32: ``a`` [B, G, W] (``exp(dt A)`` in
    each of its head's lanes) and ``dt x`` [B, G, W]."""
    b, h, p = x.shape
    dt = dt.astype(jnp.float32)
    decay = jnp.exp(dt * -jnp.exp(a_log.astype(jnp.float32)))
    xdt = x.astype(jnp.float32) * dt[..., None]
    return (_lanes_of(decay, pack, p),
            xdt.reshape(b, h // pack, pack * p))


def ssm_update_reference(pool, layer: int, slots, x, dt, a_log, bm, cm):
    """pool: [L, slots, G, N, W] float32; slots: [B] int32; x: [B, H, P];
    dt: [B, H] (after softplus); a_log: [H]; bm, cm: [B, groups, N]. Returns
    (y [B, H, P] float32 without the skip term, the pool)."""
    b, h, p = x.shape
    packs = pool.shape[2]
    a, xdt = _operands(x, dt, a_log, h // packs)
    # each pack's own group's B and C, down the state's rows: [B, G, N, 1]
    bm, cm = (jnp.repeat(v.astype(jnp.float32), packs // v.shape[1],
                         axis=1)[..., None] for v in (bm, cm))
    s = pool[layer, slots]                                    # [B, G, N, W]
    s = a[:, :, None, :] * s + bm * xdt[:, :, None, :]
    y = jnp.sum(s * cm, axis=2)
    return y.reshape(b, h, p), pool.at[layer, slots].set(s)


def _column(row):
    """[1, N] -> [N, 1]: the row's values down the sublanes. One masked
    lane-reduction of an [N, N] tile a grid step, in the place of a relayout."""
    n = row.shape[-1]
    eye = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) == \
        jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _update_kernel(slots_ref, layer_ref, a_ref, xdt_ref, b_ref, c_ref, s_ref,
                   y_ref, s_out_ref, *, groups: int):
    del slots_ref, layer_ref                  # the index maps read them
    # the tile's packs in runs of one group of B and C each
    run = groups // b_ref.shape[1]
    for g in range(groups):
        if g % run == 0:
            bcol = _column(b_ref[0, g // run])                # [N, 1]
            ccol = _column(c_ref[0, g // run])
        s = a_ref[0, g:g + 1, :] * s_ref[0, 0, g] \
            + bcol * xdt_ref[0, g:g + 1, :]                   # [N, W]
        s_out_ref[0, 0, g] = s.astype(s_out_ref.dtype)
        y_ref[0, g:g + 1, :] = jnp.sum(s * ccol, axis=0, keepdims=True)


def ssm_update(pool, layer: int, slots, x, dt, a_log, bm, cm,
               interpret: bool = False):
    """``ssm_update_reference`` as one Pallas call that moves the tiles of
    the rows' slots alone and leaves the rest of the pool where it is."""
    b, h, p = x.shape
    pack = h // pool.shape[2]
    a, xdt = _operands(x, dt, a_log, pack)
    y, pool = _update_call(
        slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
        a, xdt, bm.astype(jnp.float32)[:, :, None, :],
        cm.astype(jnp.float32)[:, :, None, :], pool, interpret=interpret)
    return y.reshape(b, h, p), pool


@functools.partial(jax.jit, static_argnames=("interpret",))
def _update_call(slots, layer, a, xdt, bm, cm, pool, *, interpret: bool):
    """The kernel call, under a ``jit`` of its own with the layer a value:
    a step program traces and lowers it once and calls it once a layer."""
    b, groups, w = a.shape
    n = pool.shape[3]
    tile = GROUP_TILE if groups % GROUP_TILE == 0 else groups
    # a tile of packs reads whole groups of B and C (``[B, G, 1, N]``), or
    # several tiles one: the packs of a group follow each other
    run = groups // bm.shape[1]
    if tile % run and run % tile:
        tile = groups
    held, tiles = max(tile // run, 1), max(run // tile, 1)
    row = lambda i, t, slots, layer: (i, t, 0)
    vec = lambda i, t, slots, layer: (i, t // tiles, 0, 0)
    state = lambda i, t, slots, layer: (layer[0], slots[i], t, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b, groups // tile),
        in_specs=[pl.BlockSpec((1, tile, w), row),
                  pl.BlockSpec((1, tile, w), row),
                  pl.BlockSpec((1, held, 1, n), vec),
                  pl.BlockSpec((1, held, 1, n), vec),
                  pl.BlockSpec((1, 1, tile, n, w), state)],
        out_specs=[pl.BlockSpec((1, tile, w), row),
                   pl.BlockSpec((1, 1, tile, n, w), state)])
    return pl.pallas_call(
        functools.partial(_update_kernel, groups=tile),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, groups, w), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 6 (after the two prefetched scalars) is the pool
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssm_update",
    )(slots, layer, a, xdt, bm, cm, pool)


# --- the convolution's tail, one decode step -----------------------------------
# A slot's tail is the last ``K - 1`` rows of the layer's convolution input,
# ``[K - 1, C]`` in the cache's type, stored ``[rows, 128]`` (``tail_stored``):
# the same values in the same order, laid so that a slot is a block of whole
# tiles that a copy can name. One row of a ``[slots, (K - 1) * C]`` array is a
# sublane of every tile it crosses, which XLA gathers and scatters a row at a
# time: 100 us a layer a tick where the bytes take 5 (my chip run, PR 44).

def tail_stored(width: int, channels: int):
    """A slot's tail as the pool stores it: [rows, lanes], the ``(K - 1) * C``
    values in their order over 128 lanes and zero rows up to a whole tile of
    8 (a copy names whole tiles: 102 rows are 104 at granite's 3 x 4,352,
    Nemotron-H's 3 x 6,144 are 144 as they are, and a short convolution's
    2 x 2,048 are 32, 16 a tap); at toy widths, where the channels fill no
    lanes, one row a tap."""
    if channels % LANES:
        return (width - 1, channels)
    return (-(-(width - 1) * channels // LANES // 8) * 8, LANES)


def pack_tail(tail, stored):
    """[..., K - 1, C] as the pool stores it (``tail_stored``)."""
    rows, lanes = stored
    lead = tail.shape[:-2]
    flat = tail.reshape(lead + (-1,))
    zeros = rows * lanes - flat.shape[-1]
    return jnp.pad(flat, ((0, 0),) * len(lead) + ((0, zeros),)).reshape(
        lead + stored)


def unpack_tail(stored, width: int, channels: int):
    """The inverse of ``pack_tail``: [..., rows, lanes] -> [..., K - 1, C]."""
    lead = stored.shape[:-2]
    return stored.reshape(lead + (-1,))[..., :(width - 1) * channels].reshape(
        lead + (width - 1, channels))


def ssm_conv_step_reference(pool, layer, slots, xbc, taps, bias,
                            activation="silu"):
    """pool: [L, slots, rows, lanes] (``tail_stored``); slots: [B] int32;
    xbc: [B, C]; taps: [C, K]; bias: [C]. Each row's tail shifted by its
    token: returns (the convolved rows [B, C] in ``xbc``'s type, after
    ``activation``, ``"silu"`` or None; the pool). Tap by tap in float32, as
    ``ssm.causal_conv`` sums a chunk's."""
    c, width = taps.shape
    used = (width - 1) * c // pool.shape[-1]     # the rest are a tile's zeros
    tails = unpack_tail(pool[layer, slots], width, c)
    rows = jnp.concatenate([tails, xbc[:, None]], axis=1)
    w = taps.astype(jnp.float32)
    conv = ACTIVATIONS[activation](sum(
        rows[:, j].astype(jnp.float32) * w[:, j] for j in range(width))
        + bias.astype(jnp.float32)).astype(xbc.dtype)
    return conv, pool.at[layer, slots, :used].set(
        rows[:, 1:].reshape(-1, used, pool.shape[-1]).astype(pool.dtype))


#: rows whose tails one grid step copies in, shifts and copies out
CONV_ROWS = 16


def _conv_kernel(slots_ref, layer_ref, x_ref, w_ref, bias_ref, pool_ref,
                 y_ref, pool_out_ref, held, shifted, sems, *, rows: int,
                 width: int, activation):
    """Step ``i`` of the grid: the tails of rows ``i * rows ..`` are in
    ``held[i % 2]`` (copied there while step ``i - 1`` computed), the step's
    new tails go from ``shifted[i % 2]`` while step ``i + 1`` computes."""
    i, n = pl.program_id(0), pl.num_programs(0)
    buf = i % 2
    cr = x_ref.shape[1]                    # a tap's rows of the stored tail

    def copies(do, step, buf, into_pool: bool):
        """Start (or wait for) the copies of step ``step``'s rows: their
        slots' tails into ``held[buf]``, or ``shifted[buf]`` into the slots."""
        def one(r, base):
            pool = pool_out_ref if into_pool else pool_ref
            slot = pool.at[layer_ref[0], slots_ref[base + r]]
            do(pltpu.make_async_copy(shifted.at[buf, r], slot, sems.at[1, buf])
               if into_pool else
               pltpu.make_async_copy(slot, held.at[buf, r], sems.at[0, buf]))
            return base
        jax.lax.fori_loop(0, rows, one, step * rows)

    start, wait = (lambda c: c.start()), (lambda c: c.wait())

    @pl.when(i == 0)
    def _():
        copies(start, i, buf, False)

    @pl.when(i + 1 < n)
    def _():
        copies(start, i + 1, 1 - buf, False)

    copies(wait, i, buf, False)

    @pl.when(i >= 2)
    def _():                               # ``shifted[buf]`` is step i - 2's
        copies(wait, i - 2, buf, True)

    def one(r, carry):
        x = x_ref[r]
        acc = held[buf, r, 0:cr].astype(jnp.float32) * w_ref[0]
        for j in range(1, width - 1):
            acc = acc + held[buf, r, j * cr:(j + 1) * cr].astype(
                jnp.float32) * w_ref[j]
        acc = acc + x.astype(jnp.float32) * w_ref[width - 1] + bias_ref[...]
        y_ref[r] = ACTIVATIONS[activation](acc).astype(y_ref.dtype)
        if width > 2:
            shifted[buf, r, 0:(width - 2) * cr] = \
                held[buf, r, cr:(width - 1) * cr]
        shifted[buf, r, (width - 2) * cr:(width - 1) * cr] = x.astype(
            shifted.dtype)
        if shifted.shape[2] > (width - 1) * cr:     # the tile's zero rows
            shifted[buf, r, (width - 1) * cr:] = held[buf, r,
                                                      (width - 1) * cr:]
        return carry
    jax.lax.fori_loop(0, rows, one, 0)

    copies(start, i, buf, True)

    @pl.when(i == n - 1)
    def _():
        copies(wait, i, buf, True)

        @pl.when(i >= 1)
        def _():
            copies(wait, i - 1, 1 - buf, True)


def ssm_conv_step(pool, layer, slots, xbc, taps, bias, activation="silu",
                  interpret: bool = False):
    """``ssm_conv_step_reference`` as one Pallas call that copies the rows'
    slots' tails alone, in place: the pool stays in HBM whole."""
    b, c = xbc.shape
    lanes = pool.shape[-1]
    laid = lambda v: v.astype(jnp.float32).reshape(-1, c // lanes, lanes)
    conv, pool = _conv_call(
        slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
        xbc.reshape(b, c // lanes, lanes), laid(taps.T), laid(bias)[0], pool,
        activation=activation, interpret=interpret)
    return conv.reshape(b, c), pool


@functools.partial(jax.jit, static_argnames=("activation", "interpret"))
def _conv_call(slots, layer, x, taps, bias, pool, *, activation,
               interpret: bool):
    """The kernel call, under a ``jit`` of its own with the layer a value
    (``_update_call``)."""
    b, cr, lanes = x.shape
    width = taps.shape[0]
    rows = math.gcd(b, CONV_ROWS)
    block = pl.BlockSpec((rows, cr, lanes), lambda i, slots, layer: (i, 0, 0))
    whole = lambda *shape: pl.BlockSpec(
        shape, lambda i, slots, layer: (0,) * len(shape))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    tails = (2, rows) + pool.shape[2:]
    return pl.pallas_call(
        functools.partial(_conv_kernel, rows=rows, width=width,
                          activation=activation),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b // rows,),
            in_specs=[block, whole(width, cr, lanes), whole(cr, lanes),
                      in_hbm],
            out_specs=[block, in_hbm],
            scratch_shapes=[pltpu.VMEM(tails, pool.dtype),
                            pltpu.VMEM(tails, pool.dtype),
                            # [in, out] x the two buffers
                            pltpu.SemaphoreType.DMA((2, 2))]),
        # the pool is in HBM by name, in and out. Left to choose, XLA moves a
        # pool of up to tens of MB whole into VMEM before a step's first call
        # and back after its last (28 MB each way a tick at Nemotron's cell,
        # 3.4 MB around EVERY call at two layers: compiled for a v5e, PR 51).
        # A program has to donate the pool it hands in, as the step programs
        # do: around the copy it makes of one it keeps, this XLA's memory
        # space assignment fails a check and takes the process with it
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   pltpu.HBM(pool.shape, pool.dtype)],
        # operand 5 (after the two prefetched scalars) is the pool
        input_output_aliases={5: 1},
        # a step starts the copies of the step after it: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ssm_conv_step",
    )(slots, layer, x, taps, bias, pool)


# --- one slot's state in and out, for a prefill chunk -------------------------
# A chunk reads its sequence's state, runs the closed form in plain
# ``jax.numpy`` and writes the state back. Written ``pool[layer, slot]`` and
# ``pool.at[layer, slot].set``, XLA on the TPU gives the slice the layout its
# transposed consumer likes, hands that layout up the chain of updates to the
# pool itself, and copies the WHOLE pool into it and back, 4.6 GB each way a
# step (my chip run, PR 44: a 2,048-token chunk program asked for 16.78 GB of
# the chip's 15.75). A kernel's operands keep the layout they have, so these
# two move one slot's tiles and nothing else.

def _copy_kernel(slot_ref, layer_ref, src_ref, dst_ref):
    del slot_ref, layer_ref
    dst_ref[...] = src_ref[...].reshape(dst_ref.shape)


def _slot_specs(groups: int, n: int, w: int):
    tile = GROUP_TILE if groups % GROUP_TILE == 0 else groups
    return tile, pl.BlockSpec((tile, n, w), lambda t, slot, layer: (t, 0, 0)), \
        pl.BlockSpec((1, 1, tile, n, w),
                     lambda t, slot, layer: (layer[0], slot[0], t, 0, 0))


def _scalars(layer, slot):
    return (jnp.asarray(slot, jnp.int32).reshape(1),
            jnp.asarray(layer, jnp.int32).reshape(1))


@functools.partial(jax.jit, static_argnames=("interpret",))
def slot_read(pool, layer, slot, interpret: bool = False):
    """``pool[layer, slot]`` [G, N, W], by a kernel."""
    _, _, groups, n, w = pool.shape
    tile, one, in_pool = _slot_specs(groups, n, w)
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(groups // tile,),
            in_specs=[in_pool], out_specs=one),
        out_shape=jax.ShapeDtypeStruct((groups, n, w), pool.dtype),
        interpret=interpret, name="ssm_slot_read",
    )(*_scalars(layer, slot), pool)


@functools.partial(jax.jit, static_argnames=("interpret",))
def slot_write(pool, layer, slot, state, interpret: bool = False):
    """``pool.at[layer, slot].set(state)`` in place, by a kernel."""
    _, _, groups, n, w = pool.shape
    tile, one, in_pool = _slot_specs(groups, n, w)
    return pl.pallas_call(
        lambda slot_ref, layer_ref, src_ref, _, dst_ref: _copy_kernel(
            slot_ref, layer_ref, src_ref, dst_ref),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(groups // tile,),
            in_specs=[one, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=in_pool),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # operand 3 (after the two prefetched scalars and the state) is the
        # pool: written where the index map says, left alone elsewhere
        input_output_aliases={3: 0},
        interpret=interpret, name="ssm_slot_write",
    )(*_scalars(layer, slot), state.astype(pool.dtype), pool)
