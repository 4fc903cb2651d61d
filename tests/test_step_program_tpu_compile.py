"""The served step programs, compiled for a described v5e chip (no chip is
attached and nothing runs): the KV pool is aliased in the executable and no
operation of it copies, slices out or re-lays-out a pool-sized or
page-set-sized buffer. Donation is only the permission; this is the proof.

Mistral-7B widths with 2 of 32 layers and the chat cell's pool of 1216
blocks, so that a compile takes seconds. What the parent of PR 28 compiled
to at these shapes: two whole-pool ``copy`` operations (a change of layout
around the K/V scatter), one sliced and re-laid-out page set for each
kernel call, and temporaries larger than the pool.

All in one file and the topology in a fixture: one process at a time may
load the TPU's library (the ``on-chip-measurement`` guide, section 2).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.inference.v2 import generic_decode as gd
from deepspeed_tpu.inference.v2.modules import policy_for
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.runtime.precision import cast_to_compute

CFG = LlamaConfig(vocab_size=32000, hidden_size=4096, intermediate_size=14336,
                  num_layers=2, num_heads=32, num_kv_heads=8,
                  max_seq_len=32768, rope_theta=10000.0, sliding_window=4096)
NUM_BLOCKS, BLOCK = 1216, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a chip that is not attached cannot be read back
    # from the persistent cache: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the matmul precision of the chip's runs, not the "highest" that
    # conftest.py sets for the CPU (Mosaic refuses it on bfloat16 operands)
    with jax.default_matmul_precision("default"):
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(one_chip, fn_name, pages):
    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    # weights in the type they are served in, as a deployment holds them
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda key: cast_to_compute(LlamaForCausalLM(CFG).init(
            key, {"input_ids": np.zeros((1, 8), np.int32)})["params"],
            CFG.dtype), jax.random.PRNGKey(0)))
    spec = policy_for(CFG).cache_spec(CFG)
    dtype = jnp.float8_e4m3fn if pages == "fp8-scaled" else spec.dtype
    pool = jax.ShapeDtypeStruct(
        (spec.num_layers, 2, spec.num_kv_heads, NUM_BLOCKS, BLOCK,
         spec.head_dim), dtype, sharding=one_chip)
    cache = pool if pages == "plain" else (pool, jax.ShapeDtypeStruct(
        pool.shape[:4], jnp.float32, sharding=one_chip))
    batch, blocks = (64, 64) if fn_name == FUSED else (16, 8)
    tail = _tail(fn_name, (ints(512), ints(), ints(16), ints()),
                 (ints(batch), ints(batch), ints(batch, blocks),
                  jax.ShapeDtypeStruct((batch,), jnp.bool_,
                                       sharding=one_chip)))
    return (params, cache) + tail, pool


#: ``prefill_chunk_g`` handed a decode half (the engine's every chunk program
#: since PR 45): the chunk's shapes, then the largest decode batch bucket over
#: the widest context bucket of the cell's engine
FUSED = "prefill_chunk_g+decode"


def _program(fn_name):
    return gd.prefill_chunk_g if fn_name == FUSED else getattr(gd, fn_name)


def _tail(fn_name, chunk, decode):
    """A step program's arguments after the pool."""
    return {"decode_step_g": decode, FUSED: chunk + (decode,)}.get(fn_name,
                                                                   chunk)


def _outputs(fn_name, pools=1):
    """Leaves a step program returns: the logits (a pair where a decode half
    rides along), the pool's, the counts."""
    return (2 if fn_name == FUSED else 1) + pools + 1


def _pool_shaped_moves(text, pool):
    """The entry computation's copies and loop fusions that make a value of
    the K/V pool's shape or of one layer's page set."""
    entry = text[text.index("\nENTRY"):]
    whole = ",".join(str(d) for d in pool.shape)
    page_set = ",".join(str(d) for d in pool.shape[2:])
    return [line.strip()[:160] for line in entry.splitlines()
            if re.search(r"= \(?\w+\[(1,1,)?(%s|%s)\]\S* (copy|fusion)\("
                         % (whole, page_set), line)
            and ("copy(" in line or "kind=kLoop" in line)]


@pytest.mark.parametrize("pages", ["plain", "fp8-scaled"])
@pytest.mark.parametrize("fn_name", ["decode_step_g", "prefill_chunk_g",
                                     "verify_chunk_g", FUSED])
def test_step_program_updates_the_pool_in_place_on_a_v5e(one_chip, fn_name,
                                                         pages):
    args, pool = _shapes(one_chip, fn_name, pages)
    compiled = _program(fn_name).lower(
        *args, policy=policy_for(CFG), cfg=CFG, block_size=BLOCK,
        attn_impl="kernel").compile()
    text = compiled.as_text()
    pool_bytes = int(np.prod(pool.shape)) * pool.dtype.itemsize
    stats = compiled.memory_analysis()

    assert stats.alias_size_in_bytes >= pool_bytes
    assert "may-alias" in text.splitlines()[0]
    # what the program holds beside its arguments: activations of a 512-token
    # chunk, nowhere near a second pool (the parent: 1.25 pools)
    assert stats.temp_size_in_bytes < pool_bytes // 4
    assert "tpu_custom_call" in text and "paged_attention" in text

    assert _pool_shaped_moves(text, pool) == []


def test_layers_of_one_kind_share_one_paged_kernel_body(one_chip):
    """What holds ``setup_s``: the kernel's call sits under a ``jit`` of its
    own and where a layer's heads start is a value, so a program of two
    layers of one kind traces and lowers ONE ``paged_attention`` body and
    calls it a layer (it was lowered to Mosaic anew for each)."""
    args, _ = _shapes(one_chip, "decode_step_g", "plain")
    text = gd.decode_step_g.lower(
        *args, policy=policy_for(CFG), cfg=CFG, block_size=BLOCK,
        attn_impl="kernel").as_text()
    assert text.count('kernel_name = "paged_attention"') == 1
    assert len(re.findall(r"call @_paged_call\b", text)) == CFG.num_layers


def _kernel_operands(fn, *shapes, name="paged_attention") -> int:
    """Operands of the one call of the kernel ``name`` in ``fn``'s trace
    beside its prefetched scalars (the table, the positions, the paged
    kernel's heads' origin, and over fp8 pages the two scale vectors)."""
    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)
    (call,) = calls(jax.make_jaxpr(fn)(*shapes).jaxpr)
    assert call.params["name"] == name
    return len(call.invars) \
        - call.params["grid_mapping"].num_index_operands


# The tallest folds and longest tables the cells hand the kernel (blocks of
# 64, heads of 128, bfloat16, 8 KV heads): batch, chunk tokens, query heads,
# table blocks, window; then the (rows, pages, heads) of a grid step: a decode
# fold takes a table entry's page of all eight KV heads in one copy (PR 40),
# a chunk's fold one head over 16 pages, behind a narrow window 256 rows over
# the one tile that holds all they see (PR 42).
_TALLEST_FOLDS = {
    "laguna-full-chunk": (1, 4096, 48, 260, None, (2048, 16, 1)),
    "laguna-sliding-chunk": (1, 4096, 72, 73, 512, (256, 16, 1)),
    "laguna-full-decode-32": (32, 1, 48, 260, None, (8, 8, 8)),
    "laguna-sliding-decode-32": (32, 1, 72, 9, 512, (16, 8, 8)),
    "mixtral-chunk": (1, 2048, 32, 64, None, (2048, 16, 1)),
    "mixtral-decode-32": (32, 1, 32, 64, None, (8, 8, 8)),
    # a row block that spans the heads of a group (the masks then reckon a
    # row's token by a remainder), behind a window wider than any tile
    "mistral-chunk-over-heads": (1, 512, 32, 16, 4096, (2048, 16, 1)),
    "mistral-first-chunk-2048-rows": (1, 512, 32, 8, 4096, (2048, 8, 1)),
    "mistral-decode-64-fp8": (64, 1, 32, 64, 4096, (8, 8, 8)),
    "mistral-decode-16": (16, 1, 32, 32, 4096, (8, 8, 8)),
    # the tallest fold that takes all eight heads by the module's count,
    # behind a window; and over fp8 pages
    "short-chunk-8-heads-232-rows": (1, 58, 32, 16, 512, (232, 8, 8)),
    "short-chunk-8-heads-296-rows-fp8": (1, 74, 32, 16, 512, (296, 8, 8)),
}


@pytest.mark.parametrize("fold", sorted(_TALLEST_FOLDS))
def test_paged_kernel_gets_the_vmem_it_asks_for_at_the_tallest_folds(one_chip,
                                                                     fold):
    """The tile ``_tile`` chooses compiles for the v5e in the VMEM the call
    asks for (``_vmem_limit``: the tile's own count and a quarter): 2,048 rows
    beside 16 pages, 256 rows beside the tile of a window of 512, a 260-block
    table, decode buckets of 32 and 64, fp8 pages dequantized an entry at a
    time; a decode fold with all eight KV heads a step (one strided copy a
    table entry, the products batched over the heads), up to the tallest fold
    the rule gives eight heads. Whatever the tile, the call has three
    operands: q and the pool, once for its keys and once for its values."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    b, t, h, mb, window, tile = _TALLEST_FOLDS[fold]
    fp8 = fold.endswith("fp8")
    dtype = jnp.float8_e4m3fn if fp8 else jnp.bfloat16
    assert pa._tile(h // 8 * t, mb, BLOCK, 128, jnp.dtype(dtype).itemsize, 8,
                    window=window) == tile
    assert pa._vmem_limit(*tile, BLOCK, 128, 128,
                          jnp.dtype(dtype).itemsize) <= 32 << 20

    def on_chip(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = on_chip((2, 2, 8, NUM_BLOCKS, BLOCK, 128), dtype)
    scales = on_chip(pool.shape[:4], jnp.float32) if fp8 else None

    def attend(q, pool, scales, tables, start):
        return pa.paged_attention_pool(q, pool, 1, tables, start,
                                       window=window, scales=scales)
    shapes = (on_chip((b, t, h, 128), jnp.bfloat16), pool, scales,
              on_chip((b, mb), jnp.int32), on_chip((b,), jnp.int32))
    assert _kernel_operands(attend, *shapes) == 3
    compiled = jax.jit(attend).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_attention" in text
    # the pool goes in whole, as it lies in memory: nothing of its size is
    # made
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


# MiMo-V2.5's folds as its pools store them (a key's 192 values in a row of
# 256 lanes, values of 128; 4 KV heads in a full layer, 8 in a windowed one;
# 64 query heads; a 390-block table, and behind a window of 128 a table of 67
# blocks for a 4,096-token chunk and of 3 for a decode row): batch, chunk
# tokens, KV heads, table blocks, window, sinks; then the step's (rows, pages,
# heads).
_SPLIT_FOLDS = {
    "mimo-full-chunk": (1, 4096, 4, 390, None, False, (2048, 16, 1)),
    "mimo-windowed-chunk-sinks": (1, 4096, 8, 67, 128, True, (256, 8, 1)),
    "mimo-full-decode-32": (32, 1, 4, 390, None, False, (16, 8, 4)),
    "mimo-windowed-decode-32-sinks": (32, 1, 8, 3, 128, True, (8, 4, 8)),
}


@pytest.mark.parametrize("fold", sorted(_SPLIT_FOLDS))
def test_paged_kernel_with_keys_wider_than_values_compiles_for_a_v5e(one_chip,
                                                                     fold):
    """K rows of 256 lanes beside V rows of 128 in pools of their own, the
    softmax scaled for a key of 192, a sink a query head where the layer has
    them: the tile ``_tile`` chooses by its count of both widths (a full
    chunk's 2,048 rows beside 16 pages as a square head's, in the 29.4 MiB
    the call asks for) compiles, every KV head of a decode fold in one step;
    the call's operands are q, the two pools and at most the sinks."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    b, t, hkv, mb, window, sunk, tile = _SPLIT_FOLDS[fold]
    assert pa._tile(64 // hkv * t, mb, BLOCK, 256, 2, hkv, 128, window) == tile

    def on_chip(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    blocks = 12481 if window is None else 161

    def attend(q, k, v, sinks, tables, start):
        return pa.paged_attention_pool(
            q, (k, v), 1, tables, start, window=window, sinks=sinks,
            scale=192 ** -0.5)
    shapes = (on_chip((b, t, 64, 256)), on_chip((2, hkv, blocks, BLOCK, 256)),
              on_chip((2, hkv, blocks, BLOCK, 128)),
              on_chip((64,), jnp.float32) if sunk else None,
              on_chip((b, mb), jnp.int32), on_chip((b,), jnp.int32))
    assert _kernel_operands(attend, *shapes) == 3 + sunk
    compiled = jax.jit(attend).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_attention" in text
    # the pools go in whole, as they lie in memory; what is made is a chunk's
    # q folded by KV head (134 MB) and its output before and after the unfold
    # (67 MB each), never a full pool (2 layers here: 3.3 GB)
    assert compiled.memory_analysis().temp_size_in_bytes < 320 << 20


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """The expert layers take the Pallas grouped matmul where the backend is
    a TPU (``modules._chosen_experts``); this process's is the CPU, so the
    test answers for it while a step program is traced."""
    from deepspeed_tpu.inference.v2 import modules
    monkeypatch.setattr(modules, "_expert_matmul_impl", lambda: "kernel")


# --- the latent (MLA) pool ----------------------------------------------------
# JoyAI-LLM-Flash at its published widths, cut to the dense layer and one
# expert layer of all 256 experts, so that a compile takes seconds.

def _latent_shapes(one_chip, fn_name):
    from deepspeed_tpu.inference.v2.kv_cache import latent_row_width
    from deepspeed_tpu.models.joyai_llm_flash import (JoyAIFlashConfig,
                                                      JoyAIFlashForCausalLM)
    cfg = JoyAIFlashConfig(num_layers=2, max_seq_len=8448)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda key: cast_to_compute(JoyAIFlashForCausalLM(cfg).init(
            key, {"input_ids": np.zeros((1, 8), np.int32)})["params"],
            cfg.dtype), jax.random.PRNGKey(0)))
    spec = policy_for(cfg).cache_spec(cfg)
    pool = jax.ShapeDtypeStruct(
        (spec.num_layers, NUM_BLOCKS, BLOCK, latent_row_width(spec.latent_dim)),
        spec.dtype, sharding=one_chip)
    tail = _tail(fn_name, (ints(2048), ints(), ints(132), ints()),
                 (ints(32), ints(32), ints(32, 132), jax.ShapeDtypeStruct(
                     (32,), jnp.bool_, sharding=one_chip)))
    return cfg, (params, pool) + tail, pool


@pytest.mark.parametrize("fn_name", ["decode_step_g", "prefill_chunk_g",
                                     "verify_chunk_g", FUSED])
def test_latent_step_program_updates_the_pool_in_place_on_a_v5e(
        one_chip, as_on_a_tpu, fn_name):
    """One plane of 640-lane rows (576 values and the zero lanes that keep the
    row minor in the pool's device layout), at the cell's largest shapes (32
    sequences or a 2,048-token chunk over 132 blocks): aliased, no operation
    copies or re-lays-out the pool, the latent kernel of the program's phase
    and the grouped expert matmul's kernel are in the program (none of XLA's
    grouped calls is), and the counts leave beside the logits."""
    cfg, args, pool = _latent_shapes(one_chip, fn_name)
    assert pool.shape == (2, NUM_BLOCKS, BLOCK, 640)
    compiled = _program(fn_name).lower(
        *args, policy=policy_for(cfg), cfg=cfg, block_size=BLOCK,
        attn_impl="kernel").compile()
    text = compiled.as_text()
    pool_bytes = int(np.prod(pool.shape)) * pool.dtype.itemsize
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= pool_bytes
    assert "may-alias" in text.splitlines()[0]
    # activations of a 2,048-token chunk (the context's up-projected keys and
    # values are 138 MB; the verifier's logits over 129,280 rows 1.06 GB),
    # not a second pool: nothing pool-shaped is made anew
    assert "tpu_custom_call" in text
    assert ("latent_paged_attention" in text) == (
        fn_name in ("decode_step_g", FUSED))
    assert ("latent_prefill_attention" in text) == (
        fn_name != "decode_step_g")
    assert "grouped_matmul" in text and "ragged-dot" not in text
    entry = text[text.index("\nENTRY"):]
    whole = ",".join(str(d) for d in pool.shape)
    moved = [line.strip()[:160] for line in entry.splitlines()
             if re.search(r"= \(?\w+\[%s\]\S* (copy|fusion|transpose)\("
                          % whole, line)
             and ("copy(" in line or "transpose(" in line
                  or "kind=kLoop" in line)]
    assert moved == []
    if fn_name != "verify_chunk_g":
        # under one [E, T, F] intermediate of the chunk (256 x 2048 x 768
        # bfloat16 = 805 MB, which all-experts-then-pick makes twice a layer)
        assert stats.temp_size_in_bytes < 256 * 2048 * 768 * 2 // 2
    assert len(jax.tree.leaves(compiled.out_info)) == _outputs(fn_name)


def test_layers_of_a_latent_pool_share_one_body_a_latent_kernel(one_chip,
                                                                as_on_a_tpu):
    """What holds ``setup_s`` where every chunk program carries a decode
    fold: the layer goes into the block tables and each latent kernel's call
    sits under a ``jit`` of its own (``kv_cache._latent_paged_call``,
    ``_latent_prefill_call``), so a program of two layers lowers each kernel
    to Mosaic ONCE and calls it a layer (a lowering a layer was 0.2 s of
    host time each on the chip's machine, PERF.md section 6, PR 45)."""
    cfg, args, _ = _latent_shapes(one_chip, FUSED)
    text = gd.prefill_chunk_g.lower(
        *args, policy=policy_for(cfg), cfg=cfg, block_size=BLOCK,
        attn_impl="kernel").as_text()
    for kernel, call in (("latent_paged_attention", "_latent_paged_call"),
                         ("latent_prefill_attention",
                          "_latent_prefill_call")):
        assert text.count(f'kernel_name = "{kernel}"') == 1
        assert len(re.findall(rf"call @{call}\b", text)) == cfg.num_layers


@pytest.mark.parametrize("rows,blocks", [(4096, 132), (1024, 65)],
                         ids=["docqa-4096-over-132", "reasoning-1024-over-65"])
def test_latent_prefill_panels_compile_for_a_v5e(one_chip, rows, blocks):
    """The panel ``prefill_panel`` chooses at the two latent cells' largest
    calls (a chunk of 4,096 rows over the 132-block bucket, one of 1,024
    over 65 blocks; 32 heads of 128 + 64 / 128), keys padded as
    ``_latent_prefill_attn`` pads them: Mosaic takes both bodies, the panel
    and the scratch inside its scoped VMEM."""
    from deepspeed_tpu.inference.v2 import kv_cache
    from deepspeed_tpu.ops.pallas.latent_attention import prefill_keys
    keys = prefill_keys(rows, blocks * BLOCK)

    def arr(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    text = kv_cache._latent_prefill_call.lower(
        arr(32, rows, 128), arr(32, rows, 64), arr(32, keys, 128),
        arr(keys, 64), arr(32, keys, 128),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        scale=192 ** -0.5, interpret=False).compile().as_text()
    assert "latent_prefill_attention" in text


@pytest.mark.parametrize("rows,blocks", [(64, 65), (32, 132)],
                         ids=["reasoning-64-over-65", "docqa-32-over-132"])
def test_latent_decode_tiles_compile_for_a_v5e(one_chip, rows, blocks):
    """The tile ``decode_pages`` chooses at the two latent cells' widest
    decode calls (64 sequences over the 65-block bucket, which no tile
    divides, and 32 over 132; 32 folded heads of 640 lanes): Mosaic takes the
    copies out of the whole pool, both bodies and the two joined buffers
    inside its scoped VMEM, the pool goes to the call once and as it lies
    (no operand a page, nothing pool-shaped made for it)."""
    from deepspeed_tpu.inference.v2 import kv_cache
    from deepspeed_tpu.ops.pallas.latent_attention import decode_pages
    assert decode_pages(blocks, BLOCK) == 16

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arr((7, NUM_BLOCKS, BLOCK, 640))
    static = dict(scale=192 ** -0.5, rank=512, interpret=False)
    shapes = (arr((rows, 32, 640)), pool, arr((rows, blocks), jnp.int32),
              arr((rows,), jnp.int32))
    # q and the pool: once, where a page of a tile was an operand each
    assert _kernel_operands(
        lambda *args: kv_cache._latent_paged_call(*args, **static), *shapes,
        name="latent_paged_attention") == 2
    compiled = kv_cache._latent_paged_call.lower(*shapes, **static).compile()
    assert "latent_paged_attention" in compiled.as_text()
    pool_bytes = int(np.prod(pool.shape)) * pool.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 100


#: (rows, window, heads, kv heads, head width, segment ids): the train cell's
#: call (Mistral-7B at 8,192 rows: twice the window), a head width of 256
#: (the panel is cut smaller above 128) and a packed row, which masks every
#: panel and takes its ids as a column and a row
_FLASH_CALLS = {"train-8k-window-4096": (8192, 4096, 32, 8, 128, False),
                "head-width-256": (4096, None, 8, 4, 256, False),
                "packed-train-8k": (8192, 4096, 32, 8, 128, True)}


@pytest.mark.parametrize("call", sorted(_FLASH_CALLS))
def test_flash_kernels_compile_for_a_v5e(one_chip, call):
    """The three flash kernels, each at the panel ``flash_attention_auto``
    would choose for it (that asks for the backend, which is the CPU's here,
    so the test makes its call): Mosaic takes the banded grid, both bodies,
    the lane-dense statistics and their row form, and the panel inside its
    scoped VMEM; ``lse`` and ``delta`` cross HBM as rows, not as ``[.., S,
    1]`` columns tiled (8, 128)."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    rows, window, heads, kv_heads, width, packed = _FLASH_CALLS[call]

    def arr(h):
        return jax.ShapeDtypeStruct((1, rows, h, width), jnp.bfloat16,
                                    sharding=one_chip)

    seg = jax.ShapeDtypeStruct((1, rows), jnp.int32, sharding=one_chip)

    def loss(q, k, v, seg):
        return jnp.sum(fa.pallas_flash_attention(
            q, k, v, True, None, None, False, window,
            seg if packed else None).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arr(heads), arr(kv_heads), arr(kv_heads), seg).compile().as_text()
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert kernel in text
    assert f"f32[{heads},1,{rows}]" in text
    assert f"f32[{heads},{rows},1]" not in text


# --- the softmax-routed experts -----------------------------------------------
# Mixtral-8x7B as the batch-rag cell serves it: 3 layers, 8 experts of
# 4096 x 14336 top-2, a pool of 1472 blocks, a 2,048-token chunk or 32
# sequences over 49 blocks (3,136 tokens).

def _mixtral_shapes(one_chip, fn_name):
    from deepspeed_tpu.models.mixtral import (MixtralForCausalLM,
                                              mixtral_config_from_hf)
    cfg = mixtral_config_from_hf({
        "model_type": "mixtral", "vocab_size": 32000, "hidden_size": 4096,
        "intermediate_size": 14336, "num_hidden_layers": 3,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "num_local_experts": 8, "num_experts_per_tok": 2,
        "max_position_embeddings": 32768, "rope_theta": 1000000.0,
        "rms_norm_eps": 1e-05, "sliding_window": None,
        "torch_dtype": "bfloat16"})

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda key: cast_to_compute(MixtralForCausalLM(cfg).init(
            key, {"input_ids": np.zeros((1, 8), np.int32)})["params"],
            cfg.base.dtype), jax.random.PRNGKey(0)))
    spec = policy_for(cfg).cache_spec(cfg)
    pool = jax.ShapeDtypeStruct(
        (spec.num_layers, 2, spec.num_kv_heads, 1472, BLOCK, spec.head_dim),
        spec.dtype, sharding=one_chip)
    batch, blocks = (64, 64) if fn_name == FUSED else (32, 49)
    tail = _tail(fn_name, (ints(2048), ints(), ints(49), ints()),
                 (ints(batch), ints(batch), ints(batch, blocks),
                  jax.ShapeDtypeStruct((batch,), jnp.bool_,
                                       sharding=one_chip)))
    return cfg, (params, pool) + tail, pool


@pytest.mark.parametrize("fn_name", ["decode_step_g", "prefill_chunk_g",
                                     "verify_chunk_g", FUSED])
def test_mixtral_step_program_computes_the_chosen_experts_alone_on_a_v5e(
        one_chip, as_on_a_tpu, fn_name):
    """A 2,048-token chunk and a decode batch of 32 alike: the grouped
    matmul's kernel is in the program, and neither XLA's grouped call nor an
    ``[8, T, 14336]`` value is (all-experts-then-pick made two a layer, 470
    MB each for a chunk; decode batches kept that form until PR 34). In all
    three the temporaries stay under one such intermediate of a chunk, the
    pool is still aliased whole, nothing pool-shaped is copied, and the
    counts leave beside the logits."""
    cfg, args, pool = _mixtral_shapes(one_chip, fn_name)
    assert cfg.moe.num_experts == 8 and cfg.base.dtype == jnp.bfloat16
    compiled = _program(fn_name).lower(
        *args, policy=policy_for(cfg), cfg=cfg, block_size=BLOCK,
        attn_impl="kernel").compile()
    text = compiled.as_text()
    pool_bytes = int(np.prod(pool.shape)) * pool.dtype.itemsize
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= pool_bytes
    assert "may-alias" in text.splitlines()[0]
    assert "tpu_custom_call" in text and "paged_attention" in text
    # 2,048 a chunk, 32 a batch, 2,048 + 64 the two in one pass
    rows = args[2].shape[0] + (64 if fn_name == FUSED else 0)
    assert "grouped_matmul" in text and "ragged-dot" not in text
    assert re.search(r"\[8,%d,14336\]" % rows, text) is None
    # 259 MB a chunk, 142 MB the verifier
    assert stats.temp_size_in_bytes < 8 * 2048 * 14336 * 2
    assert _pool_shaped_moves(text, pool) == []
    assert len(jax.tree.leaves(compiled.out_info)) == _outputs(fn_name)


# --- pages by layer kind --------------------------------------------------------
# Laguna-S-2.1 at its published widths, cut to the leading dense full layer
# and one sliding expert layer of the chip's 128 experts, so that a compile
# takes seconds: a pool a kind, 6 and 9 query heads to a KV head.

def _laguna_shapes(one_chip, fn_name):
    from deepspeed_tpu.inference.v2.kv_cache import windowed_table_blocks
    from deepspeed_tpu.models.laguna import (FULL, SLIDING, LagunaConfig,
                                             LagunaForCausalLM)
    cfg = LagunaConfig(layer_types=(FULL, SLIDING), heads_per_layer=(48, 72),
                       experts_held=128, max_seq_len=16640)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda key: cast_to_compute(LagunaForCausalLM(cfg).init(
            key, {"input_ids": np.zeros((1, 8), np.int32)})["params"],
            cfg.dtype), jax.random.PRNGKey(0)))
    spec = policy_for(cfg).cache_spec(cfg)
    assert spec.layer_windows == (None, 512)
    pool = {kind: jax.ShapeDtypeStruct(
        (1, 2, spec.num_kv_heads, blocks, BLOCK, spec.head_dim), spec.dtype,
        sharding=one_chip)
        for kind, blocks in (("full", NUM_BLOCKS), ("window", 355))}
    tail = _tail(
        fn_name,
        (ints(4096), ints(),
         {"full": ints(260),
          "window": ints(windowed_table_blocks(4096, 512, BLOCK))}, ints()),
        (ints(32), ints(32),
         {"full": ints(32, 260),
          "window": ints(32, windowed_table_blocks(1, 512, BLOCK))},
         jax.ShapeDtypeStruct((32,), jnp.bool_, sharding=one_chip)))
    return cfg, (params, pool) + tail, pool


@pytest.mark.parametrize("fn_name", ["decode_step_g", "prefill_chunk_g",
                                     FUSED])
def test_layer_kind_step_program_updates_both_pools_in_place_on_a_v5e(
        one_chip, as_on_a_tpu, fn_name):
    """A pool a layer kind at the cell's largest shapes (32 sequences or a
    4,096-token chunk over 260 blocks; the windowed layer's table 9 and 73
    blocks whatever the context): both pools aliased whole, nothing
    pool-shaped copied, the paged kernel once a layer at 6 and 9 query heads
    a KV head, the grouped matmul's kernel over the 128 held experts, and
    four counts beside the logits."""
    cfg, args, pool = _laguna_shapes(one_chip, fn_name)
    assert args[4]["window"].shape[-1] == (9 if fn_name == "decode_step_g"
                                           else 73)
    compiled = _program(fn_name).lower(
        *args, policy=policy_for(cfg), cfg=cfg, block_size=BLOCK,
        attn_impl="kernel").compile()
    text = compiled.as_text()
    pool_bytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize
                     for p in pool.values())
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= pool_bytes
    assert text.splitlines()[0].count("may-alias") >= 2
    assert "tpu_custom_call" in text and "paged_attention" in text
    assert "grouped_matmul" in text and "ragged-dot" not in text
    for p in pool.values():
        assert _pool_shaped_moves(text, p) == []
    # a 4,096-token chunk's activations (72 heads of queries are 75 MB)
    assert stats.temp_size_in_bytes < 1 << 30
    logits, _, counts = compiled.out_info
    assert counts.shape == (4,)
    assert len(jax.tree.leaves(compiled.out_info)) == _outputs(fn_name, 2)


# --- several residual streams ---------------------------------------------------
# Xing4.0 at its published widths, cut to one dense and one expert layer of
# all 64 experts, so that a compile takes seconds: JoyAI's latent pool, the
# state between layers [rows, 4, 3584].

def _xing4_shapes(one_chip, fn_name):
    from deepspeed_tpu.inference.v2.kv_cache import latent_row_width
    from deepspeed_tpu.models.xing4 import Xing4Config, Xing4ForCausalLM
    cfg = Xing4Config(num_layers=2, first_k_dense_replace=1, max_seq_len=4160)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda key: cast_to_compute(Xing4ForCausalLM(cfg).init(
            key, {"input_ids": np.zeros((1, 8), np.int32)})["params"],
            cfg.dtype), jax.random.PRNGKey(0)))
    spec = policy_for(cfg).cache_spec(cfg)
    pool = jax.ShapeDtypeStruct(
        (spec.num_layers, NUM_BLOCKS, BLOCK, latent_row_width(spec.latent_dim)),
        spec.dtype, sharding=one_chip)
    tail = _tail(fn_name, (ints(1024), ints(), ints(32), ints()),
                 (ints(64), ints(64), ints(64, 65), jax.ShapeDtypeStruct(
                     (64,), jnp.bool_, sharding=one_chip)))
    return cfg, (params, pool) + tail, pool


@pytest.mark.parametrize("fn_name", ["decode_step_g", "prefill_chunk_g",
                                     "verify_chunk_g", FUSED])
def test_xing4_step_program_keeps_its_streams_and_the_pool_in_place_on_a_v5e(
        one_chip, as_on_a_tpu, fn_name):
    """The reasoning cell's largest shapes (64 sequences over 65 blocks, a
    1,024-token chunk over 32): the policy owns a state of four streams a row
    and the loop compiles it unread; the latent pool is aliased whole, the
    latent kernel of the program's phase and the grouped matmul are in the
    program, the mixing's operations carry the ``hc`` scopes, and the counts
    leave beside the logits."""
    cfg, args, pool = _xing4_shapes(one_chip, fn_name)
    assert (cfg.hc_mult, cfg.hidden_size, cfg.n_routed_experts) == (4, 3584,
                                                                    64)
    compiled = _program(fn_name).lower(
        *args, policy=policy_for(cfg), cfg=cfg, block_size=BLOCK,
        attn_impl="kernel").compile()
    text = compiled.as_text()
    pool_bytes = int(np.prod(pool.shape)) * pool.dtype.itemsize
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= pool_bytes
    assert "may-alias" in text.splitlines()[0]
    assert "tpu_custom_call" in text
    assert ("latent_paged_attention" in text) == (
        fn_name in ("decode_step_g", FUSED))
    assert ("latent_prefill_attention" in text) == (
        fn_name != "decode_step_g")
    assert "grouped_matmul" in text and "ragged-dot" not in text
    for scope in ("/hc/pre/", "/hc/post/", "/hc/head/"):
        assert scope in text
    # a chunk's streams are 1,024 x 4 x 3,584 bfloat16 = 29 MB, in float32
    # inside a fusion at most: nowhere near a second pool (the verifier's
    # logits over 131,072 rows are 0.54 GB)
    if fn_name != "verify_chunk_g":
        assert stats.temp_size_in_bytes < pool_bytes
    assert len(jax.tree.leaves(compiled.out_info)) == _outputs(fn_name)


# --- a state kind beside the pages (granite-4.0-h-micro's widths) --------------

def _makers_of(text, shape):
    """The operations of a compiled program that make or move a value of
    ``shape`` (an XLA type, ``bf16[2,65,104,128]``), but for the program's
    own argument, a kernel that takes it aliased and what hands its result
    on: the lines that would be a gather, a scatter, a slice's update, a
    copy or a change of layout of that buffer."""
    passes_on = re.compile(
        r" (parameter|custom-call|get-tuple-element|tuple|bitcast)\(")
    return [line.strip()[:160] for line in text.splitlines()
            if " = " in line and not line.startswith("HloModule")
            and (re.search(r"= \(?[^=]*" + re.escape(shape), line)
                 or re.search(r"(gather|scatter|dynamic-update-slice)\(.*"
                              + re.escape(shape), line))
            and not passes_on.search(line)]


@pytest.mark.parametrize("fn_name", ["decode_step_g", "prefill_chunk_g",
                                     FUSED])
def test_state_kind_step_program_updates_the_whole_pool_in_place(one_chip,
                                                                 fn_name):
    """Three layers of granite-4.0-h-micro at the published widths (Mamba-2,
    attention, Mamba-2) with the cell's 64 slots and 3,073 blocks: the pages
    AND the states are aliased in the executable (what ``serve/kv_alias``
    reports), the decode program holds next to nothing beside its arguments,
    the update is the Pallas kernel, lowered once for both Mamba layers, and
    the attention layer's pages are two 64-wide KV heads a 128-lane row."""
    from deepspeed_tpu.inference.v2.kv_cache import BlockedKVCache
    from deepspeed_tpu.models import granite_hybrid as gh
    cfg = gh.GraniteHybridConfig(
        layer_types=(gh.MAMBA, gh.ATTENTION, gh.MAMBA), max_seq_len=4096)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda key: cast_to_compute(gh.GraniteHybridForCausalLM(cfg).init(
            key, {"input_ids": np.zeros((1, 8), np.int32)})["params"],
            cfg.dtype), jax.random.PRNGKey(0)))
    policy = policy_for(cfg)
    pool = jax.tree.map(on_chip, jax.eval_shape(
        lambda: BlockedKVCache.for_spec(policy.cache_spec(cfg), "model", 64,
                                        3073, state_slots=64).pool))
    assert jax.tree.map(lambda x: x.shape, pool) == {
        "full": (1, 2, 4, 3073, 64, 128),
        "state": {"ssm": (2, 65, 32, 128, 128), "conv": (2, 65, 104, 128)}}
    pool_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                     for x in jax.tree.leaves(pool))
    tail = _tail(
        fn_name,
        (ints(2048), ints(), {"full": ints(48), "state": ints()}, ints()),
        (ints(64), ints(64), {"full": ints(64, 48), "state": ints(64)},
         jax.ShapeDtypeStruct((64,), jnp.bool_, sharding=one_chip)))
    lowered = _program(fn_name).lower(
        params, pool, *tail, policy=policy, cfg=cfg, block_size=64,
        attn_impl="kernel")
    compiled = lowered.compile()
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= pool_bytes
    assert "may-alias" in compiled.as_text().splitlines()[0]
    assert "paged_attention" in compiled.as_text()
    if fn_name != "prefill_chunk_g":
        assert lowered.as_text().count('kernel_name = "ssm_update"') == 1
        assert "ssm_update" in compiled.as_text()
        # and the tails' shift: traced once, called a layer
        assert lowered.as_text().count('kernel_name = "ssm_conv_step"') == 1
        assert "ssm_conv_step" in compiled.as_text()
    if fn_name == "decode_step_g":
        assert stats.temp_size_in_bytes < 64 << 20
        # the tails move by slot in the kernel and nowhere else: nothing
        # gathers from, scatters into, copies or re-lays-out their pool
        # (left to choose, XLA also moved these 3.4 MB whole into VMEM and
        # back around each call: the kernel names HBM)
        assert _makers_of(compiled.as_text(), "bf16[2,65,104,128]") == []
    else:
        # a 2,048-token chunk's closed form: blocks of 256, scores a head;
        # and no operation makes a value of the states' pool's shape (read
        # as a slice, the pool was copied whole into another layout and
        # back: ``ops/pallas/ssm_update.py`` ``slot_read``)
        assert stats.temp_size_in_bytes < 256 << 20
        entry = compiled.as_text()
        entry = entry[entry.index("\nENTRY"):]
        assert [line.strip()[:120] for line in entry.splitlines()
                if re.search(r"= \(?f32\[2,65,32,128,128\]\S* (copy|fusion)\(",
                             line)] == []
        assert "ssm_slot_read" in entry and "ssm_slot_write" in entry


# --- pages by layer kind, each kind with its own heads and widths ----------------
# MiMo-V2.5 at its published widths, cut to the leading dense full layer and
# one windowed expert layer of the chip's 16 experts, so that a compile takes
# seconds: keys of 192 beside values of 128 in a K pool and a V pool a kind,
# 4 and 8 KV heads, a sink a query head in the windowed layer's softmax.

def _mimo_shapes(one_chip, fn_name):
    from deepspeed_tpu.inference.v2.kv_cache import (BlockedKVCache,
                                                     windowed_table_blocks)
    from deepspeed_tpu.models.mimo_v2 import MiMoV2Config, MiMoV2ForCausalLM
    cfg = MiMoV2Config(hybrid_layer_pattern=(0, 1), moe_layer_freq=(0, 1),
                       experts_held=16, max_seq_len=24960)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda key: cast_to_compute(MiMoV2ForCausalLM(cfg).init(
            key, {"input_ids": np.zeros((1, 8), np.int32)})["params"],
            cfg.dtype), jax.random.PRNGKey(0)))
    # the agent-long cell's pools: 12,481 blocks, and the windowed layers'
    # 31 x 3 + 67 + 1 as the engine derives them
    pool = jax.tree.map(on_chip, jax.eval_shape(
        lambda: BlockedKVCache.for_spec(policy_for(cfg).cache_spec(cfg),
                                        "model", BLOCK, 12481,
                                        window_blocks=161).pool))

    def tables(lead, rows):
        return {"full": ints(*lead, 390),
                "window": ints(*lead, windowed_table_blocks(rows, 128, BLOCK))}
    tail = _tail(
        fn_name, (ints(4096), ints(), tables((), 4096), ints()),
        (ints(32), ints(32), tables((32,), 1),
         jax.ShapeDtypeStruct((32,), jnp.bool_, sharding=one_chip)))
    return cfg, (params, pool) + tail, pool


def _held(stats) -> int:
    """Device bytes a program holds while it runs: its arguments, its
    temporaries and what it returns, less what it returns in place."""
    return (stats.argument_size_in_bytes + stats.temp_size_in_bytes
            + stats.output_size_in_bytes - stats.alias_size_in_bytes)


@pytest.mark.parametrize("fn_name", ["decode_step_g", FUSED])
def test_split_head_pages_step_program_updates_four_pools_in_place_on_a_v5e(
        one_chip, as_on_a_tpu, fn_name):
    """The agent-long cell's largest shapes (32 sequences or a 4,096-token
    chunk over 390 blocks; the windowed layer's table 3 and 67 blocks): a K
    pool and a V pool a kind, all four aliased whole, nothing pool-shaped
    copied, the paged kernel once a layer kind and fold with the windowed
    layer's sinks an operand of its calls; and a chunk program with the
    decode half holds no more of the chip than the chunk alone but the decode
    rows' logits (the cell peaks at 94-97% of the chip's memory)."""
    cfg, args, pool = _mimo_shapes(one_chip, fn_name)
    assert jax.tree.map(lambda x: x.shape, pool) == {
        "full": {"k": (1, 4, 12481, 64, 256), "v": (1, 4, 12481, 64, 128)},
        "window": {"k": (1, 8, 161, 64, 256), "v": (1, 8, 161, 64, 128)}}
    how = dict(policy=policy_for(cfg), cfg=cfg, block_size=BLOCK,
               attn_impl="kernel")
    lowered = _program(fn_name).lower(*args, **how)
    compiled = lowered.compile()
    text = compiled.as_text()
    leaves = jax.tree.leaves(pool)
    pool_bytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize for p in leaves)
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= pool_bytes
    assert text.splitlines()[0].count("may-alias") >= 4
    assert "grouped_matmul" in text and "ragged-dot" not in text
    for p in leaves:
        assert _pool_shaped_moves(text, p) == []
    # a kernel a layer kind and fold: the full layer's and the windowed
    # layer's (whose calls take the layer's 64 sinks beside q and the pools)
    folds = 2 if fn_name == FUSED else 1
    calls = [line for line in lowered.as_text().splitlines()
             if 'kernel_name = "paged_attention"' in line]
    assert len(calls) == 2 * folds
    assert sum("x1xf32>) ->" in call for call in calls) == folds
    assert len(jax.tree.leaves(compiled.out_info)) == _outputs(fn_name, 4)
    if fn_name == FUSED:
        alone = gd.prefill_chunk_g.lower(*args[:-1], **how).compile()
        rows, vocab = compiled.out_info[0][1].shape
        assert (rows, vocab) == (32, cfg.vocab_size)
        assert _held(stats) <= _held(alone.memory_analysis()) \
            + 2 * rows * vocab * 4
        assert stats.temp_size_in_bytes < 1 << 30


# --- a chunk's rows a page at a time ------------------------------------------

def _row_scatters(text, pool):
    """The scatters of a compiled program whose operand is one of ``pool``'s
    leaves, however XLA sees its shape (the row scatter's is the pool as
    rows, ``bf16[412160,256]`` of ``[5, 8, 161, 64, 256]``): by the element
    count of what the scatter makes."""
    sizes = {int(np.prod(p.shape)) for p in jax.tree.leaves(pool)}
    found = []
    for line in text.splitlines():
        made = re.search(r"= \(?\w+\[([\d,]+)\]\S* scatter\(", line)
        if made and int(np.prod([int(d) for d in
                                 made.group(1).split(",")])) in sizes:
            found.append(line.strip()[:160])
    return found


def _paged_program(one_chip, pages, fn_name):
    """(config, arguments, pool) of ``fn_name`` over a pool of ``pages``."""
    if pages == "plain":
        args, pool = _shapes(one_chip, fn_name, "plain")
        return CFG, args, pool
    return {"latent": _latent_shapes, "layer-kind": _laguna_shapes,
            "split": _mimo_shapes}[pages](one_chip, fn_name)


@pytest.mark.parametrize("pages", ["plain", "split", "latent", "layer-kind"])
def test_a_chunk_program_writes_its_rows_a_page_at_a_time_in_place(
        one_chip, as_on_a_tpu, pages):
    """A chunk program over plain head pages, pages whose keys are wider than
    their values (four pools), a latent plane and head pages by layer kind, at
    the cells' shapes: no scatter of one row an update on any pool (it moved
    512 bytes an update at 82 ns each, a sixth of three cells' busy time:
    PERF.md section 7, PR 54), the page write's kernel once a pool format of
    the program whatever the layers, every pool aliased whole and nothing
    pool-shaped copied; and the decode step keeps the row scatter (a batch's
    rows lie in as many pages as sequences) and holds no page write."""
    cfg, args, pool = _paged_program(one_chip, pages, "prefill_chunk_g")
    how = dict(policy=policy_for(cfg), cfg=cfg, block_size=BLOCK,
               attn_impl="kernel")
    lowered = gd.prefill_chunk_g.lower(*args, **how)
    compiled = lowered.compile()
    text = compiled.as_text()
    leaves = jax.tree.leaves(pool)
    assert _row_scatters(text, pool) == []
    bodies = lowered.as_text().count('kernel_name = "kv_page_write"')
    # one body a shape of (pool, rows): K and V planes of one array are one,
    # a split kind's K and V pools two, two layer kinds two
    assert bodies == {"plain": 1, "latent": 1, "layer-kind": 2,
                      "split": 4}[pages]
    assert "kv_page_write" in text
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= sum(
        int(np.prod(p.shape)) * p.dtype.itemsize for p in leaves)
    assert text.splitlines()[0].count("may-alias") >= len(leaves)
    for p in leaves:
        assert _pool_shaped_moves(text, p) == []

    cfg, args, pool = _paged_program(one_chip, pages, "decode_step_g")
    text = gd.decode_step_g.lower(*args, **how).compile().as_text()
    assert "kv_page_write" not in text
    assert len(_row_scatters(text, pool)) >= len(leaves)


# --- every layer ONE mixer: a state, experts of two matrices, or pages --------
# NVIDIA-Nemotron-3-Nano-30B-A3B at its published widths, one layer of each
# kind with the chip's 64 of 128 experts, the cell's 128 slots and 6,145
# blocks, so that a compile takes seconds.

@pytest.mark.parametrize("fn_name", ["decode_step_g", "prefill_chunk_g"])
def test_nemotron_h_step_program_reads_its_experts_where_they_lie(
        one_chip, as_on_a_tpu, fn_name):
    """The states (eight groups of B and C: a tile of 16 head pairs reads
    four of them) and the pages are aliased in the executable, an expert
    layer has no pool at all, the ungated experts' first product is the
    grouped kernel under its ``relu^2`` epilogue, and NO expert stack is
    copied: 1,856 is no multiple of 128 lanes, so a stack ``[64, 2688,
    1856]`` lies on the device with another axis minor and the kernel's
    operand was copied out of it whole, 609 MiB a layer a step (a compile
    of that form, PR 49); ``w_in`` is ``[64, 1856, 2688]`` and lies as the
    kernel reads it."""
    from deepspeed_tpu.inference.v2.kv_cache import BlockedKVCache
    from deepspeed_tpu.models import nemotron_h as nh
    cfg = nh.NemotronHConfig(pattern="ME*", experts_held=64,
                             max_seq_len=3072)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda key: cast_to_compute(nh.NemotronHForCausalLM(cfg).init(
            key, {"input_ids": np.zeros((1, 8), np.int32)})["params"],
            cfg.dtype), jax.random.PRNGKey(0)))
    policy = policy_for(cfg)
    pool = jax.tree.map(on_chip, jax.eval_shape(
        lambda: BlockedKVCache.for_spec(policy.cache_spec(cfg), "model", 64,
                                        6145, state_slots=128).pool))
    assert jax.tree.map(lambda x: x.shape, pool) == {
        "full": (1, 2, 2, 6145, 64, 128),
        "state": {"ssm": (1, 129, 32, 128, 128), "conv": (1, 129, 144, 128)}}
    pool_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                     for x in jax.tree.leaves(pool))
    tail = _tail(
        fn_name,
        (ints(1024), ints(), {"full": ints(48), "state": ints()}, ints()),
        (ints(128), ints(128), {"full": ints(128, 48), "state": ints(128)},
         jax.ShapeDtypeStruct((128,), jnp.bool_, sharding=one_chip)))
    compiled = _program(fn_name).lower(
        params, pool, *tail, policy=policy, cfg=cfg, block_size=64,
        attn_impl="kernel").compile()
    stats, text = compiled.memory_analysis(), compiled.as_text()
    assert stats.alias_size_in_bytes >= pool_bytes
    assert "may-alias" in text.splitlines()[0]
    for kernel in ("paged_attention", "grouped_matmul_relu2_in",
                   "grouped_matmul"):
        assert kernel in text, kernel
    assert "ragged-dot" not in text
    assert ("ssm_slot_read" in text) == (fn_name != "decode_step_g")
    assert ("ssm_slot_write" in text) == (fn_name != "decode_step_g")
    if fn_name == "decode_step_g":
        # one state layer: the tails' shift and the update, a kernel each,
        # and nothing else touches the tails' pool
        assert text.count("%ssm_conv_step") >= 1 and "ssm_update" in text
        assert _makers_of(text, "bf16[1,129,144,128]") == []
    # beside its arguments a step holds activations: no second copy of an
    # expert stack (609 MiB) or of a pool
    assert stats.temp_size_in_bytes < 128 << 20
    entry = text[text.index("\nENTRY"):]
    assert [line.strip()[:120] for line in entry.splitlines()
            if re.search(r"= \(?bf16\[64,(1856,2688|2688,1856)\]\S* "
                         r"(copy|fusion)\(", line)] == []
    assert len(jax.tree.leaves(compiled.out_info)) == 1 + 3 + 1


# --- a tail-only state kind, experts behind every mixer -----------------------
# LFM2-24B-A2B at its published widths: one dense conv layer, then an
# attention layer and a conv layer with all 64 experts behind each, the cell's
# 256 slots and 10,241 blocks, so that a compile takes seconds.

@pytest.mark.parametrize("fn_name", ["decode_step_g", "prefill_chunk_g"])
def test_lfm2_moe_step_program_keeps_tails_and_pages_and_no_states(
        one_chip, as_on_a_tpu, fn_name):
    """The 2,048-token chunk and the 256-row decode program: the pool is the
    attention layer's pages (two KV heads of 64 to a row) and the conv
    layers' tails, 32 rows of 128 lanes a slot, and NO pool of states; both
    are aliased in the executable; the experts behind a state layer and
    behind an attention layer go through the grouped kernels; a decode step
    shifts its 256 rows' tails by slot in ``ssm_conv_step`` (traced once,
    called a layer, without an activation) and nothing else touches the
    tails' pool."""
    from deepspeed_tpu.inference.v2.kv_cache import BlockedKVCache
    from deepspeed_tpu.models import lfm2_moe as lfm2
    cfg = lfm2.Lfm2MoeConfig(
        layer_types=(lfm2.CONV, lfm2.ATTENTION, lfm2.CONV),
        num_dense_layers=1, max_seq_len=2560)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda key: cast_to_compute(lfm2.Lfm2MoeForCausalLM(cfg).init(
            key, {"input_ids": np.zeros((1, 8), np.int32)})["params"],
            cfg.dtype), jax.random.PRNGKey(0)))
    policy = policy_for(cfg)
    pool = jax.tree.map(on_chip, jax.eval_shape(
        lambda: BlockedKVCache.for_spec(policy.cache_spec(cfg), "model", 64,
                                        10241, state_slots=256).pool))
    assert jax.tree.map(lambda x: x.shape, pool) == {
        "full": (1, 2, 4, 10241, 64, 128),
        "state": {"conv": (2, 257, 32, 128)}}
    pool_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                     for x in jax.tree.leaves(pool))
    tail = _tail(
        fn_name,
        (ints(2048), ints(), {"full": ints(40), "state": ints()}, ints()),
        (ints(256), ints(256), {"full": ints(256, 40), "state": ints(256)},
         jax.ShapeDtypeStruct((256,), jnp.bool_, sharding=one_chip)))
    lowered = _program(fn_name).lower(
        params, pool, *tail, policy=policy, cfg=cfg, block_size=64,
        attn_impl="kernel")
    compiled = lowered.compile()
    stats, text = compiled.memory_analysis(), compiled.as_text()
    assert stats.alias_size_in_bytes >= pool_bytes
    assert "may-alias" in text.splitlines()[0]
    for kernel in ("paged_attention", "grouped_matmul_gate_up",
                   "grouped_matmul"):
        assert kernel in text, kernel
    assert "ragged-dot" not in text
    kernels = set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text()))
    assert not kernels & {"ssm_update", "ssm_slot_read", "ssm_slot_write"}
    if fn_name == "decode_step_g":
        assert lowered.as_text().count('kernel_name = "ssm_conv_step"') == 1
        assert text.count("%ssm_conv_step") >= 1
        assert _makers_of(text, "bf16[2,257,32,128]") == []
    else:
        assert "ssm_conv_step" not in kernels
    # beside its arguments a step holds activations: no second copy of an
    # expert stack (403 MB) or of a pool
    assert stats.temp_size_in_bytes < 512 << 20
    entry = text[text.index("\nENTRY"):]
    assert [line.strip()[:120] for line in entry.splitlines()
            if re.search(r"= \(?bf16\[64,(1536,2048|2048,1536)\]\S* "
                         r"(copy|fusion)\(", line)] == []
    assert len(jax.tree.leaves(compiled.out_info)) == 1 + 2 + 1
