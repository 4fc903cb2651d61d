"""MiMo-V2 against its plain reference (``benchmarks/reference/mimo_v2.py``),
at a tiny size on seeded weights: hidden 64, a leading dense full layer then
windowed, windowed, full, windowed; 4 query heads over 1 KV head in a full
layer and over 2 in a windowed one, keys of 24 beside values of 16 (8 of the
24 rotate), window 24 over blocks of 8, a sink a head in the windowed layers
drawn N(4, 1) as the benchmark's family draws it, values scaled by 0.707, a sigmoid router over 32 experts top-4
with a correction bias and no shared expert.

Every served test goes through the engine itself (admission, SplitFuse
chunks, the block tables of both kinds of page, the windowed blocks given
back): ``served`` records the logits each step program hands the sampler and
compares them, teacher-forced on the engine's own tokens, with the
reference's full forward at the same positions. Controls run the same
comparison against a reference with one thing wrong and must fail it.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import mimo_v2 as family
from benchmarks.reference import mimo_v2 as reference
from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  V2EngineConfig)
from deepspeed_tpu.inference.v2.kv_cache import HeadPageShape
from deepspeed_tpu.inference.v2.modules import MiMoV2Policy, policy_for
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models import mimo_v2 as mm
from deepspeed_tpu.models.joyai_llm_flash import route
from deepspeed_tpu.moe.grouped_experts import grouped_expert_ffn
from deepspeed_tpu.ops.pallas import paged_attention as pa
from deepspeed_tpu.runtime.precision import cast_to_compute

HF = {
    "model_type": "mimo_v2", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 5, "num_attention_heads": 4,
    "num_key_value_heads": 1, "head_dim": 24, "v_head_dim": 16,
    "swa_num_attention_heads": 4, "swa_num_key_value_heads": 2,
    "swa_head_dim": 24, "swa_v_head_dim": 16,
    "hybrid_layer_pattern": [0, 1, 1, 0, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1], "sliding_window": 24,
    "sliding_window_size": 24, "attention_chunk_size": 24,
    "rope_theta": 10000000, "swa_rope_theta": 10000,
    "partial_rotary_factor": 0.334,
    "rope_scaling": {"rope_type": "default", "type": "default"},
    "attention_value_scale": 0.707, "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "attention_bias": False,
    "hidden_act": "silu", "layernorm_epsilon": 1e-5,
    "max_position_embeddings": 512, "tie_word_embeddings": False,
    "n_routed_experts": 32, "n_shared_experts": None,
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "routed_scaling_factor": None}
#: one chip of four: the router's 32, this chip's experts 8-15
HF_SHARE = dict(HF, n_routed_experts=8, published={"n_routed_experts": 32},
                first_expert_held=8)
BLOCK = 8
#: float32: the program and the reference differ in the order of their
#: reductions only
F32_TOL = 2e-4
#: bfloat16 weights and activations against the reference's float32 math on
#: the same bfloat16 weights, a quarter of the experts held, on logits of
#: +-4.5: sound 0.45, 0.63, 0.06, 0.34 over seeds 0-3 (a near-tie at the
#: router's cut broken the other way moves a whole expert's part where one
#: of the pair is another chip's, and at 64 wide a bfloat16 state is several
#: times noisier against its size than at the published 4,096). The same
#: controls in bfloat16, seeds 0-3: the sinks left out 2.24-3.23, the value
#: scale left out 1.33-1.79, the full layers given the windowed rope base
#: 2.32-3.96, keys cut to the values' width 3.39-3.82. With sinks of N(0, 1),
#: as the program's tests could have drawn them, leaving them out read
#: 0.51-0.68 against sound runs of 0.04-0.51: not separated (behind a window
#: of 24 such a sink holds a twentieth of a head's mass), which is why the
#: benchmark's family draws N(4, 1) (families/mimo_v2.py)
BF16_TOL = 0.8


def build(hf=HF, dtype=jnp.float32, seed=0):
    cfg, model = family.build(hf, "serve", {"max_context": 512})
    cfg = dataclasses.replace(cfg, dtype=dtype)
    model = type(model)(mm.MiMoV2ForCausalLM(cfg))     # the sinks drawn
    params = cast_to_compute(model.init(
        jax.random.PRNGKey(seed),
        {"input_ids": np.zeros((1, 8), np.int32)})["params"], dtype)
    return cfg, model.model, params


@pytest.fixture(scope="module")
def f32():
    return build()


@pytest.fixture(scope="module")
def share():
    return build(HF_SHARE)


def tokens(n, seed=1):
    return np.random.default_rng(seed).integers(0, HF["vocab_size"], n,
                                                dtype=np.int32)


def engine(cfg, params, attn_impl="gather", **over):
    return InferenceEngineV2(params, cfg, V2EngineConfig(**{**dict(
        kv_block_size=BLOCK, kv_num_blocks=64, ctx_block_buckets=(4, 8, 16),
        decode_batch_buckets=(1, 2, 4), attn_impl=attn_impl,
        scheduler=SchedulerConfig(max_tokens_per_step=32, max_decode_batch=4,
                                  prefill_buckets=(8, 16, 32))), **over}))


def served(eng, prompts, new_tokens):
    """[(tokens, logits rows)] a prompt: the engine's greedy tokens and the
    logits row that chose each, all prompts admitted together."""
    records = []
    sample = eng._sample_dispatch

    def recording(logits, rows):
        records.append(np.asarray(logits, np.float32))
        return sample(logits, rows)
    eng._sample_dispatch = recording
    uids = list(range(1, len(prompts) + 1))
    got = {u: ([], []) for u in uids}
    for u, p in zip(uids, prompts):
        eng.admit(u, list(p))
    most = eng.config.scheduler.max_decode_batch
    while any(len(got[u][0]) < new_tokens for u in uids):
        decoding = [s.uid for s in eng.state.decoding()[:most]]
        prefilling = [s.uid for s in eng.state.prefilling()]
        del records[:]
        out = eng.step()
        rows = list(records)
        # a step's records: one row a prompt that ended, in plan order, then
        # the decode batch's
        for u in [u for u in prefilling if u in out]:
            got[u][0].append(out[u])
            got[u][1].append(rows.pop(0)[0])
        for j, u in enumerate(decoding):
            got[u][0].append(out[u])
            got[u][1].append(rows[0][j])
    for u in uids:
        eng.flush(u)
    return [(got[u][0][:new_tokens], np.stack(got[u][1][:new_tokens]))
            for u in uids]


def served_error(cfg, params, hf=HF, prompts=(70,), new_tokens=12,
                 attn_impl="gather", change=None, eng=None):
    """Largest |served logit - reference logit| over the generated positions
    of every prompt (lengths ``prompts``), prefilled in chunks of at most 32
    and decoded together."""
    eng = eng or engine(cfg, params, attn_impl)
    prompts = [tokens(n, seed=n) for n in prompts]
    weights = family.reference_weights(params)
    if change:
        weights = dict(weights, layers=[
            change(dict(layer), kind) for layer, kind in
            zip(weights["layers"], hf["hybrid_layer_pattern"])])
    worst = 0.0
    for prompt, (toks, rows) in zip(prompts, served(eng, prompts,
                                                    new_tokens)):
        seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
        want = reference.logits(weights, hf, seq)[len(prompt) - 1:]
        worst = max(worst, float(jnp.max(jnp.abs(rows - want))))
    return worst


# --- the config, the tree, the names ------------------------------------------

def test_config_from_hf_keeps_each_kinds_heads_and_widths(share):
    cfg, _, params = share
    assert cfg.hybrid_layer_pattern == (0, 1, 1, 0, 1)
    assert (cfg.full, cfg.swa) == (mm.AttnShape(4, 1, 24, 16),
                                   mm.AttnShape(4, 2, 24, 16))
    assert (cfg.n_routed_experts, cfg.held, cfg.first_expert) == (32, 8, 8)
    assert [cfg.window(i) for i in range(5)] == [None, 24, 24, None, 24]
    assert [cfg.has_sink(i) for i in range(5)] == [False, True, True, False,
                                                   True]
    assert [cfg.rope_base(i) for i in (0, 1)] == [1e7, 1e4]
    assert cfg.rotary_dim(0) == 8                 # int(0.334 x 24) = 8
    assert mm.MiMoV2Config().rotary_dim(0) == 64  # of the published 192
    assert (cfg.attention_value_scale, cfg.routed_scaling_factor,
            cfg.rms_norm_eps, cfg.n_shared_experts) == (0.707, 1.0, 1e-5, 0)
    spec = MiMoV2Policy.cache_spec(cfg)
    assert spec.layer_windows == (None, 24, 24, None, 24)
    assert spec.kind_pages == {"full": HeadPageShape(1, 24, 16),
                               "window": HeadPageShape(2, 24, 16)}
    assert policy_for(cfg) is MiMoV2Policy
    full, swa = params["layer_0"]["attn"], params["layer_1"]["attn"]
    assert full["wk"]["kernel"].shape == (64, 1, 24)
    assert swa["wk"]["kernel"].shape == (64, 2, 24)
    assert swa["wv"]["kernel"].shape == (64, 2, 16)
    assert swa["wo"]["kernel"].shape == (4, 16, 64)
    assert swa["sink"].shape == (4,) and "sink" not in full
    assert float(swa["sink"].mean()) > 2.0            # the family's draw
    moe = params["layer_1"]["moe"]
    assert moe["gate"]["kernel"].shape == (64, 32)      # the full router
    assert moe["gate"]["bias"].shape == (32,)
    assert moe["experts"]["w_gate"].shape == (8, 64, 32)   # the held share
    assert "shared" not in moe
    assert "mlp" in params["layer_0"] and "moe" not in params["layer_0"]


@pytest.mark.parametrize("key,value", [
    ("scoring_func", "softmax"), ("topk_method", "greedy"), ("n_group", 8),
    ("norm_topk_prob", False), ("n_shared_experts", 1),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4}),
    ("sliding_window_size", 64), ("hidden_act", "gelu"),
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("hybrid_layer_pattern", [0, 1, 1])])
def test_config_from_hf_refuses_by_name(key, value):
    with pytest.raises(ValueError, match="unsupported mimo_v2 config"):
        mm.mimo_v2_config_from_hf(dict(HF, **{key: value}))


def test_hf_names_round_trip_unfused_and_fused(share):
    cfg, _, params = share
    state = mm.export_hf_mimo_v2(params, cfg)
    assert "model.layers.1.mlp.experts.8.gate_proj.weight" in state
    assert "model.layers.1.mlp.experts.0.gate_proj.weight" not in state
    assert state["model.layers.1.self_attn.attention_sink_bias"].shape == (4,)
    assert "model.layers.0.self_attn.attention_sink_bias" not in state
    assert state["model.layers.1.self_attn.k_proj.weight"].shape == (48, 64)
    assert state["model.layers.1.self_attn.v_proj.weight"].shape == (32, 64)
    fused = dict(state)
    for i in range(cfg.num_layers):
        a = f"model.layers.{i}.self_attn."
        fused[a + "qkv_proj.weight"] = np.concatenate(
            [fused.pop(a + f"{n}_proj.weight") for n in "qkv"], axis=0)
    for given in (state, fused):
        back = mm.convert_hf_mimo_v2(given, cfg)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
            np.testing.assert_allclose(a, b, atol=1e-7)
    from deepspeed_tpu.models.hf import from_hf_checkpoint
    _, cfg2, _ = from_hf_checkpoint(HF)
    assert cfg2.held == 32 and cfg2.first_expert == 0


# --- a held share of the experts under sigmoid routing ------------------------

def test_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer(f32):
    """Experts 0-7, 8-15, 16-23 and 24-31 of one layer, each through the
    program's grouped path told which it holds, against the reference's
    uncut layer; and each share against the reference given the same held
    range. There is no shared expert to count once."""
    cfg, _, params = f32
    moe = params["layer_2"]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(5), (24, cfg.hidden_size))
    weights, ids = route(h, moe, cfg)
    layer = family.reference_weights(params)["layers"][2]
    with jax.default_matmul_precision("highest"):
        whole = reference.expert_ffn(h, layer, HF)
        parts, rows = [], []
        for first in (0, 8, 16, 24):
            held = jax.tree.map(lambda w: w[first:first + 8], moe["experts"])
            y, counts = grouped_expert_ffn(h, held, weights, ids, first=first)
            parts.append(y)
            rows.append(counts)
            want = reference.expert_ffn(
                h, dict(layer, experts=jax.tree.map(
                    lambda w: w[first:first + 8], layer["experts"])),
                dict(HF, first_expert_held=first))
            np.testing.assert_allclose(y, want, atol=1e-5)
    np.testing.assert_allclose(sum(parts), whole, atol=1e-5)
    # every assignment lands on exactly one chip, under its local id
    assert int(sum(r.sum() for r in rows)) == 24 * 4
    np.testing.assert_array_equal(
        np.concatenate(rows), np.bincount(np.asarray(ids).reshape(-1),
                                          minlength=32))
    assert all(float(jnp.abs(p).max()) > 0.01 for p in parts)
    # the weights are the chosen scores normalised to sum 1 (scaling 1)
    np.testing.assert_allclose(jnp.sum(weights, -1), 1.0, atol=1e-6)


def test_the_served_expert_layer_runs_over_its_held_share(share):
    """``_dense_or_experts`` hands ``first_expert`` on: the policy's expert
    sublayer on experts 8-15 is the reference's over the same share."""
    from deepspeed_tpu.inference.v2.modules import _dense_or_experts
    cfg, _, params = share
    lp = params["layer_2"]
    x = jax.random.normal(jax.random.PRNGKey(6), (20, cfg.hidden_size))
    y, counts = _dense_or_experts(lp, 2, x, cfg, jnp.ones((20,), bool))
    layer = family.reference_weights(params)["layers"][2]
    with jax.default_matmul_precision("highest"):
        want = reference.expert_ffn(
            reference.mlp_input(x, layer["mlp_norm"], eps=1e-5), layer,
            HF_SHARE)
    np.testing.assert_allclose(y, want, atol=1e-5)
    held, absent = int(counts[0]), int(counts[3])
    assert held + absent == 20 * 4 and 0 < held < 20 * 4


# --- the kernel at widths of its own, with sinks -------------------------------

@pytest.mark.parametrize("case", [
    #  batch, chunk, heads, kv heads, table blocks, window, sinks, tile
    pytest.param((3, 1, 8, 2, 6, None, False, (8, 8, 2)),
                 id="decode-fold-all-heads"),
    pytest.param((3, 1, 8, 2, 6, 20, True, (8, 8, 2)),
                 id="decode-windowed-sinks"),
    pytest.param((1, 40, 4, 1, 8, None, True, (160, 8, 1)), id="chunk-sinks"),
    pytest.param((1, 40, 8, 2, 8, 20, True, (160, 8, 2)),
                 id="chunk-windowed-sinks"),
    # folds cut into row blocks at these toy widths (the rule's sizes
    # scaled down): a full fold in blocks of 64 rows that span heads over
    # tiles of 16 pages, the first blocks' horizon tiles before the table's
    # end; and behind a window blocks of 32 rows inside one q head, each over
    # the ONE tile of 8 pages that holds what its rows see, from a table
    # entry of its own (the live span of every block but the first begins
    # past entry 0)
    pytest.param((1, 300, 4, 2, 40, None, True, (64, 16, 1)),
                 id="chunk-row-blocks-sinks"),
    pytest.param((1, 256, 4, 2, 40, 24, True, (32, 8, 1)),
                 id="chunk-windowed-row-blocks-sinks")])
def test_paged_kernel_with_unequal_widths_and_sinks_is_the_reference(
        case, monkeypatch):
    """Keys of 128 lanes beside values of 256 (and, behind the window's row
    blocks, of 256 beside 128 as MiMo stores them) in K and V pools of their
    own, a sink a query head in the denominator, a softmax scale that is not
    the stored width's; decode folds that take every KV head in a step and
    chunks cut into row blocks."""
    b, t, h, hkv, mb, window, sunk, tile = case
    bs, nb, dk, dv = 8, 48, 128, 256
    if t >= 256:
        monkeypatch.setattr(pa, "_DEFAULT_VMEM_BYTES", 0)
        monkeypatch.setattr(pa, "_MAX_ROWS", 64)
        monkeypatch.setattr(pa, "_WINDOW_ROWS", 32)
        if window:
            dk, dv = 256, 128
    assert pa._tile(h // hkv * t, mb, bs, dk, 4, hkv, dv, window) == tile
    key = jax.random.split(jax.random.PRNGKey(b * 100 + t + h), 5)
    q = jax.random.normal(key[0], (b, t, h, dk))
    q = q.at[..., 96:].set(0.0)            # a key of 96 in a row of 128
    k_pages = jax.random.normal(key[1], (hkv, nb, bs, dk))
    v_pages = jax.random.normal(key[2], (hkv, nb, bs, dv))
    tables = jax.random.permutation(key[3], nb - 1)[:b * mb].reshape(b, mb) \
        .astype(jnp.int32)
    start = jnp.asarray([mb * bs - t - 3 * i for i in range(b)], jnp.int32)
    sinks = 2.0 * jax.random.normal(key[4], (h,)) if sunk else None
    how = dict(window=window, sinks=sinks, scale=96 ** -0.5)
    want = pa.paged_attention_reference(q, k_pages, v_pages, tables, start,
                                        **how)
    got = pa.paged_attention(q, k_pages, v_pages, tables, start,
                             interpret=True, **how)
    assert got.shape == (b, t, h, dv)
    np.testing.assert_allclose(got, want, atol=2e-5)
    if sunk:
        # the sink takes mass: the outputs are not the plain softmax's
        plain = pa.paged_attention_reference(
            q, k_pages, v_pages, tables, start, window=window,
            scale=96 ** -0.5)
        assert float(jnp.abs(plain - want).max()) > 0.01
        # a sink far below every score changes nothing
        far = pa.paged_attention(q, k_pages, v_pages, tables, start,
                                 interpret=True, window=window,
                                 sinks=jnp.full((h,), -1e4),
                                 scale=96 ** -0.5)
        np.testing.assert_allclose(far, plain, atol=2e-5)


def test_the_pool_pair_is_read_at_the_layers_own_heads():
    """``paged_attention_pool`` over a K pool and a V pool: layer 1's heads
    start at ``layer * hkv`` in both."""
    bs, nb, hkv = 8, 12, 2
    key = jax.random.split(jax.random.PRNGKey(0), 4)
    k_pool = jax.random.normal(key[0], (3, hkv, nb, bs, 128))
    v_pool = jax.random.normal(key[1], (3, hkv, nb, bs, 256))
    q = jax.random.normal(key[2], (2, 1, 4, 128))
    tables = jnp.asarray([[3, 7, 1], [5, 2, 9]], jnp.int32)
    start = jnp.asarray([20, 13], jnp.int32)
    sinks = jax.random.normal(key[3], (4,))
    got = pa.paged_attention_pool(q, (k_pool, v_pool), 1, tables, start,
                                  sinks=sinks, interpret=True)
    want = pa.paged_attention_reference(q, k_pool[1], v_pool[1], tables,
                                        start, sinks=sinks)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_tile_counts_both_widths():
    # square heads and MiMo's as stored (a key's row of 256, values of 128)
    # take the same steps: a full chunk 2,048 rows over 16 pages, which the
    # wider key no longer halves (the call asks for the VMEM its tile counts
    # to: 29.4 MiB); a decode fold every KV head of its kind in one step
    assert pa._tile(6 * 4096, 260, 64, 128, 2, 8) == (2048, 16, 1)
    assert pa._tile(16 * 4096, 390, 64, 256, 2, 4, 128) == (2048, 16, 1)
    assert pa._vmem_limit(2048, 16, 1, 64, 256, 128, 2) == 30801920
    assert pa._tile(8, 260, 64, 128, 2, 8) == (8, 8, 8)
    assert pa._tile(8, 260, 64, 128, 2, 8, 128) == (8, 8, 8)
    assert pa._tile(16, 390, 64, 256, 2, 4, 128) == (16, 8, 4)
    assert pa._tile(8, 3, 64, 256, 2, 8, 128, 128) == (8, 4, 8)
    # behind the window of 128 a chunk's tile does not widen with the full
    # fold's: 256 rows over the one tile of 8 pages that holds the 383 keys
    # they see wherever in a page the first falls
    assert pa._tile(8 * 4096, 67, 64, 256, 2, 8, 128, 128) == (256, 8, 1)
    # wider values leave fewer rows beside the same key tile
    assert pa._vmem_bytes(2048, 16, 1, 64, 128, 512, 2) \
        > pa._vmem_bytes(2048, 16, 1, 64, 128, 128, 2)
    # a decode call copies a K and a V page a live table entry for all four
    # heads at once: contexts of 24,960 and 100, and 30 padding rows' one
    assert pa.decode_slot_copies([24960, 100], 32, 4, 390, 64, 256, 2, 16,
                                 128) == 2 * (390 + 2 + 30)


# --- the model and the served path against the reference ----------------------

def test_flax_forward_gives_the_reference_logits(f32):
    _, model, params = f32
    toks = tokens(90)
    got = model.apply({"params": params}, {"input_ids": toks[None]},
                      method=model.logits)[0]
    want = reference.logits(family.reference_weights(params), HF, toks)[:]
    np.testing.assert_allclose(got, want, atol=F32_TOL)


@pytest.mark.parametrize("attn_impl", ["gather", "kernel_interpret"])
def test_chunked_prefill_then_decode_past_the_window_gives_the_reference_logits(
        f32, attn_impl):
    """70 tokens in chunks of 32, 32 and 6, then 12 decoded: the window (24)
    is passed in the first chunk, the second and third chunks attend over
    pages the first cached, windowed blocks are given back after every chunk
    and while decoding (positions 70-81 cross block 9's end), and both
    allocators are whole again after the flush."""
    cfg, _, params = f32
    eng = engine(cfg, params, attn_impl)
    assert {k: jax.tree.map(lambda x: x.shape[-1], v)
            for k, v in eng.kv.pool.items()} == {
        "full": {"k": 128, "v": 128}, "window": {"k": 128, "v": 128}}
    assert eng.kv.pool["full"]["k"].shape[:2] == (2, 1)
    assert eng.kv.pool["window"]["k"].shape[:2] == (3, 2)
    free = (eng.kv.allocator.free_blocks, eng.kv.window_allocator.free_blocks)
    assert served_error(cfg, params, eng=eng) < F32_TOL
    assert eng.kv.window_blocks_given_back >= 7
    assert (eng.kv.allocator.free_blocks,
            eng.kv.window_allocator.free_blocks) == free


@pytest.mark.parametrize("attn_impl", ["gather", "kernel_interpret"])
def test_a_mixed_decode_batch_gives_the_reference_logits(share, attn_impl):
    """Four prompts under and over the window, 10 to 70 tokens, prefilled by
    SplitFuse over several steps and decoded together in one batch, on the
    chip's quarter of the experts (8-15 of 32)."""
    cfg, _, params = share
    assert served_error(cfg, params, HF_SHARE, prompts=(70, 30, 10, 45),
                        new_tokens=8, attn_impl=attn_impl) < F32_TOL


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_served_logits_in_bfloat16_stay_inside_the_tolerance(seed):
    cfg, _, params = build(HF_SHARE, jnp.bfloat16, seed)
    assert served_error(cfg, params, HF_SHARE, prompts=(70, 30),
                        new_tokens=8) < BF16_TOL


def _sinks_left_out(layer, kind):
    layer.pop("sink", None)
    return layer


def _windowed_layers_read_with_the_full_layers_kv_heads(layer, kind):
    # every query head of a windowed layer reads KV head 0, as if the layer
    # had the full layers' one head
    if kind == 1:
        layer["k"], layer["v"] = layer["k"][:, :1], layer["v"][:, :1]
    return layer


def _keys_cut_to_the_values_width(layer, kind):
    # q . k over the value's 16 dims of the 24 (and scaled for 16)
    layer["q"], layer["k"] = layer["q"][..., :16], layer["k"][..., :16]
    return layer


CONTROLS = [
    pytest.param(HF, _sinks_left_out, id="sinks-left-out"),
    pytest.param(dict(HF, attention_value_scale=1.0), None,
                 id="value-scale-left-out"),
    pytest.param(dict(HF, rope_theta=HF["swa_rope_theta"]), None,
                 id="full-layers-given-the-windowed-rope-base"),
    pytest.param(dict(HF, swa_rope_theta=HF["rope_theta"]), None,
                 id="windowed-layers-given-the-full-rope-base"),
    pytest.param(HF, _windowed_layers_read_with_the_full_layers_kv_heads,
                 id="kv-heads-of-the-other-kind"),
    pytest.param(HF, _keys_cut_to_the_values_width,
                 id="keys-as-wide-as-values"),
    pytest.param(dict(HF, sliding_window=10 ** 6), None, id="window-ignored"),
    pytest.param(dict(HF, partial_rotary_factor=1.0), None,
                 id="whole-key-rotated"),
]


@pytest.mark.parametrize("hf,change", CONTROLS)
def test_a_reference_with_one_thing_wrong_fails_the_tolerance(f32, hf,
                                                              change):
    cfg, _, params = f32
    assert served_error(cfg, params, hf, change=change) > 100 * F32_TOL


@pytest.mark.parametrize("hf,change", [CONTROLS[0], CONTROLS[2],
                                       CONTROLS[5]])
def test_the_bfloat16_tolerance_still_fails_a_wrong_reference(hf, change):
    cfg, _, params = build(HF_SHARE, jnp.bfloat16, 0)
    hf = dict(hf, **{k: HF_SHARE[k] for k in (
        "n_routed_experts", "published", "first_expert_held")})
    assert served_error(cfg, params, hf, prompts=(70, 30), new_tokens=8,
                        change=change) > 1.5 * BF16_TOL


def test_a_reference_of_another_share_fails_the_tolerance(share):
    cfg, _, params = share
    assert served_error(cfg, params, dict(HF_SHARE, first_expert_held=0)) \
        > 100 * F32_TOL


# --- counts, scopes ------------------------------------------------------------

def test_bytes_by_kind_and_counts_ride_on_the_tick(share):
    from deepspeed_tpu.telemetry.tracer import get_tracer
    cfg, _, params = share
    tracer = get_tracer()
    was = tracer.enabled
    tracer.configure(enabled=True)      # the ring's capacity stays as it is
    tracer.clear()
    try:
        eng = engine(cfg, params)
        eng.generate(tokens(40).tolist(), max_new_tokens=3)
        events = tracer.events_snapshot()
    finally:
        tracer.configure(enabled=was)
    chunks = [e[7] for e in events if e[1] == "serve/prefill_chunk"]
    decodes = [e[7] for e in events if e[1] == "serve/step_decode"]
    assert [(c["tokens"], c["start"]) for c in chunks] == [(32, 0), (8, 32)]
    # 40 tokens x top-4 x 4 expert layers, each on this chip or another
    assert chunks[1]["expert_rows"] + chunks[1]["expert_rows_absent"] \
        == 40 * 4 * 4
    assert all(d["ctx_tokens_windowed"] == 24 < d["ctx_tokens"]
               for d in decodes)
    kv = eng.kv
    assert all(
        {k: d[k] for k in ("slot_copies", "slot_copies_windowed")}
        == kv.decode_slot_copies([d["ctx_tokens"]], d["bucket"],
                                 d["ctx_blocks"], 24)
        for d in decodes)
    # a chunk's span says what the kernel multiplied and copied for it
    assert all(
        {k: c[k] for k in ("tile_keys", "tile_keys_windowed", "tile_copies")}
        == kv.chunk_tile_keys(c["start"], c["bucket"],
                              eng._ctx_bucket_blocks(c["start"] + c["tokens"]),
                              24)
        and c["tile_keys_windowed"] > 0 for c in chunks)
    # a block of each kind, from its own heads and its rows as stored: 2
    # full layers x 1 head and 3 windowed x 2, K and V rows of 128 lanes
    assert kv._block_bytes == {"full": 2 * 1 * BLOCK * 256 * 4,
                               "window": 3 * 2 * BLOCK * 256 * 4}
    pages = [e[7] for e in events if e[1] == "serve/kv_pages"]
    assert pages and {"full_blocks", "window_blocks", "full_bytes",
                      "window_bytes", "held_bytes", "live_tokens"} \
        <= {k[3:] for k in pages[-1]}
    for p in pages:
        assert p["kv_full_bytes"] == p["kv_full_blocks"] * 2 * BLOCK * 256 * 4
        assert p["kv_window_bytes"] == \
            p["kv_window_blocks"] * 6 * BLOCK * 256 * 4
        assert p["kv_held_bytes"] == p["kv_full_bytes"] + p["kv_window_bytes"]
    assert eng.last_step_counters["kv_full_bytes"] > 0


def test_step_programs_open_a_scope_a_layer_kind(f32):
    from deepspeed_tpu.inference.v2.generic_decode import decode_step_g
    cfg, _, params = f32
    eng = engine(cfg, params)
    text = decode_step_g.lower(
        params, eng.kv.pool, jnp.zeros((2,), jnp.int32),
        jnp.zeros((2,), jnp.int32),
        {"full": jnp.zeros((2, 4), jnp.int32),
         "window": jnp.zeros((2, 4), jnp.int32)}, jnp.ones((2,), bool),
        policy=MiMoV2Policy, cfg=cfg, block_size=BLOCK,
        attn_impl="gather").as_text(debug_info=True)
    for scope in ("attn/full", "attn/window", "attn/qkv", "attn/kv_write",
                  "attn/paged", "attn/out", "moe/router", "moe/experts",
                  "mlp"):
        assert scope in text, scope
