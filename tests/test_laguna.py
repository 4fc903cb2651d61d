"""Laguna against its plain reference (``benchmarks/reference/laguna.py``),
at a tiny size on seeded weights: hidden 64, a leading dense full layer and
one period (sliding, sliding, sliding, full) of 4 and 6 query heads over 2 KV
heads of 16, window 24 over blocks of 8, a router over 16 experts top-4 with
a shared expert, YaRN on half of the head in full layers.

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

from benchmarks.families import laguna as family
from benchmarks.reference import laguna as reference
from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  V2EngineConfig)
from deepspeed_tpu.inference.v2.kv_cache import TwoPageKindsError
from deepspeed_tpu.inference.v2.modules import (LagunaPolicy, _rope_rows,
                                                policy_for)
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models import laguna as lm
from deepspeed_tpu.models.llama import YarnScaling, rope_freqs
from deepspeed_tpu.moe.grouped_experts import grouped_expert_ffn
from deepspeed_tpu.runtime.precision import cast_to_compute

HF = {
    "model_type": "laguna", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 512, "attention_bias": False,
    "rms_norm_eps": 1e-6, "num_experts": 16, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 24,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 64, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.2079441541679836,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": ["full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention",
                    "full_attention"],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "gating_types": ["per_head"] * 5,
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
    "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0}
#: one chip of two: the router's 16, this chip's experts 8-15
HF_HALF = dict(HF, num_experts=8, published={"num_experts": 16},
               first_expert_held=8)
BLOCK = 8
#: float32: the program and the reference differ in the order of their
#: reductions only
F32_TOL = 2e-4
#: bfloat16 weights and activations against the reference's float32 math on
#: the same bfloat16 weights, on logits of +-4.5, the chip's half of the
#: experts held: sound 0.12-0.48 over seeds 0-7 (0.12-0.19 over the four
#: used below). A near-tie at the router's cut that bfloat16 states break the
#: other way than float32 ones moves a whole expert's part where one of the
#: pair is the absent chip's, so a held share reads about twice what the
#: whole layer does (0.14-0.25), and at 64 wide a bfloat16 state is several
#: times noisier against its size than at the published 3,072. The same
#: controls in bfloat16, seeds 0-3: the window ignored 2.6-3.2, the gate left
#: out 4.6-5.3, the shared expert left out 4.1-4.8
BF16_TOL = 0.8


def build(hf=HF, dtype=jnp.float32, seed=0):
    cfg, _ = family.build(hf, "serve", {"max_context": 512})
    cfg = dataclasses.replace(cfg, dtype=dtype)
    model = lm.LagunaForCausalLM(cfg)
    params = cast_to_compute(model.init(
        jax.random.PRNGKey(seed),
        {"input_ids": np.zeros((1, 8), np.int32)})["params"], dtype)
    return cfg, model, params


@pytest.fixture(scope="module")
def f32():
    return build()


@pytest.fixture(scope="module")
def half():
    return build(HF_HALF)


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
        weights = dict(weights, layers=[change(dict(layer))
                                        for layer in weights["layers"]])
    worst = 0.0
    for prompt, (toks, rows) in zip(prompts, served(eng, prompts,
                                                    new_tokens)):
        seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
        want = reference.logits(weights, hf, seq)[len(prompt) - 1:]
        worst = max(worst, float(jnp.max(jnp.abs(rows - want))))
    return worst


# --- the pieces, by hand -----------------------------------------------------

def test_yarn_partial_rope_against_a_closed_form_table():
    """A rotary part of 8 dims at theta 500,000, factor 8 over an original
    length of 64: the correction dims are 8 ln(64 / (2 pi n)) / (2 ln
    500000) at n = 32 and 1, so the ramp runs over dims 0 .. 1 (floor
    -0.35 -> 0, ceil 0.71 -> 1): pair 0 keeps its frequency, pairs 1-3 are
    divided by 8, and cos and sin carry 0.1 ln 8 + 1."""
    yarn = YarnScaling(8.0, 64, 32.0, 1.0, None)
    cos, sin = rope_freqs(8, 32, 500000.0, yarn)
    base = 500000.0 ** (-np.arange(4) / 4.0)
    inv = base * np.array([1.0, 1 / 8, 1 / 8, 1 / 8])
    scale = 0.1 * np.log(8.0) + 1.0
    angles = np.outer(np.arange(32), inv)
    np.testing.assert_allclose(cos, np.cos(angles) * scale, atol=1e-6)
    np.testing.assert_allclose(sin, np.sin(angles) * scale, atol=1e-6)
    # the reference's own table, written apart, agrees
    ref_cos, ref_sin = reference.rope_table(
        {"rope_theta": 500000, "rope_type": "yarn", "factor": 8,
         "original_max_position_embeddings": 64, "beta_fast": 32,
         "beta_slow": 1, "partial_rotary_factor": 0.5}, 16, 32)
    np.testing.assert_allclose(ref_cos, cos, atol=1e-6)
    np.testing.assert_allclose(ref_sin, sin, atol=1e-6)
    # an attention_factor the config gives is taken as it is
    cos2, _ = rope_freqs(8, 32, 500000.0, YarnScaling(8.0, 64, 32.0, 1.0, 1.5))
    np.testing.assert_allclose(cos2, np.cos(angles) * 1.5, atol=1e-6)
    from deepspeed_tpu.models.llama import yarn_inv_freq
    inv128 = yarn_inv_freq(64, 500000.0, YarnScaling(128.0, 8192, 32.0, 1.0))
    plain = 500000.0 ** (-np.arange(32) / 32.0)
    # published sizes: pairs before the low correction dim keep their
    # frequency, pairs past the high one are divided by 128
    assert inv128[0] == pytest.approx(plain[0])
    assert inv128[-1] == pytest.approx(plain[-1] / 128)
    assert np.all(np.diff(inv128 / plain) <= 1e-12)


def test_rope_rows_rotate_the_leading_part_and_pass_the_rest():
    cos, sin = rope_freqs(8, 32, 500000.0, YarnScaling(8.0, 64, 32.0, 1.0))
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 2, 16))
    pos = jnp.asarray([0, 5, 31])
    y = _rope_rows(x, jnp.asarray(cos), jnp.asarray(sin), pos)
    np.testing.assert_array_equal(y[..., 8:], x[..., 8:])
    c, s = cos[np.asarray(pos)][:, None], sin[np.asarray(pos)][:, None]
    np.testing.assert_allclose(y[..., :4], x[..., :4] * c - x[..., 4:8] * s,
                               atol=1e-6)
    np.testing.assert_allclose(y[..., 4:8], x[..., 4:8] * c + x[..., :4] * s,
                               atol=1e-6)
    # a whole head rotates as before
    cos, sin = rope_freqs(16, 32, 10000.0)
    z = _rope_rows(x, jnp.asarray(cos), jnp.asarray(sin), pos)
    np.testing.assert_allclose(z, lm.apply_rope_half(x, cos, sin, pos),
                               atol=1e-6)


def test_config_from_hf_keeps_the_layers_kinds_and_the_held_share():
    cfg, _, params = build(HF_HALF)
    assert cfg.layer_types == tuple(HF["layer_types"])
    assert cfg.heads_per_layer == (4, 6, 6, 6, 4)
    assert (cfg.num_experts, cfg.held, cfg.first_expert) == (16, 8, 8)
    assert [cfg.window(i) for i in range(5)] == [None, 24, 24, 24, None]
    assert cfg.rope(0).yarn.factor == 8 and cfg.rope(1).yarn is None
    assert cfg.rope(0).partial_rotary_factor == 0.5
    spec = LagunaPolicy.cache_spec(cfg)
    assert spec.layer_windows == (None, 24, 24, 24, None)
    assert policy_for(cfg) is LagunaPolicy
    moe = params["layer_1"]["moe"]
    assert moe["gate"]["kernel"].shape == (64, 16)          # the full router
    assert moe["experts"]["w_gate"].shape == (8, 64, 32)    # the held half
    assert params["layer_1"]["attn"]["wq"]["kernel"].shape == (64, 6, 16)
    assert params["layer_0"]["attn"]["wg"]["kernel"].shape == (64, 4)
    assert "mlp" in params["layer_0"] and "moe" not in params["layer_0"]


@pytest.mark.parametrize("key,value", [
    ("norm_topk_prob", False), ("moe_router_logit_softcapping", 30.0),
    ("gating", "per-token"), ("attention_bias", True),
    ("decoder_sparse_step", 2), ("tie_word_embeddings", True),
    ("layer_types", ["full_attention"] * 4)])
def test_config_from_hf_refuses_by_name(key, value):
    with pytest.raises(ValueError, match="unsupported laguna config"):
        lm.laguna_config_from_hf(dict(HF, **{key: value}))


def test_hf_names_round_trip(half):
    cfg, _, params = half
    state = lm.export_hf_laguna(params, cfg)
    assert "model.layers.1.mlp.experts.8.gate_proj.weight" in state
    assert "model.layers.1.mlp.experts.0.gate_proj.weight" not in state
    assert state["model.layers.1.self_attn.g_proj.weight"].shape == (6, 64)
    back = lm.convert_hf_laguna(state, cfg)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_allclose(a, b, atol=1e-7)
    from deepspeed_tpu.models.hf import from_hf_checkpoint
    _, cfg2, _ = from_hf_checkpoint(HF)
    assert cfg2.held == 16 and cfg2.first_expert == 0


# --- a held share of the experts ---------------------------------------------

def test_the_two_shares_and_the_shared_expert_add_up_to_the_uncut_layer(f32):
    """Experts 0-7 and 8-15 of one layer, each through the program's grouped
    path told which it holds, plus the shared expert once, against the
    reference's uncut layer; and each share against the reference given the
    same held range."""
    cfg, _, params = f32
    moe = params["layer_2"]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(5), (24, cfg.hidden_size))
    weights, ids = lm.route(h, moe, cfg)
    layer = family.reference_weights(params)["layers"][2]
    no_shared = {k: v for k, v in layer.items() if k != "shared"}
    with jax.default_matmul_precision("highest"):
        whole = reference.expert_ffn(h, layer, HF)
        shared = reference.gated_mlp(h, *(layer["shared"][k] for k in
                                          ("gate", "up", "down")))
        parts, rows = [], []
        for first in (0, 8):
            held = jax.tree.map(lambda w: w[first:first + 8], moe["experts"])
            y, counts = grouped_expert_ffn(h, held, weights, ids, first=first)
            parts.append(y)
            rows.append(counts)
            want = reference.expert_ffn(
                h, dict(no_shared, experts=jax.tree.map(
                    lambda w: w[first:first + 8], layer["experts"])),
                dict(HF, first_expert_held=first))
            np.testing.assert_allclose(y, want, atol=1e-5)
    np.testing.assert_allclose(parts[0] + parts[1] + shared, whole, atol=1e-5)
    # every assignment lands on exactly one chip, under its local id
    assert int(rows[0].sum() + rows[1].sum()) == 24 * 4
    np.testing.assert_array_equal(
        np.concatenate(rows), np.bincount(np.asarray(ids).reshape(-1),
                                          minlength=16))
    assert float(jnp.abs(parts[0]).max()) > 0.01     # neither share is empty
    assert float(jnp.abs(parts[1]).max()) > 0.01


@pytest.mark.parametrize("valid", [None, [True] * 20 + [False] * 4],
                         ids=["all-rows", "padding-left-out"])
def test_a_held_share_with_the_kernel_equals_it_with_ragged_dot(f32, valid):
    import functools

    from deepspeed_tpu.ops.pallas import grouped_matmul as gmm
    cfg, _, params = f32
    moe = params["layer_3"]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(6), (24, cfg.hidden_size))
    weights, ids = lm.route(h, moe, cfg)
    held = jax.tree.map(lambda w: w[4:12], moe["experts"])
    mask = None if valid is None else jnp.asarray(valid)
    want, rows = grouped_expert_ffn(h, held, weights, ids, mask, first=4)
    got, rows_k = grouped_expert_ffn(
        h, held, weights, ids, mask, first=4,
        matmul=functools.partial(gmm.grouped_matmul, interpret=True),
        gate_up=functools.partial(gmm.grouped_gate_up, interpret=True))
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_array_equal(rows, rows_k)
    kept = np.asarray(ids[:24 if valid is None else 20]).reshape(-1)
    np.testing.assert_array_equal(
        rows, np.bincount(kept[(kept >= 4) & (kept < 12)] - 4, minlength=8))


# --- the model and the served path against the reference ---------------------

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
    is passed in the first chunk, windowed blocks are given back after every
    chunk and while decoding (positions 70-81 cross block 9's end), and both
    allocators are whole again after the flush."""
    cfg, _, params = f32
    eng = engine(cfg, params, attn_impl)
    free = (eng.kv.allocator.free_blocks, eng.kv.window_allocator.free_blocks)
    assert served_error(cfg, params, eng=eng) < F32_TOL
    assert eng.kv.window_blocks_given_back >= 7
    assert (eng.kv.allocator.free_blocks,
            eng.kv.window_allocator.free_blocks) == free


@pytest.mark.parametrize("attn_impl", ["gather", "kernel_interpret"])
def test_a_mixed_decode_batch_gives_the_reference_logits(half, attn_impl):
    """Four prompts under and over the window, 10 to 70 tokens, prefilled by
    SplitFuse over several steps and decoded together in one batch, on the
    chip's half of the experts (8-15 of 16)."""
    cfg, _, params = half
    assert served_error(cfg, params, HF_HALF, prompts=(70, 30, 10, 45),
                        new_tokens=8, attn_impl=attn_impl) < F32_TOL


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_served_logits_in_bfloat16_stay_inside_the_tolerance(seed):
    cfg, _, params = build(HF_HALF, jnp.bfloat16, seed)
    assert served_error(cfg, params, HF_HALF, prompts=(70, 30),
                        new_tokens=8) < BF16_TOL


def _without(key):
    def change(layer):
        layer.pop(key, None)
        return layer
    return change


CONTROLS = [
    pytest.param(dict(HF, sliding_window=10 ** 6), None, id="window-ignored"),
    pytest.param(HF, _without("g"), id="gate-left-out"),
    pytest.param(dict(HF, rope_parameters=dict(
        HF["rope_parameters"],
        sliding_attention=HF["rope_parameters"]["full_attention"])), None,
        id="sliding-layers-given-the-full-layers-rope"),
    pytest.param(HF, _without("shared"), id="shared-expert-left-out"),
    pytest.param(dict(HF, moe_routed_scaling_factor=1.0), None,
                 id="routed-scaling-left-out"),
]


@pytest.mark.parametrize("hf,change", CONTROLS)
def test_a_reference_with_one_thing_wrong_fails_the_tolerance(f32, hf,
                                                              change):
    cfg, _, params = f32
    assert served_error(cfg, params, hf, change=change) > 1000 * F32_TOL


@pytest.mark.parametrize("hf,change", CONTROLS[:2])
def test_the_bfloat16_tolerance_still_fails_a_wrong_reference(hf, change):
    cfg, _, params = build(HF_HALF, jnp.bfloat16, 0)
    hf = dict(hf, **{k: HF_HALF[k] for k in ("num_experts", "published",
                                             "first_expert_held")})
    assert served_error(cfg, params, hf, prompts=(70, 30), new_tokens=8,
                        change=change) > 2 * BF16_TOL


def test_a_reference_of_the_other_half_fails_the_tolerance(half):
    cfg, _, params = half
    assert served_error(cfg, params, dict(HF_HALF, first_expert_held=0)) \
        > 1000 * F32_TOL


# --- counts, scopes, refusals ------------------------------------------------

def test_counts_of_held_and_absent_rows_ride_on_the_spans(half):
    from deepspeed_tpu.telemetry.tracer import get_tracer
    cfg, _, params = half
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
    # 40 tokens x top-4 x 4 expert layers, each on this chip or the other
    assert [c["tokens"] for c in chunks] == [32, 8]
    assert chunks[1]["expert_rows"] + chunks[1]["expert_rows_absent"] \
        == 40 * 4 * 4
    assert 0 < chunks[1]["expert_rows"] < 40 * 4 * 4
    # two chunks' counts on the span that waited, 8 held experts a layer
    assert chunks[1]["experts_touched"] <= 2 * 8 * 4
    assert decodes and all(
        d["expert_rows"] + d["expert_rows_absent"] == 4 * 4 for d in decodes)
    # what a windowed layer's decode reads is cut to the window
    assert all(d["ctx_tokens_windowed"] == 24 < d["ctx_tokens"]
               for d in decodes)
    # ... over a table of its own: fewer slot copies a call than a full
    # layer's, and both the kernel's grid times its slots
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
    assert all(0 < d["slot_copies_windowed"] <= d["slot_copies"]
               for d in decodes)
    pages = [e for e in events if e[1] == "serve/kv_pages"]
    assert pages and {"full_blocks", "window_blocks",
                      "window_blocks_given_back", "live_tokens",
                      "held_bytes"} <= {k[3:] for k in pages[-1][7]}
    assert eng.last_step_counters["kv_live_tokens"] > 0


def test_step_programs_open_a_scope_a_layer_kind(f32):
    from deepspeed_tpu.inference.v2.generic_decode import decode_step_g
    cfg, _, params = f32
    eng = engine(cfg, params)
    text = decode_step_g.lower(
        params, eng.kv.pool, jnp.zeros((2,), jnp.int32),
        jnp.zeros((2,), jnp.int32),
        {"full": jnp.zeros((2, 4), jnp.int32),
         "window": jnp.zeros((2, 4), jnp.int32)}, jnp.ones((2,), bool),
        policy=LagunaPolicy, cfg=cfg, block_size=BLOCK,
        attn_impl="gather").as_text(debug_info=True)
    for scope in ("attn/full", "attn/window", "attn/gate", "attn/kv_write",
                  "attn/paged", "moe/router", "moe/experts", "moe/shared"):
        assert scope in text, scope


def test_admission_counts_both_kinds_of_page(f32):
    cfg, _, params = f32
    # the windowed pool follows from the scheduler's limits: one sequence's
    # 4 steady blocks beside a 32-token chunk's 8 and the trash block, 13
    eng = engine(cfg, params, kv_num_blocks=24, scheduler=SchedulerConfig(
        max_tokens_per_step=32, max_decode_batch=2,
        prefill_buckets=(8, 16, 32)))
    kv = eng.kv
    assert kv.window_steady_blocks == 4           # (1 + 24 - 2) // 8 + 2
    assert kv.blocks_needed(100) == 13 + 4 and kv.blocks_needed(10) == 2 + 2
    assert eng.kv_usable_blocks() == 23 + 12
    assert eng.query(7, 100) == (17, 35)
    # the windowed pool keeps a chunk's blocks spare (4 of its 12): a second
    # sequence's four fit beside them and the first's, a third's do not
    assert eng.can_schedule([1], [100])
    eng.admit(1, tokens(100).tolist())
    while eng.state.get(1).in_prefill:
        eng.step()
    assert eng.kv_held_blocks(1) == 13 + 4
    assert not eng.can_schedule([2], [100])       # 13 full blocks short
    assert eng.can_schedule([2], [40])
    eng.admit(2, tokens(40).tolist())
    eng.step()
    eng.step()
    assert not eng.can_schedule([3], [30])        # the windowed pool is short
    eng.flush(1)
    assert eng.can_schedule([3], [30])
    eng.flush(2)
    assert kv.free_blocks == eng.kv_usable_blocks()


def test_speculative_verify_keeps_to_the_window(f32):
    """Prompt-lookup speculation over pages by layer kind: a repeating
    prompt gives proposals, and the greedy tokens are the plain engine's."""
    cfg, _, params = f32
    prompt = (tokens(12).tolist() * 6)[:66]
    plain = engine(cfg, params).generate(prompt, max_new_tokens=10)
    spec = engine(cfg, params, speculative_k=4)
    assert spec.generate(prompt, max_new_tokens=10) == plain
    assert spec.kv.free_blocks == spec.kv_usable_blocks()


@pytest.mark.parametrize("what", ["prefix-cache", "prefix-cache-at-build",
                                  "offload", "handoff-export",
                                  "handoff-import", "fp8-pages"])
def test_what_moves_block_ids_of_one_pool_refuses_by_name(f32, what, tmp_path):
    cfg, _, params = f32
    with pytest.raises(TwoPageKindsError, match="two kinds"):
        if what == "prefix-cache-at-build":
            engine(cfg, params, prefix_cache_enabled=True)
        elif what == "fp8-pages":
            engine(cfg, params, kv_cache_dtype="fp8")
        else:
            eng = engine(cfg, params)
            eng.admit(1, tokens(20).tolist())
            {"prefix-cache": lambda: eng.enable_prefix_cache(),
             "offload": lambda: eng.demote_kv(1),
             "handoff-export": lambda: eng.export_prefix_handoff(
                 str(tmp_path / "h.npz")),
             "handoff-import": lambda: eng.import_prefix_handoff(
                 str(tmp_path / "h.npz"))}[what]()
