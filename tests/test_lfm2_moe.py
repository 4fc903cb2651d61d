"""LFM2-MoE (``model_type: lfm2_moe``) at tiny widths on the CPU, float32: the
flax module and the engine's logits against the plain reference
(``benchmarks/reference/lfm2_moe.py``: the convolution as a three-term sum,
every token through its chosen experts), a state kind whose slot is a
convolution TAIL alone beside pages in one cache manager, the tail-shift
kernel without an activation, the router, and the checkpoint's names.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import lfm2_moe as family
from benchmarks.reference import lfm2_moe as reference
from deepspeed_tpu.inference.v2 import engine_v2, kv_cache, modules
from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  V2EngineConfig)
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models import lfm2_moe as lfm2
from deepspeed_tpu.ops import ssm
from deepspeed_tpu.ops.pallas import ssm_update as su

# heads of 64, two KV heads to a page's row, as published
CFG = dataclasses.replace(lfm2.TINY_LFM2_MOE, head_dim=64, dtype=jnp.float32)
HF = {"model_type": "lfm2_moe", "vocab_size": CFG.vocab_size,
      "hidden_size": CFG.hidden_size, "intermediate_size": 128,
      "moe_intermediate_size": 32, "num_hidden_layers": CFG.num_layers,
      "layer_types": list(CFG.layer_types), "num_attention_heads": 4,
      "num_key_value_heads": 2, "head_dim": 64, "conv_L_cache": 3,
      "conv_bias": False, "num_experts": 8, "num_experts_per_tok": 2,
      "num_dense_layers": 2, "norm_topk_prob": True, "use_expert_bias": True,
      "routed_scaling_factor": 1, "norm_eps": 1e-5,
      "rope_parameters": {"rope_theta": 10000.0, "rope_type": "default"},
      "max_position_embeddings": 512}
TOL = 1e-4


@pytest.fixture(scope="module")
def params():
    model = lfm2.Lfm2MoeForCausalLM(CFG)
    return model.init(jax.random.PRNGKey(0),
                      {"input_ids": np.zeros((1, 8), np.int32)})["params"]


@pytest.fixture(scope="module")
def weights(params):
    return family.reference_weights(params)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n).tolist()


def engine(params, impl="gather", budget=64, **kw):
    """Blocks of 8 tokens, four slots, chunk buckets up to 64."""
    return InferenceEngineV2(params, CFG, V2EngineConfig(
        kv_block_size=8, kv_num_blocks=96, max_tracked_sequences=4,
        scheduler=SchedulerConfig(max_tokens_per_step=budget,
                                  max_decode_batch=4,
                                  prefill_buckets=(16, 32, 64)),
        decode_batch_buckets=(1, 4), ctx_block_buckets=(4, 16),
        attn_impl=impl, **kw))


@pytest.fixture
def handed(monkeypatch):
    """Every [rows, V] logits array the engine hands its sampler, in order."""
    seen = []
    inner = engine_v2.sample_into

    def spy(logits, *args, **kwargs):
        seen.append(np.asarray(logits))
        return inner(logits, *args, **kwargs)
    monkeypatch.setattr(engine_v2, "sample_into", spy)
    return seen


def generate(eng, uid, prompt, new):
    out = eng.put([uid], [prompt])
    got = []
    while len(got) < new:
        if uid in out:
            got.append(out[uid])
        if len(got) < new:
            out = eng.step()
    return got


# --- the model -------------------------------------------------------------

def test_flax_forward_is_the_reference(params, weights):
    toks = tokens(75)
    model = lfm2.Lfm2MoeForCausalLM(CFG)
    got = model.apply({"params": params}, {"input_ids": np.asarray([toks])},
                      method=model.logits)[0]
    want = reference.logits(weights, HF, toks)[:]
    np.testing.assert_allclose(got, want, atol=TOL)
    assert reference.loss(weights, HF, [toks]) == pytest.approx(
        float(model.apply({"params": params},
                          {"input_ids": np.asarray([toks])})), abs=TOL)


# (prompt tokens, the step's budget, chunks): one chunk padded to its bucket;
# a prompt longer than the largest bucket, so a second chunk (11 rows padded
# to 16) starts from the slot's tail; a cut at 23 and 46, off every bucket
# and page; and prompts shorter than the tail's two rows
_ENGINE_CASES = [(61, 64, 1), (75, 64, 2), (61, 23, 3), (1, 64, 1),
                 (2, 64, 1)]


@pytest.mark.parametrize("impl", ["gather", "kernel_interpret"])
@pytest.mark.parametrize("prompt_len,budget,chunks", _ENGINE_CASES)
def test_engine_logits_are_the_references(params, weights, handed, impl,
                                          prompt_len, budget, chunks,
                                          monkeypatch):
    """Prefill, then 12 tokens decoded through tails, pages and experts:
    every row of logits the sampler is handed is the reference's full
    forward pass at that position. With the Pallas kernels in interpret mode
    the experts go through the grouped matmul and the tails through
    ``ssm_conv_step`` without an activation."""
    if impl == "kernel_interpret":
        monkeypatch.setattr(modules, "_expert_matmul_impl", lambda: impl)
        jax.clear_caches()
    prompt = tokens(prompt_len, seed=prompt_len)
    eng = engine(params, impl, budget)
    got = generate(eng, 1, prompt, 12)
    assert eng.sched_stats()["chunks_total"] == chunks
    want = np.asarray(reference.logits(weights, HF, prompt + got)[:])
    rows = [row for batch in handed for row in batch]
    assert len(rows) >= 12
    for i, row in enumerate(rows[-12:]):
        np.testing.assert_allclose(row, want[prompt_len - 1 + i], atol=TOL)
    if impl == "kernel_interpret":
        jax.clear_caches()


def test_wave_equals_solo_and_a_reused_slot_equals_a_fresh_engine(params):
    prompts = [tokens(n, seed=n) for n in (40, 2, 27)]
    solo = [generate(engine(params), 7, p, 6) for p in prompts]
    eng = engine(params, budget=30)
    for uid, p in enumerate(prompts):
        eng.admit(uid, p, max_new_tokens=6)
    got = {uid: [] for uid in range(3)}
    for _ in range(40):
        for uid, tok in eng.step().items():
            got[uid].append(tok)
        if not eng.has_work():
            break
    assert [got[i] for i in range(3)] == solo
    for uid in range(3):
        eng.flush(uid)
    # the slots' last holders left their tails behind: position 0 starts from
    # a zero tail whoever held the slot, even for a prompt shorter than it
    assert generate(eng, 9, prompts[1], 6) == solo[1]


def test_one_cache_manager_keeps_tails_and_pages_and_no_states(params):
    eng = engine(params)
    spec = eng.policy.cache_spec(CFG)
    assert spec.layer_kinds == ("state", "state", "full") * 2
    slot = spec.state_slot
    assert not slot.recurrent and eng.kv.kind.state.activation is None
    assert slot == kv_cache.StateSlotShape.tail_only(3, CFG.hidden_size)
    # a tail alone: 2 rows of the hidden width, no pool of states
    assert slot.layer_bytes(4) == 2 * 64 * 4
    pool = eng.kv.pool
    assert set(pool) == {"full", "state"} and set(pool["state"]) == {"conv"}
    assert pool["state"]["conv"].shape == (4, 5, 2, 64)
    # two KV heads of 64 in one row of 128
    assert pool["full"].shape == (2, 2, 1, 96, 8, 128)
    eng.put([1, 2], [tokens(20), tokens(9)])
    c = eng.last_step_counters
    assert c["state_slots_held"] == 2
    assert c["kv_state_bytes"] == 2 * eng.kv.slot_bytes == 2 * 4 * 2 * 64 * 4
    # the fused step stays off over a state kind, and what a state kind
    # refuses it refuses for a tail too
    assert eng._fused_decode is None and eng.kv.has_state
    with pytest.raises(kv_cache.StateKindError):
        engine(params, speculative_k=2)


def test_published_slot_is_8_kb_a_layer_a_sequence():
    cfg = lfm2.Lfm2MoeConfig()
    slot = modules.Lfm2MoePolicy.cache_spec(cfg).state_slot
    assert slot.layer_bytes(2) == 8192 and slot.tail_stored == (32, 128)
    kinds = kv_cache._LayerKindPages(
        None, layer_kinds=("state", "full"), state_slot=slot)
    assert type(kinds.state) is kv_cache._TailSlots
    pool = jax.eval_shape(lambda: kinds.state.empty(30, 256, jnp.bfloat16))
    assert set(pool) == {"conv"} and pool["conv"].shape == (30, 257, 32, 128)
    # a Mamba-2 layer's slot keeps the class that adds the states
    granite = kv_cache.StateSlotShape(64, 64, 128, 4, 4352)
    assert type(kv_cache._LayerKindPages(
        None, layer_kinds=("state", "full"), state_slot=granite).state) \
        is kv_cache._StateSlots


# --- the tail's shift --------------------------------------------------------

# (rows, channels): the published width, and a toy one that fills no lanes
@pytest.mark.parametrize("rows,channels", [(32, 2048), (4, 24)])
@pytest.mark.parametrize("activation", [None, "silu"])
def test_tail_shift_kernel_is_the_plain_shift(rows, channels, activation):
    """``ssm_conv_step`` in interpret mode, three taps and no bias to speak
    of: the convolved rows are the three-term sum (then silu, where a Mamba
    layer asks for it) and every row's new tail is its old one a row on,
    behind its token; slots no row names keep their bytes."""
    rng = np.random.default_rng(channels)
    stored = su.tail_stored(3, channels)
    pool = jnp.asarray(rng.standard_normal((2, rows + 3) + stored),
                       jnp.bfloat16)
    slots = jnp.asarray(rng.permutation(rows + 2)[:rows], jnp.int32)
    x = jnp.asarray(rng.standard_normal((rows, channels)), jnp.bfloat16)
    taps = jnp.asarray(rng.uniform(-0.5, 0.5, (channels, 3)), jnp.float32)
    bias = jnp.zeros((channels,), jnp.float32)
    got, held = su.ssm_conv_step(pool, 1, slots, x, taps, bias,
                                 activation=activation, interpret=True)
    want, kept = su.ssm_conv_step_reference(pool, 1, slots, x, taps, bias,
                                            activation=activation)
    # to a bfloat16's last bit (the two sums may fuse their products
    # differently), and the pool to the last bit
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=2 ** -7)
    np.testing.assert_array_equal(np.asarray(held, np.float32),
                                  np.asarray(kept, np.float32))
    old = su.unpack_tail(pool[1, slots], 3, channels).astype(jnp.float32)
    plain = taps[:, 0] * old[:, 0] + taps[:, 1] * old[:, 1] \
        + taps[:, 2] * x.astype(jnp.float32)
    if activation == "silu":
        plain = jax.nn.silu(plain)
    np.testing.assert_allclose(np.asarray(got, np.float32), plain,
                               atol=2e-2)
    new = su.unpack_tail(held[1, slots], 3, channels)
    np.testing.assert_array_equal(np.asarray(new[:, 0], np.float32),
                                  np.asarray(old[:, 1]))
    np.testing.assert_array_equal(np.asarray(new[:, 1], np.float32),
                                  np.asarray(x, np.float32))
    np.testing.assert_array_equal(np.asarray(held[0], np.float32),
                                  np.asarray(pool[0], np.float32))


def test_causal_conv_applies_what_it_is_told():
    rng = np.random.default_rng(0)
    x, tail = rng.standard_normal((5, 6)), rng.standard_normal((2, 6))
    w = rng.standard_normal((6, 3))
    plain, rows = ssm.causal_conv(jnp.asarray(x), jnp.asarray(tail),
                                  jnp.asarray(w), activation=None)
    joined = np.concatenate([tail, x])
    want = sum(joined[j:j + 5] * w[:, j] for j in range(3))
    np.testing.assert_allclose(plain, want, atol=1e-5)
    np.testing.assert_allclose(rows, joined, atol=1e-6)
    mamba, _ = ssm.causal_conv(jnp.asarray(x), jnp.asarray(tail),
                               jnp.asarray(w), jnp.ones((6,)))
    np.testing.assert_allclose(mamba, jax.nn.silu(want + 1.0), atol=1e-5)


# --- the router --------------------------------------------------------------

def test_bias_steers_the_choice_and_not_the_weights():
    h = jnp.eye(4, dtype=jnp.float32)[:1]
    kernel = jnp.asarray([[2.0, 1.0, 0.0, -1.0]] + [[0.0] * 4] * 3)
    moe = {"gate": {"kernel": kernel, "bias": jnp.zeros((4,))}}
    cfg = dataclasses.replace(CFG, num_experts=4)
    w0, ids0 = lfm2.route(h, moe, cfg)
    assert ids0.tolist() == [[0, 1]]
    s = jax.nn.sigmoid(kernel[0])
    # the published 1e-6 under the sum (the reference's) and the program's
    # 1e-20 are one weight to float32
    np.testing.assert_allclose(w0[0], s[:2] / (s[0] + s[1] + 1e-6), rtol=1e-6)
    # a bias lifts expert 3 over 0 and 1; its weight is its own score's
    moe["gate"]["bias"] = jnp.asarray([0.0, 0.0, 0.0, 5.0])
    w1, ids1 = lfm2.route(h, moe, cfg)
    assert ids1.tolist() == [[3, 0]]
    np.testing.assert_allclose(w1[0], jnp.asarray([s[3], s[0]])
                               / (s[3] + s[0] + 1e-6), rtol=1e-6)
    # ties go to the lower id, in the program and the reference alike
    tied = {"gate": {"kernel": jnp.zeros((4, 4)), "bias": jnp.zeros((4,))}}
    assert lfm2.route(h, tied, cfg)[1].tolist() == [[0, 1]]
    combine = reference.routing_weights(jnp.full((1, 4), 0.5), jnp.zeros(4),
                                        top_k=2, scaling=1.0)
    assert (np.asarray(combine)[0] > 0).tolist() == [True, True, False, False]
    ref = reference.routing_weights(s[None], jnp.asarray([0.0, 0.0, 0.0, 5.0]),
                                    top_k=2, scaling=1.0)
    np.testing.assert_allclose(np.asarray(ref)[0, [3, 0]], w1[0], rtol=1e-6)


# --- HF interop ----------------------------------------------------------------

def test_config_from_hf_and_what_is_refused_by_name():
    from deepspeed_tpu.models.hf import from_hf_checkpoint
    model, cfg, none = from_hf_checkpoint(HF)
    assert none is None and cfg == dataclasses.replace(CFG, dtype=cfg.dtype)
    assert isinstance(model, lfm2.Lfm2MoeForCausalLM) and cfg.kv_pack == 2
    published = {k: v for k, v in HF.items() if k != "head_dim"}
    assert lfm2.lfm2_moe_config_from_hf(published).head_dim == 64 // 4
    for key, value, said in (
            ("conv_bias", True, "conv_bias true"),
            ("layer_types", ["conv", "mamba"] * 3, "'mamba'"),
            ("layer_types", ["conv"] * 5, "another depth"),
            ("norm_topk_prob", False, "norm_topk_prob"),
            ("use_expert_bias", False, "use_expert_bias"),
            ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"},
             "rope scaling"),
            ("tie_word_embeddings", False, "untied head")):
        with pytest.raises(ValueError, match=said):
            from_hf_checkpoint({**HF, key: value})


def test_converter_reads_the_published_names(params):
    """A made-up state dict under the published model code's names (linears
    [out, in], the depthwise convolution [C, 1, K], an expert a module of its
    own) becomes the tree the engine serves, and back."""
    state = lfm2.export_hf_lfm2_moe(params, CFG)
    c = "model.layers.0."
    assert state[c + "conv.in_proj.weight"].shape == (3 * 64, 64)
    assert state[c + "conv.conv.weight"].shape == (64, 1, 3)
    assert state[c + "conv.out_proj.weight"].shape == (64, 64)
    assert state[c + "feed_forward.w1.weight"].shape == (128, 64)
    assert state[c + "feed_forward.w2.weight"].shape == (64, 128)
    a = "model.layers.2."
    assert state[a + "self_attn.q_proj.weight"].shape == (4 * 64, 64)
    assert state[a + "self_attn.k_proj.weight"].shape == (2 * 64, 64)
    assert state[a + "self_attn.out_proj.weight"].shape == (64, 4 * 64)
    assert state[a + "self_attn.q_layernorm.weight"].shape == (64,)
    assert state[a + "feed_forward.gate.weight"].shape == (8, 64)
    assert state[a + "feed_forward.expert_bias"].shape == (8,)
    assert state[a + "feed_forward.experts.7.w3.weight"].shape == (32, 64)
    assert state[a + "feed_forward.experts.7.w2.weight"].shape == (64, 32)
    assert "lm_head.weight" not in state
    assert set(k.split(".")[3] for k in state if ".layers." in k) == \
        {"operator_norm", "ffn_norm", "conv", "self_attn", "feed_forward"}
    back = lfm2.convert_hf_lfm2_moe(state, CFG)
    flat = {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    got = {jax.tree_util.keystr(k): v for k, v in
           jax.tree_util.tree_flatten_with_path(back)[0]}
    assert set(flat) == set(got)
    for name, value in flat.items():
        np.testing.assert_allclose(got[name], value, atol=1e-7, err_msg=name)
