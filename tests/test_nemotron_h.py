"""Nemotron-H (``model_type: nemotron_h``) at tiny widths on the CPU, float32:
the engine's logits against the plain reference
(``benchmarks/reference/nemotron_h.py``: the recurrence token by token, every
token through every held expert), a layer that is ONE mixer (a state in a
slot, pages, or nothing at all in one cache manager), ``B`` and ``C`` by
group, the ungated expert through the grouped matmul, a held share of the
experts, and the checkpoint's names.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import nemotron_h as family
from benchmarks.reference import nemotron_h as reference
from deepspeed_tpu.inference.v2 import engine_v2, modules
from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  V2EngineConfig)
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models import nemotron_h as nh
from deepspeed_tpu.moe import grouped_experts as ge
from deepspeed_tpu.ops import ssm
from deepspeed_tpu.ops.pallas import grouped_matmul as gmm
from deepspeed_tpu.ops.pallas import ssm_update as su

CFG = dataclasses.replace(nh.TINY_NEMOTRON_H, dtype=jnp.float32)
HF = {"model_type": "nemotron_h", "vocab_size": CFG.vocab_size,
      "hidden_size": CFG.hidden_size, "num_hidden_layers": CFG.num_layers,
      "hybrid_override_pattern": CFG.pattern, "num_attention_heads": 4,
      "num_key_value_heads": 2, "head_dim": 32, "mamba_num_heads": 8,
      "mamba_head_dim": 8, "ssm_state_size": 16, "n_groups": 4,
      "conv_kernel": 4, "chunk_size": 16, "expand": 2,
      "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 48,
      "n_routed_experts": 16, "n_shared_experts": 1,
      "num_experts_per_tok": 3, "routed_scaling_factor": 2.5, "n_group": 1,
      "topk_group": 1, "norm_topk_prob": True, "mlp_hidden_act": "relu2",
      "mamba_hidden_act": "silu", "use_conv_bias": True, "use_bias": False,
      "mamba_proj_bias": False, "attention_bias": False, "mlp_bias": False,
      "tie_word_embeddings": False, "layer_norm_epsilon": 1e-5,
      "max_position_embeddings": 512}
TOL = 1e-4


@pytest.fixture(scope="module")
def params():
    model = nh.NemotronHForCausalLM(CFG)
    return model.init(jax.random.PRNGKey(0),
                      {"input_ids": np.zeros((1, 8), np.int32)})["params"]


@pytest.fixture(scope="module")
def weights(params):
    return family.reference_weights(params)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n).tolist()


def engine(params, impl="gather", budget=64, cfg=CFG, **kw):
    """Blocks of 8 tokens, four slots, a step's budget of ``budget`` tokens:
    a prompt longer than it is cut there, at no multiple of the scan's block
    of 16 unless the budget is one."""
    return InferenceEngineV2(params, cfg, V2EngineConfig(
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
    model = nh.NemotronHForCausalLM(CFG)
    got = model.apply({"params": params}, {"input_ids": np.asarray([toks])},
                      method=model.logits)[0]
    want = reference.logits(weights, HF, toks)[:]
    np.testing.assert_allclose(got, want, atol=TOL)
    assert reference.loss(weights, HF, [toks]) == pytest.approx(
        float(model.apply({"params": params},
                          {"input_ids": np.asarray([toks])})), abs=TOL)


@pytest.mark.parametrize("impl", ["gather", "kernel_interpret"])
@pytest.mark.parametrize("budget,chunks", [(64, 1), (37, 2), (23, 3)])
def test_engine_logits_are_the_references(params, weights, handed, impl,
                                          budget, chunks, monkeypatch):
    """A prompt of 61 tokens prefilled in one chunk, or cut at 37, or at 23
    and 46 (no multiple of the scan's block of 16, of a bucket or of a page),
    then 12 tokens decoded through slots and pages: every row of logits the
    sampler is handed is the reference's full forward pass at that position.
    With the Pallas kernels in interpret mode the experts go through the
    grouped matmul too."""
    if impl == "kernel_interpret":
        monkeypatch.setattr(modules, "_expert_matmul_impl", lambda: impl)
        jax.clear_caches()
    prompt = tokens(61)
    eng = engine(params, impl, budget)
    got = generate(eng, 1, prompt, 12)
    assert eng.sched_stats()["chunks_total"] == chunks
    want = np.asarray(reference.logits(weights, HF, prompt + got)[:])
    rows = [row for batch in handed for row in batch]
    assert len(rows) >= 12
    for i, row in enumerate(rows[-12:]):
        np.testing.assert_allclose(row, want[60 + i], atol=TOL)
    if impl == "kernel_interpret":
        jax.clear_caches()


def test_wave_equals_solo_and_a_reused_slot_equals_a_fresh_engine(params):
    prompts = [tokens(n, seed=n) for n in (40, 9, 27)]
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
    # the slots' last holders left their states behind: position 0 starts
    # from zeros whoever held the slot
    assert generate(eng, 9, prompts[1], 6) == solo[1]


def test_one_cache_manager_keeps_a_state_pages_and_nothing(params):
    eng = engine(params)
    spec = eng.policy.cache_spec(CFG)
    assert spec.layer_kinds == ("state", "none", "state", "full", "none",
                                "state")
    assert spec.state_slot.groups == 4 and spec.state_slot.pack == 2
    pool = eng.kv.pool
    # three state layers, one attention layer, and the expert layers nowhere
    assert pool["state"]["ssm"].shape == (3, 5, 4, 16, 16)
    assert pool["state"]["conv"].shape == (3, 5, 3, 64 + 2 * 4 * 16)
    assert pool["full"].shape[0] == 1 and set(pool) == {"full", "state"}
    eng.put([1, 2], [tokens(20), tokens(9)])
    c = eng.last_step_counters
    assert c["state_slots_held"] == 2
    assert c["kv_state_bytes"] == 2 * eng.kv.slot_bytes \
        == 2 * 3 * (8 * 8 * 16 * 4 + 3 * 192 * 4)
    # the fused step stays off over a state kind
    assert eng._fused_decode is None


# --- B and C by group --------------------------------------------------------

def _per_head(x, dt, a_log, bm, cm, s0):
    """The recurrence a head at a time, each from its own group's B and C,
    through the ONE-group form (granite's)."""
    rep = x.shape[1] // bm.shape[1]
    ys, ss = zip(*(ssm.ssm_token_scan(
        x[:, j:j + 1], dt[:, j:j + 1], a_log[j:j + 1],
        bm[:, j // rep:j // rep + 1], cm[:, j // rep:j // rep + 1],
        s0[j:j + 1]) for j in range(x.shape[1])))
    return jnp.concatenate(ys, axis=1), jnp.concatenate(ss, axis=0)


@pytest.mark.parametrize("groups", [1, 2, 8])
@pytest.mark.parametrize("rows", [3, 128, 161])
def test_grouped_scan_is_a_per_head_loop(groups, rows):
    """``G = 8`` against a loop over heads; ``G = 1`` is a case of the same
    code and what granite's layers run. 161 rows end off a block of 128."""
    k = jax.random.split(jax.random.PRNGKey(rows + groups), 6)
    h, p, n = 8, 8, 16
    x = jax.random.normal(k[0], (rows, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (rows, h)) - 2)
    a_log = jnp.log(jax.random.uniform(k[2], (h,), minval=1, maxval=16))
    bm = jax.random.normal(k[3], (rows, groups, n))
    cm = jax.random.normal(k[4], (rows, groups, n))
    s0 = jax.random.normal(k[5], (h, p, n))
    want_y, want_s = _per_head(x, dt, a_log, bm, cm, s0)
    scale = float(jnp.abs(want_y).max())
    for got_y, got_s in (ssm.ssm_token_scan(x, dt, a_log, bm, cm, s0),
                         ssm.ssm_chunk_scan(x, dt, a_log, bm, cm, s0, 128)):
        np.testing.assert_allclose(got_y, want_y, atol=1e-5 * scale)
        np.testing.assert_allclose(got_s, want_s, atol=1e-5 * scale)


@pytest.mark.parametrize("heads,head_dim,d_state,groups", [
    (8, 8, 16, 4), (8, 8, 16, 1), (64, 64, 128, 8), (8, 64, 128, 8)])
def test_update_kernel_reads_each_heads_own_group(heads, head_dim, d_state,
                                                  groups):
    """The Pallas kernel in interpret mode and gather, update, scatter
    against the definition with B and C by group: a tile of packs that holds
    several groups (64 heads), one group over several packs, and a head a
    group (no two heads of different groups share a pack's lanes)."""
    k = jax.random.split(jax.random.PRNGKey(groups), 6)
    pack = su.state_pack(heads, head_dim, groups)
    assert (heads // groups) % pack == 0
    slots_n, b = 3, 3
    pool = jax.random.normal(k[0], (2, slots_n + 1, heads // pack, d_state,
                                    pack * head_dim))
    slots = jnp.asarray([2, 0, slots_n], jnp.int32)
    x = jax.random.normal(k[1], (b, heads, head_dim))
    dt = jax.nn.softplus(jax.random.normal(k[2], (b, heads)))
    a_log = jnp.log(jax.random.uniform(k[3], (heads,), minval=1, maxval=16))
    bm = jax.random.normal(k[4], (b, groups, d_state))
    cm = jax.random.normal(k[5], (b, groups, d_state))
    y0, p0 = su.ssm_update_reference(pool, 1, slots, x, dt, a_log, bm, cm)
    y1, p1 = su.ssm_update(pool, 1, slots, x, dt, a_log, bm, cm,
                           interpret=True)
    np.testing.assert_allclose(y1, y0, atol=1e-5)
    np.testing.assert_allclose(p1[:, :slots_n], p0[:, :slots_n], atol=1e-6)
    rep = heads // groups
    bh, ch = (jnp.repeat(v, rep, axis=1) for v in (bm, cm))   # [B, H, N]
    s0 = su.unpack_state(pool[1, slots], pack)
    s1 = jnp.exp(-dt * jnp.exp(a_log))[..., None, None] * s0 \
        + (dt[..., None] * x)[..., None] * bh[:, :, None, :]
    np.testing.assert_allclose(y0, jnp.einsum("bhpn,bhn->bhp", s1, ch),
                               atol=1e-4)
    np.testing.assert_allclose(su.unpack_state(p0[1, slots[:2]], pack),
                               s1[:2], atol=1e-6)


def test_gated_norm_normalises_each_group_alone():
    from deepspeed_tpu.models.granite_hybrid import gated_norm
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    y, z = jax.random.normal(k[0], (5, 32)), jax.random.normal(k[1], (5, 32))
    scale = jax.random.normal(k[2], (32,))
    got = gated_norm(y, z, scale, 1e-5, 4)
    g = (y * jax.nn.silu(z)).reshape(5, 4, 8)
    want = (g / jnp.sqrt(jnp.mean(g * g, -1, keepdims=True) + 1e-5)
            ).reshape(5, 32) * scale
    np.testing.assert_allclose(got, want, atol=1e-6)
    whole = gated_norm(y, z, scale, 1e-5)
    assert float(jnp.abs(whole - got).max()) > 1e-2


# --- the ungated expert ------------------------------------------------------

@pytest.mark.parametrize("rows,width", [(48, 200), (6, 24), (300, 136)])
def test_ungated_grouped_entry_point_is_ragged_dot(rows, width):
    """``grouped_relu2_in`` (the kernel in interpret mode, the stack [E, F,
    D]) against ``relu(ragged_dot)^2`` at widths that are no multiple of 128,
    and the whole ungated expert through both."""
    k = jax.random.split(jax.random.PRNGKey(width), 5)
    e, d = 4, 128
    xs = jax.random.normal(k[0], (rows, d))
    w_in = jax.random.normal(k[1], (e, width, d)) / d ** 0.5
    w_down = jax.random.normal(k[2], (e, width, d)) / width ** 0.5
    counts = jnp.asarray([rows // 2, 0, rows // 4, rows // 8], jnp.int32)
    live = int(counts.sum())
    want = ge.relu2(jax.lax.ragged_dot(xs, jnp.swapaxes(w_in, 1, 2), counts))
    got = gmm.grouped_relu2_in(xs, w_in, counts, interpret=True)
    np.testing.assert_allclose(got[:live], want[:live], atol=1e-4)
    # several row tiles and a cut contraction
    cut = gmm.grouped_relu2_in(xs, w_in, counts, tiles=(8, 64, width),
                               interpret=True) if rows % 8 == 0 else got
    np.testing.assert_allclose(cut[:live], want[:live], atol=1e-4)
    ids = jax.random.randint(k[3], (rows, 2), 0, e + 2)     # some not held
    weights = jax.random.uniform(k[4], (rows, 2))
    experts = {"w_in": w_in, "w_down": w_down}
    plain, n0 = ge.grouped_expert_ffn(xs, experts, weights, ids)
    kernel, n1 = ge.grouped_expert_ffn(
        xs, experts, weights, ids,
        matmul=lambda *a: gmm.grouped_matmul(*a, interpret=True),
        gate_up=lambda *a: gmm.grouped_relu2_in(*a, interpret=True))
    np.testing.assert_allclose(kernel, plain, atol=1e-4)
    np.testing.assert_array_equal(n0, n1)
    dense = sum(jnp.where((ids == j).any(-1, keepdims=True), 1.0, 0.0)
                * jnp.sum(jnp.where(ids == j, weights, 0.0), -1,
                          keepdims=True)
                * (ge.relu2(xs @ w_in[j].T) @ w_down[j]) for j in range(e))
    np.testing.assert_allclose(plain, dense, atol=1e-4)


def test_two_halves_and_the_shared_expert_once_add_up_to_the_uncut_layer(
        params, weights):
    """One chip's experts 0-7 with the shared expert, the other's 8-15
    without it: together the uncut layer, in the program and in the
    reference alike."""
    moe = params["layer_1"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 19, CFG.hidden_size))

    def part(first, held, shared):
        cfg = dataclasses.replace(CFG, first_expert=first, experts_held=held,
                                  n_shared_experts=int(shared))
        mine = {"gate": moe["gate"], "experts": {
            name: w[first:first + held]
            for name, w in moe["experts"].items()}}
        if shared:
            mine["shared"] = moe["shared"]
        return nh.NemotronHMoE(cfg).apply({"params": mine}, x)[0]
    whole = part(0, 16, True)
    np.testing.assert_allclose(part(0, 8, True) + part(8, 8, False), whole,
                               atol=1e-5)
    assert float(jnp.abs(part(0, 8, True) - whole).max()) > 1e-3
    # the reference, given the same shares
    layer = {**weights["layers"][1], "norm": jnp.ones((CFG.hidden_size,))}
    rms = x[0] / jnp.sqrt(jnp.mean(x[0] ** 2, -1, keepdims=True) + 1e-5)

    def ref_part(first, held, shared):
        mine = {**layer, "experts": {name: w[first:first + held] for name, w
                                     in layer["experts"].items()}}
        if not shared:
            del mine["shared"]
        return reference.expert_mixer(x[0], mine,
                                      {**HF, "first_expert_held": first})
    np.testing.assert_allclose(ref_part(0, 8, True) + ref_part(8, 8, False),
                               ref_part(0, 16, True), atol=1e-5)
    np.testing.assert_allclose(
        ref_part(0, 16, True),
        nh.NemotronHMoE(CFG).apply({"params": moe}, rms[None])[0], atol=1e-4)


def test_a_held_share_serves_through_the_engine(params, weights, handed):
    """Experts 8-15 of the router's 16 held here: the engine's logits are
    the reference's over the same share."""
    cfg = dataclasses.replace(CFG, first_expert=8, experts_held=8)
    mine = {name: (layer if "moe" not in layer else {**layer, "moe": {
        **layer["moe"], "experts": {n: w[8:] for n, w in
                                    layer["moe"]["experts"].items()}}})
            for name, layer in params.items()}
    prompt = tokens(33, seed=3)
    got = generate(engine(mine, cfg=cfg), 1, prompt, 4)
    want = np.asarray(reference.logits(
        family.reference_weights(mine), {**HF, "first_expert_held": 8},
        prompt + got)[:])
    for i, row in enumerate([r for b in handed for r in b][-4:]):
        np.testing.assert_allclose(row, want[32 + i], atol=TOL)
    whole = np.asarray(reference.logits(weights, HF, prompt + got)[:])
    assert np.abs(whole[32] - want[32]).max() > 1e-2


# --- HF interop ----------------------------------------------------------------

def test_config_from_hf_and_what_is_refused_by_name():
    from deepspeed_tpu.models.hf import from_hf_checkpoint
    model, cfg, none = from_hf_checkpoint(HF)
    assert none is None and cfg == dataclasses.replace(
        CFG, dtype=cfg.dtype, experts_held=16)
    assert (cfg.d_inner, cfg.conv_channels, cfg.in_proj_width,
            cfg.norm_groups, cfg.held) == (64, 192, 264, 4, 16)
    # ``expand`` is not read: 2 x 64 is not the inner width
    assert cfg.d_inner != HF["expand"] * HF["hidden_size"]
    share = nh.nemotron_h_config_from_hf({**HF, "n_routed_experts": 8},
                                         router_experts=16, first_expert=8)
    assert (share.n_routed_experts, share.held, share.first_expert) == \
        (16, 8, 8)
    for key, value, said in (
            ("hybrid_override_pattern", "M-M*EM", "dense '-' MLP"),
            ("hybrid_override_pattern", "MEM*E", "another depth"),
            ("mlp_hidden_act", "silu", "mlp_hidden_act"),
            ("n_group", 4, "n_group"),
            ("attention_bias", True, "attention_bias"),
            ("use_conv_bias", False, "use_conv_bias"),
            ("tie_word_embeddings", True, "tie_word_embeddings")):
        with pytest.raises(ValueError, match=said):
            from_hf_checkpoint({**HF, key: value})
    with pytest.raises(ValueError, match="not among the router's"):
        nh.nemotron_h_config_from_hf(HF, router_experts=16, first_expert=8)


def test_converter_reads_the_published_names(params):
    """A made-up state dict under the published model code's names (linears
    [out, in], the depthwise convolution [C, 1, K], an expert a module of its
    own) becomes the tree the engine serves, and back."""
    state = nh.export_hf_nemotron_h(params, CFG)
    m = "backbone.layers.0.mixer."
    assert state[m + "in_proj.weight"].shape == (CFG.in_proj_width, 64)
    assert state[m + "conv1d.weight"].shape == (CFG.conv_channels, 1, 4)
    assert state[m + "A_log"].shape == state[m + "D"].shape == (8,)
    assert state[m + "norm.weight"].shape == (CFG.d_inner,)
    e = "backbone.layers.1.mixer."
    assert state[e + "gate.weight"].shape == (16, 64)
    assert state[e + "gate.e_score_correction_bias"].shape == (16,)
    assert state[e + "experts.15.up_proj.weight"].shape == (24, 64)
    assert state[e + "experts.15.down_proj.weight"].shape == (64, 24)
    assert state[e + "shared_experts.up_proj.weight"].shape == (48, 64)
    assert e + "experts.0.gate_proj.weight" not in state
    a = "backbone.layers.3.mixer."
    assert state[a + "q_proj.weight"].shape == (4 * 32, 64)
    assert state[a + "k_proj.weight"].shape == (2 * 32, 64)
    assert state["lm_head.weight"].shape == (512, 64)
    assert set(k.split(".")[3] for k in state if ".layers." in k) == \
        {"norm", "mixer"}
    back = nh.convert_hf_nemotron_h(state, CFG)
    flat = {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    got = {jax.tree_util.keystr(k): v for k, v in
           jax.tree_util.tree_flatten_with_path(back)[0]}
    assert set(flat) == set(got)
    for name, value in flat.items():
        np.testing.assert_allclose(got[name], value, atol=1e-7, err_msg=name)
    # a held share reads its own experts' modules alone
    share = dataclasses.replace(CFG, first_expert=8, experts_held=8)
    held = nh.convert_hf_nemotron_h(state, share)
    np.testing.assert_allclose(held["layer_1"]["moe"]["experts"]["w_in"],
                               params["layer_1"]["moe"]["experts"]["w_in"][8:],
                               atol=1e-7)
