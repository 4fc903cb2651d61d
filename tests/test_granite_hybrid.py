"""Granite 4.0-H (``model_type: granitemoehybrid``) at tiny widths on the CPU,
float32: the engine's logits against the plain reference
(``benchmarks/reference/granitemoehybrid.py``: the recurrence token by token,
which shares nothing with the program's closed form or its kernel), the
recurrent state as a layer kind of the one cache manager (a slot a sequence,
no block table), and what refuses a state kind by name.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import granitemoehybrid as family
from benchmarks.reference import granitemoehybrid as reference
from deepspeed_tpu.inference.v2 import engine_v2
from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  V2EngineConfig)
from deepspeed_tpu.inference.v2.kv_cache import StateKindError
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models import granite_hybrid as gh
from deepspeed_tpu.ops import ssm
from deepspeed_tpu.ops.pallas import ssm_update as su

CFG = dataclasses.replace(gh.TINY_GRANITE_HYBRID, dtype=jnp.float32)
HF = {"model_type": "granitemoehybrid", "vocab_size": CFG.vocab_size,
      "hidden_size": CFG.hidden_size, "num_hidden_layers": CFG.num_layers,
      "layer_types": list(CFG.layer_types), "num_attention_heads": 4,
      "num_key_value_heads": 2, "head_dim": 64,
      "shared_intermediate_size": 128, "intermediate_size": 128,
      "mamba_n_heads": 8, "mamba_d_head": 8,
      "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 1,
      "mamba_n_groups": 1, "mamba_chunk_size": 16, "mamba_conv_bias": True,
      "mamba_proj_bias": False, "num_local_experts": 0,
      "position_embedding_type": "nope", "embedding_multiplier": 12,
      "residual_multiplier": 0.22, "attention_multiplier": 0.015625,
      "logits_scaling": 8, "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
      "max_position_embeddings": 512}
TOL = 1e-4


@pytest.fixture(scope="module")
def params():
    model = gh.GraniteHybridForCausalLM(CFG)
    return model.init(jax.random.PRNGKey(0),
                      {"input_ids": np.zeros((1, 8), np.int32)})["params"]


@pytest.fixture(scope="module")
def weights(params):
    return family.reference_weights(params)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n).tolist()


def engine(params, impl="gather", budget=64, buckets=(16, 32, 64), **kw):
    """Blocks of 8 tokens, four slots, a step's budget of ``budget`` tokens:
    a prompt longer than it is cut there, at no multiple of the scan's block
    of 16 unless the budget is one."""
    return InferenceEngineV2(params, CFG, V2EngineConfig(
        kv_block_size=8, kv_num_blocks=96, max_tracked_sequences=4,
        scheduler=SchedulerConfig(max_tokens_per_step=budget,
                                  max_decode_batch=4,
                                  prefill_buckets=buckets),
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
    model = gh.GraniteHybridForCausalLM(CFG)
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
                                          budget, chunks):
    """A prompt of 61 tokens prefilled in one chunk, or cut at 37, or at 23
    and 46 (no multiple of the scan's block of 16, of a bucket or of a page),
    then 12 tokens decoded through the state: every row of logits the
    sampler is handed is the reference's at that position."""
    prompt = tokens(61)
    eng = engine(params, impl, budget)
    got = generate(eng, 1, prompt, 13)
    assert eng.sched_stats()["chunks_total"] == chunks
    want = reference.logits(weights, HF, prompt + got[:-1])[60:]
    assert len(handed) == 13
    for row, logits in enumerate(handed):
        np.testing.assert_allclose(logits[0], want[row], atol=TOL)
    assert got == [int(t) for t in np.argmax(want, axis=-1)]


def test_wave_equals_solo_and_a_reused_slot_equals_a_fresh_engine(
        params, weights, handed):
    """Four prompts admitted together (one chunk each or cut by the budget,
    then a decode batch of four) give each the tokens it gets alone; the
    sequences then leave, and the next to take their slots reads nothing of
    them: its tokens are a fresh engine's."""
    prompts = [tokens(n, seed=n) for n in (61, 9, 30, 17)]
    solo = [generate(engine(params), 7, p, 6) for p in prompts]
    eng = engine(params, budget=50)
    uids = [11, 12, 13, 14]
    got = {u: [] for u in uids}
    out = eng.put(uids, prompts)
    while any(len(g) < 6 for g in got.values()):
        for u in uids:
            if u in out and len(got[u]) < 6:
                got[u].append(out[u])
        out = eng.step()
    assert [got[u] for u in uids] == solo
    slots = {eng.state.get(u).slot for u in uids}
    assert slots == {0, 1, 2, 3}
    for u in uids:
        eng.flush(u)
    # nobody zeroed the slots
    assert float(jnp.abs(eng.kv.pool["state"]["ssm"][:, :4]).max()) > 0
    again = generate(eng, 21, prompts[2], 6)
    assert eng.state.get(21).slot in slots and again == solo[2]
    eng.flush(21)
    handed.clear()
    generate(eng, 22, prompts[0], 2)
    want = reference.logits(weights, HF, prompts[0])[-1:]
    np.testing.assert_allclose(handed[0][0], want[0], atol=TOL)


def test_padding_rows_move_no_state(params):
    """A chunk padded to its bucket and a decode batch padded to its bucket
    advance nothing they should not: a 20-token prompt leaves the same state
    and tail whether its chunk is padded to 32 or to 64 rows, a decode batch
    of one in a bucket of four writes its own slot and the padding's (the
    slot past the last), and no other."""
    prompt = tokens(20)
    states = []
    for buckets in ((32,), (64,)):
        eng = engine(params, buckets=buckets)
        eng.put([1], [prompt])
        slot = eng.state.get(1).slot
        states.append(jax.tree.map(lambda x: np.asarray(x[:, slot]),
                                   eng.kv.pool["state"]))
    for a, b in zip(jax.tree.leaves(states[0]), jax.tree.leaves(states[1])):
        np.testing.assert_allclose(a, b, atol=1e-6)
    # the tail is the last three REAL rows: a prompt one token longer moves it
    eng.put([2], [prompt + [5]])
    assert not np.allclose(
        np.asarray(eng.kv.pool["state"]["conv"][:, eng.state.get(2).slot]),
        states[1]["conv"])
    eng = InferenceEngineV2(params, CFG, V2EngineConfig(
        kv_block_size=8, kv_num_blocks=96, max_tracked_sequences=4,
        decode_batch_buckets=(4,), ctx_block_buckets=(4, 16),
        attn_impl="gather", scheduler=SchedulerConfig(
            max_tokens_per_step=64, max_decode_batch=4,
            prefill_buckets=(32,))))
    eng.put([1], [prompt])
    slot = eng.state.get(1).slot
    before = jax.tree.map(np.asarray, eng.kv.pool["state"])
    eng.step()                                  # one live row, three padding
    after = jax.tree.map(np.asarray, eng.kv.pool["state"])
    others = [s for s in range(4) if s != slot]
    for name in ("ssm", "conv"):
        np.testing.assert_array_equal(after[name][:, others],
                                      before[name][:, others])
        assert not np.array_equal(after[name][:, slot], before[name][:, slot])
    assert eng.kv.pool["state"]["ssm"].shape[1] == 4 + 1


@pytest.mark.parametrize("impl", ["gather", "kernel_interpret"])
def test_a_step_in_flight_changes_no_token(params, impl):
    """Depth 1 (a serving loop's: step k is dispatched before step k-1's
    tokens are read) gives the tokens of depth 0: the device runs the
    programs in the order they were dispatched, and a state is read and
    written by them alone."""
    prompts = [tokens(n, seed=n) for n in (40, 9)]
    want = [generate(engine(params, impl), 3, p, 8) for p in prompts]
    eng = engine(params, impl, budget=30)
    eng.depth = 1
    for uid, p in zip((1, 2), prompts):
        eng.admit(uid, p, max_new_tokens=8)
    got = {1: [], 2: []}
    for _ in range(40):
        for uid, tok in eng.step().items():
            got[uid].append(tok)
        if not eng.has_work():
            break
    assert [got[1], got[2]] == want


def test_kv_page_counters_count_the_slots_only_over_a_state(params):
    eng = engine(params)
    eng.put([1, 2], [tokens(20), tokens(9)])
    c = eng.last_step_counters
    assert c["state_slots_held"] == 2
    # 4 state layers x (8 x 8 x 16 x 4 + 3 x 96 x 4) bytes a sequence
    assert c["kv_state_bytes"] == 2 * 4 * (4096 + 1152) == \
        2 * eng.kv.slot_bytes
    assert c["kv_held_bytes"] == c["kv_full_bytes"] \
        == c["kv_full_blocks"] * eng.kv_block_bytes()
    # a pool without a state says nothing of slots
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_layers=1, num_heads=2, num_kv_heads=2,
                      max_seq_len=64, dtype=jnp.float32)
    plain = InferenceEngineV2(
        LlamaForCausalLM(cfg).init(jax.random.PRNGKey(0), {
            "input_ids": np.zeros((1, 8), np.int32)})["params"], cfg,
        V2EngineConfig(kv_block_size=8, kv_num_blocks=8))
    assert "state_slots_held" not in plain.last_step_counters
    assert not plain.kv.has_state and plain.kv.slot_bytes == 0


# --- the two computations ---------------------------------------------------

@pytest.mark.parametrize("rows", [1, 3, 256, 257])
def test_closed_form_is_the_recurrence_from_a_state_in_hand(rows):
    k = jax.random.split(jax.random.PRNGKey(rows), 6)
    h, p, n = 4, 8, 16
    x = jax.random.normal(k[0], (rows, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (rows, h)) - 2)
    a_log = jnp.log(jax.random.uniform(k[2], (h,), minval=1, maxval=16))
    bm = jax.random.normal(k[3], (rows, 1, n))      # one group of B and C
    cm = jax.random.normal(k[4], (rows, 1, n))
    s0 = jax.random.normal(k[5], (h, p, n))
    want_y, want_s = ssm.ssm_token_scan(x, dt, a_log, bm, cm, s0)
    got_y, got_s = ssm.ssm_chunk_scan(x, dt, a_log, bm, cm, s0, 256)
    scale = float(jnp.abs(want_y).max())
    np.testing.assert_allclose(got_y, want_y, atol=1e-5 * scale)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5 * scale)
    # rows that step by 0 move nothing
    dt0 = dt.at[rows // 2:].set(0.0)
    _, half = ssm.ssm_chunk_scan(x, dt0, a_log, bm, cm, s0, 256)
    _, cut = ssm.ssm_token_scan(x[:rows // 2], dt[:rows // 2], a_log,
                                bm[:rows // 2], cm[:rows // 2], s0)
    np.testing.assert_allclose(half, cut, atol=1e-5 * scale)


@pytest.mark.parametrize("heads,head_dim,d_state", [(4, 8, 16), (8, 64, 128)])
def test_update_kernel_is_the_plain_update(heads, head_dim, d_state):
    """The Pallas kernel in interpret mode against gather, update, scatter,
    and both against the definition; a padding row (the last slot) and a
    slot no row names."""
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    pack = su.state_pack(heads, head_dim)
    assert pack == (2 if head_dim == 64 else 4)
    layers, slots_n, b = 3, 5, 4
    pool = jax.random.normal(k[0], (layers, slots_n + 1, heads // pack,
                                    d_state, pack * head_dim))
    slots = jnp.asarray([2, 0, slots_n, slots_n], jnp.int32)
    x = jax.random.normal(k[1], (b, heads, head_dim))
    dt = jax.nn.softplus(jax.random.normal(k[2], (b, heads)))
    a_log = jnp.log(jax.random.uniform(k[3], (heads,), minval=1, maxval=16))
    bm = jax.random.normal(k[4], (b, 1, d_state))   # one group of B and C
    cm = jax.random.normal(k[5], (b, 1, d_state))
    y0, p0 = su.ssm_update_reference(pool, 1, slots, x, dt, a_log, bm, cm)
    y1, p1 = su.ssm_update(pool, 1, slots, x, dt, a_log, bm, cm,
                           interpret=True)
    np.testing.assert_allclose(y1, y0, atol=1e-5)
    np.testing.assert_allclose(p1[:, :slots_n], p0[:, :slots_n], atol=1e-6)
    s0 = su.unpack_state(pool[1, slots], pack)
    s1 = jnp.exp(-dt * jnp.exp(a_log))[..., None, None] * s0 \
        + (dt[..., None] * x)[..., None] * bm[:, :, None, :]
    np.testing.assert_allclose(y0, jnp.einsum("bhpn,bn->bhp", s1, cm[:, 0]),
                               atol=1e-4)
    np.testing.assert_allclose(su.unpack_state(p0[1, slots[:2]], pack),
                               s1[:2], atol=1e-6)
    # layers 0 and 2 and the slots 1, 3, 4 are what they were
    for layer, rows in ((0, slice(None)), (2, slice(None)), (1, [1, 3, 4])):
        np.testing.assert_array_equal(p1[layer, rows], pool[layer, rows])
    np.testing.assert_array_equal(
        su.unpack_state(su.pack_state(s0, pack), pack), s0)


def _conv_case(rows, channels, layers=3, spare=3, width=4, seed=0,
               dtype=jnp.bfloat16):
    """(pool, slots in a shuffled order, xbc, taps, bias) of a decode step:
    ``rows`` live slots of ``rows + spare`` and the padding's past them."""
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)
    pool = draw(layers, rows + spare + 1, *su.tail_stored(width, channels))
    slots = jnp.asarray(rng.permutation(rows + spare)[:rows], jnp.int32)
    return pool, slots, draw(rows, channels), draw(channels, width), \
        draw(channels)


# (rows, channels): granite's published widths (4,096 + 2 x 128 of one B/C
# group), Nemotron's (4,096 + 2 x 8 x 128) and a toy width
_CONV_CASES = [(64, 4352), (128, 6144), (4, 24)]


@pytest.mark.parametrize("rows,channels", _CONV_CASES)
def test_conv_step_kernel_is_the_plain_shift(rows, channels):
    """The Pallas kernel in interpret mode against gather, shift, scatter,
    to the last bit: the convolved rows and the WHOLE pool, rows in any slot
    order; slots no row names and other layers keep their bytes."""
    pool, slots, xbc, taps, bias = _conv_case(rows, channels)
    want, kept = su.ssm_conv_step_reference(pool, 1, slots, xbc, taps, bias)
    got, held = su.ssm_conv_step(pool, 1, slots, xbc, taps, bias,
                                 interpret=True)
    assert got.dtype == xbc.dtype and held.dtype == pool.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(held, np.float32),
                                  np.asarray(kept, np.float32))
    unnamed = sorted(set(range(pool.shape[1])) - set(np.asarray(slots)))
    assert len(unnamed) == 4
    for layer, where in ((0, slice(None)), (2, slice(None)), (1, unnamed)):
        np.testing.assert_array_equal(
            np.asarray(held[layer, where], np.float32),
            np.asarray(pool[layer, where], np.float32))
    # and a row's new tail is its old one a row on, behind its token
    new = su.unpack_tail(held[1, slots], 4, channels)
    old = su.unpack_tail(pool[1, slots], 4, channels)
    np.testing.assert_array_equal(np.asarray(new[:, :2], np.float32),
                                  np.asarray(old[:, 1:], np.float32))
    np.testing.assert_array_equal(np.asarray(new[:, 2], np.float32),
                                  np.asarray(xbc, np.float32))


@pytest.mark.parametrize("rows,channels", _CONV_CASES)
def test_conv_step_padding_rows_touch_the_last_slot_alone(rows, channels):
    """Half the rows are batch padding and all name the last slot: it may
    hold anything, every other slot's tail is what the reference leaves, and
    the live rows' convolutions are the reference's."""
    pool, slots, xbc, taps, bias = _conv_case(rows, channels, seed=1)
    last = pool.shape[1] - 1
    live = np.arange(rows) % 2 == 0
    slots = jnp.where(live, slots, last)
    want, kept = su.ssm_conv_step_reference(pool, 2, slots, xbc, taps, bias)
    got, held = su.ssm_conv_step(pool, 2, slots, xbc, taps, bias,
                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32)[live],
                                  np.asarray(want, np.float32)[live])
    np.testing.assert_array_equal(np.asarray(held[:, :last], np.float32),
                                  np.asarray(kept[:, :last], np.float32))
    np.testing.assert_array_equal(np.asarray(held[:2, last], np.float32),
                                  np.asarray(pool[:2, last], np.float32))


@pytest.mark.parametrize("impl", ["gather", "kernel_interpret"])
@pytest.mark.parametrize("channels", [24, 256])
def test_three_decode_steps_are_a_chunk_of_three_rows(channels, impl):
    """Decode and chunk agree on what a tail is: three steps in a row equal
    ``ssm.causal_conv`` over the same three rows from the same tail, the
    convolved rows and the tail left behind."""
    pool, slots, _, taps, bias = _conv_case(4, channels, seed=2,
                                            dtype=jnp.float32)
    xbc = jnp.asarray(np.random.default_rng(3).standard_normal(
        (3, 4, channels)), jnp.float32)
    step = su.ssm_conv_step_reference if impl == "gather" else \
        functools.partial(su.ssm_conv_step, interpret=True)
    held, got = pool, []
    for t in range(3):
        conv, held = step(held, 1, slots, xbc[t], taps, bias)
        got.append(conv)
    for row, slot in enumerate(np.asarray(slots)):
        tail = su.unpack_tail(pool[1, slot], 4, channels)
        conv, rows = ssm.causal_conv(xbc[:, row], tail, taps, bias)
        np.testing.assert_allclose(jnp.stack(got)[:, row], conv, atol=1e-5)
        np.testing.assert_array_equal(
            su.unpack_tail(held[1, slot], 4, channels), rows[3:])
    by_row = xbc.transpose(1, 0, 2)                 # [rows, K - 1, C]
    np.testing.assert_array_equal(
        su.unpack_tail(su.pack_tail(by_row, su.tail_stored(4, channels)), 4,
                       channels), by_row)


# --- what refuses a state kind by name ---------------------------------------

@pytest.mark.parametrize("what", ["speculation", "verify_chunk_g",
                                  "prefix_cache", "prefix_cache_option",
                                  "demote_kv", "export_prefix_handoff",
                                  "import_prefix_handoff", "fp8", "server_tier"])
def test_what_takes_a_cache_to_be_its_pages_is_refused_by_name(params, what,
                                                               tmp_path):
    from deepspeed_tpu.inference.v2 import generic_decode as gd
    calls = {
        "speculation": lambda: engine(params, speculative_k=4),
        "prefix_cache": lambda: engine(params).enable_prefix_cache(),
        "prefix_cache_option": lambda: engine(params,
                                              prefix_cache_enabled=True),
        "demote_kv": lambda: engine(params).demote_kv(1),
        "export_prefix_handoff": lambda: engine(
            params).export_prefix_handoff(str(tmp_path / "h.npz")),
        "import_prefix_handoff": lambda: engine(
            params).import_prefix_handoff(str(tmp_path / "h.npz")),
        "fp8": lambda: engine(params, kv_cache_dtype="fp8"),
    }
    if what == "verify_chunk_g":
        eng = engine(params)

        def call():
            gd.verify_chunk_g(
                params, eng.kv.pool, jnp.zeros((8,), jnp.int32), 0,
                {"full": jnp.zeros((4,), jnp.int32),
                 "state": jnp.int32(0)}, 3, policy=eng.policy, cfg=CFG,
                block_size=8, attn_impl="gather")
    elif what == "server_tier":
        from deepspeed_tpu.serving.server import (InferenceServer,
                                                  ServingConfig)

        def call():
            InferenceServer(engine(params),
                            ServingConfig(kv_offload_enabled=True))
    else:
        call = calls[what]
    with pytest.raises(StateKindError, match="recurrent state"):
        call()


def test_routed_experts_and_other_variants_are_refused_by_name():
    from deepspeed_tpu.models.hf import from_hf_checkpoint
    model, cfg, none = from_hf_checkpoint(HF)
    assert none is None and cfg.layer_types == CFG.layer_types
    assert cfg == dataclasses.replace(CFG, dtype=cfg.dtype)
    assert (cfg.head_dim, cfg.d_inner, cfg.conv_channels,
            cfg.in_proj_width, cfg.kv_pack) == (64, 64, 96, 168, 2)
    for key, value, said in (
            ("num_local_experts", 32, "routed experts"),
            ("mamba_n_groups", 8, "mamba_n_groups"),
            ("position_embedding_type", "rope", "position_embedding_type"),
            ("attention_bias", True, "attention_bias"),
            ("tie_word_embeddings", False, "tie_word_embeddings"),
            ("mamba_d_head", 16, "mamba_expand")):
        with pytest.raises(ValueError, match=said):
            from_hf_checkpoint({**HF, key: value})


def test_converter_reads_the_published_names(params):
    """A made-up state dict under the published model code's names (linears
    [out, in], the MLP's first matrix [gate ; up] fused on its rows, the
    depthwise convolution [C, 1, K]) becomes the tree the engine serves, and
    back."""
    state = gh.export_hf_granite_hybrid(params, CFG)
    m = "model.layers.0.mamba."
    assert state[m + "in_proj.weight"].shape == (CFG.in_proj_width, 64)
    assert state[m + "conv1d.weight"].shape == (CFG.conv_channels, 1, 4)
    assert state[m + "A_log"].shape == state[m + "D"].shape == (8,)
    assert state[m + "norm.weight"].shape == (CFG.d_inner,)
    assert state["model.layers.0.shared_mlp.input_linear.weight"].shape \
        == (2 * 128, 64)
    assert state["model.layers.2.self_attn.k_proj.weight"].shape \
        == (2 * 64, 64)
    assert "lm_head.weight" not in state and m.replace("0", "2") + "D" \
        not in state
    back = gh.convert_hf_granite_hybrid(state, CFG)
    flat = {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    got = {jax.tree_util.keystr(k): v for k, v in
           jax.tree_util.tree_flatten_with_path(back)[0]}
    assert set(flat) == set(got)
    for name, value in flat.items():
        np.testing.assert_allclose(got[name], value, atol=1e-7, err_msg=name)
