"""Xing4.0 against its plain reference (``benchmarks/reference/xing4_0.py``),
at a tiny size in float32 on seeded weights whose mixing parameters are the
benchmark family's draw (``families/xing4_0.py`` ``trained_mixing``: ``H_res``
far from the identity): hidden 64, 4 streams, 20 Sinkhorn iterations, 2
heads, ranks 24 / 16, head dims 8 / 4 / 8, YaRN (factor 8 over 64 positions),
a router over 16 experts top-4, one shared expert, two leading dense layers
and three expert layers.

The comparison of every served test is one function, ``served_error``:
prefill in chunks, then decode through the latent pages, logits against the
reference's full forward at the same positions. Controls run the same
comparison against a reference with one thing left out and must miss it by
100 times the tolerance.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import xing4_0 as family
from benchmarks.reference import xing4_0 as reference
from deepspeed_tpu.inference.v2 import hyper_connection as hcx
from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  V2EngineConfig)
from deepspeed_tpu.inference.v2.generic_decode import (decode_step_g,
                                                       prefill_chunk_g)
from deepspeed_tpu.inference.v2.kv_cache import latent_row_width
from deepspeed_tpu.inference.v2.modules import (JoyAIFlashPolicy, Xing4Policy,
                                                policy_for)
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models import xing4 as xm

YARN = {"type": "yarn", "factor": 8, "original_max_position_embeddings": 64,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
HF = {
    "model_type": "xing4_0", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 5, "num_attention_heads": 2, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "n_routed_experts": 16, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "n_group": 1, "topk_group": 1,
    "topk_method": "noaux_tc", "scoring_func": "sigmoid",
    "routed_scaling_factor": 2, "norm_topk_prob": True,
    "first_k_dense_replace": 2, "max_position_embeddings": 512,
    "rope_theta": 10000, "rope_scaling": YARN, "rms_norm_eps": 1e-6,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "num_nextn_predict_layers": 1, "ep_size": 1}
BLOCK, BLOCKS, TABLE = 8, 64, 8
#: float32: the program and the reference differ in the order of their
#: reductions only (the fold multiplies W_uk into the query first; the
#: weighted sums over the streams are taken stream by stream)
F32_TOL = 1e-4
#: what a control has to miss the comparison by
TEETH = 100 * F32_TOL


def build(seed=0, **over):
    cfg = dataclasses.replace(xm.xing4_config_from_hf(HF), dtype=jnp.float32,
                              **over)
    model = xm.Xing4ForCausalLM(cfg)
    params = family._TrainedMixing(model).init(
        jax.random.PRNGKey(seed),
        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    return cfg, model, params


@pytest.fixture(scope="module")
def f32():
    return build()


def tokens(n, seed=1):
    return np.random.default_rng(seed).integers(0, HF["vocab_size"], n,
                                                dtype=np.int32)


def reference_logits(params, toks, hf=HF, change=None):
    weights = family.reference_weights(params)
    if change:
        weights = dict(weights, layers=[change(dict(layer))
                                        for layer in weights["layers"]])
    return reference.logits(weights, hf, toks)


def fresh_pool(cfg):
    return jnp.zeros((cfg.num_layers, BLOCKS, BLOCK,
                      latent_row_width(cfg.latent_dim)), cfg.dtype)


def serve(cfg, params, toks, prompt, chunk=16, attn_impl="gather"):
    """Logits [len(toks) - prompt + 1, V]: the prompt's last position from
    prefill in chunks of ``chunk`` (each padded to a bucket of ``chunk``
    rows), every later position from a decode step fed ``toks``."""
    pool = fresh_pool(cfg)
    table = jnp.arange(TABLE, dtype=jnp.int32)
    kw = dict(policy=Xing4Policy, cfg=cfg, block_size=BLOCK,
              attn_impl=attn_impl)
    out = []
    for start in range(0, prompt, chunk):
        n = min(chunk, prompt - start)
        padded = np.zeros(chunk, np.int32)
        padded[:n] = toks[start:start + n]
        logits, pool, _ = prefill_chunk_g(params, pool, jnp.asarray(padded),
                                          start, table, n, **kw)
    out.append(logits)
    for pos in range(prompt, len(toks)):
        logits, pool, _ = decode_step_g(
            params, pool, jnp.asarray(toks[pos:pos + 1]), jnp.asarray([pos]),
            table[None], jnp.asarray([True]), **kw)
        out.append(logits[0])
    return jnp.stack(out)


def served_error(cfg, params, chunk=16, hf=HF, change=None, **how):
    """Largest |served logit - reference logit| over a 40-token prompt
    prefilled in chunks and 5 decoded positions."""
    toks = tokens(45)
    got = serve(cfg, params, toks, 40, chunk, **how)
    want = reference_logits(params, toks, hf, change)[39:]
    return float(jnp.max(jnp.abs(got - want)))


# --- (a), (b): the program gives the reference's logits ------------------------

@pytest.mark.parametrize("chunk", [64, 16], ids=["one-chunk", "three-chunks"])
@pytest.mark.parametrize("attn_impl", ["gather", "kernel_interpret"])
def test_prefill_then_decode_gives_the_reference_logits(f32, chunk,
                                                        attn_impl):
    cfg, _, params = f32
    assert served_error(cfg, params, chunk, attn_impl=attn_impl) < F32_TOL


def test_flax_forward_gives_the_reference_logits(f32):
    cfg, model, params = f32
    toks = tokens(45)
    got = model.apply({"params": params}, {"input_ids": toks[None]},
                      method=model.logits)[0]
    np.testing.assert_allclose(got, reference_logits(params, toks)[:],
                               atol=F32_TOL)


def test_decode_in_a_wave_gives_each_sequences_reference_logits(f32):
    """Three sequences of different lengths in a batch bucket of 4 (one row
    padding), each through its own pages."""
    cfg, _, params = f32
    kw = dict(policy=Xing4Policy, cfg=cfg, block_size=BLOCK,
              attn_impl="gather")
    lens = [21, 9, 30]
    seqs = [tokens(n + 1, seed=10 + i) for i, n in enumerate(lens)]
    pool = fresh_pool(cfg)
    tables = jnp.arange(4 * TABLE, dtype=jnp.int32).reshape(4, TABLE)
    for row, (n, seq) in enumerate(zip(lens, seqs)):
        padded = np.zeros(32, np.int32)
        padded[:n] = seq[:n]
        _, pool, _ = prefill_chunk_g(params, pool, jnp.asarray(padded), 0,
                                     tables[row], n, **kw)
    logits, _, counts = decode_step_g(
        params, pool, jnp.asarray([s[-1] for s in seqs] + [0]),
        jnp.asarray(lens + [0]), tables,
        jnp.asarray([True, True, True, False]), **kw)
    for row, seq in enumerate(seqs):
        np.testing.assert_allclose(
            logits[row], reference_logits(params, seq)[len(seq) - 1],
            atol=F32_TOL)
    # three expert layers x three live rows x top-4: padding takes no expert
    assert int(counts[0]) == 3 * 3 * 4


# --- (c): the mixing, by hand ------------------------------------------------

def _streams(cfg, t=24, seed=3):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (t, cfg.hc_mult, cfg.hidden_size), jnp.float32)


@pytest.mark.parametrize("spread,rows_tol", [(1.0, 2e-3), (0.25, 1e-5)],
                         ids=["benchmarks-draw", "a-quarter-of-it"])
def test_h_res_is_doubly_stochastic(f32, spread, rows_tol):
    """Columns sum to 1 (the last normalisation is theirs) and rows to within
    what 20 iterations leave: 1e-5 where the logits lie within a quarter of
    the benchmark's draw, up to 1e-3 at the benchmark's own (some tokens'
    matrices have not converged: what gives the iteration count its teeth)."""
    cfg, _, params = f32
    x = _streams(cfg)
    for layer in range(cfg.num_layers):
        for sub in ("hc_attn", "hc_mlp"):
            mixing = params[f"layer_{layer}"][sub]
            mixing = dict(mixing, phi=mixing["phi"] * spread,
                          bias=mixing["bias"] * spread)
            h_pre, h_post, h_res = hcx.coefficients(x, mixing, cfg.hc)
            np.testing.assert_allclose(h_res.sum(-2), 1.0, atol=1e-5)
            np.testing.assert_allclose(h_res.sum(-1), 1.0, atol=rows_tol)
            assert float(h_res.min()) >= 0.0
            # the draw is far from a plain residual
            if spread == 1.0:
                assert float(jnp.abs(h_res - jnp.eye(cfg.hc_mult)).max()) > 0.5
            assert h_pre.shape == h_post.shape == (24, cfg.hc_mult)
            assert 0.0 < float(h_pre.min()) and float(h_post.max()) < 2.0


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_stream_sum_obeys_its_identity_at_any_weights(f32, seed):
    """sum_i X'_i = sum_i X_i + (sum_i H_post[i]) y, because H_res's columns
    sum to 1: checked on weights drawn wide, where the clamp acts."""
    cfg, _, _ = f32
    hc = cfg.hc
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    mixing = {"phi": jax.random.normal(k1, (hc.streams * cfg.hidden_size,
                                            hc.coefficients)) * 0.5,
              "bias": 4.0 * jax.random.normal(k2, (hc.coefficients,)),
              "alpha": jnp.asarray([1.0, -2.0, 3.0])}
    x = _streams(cfg, seed=seed)
    y = jax.random.normal(k3, (x.shape[0], cfg.hidden_size))
    u, mix = hcx.pre_mix(x, mixing, hc)
    after = hcx.post_mix(x, y, mix, hc)
    h_pre, h_post, h_res = hcx.coefficients(x, mixing, hc)
    np.testing.assert_allclose(
        after.sum(1), x.sum(1) + h_post.sum(-1, keepdims=True) * y,
        rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(u, jnp.einsum("tj,tjc->tc", h_pre, x),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        after, jnp.einsum("tij,tjc->tic", h_res, x)
        + h_post[:, :, None] * y[:, None, :], rtol=1e-5, atol=1e-5)


def test_the_programs_own_draw_is_a_plain_residual(f32):
    """The papers' initialisation: H_pre 1/n, H_post 1, H_res the identity."""
    cfg, _, _ = f32
    mixing = hcx.init_params(jax.random.PRNGKey(0), cfg.hc, cfg.hidden_size)
    h_pre, h_post, h_res = hcx.coefficients(_streams(cfg), mixing, cfg.hc)
    np.testing.assert_allclose(h_pre, 1 / cfg.hc_mult, atol=0.01)
    np.testing.assert_allclose(h_post, 1.0, atol=0.02)
    np.testing.assert_allclose(
        h_res, jnp.broadcast_to(jnp.eye(cfg.hc_mult), h_res.shape), atol=0.01)


def test_yarn_tables_and_softmax_scale_follow_deepseeks_convention():
    cfg = xm.xing4_config_from_hf(HF)
    cos, sin, scale = reference.rope_tables(HF, 100)
    from deepspeed_tpu.models.llama import rope_freqs
    ours = rope_freqs(4, 512, 10000.0, cfg.rope_yarn)
    np.testing.assert_allclose(ours[0][:100], cos, atol=1e-6)
    np.testing.assert_allclose(ours[1][:100], sin, atol=1e-6)
    # mscale = mscale_all_dim: the tables unscaled, the scale squared
    assert cfg.rope_yarn.attention_factor == 1.0
    assert cfg.softmax_scale == pytest.approx(scale)
    assert scale == pytest.approx(12 ** -0.5 * (0.1 * np.log(8) + 1) ** 2)
    # pair 0 keeps its frequency, pair 1's is divided by the factor
    np.testing.assert_allclose(cos[7], [np.cos(7.0), np.cos(7 * 0.01 / 8)],
                               atol=1e-6)
    big = xm.Xing4Config()
    assert big.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)
    assert (big.latent_dim, big.hc.coefficients, big.hc_mult) == (576, 24, 4)


# --- (d): teeth --------------------------------------------------------------

def _mixing_with(pre=None, res=None):
    """A layer of reference weights with the token's part of ``H_pre``'s or
    ``H_res``'s logits zeroed and the bias set: ``pre`` a value every stream
    takes, ``res`` the [n, n] logits."""
    def change(layer):
        n = HF["hc_mult"]
        for sub in ("hc_attn", "hc_mlp"):
            mixing = dict(layer[sub])
            phi, bias = mixing["phi"], mixing["bias"]
            if pre is not None:
                phi = phi.at[:, :n].set(0.0)
                bias = bias.at[:n].set(pre)
            if res is not None:
                phi = phi.at[:, 2 * n:].set(0.0)
                bias = bias.at[2 * n:].set(jnp.asarray(res).reshape(-1))
            layer[sub] = dict(mixing, phi=phi, bias=bias)
        return layer
    return change


CONTROLS = {
    "one-sinkhorn-iteration": dict(hf=dict(HF, hc_sinkhorn_iters=1)),
    "h-res-the-identity": dict(change=_mixing_with(
        res=60.0 * np.eye(4, dtype=np.float32) - 30.0)),
    "h-pre-uniform": dict(change=_mixing_with(pre=-np.log(3.0))),
    "yarn-softmax-scale-left-out": dict(hf=dict(HF, rope_scaling=dict(
        YARN, mscale=0, mscale_all_dim=0))),
    "yarn-left-out": dict(hf=dict(HF, rope_scaling=None)),
    "streams-not-summed-but-averaged": dict(hf=None),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_control_misses_the_comparison_by_a_hundred_tolerances(f32, control):
    cfg, _, params = f32
    how = dict(CONTROLS[control])
    if how.get("hf", HF) is None:
        # the head reads the mean of the streams: every logit's input shrinks
        # by n before the final norm, which undoes it; so compare the states
        toks = tokens(45)
        weights = family.reference_weights(params)
        summed = reference.hidden_states(weights, HF, toks)
        assert float(jnp.abs(summed - summed / HF["hc_mult"]).max()) > TEETH
        return
    assert served_error(cfg, params, **how) > TEETH


# --- (e): padding ------------------------------------------------------------

def test_bucket_padding_changes_no_valid_row(f32):
    """The same 11 tokens in a bucket of 16 and of 64 rows, and padding rows
    of other token ids: the valid rows' logits are the same to rounding, and
    every row's are finite (the clamp keeps a padded row's Sinkhorn finite)."""
    from deepspeed_tpu.inference.v2.generic_decode import verify_chunk_g
    cfg, _, params = f32
    kw = dict(policy=Xing4Policy, cfg=cfg, block_size=BLOCK,
              attn_impl="gather")
    table = jnp.arange(TABLE, dtype=jnp.int32)
    toks = tokens(11)
    outs = []
    for bucket, filler in ((16, 0), (64, 0), (64, 77)):
        padded = np.full(bucket, filler, np.int32)
        padded[:11] = toks
        logits, _, _ = verify_chunk_g(params, fresh_pool(cfg),
                                      jnp.asarray(padded), 0, table, 11, **kw)
        assert np.isfinite(np.asarray(logits)).all()
        outs.append(np.asarray(logits[:11]))
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-5)
    np.testing.assert_allclose(outs[1], outs[2], atol=1e-5)
    np.testing.assert_allclose(outs[0], reference_logits(params, toks)[:],
                               atol=F32_TOL)


# --- the engine and the server -------------------------------------------------

def engine(cfg, params, **over):
    return InferenceEngineV2(params, cfg, V2EngineConfig(**{
        "kv_block_size": BLOCK, "kv_num_blocks": BLOCKS,
        "scheduler": SchedulerConfig(max_tokens_per_step=32), **over}))


def _greedy_reference(params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(jnp.argmax(reference_logits(params, seq)[len(seq) - 1])))
    return seq[len(prompt):]


def test_engine_serves_the_reference_greedy_tokens_alone_and_in_a_wave(f32):
    cfg, _, params = f32
    prompts = [tokens(n, seed=20 + n).tolist() for n in (40, 7, 19)]
    want = [_greedy_reference(params, p, 4) for p in prompts]
    assert engine(cfg, params).generate(prompts[0], max_new_tokens=4) == \
        want[0]
    eng = engine(cfg, params)
    got = {u: [] for u in range(3)}
    out = eng.put(list(got), prompts)
    while any(len(g) < 4 for g in got.values()):
        for u, tok in out.items():
            if len(got[u]) < 4:
                got[u].append(tok)
        out = eng.step()
    assert [got[u] for u in range(3)] == want


def test_server_serves_xing4_through_submit(f32):
    from deepspeed_tpu.serving.server import InferenceServer, ServingConfig
    cfg, _, params = f32
    server = InferenceServer(engine(cfg, params), ServingConfig()).start()
    try:
        prompt = tokens(30, seed=7).tolist()
        got = server.submit(prompt, max_new_tokens=5).result(timeout=120)
    finally:
        server.stop(drain_timeout=10.0)
    toks = list(got.tokens) if hasattr(got, "tokens") else list(got)
    assert toks == _greedy_reference(params, prompt, 5)


# --- the registry, the policy's reuse and the checkpoint's names ---------------

def test_model_type_is_registered_and_the_policy_reuses_joyais_pieces():
    from deepspeed_tpu.models.hf import (from_hf_checkpoint,
                                         supported_model_types)
    assert "xing4_0" in supported_model_types()
    model, cfg, params = from_hf_checkpoint(HF)
    assert isinstance(cfg, xm.Xing4Config) and params is None
    assert policy_for(cfg) is Xing4Policy
    assert issubclass(Xing4Policy, JoyAIFlashPolicy)
    assert Xing4Policy.cache_spec(cfg) == JoyAIFlashPolicy.cache_spec(cfg)
    assert (cfg.first_k_dense_replace, cfg.hc_sinkhorn_iters) == (2, 20)
    assert xm.xing4_config_from_hf(HF, max_seq_len=96).max_seq_len == 96
    assert xm.TINY_XING4.hc_mult == 4 and xm.TINY_XING4.num_layers == 5


@pytest.mark.parametrize("bad", [
    {"scoring_func": "softmax"}, {"n_group": 8, "topk_group": 4},
    {"rope_scaling": {"type": "linear", "factor": 4}},
    {"rope_scaling": {"type": "yarn", "factor": 40}},
    {"rope_interleave": False}], ids=lambda b: next(iter(b)))
def test_what_the_family_cannot_run_is_refused_by_name(bad):
    with pytest.raises(ValueError, match="unsupported xing4_0"):
        xm.xing4_config_from_hf(dict(HF, **bad))


def test_checkpoint_names_round_trip(f32):
    cfg, model, params = f32
    state = xm.export_hf_xing4(params, cfg)
    p = "model.layers.3."
    assert {p + "hc_attn.phi.weight", p + "hc_attn.bias", p + "hc_attn.alpha",
            p + "hc_ffn.phi.weight", p + "self_attn.kv_a_proj_with_mqa.weight",
            p + "mlp.gate.e_score_correction_bias",
            "model.layers.1.mlp.gate_proj.weight",
            "model.layers.0.hc_ffn.bias", "lm_head.weight"} <= set(state)
    assert "model.layers.1.mlp.gate.weight" not in state
    assert state[p + "hc_attn.phi.weight"].shape == (24, 4 * 64)
    back = xm.convert_hf_xing4(state, cfg)
    flat_a = jax.tree_util.tree_leaves_with_path(params)
    flat_b = dict(jax.tree_util.tree_leaves_with_path(back))
    assert len(flat_a) == len(flat_b)
    for path, leaf in flat_a:
        np.testing.assert_array_equal(np.asarray(leaf), flat_b[path])
    toks = tokens(24)
    got = model.apply({"params": jax.tree.map(jnp.asarray, back)},
                      {"input_ids": toks[None]}, method=model.logits)[0]
    np.testing.assert_allclose(got, reference_logits(params, toks)[:],
                               atol=F32_TOL)
