"""The plain float32 references against the program's own models, at a tiny
size on the CPU, in float32, so that they agree to rounding."""

import dataclasses
import json
import pathlib
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import mistral as mistral_family
from benchmarks.families import mixtral as mixtral_family
from benchmarks.reference import mistral as mistral_reference
from benchmarks.reference import mixtral as mixtral_reference

from benchmarks.harness import cells

DATA = pathlib.Path(__file__).resolve().parent / "data"
# the third family's files are test data, found the way the harness finds them
IN_DATA = (DATA.parents[2], {"paths": [str(DATA.relative_to(DATA.parents[2]))]})
qwen2_moe_family = cells.load_module(*IN_DATA, "families", "qwen2_moe")
qwen2_moe_reference = cells.load_module(*IN_DATA, "reference", "qwen2_moe")
TOKENS = np.random.default_rng(0).integers(0, 512, (2, 160)).astype(np.int32)


@pytest.fixture(scope="module")
def mistral():
    """Tiny Mistral in float32; the 96-token window is shorter than the rows,
    so the mask is live."""
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    hf = json.loads((DATA / "tiny-mistral.json").read_text())
    cfg, _ = mistral_family.build(hf, "serve", hf["serve"])
    model = LlamaForCausalLM(dataclasses.replace(cfg, dtype=jnp.float32))
    params = model.init(jax.random.PRNGKey(0), {"input_ids": TOKENS})["params"]
    return hf, model, params


@pytest.fixture(scope="module")
def mixtral():
    from deepspeed_tpu.models.mixtral import MixtralForCausalLM
    hf = json.loads((DATA / "tiny-mixtral.json").read_text())
    cfg, _ = mixtral_family.build(hf, "serve", hf["serve"])
    cfg = dataclasses.replace(
        cfg, base=dataclasses.replace(cfg.base, dtype=jnp.float32),
        # the training layer drops tokens over capacity; the published model
        # and the serving path do not
        moe=dataclasses.replace(cfg.moe, dtype=jnp.float32, drop_tokens=False))
    model = MixtralForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": TOKENS})["params"]
    return hf, model, params


def test_mistral_logits_match_models_llama(mistral):
    hf, model, params = mistral
    got = model.apply({"params": params}, {"input_ids": TOKENS},
                      method=model.logits)
    for row in range(2):
        ref = mistral_reference.logits(
            mistral_family.reference_weights(params), hf, TOKENS[row])
        assert float(jnp.max(jnp.abs(got[row] - ref))) < 1e-4


def test_mistral_window_is_live_in_the_reference(mistral):
    hf, _, params = mistral
    weights = mistral_family.reference_weights(params)
    windowed = mistral_reference.logits(weights, hf, TOKENS[0])
    full = mistral_reference.logits(weights, dict(hf, sliding_window=None),
                                    TOKENS[0])
    w = hf["sliding_window"]
    assert float(jnp.max(jnp.abs(windowed[:w] - full[:w]))) < 1e-5
    assert float(jnp.max(jnp.abs(windowed[w + 8:] - full[w + 8:]))) > 1e-3


def test_mistral_loss_matches_models_llama(mistral):
    hf, model, params = mistral
    got = float(model.apply({"params": params}, {"input_ids": TOKENS}))
    ref = mistral_reference.loss(mistral_family.reference_weights(params), hf,
                                 TOKENS)
    assert got == pytest.approx(ref, rel=1e-5)


def test_mixtral_logits_match_models_mixtral(mixtral):
    hf, model, params = mixtral
    got = model.apply({"params": params}, {"input_ids": TOKENS},
                      method=model.logits)
    ref = mixtral_reference.logits(mixtral_family.reference_weights(params),
                                   hf, TOKENS[0])
    assert float(jnp.max(jnp.abs(got[0] - ref))) < 1e-4


def test_mixtral_reference_mixes_exactly_top_k_experts(mixtral):
    from benchmarks.reference import decoder
    hf, _, params = mixtral
    layer = mixtral_family.reference_weights(params)["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(1), (32, hf["hidden_size"]))
    combine = decoder.routing_weights(h, layer["router"],
                                      top_k=hf["num_experts_per_tok"])
    assert combine.shape == (32, hf["num_local_experts"])
    assert np.all(np.sum(np.asarray(combine) > 0, axis=1) == 2)
    assert np.allclose(np.sum(np.asarray(combine), axis=1), 1.0, atol=1e-6)


def test_a_skipped_expert_fails_the_serving_tolerance(mixtral):
    """The tolerance of the on-chip check is tight enough for what it guards:
    zeroing one expert moves the logits by far more than it allows."""
    hf, _, params = mixtral
    weights = mixtral_family.reference_weights(params)
    good = mixtral_reference.logits(weights, hf, TOKENS[0])
    broken = jax.tree.map(lambda x: x, weights)
    ex = dict(broken["layers"][0]["experts"])
    ex["down"] = ex["down"].at[0].set(0.0)
    broken["layers"][0] = dict(broken["layers"][0], experts=ex)
    bad = mixtral_reference.logits(broken, hf, TOKENS[0])
    assert float(jnp.max(jnp.abs(good - bad))) > 5e-3


@pytest.fixture(scope="module")
def qwen2_moe():
    """Tiny Qwen2-MoE in float32 with the attention biases set (flax makes
    them zero), and room in every expert for every token: the training layer
    drops tokens over capacity, the published model and the serving path do
    not."""
    from deepspeed_tpu.models.qwen2_moe import Qwen2MoEForCausalLM
    hf = json.loads((DATA / "tiny-qwen2-moe.json").read_text())
    cfg, _ = qwen2_moe_family.build(hf, "serve", hf["serve"])
    cfg = dataclasses.replace(
        cfg, base=dataclasses.replace(cfg.base, dtype=jnp.float32),
        moe=dataclasses.replace(cfg.moe, dtype=jnp.float32,
                                eval_capacity_factor=float(hf["num_experts"])))
    model = Qwen2MoEForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": TOKENS})["params"]

    def set_bias(path, leaf):
        if path[-1].key != "bias":
            return leaf
        key = jax.random.PRNGKey(zlib.crc32(jax.tree_util.keystr(path).encode()))
        return 0.5 * jax.random.normal(key, leaf.shape)
    return hf, model, jax.tree_util.tree_map_with_path(set_bias, params)


def test_qwen2_moe_logits_match_models_qwen2_moe(qwen2_moe):
    hf, model, params = qwen2_moe
    got = model.apply({"params": params}, {"input_ids": TOKENS},
                      method=model.logits)
    ref = qwen2_moe_reference.logits(
        qwen2_moe_family.reference_weights(params), hf, TOKENS[0])
    assert float(jnp.max(jnp.abs(got[0] - ref))) < 1e-4


def test_qwen2_moe_reference_keeps_the_top_k_weights_as_they_are(qwen2_moe):
    hf, _, params = qwen2_moe
    layer = qwen2_moe_family.reference_weights(params)["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(1), (32, hf["hidden_size"]))
    k = hf["num_experts_per_tok"]
    raw, unit = (np.asarray(qwen2_moe_reference.routing_weights(
        h, layer["router"], top_k=k, renormalise=r)) for r in (False, True))
    assert raw.shape == (32, hf["num_experts"])
    assert np.all(np.sum(raw > 0, axis=1) == k)
    probs = np.asarray(jax.nn.softmax(h @ layer["router"], axis=-1))
    assert np.allclose(raw[raw > 0], probs[raw > 0], atol=1e-6)
    assert np.all(np.sum(raw, axis=1) < 0.999)
    assert np.allclose(np.sum(unit, axis=1), 1.0, atol=1e-6)


def test_qwen2_moe_reference_every_mechanism_moves_the_logits(qwen2_moe):
    """The attention biases, the shared expert and its sigmoid gate each
    change the reference's answer: none is a dead input of the check."""
    hf, _, params = qwen2_moe
    weights = qwen2_moe_family.reference_weights(params)
    good = qwen2_moe_reference.logits(weights, hf, TOKENS[0])

    def moved(change):
        layers = [dict(weights["layers"][0], **change(weights["layers"][0]))]
        bad = qwen2_moe_reference.logits(
            dict(weights, layers=layers + weights["layers"][1:]), hf,
            TOKENS[0])
        return float(jnp.max(jnp.abs(good - bad)))
    assert moved(lambda l: {"v_bias": 0.0 * l["v_bias"]}) > 5e-3
    assert moved(lambda l: {"k_bias": 0.0 * l["k_bias"]}) > 5e-3
    assert moved(lambda l: {"shared": dict(
        l["shared"], down=0.0 * l["shared"]["down"])}) > 5e-3
    assert moved(lambda l: {"shared": dict(
        l["shared"], sigmoid_gate=0.0 * l["shared"]["sigmoid_gate"])}) > 5e-3
