"""The plain float32 references against the program's own models, at a tiny
size on the CPU, in float32, so that they agree to rounding."""

import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import mistral as mistral_family
from benchmarks.families import mixtral as mixtral_family
from benchmarks.reference import mistral as mistral_reference
from benchmarks.reference import mixtral as mixtral_reference

DATA = pathlib.Path(__file__).resolve().parent / "data"
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
