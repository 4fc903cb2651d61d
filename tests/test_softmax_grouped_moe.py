"""Mixtral's and Qwen2-MoE's served experts through the grouped matmul: the
softmax router and the chosen experts against all-experts-then-pick written
out here (the formula the served path had before), each policy's step
programs against its model's own forward with ``jax.lax.ragged_dot`` and
with the Pallas kernel (interpret mode) for the matmul, and the counts they
hand out. Float32 on the CPU throughout.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2, V2EngineConfig
from deepspeed_tpu.inference.v2 import modules
from deepspeed_tpu.inference.v2.modules import (MixtralPolicy, Qwen2MoEPolicy,
                                                policy_for)
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models.llama import random_tokens
from deepspeed_tpu.models.mixtral import TINY_MIXTRAL, MixtralForCausalLM
from deepspeed_tpu.models.qwen2_moe import TINY_QWEN2_MOE, Qwen2MoEForCausalLM
from deepspeed_tpu.moe.grouped_experts import grouped_expert_ffn, softmax_route

E, K, D, F = 8, 2, 16, 24


def _all_experts_then_pick(h, gate, experts, norm_topk_prob):
    """Every row through every expert, then the top-k picked and combined."""
    probs = jax.nn.softmax(h.astype(jnp.float32) @ gate, axis=-1)
    topv, topi = jax.lax.top_k(probs, K)
    w = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9) \
        if norm_topk_prob else topv
    g = jnp.einsum("td,edf->etf", h, experts["w_gate"])
    u = jnp.einsum("td,edf->etf", h, experts["w_up"])
    every = jnp.einsum("etf,efd->etd", jax.nn.silu(g) * u, experts["w_down"])
    picked = every[topi, jnp.arange(h.shape[0])[:, None]]          # [T, K, D]
    return jnp.einsum("tk,tkd->td", w, picked), w, topi


@pytest.mark.parametrize("masked", [False, True], ids=["all-rows", "rows-left-out"])
@pytest.mark.parametrize("t", [1, 2, 33, 256])
@pytest.mark.parametrize("norm_topk_prob", [True, False], ids=["renorm", "raw"])
def test_softmax_route_and_grouped_experts_equal_all_experts_then_pick(
        norm_topk_prob, t, masked):
    keys = jax.random.split(jax.random.PRNGKey(t), 5)
    h = jax.random.normal(keys[0], (t, D))
    gate = jax.random.normal(keys[1], (D, E))
    experts = {"w_gate": jax.random.normal(keys[2], (E, D, F)) * 0.3,
               "w_up": jax.random.normal(keys[3], (E, D, F)) * 0.3,
               "w_down": jax.random.normal(keys[4], (E, F, D)) * 0.3}
    # every third row left out, the first among them (t == 1: no row stays)
    valid = jnp.arange(t) % 3 != 0 if masked else None

    weights, ids = softmax_route(h, gate, K, norm_topk_prob)
    got, rows = grouped_expert_ffn(h, experts, weights, ids, valid)

    want, want_w, want_ids = _all_experts_then_pick(h, gate, experts,
                                                    norm_topk_prob)
    np.testing.assert_array_equal(ids, want_ids)      # the choice is the same
    np.testing.assert_allclose(weights, want_w, rtol=1e-6)
    assert weights.dtype == jnp.float32
    if norm_topk_prob:
        np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    else:
        assert float(weights.sum(-1).max()) < 1.0
    kept = np.ones(t, bool) if valid is None else np.asarray(valid)
    np.testing.assert_allclose(got, np.where(kept[:, None], want, 0.0),
                               atol=1e-5)
    assert not np.asarray(got)[~kept].any()           # zeros, not small values
    assert int(rows.sum()) == int(kept.sum()) * K
    np.testing.assert_array_equal(
        rows, np.bincount(np.asarray(ids)[kept].reshape(-1), minlength=E))


def test_softmax_route_ties_go_to_the_lower_id():
    _, ids = softmax_route(jnp.zeros((3, D)), jnp.ones((D, E)), K, True)
    assert ids.tolist() == [[0, 1]] * 3


# --- the policies against their models ---------------------------------------

def _float32(cfg):
    return dataclasses.replace(
        cfg, base=dataclasses.replace(cfg.base, dtype=jnp.float32),
        moe=dataclasses.replace(cfg.moe, dtype=jnp.float32))


FAMILIES = {
    "mixtral": (_float32(TINY_MIXTRAL), MixtralForCausalLM, MixtralPolicy),
    "qwen2_moe": (_float32(TINY_QWEN2_MOE), Qwen2MoEForCausalLM,
                  Qwen2MoEPolicy),
}


@pytest.fixture(scope="module", params=[
    (name, matmul) for name in sorted(FAMILIES)
    for matmul in ("ragged-dot", "kernel-interpret")], ids="-".join)
def family(request):
    """(config, parameters, the model's logits, the matmul), with the
    grouped matmul the CPU's (``jax.lax.ragged_dot``) or the TPU's (the
    Pallas kernel of ``ops/pallas/grouped_matmul.py``, here in interpret
    mode) under every step program's expert layers."""
    name, matmul = request.param
    cfg, model_cls, policy = FAMILIES[name]
    assert policy_for(cfg) is policy
    patch = pytest.MonkeyPatch()
    request.addfinalizer(patch.undo)
    if matmul == "kernel-interpret":
        patch.setattr(modules, "_expert_matmul_impl",
                      lambda: "kernel_interpret")
        # another static argument of the step programs (a shorter rope
        # table, the same results), so that jit traces them anew under the
        # patch and nothing traced under it is found by a later test
        cfg = dataclasses.replace(cfg, base=dataclasses.replace(
            cfg.base, max_seq_len=cfg.base.max_seq_len - 8))
    model = model_cls(cfg)
    vocab = cfg.base.vocab_size
    params = model.init(jax.random.PRNGKey(3),
                        random_tokens(1, 8, vocab_size=vocab))["params"]

    def logits(ids):
        return np.asarray(model.apply(
            {"params": params}, {"input_ids": np.asarray([ids], np.int32)},
            method=model_cls.logits))[0]
    yield cfg, params, logits, matmul


def _engine(cfg, params):
    return InferenceEngineV2(params, cfg, V2EngineConfig(
        kv_block_size=16, kv_num_blocks=64,
        scheduler=SchedulerConfig(max_tokens_per_step=32,
                                  prefill_buckets=(16, 32))))


def _prompt(cfg, n, seed=11):
    return np.random.default_rng(seed).integers(
        0, cfg.base.vocab_size, n).tolist()


def test_served_tokens_are_the_models_own_forward(family):
    """A prompt of two chunks (32 and a padded 7) and four decode steps
    through the policy's ``block``: the greedy tokens of the model's
    full forward over the growing sequence."""
    cfg, params, logits, _ = family
    prompt = _prompt(cfg, 39)
    got = _engine(cfg, params).generate(list(prompt), max_new_tokens=4)
    ids = list(prompt)
    for _ in range(4):
        ids.append(int(np.argmax(logits(ids)[-1])))
    assert got == ids[len(prompt):]


def test_chunk_logits_are_the_models_and_padding_rows_take_no_expert(family):
    """One bucket-padded chunk through ``prefill_chunk_g``: the last real
    row's logits are the model's to float32 rounding, and the counts are
    those of the real rows alone."""
    from deepspeed_tpu.inference.v2 import generic_decode as gd
    cfg, params, logits, matmul = family
    policy = policy_for(cfg)
    spec = policy.cache_spec(cfg)
    prompt = _prompt(cfg, 11, seed=5)
    pool = jnp.zeros((spec.num_layers, 2, spec.num_kv_heads, 4, 16,
                      spec.head_dim), jnp.float32)
    tokens = jnp.asarray(prompt + [0] * 5, jnp.int32)        # bucket of 16
    got, _, counts = gd.prefill_chunk_g(
        params, pool, tokens, jnp.int32(0), jnp.asarray([0, 1], jnp.int32),
        jnp.int32(11), policy=policy, cfg=cfg, block_size=16,
        attn_impl="gather")
    np.testing.assert_allclose(got, logits(prompt)[-1], atol=2e-4)
    rows, touched, tile_rows, absent = np.asarray(counts).tolist()
    assert absent == 0               # every expert of the layer is held
    if matmul == "ragged-dot":
        assert tile_rows == rows      # no tiles to count: a row fills its own
    else:                             # whole tiles, no fewer than the rows
        from deepspeed_tpu.ops.pallas.grouped_matmul import tiling
        e, d, f = params["layer_0"]["moe"]["experts"]["w_gate"].shape
        tm = tiling(16 * cfg.moe.top_k, e, d, f, jnp.float32, 2)[0]
        assert tile_rows >= rows and tile_rows % tm == 0
    assert rows == 11 * cfg.moe.top_k * spec.num_layers
    assert cfg.moe.top_k * spec.num_layers <= touched \
        <= cfg.moe.num_experts * spec.num_layers


def test_counts_ride_on_the_spans_that_wait(family):
    """A traced toy engine: ``expert_rows`` = valid rows x top_k x layers,
    ``experts_touched`` <= experts x layers and ``expert_tile_rows`` >=
    ``expert_rows`` (equal where ``ragged_dot`` runs and no tile is counted),
    on ``serve/prefill_chunk`` and ``serve/step_decode``."""
    from deepspeed_tpu.telemetry.tracer import get_tracer
    cfg, params, _, _ = family
    layers, k, e = cfg.base.num_layers, cfg.moe.top_k, cfg.moe.num_experts
    tracer = get_tracer()
    was = tracer.enabled
    tracer.configure(enabled=True)      # the ring's capacity stays as it is:
    tracer.clear()                      # later files in this process need it
    try:
        eng = _engine(cfg, params)
        eng.generate(_prompt(cfg, 39), max_new_tokens=3)
        events = tracer.events_snapshot()
    finally:
        tracer.configure(enabled=was)
    chunks = [ev[7] for ev in events if ev[1] == "serve/prefill_chunk"]
    decodes = [ev[7] for ev in events if ev[1] == "serve/step_decode"]
    # 39 tokens in chunks of 32 and 7 (a bucket of 16): the first waits for
    # nothing, and its counts ride on the second's span, which ends the prompt
    assert [c["tokens"] for c in chunks] == [32, 7]
    assert "expert_rows" not in chunks[0]
    assert chunks[1]["expert_rows"] == 39 * k * layers
    assert k * layers <= chunks[1]["experts_touched"] <= 2 * e * layers
    assert "expert_tile_rows" not in chunks[0]
    assert chunks[1]["expert_tile_rows"] >= chunks[1]["expert_rows"]
    # one live stream in a decode batch bucket: its padding rows count nothing
    assert decodes and all(d["expert_rows"] == k * layers and
                           d["experts_touched"] == k * layers and
                           d["expert_tile_rows"] >= d["expert_rows"]
                           for d in decodes)
    assert eng._pending_counts == []
