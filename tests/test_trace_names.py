"""Stable names on the device: the lowered text of the served steps and of
the train step holds each ``jax.named_scope`` and each Pallas kernel's
``name=``. Lowered here for the TPU platform without one (Mosaic lowers
without a device); nothing is compiled or run."""

import dataclasses
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference.v2 import generic_decode as gd
from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  V2EngineConfig)
from deepspeed_tpu.models.llama import (TINY_LLAMA, LlamaForCausalLM,
                                        random_tokens)

SERVED = ("embed", "attn/qkv", "attn/kv_write", "attn/paged", "attn/out",
          "lm_head")


def _scopes(lowered) -> str:
    """The location lines of a lowering: where name stacks are written."""
    text = lowered.as_text(debug_info=True)
    return "\n".join(l for l in text.splitlines() if l.startswith("#loc"))


def _kernels(lowered) -> set:
    return set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text()))


def _engine(model, cfg, example):
    params = model.init(jax.random.PRNGKey(0), example)["params"]
    return InferenceEngineV2(params, cfg, V2EngineConfig(kv_num_blocks=32))


def _lower_decode(eng):
    b, mb = 2, 2
    return gd.decode_step_g.trace(
        eng.params, eng.kv.data, jnp.zeros((b,), jnp.int32),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b, mb), jnp.int32),
        jnp.ones((b,), bool), policy=eng.policy, cfg=eng.model_config,
        block_size=eng.kv.cfg.block_size,
        attn_impl="kernel").lower(lowering_platforms=("tpu",))


def _lower_prefill(eng):
    return gd.prefill_chunk_g.trace(
        eng.params, eng.kv.data, jnp.zeros((16,), jnp.int32), 0,
        jnp.zeros((2,), jnp.int32), 11, policy=eng.policy,
        cfg=eng.model_config, block_size=eng.kv.cfg.block_size,
        attn_impl="kernel").lower(lowering_platforms=("tpu",))


@pytest.fixture(scope="module")
def llama_engine():
    cfg = dataclasses.replace(TINY_LLAMA, sliding_window=8)
    return _engine(LlamaForCausalLM(cfg), cfg,
                   random_tokens(1, 8, vocab_size=cfg.vocab_size))


@pytest.mark.parametrize("lower", [_lower_decode, _lower_prefill],
                         ids=["decode_step_g", "prefill_chunk_g"])
def test_served_steps_hold_every_scope_and_the_kernels_name(llama_engine,
                                                            lower):
    lowered = lower(llama_engine)
    scopes = _scopes(lowered)
    for name in SERVED + ("mlp",):
        assert f"/{name}/" in scopes, name
    assert _kernels(lowered) == {"paged_attention"}


def test_moe_step_holds_router_and_experts_scopes():
    from deepspeed_tpu.models.mixtral import TINY_MIXTRAL, MixtralForCausalLM
    cfg = TINY_MIXTRAL
    eng = _engine(MixtralForCausalLM(cfg), cfg,
                  random_tokens(1, 8, vocab_size=cfg.base.vocab_size))
    scopes = _scopes(_lower_decode(eng))
    for name in SERVED + ("moe/router",):
        assert f"/{name}/" in scopes, name
    # the experts' callee is lowered once, under a ``jit`` of its own and
    # without its caller's names: its name stacks open with the leaves of
    # ``moe/experts`` themselves (``telemetry/names.py`` SERVED_LEAF_SCOPES)
    for leaf in ("sort", "gather", "matmul", "combine"):
        assert f'"moe/experts/{leaf}/' in scopes, leaf


def test_sampler_holds_its_scope():
    from deepspeed_tpu.inference.v2.sampling import (SamplingConfig,
                                                     sample_tokens)
    lowered = sample_tokens.trace(jnp.zeros((2, 32)), jax.random.PRNGKey(0),
                                  SamplingConfig(temperature=0.7, top_k=4)
                                  ).lower()
    assert "/sample/" in _scopes(lowered)


def test_train_step_holds_its_scopes_and_the_flash_kernels_names():
    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import create_mesh
    from deepspeed_tpu.config.config import MeshConfig
    cfg = dataclasses.replace(TINY_LLAMA, attention_backend="flash",
                              sliding_window=128, remat=True, max_seq_len=512)
    batch = random_tokens(2, 256, vocab_size=cfg.vocab_size)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(cfg),
        config={"train_batch_size": 2, "train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True}, "steps_per_print": 10 ** 9},
        mesh=create_mesh(MeshConfig(data=1, fsdp=1),
                         devices=jax.devices()[:1]),
        example_batch=batch)
    engine._build_train_batch_fn()
    stacked = {"input_ids": batch["input_ids"][:, None]}
    # the kernel is chosen by the backend; the test steers that choice, the
    # program has no option for it
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = engine._train_batch_fn.trace(
            engine.state, engine._shard_batch(stacked, stacked=True),
            jax.random.PRNGKey(0)).lower(lowering_platforms=("tpu",))
    scopes = _scopes(lowered)
    for name in ("/attn/", "/mlp/", "lm_head_loss", "optimizer"):
        assert name in scopes, name
    assert _kernels(lowered) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
