"""The leaves of the served scopes that hold several kinds of work
(``telemetry/names.py`` ``SERVED_LEAF_SCOPES``): in the compiled step programs
of a toy JoyAI-LLM-Flash (latent pages, sigmoid-routed experts) and a toy
Mixtral (head pages, softmax-routed experts) every instruction under a parent
is under exactly one of its leaves, through the callees that are lowered under
a ``jit`` of their own; and the two host counts that ride beside
``expert_rows`` and ``latent_panels`` against what the programs' shapes say.
CPU, toy sizes."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.scope_readers import under as held
from deepspeed_tpu.inference.v2 import generic_decode as gd
from deepspeed_tpu.inference.v2 import modules
from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  V2EngineConfig)
from deepspeed_tpu.inference.v2.modules import policy_for
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models import (joyai_llm_flash, laguna, lfm2_moe, mimo_v2,
                                  mixtral, nemotron_h, qwen2_moe, xing4)
from deepspeed_tpu.telemetry import names
from deepspeed_tpu.telemetry.tracer import get_tracer

LEAVES = tuple(names.SERVED_LEAF_SCOPES)
PARENTS = sorted({leaf.rsplit("/", 1)[0] for leaf in LEAVES})
BLOCK = 16


def _toy(name):
    """(config, parameters) of a toy model, float32. The configuration holds
    a ``max_seq_len`` no other test file uses, so that the step programs are
    traced anew here whatever ran before in this process."""
    if name == "joyai":
        cfg = dataclasses.replace(joyai_llm_flash.TINY_JOYAI_FLASH,
                                  dtype=jnp.float32, max_seq_len=504)
        model = joyai_llm_flash.JoyAIFlashForCausalLM(cfg)
    else:
        cfg = dataclasses.replace(mixtral.TINY_MIXTRAL, base=dataclasses.replace(
            mixtral.TINY_MIXTRAL.base, dtype=jnp.float32, max_seq_len=112))
        model = mixtral.MixtralForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    return cfg, params


@pytest.fixture(scope="module", params=["joyai", "mixtral"])
def toy(request):
    return (request.param,) + _toy(request.param)


def _engine(cfg, params):
    return InferenceEngineV2(params, cfg, V2EngineConfig(
        kv_block_size=BLOCK, kv_num_blocks=32,
        ctx_block_buckets=(4, 8), decode_batch_buckets=(1, 2, 4),
        scheduler=SchedulerConfig(max_tokens_per_step=32, max_decode_batch=4,
                                  prefill_buckets=(16, 32))))


def _op_names(program, eng, *tail):
    text = program.lower(
        eng.params, eng.kv.pool, *tail, policy=eng.policy,
        cfg=eng.model_config, block_size=BLOCK,
        attn_impl=eng.config.attn_impl).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("program", ["decode_step_g", "prefill_chunk_g",
                                     "prefill_chunk_g+decode"])
def test_every_instruction_under_a_parent_is_under_one_of_its_leaves(
        toy, program):
    family, cfg, params = toy
    eng = _engine(cfg, params)
    ints = lambda *shape: jnp.zeros(shape, jnp.int32)       # noqa: E731
    decode = (ints(4), ints(4), ints(4, 4), jnp.ones((4,), bool))
    chunk = (ints(16), 0, ints(4), 11)
    if program == "decode_step_g":
        found = _op_names(gd.decode_step_g, eng, *decode)
    else:
        found = _op_names(gd.prefill_chunk_g, eng, *chunk,
                          *((decode,) if program.endswith("+decode") else ()))
    under = {p: [n for n in found if held(n, p)] for p in PARENTS}
    for parent, op_names in under.items():
        for op_name in op_names:
            mine = [leaf for leaf in LEAVES if held(op_name, leaf)]
            assert len(mine) == 1 and mine[0].startswith(parent + "/"), op_name
    # the callee under a ``jit`` of its own carries the leaves through it
    # (all but the call itself, which is what is left of the caller)
    routed = [n for n in found if "jit(_routed_sum)/" in n]
    assert routed and all(held(n, "moe/experts") for n in routed)
    want = {leaf for leaf in LEAVES if leaf.startswith("moe/experts/")}
    if family == "joyai":
        want |= {leaf for leaf in LEAVES
                 if ("latent_paged" in leaf and program != "prefill_chunk_g")
                 or ("latent_prefill" in leaf and program != "decode_step_g")}
    seen = {leaf for leaf in LEAVES if any(held(n, leaf) for n in found)}
    assert seen == want


@pytest.fixture
def tracing():
    tracer = get_tracer()
    was = tracer.enabled
    tracer.configure(enabled=True)      # the ring's capacity stays as it is:
    tracer.clear()                      # later files in this process need it
    yield tracer
    tracer.configure(enabled=was)
    tracer.clear()


def test_rows_gathered_are_the_step_programs_ids_size(toy, tracing,
                                                      monkeypatch):
    """``expert_rows_gathered`` beside ``expert_rows``: what the spans say
    against ``ids.size`` of every routed sum the step programs traced."""
    family, cfg, params = toy
    traced = []             # [rows of the program, ids.size a layer ...]

    def spy(traced_fn):
        def routed_sum(experts, h2, weights, ids, *rest, **kw):
            traced[-1].append(ids.size)
            return traced_fn(experts, h2, weights, ids, *rest, **kw)
        return routed_sum

    def step_states(states):
        def wrapped(params, cache, chunk, decode, *rest):
            rows = (chunk[0].shape[0] if chunk is not None else 0) + \
                (decode[0].shape[0] if decode is not None else 0)
            traced.append([rows])
            return states(params, cache, chunk, decode, *rest)
        return wrapped
    monkeypatch.setattr(modules, "_routed_sum", spy(modules._routed_sum))
    monkeypatch.setattr(gd, "_step_states", step_states(gd._step_states))
    eng = _engine(cfg, params)
    prompt = np.random.default_rng(5).integers(0, 100, 39).tolist()
    eng.generate(prompt, max_new_tokens=3)
    per_row = eng.policy.routed_assignments(cfg)
    assert traced and all(sum(sizes) == rows * per_row
                          for rows, *sizes in traced)
    assert all(len(sizes) * (sizes[0] // rows) == per_row
               for rows, *sizes in traced)           # top-k x routing layers
    spans = [e for e in tracing.events_snapshot()
             if e[1] in ("serve/prefill_chunk", "serve/step_decode")]
    chunks = [e[7] for e in spans if e[1] == "serve/prefill_chunk"]
    decodes = [e[7] for e in spans if e[1] == "serve/step_decode"]
    # 39 tokens in chunks of 32 and 7 (a bucket of 16): the first ends no
    # prompt and waits for nothing, so both programs' rows (each with the
    # idle decode half every chunk program carries) ride on the second's span
    half = eng._fused_decode[0]
    assert [c["bucket"] for c in chunks] == [32, 16]
    assert names.GATHERED_ROWS_ARG not in chunks[0]
    assert chunks[1][names.GATHERED_ROWS_ARG] == (32 + 16 + 2 * half) * per_row
    assert 0 < chunks[1]["expert_rows"] <= 39 * per_row
    assert decodes and all(
        d[names.GATHERED_ROWS_ARG] == d["bucket"] * per_row
        >= d["expert_rows"] > 0 for d in decodes)
    assert eng._pending_gathered == 0


def test_spans_say_how_many_rows_went_to_the_pool_and_how_many_by_page(
        toy, tracing):
    """``kv_rows_written`` and ``kv_rows_by_page``, one a span: a chunk's
    tokens and the decode rows that rode in its program; by page the chunk's
    tokens where its bucket is a block or more (blocks of 16 here, as the
    smallest bucket: the engine's rule says no to a bucket of 8), never a
    decode row."""
    _, cfg, params = toy
    eng = _engine(cfg, params)
    eng.put([1], [[7] * 39])            # chunks of 32 and 7 (a bucket of 16)
    eng.step()
    eng.put([2], [[9] * 5])             # a chunk beside a decode row
    eng.step()
    eng.step()
    spans = [e for e in tracing.events_snapshot()
             if e[1] in ("serve/prefill_chunk", "serve/step_decode")]
    chunks = [e[7] for e in spans if e[1] == "serve/prefill_chunk"]
    decodes = [e[7] for e in spans if e[1] == "serve/step_decode"]
    assert [(c["tokens"], c["bucket"]) for c in chunks] \
        == [(32, 32), (7, 16), (5, 16)]
    assert [c[names.KV_ROWS_WRITTEN_ARG] for c in chunks] \
        == [32, 7, 5 + chunks[2]["fused_rows"]]
    assert chunks[2]["fused_rows"] == 1
    assert [c[names.KV_ROWS_BY_PAGE_ARG] for c in chunks] == [32, 7, 5]
    assert eng.kv.chunk_by_page(16) and not eng.kv.chunk_by_page(8)
    assert decodes and all(
        d[names.KV_ROWS_WRITTEN_ARG] == d["batch"]
        and d[names.KV_ROWS_BY_PAGE_ARG] == 0 for d in decodes)


def test_an_untraced_engine_counts_no_gathered_rows(toy):
    _, cfg, params = toy
    assert not get_tracer().enabled
    eng = _engine(cfg, params)
    eng.put([1], [[5] * 20])            # one chunk: ends no wait for counts
    assert eng._pending_gathered == 0 and eng._pending_counts == []
    assert all(rec.gathered == 0 for rec in eng._pending)


@pytest.mark.parametrize("module,want", [
    # top-k x the layers that route, of each family's toy: by hand
    (mixtral, 2 * 2), (qwen2_moe, 2 * 2), (joyai_llm_flash, 4 * 3),
    (xing4, 4 * 3), (laguna, 4 * 4), (mimo_v2, 4 * 4), (nemotron_h, 3 * 2),
    (lfm2_moe, 2 * 4)], ids=lambda v: getattr(v, "__name__", str(v)))
def test_every_policy_that_counts_states_its_routed_assignments(module, want):
    (cfg,) = [getattr(module, n) for n in dir(module) if n.startswith("TINY")]
    assert policy_for(cfg).routed_assignments(cfg) == want


def test_latent_keys_gathered_is_what_a_layers_call_gathers():
    """``latent_keys_gathered`` beside ``latent_panels``: the table's keys in
    whole panels of the call, whatever the chunk's horizon."""
    from deepspeed_tpu.inference.v2.kv_cache import (BlockedKVCache,
                                                     KVCacheConfig)
    from deepspeed_tpu.ops.pallas.latent_attention import prefill_keys
    kv = BlockedKVCache(KVCacheConfig(num_layers=1, num_kv_heads=1,
                                      head_dim=576, latent_dim=576,
                                      block_size=64, num_blocks=4))
    # docqa's shapes: a first chunk of 4,096 over a table of 64 blocks, a
    # second at 4,096 over one of 136 (8,704 keys: 17 panels of 512)
    first = kv.chunk_tile_keys(0, 4096, 64, None)
    second = kv.chunk_tile_keys(4096, 2048, 136, None)
    assert first[names.LATENT_KEYS_ARG] == prefill_keys(4096, 4096) == 4096
    assert second[names.LATENT_KEYS_ARG] == prefill_keys(2048, 8704) == 8704
    # a table that ends inside a panel is gathered to the panel's end
    assert kv.chunk_tile_keys(0, 1024, 9, None)[names.LATENT_KEYS_ARG] == 1024
    assert {"latent_panels", "latent_panels_masked",
            "latent_panels_dead"} < set(first)
    # head pages gather nothing of the kind
    heads = BlockedKVCache(KVCacheConfig(num_layers=1, num_kv_heads=2,
                                         head_dim=64, num_blocks=4))
    assert names.LATENT_KEYS_ARG not in heads.chunk_tile_keys(0, 64, 4, None)
