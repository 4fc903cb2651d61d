"""FastGen-equivalent engine tests: allocator, scheduler, paged decode vs full
forward, continuous batching.

Reference analog: tests/unit/inference/v2/{ragged,model_implementations}.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.blocked_allocator import BlockedAllocator
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2, V2EngineConfig
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig, plan_step, snap_bucket
from deepspeed_tpu.inference.v2.ragged_manager import StateManager
from deepspeed_tpu.models.llama import (
    LlamaConfig, LlamaForCausalLM, random_tokens, TINY_LLAMA)


def test_allocator_roundtrip():
    a = BlockedAllocator(8)
    blocks = a.allocate(5)
    assert len(set(blocks)) == 5 and a.free_blocks == 3
    a.free(blocks[:2])
    assert a.free_blocks == 5
    with pytest.raises(ValueError):
        a.allocate(6)
    more = a.allocate(5)
    assert a.free_blocks == 0
    assert len(set(more) | set(blocks[2:])) == 8


def test_allocator_invalid_free():
    a = BlockedAllocator(4)
    with pytest.raises(ValueError):
        a.free([9])


def test_scheduler_splitfuse():
    sm = StateManager()
    long_seq = sm.create(1, np.arange(5000) % 100)
    dec = sm.create(2, [1, 2, 3])
    dec.seen_tokens = 3
    dec.generated.append(7)
    cfg = SchedulerConfig(max_tokens_per_step=2048, prefill_buckets=(128, 512, 2048))
    plan = plan_step(sm.decoding(), sm.prefilling(), cfg)
    assert [s.uid for s in plan.decode_seqs] == [2]
    assert len(plan.prefill_chunks) == 1
    chunk = plan.prefill_chunks[0]
    assert chunk.length == 2047  # budget minus 1 decode token
    assert chunk.bucket == 2048


def test_snap_bucket():
    assert snap_bucket(3, (4, 8)) == 4
    assert snap_bucket(9, (4, 8)) == 8  # clamps to max


def _tiny_fp32():
    return LlamaConfig(**{**TINY_LLAMA.__dict__, "dtype": jnp.float32,
                          "max_seq_len": 512})


@pytest.fixture(scope="module")
def model_and_params():
    cfg = _tiny_fp32()
    model = LlamaForCausalLM(cfg)
    batch = random_tokens(1, 8, vocab_size=cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), batch)["params"]
    return cfg, model, params


@pytest.mark.slow
def test_paged_forward_matches_full(model_and_params):
    """Greedy generation via paged prefill+decode == argmax chain of the training
    model's full forward."""
    cfg, model, params = model_and_params
    prompt = list(np.random.default_rng(0).integers(0, cfg.vocab_size, 12))

    engine = InferenceEngineV2(params, cfg, V2EngineConfig(
        kv_block_size=16, kv_num_blocks=64,
        scheduler=SchedulerConfig(max_tokens_per_step=64,
                                  prefill_buckets=(16, 32, 64))))
    generated = engine.generate(prompt, max_new_tokens=5)

    # reference: iterative full-forward argmax
    ids = list(prompt)
    for _ in range(5):
        logits = model.apply({"params": params},
                             {"input_ids": np.asarray([ids], np.int32)},
                             method=LlamaForCausalLM.logits)
        ids.append(int(np.argmax(np.asarray(logits)[0, -1])))
    assert generated == ids[len(prompt):]


def test_chunked_prefill_matches_single_shot(model_and_params):
    """A prompt prefix processed in multiple SplitFuse chunks produces the same
    next token as one-shot prefill."""
    cfg, model, params = model_and_params
    prompt = list(np.random.default_rng(1).integers(0, cfg.vocab_size, 40))

    small = InferenceEngineV2(params, cfg, V2EngineConfig(
        kv_block_size=16, kv_num_blocks=64,
        scheduler=SchedulerConfig(max_tokens_per_step=16, prefill_buckets=(16,))))
    big = InferenceEngineV2(params, cfg, V2EngineConfig(
        kv_block_size=16, kv_num_blocks=64,
        scheduler=SchedulerConfig(max_tokens_per_step=64, prefill_buckets=(64,))))
    t_small = small.generate(prompt, max_new_tokens=3)
    t_big = big.generate(prompt, max_new_tokens=3)
    assert t_small == t_big


def test_continuous_batching_two_sequences(model_and_params):
    """Two sequences served concurrently produce the same tokens as served alone."""
    cfg, model, params = model_and_params
    rng = np.random.default_rng(2)
    p1 = list(rng.integers(0, cfg.vocab_size, 10))
    p2 = list(rng.integers(0, cfg.vocab_size, 17))

    solo1 = InferenceEngineV2(params, cfg).generate(p1, max_new_tokens=4, uid=0)
    solo2 = InferenceEngineV2(params, cfg).generate(p2, max_new_tokens=4, uid=0)

    eng = InferenceEngineV2(params, cfg)
    eng.put([10, 20], [p1, p2])
    for _ in range(10):
        eng.step()
        if len(eng.state.get(10).generated) >= 4 and \
           len(eng.state.get(20).generated) >= 4:
            break
    g1 = eng.flush(10)[:4]
    g2 = eng.flush(20)[:4]
    assert g1 == solo1[:4]
    assert g2 == solo2[:4]
    # all blocks returned
    assert eng.kv.free_blocks == eng.kv.allocator.total_blocks


def test_admission_control(model_and_params):
    cfg, model, params = model_and_params
    eng = InferenceEngineV2(params, cfg, V2EngineConfig(
        kv_block_size=16, kv_num_blocks=5))  # 4 usable blocks = 64 tokens
    assert eng.can_schedule([1], [32])
    assert not eng.can_schedule([1], [1000])
    with pytest.raises(RuntimeError):
        eng.put([1], [list(range(100))])


def test_paged_kernel_matches_gather_decode(model_and_params):
    """The Pallas paged-attention decode path (interpret mode) produces the same
    logits as the gather reference path."""
    from deepspeed_tpu.inference.v2.kv_cache import BlockedKVCache, KVCacheConfig
    from deepspeed_tpu.inference.v2.generic_decode import (decode_step_g,
                                                           prefill_chunk_g)
    from deepspeed_tpu.inference.v2.modules import LlamaPolicy
    cfg, model, params = model_and_params
    kv = BlockedKVCache(KVCacheConfig(
        num_layers=cfg.num_layers, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim_, block_size=16, num_blocks=32,
        dtype=jnp.float32))
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, 20)
    table = np.array([0, 1, 2, 3], np.int32)
    tokens = np.zeros(32, np.int32)
    tokens[:20] = prompt
    # the step programs consume the pool they are given: a pool that two
    # implementations are to start from goes to the first as a copy
    kw = dict(policy=LlamaPolicy, cfg=cfg, block_size=16)
    logits_g, cache_g, _ = prefill_chunk_g(
        params, jnp.copy(kv.data), jnp.asarray(tokens), 0, jnp.asarray(table),
        20, attn_impl="gather", **kw)
    logits_k, cache_k, _ = prefill_chunk_g(
        params, kv.data, jnp.asarray(tokens), 0, jnp.asarray(table), 20,
        attn_impl="kernel_interpret", **kw)
    np.testing.assert_allclose(np.asarray(logits_k), np.asarray(logits_g),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(cache_k), np.asarray(cache_g),
                               atol=1e-5, rtol=1e-5)

    dtok = jnp.asarray([int(np.argmax(np.asarray(logits_g))), 0], jnp.int32)
    dpos = jnp.asarray([20, 0], jnp.int32)
    tables = jnp.asarray([[0, 1, 2, 3], [31, 31, 31, 31]], jnp.int32)
    valid = jnp.asarray([True, False])
    out_g, _, _ = decode_step_g(params, jnp.copy(cache_g), dtok, dpos, tables,
                                valid, attn_impl="gather", **kw)
    out_k, _, _ = decode_step_g(params, cache_g, dtok, dpos, tables, valid,
                                attn_impl="kernel_interpret", **kw)
    np.testing.assert_allclose(np.asarray(out_k)[0], np.asarray(out_g)[0],
                               atol=1e-4, rtol=1e-4)


@pytest.mark.slow
def test_fp8_kv_cache_pages(model_and_params):
    """kv_cache_dtype='fp8': float8_e4m3 pages (half the KV memory of
    bf16 — 2x capacity),
    dequantized on load in both attention paths; greedy generation stays
    close to full-precision KV (identical on this model) and the pool
    really allocates fp8."""
    cfg, model, params = model_and_params
    prompt = [int(t)
              for t in np.random.default_rng(3).integers(0, cfg.vocab_size,
                                                         20)]

    def make(kvd):
        return InferenceEngineV2(params, cfg, V2EngineConfig(
            kv_block_size=16, kv_num_blocks=64,
            scheduler=SchedulerConfig(max_tokens_per_step=64,
                                      prefill_buckets=(16, 32, 64)),
            kv_cache_dtype=kvd))

    e8 = make("fp8")
    assert e8.kv.data.dtype == jnp.float8_e4m3fn
    g_full = make("model").generate(prompt, max_new_tokens=8)
    g_fp8 = e8.generate(prompt, max_new_tokens=8)
    # fp8 rounding can flip a late token on near-ties; the prefix must hold
    assert g_fp8[:4] == g_full[:4], (g_fp8, g_full)


def test_fp8_scaled_pages_outlier_accuracy():
    """Per-(head, page) scales keep fp8 pages accurate under outlier K/V
    magnitudes that the old scaleless clamp saturates (reference analog:
    group-scaled fp quantizer, csrc/fp_quantizer/fp_quantize.cu). Covers the
    write path (write_kv_scaled grow+requantize), the gather read path, and
    the Pallas kernel's scalar-prefetch scale indexing (interpret mode)."""
    from deepspeed_tpu.inference.v2.kv_cache import (cast_to_page_dtype,
                                                     write_kv_scaled)
    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_attention_reference)
    rng = np.random.default_rng(0)
    hkv, nb, bs, d, rep = 2, 8, 16, 32, 2
    t = 64                                       # context length (4 pages)
    k_ctx = rng.normal(size=(t, hkv, d)).astype(np.float32)
    v_ctx = rng.normal(size=(t, hkv, d)).astype(np.float32)
    k_ctx[10, 0] *= 2000.0                       # far beyond e4m3's 448
    v_ctx[33, 1] *= 1500.0
    block_ids = jnp.asarray(np.arange(t) // bs)
    offsets = jnp.asarray(np.arange(t) % bs)
    q = jnp.asarray(rng.normal(size=(1, 1, hkv * rep, d)), jnp.float32)
    tables = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
    start = jnp.asarray([t - 1], jnp.int32)

    # oracle: exact f32 pages
    f32p = jnp.zeros((2, hkv, nb, bs, d), jnp.float32)
    f32p = f32p.at[0, :, block_ids, offsets].set(jnp.asarray(k_ctx))
    f32p = f32p.at[1, :, block_ids, offsets].set(jnp.asarray(v_ctx))
    oracle = paged_attention_reference(q, f32p[0], f32p[1], tables, start)

    # scaled fp8 via the real write path (two calls exercise regrowth too)
    data = jnp.zeros((1, 2, hkv, nb, bs, d), jnp.float8_e4m3fn)
    scales = jnp.ones((1, 2, hkv, nb), jnp.float32)
    half = t // 2
    for kv, ctx in ((0, k_ctx), (1, v_ctx)):
        data, scales = write_kv_scaled(
            data, scales, 0, kv, jnp.asarray(ctx[:half]), block_ids[:half],
            offsets[:half], jnp.asarray([0, 1]))
        data, scales = write_kv_scaled(
            data, scales, 0, kv, jnp.asarray(ctx[half:]), block_ids[half:],
            offsets[half:], jnp.asarray([2, 3]))
    assert float(scales[0, 0, 0, 0]) > 1.0       # k outlier page grew
    assert float(scales[0, 1, 1, 2]) > 1.0       # v outlier page grew
    out_scaled = paged_attention_reference(
        q, data[0, 0], data[0, 1], tables, start,
        k_scales=scales[0, 0], v_scales=scales[0, 1])

    # old scaleless clamp
    datac = jnp.zeros((2, hkv, nb, bs, d), jnp.float8_e4m3fn)
    datac = datac.at[0, :, block_ids, offsets].set(
        cast_to_page_dtype(jnp.asarray(k_ctx), jnp.float8_e4m3fn))
    datac = datac.at[1, :, block_ids, offsets].set(
        cast_to_page_dtype(jnp.asarray(v_ctx), jnp.float8_e4m3fn))
    out_clamp = paged_attention_reference(q, datac[0], datac[1], tables, start)

    denom = float(jnp.max(jnp.abs(oracle)))
    err_scaled = float(jnp.max(jnp.abs(out_scaled - oracle))) / denom
    err_clamp = float(jnp.max(jnp.abs(out_clamp - oracle))) / denom
    assert err_scaled < 0.08, (err_scaled, err_clamp)
    assert err_clamp > 4 * err_scaled, (err_scaled, err_clamp)

    # Pallas kernel (interpret) with the scale prefetch == gather with scales
    out_kernel = paged_attention(
        q, data[0, 0], data[0, 1], tables, start,
        k_scales=scales[0, 0], v_scales=scales[0, 1], interpret=True)
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(out_scaled),
                               atol=1e-5, rtol=1e-4)


@pytest.mark.slow
def test_fp8_scaled_prefill_logit_error_bound(model_and_params):
    """64+-token prefill with outlier-inflated K/V projections: scaled fp8
    pages keep the last-token logits within a tight bound of the f32-cache
    logits (the scaleless clamp would saturate every K/V row of layer 0)."""
    from deepspeed_tpu.inference.v2.generic_decode import prefill_chunk_g
    from deepspeed_tpu.inference.v2.kv_cache import BlockedKVCache, KVCacheConfig
    from deepspeed_tpu.inference.v2.modules import LlamaPolicy
    cfg, model, params = model_and_params
    big = jax.tree.map(lambda x: x, params)      # shallow rebuild
    for w in ("wk", "wv"):
        big["model"]["layer_0"]["attn"][w] = jax.tree.map(
            lambda x: x * 30.0, big["model"]["layer_0"]["attn"][w])

    rngp = np.random.default_rng(7)
    tokens = np.zeros(128, np.int32)
    tokens[:80] = rngp.integers(0, cfg.vocab_size, 80)
    table = jnp.asarray(np.arange(8), jnp.int32)

    def run(dtype):
        kv = BlockedKVCache(KVCacheConfig(
            num_layers=cfg.num_layers, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim_, block_size=16, num_blocks=32,
            dtype=dtype))
        cache = kv.data if kv.scales is None else (kv.data, kv.scales)
        logits, _, _ = prefill_chunk_g(
            big, cache, jnp.asarray(tokens), 0, table, 80,
            policy=LlamaPolicy, cfg=cfg, block_size=16, attn_impl="gather")
        return np.asarray(logits)

    exact = run(jnp.float32)
    fp8 = run(jnp.float8_e4m3fn)
    err = float(np.max(np.abs(fp8 - exact)))
    spread = float(np.max(exact) - np.min(exact))
    assert err < 0.05 * spread, (err, spread)


def test_speculative_decode_fast_oracle(model_and_params):
    """Fast stand-in: oracle proposals are fully accepted and the emitted
    chain is exactly the plain greedy chain (full hit/miss/lookup matrix in
    the slow test below)."""
    cfg, model, params = model_and_params
    mk = lambda k: InferenceEngineV2(params, cfg, V2EngineConfig(
        kv_block_size=16, kv_num_blocks=64,
        scheduler=SchedulerConfig(max_tokens_per_step=64,
                                  prefill_buckets=(16, 32, 64)),
        speculative_k=k))
    prompt = list(np.random.default_rng(11).integers(0, cfg.vocab_size, 16))
    plain = mk(0).generate(prompt, max_new_tokens=8)
    eng = mk(4)
    eng._propose = lambda seq: plain[len(seq.generated):
                                     len(seq.generated) + 4]
    spec = eng.generate(prompt, max_new_tokens=8)
    assert spec == plain, (spec, plain)
    st = eng.speculative_stats()
    assert st["accepted"] == st["proposed"] > 0 and st["tokens_per_step"] > 2


def test_speculative_with_sampling_rejected_at_construction(model_and_params):
    """speculative_k + sampling must fail BEFORE any sequence state exists —
    failing inside the step would leave a half-processed sequence whose
    prefill already consumed KV blocks (round-4 advisor finding)."""
    cfg, _, params = model_and_params
    with pytest.raises(ValueError, match="greedy"):
        InferenceEngineV2(params, cfg, V2EngineConfig(
            greedy=False, speculative_k=4))


@pytest.mark.slow
def test_speculative_decode_exact_greedy_equivalence(model_and_params):
    """Speculative decoding (speculative_k>0): generation is EXACTLY the
    plain greedy chain whether proposals all hit (oracle), all miss
    (adversarial), or come from real prompt-lookup. Beyond-reference:
    FastGen has no speculative decoding."""
    cfg, model, params = model_and_params

    def make(spec_k):
        return InferenceEngineV2(params, cfg, V2EngineConfig(
            kv_block_size=16, kv_num_blocks=64,
            scheduler=SchedulerConfig(max_tokens_per_step=64,
                                      prefill_buckets=(16, 32, 64)),
            speculative_k=spec_k))

    prompt = list(np.random.default_rng(11).integers(0, cfg.vocab_size, 20))
    plain = make(0).generate(prompt, max_new_tokens=24)

    # oracle proposals (the true continuation): every proposal accepted,
    # ~k+1 tokens per verify step, output identical
    eng = make(4)
    eng._propose = lambda seq: plain[len(seq.generated):
                                     len(seq.generated) + 4]
    spec = eng.generate(prompt, max_new_tokens=24)
    assert spec[:len(plain)] == plain, (spec, plain)
    stats = eng.speculative_stats()
    assert stats["accepted"] == stats["proposed"] > 0, stats
    assert stats["tokens_per_step"] > 2.0, stats

    # adversarial proposals (always wrong): every proposal rejected, the
    # bonus/corrected token keeps the chain exact
    eng_bad = make(4)
    eng_bad._propose = lambda seq: [
        (plain[len(seq.generated) + i] + 1 + i) % cfg.vocab_size
        if len(seq.generated) + i < len(plain) else 1 for i in range(4)]
    spec_bad = eng_bad.generate(prompt, max_new_tokens=24)
    assert spec_bad[:len(plain)] == plain, (spec_bad, plain)
    assert eng_bad.speculative_stats()["accepted"] == 0

    # real prompt-lookup path end-to-end (proposals may or may not hit on a
    # random model — output must stay exact either way)
    spec_real = make(4).generate(prompt, max_new_tokens=24)
    assert spec_real[:len(plain)] == plain, (spec_real, plain)

    # sampling configs refuse AT CONSTRUCTION (acceptance compares argmax
    # chains; a step-time failure would leak a half-processed sequence)
    with pytest.raises(ValueError, match="greedy"):
        InferenceEngineV2(params, cfg, V2EngineConfig(
            kv_block_size=16, kv_num_blocks=64, greedy=False,
            speculative_k=4))


def test_speculative_propose_prompt_lookup(model_and_params):
    """_propose finds the continuation of the most recent earlier occurrence
    of the trailing n-gram (prompt-lookup decoding)."""
    cfg, model, params = model_and_params
    eng = InferenceEngineV2(params, cfg, V2EngineConfig(
        kv_block_size=16, kv_num_blocks=64, speculative_k=4,
        speculative_ngram=3))
    from deepspeed_tpu.inference.v2.ragged_manager import SequenceDescriptor
    seq = SequenceDescriptor(
        uid=0, prompt_tokens=np.asarray(
            [5, 6, 7, 9, 9, 1, 2, 3, 8, 8, 8, 8, 1, 2, 3], np.int32))
    # tail [1, 2, 3] occurred at index 5; continuation is [8, 8, 8, 8]
    assert eng._propose(seq) == [8, 8, 8, 8]
    # generated tokens extend the lookup context
    seq2 = SequenceDescriptor(
        uid=1, prompt_tokens=np.asarray([4, 1, 2, 3, 7, 7], np.int32))
    seq2.generated = [1, 2, 3]
    assert eng._propose(seq2) == [7, 7, 1, 2]     # continuation at index 1
    # no earlier occurrence -> no proposal
    seq3 = SequenceDescriptor(
        uid=2, prompt_tokens=np.asarray([1, 2, 3, 4, 5, 6], np.int32))
    assert eng._propose(seq3) == []


def test_speculative_decode_with_fp8_kv(model_and_params):
    """Speculation composes with scaled fp8 pages (the verifier chunk runs
    the scaled write path); greedy prefix still matches plain fp8 decode."""
    cfg, model, params = model_and_params
    base = list(np.random.default_rng(13).integers(0, cfg.vocab_size, 5))
    prompt = base * 4

    def make(spec_k):
        return InferenceEngineV2(params, cfg, V2EngineConfig(
            kv_block_size=16, kv_num_blocks=64,
            scheduler=SchedulerConfig(max_tokens_per_step=64,
                                      prefill_buckets=(16, 32, 64)),
            kv_cache_dtype="fp8", speculative_k=spec_k))

    plain = make(0).generate(prompt, max_new_tokens=12)
    spec = make(4).generate(prompt, max_new_tokens=12)
    assert spec[:4] == plain[:4], (spec, plain)   # fp8 near-tie tolerance


def test_fp8_scaled_cache_tuple_fast(model_and_params):
    """Fast stand-in: the (pages, scales) tuple cache flows through
    prefill_chunk_g — fp8 pool stays fp8, scales array round-trips, logits
    finite (the 80-token logit-error bound lives in the slow test)."""
    from deepspeed_tpu.inference.v2.generic_decode import prefill_chunk_g
    from deepspeed_tpu.inference.v2.kv_cache import BlockedKVCache, KVCacheConfig
    from deepspeed_tpu.inference.v2.modules import LlamaPolicy
    cfg, model, params = model_and_params
    kv = BlockedKVCache(KVCacheConfig(
        num_layers=cfg.num_layers, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim_, block_size=16, num_blocks=16,
        dtype=jnp.float8_e4m3fn))
    assert kv.scales is not None
    tokens = np.zeros(16, np.int32)
    tokens[:10] = np.random.default_rng(2).integers(0, cfg.vocab_size, 10)
    logits, (data, scales), _ = prefill_chunk_g(
        params, (kv.data, kv.scales), jnp.asarray(tokens), 0,
        jnp.asarray(np.arange(4), np.int32), 10, policy=LlamaPolicy,
        cfg=cfg, block_size=16, attn_impl="gather")
    assert np.isfinite(np.asarray(logits)).all()
    assert data.dtype == jnp.float8_e4m3fn
    assert scales.shape == data.shape[:4] and bool((scales >= 1.0).all())
