"""Serving decode-throughput microbench — prints ONE JSON line.

Drives the FastGen-equivalent continuous-batching engine (InferenceEngineV2)
end-to-end: a batch of concurrent sequences prefills, then decodes in lockstep;
steady-state decode tokens/sec is the headline. ``vs_baseline`` is the speedup
of the Pallas paged-attention kernel over the gather-based fallback at a
2048-token context, measured attention-only (the reference's FastGen headline —
2.3x vLLM — is against an external system we can't run here; the engine-level
tokens/sec is dominated by the host round trip of each step, so the kernel's
contribution is reported at the op level where it is visible).

Needs a TPU: without one it prints one line and exits nonzero, and the record
names the device it was measured on.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


_PARAM_CACHE = {}


def _make_engine(attn_impl: str, kv_dtype: str = "model"):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2, V2EngineConfig
    from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM, random_tokens

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_layers=12, num_heads=16, num_kv_heads=8, max_seq_len=4096,
        dtype=jnp.bfloat16)
    if not _PARAM_CACHE:           # one init + upload across all table rows
        model = LlamaForCausalLM(cfg)
        params = model.init(
            jax.random.PRNGKey(0),
            random_tokens(1, 8, vocab_size=cfg.vocab_size))["params"]
        _PARAM_CACHE["params"] = jax.device_put(jax.tree.map(
            lambda x: x.astype(cfg.dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params))
    params = _PARAM_CACHE["params"]

    engine = InferenceEngineV2(params, cfg, V2EngineConfig(
        kv_block_size=64, kv_num_blocks=1024,
        scheduler=SchedulerConfig(max_tokens_per_step=2048,
                                  prefill_buckets=(256,)),
        attn_impl=attn_impl, kv_cache_dtype=kv_dtype))
    return engine, cfg


def run(attn_impl: str, batch: int, prompt_len: int, decode_steps: int,
        kv_dtype: str = "model"):
    import numpy as np

    engine, cfg = _make_engine(attn_impl, kv_dtype)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab_size, prompt_len))
               for _ in range(batch)]
    engine.put(list(range(batch)), prompts)

    for _ in range(3):                       # warm the decode bucket
        engine.step()
    t0 = time.time()
    for _ in range(decode_steps):
        engine.step()
    dt = time.time() - t0
    for uid in range(batch):
        engine.flush(uid)
    return batch * decode_steps / dt


def serving_table(attn_impl: str, prompt_len: int, decode_steps: int):
    """The FastGen-comparison table (reference:
    blogs/deepspeed-fastgen/README.md:28,163,168 — tokens/s + TTFT p50/p95
    across load points): 3 batch mixes x {model-dtype, fp8-scaled} KV pages.
    Enabled by DSTPU_DECODE_TABLE=1 (adds several engine compiles of chip
    time); rows land in the JSON line's extra.serving_table."""
    rows = []
    for kv_dtype in ("model", "fp8"):
        for batch in (4, 16, 32):
            tps = run(attn_impl, batch, prompt_len, decode_steps,
                      kv_dtype=kv_dtype)
            arrivals = max(batch // 2, 1)
            # window must admit every arrival (steps 4..4*arrivals) plus a
            # steady tail so the heaviest row measures its labeled load
            mixed = mixed_load(attn_impl, initial=max(batch // 2, 1),
                               arrivals=arrivals, arrive_every=4,
                               prompt_len=prompt_len,
                               max_steps=4 * arrivals + 32,
                               kv_dtype=kv_dtype)
            rows.append({"kv": kv_dtype, "batch": batch,
                         "decode_tokens_per_sec": round(tps, 1),
                         "mixed_tokens_per_sec":
                             mixed["mixed_tokens_per_sec"],
                         "ttft_p50_ms": mixed["ttft_p50_ms"],
                         "ttft_p95_ms": mixed["ttft_p95_ms"]})
    return rows


def mixed_load(attn_impl: str, initial: int, arrivals: int,
               arrive_every: int, prompt_len: int, max_steps: int,
               kv_dtype: str = "model"):
    """Continuous-batching under MIXED prefill/decode load (the FastGen
    serving scenario the attention-only number can't show): ``initial``
    sequences arrive together, then one more every ``arrive_every`` steps —
    each arrival's prompt chunks through the SplitFuse scheduler while the
    resident sequences keep decoding. Reports overall emitted tokens/s and
    TTFT (put -> first sampled token) p50/p95.
    Reference analog: the FastGen latency/throughput benchmark
    (mii/benchmarks), reference blogs' SplitFuse headline."""
    import numpy as np

    engine, cfg = _make_engine(attn_impl, kv_dtype)
    rng = np.random.default_rng(0)
    total = initial + arrivals

    def prompt():
        return list(rng.integers(0, cfg.vocab_size, prompt_len))

    put_time = {}
    first_tok = {}
    t0 = time.time()
    engine.put(list(range(initial)), [prompt() for _ in range(initial)])
    for u in range(initial):
        put_time[u] = t0
    emitted = 0
    next_uid = initial
    now = t0
    for step_i in range(max_steps):
        if next_uid < total and step_i and step_i % arrive_every == 0:
            put_time[next_uid] = time.time()
            engine.put([next_uid], [prompt()])
            next_uid += 1
        out = engine.step()
        now = time.time()
        for uid in out:
            first_tok.setdefault(uid, now)
        emitted += len(out)
        # max_steps IS the measurement window: throughput is sustained mixed
        # load over the whole window, TTFTs accrue as arrivals get served
    for u in list(put_time):
        engine.flush(u)
    tps = emitted / max(now - t0, 1e-9)
    ttfts = sorted(first_tok[u] - put_time[u] for u in first_tok)
    pct = lambda p: ttfts[min(len(ttfts) - 1, int(p * len(ttfts)))] \
        if ttfts else float("nan")  # noqa: E731
    return {"mixed_tokens_per_sec": round(tps, 1),
            "ttft_p50_ms": round(pct(0.50) * 1e3, 1),
            "ttft_p95_ms": round(pct(0.95) * 1e3, 1),
            "sequences": total, "served_first_token": len(ttfts),
            "arrive_every_steps": arrive_every}


def attention_microbench(ctx: int = 2048, bs: int = 64):
    """Attention-only kernel vs gather at serving shapes; returns (ms_k, ms_g)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_attention_reference)
    rng = np.random.default_rng(0)
    hkv, d, b, h = 8, 128, 16, 32
    mb = ctx // bs
    nblk = b * mb + 8
    kp = jnp.asarray(rng.normal(size=(hkv, nblk, bs, d)), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=(hkv, nblk, bs, d)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.bfloat16)
    tables = jnp.asarray(
        rng.permutation(nblk - 1)[: b * mb].reshape(b, mb), jnp.int32)
    start = jnp.full((b,), ctx - 1, jnp.int32)

    def timeit(f, n=30):
        r = f()
        float(jax.device_get(jnp.sum(r.astype(jnp.float32))))
        t0 = time.time()
        for _ in range(n):
            r = f()
        float(jax.device_get(jnp.sum(r.astype(jnp.float32))))
        return (time.time() - t0) / n * 1e3

    fk = jax.jit(lambda: paged_attention(q, kp, vp, tables, start))
    fr = jax.jit(lambda: paged_attention_reference(q, kp, vp, tables, start))
    return timeit(fk), timeit(fr)


def speculative_gate(decode_tokens: int = 64, n_prompts: int = 4,
                     train_steps: int = 300, spec_k: int = 8):
    """Speculative-decoding quality gate on REAL text (round-4 verdict #8:
    prompt-lookup proposals are data-dependent, so oracle tests prove
    exactness but not value). Trains a byte-level LM on the repo's own
    docs/README (the only real corpus available with zero egress), then
    generates continuations of held-out corpus prompts with speculative on
    vs off and reports tokens/step, acceptance rate, and the wall-clock
    speedup at EQUAL (greedy-identical) output."""
    import glob as _glob

    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                      V2EngineConfig)
    from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    here = os.path.dirname(os.path.abspath(__file__))
    text = "\n".join(
        open(p, errors="ignore").read()
        for p in [os.path.join(here, "README.md")] +
        sorted(_glob.glob(os.path.join(here, "docs", "*.md"))))
    corpus = np.frombuffer(text.encode(), np.uint8).astype(np.int32)

    seq, bs = 128, 16
    cfg = LlamaConfig(vocab_size=256, hidden_size=128, intermediate_size=256,
                      num_layers=2, num_heads=4, num_kv_heads=2,
                      max_seq_len=2048, dtype=jnp.float32,
                      attention_backend="xla", remat=False)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(cfg),
        config={"train_batch_size": bs * len(jax.devices()),
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "steps_per_print": 10 ** 9},
        example_batch={"input_ids": np.zeros((2, seq), np.int32)})
    rng = np.random.default_rng(0)
    held_out = len(corpus) - 4096              # tail reserved for prompts
    losses = []
    for _ in range(train_steps):
        starts = rng.integers(0, held_out - seq, bs * len(jax.devices()))
        ids = np.stack([corpus[s:s + seq] for s in starts])
        losses.append(float(jax.device_get(
            engine.train_batch(batch={"input_ids": ids}))))
    params = jax.device_get(engine.state.params)

    def mk(k):
        return InferenceEngineV2(params, cfg, V2EngineConfig(
            kv_block_size=32, kv_num_blocks=256,
            scheduler=SchedulerConfig(max_tokens_per_step=512,
                                      prefill_buckets=(64, 128)),
            speculative_k=k))
    prompts = [list(corpus[held_out + i * 512: held_out + i * 512 + 128])
               for i in range(n_prompts)]

    def gen(k):
        eng = mk(k)
        outs, t = [], 0.0
        for p in prompts:
            t0 = time.time()
            outs.append(eng.generate(p, max_new_tokens=decode_tokens))
            t += time.time() - t0
        return outs, t, eng
    # one untimed warm-up per engine kind BEFORE any timed run: the k=0 and
    # k=spec_k engines compile different programs (decode-only vs verify
    # chunks), so warming only one side banks the other's compile time into
    # its timed pass and skews speedup_at_equal_output
    _ = gen(0)
    _ = gen(spec_k)
    plain_out, plain_t, _ = gen(0)
    spec_out, spec_t, eng = gen(spec_k)
    st = eng.speculative_stats()
    equal = plain_out == spec_out
    return {
        "corpus": "repo README+docs bytes",
        "corpus_bytes": int(len(corpus)),
        "train_steps": train_steps,
        "train_loss_first_last": [round(losses[0], 3), round(losses[-1], 3)],
        "speculative_k": spec_k,
        "tokens_per_step": st["tokens_per_step"],
        "acceptance_rate": round(st["accepted"] / max(st["proposed"], 1), 3),
        "proposed": st["proposed"], "accepted": st["accepted"],
        "output_equal_to_plain_greedy": bool(equal),
        "plain_tokens_per_sec": round(
            n_prompts * decode_tokens / max(plain_t, 1e-9), 1),
        "spec_tokens_per_sec": round(
            n_prompts * decode_tokens / max(spec_t, 1e-9), 1),
        "speedup_at_equal_output": round(plain_t / max(spec_t, 1e-9), 3),
    }


def main():
    batch = int(os.environ.get("DSTPU_DECODE_BATCH", 16))
    prompt_len = int(os.environ.get("DSTPU_DECODE_PROMPT", 256))
    steps = int(os.environ.get("DSTPU_DECODE_STEPS", 64))
    from deepspeed_tpu.accelerator import require_tpu
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    devices = require_tpu("bench_decode")
    enable_compile_cache()
    impl = "kernel"
    tps = run(impl, batch, prompt_len, steps)
    mixed = mixed_load(impl, initial=max(batch // 2, 1),
                       arrivals=max(batch // 2, 1), arrive_every=4,
                       prompt_len=prompt_len,
                       max_steps=int(os.environ.get(
                           "DSTPU_DECODE_MIXED_STEPS", 96)))
    ms_k, ms_g = attention_microbench()
    speedup = ms_g / max(ms_k, 1e-9)
    extra = {"batch": batch, "prompt_len": prompt_len,
             "decode_steps": steps, "attn_impl": impl,
             "paged_attn_kernel_ms": round(ms_k, 2),
             "paged_attn_gather_ms": round(ms_g, 2),
             "attn_ctx": 2048, **mixed}
    if os.environ.get("DSTPU_DECODE_TABLE") == "1":
        extra["serving_table"] = serving_table(impl, prompt_len, steps)
    if os.environ.get("DSTPU_DECODE_SPEC") == "1":
        extra["speculative"] = speculative_gate()

    record = {
        "metric": "llama_decode_tokens_per_sec",
        "value": round(tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(speedup, 3),
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
        "extra": extra,
    }
    print(json.dumps(record))


if __name__ == "__main__":
    main()
