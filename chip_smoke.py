"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, in one process, through the entry points a
user calls, at the full width of one model the repo supports (Mistral-7B:
h4096, ff14336, 32 q / 8 kv heads x d128, sliding window 4096, vocab 32000;
``models/families.py``). No width is cut. Depth is cut to fit one 16 GB chip
(``full_size`` below) and the weights are random, made from a seed. Three
legs, each of which fails the run:

1. kernels  — flash fwd+bwd and paged attention compile with
              ``interpret=False`` at the run's own shapes, window live, and
              agree with their ``jnp`` references;
2. trainer  — ``deepspeed_tpu.initialize()`` -> ``engine.train_batch()`` on
              every chip found (one chip: ZeRO-0; N chips: ZeRO-3 over
              ``fsdp=N``), bf16, AdamW, remat, ``attention_backend="flash"``;
3. server   — ``InferenceEngineV2`` -> ``InferenceServer`` ->
              ``ServingFrontend`` on 127.0.0.1, ``attn_impl="kernel"``,
              concurrent sync and streamed ``/generate`` requests, then a drain.

Takes no arguments and accepts no CPU: without a TPU it prints one line and
exits 1 before any model is built. Each leg prints a line with its facts, a
``chip_smoke: summary {...}`` line carries them all (seconds, compile seconds,
peak HBM, ``"claim": null``), and the last line of stdout is exactly
``{"ok": true, "device": {"platform", "kind", "count"}}`` with exit code 0; a
leg that fails on a chip ends stdout with ``"ok": false`` and exits nonzero.
The legs are functions of a ``Size`` so that tier-1 runs the same code at a
toy size on the CPU with the kernels in interpret mode
(``tests/test_chip_smoke.py``).
"""

import dataclasses
import gc
import http.client
import importlib.metadata
import json
import math
import sys
import threading
import time
from typing import Any, Tuple

# --- tolerances, with their reasons ---------------------------------------
# Kernel against reference: largest absolute error over the largest absolute
# reference value. Inputs are bf16; the reference runs on the same values in
# fp32 at "highest" matmul precision. The kernels round the softmax weights
# to bf16 before the PV matmul and the result to bf16 once more (bf16 keeps 8
# bits of mantissa: 2^-8 = 3.9e-3 a rounding), and the backward rounds dS to
# bf16 as well. Measured on a v5e: 2.2e-3 to 3.8e-3 in all seven comparisons
# (my chip run, PR 21). Three roundings' worth is allowed; fp8 pages (3 bits
# of mantissa, 6e-2) or a mask left out would be far outside it.
KERNEL_TOL = 1.2e-2
# First-step loss, N chips under ZeRO-3 against one chip at the same global
# batch: both layouts run one row per device per micro-step through the same
# bf16 program, so only the order of the fp32 mean over rows differs.
LAYOUT_LOSS_RTOL = 2e-3


class SmokeError(RuntimeError):
    """A check of one of the legs did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeError(what)


@dataclasses.dataclass(frozen=True)
class Size:
    model: Any                    # a LlamaConfig: every width comes from here
    train_layers: int             # depth of the trained model
    serve_layers: int             # depth of the served model
    seq_len: int                  # trained sequence; > window so it masks
    global_batch: int             # rows per optimizer step, on any chip count
    train_steps: int
    prompt_lens: Tuple[int, ...]  # one concurrent request per entry
    max_new_tokens: int
    kv_block_size: int
    kv_num_blocks: int
    interpret: bool               # Pallas kernels in interpret mode (CPU)


def full_size() -> Size:
    """Mistral-7B widths, depth cut for one v5e chip (16 GB). Training: 2 of
    32 layers = 698M parameters; fp32 masters + AdamW moments + bf16 gradient
    accumulator are 14 bytes a parameter, 9.8 GB, and XLA plans 14.8 GB for
    the whole step at 8192 tokens a row. Serving: 8 of 32 layers in bf16 =
    4.0 GB of weights + 1.1 GB of KV pages. Prompts run from a few hundred to
    3000 tokens, so the longer ones are prefilled in several SplitFuse chunks
    and every context stays under the engine's 64 x 64-token block table."""
    from deepspeed_tpu.models.families import MISTRAL_7B
    return Size(model=MISTRAL_7B, train_layers=2, serve_layers=8,
                seq_len=8192, global_batch=4, train_steps=4,
                prompt_lens=(384, 900, 1700, 2600, 3000, 640),
                max_new_tokens=32, kv_block_size=64, kv_num_blocks=512,
                interpret=False)


# --- set-up facts: compile seconds and device memory -----------------------

class CompileClock:
    """Seconds jax spent in backend compiles (on a persistent-cache hit: in
    reading the cache), and hit / miss counts, from jax's own monitoring."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def memory_per_device(devices) -> list:
    """``memory_stats()`` of every device ({} where the backend has none)."""
    out = []
    for d in devices:
        s = d.memory_stats() or {}
        out.append({k: int(s[k]) for k in ("bytes_in_use",
                                           "peak_bytes_in_use",
                                           "bytes_limit") if k in s})
    return out


def _rel_err(got, ref) -> float:
    import jax.numpy as jnp
    got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))


def _mosaic_calls(jitted, *args) -> int:
    return jitted.lower(*args).as_text().count("tpu_custom_call")


# --- leg 1: kernels ---------------------------------------------------------

def leg_kernels(size: Size) -> dict:
    """Flash fwd+bwd and paged attention at the run's shapes against their
    references. One KV head's group of q heads is compared: the grids run
    heads one after another, and the fp32 reference holds the whole [S, S]
    score matrix for each."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.flash_attention import attention_reference
    from deepspeed_tpu.ops.pallas.flash_attention import (
        flash_attention_auto, pallas_flash_attention)
    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_attention_reference)

    m = size.model
    d, window, s = m.head_dim_, m.sliding_window, size.seq_len
    rep = m.num_heads // m.num_kv_heads
    rng = np.random.default_rng(0)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

    def f32(*xs):
        return tuple(x.astype(jnp.float32) for x in xs)

    facts = {}

    def agree(name, got, ref):
        facts[name] = _rel_err(got, ref)
        check(facts[name] <= KERNEL_TOL,
              f"{name} {facts[name]:.3e} against a tolerance of {KERNEL_TOL}")

    # flash: the block choice the trainer gets (flash_attention_auto)
    if size.interpret:
        blk = min(256, s)

        def flash(q, k, v):
            return pallas_flash_attention(q, k, v, True, blk, blk, True,
                                          window, None)
    else:
        def flash(q, k, v):
            return flash_attention_auto(q, k, v, causal=True, window=window)

    def grads_of(attn):
        # a fixed cotangent: d(sum(out * w)) so that every row matters
        return jax.jit(jax.value_and_grad(
            lambda q, k, v, w: jnp.sum(attn(q, k, v).astype(jnp.float32) * w),
            argnums=(0, 1, 2)))

    q, k, v = normal(1, s, rep, d), normal(1, s, 1, d), normal(1, s, 1, d)
    w = normal(1, s, rep, d).astype(jnp.float32)
    flash_j = jax.jit(flash)
    check(size.interpret or _mosaic_calls(flash_j, q, k, v) > 0,
          "flash: no Mosaic tpu_custom_call in the lowered kernel")
    out = flash_j(q, k, v)
    _, got = grads_of(flash)(q, k, v, w)
    with jax.default_matmul_precision("highest"):
        def reference(q, k, v):
            return attention_reference(q, k, v, causal=True, window=window)
        ref_out = jax.jit(reference)(*f32(q, k, v))
        _, ref = grads_of(reference)(*f32(q, k, v), w)
    agree("flash_fwd_err", out, ref_out)
    for name, g, r in zip(("dq", "dk", "dv"), got, ref):
        agree(f"flash_{name}_err", g, r)
    del q, k, v, w, out, got, ref_out, ref

    # paged: a decode batch and prefill chunks, contexts past the window so
    # that it masks and whole pages fall below it
    bs = size.kv_block_size
    hkv, h = m.num_kv_heads, m.num_heads
    mb = 2 * window // bs                      # table covers 2 x window
    batch = 8
    nb = batch * mb + 1                        # + the trash page
    k_pages, v_pages = normal(hkv, nb, bs, d), normal(hkv, nb, bs, d)
    tables = jnp.asarray(
        rng.permutation(nb - 1)[:batch * mb].reshape(batch, mb), jnp.int32)
    chunk = min(2048, window)
    shapes = {
        # every row at its own depth, from inside the window to twice it
        "decode": (normal(batch, 1, h, d), tables, jnp.asarray(
            np.linspace(window // 8, 2 * window - 1, batch), jnp.int32)),
        # the tallest prefill bucket (its q fold is cut into row blocks) ...
        "prefill": (normal(1, chunk, h, d), tables[:1],
                    jnp.asarray([2 * window - chunk], jnp.int32)),
        # ... and one whose fold is a single block
        "prefill_short": (normal(1, chunk // 8, h, d), tables[:1],
                          jnp.asarray([window + 3], jnp.int32)),
    }
    for name, (q, tab, start) in shapes.items():
        paged = jax.jit(lambda q, kp, vp, tab, start: paged_attention(
            q, kp, vp, tab, start, window=window, interpret=size.interpret))
        check(size.interpret
              or _mosaic_calls(paged, q, k_pages, v_pages, tab, start) > 0,
              f"paged {name}: no Mosaic tpu_custom_call")
        out = paged(q, k_pages, v_pages, tab, start)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda q, kp, vp, tab, start:
                          paged_attention_reference(
                              q, kp, vp, tab, start, window=window))(
                *f32(q, k_pages, v_pages), tab, start)
        agree(f"paged_{name}_err", out, ref)
    return facts


# --- leg 2: trainer ---------------------------------------------------------

def _train(size: Size, devices, steps: int) -> dict:
    """``steps`` optimizer steps on the same batch over ``devices``."""
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import create_mesh
    from deepspeed_tpu.config.config import MeshConfig
    from deepspeed_tpu.models.llama import LlamaForCausalLM, random_tokens
    from deepspeed_tpu.telemetry.compiles import compiles_total

    n = len(devices)
    check(size.global_batch % n == 0,
          f"global batch {size.global_batch} does not split over {n} chips")
    gas = size.global_batch // n               # one row per chip per micro
    cfg = dataclasses.replace(size.model, num_layers=size.train_layers,
                              attention_backend="flash", remat=True)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(cfg),
        mesh=create_mesh(MeshConfig(data=1, fsdp=n), devices=devices),
        config={
            "train_batch_size": size.global_batch,
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": gas,
            "optimizer": {"type": "AdamW", "params": {"lr": 3e-4}},
            "bf16": {"enabled": True},
            "data_types": {"grad_accum_dtype": "bf16"},
            "zero_optimization": {"stage": 0 if n == 1 else 3},
            "steps_per_print": 10 ** 9,
        },
        example_batch=random_tokens(n, size.seq_len,
                                    vocab_size=cfg.vocab_size))
    batch = random_tokens(n, size.seq_len, vocab_size=cfg.vocab_size,
                          seed=1, gas=gas)

    def step():
        return float(jax.device_get(
            engine.train_batch(batch=batch, stacked=True)))

    mark = compiles_total()
    losses = [step()]                          # the warm step compiles
    check(compiles_total() > mark,
          "compile ledger is dead: the warm step counted no compile")
    mark = compiles_total()
    losses += [step() for _ in range(steps - 1)]
    facts = {"chips": n, "zero_stage": 0 if n == 1 else 3, "gas": gas,
             "losses": [round(x, 4) for x in losses],
             "compiles_after_warm": compiles_total() - mark}
    check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    check(facts["compiles_after_warm"] == 0,
          f"{facts['compiles_after_warm']} compile(s) after the warm step")
    check(steps == 1 or losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")
    if not size.interpret:
        lowered = engine._train_batch_fn.lower(
            engine.state, engine._shard_batch(batch, stacked=True),
            jax.random.PRNGKey(0))
        # flash must not have given way to a reference inside the step
        facts["mosaic_calls"] = lowered.as_text().count("tpu_custom_call")
        check(facts["mosaic_calls"] > 0,
              "no Mosaic tpu_custom_call in the lowered train step")
        # what XLA plans for the step on each device, temporaries included
        # (the allocator's peak_bytes_in_use below does not count those)
        plan = lowered.compile().memory_analysis()
        facts["planned_bytes_per_device"] = (
            plan.argument_size_in_bytes + plan.output_size_in_bytes
            + plan.temp_size_in_bytes - plan.alias_size_in_bytes)

    # where the parameter + optimizer bytes live
    per_device = {d.id: 0 for d in devices}
    total = 0
    for leaf in jax.tree.leaves((engine.state.params,
                                 engine.state.opt_state)):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] += shard.data.nbytes
    facts["state_bytes_total"] = total
    facts["state_bytes_per_device"] = list(per_device.values())
    facts["memory"] = memory_per_device(devices)
    if n > 1:
        # ZeRO-3: about total / n each (norm scales and other small leaves
        # stay replicated), and every chip really holds memory
        check(max(per_device.values()) <= 1.1 * total / n,
              f"state not split {n} ways: {per_device} of {total}")
        # (the CPU backend of the toy run has no memory_stats)
        for dev, bytes_held, mem in zip(devices, per_device.values(),
                                        facts["memory"]):
            check(size.interpret or mem.get("bytes_in_use", 0) >= bytes_held,
                  f"{dev}: reports {mem} with {bytes_held} bytes of state")
    return facts


def leg_trainer(size: Size, devices) -> dict:
    """The trainer on every chip; on several chips also one step on one chip
    at the same global batch, whose loss the sharded run must reproduce."""
    import jax
    facts = _train(size, devices, size.train_steps)
    if len(devices) > 1:
        gc.collect()
        jax.clear_caches()
        solo = _train(size, devices[:1], 1)
        facts["one_chip_first_loss"] = solo["losses"][0]
        check(abs(facts["losses"][0] - solo["losses"][0])
              <= LAYOUT_LOSS_RTOL * abs(solo["losses"][0]),
              f"first-step loss {facts['losses'][0]} on {len(devices)} chips "
              f"against {solo['losses'][0]} on one")
    return facts


# --- leg 3: server ----------------------------------------------------------

def _generate(port: int, prompt, max_new: int, stream: bool):
    """One ``POST /generate``; returns (status, final record, tokens)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    try:
        conn.request("POST", "/generate", body=json.dumps(
            {"prompt_tokens": prompt, "max_new_tokens": max_new,
             "stream": stream}),
            headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read().decode()
    finally:
        conn.close()
    if not stream or resp.status != 200:
        record = json.loads(body)
        return resp.status, record, record.get("tokens")
    lines = [json.loads(line) for line in body.splitlines() if line]
    return resp.status, lines[-1], [rec["token"] for rec in lines[:-1]]


def leg_server(size: Size) -> dict:
    import numpy as np

    from deepspeed_tpu.inference.v2.engine_v2 import V2EngineConfig
    from deepspeed_tpu.serving import ServingFrontend
    from deepspeed_tpu.serving.cli import build_server
    from deepspeed_tpu.telemetry.compiles import compiles_total

    compiled_before = compiles_total()
    cfg = dataclasses.replace(size.model, num_layers=size.serve_layers)
    server = build_server(cfg, V2EngineConfig(
        kv_block_size=size.kv_block_size, kv_num_blocks=size.kv_num_blocks,
        attn_impl="kernel_interpret" if size.interpret else "kernel")).start()
    # a cold first request waits for its shapes to compile
    frontend = ServingFrontend(server, port=0, request_timeout_s=900.0).start()
    rng = np.random.default_rng(2)
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, n)]
               for n in size.prompt_lens]
    new = size.max_new_tokens
    facts = {"requests": 0}

    def finished(status, record, tokens, who):
        facts["requests"] += 1
        check(status == 200 and record.get("state") == "finished"
              and len(tokens or ()) == new,
              f"{who}: status {status}, {record}")

    try:
        # same greedy prompt, alone each time: same batch shapes, so sync and
        # streamed tokens must be identical
        sync = _generate(frontend.port, prompts[0], new, stream=False)
        streamed = _generate(frontend.port, prompts[0], new, stream=True)
        finished(*sync, "solo sync")
        finished(*streamed, "solo stream")
        check(sync[2] == streamed[2],
              f"sync and streamed tokens differ: {sync[2]} / {streamed[2]}")

        # the concurrent wave: every prompt at once, sync and stream mixed
        results = [None] * len(prompts)

        def client(i):
            results[i] = _generate(frontend.port, prompts[i], new,
                                   stream=bool(i % 2))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        check(not any(t.is_alive() for t in threads)
              and all(r is not None for r in results),
              "a client of the concurrent wave did not come back")
        for i, r in enumerate(results):
            finished(*r, f"concurrent request {i}")
        # near-ties under random weights may flip with the batch's shape, so
        # this one is a fact, not a check
        facts["wave_matches_solo"] = results[0][2] == sync[2]

        # the server survives a failing engine step by design (evict, retry,
        # quarantine, latch 503): a refused kernel must not pass as a few
        # failed requests
        health = server.health()
        facts["step_programs_compiled"] = compiles_total() - compiled_before
        facts["step_faults"] = health["step_faults"]
        check(health["step_faults"] == 0 and health["status"] == "serving"
              and server.metrics.degraded_latches == 0,
              f"server faulted or latched degraded: {health}")
    finally:
        server.stop(drain_timeout=60.0)
        frontend.stop()
    ledger = server.engine.kv_ledger()
    check(server.health()["status"] == "stopped"
          and ledger["device_blocks_reserved"] == 0,
          f"drain left state behind: {server.health()}, {ledger}")
    return facts


# --- main -------------------------------------------------------------------

def result_line(ok: bool, device: dict) -> str:
    """The last line of stdout, which the driver parses: exactly ``ok`` and
    ``device`` = {platform, kind, count} as jax reports them. Every other
    fact of the run is on the ``chip_smoke: summary`` line before it."""
    return json.dumps({"ok": ok, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def main() -> int:
    import jax

    from deepspeed_tpu.accelerator import require_tpu
    devices = require_tpu("chip_smoke")        # one line and exit 1 without
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    versions = {"jax": jax.__version__,
                "jaxlib": importlib.metadata.version("jaxlib"),
                "libtpu": importlib.metadata.version("libtpu")}
    print(f"chip_smoke: {device} {versions}", flush=True)

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    size = full_size()
    summary = {"device": device, "versions": versions,
               "compile_cache_dir": cache_dir, "legs": {}, "seconds": {},
               "compile_seconds": {}}
    legs = (("kernels", lambda: leg_kernels(size)),
            ("trainer", lambda: leg_trainer(size, devices)),
            ("server", lambda: leg_server(size)))
    try:
        for name, leg in legs:
            t0, c0 = time.monotonic(), clock.seconds
            summary["legs"][name] = leg()       # a failed check raises
            summary["seconds"][name] = round(time.monotonic() - t0, 1)
            summary["compile_seconds"][name] = round(clock.seconds - c0, 1)
            print(f"chip_smoke: {name} passed in {summary['seconds'][name]} "
                  f"s ({summary['compile_seconds'][name]} s compiling): "
                  f"{json.dumps(summary['legs'][name])}", flush=True)
            # the next leg needs the chip's memory back
            gc.collect()
            jax.clear_caches()
    except BaseException:
        # a leg failed on a chip: say so in the result line, then fail loudly
        print(result_line(False, device), flush=True)
        raise
    summary["compile_cache"] = {"hits": clock.hits, "misses": clock.misses}
    summary["peak_hbm_bytes"] = [m.get("peak_bytes_in_use")
                                 for m in memory_per_device(devices)]
    summary["claim"] = None
    print(f"chip_smoke: summary {json.dumps(summary)}", flush=True)
    print(result_line(True, device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
