"""The tick's phases as the program names them: nested spans inside
``engine.step`` that keep ``serve/step_prefill`` and ``serve/step_decode`` as
they were, counts on the same spans, one ``tick`` on every span of a tick, and
a stage ledger that still sums as before."""

import dataclasses

import jax
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  V2EngineConfig)
from deepspeed_tpu.models.llama import (TINY_LLAMA, LlamaForCausalLM,
                                        random_tokens)
from deepspeed_tpu.telemetry import names
from deepspeed_tpu.telemetry.tracer import get_tracer

PHASES = ("serve/decode_build", "serve/decode_dispatch", "serve/decode_wait",
          "serve/decode_commit")
WINDOW = 8


@pytest.fixture(scope="module")
def params():
    cfg = dataclasses.replace(TINY_LLAMA, sliding_window=WINDOW)
    model = LlamaForCausalLM(cfg)
    tokens = random_tokens(1, 8, vocab_size=cfg.vocab_size)
    return cfg, model.init(jax.random.PRNGKey(0), tokens)["params"]


@pytest.fixture
def tracing():
    t = get_tracer()
    was = t.enabled
    t.configure(enabled=True)
    t.clear()
    yield t
    t.configure(enabled=was)
    t.clear()


def _spans(tracer):
    """The ring's spans but the interpreter's collections (``host/gc``, a
    track of their own), which land wherever one happens to run."""
    return [e for e in tracer.events_snapshot()
            if e[3] == "X" and e[1] != "host/gc"]


def _inside(inner, outer, slack=1e-6):
    return outer[4] - slack <= inner[4] and \
        inner[4] + inner[5] <= outer[4] + outer[5] + slack


def test_one_tick_emits_the_phases_nested_in_step_decode(params, tracing):
    cfg, p = params
    eng = InferenceEngineV2(p, cfg, V2EngineConfig(kv_num_blocks=32))
    prompts = [[1] * 5, [2] * 12, [3] * 20]
    eng.put([1, 2, 3], prompts)
    planned = [s.total_tokens for s in eng.state.decoding()]
    tracing.clear()
    eng.tick = 41
    eng.step()
    spans = _spans(tracing)
    by_name = {e[1]: e for e in spans}
    assert set(by_name) == {"serve/plan", "serve/step_decode",
                            "serve/step_finish", *PHASES}
    decode = by_name["serve/step_decode"]
    for name in PHASES:
        assert _inside(by_name[name], decode), name
    starts = [by_name[n][4] for n in PHASES]
    assert starts == sorted(starts)
    assert by_name["serve/plan"][4] + by_name["serve/plan"][5] <= decode[4]
    args = decode[7]
    assert args["batch"] == 3 and args["batch"] <= args["bucket"]
    assert args["bucket"] in eng.config.decode_batch_buckets
    assert args["ctx_tokens"] == sum(planned) == sum(len(x) + 1
                                                     for x in prompts)
    assert args["ctx_tokens_windowed"] == sum(min(c, WINDOW)
                                              for c in planned)
    assert args["ctx_blocks"] in eng.config.ctx_block_buckets
    assert by_name["serve/decode_build"][7]["tables_rebuilt"] is True
    assert all(e[7]["tick"] == 41 for e in spans)
    assert eng.tick == 42
    # the same pages and shapes again: the device tables are kept
    tracing.clear()
    eng.step()
    again = {e[1]: e for e in _spans(tracing)}
    assert again["serve/decode_build"][7] == {"tick": 42,
                                              "tables_rebuilt": False}
    # the phases tile the step: what no phase holds is a small share of it.
    # Held as a share (a host that six test workers share stretches step and
    # gap alike) and over a few steps' smallest (a worker preempted between
    # two spans adds its wait to one step's gap, never takes from it)
    def untiled_share(by_name):
        held = sum(by_name[n][5]
                   for n in ("serve/plan", "serve/step_finish", *PHASES))
        finish = by_name["serve/step_finish"]
        whole = finish[4] + finish[5] - by_name["serve/plan"][4]
        return (whole - held) / whole
    shares = [untiled_share(again)]
    for _ in range(4):
        tracing.clear()
        eng.step()
        shares.append(untiled_share({e[1]: e for e in _spans(tracing)}))
    assert min(shares) < 0.15, shares


def test_every_prefill_chunk_has_its_span_with_counts_and_the_wait(
        params, tracing):
    """No chunk cap set: the per-chunk span is there all the same, and the
    blocking read after a prompt's last chunk is a ``serve/decode_wait``."""
    cfg, p = params
    eng = InferenceEngineV2(p, cfg, V2EngineConfig(kv_num_blocks=32))
    assert eng.config.scheduler.prefill_chunk_tokens == 0
    eng.admit(7, [5] * 11)
    eng.step()
    spans = _spans(tracing)
    (chunk,) = [e for e in spans if e[1] == "serve/prefill_chunk"]
    (prefill,) = [e for e in spans if e[1] == "serve/step_prefill"]
    (wait,) = [e for e in spans if e[1] == "serve/decode_wait"]
    assert _inside(wait, chunk) and _inside(chunk, prefill)
    assert chunk[7]["tokens"] == 11 and chunk[7]["start"] == 0
    assert chunk[7]["bucket"] >= 11 and chunk[7]["uid"] == 7
    assert {e[7]["tick"] for e in spans} == {0}


def test_phase_names_are_registered_with_their_parents_stage():
    for name in PHASES:
        assert names.TRACE_NAMES[name] == ("span",)
        assert names.SERVE_STAGE_OF[name] == \
            names.SERVE_STAGE_OF["serve/step_decode"]
    assert names.SERVE_STAGE_OF["serve/prefill_chunk"] == \
        names.SERVE_STAGE_OF["serve/step_prefill"]
    for part in ("serve/drain_expire", "serve/drain_fanout",
                 "serve/drain_reap"):
        assert names.SERVE_STAGE_OF[part] == names.SERVE_STAGE_OF["serve/drain"]
    # no stage: engine_step's and the tick's own time stays residual
    assert "serve/plan" not in names.SERVE_STAGE_OF
    assert "serve/bookkeep" not in names.SERVE_STAGE_OF
    assert names.TRACE_NAMES["serve/plan"] == ("span",)
    assert names.TRACE_NAMES["serve/bookkeep"] == ("complete",)
    assert names.TRACE_NAMES["serve/step_finish"] == ("span",)
    assert "serve/step_finish" not in names.SERVE_STAGE_OF


def test_a_served_tick_shares_one_number_and_the_ledger_sums_as_before(
        tracing):
    from deepspeed_tpu.serving.bench_serve import build_tiny_server
    from deepspeed_tpu.telemetry import serve_attribution as sa
    server = build_tiny_server().start()
    try:
        reqs = [server.submit([1, 2, 3, 4 + i], max_new_tokens=6)
                for i in range(3)]
        for r in reqs:
            r.result(timeout=120)
    finally:
        server.stop(drain_timeout=10.0)
    events = tracing.events_snapshot()
    spans = [e for e in events if e[3] == "X"]
    ticks = {e[7]["tick"]: e for e in spans if e[1] == "serve/tick"}
    assert ticks
    loop = {e[6] for e in spans if e[1] == "serve/tick"}
    emitted = {e[1] for e in spans if e[6] in loop}
    assert {"serve/plan", "serve/bookkeep", "serve/step_finish",
            "serve/drain_expire",
            "serve/drain_fanout", "serve/drain_reap", "serve/engine_step",
            *PHASES} <= emitted
    assert "serve/drain" not in emitted
    # every span of the serve loop carries the number of the tick it lies
    # in; the loop's no-work wait (``serve/idle``) lies between ticks
    idles = [e for e in spans if e[1] == "serve/idle"]
    assert idles and all(e[6] in loop for e in idles)
    assert not any(_inside(i, t, slack=-1e-6) for i in idles
                   for t in ticks.values())
    for e in spans:
        if e[6] not in loop or e[1] in ("serve/tick", "serve/idle"):
            continue
        assert e[7] and "tick" in e[7], e[1]
        assert _inside(e, ticks[e[7]["tick"]]), (e[1], e[7]["tick"])
    # the stage ledger: every window ties out, and the decode stage is what
    # the step_decode spans alone give (the phases inside add nothing)
    chrome = tracing.to_chrome(events)
    report = sa.attribute_serve(sa.events_from_chrome(chrome))
    assert report["ticks_total"] == len(ticks)
    assert all(w["tie_out_error"] <= sa.TIE_OUT_TOLERANCE
               for w in report["windows"])
    decode_ms = sum(e[5] for e in spans if e[1] == "serve/step_decode") * 1e3
    assert report["aggregate"]["decode"]["total_ms"] == \
        pytest.approx(decode_ms, rel=1e-3, abs=1e-2)
    bookkeep_ms = sum(e[5] for e in spans if e[1] == "serve/bookkeep") * 1e3
    assert report["aggregate"]["residual"]["total_ms"] >= bookkeep_ms * 0.99


def _decode_args(tracing, eng):
    tracing.clear()
    eng.step()
    (decode,) = [e for e in _spans(tracing) if e[1] == "serve/step_decode"]
    return decode[7]


def test_step_decode_counts_the_keys_of_the_tiles_the_kernel_read(
        params, tracing):
    """``tile_keys`` beside ``ctx_tokens``: every context's live table
    entries rounded out to the key tiles of the paged kernel (several pages a
    step), so that the tiles' fill can be read from a trace; the windowed one
    counts from the page that holds the window's first key."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    cfg, p = params
    eng = InferenceEngineV2(p, cfg, V2EngineConfig(kv_num_blocks=32,
                                                   kv_block_size=4))
    eng.put([1, 2, 3], [[1] * 5, [2] * 12, [3] * 20])
    contexts = [s.total_tokens for s in eng.state.decoding()]
    args = _decode_args(tracing, eng)
    pages = pa._tile_pages(args["ctx_blocks"])
    tile = 4 * pages
    assert args["ctx_tokens"] == sum(contexts) == 6 + 13 + 21
    assert args["tile_keys"] == sum(-(-c // tile) * tile for c in contexts)
    assert args["tile_keys"] >= args["ctx_tokens"]
    # behind the window of 8 a context reads from the page that holds key
    # ``context - 8``, in whole tiles from there
    assert args["tile_keys_windowed"] == sum(
        -(-(-(-c // 4) - max(c - WINDOW, 0) // 4) // pages) * tile
        for c in contexts)
    assert args["ctx_tokens_windowed"] <= args["tile_keys_windowed"] \
        <= args["tile_keys"]


def test_step_decode_counts_the_page_copies_of_a_layers_call(params, tracing):
    """``slot_copies`` beside ``tile_keys``: the K and V page copies one
    layer's paged kernel call issued for the tick's decode batch: one of each
    a live table entry a step of the grid's head axis, a padding row's one
    entry too, and nothing for what lies past a context. One kind of page: a
    windowed layer walks the same table from the page its window starts in."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    cfg, p = params
    eng = InferenceEngineV2(p, cfg, V2EngineConfig(kv_num_blocks=32,
                                                   kv_block_size=4))
    eng.put([1, 2, 3], [[1] * 5, [2] * 12, [3] * 20])
    contexts = [s.total_tokens for s in eng.state.decoding()]
    args = _decode_args(tracing, eng)
    kv = eng.kv.cfg
    _, _, heads = pa._tile(
        cfg.num_heads // kv.num_kv_heads, args["ctx_blocks"], kv.block_size,
        kv.head_dim, jax.numpy.dtype(kv.dtype).itemsize, kv.num_kv_heads)
    assert heads == kv.num_kv_heads > 1
    padding = args["bucket"] - len(contexts)
    assert args["slot_copies"] == 2 * (
        sum(-(-c // 4) for c in contexts) + padding)
    assert args["slot_copies_windowed"] == 2 * (
        sum(-(-c // 4) - max(c - WINDOW, 0) // 4 for c in contexts) + padding)
    assert args["slot_copies"] * kv.block_size <= 2 * args["tile_keys"] \
        + 2 * padding * 4 * pa._tile_pages(args["ctx_blocks"])


@pytest.mark.parametrize("case,kv_heads,windows,contexts,bucket,blocks,want", [
    # code-mixed's decode: contexts of 16,400 and 300 in a bucket of four over
    # 260 blocks of the full kind, all eight heads a copy: 257 + 5 entries and
    # a padding row's one each; the windowed kind's own table holds the 9
    # blocks (8 where the window starts on a page's edge) the window falls in
    ("two kinds", 8, (None, 512), [16400, 300], 4, 260,
     (2 * (257 + 5 + 2), 2 * (9 + 5 + 2))),
    # batch-rag's and chat's: one kind, every layer walks the full table
    ("one kind", 8, (), [3000, 64, 65], 4, 64, (2 * (47 + 1 + 2 + 1),) * 2),
    # one KV head a step is a copy a head a page, as every fold was before
    # PR 40
    ("one head", 1, (), [16384, 300], 4, 260, (2 * (256 + 5 + 2),) * 2),
    ("a table shorter than a tile", 2, (), [130], 4, 3, (2 * (3 + 3),) * 2),
])
def test_decode_slot_copies_by_hand(case, kv_heads, windows, contexts, bucket,
                                    blocks, want):
    from deepspeed_tpu.inference.v2.kv_cache import (BlockedKVCache,
                                                     KVCacheConfig,
                                                     one_window)
    kv = BlockedKVCache(KVCacheConfig(
        num_layers=2, num_kv_heads=kv_heads, head_dim=128, block_size=64,
        num_blocks=4, layer_windows=windows,
        window_blocks=4 if windows else 0))
    got = kv.decode_slot_copies(contexts, bucket, blocks, one_window(windows))
    assert (got["slot_copies"], got["slot_copies_windowed"]) == want


@pytest.mark.parametrize("case,config,chunk,want", [
    # MiMo's pages: a chunk of 4,096 at 8,192 over 192 blocks. A full layer
    # (4 KV heads of 16 query heads, keys stored in 256 lanes): two row
    # blocks of 2,048 a head over 10 and 12 tiles of 1,024 keys, 160 + 192
    # table entries a q head. A windowed layer (8 KV heads, window 128, its
    # own table from block 126 on: the chunk starts at position 128 of it):
    # sixteen row blocks of 256 a head, each over ONE tile of 512 keys
    ("two kinds", dict(
        num_kv_heads=4, head_dim=256, layer_windows=(None, 128),
        window_blocks=4, query_heads={"full": 64, "window": 64},
        kind_pages={"full": (4, 192, 128), "window": (8, 192, 128)}),
     (8192, 4096, 192, 128),
     (2048 * (10240 + 12288), 4096 * 512, 2 * (160 + 192) * 16 * 4)),
    # chat's: one kind behind a window of 4,096, a chunk of 512 at 1,024 of
    # four query heads a KV head is one row block that spans them, over the
    # 24 live entries of its table in two tiles of 16 pages
    ("one kind", dict(num_kv_heads=8, head_dim=128, query_heads=32),
     (1024, 512, 32, 4096), (512 * 2048, 512 * 2048, 2 * 24 * 8)),
])
def test_chunk_tile_keys_by_hand(case, config, chunk, want):
    """What rides on ``serve/prefill_chunk`` as ``tile_keys``,
    ``tile_keys_windowed`` and ``tile_copies``."""
    from deepspeed_tpu.inference.v2.kv_cache import (BlockedKVCache,
                                                     HeadPageShape,
                                                     KVCacheConfig)
    kinds = config.pop("kind_pages", None)
    kv = BlockedKVCache(KVCacheConfig(
        num_layers=2, block_size=64, num_blocks=4,
        kind_pages=kinds and {k: HeadPageShape(*v) for k, v in kinds.items()},
        **config))
    got = kv.chunk_tile_keys(*chunk)
    assert (got["tile_keys"], got["tile_keys_windowed"],
            got["tile_copies"]) == want
    from benchmarks.harness.costs_latent import chunk_pairs
    assert chunk_pairs(chunk[0], chunk[1]) <= got["tile_keys"]


def test_decode_slot_copies_over_a_latent_pool_are_the_latent_kernels():
    from deepspeed_tpu.inference.v2.kv_cache import (BlockedKVCache,
                                                     KVCacheConfig)
    latent = BlockedKVCache(KVCacheConfig(num_layers=1, num_kv_heads=1,
                                          head_dim=64, latent_dim=48,
                                          num_blocks=4))
    # the latent decode kernel's copies instead: a page has no heads and one
    # row is key and value, so one copy a live page (a context of 100 holds
    # 2 blocks of 64; past the table's 4 it reads the 4) and one a padding
    # row (7 of 8)
    assert latent.decode_slot_copies([100], 8, 4, None) == {
        "latent_page_copies": 2 + 7}
    assert latent.decode_slot_copies([65, 1, 999], 3, 4, None) == {
        "latent_page_copies": 2 + 1 + 4}
    # a chunk's counts over a latent pool are the latent prefill kernel's
    # panels (one here: 64 rows over 4 blocks of 16), not the paged kernel's
    # and beside them the rows its call gathers: the table's 4 blocks, whole
    assert latent.chunk_tile_keys(0, 64, 4, None) == {
        "latent_panels": 1, "latent_panels_masked": 1,
        "latent_panels_dead": 0, "latent_keys_gathered": 4 * 64}


@pytest.mark.parametrize("case,contexts,window,want", [
    # blocks of 16 over a 96-block table: a tile is 8 pages = 128 keys
    ("whole tiles", [128, 256, 1280], None, (1664, 1664)),
    ("ends inside a tile", [1, 129, 300], None, (128 + 256 + 384,) * 2),
    # window 100: context 300 reads keys 200-299, pages 12-18, one tile from
    # page 12 on (tiles start at the first live page, not at a multiple of
    # 8); context 129 keys 29-128, pages 1-8; context 520 keys 420-519, pages
    # 26-32; context 100 keys 0-99, its one tile; a window of 100 keys that
    # starts on a page's last key takes 8 pages, still one tile
    ("windowed", [300, 129, 520, 100], 100,
     (384 + 256 + 640 + 128, 128 + 128 + 128 + 128)),
])
def test_decode_tile_keys_by_hand(case, contexts, window, want):
    from deepspeed_tpu.inference.v2.kv_cache import (BlockedKVCache,
                                                     KVCacheConfig)
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    assert pa._tile_pages(96) == 8
    kv = BlockedKVCache(KVCacheConfig(num_layers=1, num_kv_heads=2,
                                      head_dim=32, block_size=16,
                                      num_blocks=8, dtype=jax.numpy.float32))
    got = kv.decode_tile_keys(contexts, 96, window)
    assert (got["tile_keys"], got["tile_keys_windowed"]) == want
    assert (got["tile_keys"] == sum(contexts)) == (case == "whole tiles")


def test_decode_tile_keys_of_a_windowed_kind_count_from_its_table():
    """Pages by layer kind: a windowed layer's table starts behind the window
    and is nine blocks long at window 512 over blocks of 64, so context 5,000
    reads keys 4,488-4,999 as positions 8-519 of a table that starts at
    block 70: nine pages, two tiles of 8, 1,024 keys for the window's 512; a
    context of 300 is one tile of 512 keys for either kind of layer."""
    from deepspeed_tpu.inference.v2.kv_cache import (BlockedKVCache,
                                                     KVCacheConfig)
    kv = BlockedKVCache(KVCacheConfig(
        num_layers=2, num_kv_heads=1, head_dim=128, block_size=64,
        num_blocks=4, layer_windows=(None, 512), window_blocks=4))
    got = kv.decode_tile_keys([5000, 300], 128, 512)
    assert got["tile_keys_windowed"] == 1024 + 512
    assert got["tile_keys"] == 5120 + 512
    # over a latent pool, which the paged kernel does not read, the latent
    # decode kernel's tiles: the table's 4 blocks of 64 are one tile
    latent = BlockedKVCache(KVCacheConfig(num_layers=1, num_kv_heads=1,
                                          head_dim=64, latent_dim=48,
                                          num_blocks=4))
    assert latent.decode_tile_keys([100], 4, None) == {"latent_tile_keys": 256}
