"""Pool bytes that live sequences hold over the tokens whose keys and values
they hold, a tick at a time, mean over the window's ticks: ``kv_held_bytes``
over ``kv_live_tokens`` of the engine's per-tick counters (both kinds of page
where the cache keeps pages by layer kind). At five layers of 8 x 128 in
bfloat16 a pool that holds every layer's pages for the whole context costs
20,480 bytes a token, the two full layers' alone 8,192
(``harness/costs_mixed_pages.py``); the windowed layers' constant a sequence
and the blocks' rounding lie between. None where the program counts
neither."""

from benchmarks.harness import readers


def read(obs):
    per_tick = [t[2]["kv_held_bytes"] / t[2]["kv_live_tokens"]
                for t in readers.steady_ticks(obs)
                if t[2].get("kv_live_tokens") and "kv_held_bytes" in t[2]]
    return sum(per_tick) / len(per_tick) if per_tick else None
