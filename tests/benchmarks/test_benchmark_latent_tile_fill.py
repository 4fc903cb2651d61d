"""``latent_decode_tile_fill`` on hand-built events: the tokens a decode
batch's rows see (``ctx_tokens`` on a ``serve/step_decode`` span) over the keys
of the latent decode kernel's tiles (``latent_tile_keys`` beside it, over a
latent pool); and where ``BENCHMARK.json`` lists it."""

import pathlib

import pytest

from benchmarks.harness import program_events as pe
from benchmarks.harness import trace as tr
from benchmarks.harness.cells import load_module
from benchmarks.harness.observations import Observations

REPO = pathlib.Path(__file__).resolve().parents[2]
NAME = "latent_decode_tile_fill"
LOOP = 7


def decode(t0, ctx=None, keys=None):
    args = {"batch": 4, "bucket": 8}
    if ctx is not None:
        args["ctx_tokens"] = ctx
    if keys is not None:
        args.update(latent_tile_keys=keys, latent_page_copies=keys // 64)
    return pe.Event(pe.STEP_DECODE, t0, 0.004, LOOP, args)


@pytest.fixture
def read(bench, monkeypatch):
    reader = load_module(REPO, bench, "metrics", NAME)

    def run(ring, on_a_device=True):
        obs = Observations(kind="serve", cell=None, devices=[])
        obs.window = (50.0, 52.0)
        obs.host_exclude = [(50.4, 50.5)]
        obs.trace = tr.Trace([], [], [0] if on_a_device else [],
                             window=(50.5, 51.5))
        monkeypatch.setattr(pe, "events", lambda: ring)
        return reader.read(obs)
    return run


def test_tokens_seen_over_the_keys_of_the_tiles(read):
    ring = [decode(50.10, ctx=1500, keys=2048),
            decode(50.20, ctx=5000, keys=5120),
            decode(50.30, ctx=700),              # a span without the count
            decode(50.45, ctx=9, keys=1024),     # the profiler's start
            pe.Event(pe.PREFILL_CHUNK, 51.0, 0.004, LOOP,
                     {"tokens": 8, "latent_panels": 3}),
            decode(51.10, ctx=1024, keys=1024),
            decode(52.5, ctx=1, keys=1024)]      # after the window
    assert read(ring) == pytest.approx(100.0 * 7524 / 8192)
    # every tile full; one token in a tile of 1,024
    assert read([decode(50.1, ctx=4096, keys=4096)]) == 100.0
    assert read([decode(50.1, ctx=1, keys=1024)]) == pytest.approx(100 / 1024)


def test_nothing_to_read_is_none_and_never_raises(read):
    # a program that stamps no count (the parent of PR 53, or a pool that is
    # not latent), no spans, and a run with no device plane
    assert read([decode(50.1, ctx=700)]) is None
    assert read([pe.Event(pe.PREFILL_CHUNK, 50.2, 0.004, LOOP,
                          {"tokens": 8})]) is None
    assert read([]) is None
    assert read([decode(50.1, ctx=1500, keys=2048)],
                on_a_device=False) is None


def test_the_entry_and_the_cells_that_report_it(bench):
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert (entry["source"], entry["layer"], entry["moves"], entry["unit"],
            entry["better"]) == ("program_counter", "kernels",
                                 "serve_tokens_per_s", "%", "higher")
    (moved,) = [m for m in bench["end_to_end"]
                if m["name"] == "serve_tokens_per_s"]
    for cell in ("joyai-flash-serve-docqa", "xing4-serve-reasoning"):
        assert cell in entry["workloads"] and cell in moved["workloads"]
    # beside the kernel's roofline, which the same cells report
    (roofline,) = [m for m in bench["per_layer"]
                   if m["name"] == "latent_paged_roofline"]
    assert set(entry["workloads"]) <= set(roofline["workloads"])
