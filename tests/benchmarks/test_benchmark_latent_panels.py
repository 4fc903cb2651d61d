"""``latent_prefill_masked_panel_share`` on hand-built events: the panels of
the latent prefill kernel that the causal horizon crosses
(``latent_panels_masked`` on a ``serve/prefill_chunk`` span over a latent
pool) over its panels that compute (``latent_panels``); and where
``BENCHMARK.json`` lists it."""

import pathlib

import pytest

from benchmarks.harness import program_events as pe
from benchmarks.harness import trace as tr
from benchmarks.harness.cells import load_module
from benchmarks.harness.observations import Observations

REPO = pathlib.Path(__file__).resolve().parents[2]
NAME = "latent_prefill_masked_panel_share"
LOOP = 7


def chunk(t0, panels=None, masked=None, dead=0):
    args = {"tokens": 8, "bucket": 8, "start": 0}
    if panels is not None:
        args.update(latent_panels=panels, latent_panels_masked=masked,
                    latent_panels_dead=dead)
    return pe.Event(pe.PREFILL_CHUNK, t0, 0.004, LOOP, args)


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


def test_masked_panels_over_the_panels_that_compute(read):
    ring = [chunk(50.10, panels=36, masked=8),
            chunk(50.20, panels=100, masked=8, dead=36),
            chunk(50.30),                        # a span without the counts
            chunk(50.45, panels=5, masked=5),    # the profiler's start
            pe.Event(pe.STEP_DECODE, 51.0, 0.004, LOOP, {"batch": 4}),
            chunk(51.10, panels=14, masked=4, dead=2),
            chunk(52.5, panels=9, masked=9)]     # after the window
    assert read(ring) == pytest.approx(100.0 * 20 / 150)
    # every panel on the diagonal; none
    assert read([chunk(50.1, panels=3, masked=3)]) == 100.0
    assert read([chunk(50.1, panels=7, masked=0, dead=1)]) == 0.0


def test_nothing_to_read_is_none_and_never_raises(read):
    # a program that stamps no counts (the parent of PR 46, or a pool that
    # is not latent), no spans, and a run with no device plane
    assert read([chunk(50.1)]) is None
    assert read([pe.Event(pe.STEP_DECODE, 50.2, 0.004, LOOP,
                          {"batch": 4})]) is None
    assert read([]) is None
    assert read([chunk(50.1, panels=36, masked=8)],
                on_a_device=False) is None


def test_the_entry_and_the_cells_that_report_it(bench):
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert (entry["source"], entry["layer"], entry["moves"], entry["unit"],
            entry["better"]) == ("program_counter", "kernels",
                                 "serve_tokens_per_s", "%", "lower")
    (moved,) = [m for m in bench["end_to_end"]
                if m["name"] == "serve_tokens_per_s"]
    for cell in ("joyai-flash-serve-docqa", "xing4-serve-reasoning"):
        assert cell in entry["workloads"] and cell in moved["workloads"]
    # beside the kernel's roofline, which the same cells report
    (roofline,) = [m for m in bench["per_layer"]
                   if m["name"] == "latent_prefill_roofline"]
    assert set(entry["workloads"]) <= set(roofline["workloads"])
