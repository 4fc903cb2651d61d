"""``decode_rows_fused_share`` on hand-built events: the decode rows that rode
in a chunk's step program (``fused_rows`` on a tick's last
``serve/prefill_chunk`` span) over those and the rows of the decode batches
that had a program of their own (``batch`` on ``serve/step_decode``); and
where ``BENCHMARK.json`` lists it."""

import pathlib

import pytest

from benchmarks.harness import program_events as pe
from benchmarks.harness import trace as tr
from benchmarks.harness.cells import load_module
from benchmarks.harness.observations import Observations

REPO = pathlib.Path(__file__).resolve().parents[2]
NAME = "decode_rows_fused_share"
LOOP = 7


def chunk(t0, **args):
    return pe.Event(pe.PREFILL_CHUNK, t0, 0.004, LOOP,
                    dict({"tokens": 8, "bucket": 8, "start": 0}, **args))


def decode(t0, batch):
    return pe.Event(pe.STEP_DECODE, t0, 0.004, LOOP,
                    {"batch": batch, "bucket": 8})


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


def test_rows_in_a_chunks_program_over_all_decode_rows(read):
    ring = [chunk(50.10),                       # not its tick's last: no arg
            chunk(50.11, fused_rows=6),
            decode(50.20, batch=4),
            chunk(50.30, fused_rows=0),
            chunk(50.45, fused_rows=7),   # the profiler's start
            chunk(51.00, fused_rows=2),
            pe.Event(pe.STEP_DECODE, 51.2, 0.004, LOOP, {"ahead": 0}),
            decode(52.5, batch=8)]              # after the window
    assert read(ring) == pytest.approx(100.0 * 8 / 12)
    # every decode batch in a chunk's program; none
    assert read([chunk(50.1, fused_rows=3)]) == 100.0
    assert read([chunk(50.1, fused_rows=0), decode(50.2, batch=5)]) == 0.0


def test_nothing_to_read_is_none_and_never_raises(read):
    # a program that stamps no ``fused_rows`` (the parent of PR 45), a window
    # with no decode row at all, no spans, and a run with no device plane
    assert read([chunk(50.1), decode(50.2, batch=4)]) is None
    assert read([chunk(50.1, fused_rows=0)]) is None
    assert read([]) is None
    assert read([chunk(50.1, fused_rows=3)], on_a_device=False) is None


def test_the_entry_and_the_cells_that_report_it(bench):
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert (entry["source"], entry["layer"], entry["moves"], entry["unit"],
            entry["better"]) == ("program_counter", "engine tick",
                                 "serve_tokens_per_s", "%", "higher")
    (moved,) = [m for m in bench["end_to_end"]
                if m["name"] == "serve_tokens_per_s"]
    for cell in ("mixtral8x7b-serve-batch-rag", "joyai-flash-serve-docqa",
                 "laguna-s-serve-code-mixed"):
        assert cell in entry["workloads"] and cell in moved["workloads"]
