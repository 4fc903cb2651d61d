"""The two metrics of a step's write into the paged pool
(``metrics/kv_write_share.py``, ``metrics/kv_write_paged_share.py``): the
first on a toy latent MoE server's ticks recorded on a v5e
(``data/scoped-serve.xplane.pb.gz``: that trace's ``attn/latent_write`` and
``attn/kv_write`` seconds over busy time), the second on hand-built spans."""

import gzip
import pathlib
import types

import pytest

from benchmarks.harness import program_events as pe
from benchmarks.harness import scope_readers
from benchmarks.harness import trace as tr
from benchmarks.harness import xplane_names as xn
from benchmarks.harness.cells import load_module
from benchmarks.harness.observations import Observations

REPO = pathlib.Path(__file__).resolve().parents[2]
LOOP = 7
SCOPES = ("attn/kv_write", "attn/latent_write")
SERVED = ("mixtral8x7b-serve-batch-rag", "joyai-flash-serve-docqa",
          "laguna-s-serve-code-mixed", "xing4-serve-reasoning",
          "mimo-v2.5-serve-agent-long", "granite4h-micro-serve-chat-many",
          "nemotron3-nano-serve-reasoning-many",
          "lfm2-24b-serve-toolcalls-many")


def metric(bench, name):
    return load_module(REPO, bench, "metrics", name)


def observed(monkeypatch, named, ring=()):
    """A traced run whose window is all of ``named``'s operations."""
    ops = [tr.Op(o.name, o.start, o.dur, o.device) for o in named.ops]
    window = (min(o.start for o in ops) - 1e-3, max(o.end for o in ops) + 1e-3)
    sync = min(s.start for s in named.host if s.name == "bench/clock_sync")
    obs = Observations(
        kind="serve", cell=types.SimpleNamespace(name="c", root=REPO),
        devices=[], window=(window[0] - 1.0, window[1] + 1.0),
        trace=tr.Trace(ops, [tr.Span("clock_sync", sync, 1e-6)],
                       list(named.devices), window=window))
    monkeypatch.setattr(xn, "of_run", lambda o: o.trace and named)
    monkeypatch.setattr(pe, "events", lambda: list(ring))
    return obs


def test_kv_write_share_on_the_recorded_trace(bench, monkeypatch, tmp_path):
    path = tmp_path / "scoped-serve.xplane.pb"
    path.write_bytes(gzip.decompress(
        (REPO / "tests" / "benchmarks" / "data" /
         "scoped-serve.xplane.pb.gz").read_bytes()))
    named = xn.read(str(path))
    obs = observed(monkeypatch, named)
    by_scope = scope_readers.seconds_by_scope(named.ops, SCOPES)
    # a latent pool: its rows are written under ``attn/latent_write``
    assert by_scope.get("attn/latent_write", 0.0) > 0.0
    own = sum(o.dur for o in named.ops
              if scope_readers.under_any(o.scope, SCOPES))
    assert own == pytest.approx(sum(by_scope.values()), rel=1e-9)
    busy = tr.busy_seconds(obs.trace, obs.trace.window)
    got = metric(bench, "kv_write_share").read(obs)
    assert got == pytest.approx(100.0 * own / len(named.devices) / busy)
    assert 0.0 < got < 100.0
    assert metric(bench, "kv_write_share").SCOPES == SCOPES
    # an untraced run, and a program that opens neither scope
    obs.trace = None
    assert metric(bench, "kv_write_share").read(obs) is None
    bare = xn.Names([o for o in named.ops
                     if not scope_readers.under_any(o.scope, SCOPES)],
                    named.host, named.devices)
    assert metric(bench, "kv_write_share").read(
        observed(monkeypatch, bare)) is None


def chunk(t0, tokens, by_page=None, fused=0):
    args = {"tokens": tokens, "bucket": 4096, "start": 0}
    if by_page is not None:
        args.update(kv_rows_written=tokens + fused, kv_rows_by_page=by_page)
    return pe.Event(pe.PREFILL_CHUNK, t0, 0.004, LOOP, args)


def decode(t0, batch, counted=True):
    args = {"batch": batch, "bucket": 8}
    if counted:
        args.update(kv_rows_written=batch, kv_rows_by_page=0)
    return pe.Event(pe.STEP_DECODE, t0, 0.004, LOOP, args)


def test_kv_write_paged_share_sums_before_it_divides(bench):
    read = metric(bench, "kv_write_paged_share").read

    def obs(ring, monkeypatch, on_a_device=True):
        monkeypatch.setattr(pe, "events", lambda: list(ring))
        return Observations(
            kind="serve", cell=types.SimpleNamespace(name="c", root=REPO),
            devices=[], window=(50.0, 52.0),
            trace=tr.Trace([], [], [0] if on_a_device else [],
                           window=(50.5, 51.5)))
    with pytest.MonkeyPatch.context() as mp:
        ring = [chunk(50.1, 3867, by_page=3867, fused=15),
                chunk(50.2, 40, by_page=0, fused=3),    # a bucket under a block
                decode(50.3, 32),
                decode(50.4, 7, counted=False),          # carries neither
                chunk(52.5, 4096, by_page=4096),         # after the window
                pe.Event("serve/tick", 50.0, 1.0, LOOP, {"tick": 1})]
        got = read(obs(ring, mp))
        assert got == pytest.approx(
            100.0 * 3867 / (3867 + 15 + 40 + 3 + 32))
        # not the mean of the spans' own shares
        assert got != pytest.approx(100.0 * (3867 / 3882 + 0 + 0) / 3)
        # decode spans alone: every row one by one
        assert read(obs([decode(50.3, 32)], mp)) == 0.0
        # a program older than PR 55 stamps neither count
        assert read(obs([chunk(50.1, 3867), decode(50.3, 4, counted=False)],
                        mp)) is None
        assert read(obs([], mp)) is None
        # as the other shares of the program's spans: nothing on a run with
        # no device in its trace
        assert read(obs([decode(50.3, 32)], mp, on_a_device=False)) is None


@pytest.mark.parametrize("name,source,better", [
    ("kv_write_paged_share", "program_counter", "higher"),
    ("kv_write_share", "device_trace", "lower")])
def test_the_two_entries_are_listed_in_the_served_cells(bench, name, source,
                                                        better):
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    served = next(m for m in bench["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": name, "unit": "%", "better": better, "source": source,
        "layer": "model step", "moves": "serve_tokens_per_s"}
    assert set(SERVED) <= set(entry["workloads"]) <= set(served["workloads"])
