"""The tracer's bridge to the profiler: while tracing is on, every live span
also opens a ``jax.profiler.TraceAnnotation`` of its name (the two step-level
spans a ``StepTraceAnnotation`` with their number); off, ``span()`` is the
shared no-op and never reaches for jax. ``utils/nvtx.py`` rides on it."""

import glob
import os
import pathlib

import pytest

from deepspeed_tpu.telemetry import tracer as tracer_mod
from deepspeed_tpu.telemetry.tracer import Tracer

REPO = pathlib.Path(__file__).resolve().parents[1]


class _Fake:
    """Stands in for the profiler's annotation classes; records its use."""
    log = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs = name, kwargs

    def __enter__(self):
        _Fake.log.append(("enter", type(self).__name__, self.name,
                          self.kwargs))
        return self

    def __exit__(self, *exc):
        _Fake.log.append(("exit", type(self).__name__, self.name,
                          self.kwargs))
        return False


class _FakeStep(_Fake):
    pass


def _ring(tracer):
    """The ring less the interpreter's collections (``host/gc``), which an
    enabled tracer stamps whenever one happens to run."""
    return [e for e in tracer.events_snapshot()
            if e[6] != tracer_mod.HOST_GC_TID]


@pytest.fixture
def fakes(monkeypatch):
    _Fake.log = []
    monkeypatch.setattr(tracer_mod, "_annotations", (_Fake, _FakeStep))
    return _Fake.log


def test_enabled_span_enters_and_leaves_a_trace_annotation(fakes):
    t = Tracer().configure(enabled=True)
    span = t.span("serve/plan", cat="serve", tick=4)
    assert fakes == []        # made inside the span, after its start stamp
    with span:
        assert fakes == [("enter", "_Fake", "serve/plan", {})]
    assert [e[0] for e in fakes] == ["enter", "exit"]
    (ev,) = _ring(t)
    assert ev[1] == "serve/plan" and ev[7] == {"tick": 4}


@pytest.mark.parametrize("name,key", sorted(tracer_mod.STEP_SPANS.items()))
def test_step_spans_carry_their_number_into_the_profiler(fakes, name, key):
    t = Tracer().configure(enabled=True)
    with t.span(name, **{key: 17}):
        pass
    assert fakes[0] == ("enter", "_FakeStep", name, {"step_num": 17})
    # without its number a step span is an ordinary annotation
    with t.span(name):
        pass
    assert fakes[2][:3] == ("enter", "_Fake", name)


def test_disabled_span_is_the_shared_noop_and_never_reaches_for_jax(
        monkeypatch):
    def boom(name, args):
        raise AssertionError("a disabled tracer asked for the profiler")
    monkeypatch.setattr(tracer_mod, "_profiler_annotation", boom)
    t = Tracer()
    assert not t.enabled
    span = t.span("serve/plan", tick=1)
    assert span is tracer_mod._NOOP_SPAN
    with span as s:
        s.note(tables_rebuilt=True)       # accepted and dropped
    assert t.events_snapshot() == []


def test_note_adds_args_known_only_inside_the_span(fakes):
    t = Tracer().configure(enabled=True)
    with t.span("serve/decode_build", tick=2) as sp:
        sp.note(tables_rebuilt=False)
    with t.span("serve/plan") as sp:
        sp.note(n=1)
    a, b = _ring(t)
    assert a[7] == {"tick": 2, "tables_rebuilt": False} and b[7] == {"n": 1}


def test_retro_events_stay_on_the_ring_alone(fakes):
    t = Tracer().configure(enabled=True)
    t.complete("serve/tick", 0.01, tick=1)
    t.instant("serve/ladder")
    assert fakes == [] and len(_ring(t)) == 2


def test_a_profile_taken_while_tracing_holds_the_programs_spans(tmp_path):
    """What an operator gets from ``engine.start_profile_trace()`` with
    tracing on: the program's spans in the profiler's host plane, the step
    span with its number."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    t = Tracer().configure(enabled=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for tick in (1, 2):
            with t.span("serve/engine_step", cat="serve", tick=tick):
                with t.span("serve/decode_dispatch", cat="serve", tick=tick):
                    jnp.ones((8,)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve/"):
                    found.setdefault(e.name, []).append(dict(e.stats))
    assert len(found["serve/decode_dispatch"]) == 2
    assert sorted(s["step_num"] for s in found["serve/engine_step"]) == [1, 2]


def test_nvtx_is_a_few_lines_over_the_tracer():
    text = (REPO / "deepspeed_tpu" / "utils" / "nvtx.py").read_text()
    code = text.split('"""', 2)[2]
    assert "TraceAnnotation" not in code
    from deepspeed_tpu.utils import nvtx
    t = tracer_mod.get_tracer()
    was = t.enabled
    try:
        t.configure(enabled=False)
        assert nvtx.annotate("quiet") is tracer_mod._NOOP_SPAN
        t.configure(enabled=True)
        ctx = nvtx.range_push("pushed")
        nvtx.range_pop(ctx)
        assert "pushed" in {e[1] for e in t.events_snapshot()}
    finally:
        t.configure(enabled=was)
