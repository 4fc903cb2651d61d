"""The program's own spans (``telemetry/tracer.py``, monotonic clock) as
``trace.Span``s: complete events only."""

from benchmarks.harness.trace import Span

_NAME, _PH, _TS, _DUR = 1, 3, 4, 5


def program_spans() -> list:
    from deepspeed_tpu.telemetry.tracer import get_tracer
    return [Span(e[_NAME], e[_TS], e[_DUR])
            for e in get_tracer().events_snapshot() if e[_PH] == "X"]
