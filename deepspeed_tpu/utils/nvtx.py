"""Profiler range annotation.

Reference analog: ``deepspeed/utils/nvtx.py`` (``instrument_w_nvtx`` pushes an
NVTX range via ``get_accelerator().range_push/pop`` around hot functions, e.g.
every ZeRO-3 coordinator method).

TPU redesign: a range is a span of the dstrace tracer
(``deepspeed_tpu.telemetry.tracer``), which is the repo's one bridge to the
profiler: while tracing is on, every span also opens a
``jax.profiler.TraceAnnotation`` of the same name, so annotated functions sit
beside the engine's spans on the ring and beside the device operations in a
profiler trace. ``instrument`` adds a ``jax.named_scope``, so that inside jit
the name also lands on the emitted ops. With tracing off a range is the
tracer's shared no-op (one attribute read).
"""

import functools

import jax

from deepspeed_tpu.telemetry.tracer import get_tracer


def annotate(name: str):
    """``with annotate("step"): ...`` — a host-side range: the tracer's span,
    mirrored into the profiler while tracing is on."""
    return get_tracer().span(name, cat="annotate")


def instrument(fn=None, *, name: str = None):
    """Decorator: wrap ``fn`` in a range named after it (reference
    ``instrument_w_nvtx``). Usable bare (``@instrument``) or with a name
    (``@instrument(name="fetch")``)."""
    if fn is None:
        return functools.partial(instrument, name=name)
    label = name or getattr(fn, "__qualname__", getattr(fn, "__name__", "fn"))

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with annotate(label), jax.named_scope(label):
            return fn(*args, **kwargs)

    return wrapped


# reference-name alias so call sites read the same
instrument_w_nvtx = instrument


def range_push(name: str):
    """Manual range begin (reference accelerator.range_push). Returns the
    entered context for ``range_pop``; prefer ``with annotate(name):``."""
    ctx = annotate(name)
    ctx.__enter__()
    return ctx


def range_pop(ctx) -> None:
    ctx.__exit__(None, None, None)
