"""Compile-event ledger — every XLA compile becomes a trace instant.

PRs 10 and 13 each re-learned the same lesson by hand: a mid-measurement
XLA compile stalls the serve tick (or the timed bench window) for seconds
and silently poisons every counter and latency number downstream — the
fix was always "warm the exact shapes first", re-discovered per drill.
This module mechanizes the discipline:

- ``watch_jit(fn, name)`` wraps a jitted callable. Every dispatch probes
  the jit cache size before/after (one C-level int read — never a host
  sync; the wrapper is a registered DS002 hot path): when the cache grew,
  THIS call traced+compiled, and an ``xla/compile`` instant is emitted
  carrying the fn qualname, the abstract shape signature of the call, and
  the wall ms the dispatch took (trace+lower+compile all block dispatch,
  so the first-call wall time IS the compile cost).
- ``compiles_total()`` is the process-wide counter benches mark before
  their timed window and diff after: ``compiles_during_measurement`` in
  the proof set, asserted ZERO after warmup — the "warm the exact shapes
  first" rule as a machine-checked invariant instead of tribal knowledge.

The signature builder runs ONLY on the compile (slow) path and describes
arguments duck-typed (``.shape``/``.dtype`` attribute reads, never a
materialization), so the ledger itself can never add a transfer.
Stdlib-only at module level — importable from any hot-path file.
"""

import threading
import time
from typing import Any, Callable, Optional

from deepspeed_tpu.telemetry.tracer import get_tracer

COMPILE_INSTANT = "xla/compile"

#: cap on rendered signature length (a 100-layer param tree would bloat
#: every compile instant; the head + leaf count identifies the shape set)
_SIG_MAX_LEAVES = 12

_lock = threading.Lock()
_total = 0


def compiles_total() -> int:
    """XLA compiles observed by watched dispatch sites so far in this
    process. Benches snapshot it before the timed window; the diff is
    ``compiles_during_measurement``."""
    with _lock:
        return _total


def _describe(x: Any) -> Optional[str]:
    """One leaf's abstract signature — attribute reads only, no
    materialization (``f32[8,128]`` idiom)."""
    shape = getattr(x, "shape", None)
    if shape is None:
        if isinstance(x, (int, float, bool)):
            return type(x).__name__
        return None
    dtype = getattr(x, "dtype", None)
    dname = getattr(dtype, "name", str(dtype)) if dtype is not None else "?"
    return f"{dname}[{','.join(str(d) for d in shape)}]"


def _walk(obj: Any, out: list) -> int:
    """Collect up to ``_SIG_MAX_LEAVES`` rendered leaf descriptions into
    ``out`` but COUNT every leaf (cheap attribute reads) — the tail count
    in the signature must be the tree's true size, not the render cap."""
    desc = _describe(obj)
    if desc is not None:
        if len(out) < _SIG_MAX_LEAVES:
            out.append(desc)
        return 1
    n = 0
    if isinstance(obj, dict):
        for k in sorted(obj, key=str):
            n += _walk(obj[k], out)
    elif isinstance(obj, (list, tuple)):
        for v in obj:
            n += _walk(v, out)
    # other leaves (None, configs, rng keys without .shape) add nothing
    return n


def signature_of(args: tuple, kwargs: dict) -> str:
    """Abstract shape signature of one call — the compile cache key's
    human-readable shadow. Computed ONLY on the compile path."""
    leaves: list = []
    total = _walk(args, leaves) + _walk(kwargs, leaves)
    if total > len(leaves):
        return ",".join(leaves) + f",...({total} leaves)"
    return ",".join(leaves)


def record_compile(name: str, signature: str, wall_s: float) -> None:
    """Count + trace one observed compile (the slow path — the compile
    itself just took orders of magnitude longer than this bookkeeping)."""
    global _total
    with _lock:
        _total += 1
    get_tracer().instant(COMPILE_INSTANT, cat="compile", fn=name,
                         signature=signature,
                         wall_ms=round(wall_s * 1e3, 3))


class CompileWatched:
    """Transparent wrapper over a jitted callable: dispatch passes
    straight through; a jit-cache growth marks the call as a compile and
    emits the ``xla/compile`` instant. Attribute access (``.lower``,
    ``.clear_cache``...) delegates to the wrapped function."""
    __slots__ = ("_fn", "_name", "_watched", "_on_compile")

    def __init__(self, fn: Callable, name: str,
                 on_compile: Optional[Callable] = None):
        self._fn = fn
        self._name = name
        self._on_compile = on_compile
        # jax.jit functions expose the compiled-signature cache size; a
        # callable without it (a plain python fn) is passed through
        # unwatched rather than broken. Only the fact is kept: the bound
        # ``fn._cache_size`` is a native method object the cycle collector
        # cannot look through, and holding it would pin the jitted function
        # — and with it whatever its closure reaches, an engine and all its
        # device state — for the life of the process
        self._watched = hasattr(fn, "_cache_size")

    def __call__(self, *args, **kwargs):
        if not self._watched:
            return self._fn(*args, **kwargs)
        before = self._fn._cache_size()
        t0 = time.monotonic()
        out = self._fn(*args, **kwargs)
        if self._fn._cache_size() > before:
            record_compile(self._name, signature_of(args, kwargs),
                           time.monotonic() - t0)
            if self._on_compile is not None:
                self._on_compile(self._fn, self._name, args, kwargs, out)
        return out

    def __getattr__(self, item):
        return getattr(self._fn, item)


def watch_jit(fn: Callable, name: str,
              on_compile: Optional[Callable] = None) -> CompileWatched:
    """Wrap a jitted callable so its compiles land in the ledger. The
    contract every engine/serving jit dispatch site follows: the wrapper
    is shape-transparent (same args, same return, donation semantics
    untouched) and adds one int probe per dispatch. ``on_compile(fn, name,
    args, kwargs, out)`` runs after a call that compiled, on that slow
    path only: what a site wants to say about the program it just built."""
    return CompileWatched(fn, name, on_compile)
