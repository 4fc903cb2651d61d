"""commguard — timeout-bounded collectives and wedge-proof initialization.

The repo's own trajectory is the bug report this module closes: BENCH
r02–r05 all wedged at TPU device discovery with no timeout and no
diagnosis, and a single dead peer turns every eager collective into an
infinite hang the step-level resilience layer (PR 2) cannot see because it
lives *below* the engine. Reference engines treat communicator hang as a
first-class recoverable event (torch NCCL ``timeout=`` + coordinated
abort; elastic-training lineage in PAPERS.md); this is the TPU-native
equivalent.

Scope — what CAN be bounded on TPU:

- **Eager host-driven ops** (checkpoint scatter, ``device_broadcast``,
  debugging collectives) and **initialization** (``jax.distributed``
  rendezvous, PJRT device discovery). These block the calling Python
  thread in native code, so the guard runs them on a watched worker
  thread and the *caller* keeps a deadline: a wedge becomes a
  ``CommWedgeError`` carrying the dstrace comm-span tail instead of a
  silent forever-hang. The abandoned worker thread is daemonic — the
  process is about to coordinated-abort anyway (that is the recovery
  contract, see ``FaultTolerantRunner``).
- Collectives **inside jit** are XLA ops scheduled by the compiler; no
  host-side deadline can exist there. Their health is covered from the
  side instead: the facade's trace-time ``_record`` notes every comm op
  into the active heartbeat (``note_comm_op``), so the membership view
  carries "last-completed comm op" per worker and a wedged device shows
  up as a stalled op sequence + stale heartbeat.

Outcome classes (every guarded call is classified, never just raised):

  ok        completed inside the deadline
  timeout   wedged past the deadline -> ``CommWedgeError``
  transient retryable init failure (connection refused/reset, UNAVAILABLE,
            DEADLINE_EXCEEDED, ...) -> exponential-backoff retry
  fatal     anything else -> ``CommInitError`` / re-raise immediately

Chaos: a ``ChaosMonkey`` with comm knobs (``DSTPU_CHAOS_COMM_*``) injects
deterministic delay/wedge faults into guarded ops so the whole
detect → classify → abort → autosave → resume loop is drillable on CPU.
"""

import enum
import itertools
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from pydantic import model_validator

from deepspeed_tpu.config.config_utils import DeepSpeedTPUConfigModel
from deepspeed_tpu.telemetry.tracer import get_tracer
from deepspeed_tpu.utils.logging import logger

#: worker exit status meaning "a comm fault was detected and handled"
#: (classified abort after autosave). Distinct from preemption signals and
#: from crash codes so the elastic agent's restart accounting can treat
#: comm faults like preemptions (free relaunch) instead of crashes
#: (budgeted). 75 = BSD EX_TEMPFAIL: "temporary failure, retry".
COMM_FAULT_EXIT_CODE = 75

#: env overrides for the init path (set by the elastic agent from the
#: "comm_guard" config group so relaunched workers' rendezvous honors the
#: configured budget; 0 deadline disables bounding)
INIT_DEADLINE_ENV = "DSTPU_COMM_INIT_DEADLINE_S"
INIT_RETRIES_ENV = "DSTPU_COMM_INIT_RETRIES"
INIT_BACKOFF_ENV = "DSTPU_COMM_INIT_BACKOFF_S"


class CommOutcome(enum.Enum):
    OK = "ok"
    TIMEOUT = "timeout"
    TRANSIENT = "transient"
    FATAL = "fatal"


class CommGuardConfig(DeepSpeedTPUConfigModel):
    """The ``"comm_guard"`` config group (see ``config/constants.py``)."""
    enabled: bool = False
    # deadline for one eager guarded collective
    op_deadline_s: float = 60.0
    # deadline for init/rendezvous/device discovery (0 = unbounded)
    init_deadline_s: float = 300.0
    # exponential-backoff retry budget for TRANSIENT init failures
    init_retries: int = 3
    init_backoff_s: float = 1.0
    # distributed-health heartbeat (consumed by resilience/membership.py)
    heartbeat_interval_s: float = 1.0
    # a peer whose heartbeat is older than this is LOST
    lost_after_s: float = 10.0
    # where per-rank heartbeat files land ("" -> DSTPU_MEMBERSHIP_DIR or
    # ./membership under the cwd)
    membership_dir: str = ""
    # straggler detection: a rank is an outlier when its per-op duration
    # exceeds median * factor AND the excess exceeds min_s
    straggler_factor: float = 3.0
    straggler_min_s: float = 0.0
    # trailing dstrace slice attached to CommWedgeError
    trace_tail_s: float = 30.0

    @model_validator(mode="after")
    def _check(self):
        if self.op_deadline_s <= 0:
            raise ValueError("op_deadline_s must be > 0")
        if self.init_deadline_s < 0:
            raise ValueError("init_deadline_s must be >= 0 (0 = unbounded)")
        if self.straggler_factor <= 1.0:
            raise ValueError("straggler_factor must be > 1.0")
        if self.heartbeat_interval_s <= 0:
            raise ValueError("heartbeat_interval_s must be > 0")
        if self.lost_after_s <= self.heartbeat_interval_s:
            raise ValueError("lost_after_s must exceed heartbeat_interval_s")
        return self


# ---------------------------------------------------------------------------
# fault classes
# ---------------------------------------------------------------------------
class CommFaultError(RuntimeError):
    """Base class for classified comm faults. Carries the op name, the
    classified outcome, and elapsed time — everything the coordinated
    recovery path and the exit-code classification need."""

    def __init__(self, msg: str, op: str, outcome: CommOutcome,
                 elapsed_s: float = 0.0):
        super().__init__(msg)
        self.op = op
        self.outcome = outcome
        self.elapsed_s = elapsed_s


class CommWedgeError(CommFaultError):
    """A guarded op ran past its deadline — the BENCH r02–r05 failure,
    mechanized. ``comm_tail`` is the trailing slice of dstrace comm events
    (op/bytes/world per entry) so the error itself says what the
    communicator was doing when it wedged."""

    def __init__(self, msg: str, op: str, elapsed_s: float,
                 comm_tail: Optional[List[dict]] = None):
        super().__init__(msg, op, CommOutcome.TIMEOUT, elapsed_s)
        self.comm_tail = comm_tail or []

    def __str__(self):
        base = super().__str__()
        if not self.comm_tail:
            return base
        last = self.comm_tail[-3:]
        ops = ", ".join(e.get("name", "?") for e in last)
        return f"{base} [comm tail ({len(self.comm_tail)} events): ... {ops}]"


class CommInitError(CommFaultError):
    """Initialization / rendezvous / device discovery failed after the
    retry budget (TRANSIENT exhausted) or immediately (FATAL)."""

    def __init__(self, msg: str, op: str, outcome: CommOutcome,
                 attempts: int = 1, cause: Optional[BaseException] = None):
        super().__init__(msg, op, outcome)
        self.attempts = attempts
        self.__cause__ = cause


class CommPeerLostError(CommFaultError):
    """The membership view declared a peer dead (stale heartbeat)."""

    def __init__(self, msg: str, ranks):
        super().__init__(msg, "membership", CommOutcome.FATAL)
        self.ranks = tuple(ranks)


#: exception-text markers meaning "the fabric/control plane hiccuped —
#: retry with backoff" (gRPC status names the TPU runtime surfaces, plus
#: the socket-level spellings)
_TRANSIENT_MARKERS = (
    "unavailable", "deadline_exceeded", "deadline exceeded", "aborted",
    "connection refused", "connection reset", "connection closed",
    "broken pipe", "temporarily", "try again", "resource_exhausted",
    "failed to connect", "socket closed", "timed out",
)
#: markers meaning "credentials, not connectivity" — never retried
_AUTH_MARKERS = ("permission denied", "permission_denied", "unauthenticated",
                 "forbidden", "credential", "authentication", "oauth")


def classify_exception(exc: BaseException) -> CommOutcome:
    """TRANSIENT iff the error text (or type) says the control plane may
    recover; auth and everything else are FATAL — retrying a credential
    failure just burns the deadline."""
    text = f"{type(exc).__name__}: {exc}".lower()
    if any(m in text for m in _AUTH_MARKERS):
        return CommOutcome.FATAL
    if isinstance(exc, (ConnectionError, TimeoutError)):
        return CommOutcome.TRANSIENT
    if any(m in text for m in _TRANSIENT_MARKERS):
        return CommOutcome.TRANSIENT
    return CommOutcome.FATAL


def comm_trace_tail(tail_s: float = 30.0) -> List[dict]:
    """The trailing ``tail_s`` of dstrace comm events as plain dicts —
    what CommWedgeError embeds so a wedge diagnosis never requires the
    full trace dump."""
    tracer = get_tracer()
    if not tracer.enabled:
        return []
    out = []
    for eid, name, cat, ph, ts, dur, tid, args in tracer.tail(tail_s):
        if cat != "comm" and not name.startswith("comm/"):
            continue
        out.append({"name": name, "ph": ph, "ts": ts, "dur_s": dur,
                    "args": dict(args) if args else {}})
    return out


# ---------------------------------------------------------------------------
# comm-op sequence numbers (the cross-rank join key)
# ---------------------------------------------------------------------------
#: process-wide monotonic comm-op counter. SPMD programs record collectives
#: in the SAME order on every rank (trace-time for jit ops, call order for
#: eager guarded ops), so the k-th recorded op on rank 0 IS the k-th on
#: rank 3 — ``op_seq`` stamped into every comm span/instant is what
#: ``dstpu trace merge`` joins per-rank timelines on. itertools.count is
#: GIL-atomic: allocation never locks the hot path.
_op_seq = itertools.count(1)


def next_op_seq() -> int:
    """Allocate the next comm-op sequence number (registered DS002 hot
    path: one C-level counter increment, never a host sync)."""
    return next(_op_seq)


# ---------------------------------------------------------------------------
# comm-op listener (membership's "last-completed comm op" feed)
# ---------------------------------------------------------------------------
_comm_listener: Optional[Callable[[str], None]] = None


def set_comm_op_listener(fn: Optional[Callable[[str], None]]) -> None:
    """Install the active heartbeat's ``note_op`` (one listener; the
    heartbeat un-installs itself on stop via ``clear_comm_op_listener``)."""
    global _comm_listener
    _comm_listener = fn


def clear_comm_op_listener(fn: Callable[[str], None]) -> None:
    """Uninstall ``fn`` only if it is still the active listener — a stopped
    heartbeat must never sever a newer heartbeat's feed (overlapping
    lifetimes: rolling runner replacement, training + serving in one
    process). Equality, not identity: each ``obj.method`` access builds a
    fresh bound-method object, and ``==`` is what compares the underlying
    (instance, function) pair."""
    global _comm_listener
    if _comm_listener == fn:
        _comm_listener = None


def note_comm_op(op_name: str) -> None:
    """Called by the collective facade for every recorded comm op (trace
    time under jit, per call when eager). Registered DS002 hot path: one
    attribute read + one Python call, never a host sync."""
    lis = _comm_listener
    if lis is not None:
        lis(op_name)


# ---------------------------------------------------------------------------
# active guard (the facade's eager ops route through it automatically)
# ---------------------------------------------------------------------------
_active_guard: Optional["CommGuard"] = None


def set_active_guard(guard: Optional["CommGuard"]) -> None:
    """Install the process-wide guard (the ``FaultTolerantRunner`` does this
    when the ``"comm_guard"`` group is enabled). While installed, the comm
    facade's eager host-driven ops (``device_broadcast``) run deadline-
    bounded without any caller change — the chaos comm drill works against
    an unmodified training script."""
    global _active_guard
    _active_guard = guard


def get_active_guard() -> Optional["CommGuard"]:
    return _active_guard


def clear_active_guard(guard: "CommGuard") -> None:
    """Uninstall ``guard`` only if it is still the active one (overlapping
    runner lifetimes must not strip a newer runner's guard)."""
    global _active_guard
    if _active_guard is guard:
        _active_guard = None


def guarded(op: str, fn: Callable[[], Any],
            deadline_s: Optional[float] = None) -> Any:
    """Run one eager comm op under the active guard, or inline when no
    guard is installed (zero-overhead default: one global read)."""
    g = _active_guard
    if g is None:
        return fn()
    return g.run(op, fn, deadline_s=deadline_s)


# ---------------------------------------------------------------------------
# deadline-bounded execution
# ---------------------------------------------------------------------------
def _run_with_deadline(fn: Callable[[], Any], deadline_s: float,
                       name: str) -> Dict[str, Any]:
    """Run ``fn`` on a daemon worker thread; wait up to ``deadline_s``.

    Returns ``{"done": bool, "value": ..., "error": ...}``. On timeout the
    worker is abandoned (it is stuck in native code no Python mechanism can
    unwind — that is the whole point); the caller raises and the
    coordinated-recovery contract tears the process down.
    """
    box: Dict[str, Any] = {}
    done = threading.Event()

    def _target():
        try:
            box["value"] = fn()
        except BaseException as e:   # noqa: BLE001 — classified by caller
            box["error"] = e
        finally:
            done.set()

    t = threading.Thread(target=_target, daemon=True,
                         name=f"dstpu-commguard-{name}")
    t.start()
    box["done"] = done.wait(deadline_s)
    return box


def bounded_init(fn: Callable[[], Any], *, name: str = "init",
                 deadline_s: float = 300.0, retries: int = 3,
                 backoff_s: float = 1.0,
                 classify: Callable[[BaseException], CommOutcome]
                 = classify_exception,
                 trace_tail_s: float = 30.0) -> Any:
    """Run an init/rendezvous/discovery callable under a deadline with
    exponential-backoff retry for TRANSIENT failures.

    - wedge (no return inside ``deadline_s``) -> ``CommWedgeError``
      immediately: a wedged native init poisons the backend, retrying
      in-process would just stack abandoned threads;
    - TRANSIENT exception -> retry up to ``retries`` times, sleeping
      ``backoff_s * 2^(attempt-1)`` between attempts;
    - FATAL exception -> ``CommInitError`` at once.

    ``deadline_s <= 0`` runs ``fn`` inline (unbounded, for callers that
    explicitly opt out, e.g. DSTPU_COMM_INIT_DEADLINE_S=0).
    """
    tracer = get_tracer()
    attempts = 0
    while True:
        attempts += 1
        t0 = time.monotonic()
        with tracer.span(f"comm/init/{name}", cat="comm", attempt=attempts):
            if deadline_s and deadline_s > 0:
                box = _run_with_deadline(fn, deadline_s, name)
            else:
                # inline (unbounded opt-out): catch Exception only —
                # KeyboardInterrupt/SystemExit must keep their meaning (the
                # runner's preemption contract), not become a FATAL init
                # failure. The threaded path is immune: interrupts land on
                # the main thread's done.wait(), not in the worker.
                try:
                    box = {"done": True, "value": fn()}
                except Exception as e:
                    box = {"done": True, "error": e}
        elapsed = time.monotonic() - t0
        if not box["done"]:
            tracer.instant("comm/init_wedge", cat="comm", op=name,
                           deadline_s=deadline_s)
            raise CommWedgeError(
                f"{name}: initialization exceeded {deadline_s:.0f}s deadline "
                f"(wedged in native init; attempt {attempts})",
                op=name, elapsed_s=elapsed,
                comm_tail=comm_trace_tail(trace_tail_s))
        if "error" not in box:
            return box.get("value")
        exc = box["error"]
        outcome = classify(exc)
        if outcome is CommOutcome.TRANSIENT and attempts <= retries:
            sleep = backoff_s * 2 ** (attempts - 1)
            tracer.instant("comm/init_retry", cat="comm", op=name,
                           attempt=attempts, backoff_s=round(sleep, 3))
            logger.warning(f"commguard: {name} transient init failure "
                           f"(attempt {attempts}/{retries + 1}): {exc!r}; "
                           f"retrying in {sleep:.1f}s")
            time.sleep(sleep)
            continue
        kind = "transient (retry budget exhausted)" \
            if outcome is CommOutcome.TRANSIENT else "fatal"
        raise CommInitError(
            f"{name}: initialization failed ({kind}) after {attempts} "
            f"attempt(s): {exc!r}",
            op=name, outcome=outcome, attempts=attempts, cause=exc)


class CommGuard:
    """Deadline-bounds eager collectives and classifies every outcome.

    ``run(op, fn)`` executes ``fn`` on a watched worker thread; a return
    inside ``op_deadline_s`` is OK (duration fed to the straggler window
    and the heartbeat), a chaos delay is OK-but-slow, and a wedge raises
    ``CommWedgeError`` with the dstrace comm tail attached. Counters are
    plain ints (single guarded-caller discipline: eager ops are host-driven
    and rare) exposed for deterministic tests and env reports.
    """

    def __init__(self, config: Optional[CommGuardConfig] = None,
                 chaos=None):
        self.cfg = config or CommGuardConfig(enabled=True)
        # duck-typed ChaosMonkey (avoids a comm -> resilience import cycle):
        # anything with .comm_fault(op, call_index) -> None|"delay"|"wedge"
        self.chaos = chaos
        self.counters: Dict[str, int] = {o.value: 0 for o in CommOutcome}
        self._calls = 0                    # guarded-op call index (chaos key)

    # ------------------------------------------------------------------
    def run(self, op: str, fn: Callable[[], Any],
            deadline_s: Optional[float] = None) -> Any:
        """One guarded eager op. Raises ``CommWedgeError`` on deadline,
        re-raises (classified, counted) on failure."""
        deadline = deadline_s if deadline_s is not None \
            else self.cfg.op_deadline_s
        call_idx = self._calls
        self._calls += 1
        tracer = get_tracer()
        # allocated at ENTRY so the k-th guarded op carries the same seq on
        # every rank even when one of them wedges mid-op
        op_seq = next_op_seq()
        fault = self.chaos.comm_fault(op, call_idx) \
            if self.chaos is not None else None
        run_fn = fn
        if fault == "wedge":
            # the injected wedge IS a never-returning native call as far as
            # the guard can tell: the worker sleeps far past any deadline
            run_fn = self._wedged(op, deadline)
        elif fault == "delay":
            run_fn = self._delayed(op, fn)
        t0 = time.monotonic()
        with tracer.span(f"comm/guarded/{op}", cat="comm", call=call_idx,
                         op_seq=op_seq, deadline_s=deadline):
            box = _run_with_deadline(run_fn, deadline, op)
        elapsed = time.monotonic() - t0
        if not box["done"]:
            self.counters["timeout"] += 1
            tracer.instant("comm/wedge", cat="comm", op=op,
                           deadline_s=deadline)
            raise CommWedgeError(
                f"collective '{op}' exceeded {deadline:.1f}s deadline "
                f"(wedged; call #{call_idx})",
                op=op, elapsed_s=elapsed,
                comm_tail=comm_trace_tail(self.cfg.trace_tail_s))
        if "error" in box:
            exc = box["error"]
            outcome = classify_exception(exc)
            self.counters[outcome.value] += 1
            tracer.instant("comm/op_failed", cat="comm", op=op,
                           outcome=outcome.value)
            raise exc
        self.counters["ok"] += 1
        note_comm_op(op)
        return box.get("value")

    # ------------------------------------------------------------------
    def _wedged(self, op: str, deadline: float) -> Callable[[], None]:
        def _hang():
            # bounded far past the deadline (not infinite) so the abandoned
            # daemon thread eventually exits in long-lived test processes
            time.sleep(max(deadline, 0.1) * 100)
        return _hang

    def _delayed(self, op: str, fn: Callable[[], Any]) -> Callable[[], Any]:
        delay = getattr(self.chaos.config, "comm_delay_s", 0.0)

        def _slow():
            time.sleep(delay)
            return fn()
        return _slow
