"""sched — the shared host-orchestration core both loops consume.

PR 3 grew the training engine an async step pipeline (deferred metric
readback through a device-side ring, ONE designated batched ``device_get``
drain, staged prefetch); ROADMAP item 1 asks the serve loop to run on the
same machinery instead of growing a parallel copy. This module is that
extraction: the engine-agnostic host-orchestration primitives, consumed by
``runtime/engine.py`` (train) and ``inference/v2/engine_v2.py`` +
``serving/server.py`` (serve).

Three pieces, all DS002-registered hot paths (tools/dslint/hotpath.py):

* ``DispatchRing`` — the dispatch ring: device-side pending payloads, the
  bounded host-entry queue consumers replay from, and ``drain()`` — THE
  designated readback point. One batched ``jax.device_get`` moves every
  pending payload to host (and, by data dependency, proves the queued
  device work completed — the anchor that keeps reconciled timers
  honest). Nothing else in a hot loop may call ``.device_get``.
* ``StagedPrefetcher`` — identity-keyed staged-prefetch lifecycle: one
  background loader per source iterator, loud (then throttled) warnings
  when iterator churn defeats the staging.
* ``TickLedger`` — the serve tick's deterministic scheduler counters:
  per-tick prefill-token caps, decode-stall tokens, chunk conservation.
  On a CPU container wall-clock is noise; these counters are the proof
  set the decode-first chunked-prefill scheduler is judged by
  (``dstpu_bench_serve`` ``report["scheduler"]``).

Host-side bookkeeping only: no jit, no collectives, no per-step
allocation beyond the payload dicts the caller already built.
"""

import collections
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax

from deepspeed_tpu.telemetry.tracer import get_tracer
from deepspeed_tpu.utils.logging import logger


class DrainResult(NamedTuple):
    """One ``DispatchRing.drain()``: host payloads + the extra operand that
    rode the same transfer, and the window the drained steps span."""
    payloads: List[Dict[str, Any]]
    extra: Any
    window_s: float        # seconds since the window anchor (0.0 unanchored)
    anchored: bool


class DispatchRing:
    """Device-side pending payload ring + bounded host-entry queue + THE
    designated drain ``device_get``.

    The producer pushes payload dicts whose values may be live device
    arrays (fresh jit outputs — never donated buffers: donation deletes
    them while they'd still sit in the ring). ``drain`` moves everything
    across in one batched transfer, computes the reconciliation window
    from the anchor the producer set at the window's first dispatch, and
    leaves host fan-out to the caller. Drained entries the caller stores
    land in a bounded deque consumers ``take()``/``requeue()`` from —
    overflow is never silent.
    """

    def __init__(self, capacity: int = 4096, sync_every: int = 1,
                 span_name: str = "engine/drain", span_cat: str = "train",
                 name: str = "async_pipeline"):
        self.pending: List[Dict[str, Any]] = []    # device-side payloads
        self.drained: collections.deque = collections.deque(maxlen=capacity)
        self.sync_every = int(sync_every)
        self.span_name = span_name
        self.span_cat = span_cat
        self.name = name
        self.anchor: Optional[float] = None        # window start (time.time)

    def __len__(self) -> int:
        return len(self.pending)

    def push(self, payload: Dict[str, Any]) -> bool:
        """Queue one step's device-side payload; returns True when the
        ring reached the drain cadence (caller runs its drain)."""
        self.pending.append(payload)
        return len(self.pending) >= self.sync_every

    def rearm_if_idle(self) -> None:
        """Anchor a fresh window at this dispatch iff the ring is empty —
        host pauses between windows (checkpoint I/O, idle gaps after a
        flush) must never be booked as step time at the next drain."""
        if not self.pending:
            self.anchor = time.time()

    def reset_anchor(self) -> None:
        self.anchor = None

    def drain(self, extra: Any = None,
              on_error: Optional[Callable[[BaseException], None]] = None
              ) -> Optional[DrainResult]:
        """THE designated readback point: one batched ``device_get`` over
        every pending payload (+ ``extra``, which rides the same
        transfer). Returns None when nothing is pending. ``on_error``
        sees a raising transfer before the exception unwinds (the
        execution-time-OOM classify-and-stash contract)."""
        if not self.pending:
            return None
        ring, self.pending = self.pending, []
        try:
            with get_tracer().span(self.span_name, cat=self.span_cat,
                                   steps=len(ring)):
                host, extra_host = jax.device_get((ring, extra))
        except Exception as e:
            if on_error is not None:
                on_error(e)
            raise
        window, anchored = 0.0, self.anchor is not None
        if anchored:
            window = max(time.time() - self.anchor, 0.0)
        return DrainResult(payloads=host, extra=extra_host,
                           window_s=window, anchored=anchored)

    def store(self, entries: List[Dict[str, Any]]) -> int:
        """Append drained host entries to the consumer queue; returns the
        number of oldest un-consumed entries the bounded deque evicted
        (warned — with no consumer attached the bounded-lag guard
        guarantee degrades past this point)."""
        dropped = len(self.drained) + len(entries) - self.drained.maxlen
        if dropped > 0:
            logger.warning(
                "%s: drained-metrics queue overflow — %d oldest "
                "un-consumed entries dropped (no take_drained_metrics "
                "consumer attached?)", self.name, dropped)
        self.drained.extend(entries)
        return max(dropped, 0)

    def take(self) -> List[Dict[str, Any]]:
        """Pop every drained-but-unconsumed host entry, in order."""
        out = list(self.drained)
        self.drained.clear()
        return out

    def requeue(self, entries: List[Dict[str, Any]]) -> None:
        """Put taken-but-unprocessed entries back at the FRONT (original
        order preserved); refuses to evict newer entries silently."""
        free = self.drained.maxlen - len(self.drained)
        if len(entries) > free:
            # appendleft on a full deque would evict the NEWEST entries
            # from the right — refuse to lose them silently
            logger.warning(
                "%s: requeue overflow — %d newest entries dropped from "
                "the drained-metrics queue", self.name, len(entries) - free)
            entries = entries[:free]
        for e in reversed(entries):
            self.drained.appendleft(e)


class StagedPrefetcher:
    """Identity-keyed staged-prefetch lifecycle: one loader per source
    iterator. A new source closes the old loader (dropping its staged
    batches — the source iterator has already advanced past them), loud
    the first few switches and throttled after."""

    def __init__(self, depth: int = 2, name: str = "async_pipeline"):
        self.depth = int(depth)
        self.name = name
        self.loader = None
        self.source = None
        self.switches = 0

    def ensure(self, source, factory: Callable[[], Any]):
        """Return the live loader for ``source``, building one via
        ``factory`` when the source identity changed (or none exists)."""
        if self.loader is not None and self.source is source:
            return self.loader
        if self.loader is not None:
            self.switches += 1
            if self.switches <= 3 or self.switches % 100 == 0:
                # a fresh iterator object per call defeats prefetch (thread
                # churn + staged batches already pulled from the source are
                # dropped) — loud the first few times, throttled after
                logger.warning(
                    "%s: data_iter identity changed (switch #%d) — "
                    "discarding the previous prefetcher and up to %d "
                    "staged batches; pass a STABLE iterator across "
                    "train_batch calls", self.name, self.switches,
                    self.depth)
            self.loader.close()
        self.loader = factory()
        self.source = source
        return self.loader

    def close(self) -> None:
        if self.loader is not None:
            self.loader.close()
            self.loader = None
            self.source = None


class TickLedger:
    """Deterministic per-tick serve-scheduler counters — the chunked
    prefill proof set. ``observe_tick`` is called once per engine step
    with that tick's planned work; everything else is host int
    arithmetic (no clocks, so the counters are identical across hosts
    for the same seeded workload).

    Window semantics: warmed bench runs call ``reset_window()`` at the
    measurement mark so the warm wave's ticks never leak into the
    measured maxima; cumulative totals keep running (every proof
    identity over them is conservation-shaped)."""

    #: bounded per-request attribution table (serving runs indefinitely;
    #: finished requests are popped, abandoned ones age out FIFO)
    REQUEST_CAP = 4096

    def __init__(self):
        self.ticks = 0                    # observed (working) ticks
        self.prefill_ticks = 0            # ticks that ran >= 1 chunk
        self.decode_ticks = 0             # ticks that ran a decode batch
        self.chunk_tokens_total = 0       # prefill tokens through chunks
        self.chunks_total = 0
        self.decode_tokens_total = 0
        self.capped_chunk_ticks = 0       # prefill ticks bound by the cap
        # uid -> {"ticks", "prefill_tokens", "chunks", "decode_tokens"}:
        # which slice of the tick stream each request consumed — the
        # wall-clock-free denominator the SLO layer states latencies in
        # (ceil-div cap units via ``units()``)
        self.request_ticks: Dict[int, Dict[str, int]] = {}
        self.reset_window()

    @staticmethod
    def units(tokens: int, unit_tokens: int) -> int:
        """Ceil-div of a token count into ``unit_tokens``-sized scheduling
        quanta — the ``max_decode_gap_ticks`` normalizer, exposed so the
        SLO histograms can be fed in cap units instead of wall seconds
        (deterministic across hosts; 0 when either operand is)."""
        if unit_tokens <= 0 or tokens <= 0:
            return 0
        return -(-int(tokens) // int(unit_tokens))    # ceil div

    def reset_window(self) -> None:
        """Start the measured window: maxima reset, totals keep running."""
        self.max_prefill_tokens_per_tick = 0
        # prefill tokens in the worst tick that ALSO ran decodes — the
        # exact "tokens of prefill a decode token waited behind" measure
        self.max_decode_stall_tokens = 0
        self.window_prefill_ticks = 0
        self.window_chunk_tokens = 0

    def observe_tick(self, prefill_tokens: int, chunks: int,
                     decode_tokens: int, cap: int = 0) -> None:
        self.ticks += 1
        if chunks:
            self.prefill_ticks += 1
            self.window_prefill_ticks += 1
            self.chunks_total += chunks
            self.chunk_tokens_total += prefill_tokens
            self.window_chunk_tokens += prefill_tokens
            if cap > 0 and prefill_tokens >= cap:
                self.capped_chunk_ticks += 1
        if decode_tokens:
            self.decode_ticks += 1
            self.decode_tokens_total += decode_tokens
        if prefill_tokens > self.max_prefill_tokens_per_tick:
            self.max_prefill_tokens_per_tick = prefill_tokens
        if decode_tokens and prefill_tokens > self.max_decode_stall_tokens:
            self.max_decode_stall_tokens = prefill_tokens

    def attribute_request(self, uid: int, prefill_tokens: int = 0,
                          chunks: int = 0, decode_tokens: int = 0) -> None:
        """Book one tick's work against the request that consumed it.
        Called alongside ``observe_tick`` by callers that know the
        per-request split (the serve loop's fan-out does); pure host int
        arithmetic like everything else here."""
        entry = self.request_ticks.get(uid)
        if entry is None:
            while len(self.request_ticks) >= self.REQUEST_CAP:
                # FIFO age-out: dict preserves insertion order
                self.request_ticks.pop(next(iter(self.request_ticks)))
            entry = {"ticks": 0, "prefill_tokens": 0, "chunks": 0,
                     "decode_tokens": 0}
            self.request_ticks[uid] = entry
        entry["ticks"] += 1
        entry["prefill_tokens"] += int(prefill_tokens)
        entry["chunks"] += int(chunks)
        entry["decode_tokens"] += int(decode_tokens)

    def pop_request(self, uid: int) -> Optional[Dict[str, int]]:
        """Remove and return a finished request's attribution entry (None
        when the request was never attributed or already aged out)."""
        return self.request_ticks.pop(uid, None)

    def snapshot(self, cap: int = 0, gap_unit_tokens: int = 0
                 ) -> Dict[str, Any]:
        """The scheduler proof set. ``max_decode_gap_ticks`` states the
        worst decode stall in cap-sized scheduling ticks: how many
        chunk-cap quanta of prefill a decode token waited behind in the
        worst tick (1 == decode never waited more than one chunk —
        "never serialized behind a full prefill"). ``gap_unit_tokens``
        overrides the normalizer so an uncapped baseline run can be
        stated in the SAME units as the capped run it is compared to."""
        unit = int(gap_unit_tokens or cap or 0)
        gap = self.units(self.max_decode_stall_tokens, unit)
        util = 0.0
        if cap > 0 and self.window_prefill_ticks > 0:
            util = self.window_chunk_tokens / float(
                cap * self.window_prefill_ticks)
        return {
            "prefill_chunk_tokens": int(cap),
            "ticks": self.ticks,
            "prefill_ticks": self.prefill_ticks,
            "decode_ticks": self.decode_ticks,
            "chunks_total": self.chunks_total,
            "chunk_tokens_total": self.chunk_tokens_total,
            "decode_tokens_total": self.decode_tokens_total,
            "capped_chunk_ticks": self.capped_chunk_ticks,
            "max_prefill_tokens_per_tick": self.max_prefill_tokens_per_tick,
            "max_decode_stall_tokens": self.max_decode_stall_tokens,
            "decode_gap_unit_tokens": unit,
            "max_decode_gap_ticks": gap,
            "prefill_cap_utilization": round(util, 4),
        }
