"""What a serving engine is: the ONE contract between ``deepspeed_tpu/serving``
and an engine.

``serving/`` calls an engine through the names of ``ServingEngine`` and
through nothing else: no ``hasattr``, no ``engine.state``, no ``engine.kv``.
``InferenceEngineV2`` subclasses it, and so does every engine double of the
tests; ``InferenceServer`` takes nothing else. The arrow points down:
``serving/`` imports this module, ``inference/v2/`` imports nothing of
``serving/``.

Three sorts of name:

* what every engine does for the loop to run is **abstract**: a double
  implements what it scripts, and a missing method stops its construction;
* what a step reports has a **default** only where "nothing of the sort has
  happened" is a true answer for any engine (no step collected, no fault);
* a **capability** (the host KV offload tier, the prefix cache, a prefill
  chunk cap, the prefix handoff, what a load harness reads) raises
  ``EngineCapabilityError`` by name from here unless the engine provides it,
  so a ``ServingConfig`` that asks for one over an engine without it is
  refused at the server's construction or at the call, never served
  "uncached" or "uncapped" in silence.

Depth. ``step`` dispatches a step and collects pending ones until ``depth``
are left: 0 hands every direct caller (``put``, ``generate``, a benchmark's
check and warm-up) this step's tokens from its own call; a serve loop sets 1
for as long as it runs, fans out at tick k what tick k-1 dispatched, and
calls ``collect`` before it hands the engine back. An engine that computes a
step's tokens inside ``step`` (a double) may ignore ``depth``: its
``collect`` then has nothing pending.
"""

import abc
import types
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

_NOTHING: Mapping = types.MappingProxyType({})


class EngineCapabilityError(NotImplementedError):
    """A capability of the serving contract was asked of an engine that does
    not provide it (the sibling of ``kv_cache.TwoPageKindsError``: there the
    pool cannot, here the engine does not)."""


class ServingEngine(abc.ABC):
    """The engine as ``serving/`` sees it. Single-threaded: only the serve
    loop calls it while that loop runs."""

    # ------------------------------------------------------------------
    # the loop
    # ------------------------------------------------------------------
    #: pending steps ``step`` leaves uncollected (module docstring)
    depth: int = 0
    #: number of the step about to run, carried by every span of that step;
    #: a serve loop writes its own tick's number here before each step
    tick: int = 0

    @abc.abstractmethod
    def can_schedule(self, uids: Sequence[int],
                     lengths: Sequence[int]) -> bool:
        """Is there room (blocks, sequence slots) for ``uids`` to grow by
        ``lengths`` tokens each?"""

    @abc.abstractmethod
    def admit(self, uid: int, prompt_tokens: Sequence[int],
              max_new_tokens: Optional[int] = None):
        """Create the sequence WITHOUT running a step. ``max_new_tokens`` is
        the request's budget: the engine gives the sequence no row past it."""

    @abc.abstractmethod
    def step(self) -> Dict[int, int]:
        """One tick; {uid: token} of what was collected (see ``depth``)."""

    @abc.abstractmethod
    def collect(self) -> None:
        """Collect every pending step now; the next ``step`` returns the
        tokens with its own."""

    @abc.abstractmethod
    def finish(self, uid: int) -> None:
        """Mark a sequence done (length limit, cancel, deadline); its blocks
        are released at the reap."""

    @abc.abstractmethod
    def reap_finished(self):
        """Release every done sequence with nothing in flight; the uids
        reaped (an iterable, or a dict keyed by them)."""

    @abc.abstractmethod
    def has_work(self) -> bool:
        """Would a ``step`` advance a sequence, collect or hand over?"""

    @abc.abstractmethod
    def is_done(self, uid: int) -> bool:
        """Has the sequence ended (its end-of-sequence token was read, or
        ``finish`` was called)? False for one that runs on, and for a uid
        the engine does not hold."""

    @abc.abstractmethod
    def max_context_length(self) -> int:
        """The longest prompt plus generation a sequence may reach."""

    # ------------------------------------------------------------------
    # what a tick reports
    # ------------------------------------------------------------------
    #: the last step's counts (``ahead``, ``starved``, ``rows_dropped``, ...)
    last_step_counters: Mapping[str, int] = _NOTHING
    #: the last step's host-timed ``prefill_s`` / ``decode_s``
    last_step_timing: Mapping[str, float] = _NOTHING
    #: sequences of the steps the last ``step`` call collected
    last_collected_uids: Tuple[int, ...] = ()
    #: after a step whose collection raised: {"uids": that step's sequences,
    #: "lost": those that cannot go on}; None after any other step
    last_fault: Optional[Dict[str, List[int]]] = None
    #: a ``runtime.sched.TickLedger``: the engine books every step there, the
    #: serve loop every token it fans out against its request. No default:
    #: an engine makes its own
    sched_ledger: object

    @abc.abstractmethod
    def prefix_stats(self) -> Dict[str, float]:
        """The prefill-work counters (``prefill_tokens_total`` / ``_saved`` /
        ``_computed``) and, with a prefix cache, its ``prefix_*`` counters."""

    # ------------------------------------------------------------------
    # the pool's accounts (host ints, never a transfer)
    # ------------------------------------------------------------------
    @abc.abstractmethod
    def kv_usable_blocks(self) -> int:
        """Blocks sequences can hold."""

    @abc.abstractmethod
    def kv_reserved_blocks(self) -> int:
        """Blocks held right now (``kv_usable_blocks`` less these are free)."""

    @abc.abstractmethod
    def kv_occupancy(self) -> float:
        """``kv_reserved_blocks`` over ``kv_usable_blocks`` (0..1)."""

    @abc.abstractmethod
    def kv_block_bytes(self) -> int:
        """Device bytes of one block over all layers."""

    @abc.abstractmethod
    def kv_blocks_needed(self, num_tokens: int) -> int:
        """Blocks a sequence of ``num_tokens`` tokens holds between steps."""

    @abc.abstractmethod
    def resident_tokens(self) -> int:
        """Tokens whose KV is resident, in either tier."""

    @abc.abstractmethod
    def kv_resident_bytes(self) -> int:
        """Bytes that hold resident KV, over both tiers."""

    # ------------------------------------------------------------------
    # capabilities: refused by name unless the engine provides them
    # ------------------------------------------------------------------
    def _lacks(self, what: str) -> EngineCapabilityError:
        return EngineCapabilityError(
            f"{type(self).__name__} does not provide {what}")

    def require_one_page_kind(self, what: str) -> None:
        """Raise by name unless the engine keeps KV pages of ONE kind, which
        ``what`` (the host tier, the prefix cache, the prefix handoff) moves
        by block id."""
        raise self._lacks(what)

    # --- the host KV offload tier (``ServingConfig.kv_offload_enabled``)
    def demote_kv(self, uid: int, quantize: str = "none") -> int:
        """Spill a sequence's pages to host RAM and pause it; host bytes now
        held for it (0: nothing to demote)."""
        raise self._lacks("the host KV offload tier (demote_kv)")

    def promote_kv(self, uid: int) -> Optional[int]:
        """Bring a demoted sequence back; the bytes restored, or None where
        the device has too few free blocks right now."""
        raise self._lacks("the host KV offload tier (promote_kv)")

    def has_rows_left(self, uid: int) -> bool:
        """Does the engine hold the sequence, not done and with rows of its
        budget still to dispatch? (One without is about to be reaped: moving
        its pages would be a wasted copy.)"""
        raise self._lacks("the host KV offload tier (has_rows_left)")

    def kv_held_blocks(self, uid: int) -> int:
        """Device blocks a sequence holds right now (0 when demoted)."""
        raise self._lacks("the host KV offload tier (kv_held_blocks)")

    def demoted_blocks(self, uid: int) -> int:
        """Device blocks a demoted sequence needs back at promotion."""
        raise self._lacks("the host KV offload tier (demoted_blocks)")

    def host_kv_bytes(self) -> int:
        """Host bytes the tier holds."""
        raise self._lacks("the host KV offload tier (host_kv_bytes)")

    def host_kv_compression(self) -> float:
        """Raw over stored bytes of everything demoted so far (1.0: none)."""
        raise self._lacks("the host KV offload tier (host_kv_compression)")

    # --- the prefix cache (``ServingConfig.prefix_cache_enabled``)
    #: the ``prefix_cache.PrefixCache`` once enabled; None: no cache
    prefix_cache = None

    def enable_prefix_cache(self, max_cached_blocks: int = 0) -> None:
        """Turn the radix prefix cache on (idempotent)."""
        raise self._lacks("the prefix cache (enable_prefix_cache)")

    def evict_prefix_blocks(self, want: int) -> int:
        """Evict up to ``want`` unpinned cached blocks; the blocks freed."""
        raise self._lacks("the prefix cache (evict_prefix_blocks)")

    # --- a prefill chunk cap (``serving.scheduler.prefill_chunk_tokens``)
    def configure_chunked_prefill(self, prefill_chunk_tokens: int) -> None:
        """Cap the prefill tokens a tick plans (decode-first chunking)."""
        raise self._lacks("a prefill chunk cap (configure_chunked_prefill)")

    # --- the fleet's prefix handoff
    def export_prefix_handoff(self, path: str,
                              quantize: str = "none") -> Dict[str, int]:
        """Write every cached prefix chain to ``path``; what was written."""
        raise self._lacks("the prefix handoff (export_prefix_handoff)")

    def import_prefix_handoff(self, path: str) -> Dict[str, int]:
        """Adopt a predecessor's exported chains; what was adopted."""
        raise self._lacks("the prefix handoff (import_prefix_handoff)")

    # --- what a load harness reads (``serving/bench_serve.py``)
    def decode_batch_buckets(self) -> Sequence[int]:
        """The decode batch ladder a warm-up has to cover."""
        raise self._lacks("its bucket ladders (decode_batch_buckets)")

    def prefill_buckets(self) -> Sequence[int]:
        """The prefill chunk ladder, smallest first."""
        raise self._lacks("its bucket ladders (prefill_buckets)")

    def sched_mark(self) -> None:
        """Start the measured window of the scheduler's counters."""
        raise self._lacks("the scheduler's proof set (sched_mark)")

    def sched_stats(self, gap_unit_tokens: int = 0) -> Dict[str, object]:
        """The scheduler's proof set (``TickLedger.snapshot``)."""
        raise self._lacks("the scheduler's proof set (sched_stats)")

    def kv_ledger(self) -> Dict[str, int]:
        """Both tiers' occupancy and lifetime movement in one dict."""
        raise self._lacks("the KV ledger (kv_ledger)")
