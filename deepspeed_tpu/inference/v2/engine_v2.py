"""Continuous-batching inference engine (FastGen equivalent).

Reference analog: ``deepspeed/inference/v2/engine_v2.py:30``
(``InferenceEngineV2``): ``put(batch_uids, batch_tokens)`` schedules a ragged
forward; ``query``/``can_schedule`` gate admission on free KV blocks; the state
manager + blocked KV cache hold per-sequence context.

TPU adaptation: per step, the SplitFuse plan becomes one bucketed
``prefill_chunk_g`` call per admitted chunk, the last of which carries the
running decodes' rows through the layers with its own (one forward pass a
step, every weight read once: the reference's ragged forward at static
shapes), or, where the plan holds no chunk (and always over a pool with a
state kind), one padded ``decode_step_g`` call (``generic_decode.py``) — every
shape from a small bucket ladder, so steady-state serving runs entirely from
compiled programs. What a KV page is, the engine leaves to ``kv_cache.py``.

A step is dispatched (``_dispatch``: plan, build, hand the programs to the
device; lengths decide everything there) and later collected
(``_collect_oldest``: read the sampled tokens, the one place the host waits
for the device; values decide there). ``step`` does both; how many
dispatched steps it leaves uncollected is ``depth``, which a serving loop
sets to 1 so that its host work between two ticks runs while the device does.
"""

import collections
import dataclasses
import time
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2.engine_contract import ServingEngine
from deepspeed_tpu.inference.v2.generic_decode import (decode_step_g,
                                                       prefill_chunk_g,
                                                       verify_chunk_g)
from deepspeed_tpu.inference.v2.kv_cache import (BlockedKVCache,
                                                 blocks_behind_window)
from deepspeed_tpu.inference.v2.kv_offload import (HostKVEntry, HostKVStore,
                                                   dequantize_pages,
                                                   quantize_pages)
from deepspeed_tpu.inference.v2.modules import policy_for
from deepspeed_tpu.inference.v2.prefix_cache import PrefixCache
from deepspeed_tpu.inference.v2.ragged_manager import SequenceDescriptor, StateManager
from deepspeed_tpu.inference.v2.sampling import (SamplingConfig, feed_tokens,
                                                 sample_into)
from deepspeed_tpu.inference.v2.scheduler import (
    SchedulerConfig,
    StepPlan,
    plan_step,
    snap_bucket,
)
from deepspeed_tpu.models.llama import LlamaConfig
from deepspeed_tpu.runtime.sched import TickLedger
from deepspeed_tpu.telemetry.names import (GATHERED_ROWS_ARG,
                                           KV_ROWS_BY_PAGE_ARG,
                                           KV_ROWS_WRITTEN_ARG,
                                           STEP_COUNTER_ARGS)
from deepspeed_tpu.telemetry.tracer import get_tracer
from deepspeed_tpu.utils.logging import log_dist


@dataclasses.dataclass
class V2EngineConfig:
    kv_block_size: int = 64
    kv_num_blocks: int = 512
    # over a model that mixes full and windowed layers this is the full
    # layers' pool; the windowed layers' follows from the scheduler's limits
    # (``_window_pool_blocks``)
    max_tracked_sequences: int = 256
    scheduler: SchedulerConfig = dataclasses.field(default_factory=SchedulerConfig)
    decode_batch_buckets: Tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64)
    ctx_block_buckets: Tuple[int, ...] = (4, 8, 16, 32, 64)   # blocks per table
    eos_token_id: Optional[int] = None
    greedy: bool = True            # back-compat; sampling is the full control
    sampling: SamplingConfig = dataclasses.field(default_factory=SamplingConfig)
    # attention implementation: auto (Pallas kernel on TPU, gather elsewhere),
    # kernel, kernel_interpret, gather — see kv_cache.ATTN_IMPLS
    attn_impl: str = "auto"
    # KV page dtype: "model" stores pages in the model compute dtype; "fp8"
    # stores float8_e4m3 pages — HALF the KV memory vs bf16 (2x capacity:
    # bigger batches / longer contexts), with per-(head, page) fp32 scales
    # (grown on outliers, page requantized — reference group-scaled fp
    # quantizer, csrc/fp_quantizer) applied on load inside both attention
    # paths
    kv_cache_dtype: str = "model"
    # draft-free speculative decoding (prompt-lookup): propose the k tokens
    # that followed the last occurrence of the trailing n-gram, verify them
    # in ONE chunk forward, accept the longest argmax-matching prefix + one
    # bonus token — 1..k+1 tokens per step, greedy-equivalent up to batching
    # numerics (verified bitwise on CPU f32; on TPU bf16 the [bucket, D]
    # verify matmul can reorder reductions vs the 1-row decode and flip
    # argmax on near-ties). Beyond-reference: FastGen has no speculative
    # decoding. 0 = off; greedy-only (rejected at construction under
    # sampling)
    speculative_k: int = 0
    speculative_ngram: int = 3
    # block-granular radix prefix cache (prefix_cache.py): admission
    # reuses already-materialized KV blocks for the longest cached
    # prompt prefix (refcounted pins on shared pages) and only prefills
    # the novel suffix. Default OFF = pre-cache semantics (same opt-in
    # discipline as kv_offload / async_pipeline); the serving group's
    # `prefix_cache_enabled` flips it on through enable_prefix_cache()
    prefix_cache_enabled: bool = False
    # soft cap on UNPINNED cached blocks (0 = unlimited up to pool size);
    # the serve tick trims the cache down to it even without pressure
    prefix_cache_max_blocks: int = 0


@dataclasses.dataclass
class _PendingStep:
    """One dispatched step whose sampled tokens are still on the device.
    Put on ``_pending`` before its first program is dispatched and filled as
    they are, so that a fault in the middle of a dispatch leaves what was
    dispatched on record."""
    uids: Tuple[int, ...]                    # every sequence of the plan
    # (sequence, the chunk's start and length, the [1] token sampled where
    # the chunk ended its prompt)
    chunks: List[tuple] = dataclasses.field(default_factory=list)
    decode_seqs: Sequence[SequenceDescriptor] = ()
    decode_sampled: Optional[jax.Array] = None
    # what the step's programs counted (``_keep_counts``), and beside it
    # the rows their expert layers gathered (``GATHERED_ROWS_ARG``)
    counts: List[jax.Array] = dataclasses.field(default_factory=list)
    gathered: int = 0


@dataclasses.dataclass
class _Dispatched:
    """What ``_dispatch`` hands ``step`` for the spans it stamps once the
    collection is over."""
    plan: StepPlan
    ahead: int = 0
    # where ``ahead`` is 1: had the step before already finished when this
    # one's first program was dispatched (None: not to be told)
    starved: Optional[int] = None
    chunk_marks: List[tuple] = dataclasses.field(default_factory=list)
    prefill_t0: float = 0.0                  # where serve/step_prefill opens
    t_prefill: float = 0.0
    decode_t0: Optional[float] = None        # where serve/step_decode opens
    decode_args: Optional[dict] = None

    def flight_args(self) -> Dict[str, int]:
        """``ahead``, and ``starved`` where it could be told: what the
        step's spans and ``last_step_counters`` say of the step in flight."""
        if self.starved is None:
            return {"ahead": self.ahead}
        return {"ahead": self.ahead, "starved": self.starved}


class InferenceEngineV2(ServingEngine):
    """Serves any registered arch (llama family incl. mistral/qwen2/phi3,
    falcon, opt, mixtral, joyai_llm_flash over its latent cache, ...) — the
    policy registry picks the decode implementation from the model config
    type (reference: engine_factory + heuristics)."""

    def __init__(self, params, model_config,
                 config: Optional[V2EngineConfig] = None):
        self.params = params
        self.model_config = model_config
        self.config = config or V2EngineConfig()
        if self.config.speculative_k > 0 and not self.config.greedy:
            # reject BEFORE any sequence state exists: failing inside
            # _speculative_step would leave a half-processed sequence whose
            # prefill already consumed KV blocks
            raise ValueError(
                "speculative_k > 0 requires greedy=True: proposal "
                "acceptance compares argmax chains, which sampling breaks")
        self.policy = policy_for(model_config)
        spec = self.policy.cache_spec(model_config)
        self.kv = BlockedKVCache.for_spec(
            spec, self.config.kv_cache_dtype,
            block_size=self.config.kv_block_size,
            num_blocks=self.config.kv_num_blocks,
            window_blocks=self._window_pool_blocks(spec),
            state_slots=self.config.max_tracked_sequences)
        if self.config.speculative_k > 0 and self.kv.has_state:
            # refused here, not at the first proposal that hits
            self.kv.require_one_kind("speculative decoding")
        self.state = StateManager(
            max_tracked_sequences=self.config.max_tracked_sequences,
            max_context_length=spec.max_seq_len)
        if not self.config.greedy and \
                self.config.sampling.temperature <= 0.0:
            self.config = dataclasses.replace(
                self.config,
                sampling=dataclasses.replace(self.config.sampling,
                                             temperature=1.0))
        self._rng = jax.random.PRNGKey(self.config.sampling.seed)
        # dispatched steps whose tokens the host has not read, oldest first,
        # and how many of them ``step`` leaves so when it returns: 0 hands
        # every caller this step's tokens (``put``, ``generate``, a test, a
        # benchmark's check); a serving loop sets 1 for as long as it runs
        # and fans out at tick k what tick k-1 dispatched. Not a
        # configuration key: nothing but the order of the host's work
        # depends on it
        self.depth = 0
        self._pending: Deque[_PendingStep] = collections.deque()
        # tokens collected outside a ``step`` (``collect``), which the next
        # ``step`` returns with its own
        self._ready: Dict[int, int] = {}
        # every sequence's last sampled token by ``SequenceDescriptor.slot``,
        # on the device: ``_sample_dispatch`` stores there what the next
        # decode row reads (``sampling.feed_tokens``), so a token's value
        # crosses to the host for the client alone. The slot past the last
        # takes the stores of batch padding
        self._pad_slot = self.state.max_tracked_sequences
        self._last_tokens = jnp.zeros((self._pad_slot + 1,), jnp.int32)
        # rows computed for a sequence that had ended by the time their
        # token was read (``eos_token_id`` read a tick late, a cancel, a
        # deadline), over the engine's life; never delivered
        self.rows_dropped = 0
        # after a step whose collection raised: {"uids": the failed step's
        # sequences, "lost": those that cannot go on} (``_abandon_pending``);
        # None after any other step
        self.last_fault: Optional[Dict[str, List[int]]] = None
        # sequences of the steps the last ``step`` call collected
        self.last_collected_uids: Tuple[int, ...] = ()
        # persistent device-side decode tables: in steady-state decode the
        # block tables only change when a sequence crosses a block boundary,
        # so the [B, MB] table upload is skipped while the allocation
        # signature (bucket shape + every sequence's block-id list) is
        # unchanged (addresses the per-step host re-pad/re-upload cost;
        # tokens/positions are [B] ints and always refresh)
        self._table_sig = None
        self._dev_tables = None
        # the decode half every chunk program carries, (rows, table blocks):
        # the largest decode batch bucket over the widest context bucket a
        # sequence can reach (the model's longest context, in a pool that
        # holds it), whatever a tick decodes, so that a chunk program's
        # shape follows from its chunk and this configuration alone; and a
        # half of padding, for the chunks that carry no decode rows. None
        # over a pool with a state kind: there a tick keeps its two programs
        # (the fused step read 2-5% UNDER them on the chip, cause not found:
        # PERF.md section 6, PR 45; ROADMAP S1(f))
        sched = self.config.scheduler
        self._fused_decode = self._no_decode = None
        if not self.kv.has_state:
            b = snap_bucket(min(sched.max_decode_batch,
                                self.state.max_tracked_sequences),
                            self.config.decode_batch_buckets)
            mb = snap_bucket(min(self.kv.blocks_of(spec.max_seq_len),
                                 self.kv.cfg.num_blocks - 1),
                             self.config.ctx_block_buckets)
            self._fused_decode = (b, mb)
            self._no_decode = (
                jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
                self._decode_tables((), b, mb), jnp.zeros((b,), bool))
        # host-RAM KV offload tier (serving demotion target; kv_offload.py)
        self.host_kv = HostKVStore()
        # radix prefix cache over KV pages (prefix_cache.py); None = off
        self.prefix_cache: Optional[PrefixCache] = None
        if self.config.prefix_cache_enabled:
            self.enable_prefix_cache(self.config.prefix_cache_max_blocks)
        # prefill-work conservation counters (prefix_stats): at drain,
        # saved + computed == total exactly (never-prefilled remainders
        # of cancelled sequences are subtracted from total at flush)
        self._prefill_total = 0
        self._prefill_saved = 0
        self._prefill_computed = 0
        # last step's host-timed prefill/decode split (serve-tick clocks)
        self.last_step_timing = {"prefill_s": 0.0, "decode_s": 0.0}
        # deterministic per-tick scheduler counters (runtime/sched.py) — the
        # decode-first chunked-prefill proof set; fed every non-empty step
        # in BOTH modes so an uncapped run yields the A/B baseline counters
        self.sched_ledger = TickLedger()
        self.last_step_counters = {"prefill_tokens": 0, "chunks": 0,
                                   "decode_tokens": 0, "ahead": 0,
                                   "rows_dropped": 0}
        self.last_step_counters.update(self._kv_page_counters())
        # number of the step about to run, carried by every span of that
        # step; a serving loop overwrites it with its own tick's number
        # before each step so that its spans and the engine's share it
        self.tick = 0
        # counts of step programs that were waited for by nothing (device
        # vectors, where the policy counts and a tracer is on: a chunk that
        # ended no prompt), kept until the next read of a sampled token
        # reads them with it
        self._pending_counts: List[jax.Array] = []
        self._pending_gathered = 0
        # what a windowed layer's decode reads of a context, in tokens (the
        # one window of all layers, or the windowed kind's), or None
        self._window = self.kv.kind.window if self.kv.two_kinds \
            else spec.window
        # speculative-decoding counters (speculative_stats)
        self._spec_steps = 0
        self._spec_proposed = 0
        self._spec_accepted = 0

    def _window_pool_blocks(self, spec) -> int:
        """The windowed layers' pool in blocks (0 where the spec has one
        kind of page): what ``max_decode_batch`` sequences hold between
        steps, one chunk of the longest prefill bucket beside them, and the
        trash block. ``can_schedule`` admits no more than that, so a larger
        pool would never fill."""
        from deepspeed_tpu.inference.v2.kv_cache import (mixes_layer_kinds,
                                                         one_window,
                                                         windowed_table_blocks)
        if not mixes_layer_kinds(spec.layer_windows):
            return 0
        window = one_window(spec.layer_windows)
        bs, sched = self.config.kv_block_size, self.config.scheduler
        return (sched.max_decode_batch - 1) \
            * windowed_table_blocks(1, window, bs) \
            + windowed_table_blocks(sched.prefill_buckets[-1], window, bs) + 1

    def require_one_page_kind(self, what: str) -> None:
        """Raise ``TwoPageKindsError`` naming ``what`` over a cache that
        keeps pages of two kinds, ``StateKindError`` over one some of whose
        layers keep a recurrent state."""
        self.kv.require_one_kind(what)

    def enable_prefix_cache(self, max_cached_blocks: int = 0) -> None:
        """Turn the radix prefix cache on (idempotent) — the serving
        layer's wiring point for the ``serving.prefix_cache_enabled``
        config key when the engine wasn't constructed with it."""
        self.require_one_page_kind("the prefix cache")
        if self.prefix_cache is None:
            self.prefix_cache = PrefixCache(self.config.kv_block_size,
                                            max_cached_blocks)

    def configure_chunked_prefill(self, prefill_chunk_tokens: int) -> None:
        """Set the decode-first prefill cap (the serving layer's wiring
        point for ``serving.scheduler.prefill_chunk_tokens``). The cap
        must cover at least one KV block: capped mid-prompt boundaries
        snap DOWN to block granularity, so a smaller cap could never
        make progress."""
        cap = int(prefill_chunk_tokens)
        if cap > 0 and cap < self.kv.cfg.block_size:
            raise ValueError(
                f"prefill_chunk_tokens={cap} is smaller than the KV block "
                f"size ({self.kv.cfg.block_size}): block-aligned chunking "
                f"could never make progress")
        self.config = dataclasses.replace(
            self.config, scheduler=dataclasses.replace(
                self.config.scheduler, prefill_chunk_tokens=cap))

    def sched_mark(self) -> None:
        """Start the measured counter window (bench: at the compile mark,
        so warm-wave ticks never leak into the measured maxima)."""
        self.sched_ledger.reset_window()

    def sched_stats(self, gap_unit_tokens: int = 0) -> Dict[str, object]:
        """The scheduler proof set (see TickLedger.snapshot)."""
        return self.sched_ledger.snapshot(
            cap=self.config.scheduler.prefill_chunk_tokens,
            gap_unit_tokens=gap_unit_tokens)

    # ------------------------------------------------------------------
    # admission control (reference: engine_v2.py:158 query, :184 can_schedule)
    # ------------------------------------------------------------------
    def _lacking(self, uid: int, max_request_length: int) -> Tuple[int, int]:
        """(full, windowed) blocks ``uid`` lacks for ``max_request_length``
        more tokens; the second is 0 where all pages are of one kind."""
        seq = self.state.get(uid)
        tracked = seq.total_tokens if seq else 0
        return self.kv.lacking(seq, tracked + max_request_length)

    def query(self, uid: int, max_request_length: int) -> Tuple[int, int]:
        """Returns (max_new_blocks_needed, free_blocks), each over both
        kinds of page where the cache keeps two."""
        return sum(self._lacking(uid, max_request_length)), \
            self.kv.free_blocks

    def can_schedule(self, uids: Sequence[int],
                     lengths: Sequence[int]) -> bool:
        lacking = [self._lacking(uid, n) for uid, n in zip(uids, lengths)]
        full = sum(f for f, _ in lacking)
        # unpinned cached prefix blocks count as schedulable capacity:
        # they are evicted on demand the moment a reservation needs them
        fits = full <= self.kv.allocator.free_blocks + self._evictable_blocks()
        if self.kv.two_kinds:
            # the windowed pool keeps one chunk's blocks free beside what
            # sequences hold between steps (a chunk's are given back when
            # it ends)
            spare = self.kv.window_step_blocks(
                self.config.scheduler.prefill_buckets[-1])
            fits = fits and sum(w for _, w in lacking) + spare <= \
                self.kv.window_allocator.free_blocks
        return fits and \
            len(self.state) + len([u for u in uids if u not in self.state]) <= \
            self.state.max_tracked_sequences

    # ------------------------------------------------------------------
    # block bookkeeping
    # ------------------------------------------------------------------
    def _evictable_blocks(self) -> int:
        return (self.prefix_cache.evictable_blocks()
                if self.prefix_cache is not None else 0)

    def _reserve(self, num_blocks: int) -> List[int]:
        """Reserve device blocks, reclaiming unpinned prefix-cache blocks
        on demand when the free list alone can't cover the request —
        cached-but-unreferenced pages are capacity, not occupancy."""
        free = self.kv.allocator.free_blocks
        if self.prefix_cache is not None and num_blocks > free:
            self.evict_prefix_blocks(num_blocks - free)
        return self.kv.reserve(num_blocks)

    def evict_prefix_blocks(self, want: int) -> int:
        """Evict up to ``want`` unpinned cached blocks (LRU leaf-first)
        and release them to the allocator. Returns blocks actually
        freed. Called on-demand by reservation and by the serving tier's
        pressure policy (cache eviction ALWAYS precedes sequence
        demotion — see serving/kv_tier.plan_prefix_evictions)."""
        if self.prefix_cache is None or want <= 0:
            return 0
        blocks = self.prefix_cache.evict_blocks(
            self.prefix_cache.plan_evictions(want))
        if blocks:
            # refs == 0 by construction: no reader left, a plain release
            # (with its scale reset) is exactly right
            self.kv.release(blocks)
        return len(blocks)

    def _ensure_blocks(self, seq: SequenceDescriptor, up_to_tokens: int):
        need = self.kv.blocks_of(up_to_tokens) - len(seq.blocks)
        if need > 0:
            seq.blocks.extend(self._reserve(need))
        if self.kv.two_kinds:
            self.kv.ensure_window(seq, up_to_tokens)

    def _block_table(self, seq: SequenceDescriptor, bucket_blocks: int) -> np.ndarray:
        trash = self.kv.cfg.num_blocks - 1
        table = np.full((bucket_blocks,), trash, dtype=np.int32)
        n = min(len(seq.blocks), bucket_blocks)
        table[:n] = seq.blocks[:n]
        return table

    def _step_tables(self, seq: SequenceDescriptor, bucket_blocks: int,
                     first_query: int, rows: int):
        """What a step program takes as ``seq``'s block table: its blocks
        padded to the context bucket, and over pages by layer kind the
        windowed layers' table beside it (``BlockedKVCache.window_table``)."""
        table = self._block_table(seq, bucket_blocks)
        if not self.kv.by_layer_kind:
            return table
        tables = {"full": table}
        if self.kv.two_kinds:
            tables["window"] = self.kv.window_table(seq, first_query, rows)
        if self.kv.has_state:
            # the state kind's entry is the sequence's slot itself
            tables["state"] = np.int32(seq.slot)
        return tables

    def _decode_tables(self, seqs, batch: int, bucket_blocks: int):
        """The decode batch's block tables on the device, [batch, ...] a
        kind, the rows that are batch padding (all of them where ``seqs`` is
        empty) all trash."""
        cfg = self.kv.cfg
        # what pads a kind's rows: its trash block, or the slot past the last
        pad = {"full": np.full((bucket_blocks,), cfg.num_blocks - 1, np.int32)}
        if self.kv.two_kinds:
            pad["window"] = np.full(
                (self.kv.window_steady_blocks,), cfg.window_blocks - 1,
                np.int32)
        if self.kv.has_state:
            pad["state"] = np.int32(self._pad_slot)
        rows = [self._step_tables(seq, bucket_blocks, seq.total_tokens - 1, 1)
                for seq in seqs]

        def stacked(tables, pad):
            out = np.broadcast_to(pad, (batch,) + pad.shape).copy()
            if tables:
                out[:len(tables)] = tables
            return jnp.asarray(out)
        if not self.kv.by_layer_kind:
            return stacked(rows, pad["full"])
        return {kind: stacked([r[kind] for r in rows], pad[kind])
                for kind in pad}

    def _decode_rows(self, seqs, batch: int, bucket_blocks: int):
        """A decode batch of ``seqs`` as a step program takes it, padded to
        ``batch`` rows over tables of ``bucket_blocks``: (``rows`` [2, batch]
        int32: row j's slot in the last-token vector, and its token where the
        host holds it (-1: read the slot); (positions, block tables, valid)
        on the device; every sequence's context in tokens; whether the
        tables were built anew). Reserves the blocks the rows land in."""
        rows = np.full((2, batch), -1, np.int32)
        rows[0] = self._pad_slot
        positions = np.zeros((batch,), np.int32)
        valid = np.zeros((batch,), bool)
        for j, seq in enumerate(seqs):
            self._ensure_blocks(seq, seq.total_tokens)
            rows[0, j] = seq.slot
            if not seq.token_on_device:
                rows[1, j] = seq.generated[-1] if seq.generated \
                    else seq.prompt_tokens[-1]
            positions[j] = seq.total_tokens - 1
            valid[j] = True
        # signature covers the actual block ids: uid reuse after
        # flush() can hand a same-shaped batch different pages
        # (a windowed block given back resets the signature:
        # ``_advanced``)
        sig = (batch, bucket_blocks, tuple(tuple(s.blocks) for s in seqs))
        if self.kv.has_state:
            sig += (tuple(s.slot for s in seqs),)
        rebuilt = sig != self._table_sig
        if rebuilt:
            self._dev_tables = self._decode_tables(seqs, batch, bucket_blocks)
            self._table_sig = sig
        return rows, (jnp.asarray(positions), self._dev_tables,
                      jnp.asarray(valid)), \
            [s.total_tokens for s in seqs], rebuilt

    def _decoded(self, rec: "_PendingStep", seqs, logits, rows) -> None:
        """A decode batch's logits are on their way: sample on the device
        (only [B] token ids cross to the host: the [B, vocab] logits D2H
        fetch would dominate the loop) and move ``seqs`` on by their row. A
        batch of padding alone is sampled too (one path, and the sampler's
        shapes warm whether a tick decodes or not) and leaves no record."""
        sampled = self._sample_dispatch(logits, rows)
        if not seqs:
            return
        rec.decode_sampled = sampled
        rec.decode_seqs = seqs
        for seq in seqs:
            seq.seen_tokens = seq.total_tokens
            self._advanced(seq)
            seq.in_flight += 1
            seq.token_on_device = True

    def _advanced(self, seq: SequenceDescriptor) -> None:
        """``seq.seen_tokens`` moved on: over pages by layer kind, give back
        the windowed blocks that fell behind its window."""
        if self.kv.two_kinds and self.kv.give_back_behind_window(seq):
            self._table_sig = None

    def _release_blocks(self, seq: SequenceDescriptor, blocks) -> None:
        """``blocks`` of ``seq``'s full pages, and all its windowed ones,
        back to their allocators."""
        self.kv.release(blocks)
        if self.kv.two_kinds:
            self.kv.release_window(seq)

    def _ctx_bucket_blocks(self, tokens: int) -> int:
        blocks = self.kv.blocks_of(max(tokens, 1))
        return snap_bucket(blocks, self.config.ctx_block_buckets)

    # ------------------------------------------------------------------
    # the step
    # ------------------------------------------------------------------
    def put(self, batch_uids: Sequence[int], batch_tokens: Sequence[Sequence[int]],
            do_checks: bool = True) -> Dict[int, int]:
        """Admit new/continued sequences and run ONE engine step
        (reference: engine_v2.put engine_v2.py:107). Returns {uid: next_token}
        for every sequence that produced a token this step."""
        self.collect()    # a continuation extends a prompt: lengths settle first
        if do_checks and not self.can_schedule(
                batch_uids, [len(t) for t in batch_tokens]):
            raise RuntimeError("cannot schedule batch: out of KV blocks or slots")
        for uid, toks in zip(batch_uids, batch_tokens):
            if uid in self.state:
                seq = self.state.get(uid)
                seq.prompt_tokens = np.concatenate(
                    [seq.prompt_tokens, np.asarray(toks, np.int32)])
                seq.done = False
                self._prefill_total += len(toks)
            else:
                self._prefix_admit(self.state.create(uid, toks))
        return self.step()

    def _prefix_admit(self, seq: SequenceDescriptor) -> int:
        """Prefix-cache admission for a freshly created sequence: pin the
        longest cached full-block prefix of its prompt, seed its block
        table with the shared pages, and mark that prefix as already
        seen — prefill then covers only the novel suffix. Returns the
        reused token count. Pure bookkeeping; no page moves."""
        self._prefill_total += len(seq.prompt_tokens)
        if self.prefix_cache is None:
            return 0
        blocks, matched = self.prefix_cache.admit_match(
            seq.uid, seq.prompt_tokens)
        if matched:
            seq.blocks = list(blocks)
            seq.seen_tokens = matched
            self._prefill_saved += matched
        return matched

    def step(self) -> Dict[int, int]:
        """One tick: dispatch this step's programs, then collect pending
        steps, oldest first, until ``depth`` are left. Returns {uid: token}
        of what was collected: this step's tokens at depth 0, the step
        before's at depth 1 (same programs, same code; the depths differ in
        when the collection runs). A step that dispatched nothing collects
        everything: there is nothing to run beside the wait. Speculative
        decoding proposes from the values, so it keeps depth 0."""
        tracer = get_tracer()
        # every span of this call carries the tick's number (see ``tick``):
        # the spans of the dispatch, and the wait and the commit of the
        # collection made in this call, whichever step that one is for. The
        # device's work between the ends of two waits is then one step's
        # programs, at either depth. The phases tile the call: the device's
        # idle gaps are named by the phase their middle falls in, so what
        # lies between two phases is kept to a few microseconds.
        tick = self.tick
        self.last_fault = None
        d = self._dispatch(tick, tracer)
        plan = d.plan
        keep = 0 if plan.empty or self.config.speculative_k > 0 \
            else self.depth
        t0 = time.monotonic()
        counts: Dict[str, int] = {}
        dropped_before = self.rows_dropped
        collected: List[int] = []
        while len(self._pending) > keep:
            collected.extend(self._pending[0].uids)
            for name, n in self._collect_oldest(tick, tracer).items():
                counts[name] = counts.get(name, 0) + n
        self.last_collected_uids = tuple(collected)
        dropped = self.rows_dropped - dropped_before
        t_collect = time.monotonic() - t0
        t_prefill, t_decode = d.t_prefill, 0.0
        if d.decode_t0 is not None:
            t_decode = t0 + t_collect - d.decode_t0
        elif plan.prefill_chunks:
            t_prefill = t0 + t_collect - d.prefill_t0
        else:
            t_decode = t_collect
        flight = d.flight_args()
        if tracer.enabled:
            # what the collection read rides on this tick's decode span, or
            # where it decoded nothing on its last chunk's, which then
            # holds the wait as the decode span would
            read = dict(counts, rows_dropped=dropped)
            marks = d.chunk_marks
            if marks and d.decode_t0 is None:
                c0, _, args = marks[-1]
                marks[-1] = (c0, t0 + t_collect, dict(args, **read))
            for c0, c1, args in marks:
                tracer.complete("serve/prefill_chunk", c1 - c0, cat="serve",
                                end_ts=c1, tick=tick, **flight, **args)
            if marks:
                tracer.complete("serve/step_prefill", t_prefill, cat="serve",
                                end_ts=d.prefill_t0 + t_prefill, tick=tick,
                                chunks=len(marks))
            if d.decode_t0 is not None:
                tracer.complete("serve/step_decode", t_decode, cat="serve",
                                end_ts=d.decode_t0 + t_decode, tick=tick,
                                **flight, **d.decode_args, **read)
            elif collected and not marks:
                # nothing left to dispatch: the tick is the wait for the
                # last step and its commit, the decode stage's all the same
                tracer.complete("serve/step_decode", t_decode, cat="serve",
                                end_ts=t0 + t_decode, tick=tick, ahead=0,
                                **read)

        with tracer.span("serve/step_finish", cat="serve", tick=tick):
            self.tick = tick + 1
            # the serve tick's stage clocks read these
            # (serve/tick_stage_share gauges + `dstpu plan --serve`
            # prefill/decode attribution)
            self.last_step_timing = {"prefill_s": t_prefill,
                                     "decode_s": t_decode}
            prefill_tokens = sum(c.length for c in plan.prefill_chunks)
            decode_tokens = len(plan.decode_seqs)
            pages = self._kv_page_counters()
            self.last_step_counters = {"prefill_tokens": prefill_tokens,
                                       "chunks": len(plan.prefill_chunks),
                                       "decode_tokens": decode_tokens,
                                       **flight,
                                       "rows_dropped": dropped,
                                       **pages}
            if tracer.enabled and not plan.empty:
                tracer.counter("serve/kv_pages", cat="mem", **pages)
            if not plan.empty:
                self.sched_ledger.observe_tick(
                    prefill_tokens, len(plan.prefill_chunks), decode_tokens,
                    cap=self.config.scheduler.prefill_chunk_tokens)
            out, self._ready = self._ready, {}
        return out

    def _dispatch(self, tick: int, tracer) -> _Dispatched:
        """Plan a step and hand its programs to the device; nothing here
        reads a value back. The bookkeeping that lengths decide happens now:
        blocks, positions, ``seen_tokens``, the windowed blocks a sequence
        gives back as it advances, the tokens it has in flight. Blocks may
        be freed and handed to another sequence by this bookkeeping while a
        program that reads them is still queued: the device runs programs
        in the order they were dispatched, and every reader and writer of
        the pool is such a program, so whatever the new holder writes, it
        writes after the old one has read."""
        with tracer.span("serve/plan", cat="serve", tick=tick):
            plan = plan_step(self.state.decoding(), self.state.prefilling(),
                             self.config.scheduler,
                             block_tokens=self.kv.cfg.block_size)
            d = _Dispatched(plan)
            if plan.empty:
                return d
            d.ahead = int(bool(self._pending))
            rec = _PendingStep(uids=tuple(
                [s.uid for s in plan.decode_seqs]
                + [c.seq.uid for c in plan.prefill_chunks]))
            self._pending.append(rec)

        # --- prefill chunks (SplitFuse), the decode batch in the last ---
        d.prefill_t0 = time.monotonic()
        for chunk in plan.prefill_chunks:
            seq = chunk.seq
            end = chunk.start + chunk.length
            # per-chunk sub-span, nested inside serve/step_prefill (same
            # exclusive stage): building the chunk, its dispatch, and the
            # bookkeeping after it; ``step`` stamps it from these marks
            c0 = time.monotonic()
            self._ensure_blocks(seq, end)
            tokens = np.zeros((chunk.bucket,), np.int32)
            tokens[:chunk.length] = seq.prompt_tokens[chunk.start:end]
            mb = self._ctx_bucket_blocks(end)
            table = self._step_tables(seq, mb, chunk.start, chunk.bucket)
            # every chunk program carries a decode half of one shape
            # (``_fused_decode``): the tick's decode rows ride in its last
            # chunk's, so that the layers' weights are read once for both;
            # any other chunk's half is padding (over a state kind no chunk
            # carries one, and the decode rows follow in their own program)
            last = chunk is plan.prefill_chunks[-1]
            fused = last and self._fused_decode is not None
            if fused:
                rows, half, _, _ = self._decode_rows(
                    plan.decode_seqs, *self._fused_decode)
                rows = jnp.asarray(rows)
                half = (feed_tokens(self._last_tokens, rows),) + half
            else:
                half = self._no_decode
            if d.ahead and chunk is plan.prefill_chunks[0]:
                d.starved = self._device_ran_dry()
            # the step programs consume the pool they are given: what
            # comes back is bound at once, so that a fault later in the
            # tick (and the server's next step after it) finds the
            # engine on a live pool
            logits, self.kv.pool, counts = prefill_chunk_g(
                self.params, self.kv.pool, jnp.asarray(tokens),
                chunk.start,
                jax.tree.map(jnp.asarray, table), chunk.length, half,
                policy=self.policy, cfg=self.model_config,
                block_size=self.kv.cfg.block_size,
                attn_impl=self.config.attn_impl)
            if half is not None:
                logits, decode_logits = logits
            self._keep_counts(rec, counts, chunk.bucket + (
                0 if half is None else self._fused_decode[0]))
            seq.seen_tokens = end
            self._advanced(seq)
            self._prefill_computed += chunk.length
            rec.chunks.append((seq, chunk.start, chunk.length, None))
            if self.prefix_cache is not None:
                # register the freshly materialized FULL prompt blocks
                # so concurrent arrivals with the same prefix reuse them
                # (pinned for this sequence's lifetime — the pin is what
                # keeps a shared page safe from release/demotion)
                self.prefix_cache.insert_from_seq(
                    seq.uid, seq.prompt_tokens, seq.blocks,
                    min(seq.seen_tokens, len(seq.prompt_tokens)))
            if not seq.in_prefill:
                slot = np.full((2, 1), -1, np.int32)
                slot[0] = seq.slot
                sampled = self._sample_dispatch(logits[None],
                                                jnp.asarray(slot))
                seq.in_flight += 1
                seq.token_on_device = True
                rec.chunks[-1] = (seq, chunk.start, chunk.length, sampled)
            # ... the keys of the tiles the paged kernel multiplied the
            # chunk's rows by and the page copies a layer's call issued,
            # and on the tick's last chunk what rode in its decode half
            # (no ``serve/step_decode`` span says it: the decode rooflines
            # set that span's bytes against ``decode_step_g``'s kernels)
            args = dict(uid=seq.uid, tokens=chunk.length, bucket=chunk.bucket,
                        start=chunk.start, **(self.kv.chunk_tile_keys(
                            chunk.start, chunk.bucket, mb, self._window)
                            if tracer.enabled else {}))
            # ... and the rows the program wrote to the pool: the chunk's,
            # a page at a time where its bucket is a block or more over
            # plain pages, and its decode half's, always one by one
            args.update({
                KV_ROWS_WRITTEN_ARG: chunk.length + (
                    len(plan.decode_seqs) if fused else 0),
                KV_ROWS_BY_PAGE_ARG: chunk.length
                if self.kv.chunk_by_page(chunk.bucket) else 0})
            if fused:
                self._decoded(rec, plan.decode_seqs, decode_logits, rows)
            if last:
                args.update(fused_rows=len(plan.decode_seqs) if fused else 0)
            d.chunk_marks.append((c0, time.monotonic(), args))
        if plan.prefill_chunks:
            d.t_prefill = time.monotonic() - d.prefill_t0
            if self._fused_decode is not None or not plan.decode_seqs:
                return d

        # --- decode rows that rode in no chunk: their own program ---
        d.decode_t0 = time.monotonic()
        with tracer.span("serve/decode_build", cat="serve",
                         tick=tick) as build:
            seqs = plan.decode_seqs
            b = snap_bucket(len(seqs), self.config.decode_batch_buckets)
            mb = self._ctx_bucket_blocks(max(s.total_tokens for s in seqs))
            rows, half, contexts, rebuilt = self._decode_rows(seqs, b, mb)
            build.note(tables_rebuilt=rebuilt)
        with tracer.span("serve/decode_dispatch", cat="serve", tick=tick):
            if d.ahead and not plan.prefill_chunks:
                d.starved = self._device_ran_dry()
            rows = jnp.asarray(rows)
            logits, self.kv.pool, counts = decode_step_g(
                self.params, self.kv.pool,
                feed_tokens(self._last_tokens, rows), *half,
                policy=self.policy, cfg=self.model_config,
                block_size=self.kv.cfg.block_size,
                attn_impl=self.config.attn_impl)
            self._keep_counts(rec, counts, b)
            self._decoded(rec, seqs, logits, rows)
        # what the scheduler decided, as plain host ints the step
        # already holds: the batch and the bucket it was padded to,
        # the context the paged kernel had to read (whole, and cut
        # to the sliding window where the model has one), the keys of
        # the tiles it read them in and the slot copies a layer's call
        # issued for them
        if tracer.enabled:
            window, whole = self._window, sum(contexts)
            d.decode_args = dict(
                batch=len(seqs), bucket=b, ctx_tokens=whole,
                ctx_tokens_windowed=sum(min(c, window) for c in contexts)
                if window else whole, ctx_blocks=mb,
                **{KV_ROWS_WRITTEN_ARG: len(seqs), KV_ROWS_BY_PAGE_ARG: 0},
                **self.kv.decode_tile_keys(contexts, mb, window),
                **self.kv.decode_slot_copies(contexts, b, mb, window))
        return d

    def _device_ran_dry(self) -> Optional[int]:
        """Asked right before this step's first program goes to the device,
        with a step pending before it: are that step's sampled tokens (its
        decode's, or its last sampled chunk's where it decoded nothing)
        already there? 1: the device finished it and has sat idle since, for
        want of the host; 0: it is still running, and this step queues
        behind it. ``is_ready`` asks the runtime and waits for nothing. None
        where the step sampled nothing or its array cannot say: left out,
        not guessed."""
        rec = self._pending[-2]
        sampled = rec.decode_sampled
        if sampled is None:
            sampled = next((c[3] for c in reversed(rec.chunks)
                            if c[3] is not None), None)
        is_ready = getattr(sampled, "is_ready", None)
        return None if is_ready is None else int(bool(is_ready()))

    def _collect_oldest(self, tick: int, tracer) -> Dict[str, int]:
        """Read the oldest pending step's sampled tokens and commit them:
        the one place the engine waits for the device, and where values
        decide (``generated``, what ``step`` returns, ``eos_token_id``).
        Returns what the step programs counted, under their
        ``STEP_COUNTER_ARGS`` names, or {}. The counts are results of
        programs that ran before the sampling, so once the tokens are here
        they are too; a step that sampled nothing (chunks that ended no
        prompt) is waited for by nothing, and its counts are read with the
        next token that is. A read that raises abandons every pending step
        (``_abandon_pending``) and says so in ``last_fault``."""
        rec = self._pending[0]
        rows = [([seq], sampled, False) for seq, _, _, sampled in rec.chunks
                if sampled is not None]
        if rec.decode_sampled is not None:
            rows.append((rec.decode_seqs, rec.decode_sampled, True))
        if not rows:
            self._pending.popleft()
            self._pending_counts += rec.counts
            self._pending_gathered += rec.gathered
            return {}
        kept = self._pending_counts + rec.counts
        gathered = self._pending_gathered + rec.gathered
        try:
            with tracer.span("serve/decode_wait", cat="serve", tick=tick):
                # the step's one wait: the sampled tokens' readback, with
                # vectors that were ready before them (hotpath.py declares
                # this function the served step's readback)
                values = jax.device_get([r[1] for r in rows] + kept)
        except Exception:
            self.last_fault = {"uids": list(rec.uids),
                               "lost": self._abandon_pending()}
            raise
        self._pending.popleft()
        self._pending_counts, self._pending_gathered = [], 0
        with tracer.span("serve/decode_commit", cat="serve", tick=tick):
            for (seqs, _, decode), toks in zip(rows, values):
                for seq, tok in zip(seqs, toks):
                    seq.in_flight -= 1
                    if seq.done:
                        # ended while this row was queued: dropped
                        self.rows_dropped += 1
                        continue
                    tok = int(tok)
                    seq.generated.append(tok)
                    self._ready[seq.uid] = tok
                    if decode and self.config.eos_token_id is not None \
                            and tok == self.config.eos_token_id:
                        seq.done = True
            if not kept:
                return {}
            return dict(zip(STEP_COUNTER_ARGS, (
                int(v) for v in np.sum(values[len(rows):], axis=0))),
                **{GATHERED_ROWS_ARG: gathered})

    def _abandon_pending(self) -> List[int]:
        """Take back every pending step, newest first, after a read that
        raised: its rows and chunks count as not run, so the next step
        dispatches them again with the tokens the host holds. Returns the
        uids that cannot go on: over pages by layer kind, a sequence whose
        windowed blocks were given back past where it now stands, and over a
        state kind every sequence taken back (the server recomputes it from
        its tokens)."""
        touched = {}
        while self._pending:
            rec = self._pending.pop()
            for seq in rec.decode_seqs:
                seq.in_flight -= 1
                seq.seen_tokens -= 1
                touched[seq.uid] = seq
            for seq, start, length, sampled in reversed(rec.chunks):
                seq.in_flight -= sampled is not None
                seq.seen_tokens = start
                self._prefill_computed -= length
                touched[seq.uid] = seq
        self._pending_counts, self._pending_gathered = [], 0
        self._table_sig = None
        lost = []
        for uid, seq in touched.items():
            seq.token_on_device = False
            # a state has summed the rows taken back and cannot give them up
            if self.kv.has_state or (
                    self.kv.two_kinds and seq.window_base >
                    blocks_behind_window(seq.seen_tokens, self.kv.kind.window,
                                         self.kv.cfg.block_size)):
                lost.append(uid)
        return lost

    def collect(self) -> None:
        """Collect every pending step now; the next ``step`` returns the
        tokens with its own. For whoever is about to touch a sequence that
        may have a row in flight, or to stop stepping."""
        tracer = get_tracer()
        while self._pending:
            self._collect_oldest(self.tick, tracer)

    def _kv_page_counters(self) -> Dict[str, int]:
        """The pool after a step, as host ints: blocks sequences hold by
        kind of page (``kv_full_blocks``; over pages by layer kind
        ``kv_window_blocks`` and, running, ``kv_window_blocks_given_back``),
        their bytes (``kv_held_bytes``, and by kind ``kv_full_bytes`` and
        ``kv_window_bytes``, each from a block of the kind's own pool), and
        the tokens whose keys and values they hold."""
        held = self.kv.pages_held()
        out = {"kv_live_tokens": sum(s.seen_tokens for s in self.state.all()
                                     if not s.paused),
               **{f"kv_{name}": n for name, n in held.items()}}
        if self.kv.slot_bytes:
            # a state kind: the slots sequences hold, whatever their lengths
            out["state_slots_held"] = len(self.state)
            out["kv_state_bytes"] = len(self.state) * self.kv.slot_bytes
        return out

    def _keep_counts(self, rec: _PendingStep, counts, rows: int) -> None:
        """Keep what a step program of ``rows`` rows (padding and a chunk
        program's decode half among them) counted, nothing where its policy
        counts nothing, with its step, while a tracer is there to read it;
        and with it the rows the program's expert layers sorted and
        gathered, which its shapes say: ``ids.size`` a layer."""
        if counts.size and get_tracer().enabled:
            rec.counts.append(counts)
            rec.gathered += rows * self.policy.routed_assignments(
                self.model_config)

    def _sample_dispatch(self, logits, rows):
        """[B, V] device logits -> [B] device token ids, stored into the
        last-token vector at ``rows[0]`` too: dispatch only.
        ``_collect_oldest``'s read of the result (one small D2H, inside a
        ``serve/decode_wait`` span) is where the host waits for the
        device."""
        self._rng, key = jax.random.split(self._rng)
        tokens, self._last_tokens = sample_into(
            logits, key, self._last_tokens, rows, self.config.sampling)
        return tokens

    # ------------------------------------------------------------------
    # lifecycle (reference: engine_v2.flush)
    # ------------------------------------------------------------------
    def flush(self, uid: int) -> List[int]:
        """Release a sequence's KV blocks (both tiers); returns its
        generated tokens. With the prefix cache on, full blocks covering
        the materialized prompt+generated history are ABSORBED into the
        trie instead of freed (refcount 0, evictable) — the multi-turn
        win: the next turn's prompt starts with exactly these tokens —
        and blocks the cache owns are excluded from the allocator
        release (pinned pages additionally excluded from the fp8 scale
        reset inside ``BlockedKVCache.release``). A sequence with a row in
        flight is collected first (``reap_finished`` waits a tick for it
        instead)."""
        seq = self.state.get(uid)
        if seq is not None and seq.in_flight:
            self.collect()
        seq = self.state.pop(uid)
        self._ready.pop(uid, None)
        if seq.in_prefill:
            # cancelled mid-prefill: the never-computed remainder leaves
            # the conservation identity (saved + computed == total) exact
            self._prefill_total -= max(
                len(seq.prompt_tokens) - seq.seen_tokens, 0)
        if self.prefix_cache is not None:
            history = np.concatenate(
                [seq.prompt_tokens,
                 np.fromiter(seq.generated, np.int32)]) if seq.generated \
                else seq.prompt_tokens
            self.prefix_cache.insert_from_seq(
                uid, history, seq.blocks, seq.seen_tokens, pin=False)
            self.prefix_cache.release_seq(uid)
            cache = self.prefix_cache
            # cache-owned blocks (pinned OR retained at refs 0) are
            # excluded outright — the owns() partition is what protects
            # shared pages and their fp8 scales here; release(pinned=)
            # remains the contract for callers without a partition
            self.kv.release([b for b in seq.blocks if not cache.owns(b)])
        else:
            self._release_blocks(seq, seq.blocks)
        self.host_kv.pop(uid)     # no-op unless the sequence was demoted
        return seq.generated

    # ------------------------------------------------------------------
    # host KV offload tier (serving demotion/promotion; kv_offload.py)
    # ------------------------------------------------------------------
    def demote_kv(self, uid: int, quantize: str = "none") -> int:
        """Spill a sequence's KV pages to host RAM and release its device
        blocks; the sequence pauses (invisible to the step planner) until
        ``promote_kv``. Returns host bytes now held for it (0 when the uid
        is unknown or already demoted). A deliberate device->host copy —
        called from the serving tier policy, never from the jitted step.

        ``quantize`` selects the host-tier page codec ("none"/"int8"/
        "fp8", the serving group's ``host_kv_quantize``): the gathered
        pages are stored narrow with per-page fp32 scales, roughly
        doubling-to-quadrupling the host budget's effective blocks.
        Device-fp8 pages are never re-quantized (their scales already
        ride along; the round-trip stays bit-identical).

        Prefix-cache composition: pages the cache owns are NOT discarded
        with the sequence — this reader's pins drop, but the pages stay
        on device for the surviving readers (or evictable at refcount 0)
        AND travel to the host tier inside this entry, so promotion is
        self-sufficient even if the cached copies get evicted meanwhile."""
        self.require_one_page_kind("the host KV offload tier (demote_kv)")
        seq = self.state.get(uid)
        if seq is not None and seq.in_flight:
            self.collect()    # a paused sequence has nothing in flight
        if seq is None or seq.paused or seq.done or seq.budget_spent:
            # a done sequence is about to be reaped (one whose budget is
            # spent, once its last token is read) — gathering its pages
            # would be a pure wasted device->host copy
            return 0
        if seq.blocks:
            data, scales = self.kv.gather_blocks(seq.blocks)
        else:
            data, scales = None, None
        codec = "none"
        qscales = None
        raw = (int(data.nbytes) if data is not None else 0) + \
              (int(scales.nbytes) if scales is not None else 0)
        if data is not None and quantize != "none" and \
                self.kv.cfg.dtype != jnp.float8_e4m3fn:
            data, qscales = quantize_pages(data, quantize)
            codec = quantize
        entry = HostKVEntry(blocks=len(seq.blocks), data=data, scales=scales,
                            seen_tokens=seq.seen_tokens, codec=codec,
                            qscales=qscales, raw_nbytes=raw)
        self.host_kv.put(uid, entry)
        if self.prefix_cache is not None:
            self.prefix_cache.release_seq(uid)
            cache = self.prefix_cache
            self.kv.release([b for b in seq.blocks if not cache.owns(b)])
        else:
            self.kv.release(seq.blocks)
        seq.blocks = []
        seq.paused = True
        self._table_sig = None    # decode tables must rebuild
        return entry.nbytes

    def promote_kv(self, uid: int) -> Optional[int]:
        """Bring a demoted sequence back: reserve (possibly different)
        device blocks, scatter its host pages in, resume scheduling.
        Returns the bytes restored, or None when the uid is unknown or the
        device has too few free blocks right now."""
        seq = self.state.get(uid)
        entry = self.host_kv.get(uid)
        if seq is None or entry is None or seq.done:
            # a done sequence is about to be reaped (flush drops the host
            # entry) — restoring its pages would be a wasted copy
            return None
        if entry.blocks > self.kv.free_blocks + self._evictable_blocks():
            return None
        blocks = self._reserve(entry.blocks)
        if entry.blocks:
            # quantized entries dequantize back to the device page width
            # here (tolerance-bounded); full-width entries scatter
            # verbatim (bit-identical round-trip)
            data = dequantize_pages(entry.data, entry.qscales, entry.codec,
                                    np.dtype(np.float32)
                                    if entry.codec != "none"
                                    else entry.data.dtype)
            self.kv.scatter_blocks(blocks, data, entry.scales)
        seq.blocks = list(blocks)
        seq.paused = False
        self.host_kv.pop(uid, promoted=True)
        self._table_sig = None
        return entry.nbytes

    # ------------------------------------------------------------------
    # fleet prefix handoff (drain-time export / adopt-time import)
    # ------------------------------------------------------------------
    def export_prefix_handoff(self, path: str,
                              quantize: str = "none") -> Dict[str, int]:
        """Serialize every cached prefix chain to ``path`` (npz): for each
        root-to-leaf trie chain, the token key plus its KV pages gathered
        from the device and stored through the host-tier codec
        (``quantize``: "none"/"int8"/"fp8" — the same ``quantize_pages``
        path demotion uses; device-fp8 pages are never re-quantized).
        This is a retiring fleet replica's warm-cache handoff: its
        successor adopts the file and the shared prefixes survive the
        retirement instead of being recomputed fleet-wide. A deliberate
        device->host gather — drain-time only, never on the serve tick."""
        self.require_one_page_kind("the prefix handoff (export)")
        self.collect()
        cache = self.prefix_cache
        out = {"chains": 0, "blocks": 0, "stored_bytes": 0, "raw_bytes": 0}
        payload: Dict[str, np.ndarray] = {}
        for tokens, blocks in (cache.chains() if cache is not None else ()):
            data, scales = self.kv.gather_blocks(list(blocks))
            raw = int(data.nbytes) + (int(scales.nbytes)
                                      if scales is not None else 0)
            codec, qscales = "none", None
            if quantize != "none" and self.kv.cfg.dtype != jnp.float8_e4m3fn:
                data, qscales = quantize_pages(data, quantize)
                codec = quantize
            entry = HostKVEntry(blocks=len(blocks), data=data, scales=scales,
                                seen_tokens=len(tokens), codec=codec,
                                qscales=qscales, raw_nbytes=raw)
            i = out["chains"]
            payload[f"tokens_{i}"] = np.asarray(tokens, np.int32)
            payload[f"data_{i}"] = entry.data
            payload[f"codec_{i}"] = np.array(entry.codec)
            if entry.scales is not None:
                payload[f"scales_{i}"] = entry.scales
            if entry.qscales is not None:
                payload[f"qscales_{i}"] = entry.qscales
            out["chains"] += 1
            out["blocks"] += entry.blocks
            out["stored_bytes"] += entry.nbytes
            out["raw_bytes"] += raw
        payload["block_size"] = np.asarray(self.config.kv_block_size,
                                           np.int32)
        payload["num_chains"] = np.asarray(out["chains"], np.int32)
        with open(path, "wb") as f:
            np.savez(f, **payload)
        return out

    def import_prefix_handoff(self, path: str) -> Dict[str, int]:
        """Adopt a predecessor's exported prefix chains: reserve device
        blocks, scatter the dequantized pages, and register each chain in
        the trie as EVICTABLE nodes (refcount 0 — a warm start, not a
        pin: pressure can reclaim them like any flush-absorbed prefix).
        Chains that don't fit the free pool, mismatch the block geometry,
        or collide with incumbents are skipped/trimmed and counted —
        adoption is best-effort by design. Deliberate host->device
        copies — wire it through ``InferenceServer.adopt_prefix_handoff``
        so the serve-loop thread (the engine's owner) runs it between
        ticks."""
        self.require_one_page_kind("the prefix handoff (import)")
        out = {"chains": 0, "blocks": 0, "skipped": 0, "bytes": 0}
        cache = self.prefix_cache
        with np.load(path, allow_pickle=False) as z:
            n = int(z["num_chains"]) if "num_chains" in z else 0
            bs = int(z["block_size"]) if "block_size" in z else -1
            for i in range(n):
                tokens = [int(t) for t in z[f"tokens_{i}"]]
                stored = z[f"data_{i}"]
                codec = str(z[f"codec_{i}"])
                scales = z[f"scales_{i}"] if f"scales_{i}" in z.files else None
                qscales = (z[f"qscales_{i}"] if f"qscales_{i}" in z.files
                           else None)
                nb = self.kv.gathered_blocks(stored)
                if (cache is None or bs != self.config.kv_block_size
                        or nb > self.kv.free_blocks):
                    out["skipped"] += 1
                    continue
                blocks = self.kv.reserve(nb)
                try:
                    data = dequantize_pages(stored, qscales, codec,
                                            np.dtype(np.float32)
                                            if codec != "none"
                                            else stored.dtype)
                    self.kv.scatter_blocks(blocks, data, scales)
                except Exception:
                    # geometry mismatch (different model/layout): give the
                    # reservation back and skip — adoption must never
                    # poison a healthy successor
                    self.kv.release(blocks)
                    out["skipped"] += 1
                    continue
                added = cache.insert_from_seq(0, tokens, blocks,
                                              seen_tokens=len(tokens),
                                              pin=False)
                # chain prefixes the trie already held keep the incumbent
                # pages (first writer wins); unclaimed reservations go
                # straight back to the allocator via the owns() partition
                self.kv.release([b for b in blocks if not cache.owns(b)])
                out["chains"] += 1
                out["blocks"] += added
                out["bytes"] += int(stored.nbytes)
        return out

    def demoted_uids(self) -> List[int]:
        """Demotion-ordered uids currently in the host tier."""
        return self.host_kv.uids()

    def demoted_blocks(self, uid: int) -> int:
        """Device blocks a demoted sequence will need back at promotion."""
        entry = self.host_kv.get(uid)
        return entry.blocks if entry is not None else 0

    def kv_held_blocks(self, uid: int) -> int:
        """Device blocks a sequence holds right now (0 when demoted)."""
        seq = self.state.get(uid)
        return len(seq.blocks) + len(seq.window_blocks) \
            if seq is not None else 0

    def host_kv_bytes(self) -> int:
        return self.host_kv.total_bytes

    def host_kv_compression(self) -> float:
        return self.host_kv.compression_ratio()

    def kv_ledger(self) -> Dict[str, int]:
        """Both tiers' occupancy in one dict — the serving drain test's
        "ledger returns to zero" surface and the bench_serve proof.
        ``device_blocks_reserved`` excludes prefix-cache-held blocks
        (reported separately as ``prefix_cached_blocks``): a drained
        server legitimately keeps a warm cache, and the drain invariant
        is "no SEQUENCE holds blocks", not "the cache is cold"."""
        cached = (self.prefix_cache.cached_blocks()
                  if self.prefix_cache is not None else 0)
        return {
            "device_blocks_reserved": self.kv_reserved_blocks() - cached,
            "device_block_bytes": self.kv_block_bytes(),
            "prefix_cached_blocks": cached,
            "host_entries": len(self.host_kv),
            "host_bytes": self.host_kv.total_bytes,
            "host_raw_bytes": self.host_kv.raw_bytes,
            "demotions": self.host_kv.demotions,
            "promotions": self.host_kv.promotions,
            "demoted_bytes": self.host_kv.demoted_bytes,
            "promoted_bytes": self.host_kv.promoted_bytes,
            "demoted_raw_bytes": self.host_kv.demoted_raw_bytes,
            "host_compression_ratio": self.host_kv.compression_ratio(),
        }

    # ------------------------------------------------------------------
    # prefix cache surface (serving gauges + bench_serve proof set)
    # ------------------------------------------------------------------
    def resident_tokens(self) -> int:
        """Tokens whose KV is resident in EITHER tier right now — the
        denominator of bytes-per-resident-token. Host int arithmetic."""
        total = 0
        for s in self.state.all():
            if not s.paused:
                total += s.seen_tokens
        for u in self.host_kv.uids():
            entry = self.host_kv.get(u)
            if entry is not None:
                total += entry.seen_tokens
        return total

    def kv_resident_bytes(self) -> int:
        """Bytes holding resident KV across both tiers (device blocks at
        block-byte width + host entries at stored width)."""
        return (self.kv_reserved_blocks() * self.kv_block_bytes()
                + self.host_kv.total_bytes)

    def prefix_stats(self) -> Dict[str, float]:
        """Prefix-cache counters + the prefill-work conservation triple:
        ``prefill_tokens_saved + prefill_tokens_computed ==
        prefill_tokens_total`` holds exactly once every admitted
        sequence has either finished prefill or been flushed."""
        out: Dict[str, float] = {
            "prefill_tokens_total": self._prefill_total,
            "prefill_tokens_saved": self._prefill_saved,
            "prefill_tokens_computed": self._prefill_computed,
        }
        if self.prefix_cache is not None:
            for k, v in self.prefix_cache.snapshot().items():
                out[f"prefix_{k}"] = v
            looked = max(self.prefix_cache.stats.lookup_tokens, 1)
            out["prefix_hit_ratio"] = \
                self.prefix_cache.stats.hit_tokens / looked
        return out

    # ------------------------------------------------------------------
    # serving hooks (``engine_contract.ServingEngine`` names all that
    # deepspeed_tpu/serving uses of an engine: the serve loop admits
    # without stepping, steps in its own cadence, and reaps finished
    # sequences between steps)
    # ------------------------------------------------------------------
    def admit(self, uid: int, prompt_tokens: Sequence[int],
              max_new_tokens: Optional[int] = None) -> SequenceDescriptor:
        """Admission-only: create sequence state WITHOUT running a step.
        ``put`` couples admission to stepping; a serving loop needs them
        apart so a burst of arrivals lands in one SplitFuse plan.
        ``max_new_tokens`` is the request's budget: the planner gives the
        sequence no row past it, so a step dispatched before the last
        token's value is read never computes one for nothing."""
        if not self.can_schedule([uid], [len(prompt_tokens)]):
            raise RuntimeError(
                "cannot admit: out of KV blocks or sequence slots")
        seq = self.state.create(uid, prompt_tokens)
        seq.max_new_tokens = max_new_tokens
        self._prefix_admit(seq)
        return seq

    def finish(self, uid: int) -> None:
        """Mark a sequence done (length limit / cancel) so the scheduler
        stops planning it; KV blocks are released at reap time."""
        seq = self.state.get(uid)
        if seq is not None:
            seq.done = True

    def is_done(self, uid: int) -> bool:
        seq = self.state.get(uid)
        return seq is not None and seq.done

    def has_rows_left(self, uid: int) -> bool:
        seq = self.state.get(uid)
        return seq is not None and not seq.done and not seq.budget_spent

    def max_context_length(self) -> int:
        return self.state.max_context_length

    def decode_batch_buckets(self) -> Sequence[int]:
        return self.config.decode_batch_buckets

    def prefill_buckets(self) -> Sequence[int]:
        return self.config.scheduler.prefill_buckets

    def finished_uids(self) -> List[int]:
        """Done sequences with no row in flight: one that ended with a row
        already queued (``eos_token_id`` read a tick late, a cancel) is
        reaped a tick later, once that row's token has been dropped."""
        return [s.uid for s in self.state.all() if s.done and not s.in_flight]

    def reap_finished(self) -> Dict[int, List[int]]:
        """Flush every done sequence (releasing its KV blocks); returns
        {uid: generated_tokens} for the reaped set."""
        return {uid: self.flush(uid) for uid in self.finished_uids()}

    def has_work(self) -> bool:
        """Any sequence the next step plan could advance — demoted (paused)
        sequences don't count until the tier policy promotes them — or
        tokens the next ``step`` has to collect or to hand over."""
        return bool(self._pending or self._ready) or any(
            not s.done and not s.paused and not s.budget_spent
            for s in self.state.all())

    def kv_usable_blocks(self) -> int:
        """Blocks available to sequences (the last block is the permanent
        trash page for padding writes and never allocates)."""
        return self.kv.usable_blocks

    def kv_blocks_needed(self, num_tokens: int) -> int:
        return self.kv.blocks_needed(num_tokens)

    def kv_occupancy(self) -> float:
        """Fraction of usable KV cache blocks currently reserved (0..1)."""
        usable = self.kv_usable_blocks()
        return (usable - self.kv.free_blocks) / max(usable, 1)

    def kv_reserved_blocks(self) -> int:
        """Blocks currently reserved by live sequences — the *observed*
        side of the serving layer's projected-vs-observed reconciliation."""
        return self.kv_usable_blocks() - self.kv.free_blocks

    def kv_block_bytes(self) -> int:
        """Device bytes per KV block across all layers/heads (metadata
        arithmetic on the cache array — never a transfer): the conversion
        the serving gauges use to state occupancy in bytes instead of
        blocks."""
        pool = self.kv.pool
        if self.kv.has_state:
            pool = {k: v for k, v in pool.items() if k != "state"}
        nbytes = sum(int(x.nbytes) for x in jax.tree.leaves(pool))
        return nbytes // max(self.kv.cfg.num_blocks
                             + self.kv.cfg.window_blocks, 1)

    def generate(self, prompt_tokens: Sequence[int], max_new_tokens: int = 32,
                 uid: int = 0) -> List[int]:
        """Convenience serial generation loop over the continuous-batching
        step; with ``speculative_k > 0`` each step verifies a prompt-lookup
        proposal in one chunk forward (1..k+1 tokens/step, exact greedy)."""
        self.put([uid], [list(prompt_tokens)])
        seq = self.state.get(uid)
        while len(seq.generated) < max_new_tokens and not seq.done:
            if self.config.speculative_k > 0 and not seq.in_prefill:
                self._speculative_step(seq)
            else:
                self.step()
        # a fully-accepted verify step can overshoot the budget by up to k
        return self.flush(uid)[:max_new_tokens]

    # ------------------------------------------------------------------
    # speculative decoding (draft-free prompt-lookup; no reference analog)
    # ------------------------------------------------------------------
    def _propose(self, seq: SequenceDescriptor) -> List[int]:
        """Prompt-lookup proposal: the k tokens that followed the previous
        occurrence of the context's trailing n-gram (exact match, most
        recent occurrence wins). Empty when the tail never repeats."""
        k, n = self.config.speculative_k, self.config.speculative_ngram
        ctx = np.concatenate([seq.prompt_tokens,
                              np.asarray(seq.generated, np.int32)])
        if len(ctx) < n + 1:
            return []
        tail = ctx[-n:]
        # vectorized scan over earlier n-gram positions; windows over
        # ctx[:-1] exclude the tail itself, so any hit has a nonempty
        # continuation — take the most recent
        windows = np.lib.stride_tricks.sliding_window_view(ctx[:-1], n)
        hits = np.flatnonzero((windows == tail).all(axis=1))
        if not hits.size:
            return []
        i = int(hits[-1])
        return [int(t) for t in ctx[i + n:i + n + k]]

    def _speculative_step(self, seq: SequenceDescriptor) -> None:
        """Verify [last_token, p1..pk] in one chunk forward: row i's argmax
        predicts position ctx+i, so accept p_{i+1} while it matches, then
        emit the first mismatch's argmax as the bonus/corrected token.
        Rejected rows' stale K/V sits beyond the accepted context (invisible
        under causal masking) and is overwritten by the next step. fp8
        caveat: a rejected row's K/V can still GROW its page's scale
        (monotone until release) — a precision effect on that page, same as
        any outlier write, not a correctness hole."""
        if not self.config.greedy:
            raise ValueError("speculative decoding is greedy-only: "
                             "proposal acceptance compares argmax chains")
        proposed = self._propose(seq)[:31]   # bucket ladder caps rows at 32
        if not proposed:
            # no lookup hit: the 1-row decode path is ~bucket x cheaper than
            # an empty verify chunk
            self.step()
            return
        last = seq.generated[-1] if seq.generated else \
            int(seq.prompt_tokens[-1])
        ctx = seq.total_tokens                    # last's position is ctx-1
        true_len = 1 + len(proposed)
        bucket = snap_bucket(true_len, (8, 16, 32))
        self._ensure_blocks(seq, ctx + true_len)
        mb = self._ctx_bucket_blocks(ctx + true_len)
        tokens = np.zeros((bucket,), np.int32)
        tokens[0] = last
        tokens[1:true_len] = proposed
        logits, self.kv.pool, _ = verify_chunk_g(
            self.params, self.kv.pool, jnp.asarray(tokens), ctx - 1,
            jax.tree.map(jnp.asarray,
                         self._step_tables(seq, mb, ctx - 1, bucket)),
            true_len,
            policy=self.policy, cfg=self.model_config,
            block_size=self.kv.cfg.block_size,
            attn_impl=self.config.attn_impl)
        preds = np.asarray(jnp.argmax(logits[:true_len], axis=-1))
        emitted = []
        for i, p in enumerate(proposed):
            if int(preds[i]) == p:
                emitted.append(p)               # accepted proposal token
            else:
                break
        emitted.append(int(preds[len(emitted)]))  # bonus / corrected token
        appended = 0
        for tok in emitted:
            seq.generated.append(tok)
            appended += 1
            if self.config.eos_token_id is not None and \
                    tok == self.config.eos_token_id:
                seq.done = True
                break
        # count what actually landed (EOS may truncate the step); the last
        # entry of `emitted` is the bonus token, the rest were proposals
        self._spec_proposed += len(proposed)
        self._spec_accepted += min(appended, len(emitted) - 1)
        self._spec_steps += 1
        seq.token_on_device = False               # the host chose these
        seq.seen_tokens = seq.total_tokens - 1    # last emitted has no KV yet
        self._advanced(seq)

    def speculative_stats(self) -> Dict[str, float]:
        """{steps, proposed, accepted, tokens_per_step} over this engine's
        speculative steps (acceptance rate drives the speedup)."""
        return {"steps": self._spec_steps, "proposed": self._spec_proposed,
                "accepted": self._spec_accepted,
                "tokens_per_step": (self._spec_accepted + self._spec_steps)
                / max(self._spec_steps, 1)}
