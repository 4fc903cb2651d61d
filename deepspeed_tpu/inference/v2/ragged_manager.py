"""Sequence state tracking for continuous batching.

Reference analogs: ``deepspeed/inference/v2/ragged/sequence_descriptor.py``
(``DSSequenceDescriptor``) and ``ragged_manager.py:19`` (``DSStateManager``) —
uid-keyed sequence records holding seen-token counts and KV block tables.
"""

import dataclasses
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class SequenceDescriptor:
    uid: int
    prompt_tokens: np.ndarray                 # full prompt (host)
    blocks: List[int] = dataclasses.field(default_factory=list)
    # over a cache that keeps pages by layer kind: the windowed layers' live
    # blocks, the first of them the sequence's block ``window_base`` (those
    # behind were given back as the window moved on)
    window_blocks: List[int] = dataclasses.field(default_factory=list)
    window_base: int = 0
    seen_tokens: int = 0                      # tokens whose KV is in cache
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # demoted to the host KV tier: holds no device blocks, invisible to the
    # step planner until promoted back (engine_v2.demote_kv/promote_kv)
    paused: bool = False
    # tokens a dispatched step program has sampled for this sequence and the
    # host has not read yet (engine_v2 ``_collect`` appends them to
    # ``generated``): lengths count them, values wait for the read
    in_flight: int = 0
    # the request's budget of new tokens where the engine was told it
    # (``admit``): the planner dispatches no row past it. None: unbounded
    max_new_tokens: Optional[int] = None
    # place in the engine's device-resident last-token vector, held from
    # ``create`` to ``pop``; ``token_on_device`` says that the vector has
    # this sequence's last token (a step program sampled it), so the next
    # decode row reads it there and the host uploads nothing
    slot: int = 0
    token_on_device: bool = False

    @property
    def dispatched(self) -> int:
        """New tokens sampled for this sequence, read back or not."""
        return len(self.generated) + self.in_flight

    @property
    def total_tokens(self) -> int:
        return len(self.prompt_tokens) + self.dispatched

    @property
    def budget_spent(self) -> bool:
        return self.max_new_tokens is not None \
            and self.dispatched >= self.max_new_tokens

    @property
    def in_prefill(self) -> bool:
        return self.seen_tokens < len(self.prompt_tokens)

    def remaining_prompt(self) -> np.ndarray:
        return self.prompt_tokens[self.seen_tokens:]


class StateManager:
    """uid -> SequenceDescriptor (reference: DSStateManager ragged_manager.py:19)."""

    def __init__(self, max_tracked_sequences: int = 256,
                 max_context_length: int = 8192):
        self.max_tracked_sequences = max_tracked_sequences
        self.max_context_length = max_context_length
        self._seqs: Dict[int, SequenceDescriptor] = {}
        # slots of the last-token vector not held by a sequence; the one
        # past them all (``max_tracked_sequences``) is batch padding's
        self._free_slots = list(range(max_tracked_sequences - 1, -1, -1))

    def __contains__(self, uid: int) -> bool:
        return uid in self._seqs

    def __len__(self) -> int:
        return len(self._seqs)

    def get(self, uid: int) -> Optional[SequenceDescriptor]:
        return self._seqs.get(uid)

    def create(self, uid: int, prompt_tokens) -> SequenceDescriptor:
        if uid in self._seqs:
            raise ValueError(f"uid {uid} already tracked")
        if len(self._seqs) >= self.max_tracked_sequences:
            raise RuntimeError("max_tracked_sequences exceeded")
        prompt = np.asarray(prompt_tokens, dtype=np.int32)
        if len(prompt) > self.max_context_length:
            raise ValueError(f"prompt length {len(prompt)} > max context "
                             f"{self.max_context_length}")
        seq = SequenceDescriptor(uid=uid, prompt_tokens=prompt,
                                 slot=self._free_slots.pop())
        self._seqs[uid] = seq
        return seq

    def pop(self, uid: int) -> SequenceDescriptor:
        seq = self._seqs.pop(uid)
        self._free_slots.append(seq.slot)
        return seq

    def all(self) -> List[SequenceDescriptor]:
        return list(self._seqs.values())

    def running(self) -> List[SequenceDescriptor]:
        return [s for s in self._seqs.values() if not s.done]

    def decoding(self) -> List[SequenceDescriptor]:
        """Sequences the next step may give a decode row: a budget that is
        spent by what was dispatched ends a sequence for the planner before
        the last token's value is read."""
        return [s for s in self._seqs.values()
                if not s.done and not s.paused and not s.in_prefill
                and not s.budget_spent]

    def prefilling(self) -> List[SequenceDescriptor]:
        return [s for s in self._seqs.values()
                if not s.done and not s.paused and s.in_prefill]
