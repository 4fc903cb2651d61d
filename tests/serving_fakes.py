"""The engine double the serving tests script from: a ``ServingEngine``
(``deepspeed_tpu/inference/v2/engine_contract.py``) that keeps no page and
computes every token inside ``step``. A test file subclasses it and overrides
what it scripts (a step that raises, a reservation the test controls, no work
at all); what it does not provide (the host tier, the prefix cache, a chunk
cap, the handoff) the contract refuses by name.
"""

from typing import Dict, List, Optional, Sequence

from deepspeed_tpu.inference.v2.engine_contract import ServingEngine
from deepspeed_tpu.runtime.sched import TickLedger


class ResidentEngine(ServingEngine):
    """Every resident sequence yields token 7 a step, in admission order,
    until it is finished; a finished one waits for the reap. Nothing is
    ever pending: ``depth`` is ignored and ``collect`` finds nothing."""

    def __init__(self):
        self.sched_ledger = TickLedger()
        self._resident: List[int] = []
        self._finished: List[int] = []
        self.admitted: Dict[int, Optional[int]] = {}   # uid -> budget told

    # --- the loop
    def can_schedule(self, uids: Sequence[int],
                     lengths: Sequence[int]) -> bool:
        return True

    def admit(self, uid: int, prompt_tokens: Sequence[int],
              max_new_tokens: Optional[int] = None):
        self.admitted[uid] = max_new_tokens
        self._resident.append(uid)

    def step(self) -> Dict[int, int]:
        out = {uid: 7 for uid in self._resident}
        self.last_collected_uids = tuple(out)
        return out

    def collect(self) -> None:
        pass

    def finish(self, uid: int) -> None:
        if uid in self._resident:
            self._resident.remove(uid)
            self._finished.append(uid)

    def reap_finished(self) -> List[int]:
        gone, self._finished = self._finished, []
        return gone

    def has_work(self) -> bool:
        return bool(self._resident)

    def is_done(self, uid: int) -> bool:
        return uid in self._finished

    def max_context_length(self) -> int:
        return 512

    def prefix_stats(self) -> Dict[str, float]:
        return {}

    # --- the pool's accounts: 64 blocks, none of which holds a page
    def kv_usable_blocks(self) -> int:
        return 64

    def kv_reserved_blocks(self) -> int:
        return 0

    def kv_occupancy(self) -> float:
        return self.kv_reserved_blocks() / self.kv_usable_blocks()

    def kv_block_bytes(self) -> int:
        return 0

    def kv_blocks_needed(self, num_tokens: int) -> int:
        return 1

    def resident_tokens(self) -> int:
        return 0

    def kv_resident_bytes(self) -> int:
        return 0
