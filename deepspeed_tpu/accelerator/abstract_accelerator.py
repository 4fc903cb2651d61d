"""Abstract accelerator interface.

TPU-native analog of the reference's hardware-abstraction layer
(``accelerator/abstract_accelerator.py:10`` ``DeepSpeedAccelerator`` ABC). Where the
reference abstracts over CUDA/HPU/XPU device runtimes for an eager framework, here the
abstraction is over **JAX platforms** (tpu / cpu / gpu): device enumeration, memory
introspection, dtype support, collective-backend name, and profiler hooks. Streams,
events and per-op allocators do not exist in the XLA execution model — XLA owns
scheduling and memory — so those reference methods map onto async-dispatch /
``block_until_ready`` semantics.
"""

import abc
from typing import Any, List


class Accelerator(abc.ABC):
    """Platform abstraction consumed by every other layer (cf. get_accelerator())."""

    _name: str = "abstract"

    @property
    def name(self) -> str:
        return self._name

    # --- device management -------------------------------------------------
    @abc.abstractmethod
    def devices(self) -> List[Any]:
        """All addressable devices for this process."""

    @abc.abstractmethod
    def device_count(self) -> int:
        ...

    def global_device_count(self) -> int:
        import jax
        return jax.device_count()

    def process_index(self) -> int:
        import jax
        return jax.process_index()

    def process_count(self) -> int:
        import jax
        return jax.process_count()

    @abc.abstractmethod
    def communication_backend_name(self) -> str:
        """Name of the collective fabric ('ici+dcn' on TPU, 'xla-cpu' on CPU)."""

    # --- synchronization ---------------------------------------------------
    def synchronize(self) -> None:
        """Drain the async dispatch queue (the XLA analog of cudaDeviceSynchronize)."""
        import jax
        import jax.numpy as jnp
        jax.block_until_ready(jnp.zeros(()))

    # --- memory ------------------------------------------------------------
    def memory_stats(self) -> dict:
        """Best-effort live/peak bytes per device (reference: memory_allocated etc.)."""
        stats = {}
        for d in self.devices():
            try:
                s = d.memory_stats()
            except Exception:
                s = None
            if s:
                stats[str(d)] = {
                    "bytes_in_use": s.get("bytes_in_use", 0),
                    "peak_bytes_in_use": s.get("peak_bytes_in_use", 0),
                    "bytes_limit": s.get("bytes_limit", 0),
                }
        return stats

    def total_memory(self) -> int:
        total = 0
        for s in self.memory_stats().values():
            total += s.get("bytes_limit", 0)
        return total

    # --- dtype support -----------------------------------------------------
    def is_bf16_supported(self) -> bool:
        return True

    def is_fp16_supported(self) -> bool:
        return True

    def preferred_dtype(self):
        import jax.numpy as jnp
        return jnp.bfloat16

    # --- profiler / tracing ------------------------------------------------
    def range_push(self, name: str):
        """Named trace annotation (reference: nvtx range_push): a span of
        the dstrace tracer (``utils.nvtx.annotate``), on the ring and in
        the profiler's trace while tracing is on."""
        from deepspeed_tpu.utils.nvtx import annotate
        return annotate(name)

    # --- op-builder dir (kept for API parity; see deepspeed_tpu.ops) -------
    def op_builder_dir(self) -> str:
        return "deepspeed_tpu.ops"

    # --- flops -------------------------------------------------------------
    def peak_tflops(self, dtype: str = "bf16") -> float:
        """Published peak TFLOP/s of one chip, for utilization math."""
        raise ValueError(f"the {self._name} accelerator has no published peak")
