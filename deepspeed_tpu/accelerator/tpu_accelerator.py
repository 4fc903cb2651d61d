"""TPU accelerator (the first-class platform).

Reference analog: ``accelerator/cuda_accelerator.py``. Peak-TFLOPS table is used for
MFU reporting by the throughput timer / flops profiler.
"""

from typing import Any, List

from deepspeed_tpu.accelerator.abstract_accelerator import Accelerator

# Peak dense TFLOP/s (TOP/s for int8) of one chip, keyed by the device_kind
# jax reports. Source: Google Cloud TPU documentation, the "System
# architecture" page of each generation (v4: 275 bf16; v5e: 197 bf16 / 393
# int8; v5p: 459 bf16 / 918 int8; v6e: 918 bf16 / 1836 int8). A kind or dtype
# that is not here is an error, not a default.
_PEAK_TFLOPS = {
    "TPU v4": {"bf16": 275.0},
    "TPU v5 lite": {"bf16": 197.0, "int8": 393.0},      # v5e
    "TPU v5": {"bf16": 459.0, "int8": 918.0},           # v5p
    "TPU v6 lite": {"bf16": 918.0, "int8": 1836.0},     # v6e (Trillium)
}


class TPUAccelerator(Accelerator):
    _name = "tpu"

    def devices(self) -> List[Any]:
        import jax
        return jax.local_devices()

    def device_count(self) -> int:
        return len(self.devices())

    def communication_backend_name(self) -> str:
        return "ici+dcn"

    def peak_tflops(self, dtype: str = "bf16") -> float:
        kind = self.devices()[0].device_kind
        try:
            return _PEAK_TFLOPS[kind][dtype]
        except KeyError:
            raise ValueError(
                f"no published peak for device_kind {kind!r} in {dtype!r} "
                f"(table: {_PEAK_TFLOPS})") from None
