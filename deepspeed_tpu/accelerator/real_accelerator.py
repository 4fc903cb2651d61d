"""Accelerator auto-detection.

Reference analog: ``accelerator/real_accelerator.py:51`` (env override
``DS_ACCELERATOR`` + probe-based detection). Here detection is by JAX platform;
override with ``DSTPU_ACCELERATOR=cpu|tpu``.
"""

import os
from typing import Optional

from deepspeed_tpu.accelerator.abstract_accelerator import Accelerator

_accelerator: Optional[Accelerator] = None


def _detect() -> Accelerator:
    from deepspeed_tpu.accelerator.cpu_accelerator import CPUAccelerator
    from deepspeed_tpu.accelerator.tpu_accelerator import TPUAccelerator

    override = os.environ.get("DSTPU_ACCELERATOR", "").lower()
    if override == "cpu":
        return CPUAccelerator()
    if override == "tpu":
        return TPUAccelerator()

    import jax
    # no guessing: a probe that fails, or a platform this package has no
    # accelerator for, is an error — never a silent CPU or TPU stand-in
    platform = jax.local_devices()[0].platform
    if platform == "tpu":
        return TPUAccelerator()
    if platform == "cpu":
        return CPUAccelerator()
    raise RuntimeError(
        f"no accelerator for jax platform {platform!r} (tpu | cpu); set "
        "DSTPU_ACCELERATOR to force one")


def require_tpu(who: str) -> list:
    """For scripts that measure or prove something about the chip: jax's
    devices when they are TPUs, otherwise one line and a nonzero exit. A run
    that finds no chip fails; it never falls back to the CPU."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:             # no backend could be initialised
        raise SystemExit(f"{who}: no TPU: {str(e).splitlines()[0]}")
    if devices[0].platform != "tpu":
        raise SystemExit(f"{who}: no TPU: jax found platform "
                         f"{devices[0].platform!r}, and this script never "
                         "runs on the CPU")
    return devices


def get_accelerator() -> Accelerator:
    global _accelerator
    if _accelerator is None:
        _accelerator = _detect()
    return _accelerator


def set_accelerator(acc: Accelerator) -> None:
    global _accelerator
    _accelerator = acc
