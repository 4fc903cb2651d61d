from deepspeed_tpu.accelerator.abstract_accelerator import Accelerator
from deepspeed_tpu.accelerator.real_accelerator import (get_accelerator,
                                                        require_tpu,
                                                        set_accelerator)

__all__ = ["Accelerator", "get_accelerator", "require_tpu", "set_accelerator"]
