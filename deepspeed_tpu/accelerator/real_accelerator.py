"""Accelerator selection.

Reference parity: ``get_accelerator()`` singleton with env override +
import-probe auto-detect (accelerator/real_accelerator.py:45,52-120).
Env override: ``DS_ACCELERATOR=tpu|cpu`` (same variable name as the
reference so launch scripts carry over).
"""

import os
from typing import Optional

from .abstract_accelerator import DeepSpeedAccelerator

_accelerator: Optional[DeepSpeedAccelerator] = None

SUPPORTED = ("tpu", "cpu")


def _detect() -> str:
    override = os.environ.get("DS_ACCELERATOR")
    if override:
        if override not in SUPPORTED:
            raise ValueError(
                f"DS_ACCELERATOR={override!r} not in {SUPPORTED}")
        return override
    import jax

    return "tpu" if jax.default_backend() == "tpu" else "cpu"


def get_accelerator() -> DeepSpeedAccelerator:
    global _accelerator
    if _accelerator is None:
        name = _detect()
        if name == "tpu":
            from .tpu_accelerator import TpuAccelerator

            _accelerator = TpuAccelerator()
        else:
            from .tpu_accelerator import CpuAccelerator

            _accelerator = CpuAccelerator()
    return _accelerator


def set_accelerator(accel: DeepSpeedAccelerator) -> None:
    global _accelerator
    _accelerator = accel


def is_current_accelerator_supported() -> bool:
    return get_accelerator()._name in SUPPORTED
