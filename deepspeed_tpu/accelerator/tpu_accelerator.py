"""TPU and CPU accelerator implementations.

The TPU accelerator fills the seam the reference leaves for new hardware
(accelerator/real_accelerator.py:52-120 auto-detect; cuda_accelerator.py as the
template implementation). Memory stats come from
``jax.Device.memory_stats()`` (HBM allocator counters).
"""

import os
from typing import Dict, Optional, Tuple

from .abstract_accelerator import DeepSpeedAccelerator

# XLA knobs that enable compute/collective overlap on the TPU backend:
# the latency-hiding scheduler plus async collective fusion for BOTH sides
# of the ZeRO exchange (param all-gathers and the bucketed gradient
# reduce-scatter/all-reduce, runtime/grad_overlap.py). These are libtpu
# flags — this jaxlib's XLA_FLAGS parser rejects them as unknown and would
# abort CPU runs — so they ride LIBTPU_INIT_ARGS, which only the TPU
# runtime reads (README perf methodology).
COLLECTIVE_OVERLAP_XLA_FLAGS: Tuple[str, ...] = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    # reduce-scatter chaining is OFF by default in the TPU backend; the
    # bucketed gradient program (runtime/grad_overlap.py) emits its
    # reduction as native reduce-scatters precisely so this flag can float
    # them into the backward
    "--xla_tpu_enable_async_collective_fusion_fuse_reduce_scatter=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
)

# the same knobs as per-compile options (jax AOT `.compile(compiler_options=...)`
# on a topology description — LIBTPU_INIT_ARGS is not consulted there)
COLLECTIVE_OVERLAP_COMPILER_OPTIONS: Dict[str, str] = {
    f.lstrip("-").split("=", 1)[0]: f.split("=", 1)[1]
    for f in COLLECTIVE_OVERLAP_XLA_FLAGS
}


# bf16 peak matmul FLOPS per chip by device_kind substring — the MFU
# denominator for serving_bench and the flops profiler (utilization =
# achieved flops/s over this peak). Sources: Google Cloud TPU
# documentation, per-chip bf16 peaks of "TPU v5e", "TPU v5p", "TPU v4".
PEAK_FLOPS_BY_KIND: Dict[str, float] = {
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5": 459e12,        # v5p
    "TPU v4": 275e12,
}


def peak_flops(device) -> float:
    """Peak bf16 FLOPS of ``device`` (a jax.Device), by device_kind
    substring. A device that is not in the table is an error, not a
    default: a utilization against a made-up peak is not a number."""
    kind = str(getattr(device, "device_kind", None))
    for key, val in PEAK_FLOPS_BY_KIND.items():
        if key.lower() in kind.lower():
            return val
    raise ValueError(
        f"no peak-FLOPS entry for device_kind {kind!r}; known kinds: "
        f"{sorted(PEAK_FLOPS_BY_KIND)} (add the chip, with its source, to "
        f"PEAK_FLOPS_BY_KIND)")


def require_tpu(min_devices: int = 1):
    """The TPU devices JAX sees, or an error naming what it saw instead.

    For programs whose output is a statement about the chip
    (chip_smoke.py, scripts/): no TPU is a failure, never a CPU fallback.
    Runs in-process — the caller becomes the one process that holds the
    chip, so it must not start a child that needs it."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"this program needs a TPU and JAX found none: "
            f"jax.devices()[0].platform == {platform!r} "
            f"(device_kind {devices[0].device_kind!r}, JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r})")
    if len(devices) < min_devices:
        raise RuntimeError(
            f"this program needs {min_devices} TPU devices and JAX found "
            f"{len(devices)} ({devices[0].device_kind})")
    return devices


def collective_overlap_init_args(existing: str = "") -> str:
    """Merge the overlap flags into a LIBTPU_INIT_ARGS string, keeping any
    flag the caller already pinned (their value wins over our default).
    Matching is by exact flag NAME token — substring matching would let a
    pinned longer flag (e.g. ..._fusion_fuse_reduce_scatter) silently
    suppress a shorter default (..._fusion)."""
    merged = existing.strip()
    present = {tok.split("=", 1)[0].lstrip("-")
               for tok in merged.split() if tok.startswith("-")}
    for flag in COLLECTIVE_OVERLAP_XLA_FLAGS:
        name = flag.split("=", 1)[0].lstrip("-")
        if name not in present:
            merged = f"{merged} {flag}".strip()
    return merged


def apply_collective_overlap_flags(env=None) -> str:
    """Export the overlap flags via LIBTPU_INIT_ARGS (idempotent). Must run
    before the TPU runtime initializes to take effect for this process; a
    later call still updates the env for spawned workers."""
    env = os.environ if env is None else env
    merged = collective_overlap_init_args(env.get("LIBTPU_INIT_ARGS", ""))
    env["LIBTPU_INIT_ARGS"] = merged
    return merged


class TpuAccelerator(DeepSpeedAccelerator):
    def __init__(self):
        super().__init__()
        self._name = "tpu"
        self._communication_backend_name = "xla"

    def device_name(self, device_index: Optional[int] = None) -> str:
        if device_index is None:
            return "tpu"
        return f"tpu:{device_index}"

    def device(self, device_index: Optional[int] = None):
        import jax

        devs = jax.devices("tpu")
        return devs[device_index or 0]

    def device_count(self) -> int:
        import jax

        try:
            return len(jax.devices("tpu"))
        except RuntimeError:
            return 0

    def is_available(self) -> bool:
        return self.device_count() > 0

    def memory_stats(self, device_index: Optional[int] = None) -> Dict[str, int]:
        stats = self.device(device_index).memory_stats()
        return dict(stats or {})

    def op_builder_dir(self) -> str:
        return "deepspeed_tpu.ops.op_builder.tpu"

    def apply_collective_overlap_flags(self, env=None) -> str:
        """See module-level :func:`apply_collective_overlap_flags`."""
        return apply_collective_overlap_flags(env)


class CpuAccelerator(DeepSpeedAccelerator):
    """CPU fallback (reference cpu_accelerator.py) — used for tests and for
    host-side work (offloaded optimizers run here via the native cpu_adam)."""

    def __init__(self):
        super().__init__()
        self._name = "cpu"
        self._communication_backend_name = "gloo"

    def device_name(self, device_index: Optional[int] = None) -> str:
        return "cpu"

    def device(self, device_index: Optional[int] = None):
        import jax

        return jax.devices("cpu")[device_index or 0]

    def device_count(self) -> int:
        import jax

        return len(jax.devices("cpu"))

    def is_available(self) -> bool:
        return True

    def memory_stats(self, device_index: Optional[int] = None) -> Dict[str, int]:
        try:
            with open("/proc/meminfo") as f:
                info = {}
                for line in f:
                    parts = line.split()
                    info[parts[0].rstrip(":")] = int(parts[1]) * 1024
            total = info.get("MemTotal", 0)
            avail = info.get("MemAvailable", 0)
            return {"bytes_limit": total, "bytes_in_use": total - avail,
                    "peak_bytes_in_use": total - avail}
        except OSError:
            return {}

    def is_fp16_supported(self) -> bool:
        return False  # matches reference cpu_accelerator (bf16 only on host)

    def op_builder_dir(self) -> str:
        return "deepspeed_tpu.ops.op_builder.cpu"
