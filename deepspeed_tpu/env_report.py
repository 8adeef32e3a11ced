"""Environment & op-compatibility report (reference deepspeed/env_report.py +
bin/ds_report): prints versions, device inventory, and which op builders are
compatible/buildable on this machine. CLI: ``python -m deepspeed_tpu.env_report``."""

import importlib
import sys

GREEN_OK = "[OKAY]"
RED_NO = "[NO]"


def _try_version(mod):
    try:
        m = importlib.import_module(mod)
        return getattr(m, "__version__", "unknown")
    except ImportError:
        return None


def op_report(verbose: bool = False):
    """Rows of (op_name, kind, compatible) for every registered builder
    (reference env_report.py op_report)."""
    rows = []
    from .ops.op_builder.tpu import ALL_OPS as TPU_OPS
    from .ops.op_builder.cpu import ALL_OPS as CPU_OPS

    for name, builder_cls in sorted(TPU_OPS.items()):
        rows.append((name, "pallas/xla", builder_cls().builder_available()))
    for name, builder_cls in sorted(CPU_OPS.items()):
        rows.append((name, "host C++", builder_cls().builder_available()))
    return rows


def software_report():
    rows = [("python", sys.version.split()[0])]
    for mod in ("jax", "jaxlib", "libtpu", "flax", "optax", "numpy",
                "ml_dtypes"):
        v = _try_version(mod)
        rows.append((mod, v or "not installed"))
    from . import __version__ as ds_version
    rows.append(("deepspeed_tpu", ds_version))
    return rows


def compiler_fingerprint():
    """The exact compiler configuration a perf artifact ran under:
    jax/jaxlib/libtpu versions plus the RESOLVED ``LIBTPU_INIT_ARGS``
    (the env merged with the collective-overlap defaults
    ``apply_collective_overlap_flags`` would export) and the overlap
    flag list itself. A measured number without this dict is not
    attributable to a compiler."""
    import os

    from .accelerator.tpu_accelerator import (
        COLLECTIVE_OVERLAP_XLA_FLAGS, collective_overlap_init_args)
    return {
        "jax": _try_version("jax"),
        "jaxlib": _try_version("jaxlib"),
        "libtpu": _try_version("libtpu"),
        "libtpu_init_args_env": os.environ.get("LIBTPU_INIT_ARGS", ""),
        "libtpu_init_args_resolved": collective_overlap_init_args(
            os.environ.get("LIBTPU_INIT_ARGS", "")),
        "collective_overlap_flags": list(COLLECTIVE_OVERLAP_XLA_FLAGS),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
    }


def compiler_config_report():
    """compiler_fingerprint() as printable rows (ds_report section)."""
    fp = compiler_fingerprint()
    return [
        ("libtpu", fp["libtpu"] or "not installed"),
        ("LIBTPU_INIT_ARGS", fp["libtpu_init_args_env"] or "(unset)"),
        ("resolved overlap args", fp["libtpu_init_args_resolved"]),
        ("XLA_FLAGS", fp["xla_flags"] or "(unset)"),
    ]


def hardware_report():
    """Device inventory, in-process: this process becomes the one that
    holds the chip while it lives, which is what a report about the chip
    should be (a probe child would be refused the chip by a parent that
    already held it)."""
    import jax

    devices = jax.devices()
    return [("backend", jax.default_backend()),
            ("device count", str(len(devices))),
            ("device kind", str(devices[0].device_kind)),
            ("process count", str(jax.process_count()))]


def main(hide_operator_status=False, hide_errors_and_warnings=False):
    print("-" * 60)
    print("deepspeed_tpu environment report (ds_report)")
    print("-" * 60)
    print("software:")
    for k, v in software_report():
        print(f"  {k:>16}: {v}")
    print("hardware:")
    for k, v in hardware_report():
        print(f"  {k:>16}: {v}")
    print("compiler configuration:")
    for k, v in compiler_config_report():
        print(f"  {k:>22}: {v}")
    if not hide_operator_status:
        print("op compatibility:")
        for name, kind, ok in op_report():
            print(f"  {name:>20} [{kind:>9}] {GREEN_OK if ok else RED_NO}")
    print("-" * 60)
    return 0


if __name__ == "__main__":
    sys.exit(main())
