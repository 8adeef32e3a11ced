"""What a run hands around: the ``Context`` a runner is given, the
``Result`` it returns, and the ``Evidence`` the per-layer readers read.
"""

import functools
import importlib
import shutil
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

from . import tracing

# the program's span names the reduction knows: mirrored into the
# profiler's trace by trace.enable_xla_annotations(True)
PROGRAM_SPANS = ("train_step", "train_data", "train_device_dispatch",
                 "train_host_sync", "train_bookkeeping")
KERNEL_MARK = ":tpu_custom_call"


def scope_map():
    """``{instruction name: op_name}`` of the step that ran
    (``deepspeed_tpu.telemetry.memory.scopes("train_step")``: the
    ``jax.named_scope`` and autodiff path each instruction was traced
    under), and the program's classifier of an ``op_name`` into a phase
    (``deepspeed_tpu.utils.xla_profile.scope_phase``); (None, None)
    where the program offers none. The v5e's op events carry no
    ``op_name`` of their own, so this join is where a scope comes from."""
    try:
        from deepspeed_tpu.telemetry import memory
        from deepspeed_tpu.utils.xla_profile import scope_phase
    except ImportError:
        return None, None
    scopes = getattr(memory, "scopes", None)
    return (scopes("train_step") if scopes is not None else None), scope_phase


def instruction(name):
    """The instruction's name as the scope map has it: without the mark
    ``tracing.short_name`` puts after a Mosaic kernel's."""
    return name[:-len(KERNEL_MARK)] if name.endswith(KERNEL_MARK) else name


def program_bytes(compiled):
    """Bytes one chip needs to run ``compiled`` (a jax ``Compiled``), by
    the compiler's own ``memory_analysis()``: arguments + temporaries +
    outputs - what the outputs alias of the arguments + generated code.

    This one figure, from this one source, is the run's
    ``memory_peak_bytes``. The allocator's ``peak_bytes_in_use`` is not
    used: it counts live buffers and leaves a program's temporaries out
    (a program that held a 2 GiB temporary left it at 134 MB; my chip
    run, PR 24), and what it does count includes the benchmark's own
    float32 reference check, which is no part of the system under test.
    The two may not be added either: their peaks need not coincide."""
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.temp_size_in_bytes
               + m.output_size_in_bytes - m.alias_size_in_bytes
               + m.generated_code_size_in_bytes)


# What a configuration's modules must offer, whichever block they are
# of. A configuration's file names them under ``"reference"`` and
# ``"weights"``: modules under ``benchmark/``, found by name as runners
# and readers are (absent: ``reference`` and ``weights``, the OPT
# block's).
#
# * ``reference.logits(params, fields, ids) -> [S, vocab]`` float32 of
#   one sequence ``ids`` [S], every matmul under
#   ``jax.default_matmul_precision("highest")``;
#   ``reference.next_token_loss(params, fields, ids) -> float``, the mean
#   next-token cross-entropy of that sequence;
#   ``reference.check_supported(fields)`` raises where ``fields``
#   describe another block than the module implements. It imports
#   nothing of the program and reads ``params`` in the program's layout.
# * ``weights.make(fields, seed[, dtype])``: the whole tree in the
#   program's layout, on the device, in one jitted call of which the seed
#   (any whole number, over 2**31 too) is an argument; ``dtype`` left out
#   is the type the configuration is served in.
CONTRACT = {"reference": ("logits", "next_token_loss", "check_supported"),
            "weights": ("make",)}


def config_module(config, key, config_name):
    """The module that configuration ``config_name``'s file ``config``
    names under ``key`` (``"reference"`` or ``"weights"``), held to
    ``CONTRACT``. A module that is not there, or lacks what a runner
    calls, is an error naming the configuration: there is no falling
    back to another block's."""
    name = config.get(key, key)
    try:
        module = importlib.import_module(f"benchmark.{name}")
    except ImportError as e:
        raise SystemExit(
            f"benchmark: configuration {config_name} names {key} "
            f"{name!r} and benchmark/{name.replace('.', '/')}.py does "
            f"not import ({e})")
    lacks = [f for f in CONTRACT[key] if not callable(
        getattr(module, f, None))]
    if lacks:
        raise SystemExit(
            f"benchmark: configuration {config_name}'s {key} module "
            f"benchmark.{name} lacks {', '.join(lacks)} "
            f"(benchmark/evidence.py says what it must offer)")
    return module


@dataclass
class Context:
    """What a runner is given. ``config`` is the configuration's file:
    ``fields`` (the ``TransformerConfig`` it runs as; at ``--rehearse``
    with the file's ``toy_fields`` laid on), and by name the modules
    that hold its block: ``reference`` and ``weights`` (``CONTRACT``),
    which a runner reaches through ``ctx.reference`` and
    ``ctx.weights`` and imports neither by name."""
    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    devices: list
    clock: Any                      # run.CompileClock
    t_process_start: float
    log: Callable[[str], None]
    scratch: Path
    # where set-up's seconds go, by part, in order: printed with the
    # run's detail and judged by nothing
    setup_parts: Dict[str, float] = field(default_factory=dict)

    def __post_init__(self):
        self._part_from = self.t_process_start

    def part(self, name, at=None):
        """Close a part of set-up: it took the seconds since the last one
        closed, or since the process started."""
        at = at or time.perf_counter()
        self.setup_parts[name] = at - self._part_from
        self._part_from = at

    @property
    def fields(self):
        return self.config["fields"]

    @functools.cached_property
    def reference(self):
        """The configuration's plain reference, which has to describe
        the fields it is asked about."""
        module = config_module(self.config, "reference", self.cell["config"])
        module.check_supported(self.fields)
        return module

    @functools.cached_property
    def weights(self):
        return config_module(self.config, "weights", self.cell["config"])

    def model_config(self):
        """``TransformerConfig(**fields)``: a later model of the same
        block is a configuration file and no code."""
        from deepspeed_tpu.models.transformer import TransformerConfig
        return TransformerConfig(**self.fields)

    def setup_seconds(self, now=None):
        return (now or time.perf_counter()) - self.t_process_start


@dataclass
class Evidence:
    """Everything a per-layer reader may read. Built in every run; the
    trace events are there only when ``--trace 1`` took a slice."""
    ctx: Context
    events: List[tracing.Event] = field(default_factory=list)
    compiles_in_window: Optional[int] = None
    slice_steps: int = 0                     # training steps traced
    tokens_per_step: int = 0
    step_tok_s: Optional[float] = None       # of the median step, a chip
    # seconds of each step that finished inside the window, in order
    step_seconds: List[float] = field(default_factory=list)
    # what the cell's largest program needs on one chip (program_bytes)
    memory_peak_bytes: Optional[int] = None

    def host_spans(self):
        return [e for e in self.events if e.plane.startswith("/host:")
                and e.name in PROGRAM_SPANS]

    def busy_and_window(self):
        if not self.events:
            raise RuntimeError("no trace was taken in this run")
        return tracing.busy_and_window(self.events)

    def breakdown(self):
        """The ledger's ``device_ops`` and ``idle_gaps``. An operation's
        name is followed by its phase and the last two scopes of its
        ``op_name`` (``fusion.492 backward:mlp/dot_general``), so that
        the line says what a fusion is; the name alone where the program
        offers no map or the map lacks it."""
        planes = tracing.device_planes(self.events)
        gaps = tracing.program_gaps(self.events, planes[0])
        mapped, scope_phase = scope_map()

        def label(name):
            op_name = (mapped or {}).get(instruction(name))
            if not op_name:
                return name
            tail = "/".join(op_name.split("/")[-2:])
            return f"{name} {scope_phase(op_name)}:{tail}"[:96]

        return {
            "device_ops": [[label(n), s]
                           for n, s in tracing.top_ops(self.events)],
            "idle_gaps": [[n, s] for n, s in tracing.attribute_gaps(
                gaps, self.host_spans())]}


@dataclass
class Result:
    attempted: int
    failed: int
    correct: bool
    correct_detail: Dict[str, Any]
    end_to_end: Dict[str, float]
    evidence: Evidence


class TraceSlice:
    """The profiler over a short slice of the window, host tracer on (the
    program's spans ride it as TraceAnnotations), Python tracer off (it
    records every call and drowns the rest)."""

    def __init__(self, ctx: Context):
        self.dir = ctx.scratch / "trace"

    def start(self):
        import jax
        from deepspeed_tpu.telemetry import trace
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True, exist_ok=True)
        trace.enable_xla_annotations(True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)

    def stop(self):
        import jax
        from deepspeed_tpu.telemetry import trace
        jax.profiler.stop_trace()
        trace.enable_xla_annotations(False)

    def events(self):
        """Neutral events of the slice; removes the raw trace."""
        path = tracing.newest_xplane(str(self.dir))
        events = tracing.load_events(path, keep_host_line=PROGRAM_SPANS)
        shutil.rmtree(self.dir, ignore_errors=True)
        return events
