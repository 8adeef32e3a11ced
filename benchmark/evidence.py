"""What a run hands around: the ``Context`` a runner is given, the
``Result`` it returns, and the ``Evidence`` the per-layer readers read.
"""

import shutil
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

from . import tracing

# the program's span names the reduction knows: mirrored into the
# profiler's trace by trace.enable_xla_annotations(True)
PROGRAM_SPANS = ("train_step", "train_data", "train_device_dispatch",
                 "train_host_sync")


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


@dataclass
class Context:
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

    @property
    def fields(self):
        return self.config["fields"]

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
        planes = tracing.device_planes(self.events)
        gaps = tracing.program_gaps(self.events, planes[0])
        return {
            "device_ops": [[n, s] for n, s in tracing.top_ops(self.events)],
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
