"""Backend-agnostic communication API.

TPU-native analogue of the reference's ``deepspeed/comm/comm.py`` (init_distributed
:604, all_reduce :483, all_gather_into_tensor :297, reduce_scatter_tensor :280,
all_to_all_single :331, barrier :406, timed_op :101). Two faces:

1. **Process bootstrap / host-level ops** — `init_distributed()` wires
   `jax.distributed.initialize` (the rendezvous the reference delegates to
   torch.distributed/NCCL, comm/torch.py:144). Rank/world come from JAX's
   process + device model.

2. **In-graph collectives** — the hot path. Collectives are expressed over a
   *mesh axis name* and lowered by XLA onto ICI/DCN (`psum`, `all_gather`,
   `psum_scatter`, `all_to_all`, `ppermute`). These are the functions parallel
   layers call inside `shard_map`; a "process group" is a mesh axis, matching
   §2.4 of SURVEY.md.

Every op routes through `timed_op` feeding the CommsLogger (reference
comm/comm.py:101) when logging is configured.
"""

import functools
import os
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.logging import logger

_INITIALIZED = False
_comms_logger = None


# ---------------------------------------------------------------------------
# Process bootstrap (host level)
# ---------------------------------------------------------------------------

def init_distributed(dist_backend: str = "xla",
                     auto_mpi_discovery: bool = True,
                     distributed_port: int = 29500,
                     verbose: bool = True,
                     timeout=None,
                     init_method: Optional[str] = None,
                     dist_init_required: Optional[bool] = None,
                     config=None,
                     rank: int = -1,
                     world_size: int = -1) -> None:
    """Initialize multi-process JAX if a multi-host environment is detected.

    Single-process (possibly multi-device) runs need no rendezvous — JAX already
    sees all local devices. Multi-host TPU pods set the coordinator env vars
    (or we derive them the way the reference's mpi_discovery does,
    comm/comm.py:673).
    """
    global _INITIALIZED
    if _INITIALIZED:
        return
    # env protocols, in precedence order: our launcher (DS_TPU_*), jax-native
    # (COORDINATOR_ADDRESS), torch-style (MASTER_ADDR/RANK — the reference's)
    coord = (os.environ.get("DS_TPU_COORDINATOR")
             or os.environ.get("COORDINATOR_ADDRESS")
             or (f"{os.environ['MASTER_ADDR']}:"
                 f"{os.environ.get('MASTER_PORT', distributed_port)}"
                 if "MASTER_ADDR" in os.environ and "RANK" in os.environ
                 else None))
    if coord is not None:
        nproc = world_size if world_size > 0 else int(
            os.environ.get("DS_TPU_NUM_PROCESSES",
                           os.environ.get("WORLD_SIZE", 1)))
        pid = rank if rank >= 0 else int(
            os.environ.get("DS_TPU_PROCESS_ID", os.environ.get("RANK", 0)))
        if nproc > 1:
            jax.distributed.initialize(coordinator_address=coord,
                                       num_processes=nproc, process_id=pid)
            if verbose:
                logger.info(
                    f"jax.distributed initialized: process {pid}/{nproc} @ {coord}")
    _INITIALIZED = True


def is_initialized() -> bool:
    return _INITIALIZED


def get_rank(group=None) -> int:
    return jax.process_index()


def get_world_size(group=None) -> int:
    return jax.process_count()


def get_local_rank() -> int:
    return int(os.environ.get("LOCAL_RANK", 0))


def get_device_count() -> int:
    return jax.device_count()


def barrier(group=None):
    """Host-level barrier: a tiny psum across all devices, blocked on."""
    x = jnp.ones((jax.device_count(),))
    from jax.sharding import PartitionSpec as P, NamedSharding
    import numpy as np
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("x",))
    y = jax.jit(lambda a: jnp.sum(a), in_shardings=NamedSharding(mesh, P("x")),
                out_shardings=NamedSharding(mesh, P()))(x)
    jax.block_until_ready(y)


# ---------------------------------------------------------------------------
# Comms logging (reference utils/comms_logging.py + comm.py:101 timed_op)
# ---------------------------------------------------------------------------

def configure(comms_config=None, enabled=None, prof_all=None, prof_ops=None,
              verbose=None, debug=None):
    global _comms_logger
    from ..utils.comms_logging import CommsLogger

    if comms_config is not None:
        cl = comms_config.comms_logger if hasattr(comms_config, "comms_logger") else comms_config
        if getattr(cl, "enabled", False):
            _comms_logger = CommsLogger(verbose=cl.verbose, debug=cl.debug,
                                        prof_all=cl.prof_all, prof_ops=list(cl.prof_ops))
        else:   # re-applying a config with logging off disables it
            _comms_logger = None
    elif enabled:
        _comms_logger = CommsLogger(verbose=bool(verbose), debug=bool(debug),
                                    prof_all=prof_all is not False,
                                    prof_ops=list(prof_ops or []))
    elif enabled is False:   # explicit disable (None = leave unchanged)
        _comms_logger = None


def get_comms_logger():
    return _comms_logger


def log_summary(show_straggler: bool = False):
    if _comms_logger is not None:
        _comms_logger.log_summary(show_straggler=show_straggler)


def timed_op(fn):
    """Wrap an in-graph collective for logging (reference comm/comm.py:101).

    In eager/interpret mode the wall-clock latency is real. Under jit the op
    is traced once and `block_until_ready` is a no-op on tracers, so the
    recorded time is *trace time*, not execution time — such records are
    flagged and the summary marks them ``[trace]``; real per-op device
    timings come from ``jax.profiler`` (see utils/xla_profile.py)."""

    @functools.wraps(fn)
    def wrapper(*args, log_name=None, **kwargs):
        if _comms_logger is None:
            return fn(*args, **kwargs)
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        traced = any(isinstance(l, jax.core.Tracer) for l in jax.tree.leaves(out))
        if not traced:
            jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        msg_size = 0
        for a in args:
            if hasattr(a, "nbytes"):
                msg_size += a.nbytes
        _comms_logger.append(log_name or fn.__name__, fn.__name__, dt, msg_size,
                             traced=traced)
        return out

    return wrapper


# ---------------------------------------------------------------------------
# In-graph collectives over mesh axes (ICI/DCN path)
# ---------------------------------------------------------------------------

class ReduceOp:
    SUM = "sum"
    AVG = "avg"
    MAX = "max"
    MIN = "min"
    PROD = "prod"


def _maybe_tuple(axis):
    return tuple(axis) if isinstance(axis, (list, tuple)) else axis


@timed_op
def all_reduce(x, op: str = ReduceOp.SUM, axis_name="data", group=None):
    """psum/pmax/... over a mesh axis (reference comm/comm.py:483)."""
    axis_name = _maybe_tuple(group or axis_name)
    if op == ReduceOp.SUM:
        return lax.psum(x, axis_name)
    if op == ReduceOp.AVG:
        return lax.pmean(x, axis_name)
    if op == ReduceOp.MAX:
        return lax.pmax(x, axis_name)
    if op == ReduceOp.MIN:
        return lax.pmin(x, axis_name)
    if op == ReduceOp.PROD:
        return jnp.exp(lax.psum(jnp.log(x), axis_name))
    raise ValueError(f"unsupported reduce op {op}")


@timed_op
def inference_all_reduce(x, axis_name="model", group=None):
    """Latency-path allreduce over the (small, innermost) model axis — the ICI
    analogue of the reference's low-latency path (comm/ccl.py:89)."""
    return lax.psum(x, _maybe_tuple(group or axis_name))


# --- Megatron-style tensor-parallel boundary ops (reference AutoTP inserts
# the same pair around sharded Linears, module_inject/auto_tp.py). Needed
# as custom-VJP ops because under shard_map without replication tracking a
# bare psum transposes to psum, double-counting replicated cotangents.

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_copy(x, axis_name="model"):
    """Identity forward / psum backward: marks a replicated activation
    entering a column-parallel region (Megatron's ``f``). The backward psum
    sums the per-shard partial input-cotangents."""
    return x


def _tp_copy_fwd(x, axis_name):
    return x, None


def _tp_copy_bwd(axis_name, _res, ct):
    try:
        return (lax.psum(ct, axis_name),)
    except NameError:  # axis unbound: not under shard_map -> no TP
        return (ct,)


tp_copy.defvjp(_tp_copy_fwd, _tp_copy_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_reduce(x, axis_name="model"):
    """psum forward / identity backward: reduces the partial outputs of a
    row-parallel region to the full (replicated) activation (Megatron's
    ``g``). The cotangent of a replicated output is already complete on
    every shard."""
    try:
        return lax.psum(x, axis_name)
    except NameError:  # axis unbound: not under shard_map -> no TP
        return x


def _tp_reduce_fwd(x, axis_name):
    return tp_reduce(x, axis_name), None


def _tp_reduce_bwd(axis_name, _res, ct):
    return (ct,)


tp_reduce.defvjp(_tp_reduce_fwd, _tp_reduce_bwd)


@timed_op
def all_gather_into_tensor(x, axis_name="data", axis: int = 0, group=None, tiled: bool = True):
    """Gather shards along `axis` (reference comm/comm.py:297)."""
    return lax.all_gather(x, _maybe_tuple(group or axis_name), axis=axis, tiled=tiled)


# capability probes (reference comm/comm.py:308,:239) — always true on XLA
def has_all_gather_into_tensor() -> bool:
    return True


def has_reduce_scatter_tensor() -> bool:
    return True


@timed_op
def reduce_scatter_tensor(x, op: str = ReduceOp.SUM, axis_name="data", axis: int = 0,
                          group=None, tiled: bool = True):
    """Reduce + scatter along `axis` (reference comm/comm.py:280)."""
    axis_name = _maybe_tuple(group or axis_name)
    out = lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=tiled)
    if op == ReduceOp.AVG:
        sz = lax.psum(jnp.ones((), x.dtype), axis_name)
        out = out / sz
    return out


@timed_op
def all_to_all_single(x, axis_name="seq", split_axis: int = 0, concat_axis: int = 0,
                      group=None, tiled: bool = True):
    """All-to-all repartition (reference comm/comm.py:331); the Ulysses primitive."""
    return lax.all_to_all(x, _maybe_tuple(group or axis_name), split_axis=split_axis,
                          concat_axis=concat_axis, tiled=tiled)


all_to_all = all_to_all_single


@timed_op
def broadcast(x, src: int = 0, axis_name="data", group=None):
    """Select src's shard and replicate it over the axis."""
    axis_name = _maybe_tuple(group or axis_name)
    idx = lax.axis_index(axis_name)
    masked = jnp.where(idx == src, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name)


@timed_op
def permute(x, perm: Sequence, axis_name="pipe"):
    """Point-to-point ring shift: the compiled-form send/recv used by the
    pipeline engine (reference runtime/pipe/p2p.py:50 send/recv -> ICI
    collective-permute)."""
    return lax.ppermute(x, axis_name, perm=list(perm))


def send_next(x, axis_name="pipe", n: Optional[int] = None):
    n = n if n is not None else axis_size(axis_name)
    return lax.ppermute(x, axis_name, perm=[(i, (i + 1) % n) for i in range(n)])


def recv_prev(x, axis_name="pipe", n: Optional[int] = None):
    return send_next(x, axis_name, n)


def send_prev(x, axis_name="pipe", n: Optional[int] = None):
    n = n if n is not None else axis_size(axis_name)
    return lax.ppermute(x, axis_name, perm=[(i, (i - 1) % n) for i in range(n)])


def axis_rank(axis_name) -> jnp.ndarray:
    return lax.axis_index(axis_name)


def axis_size(axis_name) -> int:
    from .quantized import _axis_size
    return _axis_size(axis_name)


# dispatch helpers mirroring reference comm.py:315/:246
def allgather_fn(x, axis_name="data", axis: int = 0):
    return all_gather_into_tensor(x, axis_name=axis_name, axis=axis)


def reduce_scatter_fn(x, axis_name="data", axis: int = 0):
    return reduce_scatter_tensor(x, axis_name=axis_name, axis=axis)
