"""Activation checkpointing (rematerialization).

TPU-native analogue of the reference's Megatron-compatible activation
checkpointing (runtime/activation_checkpointing/checkpointing.py:
CheckpointFunction :477, partition_activations :369, non_reentrant_checkpoint
:711, configure :1057, CudaRNGStatesTracker :122).

The torch implementation re-runs the forward in backward by saving/restoring
RNG states and manually partitioning/offloading saved tensors. Under XLA all
of that collapses into ``jax.checkpoint``:

  * recompute-in-backward  -> jax.checkpoint(fn, policy)
  * partition_activations  -> free: a saved residual keeps whatever sharding
    it has; activations computed under sequence/tensor sharding are saved as
    shards, which is what the reference's scatter-to-mp-group does by hand
  * cpu_checkpointing      -> offload policy ("device" -> "pinned_host"
    memory space), the reference's copy_to_main_memory path
  * RNG tracking           -> functional jax PRNG keys; the tracker below is
    an API shim for Megatron-style callers

``configure`` accepts the same config block as the reference (engine wires
``activation_checkpointing`` from the JSON config), plus a TPU-native
``policy`` knob naming any jax.checkpoint_policies entry for selective
checkpointing (e.g. "dots_saveable" to keep matmul outputs).

The default ``policy`` is ``"auto"``: the engine picks, once, when it first
sees a batch's shapes, the richest of the model's save sets that fits the
memory its placed state leaves free (``choose_policy``). Anything that
traces a model without an engine's choice gets ``nothing_saveable``.
"""

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax

AUTO = "auto"
NOTHING = "nothing_saveable"
# what the model names for a policy to keep (jax.ad_checkpoint
# .checkpoint_name): the attention output, once, where it is made, and the
# flash kernel's row statistics, which its backward reads beside it
# (ops/flash_attention._flash_core_fwd)
ATTN_NAMES = ("attn_out", "attn_lse")
# the share of the memory left free by the placed state that saved
# activations may take; the rest is the step's own working set (gradients,
# layer-boundary activations, one layer's recompute, the loss head). The
# benchmark's cells take 8 % and 7 % for save_attn and peak at 46 % and
# 54 % of the chip (PERF.md, PR 27)
SAVE_SHARE = 0.25

_config: Dict[str, Any] = {
    "partition_activations": False,
    "cpu_checkpointing": False,
    "contiguous_memory_optimization": False,
    "number_checkpoints": None,
    "synchronize_checkpoint_boundary": False,
    "profile": False,
    "policy": AUTO,
}
_configured = False


def _resolve_policy(name: str, cpu_checkpointing: bool = False):
    if cpu_checkpointing:
        # save matmul outputs to host memory instead of recomputing or
        # keeping them in HBM (reference checkpoint_in_cpu / copy_to_main_memory)
        return jax.checkpoint_policies.offload_dot_with_no_batch_dims(
            "device", "pinned_host")
    if name == AUTO:
        # no engine has chosen (yet)
        return jax.checkpoint_policies.nothing_saveable
    if name == "save_attn":
        # keep what the attention backward reads and only the attention
        # forward can make: its output and, on the flash path, the row
        # statistics. dots_with_no_batch_dims skips them (attention einsums
        # have batch dims, and the Pallas call is opaque to dot policies);
        # with both saved the recomputed layer holds no attention forward
        return jax.checkpoint_policies.save_only_these_names(*ATTN_NAMES)
    if name == "save_dots_and_attn":
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(*ATTN_NAMES))
    policy = getattr(jax.checkpoint_policies, name, None)
    if policy is None:
        raise ValueError(
            f"unknown activation-checkpointing policy '{name}'; options: "
            f"{AUTO}, save_attn, save_dots_and_attn, "
            f"{[p for p in dir(jax.checkpoint_policies) if not p.startswith('_')]}")
    return policy


def choose_policy(bytes_limit: int, state_bytes: int,
                  save_sets: Sequence[Tuple[str, int]]) -> Tuple[str, int]:
    """(policy name, bytes it keeps a device) for ``policy: auto``.

    ``save_sets`` is the model's ordered list, smallest first, of (policy
    name, bytes a device the policy keeps over all layers of a step); the
    richest one within ``SAVE_SHARE`` of what ``state_bytes`` (parameters,
    master weights, optimizer state as placed) leaves of ``bytes_limit``
    wins. A chip the state fills, or a device that reports no limit, gets
    ``nothing_saveable``. Nothing is compiled or traced to decide."""
    budget = SAVE_SHARE * max(int(bytes_limit or 0) - int(state_bytes), 0)
    chosen = (NOTHING, 0)
    for name, nbytes in save_sets:
        if 0 < nbytes <= budget:
            chosen = (name, int(nbytes))
    return chosen


def configure(mpu_=None, deepspeed_config=None, partition_activations=None,
              contiguous_checkpointing=None, num_checkpoints=None,
              checkpoint_in_cpu=None, synchronize=None, profile=None,
              policy=None):
    """Reference configure() signature (checkpointing.py:1057); also accepts
    the ActivationCheckpointingConfig dataclass via deepspeed_config."""
    global _configured
    if deepspeed_config is not None:
        ac = getattr(deepspeed_config, "activation_checkpointing",
                     deepspeed_config)
        _config.update(
            partition_activations=ac.partition_activations,
            cpu_checkpointing=ac.cpu_checkpointing,
            contiguous_memory_optimization=ac.contiguous_memory_optimization,
            number_checkpoints=ac.number_checkpoints,
            synchronize_checkpoint_boundary=ac.synchronize_checkpoint_boundary,
            profile=ac.profile,
            policy=ac.policy,
        )
    overrides = {
        "partition_activations": partition_activations,
        "contiguous_memory_optimization": contiguous_checkpointing,
        "number_checkpoints": num_checkpoints,
        "cpu_checkpointing": checkpoint_in_cpu,
        "synchronize_checkpoint_boundary": synchronize,
        "profile": profile,
        "policy": policy,
    }
    _config.update({k: v for k, v in overrides.items() if v is not None})
    _configured = True


def is_configured() -> bool:
    return _configured


def get_config() -> Dict[str, Any]:
    return dict(_config)


def active_policy():
    return _resolve_policy(_config["policy"], _config["cpu_checkpointing"])


def checkpoint(function: Callable, *args, policy_name: Optional[str] = None):
    """Megatron-compatible entry (reference CheckpointFunction.apply,
    checkpointing.py:477): checkpoint `function(*args)`, recomputing its
    activations in backward according to the configured policy."""
    pol = (_resolve_policy(policy_name) if policy_name is not None
           else active_policy())
    return jax.checkpoint(function, policy=pol)(*args)


def checkpoint_wrapper(function: Callable,
                       policy_name: Optional[str] = None) -> Callable:
    """Wrap once, call many times (what models use around a layer body)."""
    pol = (_resolve_policy(policy_name) if policy_name is not None
           else active_policy())
    return jax.checkpoint(function, policy=pol)


# the non-reentrant path is the only path under XLA (no autograd reentry)
non_reentrant_checkpoint = checkpoint


class RNGStatesTracker:
    """API shim for Megatron's CudaRNGStatesTracker (checkpointing.py:122).

    jax PRNG is functional, so "tracking states" is holding named keys and
    splitting deterministically; fork() returns a fresh key and advances the
    stored one, which is what the torch tracker's fork/restore achieves for
    reproducible dropout inside checkpointed regions.
    """

    def __init__(self):
        self._states: Dict[str, jax.Array] = {}

    def reset(self):
        self._states.clear()

    def get_states(self):
        return dict(self._states)

    def set_states(self, states):
        self._states = dict(states)

    def add(self, name: str, seed: int):
        if name in self._states:
            raise ValueError(f"rng state {name} already present")
        self._states[name] = jax.random.PRNGKey(seed)

    def fork(self, name: str = "model-parallel-rng"):
        if name not in self._states:
            raise KeyError(f"rng state {name} not added")
        self._states[name], sub = jax.random.split(self._states[name])
        return sub


_RNG_TRACKER = RNGStatesTracker()


def get_cuda_rng_tracker() -> RNGStatesTracker:  # reference-compat name
    return _RNG_TRACKER


def model_parallel_reconfigure_tp_seed(seed: int):
    """Reference model_parallel_reconfigure_tp_seed (checkpointing.py)."""
    _RNG_TRACKER.reset()
    _RNG_TRACKER.add("model-parallel-rng", seed)


def reset():
    """Testing hook: restore defaults."""
    global _configured
    _config.update(partition_activations=False, cpu_checkpointing=False,
                   contiguous_memory_optimization=False,
                   number_checkpoints=None,
                   synchronize_checkpoint_boundary=False, profile=False,
                   policy=AUTO)
    _configured = False
    _RNG_TRACKER.reset()
