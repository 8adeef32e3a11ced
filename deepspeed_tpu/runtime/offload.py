"""Tiered host-offloaded optimizer state with bucket-streamed prefetch.

ZeRO-Infinity's insight (arXiv:2104.07857) is that optimizer state only
needs to be NEAR the device for the few microseconds its bucket is being
updated — the rest of the step it can live a PCIe hop away. The legacy
``runtime/zero/offload.py`` path moves the whole UPDATE to the host C++
kernels; this module keeps the update on the device (the same jitted
math as the resident path, so offloaded training is bit-identical to
resident training) and moves only the STORAGE to the host:

  * fp32 master weights and optimizer moments live in host memory —
    as ``memory_kind="pinned_host"`` jax arrays where this runtime
    supports committing them there (:func:`pinned_host_supported`), and
    as plain numpy staging buffers otherwise (jax 0.9 places them on the
    CPU backend too, so tier-1 runs the pinned path);
  * the update streams BUCKET by BUCKET: leaf-aligned groups capped at
    ``zero_optimization.stage3_prefetch_bucket_size`` elements (the
    same knob that sizes the reference's stage-3 prefetch), so HBM
    holds one bucket's fp32 state at a time instead of the full tree;
  * bucket ``i+1 .. i+buffer_count``'s host->device fetches are issued
    while bucket ``i`` updates, and the first ``buffer_count`` fetches
    are issued BEFORE the gradient program runs
    (:meth:`TieredOptimizerOffload.prefetch` — the engine calls it
    ahead of the bucketed grad ring's dispatch, so the H2D transfers
    ride under the backward+reduce window the same way
    ``grad_overlap.py`` hides the gradient collectives);
  * the device->host writeback of bucket ``i`` overlaps bucket
    ``i+1``'s update dispatch (``copy_to_host_async`` where the
    runtime provides it).

Overlap is MEASURED, not assumed: ``offload_prefetch_hit_fraction``
counts fetches already in flight when their bucket needed them, and
``offload_prefetch_exposed_fraction`` is the fraction of streaming wall
time spent blocked on a transfer (the analogue of the grad ring's
exposed-collective fraction). ``optimizer_offload_bytes`` reports the
HBM bytes this tier moved off-device.

Bit-identity with the resident path holds because the buckets are
LEAF-aligned: ``optimizer.apply`` maps leaf-wise (including FusedLamb's
per-leaf trust ratios), so updating a bucket's leaves with the same
``apply_update_with_skip`` math the resident jitted step uses produces
the same bits leaf by leaf — pinned by
tests/unit/runtime/test_tiered_offload.py across ZeRO stages 1/2 x GAS.
"""

import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.logging import logger

_PINNED_SUPPORT: Optional[bool] = None


def pinned_host_supported() -> bool:
    """Can this runtime COMMIT an array to a ``pinned_host`` memory
    space? Probed once per process: a backend that parses the memory
    kind but fails placement is exactly the case the numpy staging
    fallback exists for."""
    global _PINNED_SUPPORT
    if _PINNED_SUPPORT is None:
        try:
            from jax.sharding import SingleDeviceSharding
            dev = jax.devices()[0]
            sh = SingleDeviceSharding(dev, memory_kind="pinned_host")
            arr = jax.device_put(np.zeros(8, np.float32), sh)
            arr.block_until_ready()
            _PINNED_SUPPORT = (
                getattr(arr.sharding, "memory_kind", None) == "pinned_host")
        except Exception:
            _PINNED_SUPPORT = False
        if not _PINNED_SUPPORT:
            logger.info(
                "tiered offload: pinned_host memory spaces unavailable on "
                "this runtime; optimizer state stages through host numpy "
                "buffers instead")
    return _PINNED_SUPPORT


def plan_prefetch_buckets(numels: Sequence[int],
                          bucket_elems: int) -> List[List[int]]:
    """Group leaf indices into prefetch buckets: consecutive leaves
    (flatten order — the order their gradients arrive in) packed until
    the bucket would exceed ``bucket_elems``. A single leaf larger than
    the cap forms its own bucket — leaves are never split, which is
    what keeps per-leaf optimizer math (LAMB trust ratios) exact."""
    if bucket_elems <= 0:
        raise ValueError(f"bucket_elems must be > 0, got {bucket_elems}")
    buckets: List[List[int]] = []
    cur: List[int] = []
    cur_elems = 0
    for i, n in enumerate(numels):
        if cur and cur_elems + n > bucket_elems:
            buckets.append(cur)
            cur, cur_elems = [], 0
        cur.append(i)
        cur_elems += n
    if cur:
        buckets.append(cur)
    return buckets


class TieredOptimizerOffload:
    """Host tier for optimizer state; device tier for the update.

    Exposes the same checkpoint surface as
    ``runtime/zero/offload.py:HostOffloadOptimizer`` (``state_keys`` /
    ``get_all_leaves`` / ``template_leaves`` / ``load_leaves`` /
    ``current_bf16_leaves`` / ``close``), so the engine's save/load and
    universal-checkpoint paths work unchanged with either backend.

    Parameters
    ----------
    optimizer : TpuOptimizer — the SAME registry instance the resident
        path would apply; its leaf-wise math is reused verbatim.
    lr_fn : the engine's compiled LR schedule; traced INSIDE the bucket
        update (``lr = lr_fn(step)``) exactly as the resident step does.
    master_leaves : fp32 numpy leaves in tree-flatten order.
    bucket_elems : prefetch granularity
        (``zero_optimization.stage3_prefetch_bucket_size``).
    buffer_count : prefetch depth (``offload_optimizer.buffer_count``).
    fetch_sharding : committed placement for fetched buckets (the
        engine passes its replicated NamedSharding so repeated steps
        hit one executable per bucket signature).
    """

    def __init__(self, optimizer, lr_fn, master_leaves: List[np.ndarray],
                 leaf_names: List[str], bucket_elems: int,
                 buffer_count: int = 4, compute_dtype=None,
                 fetch_sharding=None):
        import ml_dtypes

        self.opt = optimizer
        self.lr_fn = lr_fn
        self.names = list(leaf_names)
        self.shapes = [tuple(m.shape) for m in master_leaves]
        self.sizes = [int(m.size) for m in master_leaves]
        self.out_dtype = np.dtype(
            ml_dtypes.bfloat16 if compute_dtype is None else compute_dtype)
        self.depth = max(1, int(buffer_count))
        self.fetch_sharding = fetch_sharding
        self.pinned = pinned_host_supported()
        self.device = "cpu"   # HostOffloadOptimizer surface parity
        self.buckets = plan_prefetch_buckets(self.sizes, bucket_elems)

        # moment layout from the optimizer itself (SGD may carry zero or
        # one moment, Adam two, ...): probe init_state on a scalar tree
        probe = self.opt.init_state({"p": jnp.zeros((1,), jnp.float32)})
        self.state_keys = sorted(probe.keys())

        # host storage: one fp32 buffer per leaf (master + each moment).
        # pinned mode keeps them as committed pinned_host jax arrays so
        # fetches are true pinned-DMA H2D copies; fallback keeps numpy.
        self.master = [self._to_host(np.asarray(m, np.float32))
                       for m in master_leaves]
        self.state = {k: [self._to_host(np.zeros(s, np.float32))
                          for s in self.shapes]
                      for k in self.state_keys}

        self._update_fns: Dict[Any, Any] = {}
        self._inflight: Dict[int, Any] = {}   # bucket idx -> fetched leaves
        self._pending_writeback: List[Any] = []
        self._fetch_hits = 0
        self._fetch_total = 0
        self._wait_s = 0.0
        self._stream_s = 0.0

        from ..telemetry import get_registry
        reg = get_registry()
        state_bytes = sum(self.sizes) * 4 * (1 + len(self.state_keys))
        self._m_bytes = reg.gauge(
            "optimizer_offload_bytes",
            "fp32 master + moment bytes resident in the host tier "
            "instead of HBM (tiered optimizer offload)")
        self._m_bytes.set(state_bytes)
        self._m_hit = reg.gauge(
            "offload_prefetch_hit_fraction",
            "fraction of bucket fetches already issued (in flight or "
            "done) when the streaming update needed them")
        self._m_exposed = reg.gauge(
            "offload_prefetch_exposed_fraction",
            "fraction of optimizer streaming wall time spent blocked "
            "on host<->device state transfers (0 = fully hidden)")
        self._m_h2d = reg.counter(
            "offload_h2d_bytes_total",
            "optimizer-state bytes fetched host->device by the "
            "streaming update")
        self._m_d2h = reg.counter(
            "offload_d2h_bytes_total",
            "optimizer-state bytes written back device->host by the "
            "streaming update")
        logger.info(
            f"tiered optimizer offload: {len(self.buckets)} buckets over "
            f"{len(self.sizes)} leaves ({state_bytes / 1e6:.1f} MB host "
            f"state, prefetch depth {self.depth}, "
            f"pinned_host={self.pinned})")

    # -- host placement ------------------------------------------------
    def _to_host(self, arr: np.ndarray):
        if not self.pinned:
            # owned, WRITABLE buffer (np.asarray of a jax array is a
            # read-only view; writebacks copy into this in place)
            return np.array(arr, np.float32, copy=True)
        from jax.sharding import SingleDeviceSharding
        sh = SingleDeviceSharding(jax.devices()[0],
                                  memory_kind="pinned_host")
        return jax.device_put(arr, sh)

    def _host_view(self, leaf) -> np.ndarray:
        return np.asarray(leaf)

    def _store_host(self, i: int, key: Optional[str], value: np.ndarray):
        """Write one leaf back into host storage. numpy mode copies in
        place (buffer identity is stable across steps); pinned mode
        re-commits the fresh array to the pinned space."""
        if self.pinned:
            if key is None:
                self.master[i] = self._to_host(value)
            else:
                self.state[key][i] = self._to_host(value)
        else:
            dst = self.master[i] if key is None else self.state[key][i]
            np.copyto(dst, np.asarray(value, np.float32).reshape(dst.shape))

    # -- streaming update ----------------------------------------------
    def _bucket_sig(self, b: int):
        return tuple((self.shapes[i], self.sizes[i])
                     for i in self.buckets[b])

    def _update_fn(self, b: int):
        sig = self._bucket_sig(b)
        fn = self._update_fns.get(sig)
        if fn is not None:
            return fn
        opt, lr_fn = self.opt, self.lr_fn
        out_dtype = jnp.dtype(self.out_dtype)
        from .engine import apply_update_with_skip

        def update(masters, states, grads, step):
            # the exact resident-step sequence for this bucket's leaves:
            # lr from the schedule at the PRE-increment step, then
            # apply_update_with_skip (finite=True — skipped steps never
            # reach the streaming update; the host gates on the grad
            # program's `skipped` flag instead)
            with jax.named_scope("optimizer"):
                lr = lr_fn(step)
                new_master, new_state, _ = apply_update_with_skip(
                    opt, masters, grads, states, step, lr,
                    jnp.asarray(True))
                new_params = [m.astype(out_dtype) for m in new_master]
            return new_master, new_state, new_params

        fn = jax.jit(update, donate_argnums=(0, 1))
        self._update_fns[sig] = fn
        return fn

    def _issue_fetch(self, b: int) -> None:
        if b in self._inflight or b >= len(self.buckets):
            return
        idx = self.buckets[b]
        put = (lambda x: jax.device_put(x, self.fetch_sharding)) \
            if self.fetch_sharding is not None else jax.device_put
        masters = [put(self._bucket_leaf_source(i, None)) for i in idx]
        states = {k: [put(self._bucket_leaf_source(i, k)) for i in idx]
                  for k in self.state_keys}
        self._inflight[b] = (masters, states)
        self._m_h2d.inc(sum(self.sizes[i] for i in idx) * 4
                        * (1 + len(self.state_keys)))

    def _bucket_leaf_source(self, i: int, key: Optional[str]):
        leaf = self.master[i] if key is None else self.state[key][i]
        # pinned mode device_puts the pinned array directly (a DMA’able
        # source); numpy mode hands the staging buffer itself
        return leaf

    def prefetch(self) -> None:
        """Issue the first ``buffer_count`` buckets' H2D fetches. The
        engine calls this BEFORE dispatching the gradient program, so
        the state transfers overlap the backward + bucketed grad ring
        instead of serializing after them."""
        for b in range(min(self.depth, len(self.buckets))):
            self._issue_fetch(b)

    def _drain_writebacks(self) -> None:
        for i, key, dev in self._pending_writeback:
            self._store_host(i, key, np.asarray(dev))
        self._pending_writeback.clear()

    def stream_update(self, grad_leaves: List[Any], step) -> List[Any]:
        """One optimizer step, streamed bucket-by-bucket. ``grad_leaves``
        are the grad program's DEVICE outputs in tree-flatten order;
        returns the updated compute-dtype param leaves (device arrays,
        same order)."""
        assert len(grad_leaves) == len(self.sizes), \
            f"{len(grad_leaves)} grads vs {len(self.sizes)} leaves"
        if self.fetch_sharding is not None:
            # commit the step scalar like the fetched buckets: callers
            # hand it in whatever placement their path left it (fresh
            # init, checkpoint load), and mixing committed device sets
            # inside one jit is an error
            step = jax.device_put(step, self.fetch_sharding)
        t_start = time.perf_counter()
        new_params: List[Any] = [None] * len(self.sizes)
        for b, idx in enumerate(self.buckets):
            self._fetch_total += 1
            if b in self._inflight:
                self._fetch_hits += 1
            else:
                self._issue_fetch(b)
            t0 = time.perf_counter()
            masters, states = self._inflight.pop(b)
            # the wait on the fetched leaves is the EXPOSED transfer
            # time; a prefetch that landed under the grad window (or a
            # previous bucket's update) costs ~0 here. Moments are 2/3
            # of a bucket's Adam bytes — waiting on the masters alone
            # would misattribute a state-transfer stall to update time
            for leaf in masters:
                leaf.block_until_ready()
            for leaves in states.values():
                for leaf in leaves:
                    leaf.block_until_ready()
            self._wait_s += time.perf_counter() - t0
            grads = [grad_leaves[i] for i in idx]
            out_master, out_state, out_params = self._update_fn(b)(
                masters, states, grads, step)
            # prefetch ahead while this bucket's outputs materialize
            self._issue_fetch(b + self.depth)
            # drain PREVIOUS buckets' async copies now that this bucket's
            # update is dispatched — the current bucket's entries are
            # appended below, so one bucket of writeback latency stays
            # hidden behind the next bucket's work
            self._drain_writebacks()
            for j, i in enumerate(idx):
                new_params[i] = out_params[j]
                dev = out_master[j]
                if hasattr(dev, "copy_to_host_async"):
                    dev.copy_to_host_async()
                self._pending_writeback.append((i, None, dev))
                for k in self.state_keys:
                    devk = out_state[k][j]
                    if hasattr(devk, "copy_to_host_async"):
                        devk.copy_to_host_async()
                    self._pending_writeback.append((i, k, devk))
            self._m_d2h.inc(sum(self.sizes[i] for i in idx) * 4
                            * (1 + len(self.state_keys)))
        self._drain_writebacks()
        # any in-flight over-prefetch (next step's buckets) stays cached
        # for the next stream_update call
        self._stream_s += time.perf_counter() - t_start
        if self._fetch_total:
            self._m_hit.set(self._fetch_hits / self._fetch_total)
        if self._stream_s > 0:
            self._m_exposed.set(min(1.0, self._wait_s / self._stream_s))
        return new_params

    # -- checkpoint surface (HostOffloadOptimizer-compatible) -----------
    def get_all_leaves(self):
        master = [self._host_view(m).reshape(s)
                  for m, s in zip(self.master, self.shapes)]
        state = {k: [self._host_view(st).reshape(s)
                     for st, s in zip(self.state[k], self.shapes)]
                 for k in self.state_keys}
        return master, state

    def get_master_leaves(self) -> List[np.ndarray]:
        return self.get_all_leaves()[0]

    def get_state_leaves(self) -> Dict[str, List[np.ndarray]]:
        return self.get_all_leaves()[1]

    def template_leaves(self):
        master = [np.empty(s, np.float32) for s in self.shapes]
        state = {k: [np.empty(s, np.float32) for s in self.shapes]
                 for k in self.state_keys}
        return master, state

    def load_leaves(self, master: List[np.ndarray],
                    state: Optional[Dict[str, List[np.ndarray]]] = None):
        self._inflight.clear()   # stale prefetches would resurrect the
        self._pending_writeback.clear()   # pre-restore state
        for i, m in enumerate(master):
            self._store_host(i, None,
                             np.asarray(m, np.float32).reshape(
                                 self.shapes[i]))
            if state is not None:
                for k in self.state_keys:
                    self._store_host(i, k,
                                     np.asarray(state[k][i],
                                                np.float32).reshape(
                                         self.shapes[i]))

    def current_bf16_leaves(self) -> List[np.ndarray]:
        return [self._host_view(m).astype(self.out_dtype)
                for m in self.master]

    def close(self):
        self._inflight.clear()
        self._pending_writeback.clear()
