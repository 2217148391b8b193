"""Device prefetcher: overlaps host->device transfer with consumption.

The TPU analogue of the paper's pinned-memory + ``.cuda()`` copy: batches
are ``jax.device_put`` onto the global ``NamedSharding`` (each host provides
its local shard) ``depth`` steps ahead of the training loop, so the HBM DMA
runs concurrently with the previous step's compute.

Fast-path extensions (DESIGN.md §3):

* ``donate=True`` passes ``jax.device_put(..., donate=True)`` so
  device-resident inputs hand their buffers to the result instead of
  copying (host numpy inputs are copied regardless — donation matters when
  an upstream stage already produced ``jax.Array``s, e.g. re-sharding);
* ``transfer_threads=2`` overlaps two host->HBM copies: a submitter thread
  feeds a tiny executor in batch order and queues the futures, so delivery
  order is preserved while transfers for consecutive batches run
  concurrently with each other and with compute;
* arena-backed batches (``ArenaBatch``) are ``detach``ed before an async
  transfer and released the moment their device copy completes, returning
  the slab to the ring as early as possible;
* a ``StagingPool`` (``staging_buffers > 0``, the default) interposes a
  small ring of preallocated host staging buffers on the device edge: the
  slab is copied into a pooled buffer once and released *immediately*
  (before the device copy even starts), and the device put runs from the
  pooled buffer with no ``may_alias=False`` / verify-and-re-put dance —
  a buffer the backend zero-copied is retired from the ring instead of
  reused, so privacy holds by construction (DESIGN.md §5).
"""
from __future__ import annotations

import queue
import threading
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, Iterator, Optional

import jax
import numpy as np

from repro.data.arena import ArenaBatch
from repro.utils.spans import span

_SENTINEL = object()


def _leaf_aliases(dev, host: np.ndarray) -> bool:
    """Does device array ``dev`` share its buffer with host array ``host``?
    Only answerable (and only possible) on the CPU backend; anything that
    can't report a buffer pointer genuinely copied."""
    try:
        return dev.unsafe_buffer_pointer() == \
            host.__array_interface__["data"][0]
    except Exception:  # pragma: no cover - non-CPU / sharded arrays
        return False


def put_global_batch(batch, sharding=None, *, donate: bool = False,
                     may_alias=None):
    """Host batch (numpy dict) -> device array(s).

    With a NamedSharding whose mesh spans multiple processes, each host
    contributes its local shard via ``make_array_from_process_local_data``;
    single-process meshes (and sharding=None) fall back to device_put.

    ``may_alias=False`` forces a real copy: on the CPU backend device_put
    zero-copies numpy buffers when it can, which is exactly wrong for a
    recycled arena slab (the "device" array would mutate when the slab is
    reused) — the prefetcher passes False for arena-backed batches.
    """
    if sharding is None:
        return jax.device_put(batch, donate=donate, may_alias=may_alias)

    def _put(x):
        x = np.asarray(x)
        if jax.process_count() > 1:  # pragma: no cover - multi-host only
            return jax.make_array_from_process_local_data(sharding, x)
        return jax.device_put(x, sharding, donate=donate,
                              may_alias=may_alias)

    return jax.tree_util.tree_map(_put, batch)


class StagingPool:
    """Pinned staging-buffer ring for the device edge (DESIGN.md §5).

    The zero-copy pipeline's last host hop: an arena slab must not be
    recycled while a device copy might still read (or alias) it.  PR 2
    solved that with ``may_alias=False`` + a per-batch verify-and-re-put
    (``_ensure_private`` — jax 0.4.37's concurrent ``device_put`` can
    ignore ``may_alias=False``).  The pool replaces the dance: the slab is
    copied ONCE into a pooled buffer shaped like the device batch and
    released on the spot, and the device put runs from the pooled buffer.
    A buffer the backend genuinely copied returns to the ring (hit on next
    acquire); one the backend zero-copied now *backs a live device array*
    and is retired instead — it is never written again, so the device
    array can never be mutated by recycling.

    The spec (field shapes/dtypes) latches from the first batch; a batch
    of a different shape (reshard, ragged makeup chunk) drops the stale
    ring and re-establishes it.  ``hit_rate``/``retired`` feed
    ``TransferStats.staging_hit_rate`` and the monitor report.
    """

    def __init__(self, capacity: int):
        self.capacity = max(1, capacity)
        self._spec: Optional[Dict[str, tuple]] = None
        self._free: deque = deque()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.retired = 0

    def acquire(self, batch: Dict) -> Dict[str, np.ndarray]:
        """A staging dict matching ``batch``'s field spec.  Never blocks
        and never fails: a miss allocates (transfers already in flight
        bound how many buffers can be out; ``release`` drops surplus)."""
        spec = {k: (np.asarray(v).shape, np.asarray(v).dtype)
                for k, v in batch.items()}
        with self._lock:
            if self._spec != spec:
                if self._ragged_of(spec, self._spec):
                    # a short batch (skip-mode quarantine, makeup tail):
                    # transient — allocate fresh without thrashing the
                    # ring the full-size batches still need
                    self.misses += 1
                    return {k: np.empty(shape, dtype)
                            for k, (shape, dtype) in spec.items()}
                # first batch, or the batch shape changed (reshard):
                # pooled buffers of the old shape are useless — drop them
                self._free.clear()
                self._spec = spec
            if self._free:
                self.hits += 1
                return self._free.popleft()
            self.misses += 1
        return {k: np.empty(shape, dtype) for k, (shape, dtype) in
                spec.items()}

    @staticmethod
    def _ragged_of(spec, latched) -> bool:
        """Is ``spec`` the latched spec with a smaller leading dim (same
        fields, dtypes, trailing dims)?"""
        if latched is None or set(spec) != set(latched):
            return False
        for k, (shape, dtype) in spec.items():
            lshape, ldtype = latched[k]
            if (dtype != ldtype or len(shape) != len(lshape)
                    or not shape or shape[0] >= lshape[0]
                    or shape[1:] != lshape[1:]):
                return False
        return True

    def release(self, buf: Dict[str, np.ndarray]) -> None:
        """The device copy landed in a private buffer: back to the ring
        (dropped if the spec moved on or the ring is full)."""
        with self._lock:
            spec = {k: (v.shape, v.dtype) for k, v in buf.items()}
            if spec == self._spec and len(self._free) < self.capacity:
                self._free.append(buf)

    def retire(self, buf: Dict[str, np.ndarray]) -> None:
        """The device array aliases this buffer — it belongs to the device
        array now and must never be reused."""
        with self._lock:
            self.retired += 1

    def resize(self, capacity: int) -> None:
        with self._lock:
            self.capacity = max(1, capacity)
            while len(self._free) > self.capacity:
                self._free.pop()

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0


class _DepthGate:
    """Resizable in-flight bound (the hot-swappable ``device_prefetch``).

    A plain ``queue.Queue(maxsize=depth)`` fixes the depth at construction;
    this gate moves the bound into a permit counter so ``set_depth`` can
    grow it (release extra permits) or shrink it (absorb permits as the
    consumer returns them) on a LIVE prefetcher without blocking either
    side — which is what lets ``apply_params`` retune the device buffer
    depth mid-stream instead of only at stream creation.
    """

    def __init__(self, depth: int):
        self.depth = max(1, depth)
        self._sem = threading.Semaphore(self.depth)
        self._lock = threading.Lock()
        self._deficit = 0            # permits to absorb after a shrink

    def acquire(self, stop: threading.Event) -> bool:
        """Producer side: take a permit (False when stopped while waiting)."""
        while not stop.is_set():
            if self._sem.acquire(timeout=0.05):
                return True
        return False

    def release(self) -> None:
        """Consumer side: return a permit (absorbed if the depth shrank)."""
        with self._lock:
            if self._deficit > 0:
                self._deficit -= 1
                return
        self._sem.release()

    def set_depth(self, depth: int) -> None:
        depth = max(1, depth)
        with self._lock:
            delta = depth - self.depth
            self.depth = depth
            if delta > 0:
                absorb = min(self._deficit, delta)
                self._deficit -= absorb
                for _ in range(delta - absorb):
                    self._sem.release()
            elif delta < 0:
                self._deficit += -delta


class DevicePrefetcher:
    def __init__(self, host_iter: Iterator, *, depth: int = 2, sharding=None,
                 transfer_threads: int = 1, donate: bool = False,
                 staging_buffers: int = 2):
        self.sharding = sharding
        self.donate = donate
        self.transfer_threads = max(1, transfer_threads)
        self._staging = (StagingPool(staging_buffers)
                         if staging_buffers > 0 else None)
        self._gate = _DepthGate(depth)
        self._queue: queue.Queue = queue.Queue()   # bounded by the gate
        self._error: Optional[BaseException] = None
        self._stop = threading.Event()
        self._executor = (ThreadPoolExecutor(
            max_workers=self.transfer_threads,
            thread_name_prefix="device-transfer")
            if self.transfer_threads > 1 else None)
        self._thread = threading.Thread(target=self._run, args=(host_iter,),
                                        daemon=True)
        self._thread.start()

    @property
    def depth(self) -> int:
        return self._gate.depth

    def set_depth(self, depth: int) -> None:
        """Retune the prefetch depth on the live stream (hot swap)."""
        self._gate.set_depth(depth)

    def set_staging(self, staging_buffers: int) -> None:
        """Retune (or disable) the staging ring on the live stream.  Runs
        at the same params boundary as ``set_depth``; in-flight transfers
        finish against the pool they started with."""
        if staging_buffers <= 0:
            self._staging = None
        elif self._staging is None:
            self._staging = StagingPool(staging_buffers)
        else:
            self._staging.resize(staging_buffers)

    @property
    def staging_hit_rate(self) -> Optional[float]:
        """Staging-pool hit rate (None when the pool is disabled)."""
        return self._staging.hit_rate if self._staging is not None else None

    def close(self) -> None:
        """Stop prefetching and unblock the producer thread (which may be
        parked on the depth gate).  Safe to call more than once."""
        self._stop.set()
        while self._thread.is_alive():
            try:
                self._queue.get_nowait()
            except queue.Empty:
                self._thread.join(timeout=0.05)

    def _transfer(self, batch):
        nbytes = sum(getattr(x, "nbytes", 0)
                     for x in jax.tree_util.tree_leaves(batch))
        with span("loader.h2d", bytes=nbytes):
            return self._transfer_batch(batch)

    def _transfer_batch(self, batch):
        # ArenaBatch is a dict subclass, which jax's pytree registry treats
        # as a leaf — hand device_put a plain dict over the same arrays, and
        # forbid buffer aliasing so the recycled slab can't mutate the
        # transferred array (CPU backend zero-copies plain numpy otherwise)
        arena_backed = isinstance(batch, ArenaBatch)
        payload = dict(batch) if arena_backed else batch
        # snapshot the pool: set_staging(0) may null self._staging while a
        # transfer is in flight — it must finish against the pool it
        # started with
        staging = self._staging
        if arena_backed and staging is not None:
            try:
                staged = staging.acquire(payload)
            except BaseException:
                batch.release()    # allocation failed: never strand a slot
                raise
            return self._transfer_staged(batch, staged, staging)
        try:
            with span("loader.put"):
                dev = put_global_batch(payload, self.sharding,
                                       donate=self.donate,
                                       may_alias=False if arena_backed
                                       else None)
            if arena_backed:
                # device_put is asynchronous: the host->device copy may
                # still be reading the slab.  Block (in this transfer
                # thread, not the consumer) until the copy lands.
                with span("loader.ready"):
                    jax.block_until_ready(dev)
                dev = self._ensure_private(dev, payload)
            return dev
        finally:
            if arena_backed:
                batch.release()    # even on a failed transfer: never leak

    def _transfer_staged(self, batch: ArenaBatch, staged, pool: StagingPool):
        """Staging fast path: one host memcpy frees the slab immediately;
        the device put runs from the pooled buffer, whose privacy is
        settled once (alias -> retire) instead of verified-and-re-put per
        batch."""
        try:
            with span("loader.stage"):
                batch.copy_into(staged)
        finally:
            batch.release()        # slab is free the moment the copy ends
        try:
            with span("loader.put"):
                dev = put_global_batch(staged, self.sharding,
                                       donate=self.donate)
            # the (async) put may still be reading the staging buffer — and
            # on a zero-copying backend the result may *be* the buffer
            with span("loader.ready"):
                jax.block_until_ready(dev)
        except BaseException:
            pool.release(staged)   # unused after a failed put
            raise
        if any(_leaf_aliases(d, staged[k]) for k, d in dev.items()):
            pool.retire(staged)    # owned by the device array now
        else:
            pool.release(staged)
        return dev

    def _ensure_private(self, dev, host):
        """Guarantee no transferred leaf still aliases its source slab.

        Observed on jax 0.4.37 (CPU backend): concurrent ``device_put``
        dispatches can ignore ``may_alias=False`` and return a zero-copy
        view of the input — fatal for a slab that is about to be recycled.
        Leaves that did get private buffers pass through untouched; an
        aliased leaf is re-put from an explicit host copy (which jax may
        alias freely: nothing ever mutates it).
        """
        fixed = {}
        for k, d in dev.items():
            h = np.asarray(host[k])
            if _leaf_aliases(d, h):
                with span("loader.reput"):
                    d = put_global_batch(np.array(h), self.sharding,
                                         donate=self.donate)
            fixed[k] = d
        return fixed

    def _run(self, host_iter):
        try:
            for batch in host_iter:
                if self._stop.is_set():
                    break
                # take ownership *before* advancing host_iter (the pool
                # would otherwise recycle the slab under an in-flight copy)
                if isinstance(batch, ArenaBatch):
                    batch.detach()
                if not self._gate.acquire(self._stop):
                    # closed while waiting for a free depth slot: the batch
                    # never transfers — recycle it rather than leak
                    if isinstance(batch, ArenaBatch):
                        batch.release()
                    break
                if self._executor is None:
                    # synchronous put: the slab is free once _transfer
                    # returns, before the pool's auto-release even runs
                    self._queue.put(self._transfer(batch))
                else:
                    self._queue.put(self._executor.submit(
                        self._transfer, batch))
        except BaseException as e:  # noqa: BLE001
            self._error = e
        finally:
            if self._executor is not None:
                self._executor.shutdown(wait=True)
            self._queue.put(_SENTINEL)

    def __iter__(self):
        while True:
            item = self._queue.get()
            if item is _SENTINEL:
                if self._error is not None:
                    raise self._error
                return
            self._gate.release()
            if isinstance(item, Future):
                item = item.result()
            yield item
