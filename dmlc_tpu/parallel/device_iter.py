"""Async host→device double-buffered batch iterator.

The TPU-native replacement for the reference's ThreadedIter on the
host→HBM edge (SURVEY.md §7 step 6): while the model consumes batch t,
batch t+1 is already in flight to HBM. jax.device_put is async (returns
immediately with the transfer enqueued), so a lookahead queue of in-flight
device batches gives transfer/compute overlap without threads.

Double-buffered staging (r7): with ``staging=True`` each batch is first
copied into a reusable host-side staging slot (a pinned-host buffer on
real accelerators; plain page-aligned numpy here), the transfer is
enqueued FROM the slot, and the source arrays are free the moment the
copy lands — so a leased native-engine block returns to its arena while
its bytes are still in flight, and batch N's H2D transfer overlaps
batch N+1's assembly. Each stage copy emits a ``to_device.stage`` span
(also on the profiler's timeline while one collects) and each transfer
a ``device.xfer`` span (enqueue → ready, recorder only) on the same
timeline, so the overlap is visible in one Perfetto trace; the
``device.staging`` gauge tracks slots in flight.
"""

from __future__ import annotations

import collections
import time
from typing import Any, Callable, Dict, Iterable, Iterator, List, Optional

import jax
import numpy as np

from dmlc_tpu.obs import trace as _trace
from dmlc_tpu.obs.metrics import REGISTRY as _METRICS

__all__ = ["device_prefetch", "DeviceIter", "HostStaging"]


class HostStaging:
    """Reusable host-side staging slots for H2D double-buffering.

    ``stage(arrs)`` copies a dict of arrays into a free slot whose
    shapes/dtypes match (allocating one when none does) and returns the
    slot's dict; the caller enqueues the device transfer FROM the slot
    and hands the slot back via ``release`` once the transfer has
    completed. Fixed-shape batches (the padded steady path) reuse the
    same two slots forever — steady state allocates nothing and the
    source buffers are free at copy time, not at transfer-drain time.

    ``alias_unsafe`` marks backends whose device_put may ALIAS host
    memory (the CPU backend — io/tpu_fs._device_put_safe precedent):
    there a released slot is NOT reused (the consumer's device arrays
    may be views of it) and ownership passes to the consumer instead —
    correctness first, reuse where transfers really copy.
    """

    def __init__(self, slots: int = 2, alias_unsafe: bool = False):
        self.slots = max(2, int(slots))
        self.alias_unsafe = alias_unsafe
        self._free: List[Dict[str, np.ndarray]] = []
        self.in_flight = 0
        self.assemble_s = 0.0  # total staged-copy seconds this epoch

    @staticmethod
    def _matches(slot: Dict[str, np.ndarray],
                 arrs: Dict[str, Any]) -> bool:
        if slot.keys() != arrs.keys():
            return False
        for k, v in arrs.items():
            a = np.asarray(v)
            if slot[k].shape != a.shape or slot[k].dtype != a.dtype:
                return False
        return True

    def stage(self, arrs: Dict[str, Any]) -> Dict[str, np.ndarray]:
        """Copy ``arrs`` into a staging slot (``to_device.stage`` span);
        the source arrays are dead to this pool after the call."""
        sp = _trace.begin("to_device.stage", "transfer")
        try:
            slot = None
            for i, s in enumerate(self._free):
                if self._matches(s, arrs):
                    slot = self._free.pop(i)
                    break
            if slot is None:
                slot = {k: np.empty(np.shape(v), np.asarray(v).dtype)
                        for k, v in arrs.items()}
            for k, v in arrs.items():
                np.copyto(slot[k], v)
        finally:
            self.assemble_s += sp.end(in_flight=self.in_flight + 1)
        self.in_flight += 1
        _METRICS.gauge("device.staging").set(self.in_flight)
        return slot

    def release(self, slot: Dict[str, np.ndarray]) -> None:
        """Transfer drained: recycle the slot (ownership passes to the
        consumer's aliasing device arrays on alias-unsafe backends)."""
        self.in_flight -= 1
        _METRICS.gauge("device.staging").set(self.in_flight)
        if not self.alias_unsafe and len(self._free) < self.slots:
            self._free.append(slot)

    def reset_epoch(self) -> None:
        self.assemble_s = 0.0


def _backend_aliases(sharding) -> bool:
    """True when device_put on the target may alias host numpy memory
    (the CPU backend)."""
    if sharding is None:
        return jax.default_backend() == "cpu"
    if hasattr(sharding, "platform"):  # a Device
        return sharding.platform == "cpu"
    devs = getattr(sharding, "device_set", None)  # a Sharding
    if devs:
        return next(iter(devs)).platform == "cpu"
    return jax.default_backend() == "cpu"


def device_prefetch(host_batches: Iterable[Dict[str, Any]], size: int = 2,
                    sharding=None,
                    staging: bool = False) -> Iterator[Dict[str, Any]]:
    """Yield device-resident batches with ``size`` transfers in flight.

    ``sharding`` may be a jax.sharding.Sharding (multi-device placement) or
    None (default device). Structure of each batch (dict/pytree of numpy
    arrays) is preserved.

    Without staging, batches must own their buffers (or stay leased)
    until their transfer completes: up to ``size`` device_puts are in
    flight while the source iterator advances. Ephemeral native-parser
    views (RowBlock.lease set) must be copied or lease-detached by the
    producing iterator — ShardedRowBlockIter's pad_to_bucket does this
    by construction.

    ``staging=True`` (dict batches only) routes every batch through a
    reusable :class:`HostStaging` pair: the source arrays are free the
    moment the staged copy lands, ≥2 batches stay in flight, and each
    yielded batch is blocked-until-ready with ``to_device.stage`` /
    ``device.xfer`` spans proving the copy/transfer overlap.
    """
    queue: collections.deque = collections.deque()

    def _put(batch):
        if sharding is None:
            return jax.tree.map(jax.device_put, batch)
        return jax.tree.map(lambda a: jax.device_put(a, sharding), batch)

    it = iter(host_batches)
    if not staging:
        try:
            for _ in range(size):
                queue.append(_put(next(it)))
        except StopIteration:
            pass
        while queue:
            out = queue.popleft()
            try:
                queue.append(_put(next(it)))
            except StopIteration:
                pass
            yield out
        return

    pool = HostStaging(slots=size, alias_unsafe=_backend_aliases(sharding))

    def _enqueue():
        batch = next(it)  # StopIteration propagates to the caller
        slot = pool.stage(batch)
        return _put(slot), slot, time.perf_counter()

    def _drain(entry):
        fut, slot, t_enq = entry
        jax.block_until_ready(fut)
        rec = _trace.active()
        if rec is not None:
            # the full async window, enqueue → ready: overlaps the NEXT
            # batch's to_device.stage span when staging does its job
            rec.complete("device.xfer", t_enq,
                         time.perf_counter() - t_enq, "transfer")
        pool.release(slot)
        return fut

    try:
        for _ in range(size):
            queue.append(_enqueue())
    except StopIteration:
        pass
    while queue:
        entry = queue.popleft()
        try:
            queue.append(_enqueue())
        except StopIteration:
            pass
        yield _drain(entry)


class DeviceIter:
    """DataIter-protocol wrapper around device_prefetch
    (reference: ThreadedIter's consumer API, device-side)."""

    def __init__(self, host_iter_factory: Callable[[], Iterable],
                 size: int = 2, sharding=None, staging: bool = False):
        self._factory = host_iter_factory
        self._size = size
        self._sharding = sharding
        self._staging = staging
        self._gen: Optional[Iterator] = None
        self._value = None

    def before_first(self) -> None:
        self._gen = device_prefetch(self._factory(), self._size,
                                    self._sharding, self._staging)
        self._value = None

    def next(self) -> bool:
        if self._gen is None:
            self.before_first()
        try:
            self._value = next(self._gen)
            return True
        except StopIteration:
            self._value = None
            return False

    def value(self):
        return self._value

    def __iter__(self):
        self.before_first()
        while self.next():
            yield self.value()
