"""Double-buffered DEVICE prefetch: H2D transfer overlapped with compute.

The host-thread prefetch in data/pipeline.py hides decode/augment latency,
but the batch still crosses PCIe/ICI *inside* the step: Trainer.train_step
called `shard_batch` (a `jax.device_put`) on the critical path, so every
step paid the H2D transfer before it could dispatch. This module moves the device_put OFF the critical
path: a producer thread pads/shards the NEXT batch(es) onto the mesh while
the device executes the current step. jax's async dispatch makes the
transfer itself non-blocking, so a depth-2 buffer is enough for full
overlap; by the time the training loop asks for the batch, its buffers are
on (or streaming onto) the accelerator and `data_wait` collapses to a queue
get.

Observability rides the existing registry, next to the host-prefetch
gauges (data_prefetch_* in pipeline.py):

    device_prefetch_depth          placed batches ready at the consumer get
    device_prefetch_starved_total  gets that found the buffer empty
    device_prefetch_batches_total  placed batches handed to the step loop

With `group > 1` (the scan-multistep Trainer) the producer coalesces G host
batches into one stacked device batch per dispatch; a short tail (fewer
than G batches left in the epoch) is emitted as single-step items so the
stacked executable never sees a ragged shape (no recompiles).
"""
from __future__ import annotations

import queue
import threading
from typing import Callable, Iterable, Iterator, Optional


class PlacedBatch:
    """A device-resident batch + the host-side metadata the loop needs
    without a device fetch: `data` (the sharded pytree), `n` (valid
    examples, padding excluded), `group` (microsteps this item carries —
    1, or the multistep G for a stacked superstep batch)."""

    __slots__ = ("data", "n", "group")

    def __init__(self, data, n: int, group: int = 1):
        self.data = data
        self.n = int(n)
        self.group = int(group)


class DevicePrefetcher:
    """Wrap a host-batch iterable; yield `PlacedBatch`es placed ahead of
    consumption.

    place_one(batch)    -> PlacedBatch(group=1)
    place_group(batches)-> PlacedBatch(group=len(batches)); required when
                           group > 1, used for full groups only.

    Placement runs on the producer thread — `jax.device_put` dispatch is
    thread-safe and asynchronous, so the transfer overlaps both the host
    pipeline and device compute.
    """

    def __init__(self, place_one: Callable, depth: int = 2,
                 group: int = 1, place_group: Optional[Callable] = None,
                 name: str = "train", registry=None):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if group > 1 and place_group is None:
            raise ValueError("group > 1 requires place_group")
        self.place_one = place_one
        self.place_group = place_group
        self.depth = int(depth)
        self.group = max(1, int(group))
        self.name = name
        if registry is None:
            from deep_vision_tpu.obs.registry import get_registry

            registry = get_registry()
        labels = {"loader": name}
        self._g_depth = registry.gauge(
            "device_prefetch_depth",
            "device-placed batches ready when the consumer asked",
            labels=labels)
        self._c_starved = registry.counter(
            "device_prefetch_starved_total",
            "consumer gets that found no placed batch ready",
            labels=labels)
        self._c_batches = registry.counter(
            "device_prefetch_batches_total",
            "device-placed batches yielded", labels=labels)

    def __call__(self, source: Iterable) -> Iterator[PlacedBatch]:
        q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        sentinel = object()
        stop = threading.Event()
        err: list = []

        def put(item) -> bool:
            # bounded put that keeps observing stop: an abandoned consumer
            # (preemption broke the loop) leaves the queue full, and a
            # plain put would pin this thread forever
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.2)
                    return True
                except queue.Full:
                    continue
            return False

        def producer():
            try:
                pending = []
                for batch in source:
                    pending.append(batch)
                    if len(pending) < self.group:
                        continue
                    if self.group > 1:
                        placed = self.place_group(pending)
                    else:
                        placed = self.place_one(pending[0])
                    pending = []
                    if not put(placed):
                        return
                # tail: short of a full group — single-step items so the
                # stacked executable never compiles a ragged shape
                for batch in pending:
                    if not put(self.place_one(batch)):
                        return
            except BaseException as e:  # surfaced at the consumer's get
                err.append(e)
            finally:
                put(sentinel)

        t = threading.Thread(target=producer, daemon=True,
                             name=f"device-prefetch-{self.name}")
        t.start()
        first = True
        try:
            while True:
                depth = q.qsize()
                item = q.get()
                if item is sentinel:
                    break
                self._g_depth.set(depth)
                # the first get races the producer's warm-up fill and would
                # stamp phantom starvation on every healthy epoch
                if depth == 0 and not first:
                    self._c_starved.inc()
                first = False
                self._c_batches.inc()
                yield item
        finally:
            stop.set()
            try:  # unblock a producer stuck in put()
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            t.join(timeout=5)
        if err:
            raise err[0]
