"""Rate-limited work queues with per-item exponential backoff.

Combines the semantics of client-go's workqueue (dedup: an item re-added
while being processed is reprocessed once, never concurrently;
client-go/util/workqueue/) and the scheduler's PodBackoff (exponential
per-pod delay, doubling to a max of 60s by default; reference
plugin/pkg/scheduler/util/backoff_utils.go and factory.go:897
MakeDefaultErrorFunc).
"""

from __future__ import annotations

import asyncio
import heapq
import time
from typing import Hashable


class Backoff:
    """Per-item exponential backoff (backoff_utils.go semantics)."""

    def __init__(self, initial: float = 1.0, max_duration: float = 60.0):
        self.initial = initial
        self.max_duration = max_duration
        self._durations: dict[Hashable, float] = {}
        self._last: dict[Hashable, float] = {}

    def next_delay(self, item: Hashable) -> float:
        cur = self._durations.get(item, 0.0)
        nxt = min(cur * 2 if cur else self.initial, self.max_duration)
        self._durations[item] = nxt
        self._last[item] = time.monotonic()
        return nxt

    def reset(self, item: Hashable) -> None:
        self._durations.pop(item, None)
        self._last.pop(item, None)

    def gc(self, max_age: float = 600.0) -> None:
        cutoff = time.monotonic() - max_age
        for item in [i for i, t in self._last.items() if t < cutoff]:
            self._durations.pop(item, None)
            self._last.pop(item, None)


class BackoffQueue:
    """Async dedup queue with optional delayed re-adds.

    - `add(item)`: enqueue now (no-op if queued; marked dirty if processing)
    - `add_after(item, delay)`: enqueue once `delay` elapses
    - `get()` / `get_batch(n)`: pop items, marking them processing
    - `done(item)`: finish processing; if dirtied meanwhile, requeue
    """

    def __init__(self, name: str | None = None, registry=None):
        self._queue: list[Hashable] = []
        self._queued: set[Hashable] = set()
        self._processing: set[Hashable] = set()
        self._dirty: set[Hashable] = set()
        self._delayed: list[tuple[float, int, Hashable]] = []
        self._seq = 0
        self._event = asyncio.Event()
        self._closed = False
        # metrics engage only for NAMED queues (client-go's
        # NewNamedRateLimitingQueue contract — unnamed queues stay free of
        # per-item accounting); `name` may be assigned after construction
        # (controllers learn their name post-__init__) and children
        # re-resolve lazily
        self.name = name
        self._registry = registry
        self._mx: tuple | None = None
        self._added_at: dict[Hashable, float] = {}
        self._started_at: dict[Hashable, float] = {}
        # optional sink for the same per-item timings (named queues only):
        # wait_sink("queue_wait", add-to-pop seconds summed over a popped
        # batch) and wait_sink("in_flight", one item's pop-to-done seconds)
        self.wait_sink = None

    def _metrics(self) -> tuple | None:
        """(name, depth, adds, retries, queue_dur, work_dur) children for
        the current queue name — the client-go workqueue metrics provider
        families (workqueue/metrics.go), labeled by queue."""
        if self.name is None:
            return None
        if self._mx is None or self._mx[0] != self.name:
            from kubernetes_tpu.obs import metrics as m

            reg = self._registry if self._registry is not None else m.REGISTRY
            lat_buckets = m.exponential_buckets(1e-5, 4.0, 10)
            self._mx = (
                self.name,
                reg.gauge("workqueue_depth",
                          "Current depth of the workqueue.",
                          ("name",)).labels(self.name),
                reg.counter("workqueue_adds_total",
                            "Total adds handled by the workqueue.",
                            ("name",)).labels(self.name),
                reg.counter("workqueue_retries_total",
                            "Total delayed (backoff) re-adds of items "
                            "requeued after a failure.",
                            ("name",)).labels(self.name),
                reg.histogram("workqueue_queue_duration_seconds",
                              "How long an item stays queued before "
                              "processing starts.",
                              ("name",), buckets=lat_buckets
                              ).labels(self.name),
                reg.histogram("workqueue_work_duration_seconds",
                              "How long processing an item takes.",
                              ("name",), buckets=lat_buckets
                              ).labels(self.name),
            )
        return self._mx

    def __len__(self) -> int:
        return len(self._queue)

    def add(self, item: Hashable) -> None:
        if item in self._processing:
            self._dirty.add(item)
            return
        if item in self._queued:
            return
        self._queued.add(item)
        self._queue.append(item)
        mx = self._metrics()
        if mx is not None:
            mx[2].inc()
            mx[1].set(len(self._queue))
            self._added_at[item] = time.monotonic()
        self._event.set()

    def add_after(self, item: Hashable, delay: float) -> None:
        if delay <= 0:
            self.add(item)
            return
        mx = self._metrics()
        if mx is not None:
            mx[3].inc()
        self._seq += 1
        heapq.heappush(self._delayed, (time.monotonic() + delay, self._seq, item))
        self._event.set()

    def done(self, item: Hashable) -> None:
        self._processing.discard(item)
        mx = self._metrics()
        if mx is not None:
            started = self._started_at.pop(item, None)
            if started is not None:
                busy = time.monotonic() - started
                mx[5].observe(busy)
                if self.wait_sink is not None:
                    self.wait_sink("in_flight", busy)
        if item in self._dirty:
            self._dirty.discard(item)
            self.add(item)

    def close(self) -> None:
        self._closed = True
        self._event.set()

    def _drain_delayed(self) -> float | None:
        """Move due delayed items into the queue; return seconds until the
        next delayed item (None if no delayed items)."""
        now = time.monotonic()
        while self._delayed and self._delayed[0][0] <= now:
            _, _, item = heapq.heappop(self._delayed)
            self.add(item)
        return self._delayed[0][0] - now if self._delayed else None

    async def get_batch(self, max_items: int, wait: float | None = None) -> list[Hashable]:
        """Pop up to max_items; blocks until at least one is available (or
        `wait` elapses -> empty list; queue closed -> empty list)."""
        deadline = time.monotonic() + wait if wait is not None else None
        while True:
            if self._closed:
                return []
            next_delay = self._drain_delayed()
            if self._queue:
                n = min(max_items, len(self._queue))
                items = self._queue[:n]
                del self._queue[:n]
                for item in items:
                    self._queued.discard(item)
                    self._processing.add(item)
                mx = self._metrics()
                if mx is not None:
                    now = time.monotonic()
                    observe = mx[4].observe
                    added_pop = self._added_at.pop
                    waited = 0.0
                    for item in items:
                        wait = now - added_pop(item, now)
                        observe(wait)
                        waited += wait
                        self._started_at[item] = now
                    mx[1].set(len(self._queue))
                    if self.wait_sink is not None:
                        self.wait_sink("queue_wait", waited)
                return items
            timeout = next_delay
            if deadline is not None:
                remain = deadline - time.monotonic()
                if remain <= 0:
                    return []
                timeout = min(timeout, remain) if timeout is not None else remain
            self._event.clear()
            try:
                await asyncio.wait_for(self._event.wait(), timeout)
            except asyncio.TimeoutError:
                if deadline is not None and time.monotonic() >= deadline:
                    return []

    async def get(self, wait: float | None = None) -> Hashable | None:
        items = await self.get_batch(1, wait=wait)
        return items[0] if items else None
