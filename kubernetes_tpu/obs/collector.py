"""The process's collection policy: a full collection once the heap has
doubled, not after every quarter of promotions.

CPython starts a full (generation 2) collection once the objects promoted
since the last one exceed a quarter of those that survived it. That rule
follows churn, not growth. The control plane deletes and replaces its
residents all the time, so promotions pile up and full collections recur
over a heap that barely grows. And that heap holds almost no cycles: store
buckets, informer caches, Events and accounted pods are acyclic trees that
reference counting frees. Each full collection stops every thread for a
walk of the whole heap and finds next to nothing.

``install`` keeps the young generations' thresholds and puts the full
collection's out of reach. After each generation-1 collection it reads the
process's allocated blocks; once they pass twice what the last full
collection left (Go's GOGC=100), the threshold drops to 0, so CPython's
next automatic collection is a full one. (CPython still waits for the
quarter of promotions there: a heap that grew in untracked objects alone
has no new cycles to find.) Every full collection, whatever started it,
records the new base and puts the threshold back out of reach. So a
process that does make cycles still reclaims them as its heap grows, and
holds at most about its live heap in undetected garbage.

Counted in ``process_gc_full_collections_total{trigger}`` ("growth": the
collection ran while the growth trigger was armed; "other": an explicit
``gc.collect()``, the install's own among them) and
``process_gc_heap_blocks_base``.
"""

from __future__ import annotations

import gc
import sys
import threading

# generation 2's threshold when no growth is pending: the number of
# generation-1 collections since the last full one never reaches it
OUT_OF_REACH = 2**31 - 1

_STATE: dict = {"installed": False, "armed": False, "trigger": "other",
                "base": 0, "counters": None, "gauge": None}
_INSTALL_LOCK = threading.Lock()


def _young() -> tuple[int, int]:
    t0, t1, _ = gc.get_threshold()
    return t0, t1


def _on_gc(phase: str, info: dict) -> None:
    gen = info["generation"]
    st = _STATE
    if phase == "start":
        if gen == 2:
            st["trigger"] = "growth" if st["armed"] else "other"
        return
    if gen == 1 and not st["armed"]:
        if sys.getallocatedblocks() > 2 * st["base"]:
            st["armed"] = True
            gc.set_threshold(*_young(), 0)
    elif gen == 2:
        st["counters"][st["trigger"]].inc()
        st["base"] = sys.getallocatedblocks()
        st["gauge"].set(st["base"])
        st["armed"] = False
        gc.set_threshold(*_young(), OUT_OF_REACH)


def install() -> None:
    """Install the policy for this process (once; later calls do
    nothing) and start it with one full collection, whose end records the
    first base."""
    st = _STATE
    with _INSTALL_LOCK:
        if st["installed"]:
            return
        from kubernetes_tpu.obs import metrics as _metrics

        fam = _metrics.REGISTRY.counter(
            "process_gc_full_collections_total",
            "Full (generation 2) collections, by what started them.",
            ("trigger",))
        # children made here, so that the callback takes no family's lock
        st["counters"] = {t: fam.labels(t) for t in ("growth", "other")}
        st["gauge"] = _metrics.REGISTRY.gauge(
            "process_gc_heap_blocks_base",
            "Allocated blocks the last full collection left; the next "
            "starts once the heap passes twice this.").labels()
        st["installed"] = True
        st["base"] = sys.getallocatedblocks()
        # first in line, so that pause timers registered before it count
        # the policy's own heap reads inside the collection they follow
        gc.callbacks.insert(0, _on_gc)
    gc.collect()
