"""The process's collection policy (obs/collector.py): a full collection
once the heap has doubled, not after every quarter of promotions.

Each case runs in a fresh interpreter, since the policy is process-wide:

- churn without growth (waves of 200k long-lived objects created,
  promoted and freed over a resident heap) runs no automatic full
  collection under the policy, and at least one under CPython's default;
- growth past twice the base runs exactly one automatic full collection,
  and it reclaims a reference cycle promoted before the growth;
- an explicit ``gc.collect()`` records a new base;
- two ``Scheduler.start()`` calls install the policy once;
- ``process_gc_full_collections_total{trigger}`` counts each case.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PRELUDE = """
import gc, json, sys, weakref
from kubernetes_tpu.obs import collector
from kubernetes_tpu.obs.metrics import REGISTRY

full = []          # one entry per full collection, whatever started it
gc.callbacks.append(lambda phase, info: full.append(1)
                    if phase == "stop" and info["generation"] == 2 else None)


def counts():
    fam = REGISTRY.get("process_gc_full_collections_total")
    return {t: fam.labels(t).value if fam else 0.0
            for t in ("growth", "other")}


def grow_to(keep, blocks):
    # lists are always tracked: each one promoted counts toward the
    # quarter of promotions CPython also asks of a full collection
    while sys.getallocatedblocks() < blocks:
        keep.extend([i] for i in range(1000))


def out(**kw):
    kw.update(counts=counts(), threshold=gc.get_threshold(),
              base=collector._STATE["base"],
              blocks=sys.getallocatedblocks(),
              out_of_reach=collector.OUT_OF_REACH)
    print(json.dumps(kw))
"""


def _run(body: str, timeout: float = 120.0) -> dict:
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", PRELUDE + textwrap.dedent(body)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


CHURN = """
ballast = [[i] for i in range(600_000)]   # a resident heap
if POLICY:
    collector.install()
else:
    gc.collect()
del full[:]
for _ in range(5):
    wave = [[i] for i in range(200_000)]
    del wave
out(full=len(full))
"""


@pytest.mark.parametrize("policy", [True, False], ids=["policy", "default"])
def test_churn_without_growth(policy):
    got = _run(f"POLICY = {policy}\n" + CHURN)
    if policy:
        assert got["full"] == 0, got
        assert got["counts"] == {"growth": 0.0, "other": 1.0}
        assert got["threshold"][2] == got["out_of_reach"]
        # the young generations keep CPython's thresholds
        assert got["threshold"][:2] == [700, 10]
    else:
        # the control: CPython's own rule runs full collections on churn
        assert got["full"] >= 1, got
        assert got["counts"] == {"growth": 0.0, "other": 0.0}


def test_growth_runs_one_full_collection_and_frees_a_cycle():
    got = _run("""
    class Peer:
        pass

    collector.install()
    base = collector._STATE["base"]
    a, b = Peer(), Peer()
    a.peer, b.peer = b, a
    freed = []
    ref = weakref.ref(a, lambda _: freed.append(1))
    gc.collect(1)             # the cycle now sits in the oldest generation
    del a, b
    del full[:]
    keep = []
    grow_to(keep, 3 * base)   # past twice the base, short of twice the next
    out(full=len(full), freed=len(freed), first_base=base)
    """)
    assert got["full"] == 1, got
    assert got["freed"] == 1
    assert got["counts"] == {"growth": 1.0, "other": 1.0}
    assert got["base"] > 2 * got["first_base"]
    assert got["threshold"][2] == got["out_of_reach"]


def test_explicit_collect_records_a_new_base():
    got = _run("""
    collector.install()
    first = collector._STATE["base"]
    keep = []
    grow_to(keep, int(1.5 * first))
    gc.collect()
    second = collector._STATE["base"]
    del full[:]
    # past twice the first base, short of twice the second: no full
    # collection, so the explicit one moved the base
    grow_to(keep, int(2.5 * first))
    out(full=len(full), first=first, second=second,
        gauge=REGISTRY.get("process_gc_heap_blocks_base").labels().value)
    """)
    assert got["second"] >= 1.4 * got["first"], got
    assert got["gauge"] == got["second"]
    assert got["full"] == 0
    assert got["counts"] == {"growth": 0.0, "other": 2.0}
    assert got["threshold"][2] == got["out_of_reach"]


def test_install_once_across_two_scheduler_starts():
    got = _run("""
    import asyncio

    from kubernetes_tpu.apiserver.store import ObjectStore
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.state import Capacities

    async def main():
        store = ObjectStore()
        caps = Capacities(num_nodes=64, batch_pods=8)
        scheds = [Scheduler(store, caps=caps) for _ in range(2)]
        before = gc.get_threshold()
        for s in scheds:
            await s.start()
        collector.install()
        for s in scheds:
            s.stop()
        return before

    before = asyncio.run(main())
    out(before=before, hooks=gc.callbacks.count(collector._on_gc))
    """, timeout=300.0)
    # building a Scheduler does not install it; starting one does, once
    assert got["before"][2] == 10, got
    assert got["hooks"] == 1
    assert got["counts"] == {"growth": 0.0, "other": 1.0}
    assert got["threshold"][2] == got["out_of_reach"]
