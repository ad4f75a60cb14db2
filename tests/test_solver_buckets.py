"""The batch ladder: a batch is solved at the smallest power-of-two row
count that holds it (state/pod_batch.solve_rows). The rung solve answers
exactly as the full-capacity solve of the same encoded batch; every rung
of a solver variant compiles when the variant is first met, so later
batches of any size compile nothing; and the scheduler counts the batches
per rung and the padding rows it solved."""

import asyncio
import functools

import jax.monitoring
import numpy as np
import pytest

from kubernetes_tpu.apiserver import ObjectStore
from kubernetes_tpu.obs.metrics import Registry
from kubernetes_tpu.obs.profiling import CompileRegistry
from kubernetes_tpu.perf.fixtures import make_nodes, make_pods
from kubernetes_tpu.preemption import build_victim_table
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.scheduler import driver as driver_mod
from kubernetes_tpu.state import Capacities
from kubernetes_tpu.state.pod_batch import (
    blob_col,
    empty_batch,
    pack_batch,
    packed_batch_flags,
    solve_rows,
    solve_rungs,
)

CAPS = Capacities(num_nodes=16, batch_pods=64, victim_slots=8)
COUNTS = (1, 7, 8, 9, 33, 64)
GANG = 4   # rows a gang group spans
FIELDS = ("assignments", "scores", "feasible_counts", "new_requested",
          "new_nonzero", "new_port_count", "new_podsel", "new_term",
          "new_vol_any", "new_vol_rw", "new_attach", "preempt_node",
          "victim_count", "rr_end")


def test_ladder_rungs():
    assert solve_rungs(4096) == (512, 1024, 2048, 4096)
    assert solve_rungs(64) == (8, 16, 32, 64)
    assert [solve_rows(n, 64) for n in COUNTS] == [8, 8, 8, 16, 64, 64]
    # solversvc's buckets: its own floor, the same rule
    assert [solve_rows(n, 64, 4) for n in (0, 1, 4, 5, 64)] == \
        [4, 4, 4, 8, 64]


@functools.cache
def _cluster():
    """16 nodes of 4 CPUs, each holding two priority-0 residents of 1.5
    CPUs: 32 batch pods of 0.5 CPU fit, the rest of a 64-pod batch does
    not, and a priority batch finds victims."""
    sched = Scheduler(ObjectStore(), caps=CAPS)
    nodes = make_nodes(16, cpu="4", memory="8Gi")
    for node in nodes:
        sched.statedb.upsert_node(node)
    residents = make_pods(32, cpu="1500m", memory="1Gi",
                          name_prefix="resident")
    for i, pod in enumerate(residents):
        pod.spec.node_name = nodes[i % len(nodes)].metadata.name
        sched.statedb.add_pod(pod)
    victims, _slots = build_victim_table(
        sched.statedb, {p.key: p for p in residents})
    assert victims is not None
    return sched, victims


def _encode(sched, n: int, variant: str):
    pods = make_pods(n, cpu="500m", memory="256Mi",
                     name_prefix=f"{variant}-{n}-")
    if variant == "preempt":
        for pod in pods:
            pod.spec.priority = 10
    fblob, iblob = pack_batch(empty_batch(CAPS), CAPS)
    for i, pod in enumerate(pods):
        sched.encode_cache.encode_packed_into(fblob, iblob, i, pod)
    if variant == "gang":
        # groups of GANG rows; the last group, which ends at row n - 1
        # (a rung edge where n is a rung), asks for one member more than
        # it has, so the solve must revert it
        gid = blob_col(fblob, iblob, "gang_id", CAPS)
        gmin = blob_col(fblob, iblob, "gang_min", CAPS)
        last = (n - 1) // GANG
        for i in range(n):
            g = i // GANG
            size = min(GANG, n - g * GANG)
            gid[i] = g + 1
            gmin[i] = size + 1 if g == last else size
    flags = packed_batch_flags(fblob, iblob, n, sched.statedb.table, CAPS)
    assert flags.gang == (variant == "gang")
    assert flags.preempt == (variant == "preempt")
    return fblob, iblob, flags


@pytest.mark.parametrize("variant", ["baseline", "gang", "preempt"])
@pytest.mark.parametrize("n", COUNTS)
def test_rung_solve_matches_full_solve(n, variant):
    sched, table = _cluster()
    fblob, iblob, flags = _encode(sched, n, variant)
    victims = table if variant == "preempt" else None
    fn = sched._get_schedule_fn(flags)
    with sched._state_lock:
        state = sched.statedb.flush()
    rr = np.uint32(5)
    rows = solve_rows(n, CAPS.batch_pods)
    full = fn(state, fblob, iblob, rr, victims)
    rung = fn(state, fblob[:rows], iblob[:rows], rr, victims)
    assert np.asarray(rung.assignments).shape == (rows,)
    for name in FIELDS:
        want = np.asarray(getattr(full, name))
        got = np.asarray(getattr(rung, name))
        if name in ("assignments", "scores", "feasible_counts",
                    "preempt_node", "victim_count"):
            # rows past the rung are padding in the full solve
            assert (want[rows:] == (-1 if name in ("assignments",
                                                   "preempt_node")
                                    else 0)).all(), name
            want = want[:rows]
        np.testing.assert_array_equal(got, want, err_msg=name)
    placed = np.asarray(rung.assignments)[:n]
    if variant == "gang":
        # the last group is below quorum: none of its members placed
        assert (placed[(n - 1) // GANG * GANG:] == -1).all()
    elif n > 32:
        assert (placed >= 0).sum() == 32
        if variant == "preempt":
            assert (np.asarray(rung.preempt_node)[:n] >= 0).any()


async def _waves(sched, store, sizes, after_first=None):
    """One batch of each size: create the pods, wait for all of them to
    reach the queue, then pop them as one batch and settle it."""
    for w, k in enumerate(sizes):
        for pod in make_pods(k, name_prefix=f"wave{w}-"):
            store.create(pod)
        async with asyncio.timeout(30):
            while len(sched.queue) < k:
                await asyncio.sleep(0.005)
            assert await sched.schedule_pending(wait=0) == k
        if w == 0 and after_first is not None:
            after_first()


def _scheduler(store):
    for node in make_nodes(16):
        store.create(node)
    return Scheduler(store, caps=Capacities(num_nodes=16, batch_pods=16))


def test_first_batch_compiles_every_rung(monkeypatch):
    reg = CompileRegistry(registry=Registry())
    monkeypatch.setattr(driver_mod, "COMPILES", reg)
    events: list = []

    def on_duration(event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            events.append(kw.get("fun_name"))
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    seen: dict = {}

    def after_first():
        seen["misses"] = sched.metrics._c_jit_misses.labels().value
        seen["variants"] = set(reg.snapshot())
        seen["events"] = len(events)

    async def run():
        nonlocal sched
        store = ObjectStore()
        sched = _scheduler(store)
        await sched.start()
        await _waves(sched, store, (1, 3, 6, 13, 2), after_first)
        sched.stop()

    sched = None
    try:
        asyncio.run(run())
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert seen["misses"] == 1
    assert seen["variants"] == {f"baseline@p{r}" for r in (2, 4, 8, 16)}
    # batches at every rung after the first: no miss, no new variant,
    # nothing compiled
    assert sched.metrics._c_jit_misses.labels().value == 1
    assert sched.metrics._c_jit_hits.labels().value == 4
    assert set(reg.snapshot()) == seen["variants"]
    assert len(events) == seen["events"]
    for rec in reg.snapshot().values():
        assert rec["calls"] >= 2   # the warm call and a batch's solve


def test_solve_rows_counters():
    async def run():
        store = ObjectStore()
        sched = _scheduler(store)
        await sched.start()
        await _waves(sched, store, (1, 3, 6, 13, 2, 16, 5))
        sched.stop()
        return sched

    sched = asyncio.run(run())
    # the 5-pod batch reuses the blob pair the 16-pod batch filled: rows
    # 5-7 of its rung must have been cleared, or the device ledger would
    # carry charges the host never committed
    db = sched.statedb
    for name in ("requested", "nonzero_requested"):
        np.testing.assert_array_equal(np.asarray(getattr(db._device, name)),
                                      getattr(db.host, name), err_msg=name)
    m = sched.metrics
    batches = {2: 2, 4: 1, 8: 2, 16: 2}
    padding = 1 + 1 + 2 + 3 + 0 + 0 + 3
    assert m.solve_batches == batches
    assert m.solve_padding_rows == padding
    assert m.snapshot()["solve_rows"] == {"batches": batches,
                                          "padding_rows": padding}
    for rows, count in batches.items():
        assert m._c_solve_batches.labels(str(rows)).value == count
    assert m._c_solve_padding.labels().value == padding
    text = m.registry.render()
    assert 'scheduler_solve_batches_total{rows="8"} 2' in text
    assert f"scheduler_solve_padding_rows_total {padding}" in text
