"""Scheduler stage spans (obs/tracing.py `stage`, `watch_gc`).

- a staged run under a jax.profiler trace puts every stage span on the
  host plane, and one batch's spans share one `batch` id across the
  loop, dispatch, settle and commit threads;
- a collection inside a trace gives a `ktpu.gc.gen2` span and raises
  the `gc_pause` phase and `process_gc_pause_seconds_total`; one hook
  per process, however many schedulers;
- the ledger-dirty barrier and the apply wait are counted in the
  pipeline's busy totals, and reset_stats zeroes them;
- `queue_wait` / `in_flight` equal the workqueue's own per-pod sums;
- with no profiler capture open the existing phases keep their clocks: wall
  time where they were wall, thread CPU time where they were CPU, read
  against sleeps injected into each stage's work.
"""

import asyncio
import gc
import glob
import os
import threading
import time
import warnings

import jax
import pytest
from jax.profiler import ProfileData

from kubernetes_tpu.apiserver.store import ObjectStore
from kubernetes_tpu.obs import tracing
from kubernetes_tpu.obs.metrics import REGISTRY
from kubernetes_tpu.perf.fixtures import make_nodes, make_pods
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.scheduler import pipeline as pipeline_mod
from kubernetes_tpu.scheduler.driver import SchedulerMetrics
from kubernetes_tpu.state import Capacities

CAPS = Capacities(num_nodes=64, batch_pods=8)

STAGE_SPANS = (
    "ktpu.queue.get_batch", "ktpu.encode", "ktpu.loop.wait_capacity",
    "ktpu.dispatch.idle", "ktpu.dispatch.barrier_wait",
    "ktpu.dispatch.flush", "ktpu.dispatch.solve", "ktpu.settle.readback",
    "ktpu.commit.apply_wait", "ktpu.apply", "ktpu.commit.ledger",
    "ktpu.events.build", "ktpu.events.install", "ktpu.gc.gen1",
    "ktpu.gc.gen2")


def _dirty_hook(sched, sleep_s):
    """solve_fault_hook: every solve dirties the host ledger (as a
    delete does) and takes `sleep_s`, so the next dispatch finds a batch
    in flight and waits at the barrier."""
    def hook(_keys):
        with sched._state_lock:
            sched.statedb.mark_ledger_dirty()
        time.sleep(sleep_s)
    return hook


async def _schedule(sched, store, n_pods):
    for node in make_nodes(8, cpu="16", memory="32Gi"):
        store.create(node)
    await sched.start()
    for pod in make_pods(n_pods, cpu="100m", memory="64Mi"):
        store.create(pod)
    await asyncio.sleep(0)
    done = 0
    for _ in range(400):
        done += await sched.schedule_pending(wait=0.05)
        if done >= n_pods and not sched.inflight_batches:
            break
    await sched._drain_events_async()
    assert done == n_pods
    return done


def _host_events(trace_dir):
    """[(line index, name, {stat: value})] of the newest trace's host
    plane."""
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith("ktpu."):
                        out.append(((plane.name, i), ev.name,
                                    dict(ev.stats)))
    return out


def test_stage_spans_on_the_profiler_clock(tmp_path):
    async def run():
        store = ObjectStore()
        sched = Scheduler(store, caps=CAPS)
        assert sched._staged is not None
        sched._staged.depth = 2
        sched.solve_fault_hook = _dirty_hook(sched, 0.02)
        jax.profiler.start_trace(str(tmp_path))
        try:
            gc.collect(1)
            gc.collect(2)
            await _schedule(sched, store, 48)
        finally:
            jax.profiler.stop_trace()
            sched.stop()

    asyncio.run(run())
    events = _host_events(str(tmp_path))
    names = {name for _, name, _ in events}
    missing = [s for s in STAGE_SPANS if s not in names]
    assert not missing, missing
    # one batch, one id, on four threads: the loop (encode, apply), the
    # dispatch, settle and commit stage threads
    rows: dict = {}
    for line, name, stats in events:
        if "batch" in stats:
            rows.setdefault(stats["batch"], {}).setdefault(name, set()).add(
                line)
    full = [b for b, by in rows.items()
            if {"ktpu.encode", "ktpu.apply", "ktpu.dispatch.solve",
                "ktpu.settle.readback", "ktpu.commit.ledger"} <= set(by)]
    assert len(full) >= 4, rows
    for b in full:
        by = rows[b]
        assert by["ktpu.encode"] == by["ktpu.apply"]       # the loop row
        lines = [by[n] for n in ("ktpu.encode", "ktpu.dispatch.solve",
                                 "ktpu.settle.readback",
                                 "ktpu.commit.ledger")]
        assert all(len(ls) == 1 for ls in lines)
        assert len(set().union(*lines)) == 4, by


def test_gc_pause_span_phase_and_counter(tmp_path):
    fam = REGISTRY.counter("process_gc_pause_seconds_total",
                           labels=("generation",))
    sched = Scheduler(ObjectStore(), caps=CAPS)
    assert gc.callbacks.count(tracing._on_gc) == 1
    n_callbacks = len(gc.callbacks)
    # the benchmark swaps the metrics at a window's start: pauses land
    # in whichever object is current when the collection ends
    sched.metrics = SchedulerMetrics()
    before = fam.labels("2").value
    jax.profiler.start_trace(str(tmp_path))
    try:
        gc.collect(2)
    finally:
        jax.profiler.stop_trace()
    assert fam.labels("2").value > before
    assert sched.metrics.phase_s["gc_pause"] > 0.0
    spans = [name for _, name, _ in _host_events(str(tmp_path))]
    assert "ktpu.gc.gen2" in spans
    keep = [Scheduler(ObjectStore(), caps=CAPS) for _ in range(50)]
    assert len(gc.callbacks) == n_callbacks
    del keep


def test_barrier_and_apply_waits_counted():
    async def run():
        store = ObjectStore()
        sched = Scheduler(store, caps=CAPS)
        sched.solve_fault_hook = _dirty_hook(sched, 0.03)
        try:
            await _schedule(sched, store, 32)
            busy = sched._staged.snapshot()["stage_busy_frac"]
            assert busy["barrier_wait"] > 0.0, busy
            assert busy["apply_wait"] >= 0.0
            assert sched._staged.busy["apply_wait"] > 0.0
            sched._staged.reset_stats()
            assert sched._staged.busy["barrier_wait"] == 0.0
            assert sched._staged.busy["apply_wait"] == 0.0
        finally:
            sched.stop()

    asyncio.run(run())


def _hist_sum(name):
    fam = REGISTRY.get(name)
    return fam.labels("scheduler").sum if fam is not None else 0.0


def test_queue_waits_equal_the_workqueue_observations():
    async def run():
        store = ObjectStore()
        sched = Scheduler(store, caps=CAPS)
        q0 = _hist_sum("workqueue_queue_duration_seconds")
        w0 = _hist_sum("workqueue_work_duration_seconds")
        try:
            await _schedule(sched, store, 40)
            return (sched.metrics,
                    _hist_sum("workqueue_queue_duration_seconds") - q0,
                    _hist_sum("workqueue_work_duration_seconds") - w0)
        finally:
            sched.stop()

    metrics, queued, worked = asyncio.run(run())
    assert queued > 0.0 and worked > 0.0
    assert metrics.phase_s["queue_wait"] == pytest.approx(queued, rel=1e-9)
    assert metrics.phase_s["in_flight"] == pytest.approx(worked, rel=1e-9)
    per_pod = metrics.snapshot()["phase_us_per_pod"]
    assert metrics.phase_pods == 40
    assert per_pod["queue_wait"] == pytest.approx(1e6 * queued / 40,
                                                  abs=0.011)
    assert per_pod["in_flight"] == pytest.approx(1e6 * worked / 40,
                                                 abs=0.011)
    # sums, not batch phases: the phase histogram never sees them
    hist = metrics.phase_histograms()
    assert "queue_wait" not in hist and "in_flight" not in hist


SLEEP_S = 0.02


@pytest.fixture(scope="module")
def slept_run():
    """One staged run with a fixed sleep inside each stage's work and no
    profiler capture: (phase_s, pipeline busy seconds, sleep counts)."""
    counts = {k: 0 for k in ("encode", "flush", "solve", "readback",
                             "bind", "commit", "install")}

    def slept(key, fn):
        def wrapper(*a, **kw):
            counts[key] += 1
            time.sleep(SLEEP_S)
            return fn(*a, **kw)
        return wrapper

    real_readback = pipeline_mod.record_readback

    async def run():
        store = ObjectStore()
        sched = Scheduler(store, caps=CAPS)
        for node in make_nodes(8, cpu="16", memory="32Gi"):
            store.create(node)
        await sched.start()
        sched.encode_cache.encode_packed_into = slept(
            "encode", sched.encode_cache.encode_packed_into)
        real_flush = sched.statedb.flush

        def flush():
            if threading.current_thread().name == "ktpu-dispatch-stage":
                counts["flush"] += 1
                time.sleep(SLEEP_S)
            return real_flush()

        sched.statedb.flush = flush
        sched.solve_fault_hook = lambda _keys: slept("solve", lambda: 0)()
        pipeline_mod.record_readback = slept("readback", real_readback)
        store.bind_many = slept("bind", store.bind_many)
        sched.statedb.commit_batch = slept("commit",
                                           sched.statedb.commit_batch)
        sched.events.install_many = slept("install",
                                          sched.events.install_many)
        try:
            for pod in make_pods(32, cpu="100m", memory="64Mi"):
                store.create(pod)
            await asyncio.sleep(0)
            done = 0
            for _ in range(400):
                done += await sched.schedule_pending(wait=0.05)
                if done >= 32 and not sched.inflight_batches:
                    break
            await sched._drain_events_async()
            assert done == 32
            return dict(sched.metrics.phase_s), dict(sched._staged.busy)
        finally:
            pipeline_mod.record_readback = real_readback
            sched.stop()

    phases, busy = asyncio.run(run())
    return phases, busy, counts


@pytest.mark.parametrize("phase", ["encode", "flush", "dispatch",
                                   "settle_wait", "bind", "commit",
                                   "events_async"])
def test_phase_clocks_unchanged(slept_run, phase):
    phases, busy, n = slept_run
    got = phases[phase]
    slept = {k: v * SLEEP_S for k, v in n.items()}
    assert n["solve"] >= 4 and n["install"] >= 1
    if phase == "encode":
        # thread CPU: 32 slept encodes leave the phase well below them
        assert n["encode"] >= 32
        assert got < 0.5 * slept["encode"]
    elif phase == "flush":
        # thread CPU inside the wall-clocked dispatch, which holds both
        # the flush's and the solve's sleeps
        assert got <= phases["dispatch"] - slept["flush"] - slept["solve"]
    elif phase == "dispatch":
        assert got >= slept["flush"] + slept["solve"]
        assert got == pytest.approx(busy["dispatch"], rel=1e-9)
    elif phase == "settle_wait":
        assert got >= slept["readback"]
        assert got == pytest.approx(busy["settle"], rel=1e-9)
    elif phase == "bind":
        # thread CPU of the apply, whose wall (busy) holds the sleeps
        assert busy["apply"] >= slept["bind"]
        assert got <= busy["apply"] - 0.9 * slept["bind"]
    elif phase == "commit":
        assert busy["commit"] >= slept["commit"]
        assert got <= busy["commit"] - 0.9 * slept["commit"]
    else:
        assert got >= slept["install"]


class _Phases:
    def __init__(self):
        self.phase_s = {}

    def add_phase(self, name, seconds):
        self.phase_s[name] = self.phase_s.get(name, 0.0) + seconds


def test_stage_without_metrics_reports_to_the_enclosing_stage():
    """A stage given no metrics (StateDB's `ktpu.flush.upload_full`)
    adds its phase to the innermost enclosing stage's metrics, records
    nothing outside one, and an asyncio task does not see a stage that
    another task holds open across an await."""
    outer, other = _Phases(), _Phases()
    with tracing.stage("outer", metrics=outer, phase="flush"):
        with tracing.stage("inner", phase="upload_full"):
            pass
    assert set(outer.phase_s) == {"flush", "upload_full"}
    with tracing.stage("alone", phase="upload_full"):
        pass                                   # no enclosing stage
    assert tracing._STAGE_METRICS.get() is None

    async def holder(opened, release):
        with tracing.stage("held", metrics=other, phase="held"):
            opened.set()
            await release.wait()

    async def bystander(opened):
        await opened.wait()
        with tracing.stage("bystander", phase="bystander"):
            pass

    async def run():
        opened, release = asyncio.Event(), asyncio.Event()
        task = asyncio.create_task(holder(opened, release))
        await bystander(opened)
        release.set()
        await task

    asyncio.run(run())
    assert set(other.phase_s) == {"held"}
