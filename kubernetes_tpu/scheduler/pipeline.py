"""Staged scheduler pipeline: stage-per-thread batch processing.

The input-pipeline treatment from accelerator training stacks applied to
the scheduling loop: the compiled solver sustains ~50k pods/s on-device,
but one asyncio loop serially encoding, dispatching, settling, binding and
committing every batch caps e2e throughput at a fraction of that. Here the
batch loop is split into stages connected by queues:

    encode (event loop) | dispatch | settle | commit+bind

- **encode** stays on the event loop: informers, the EncodeCache and the
  workqueue are loop-owned, and encoding batch k+1 overlaps batch k's
  solve (which runs in the dispatch thread) by construction.
- **dispatch** (thread): ledger flush -> compiled solve -> adopt the
  output ledger for chaining -> start the async device->host copy. Runs
  FIFO in one thread so round-robin/ledger chaining stays serial.
- **settle** (thread): the blocking device->host readback plus row-list
  conversion — the per-batch transport wait leaves the loop entirely.
- **commit** (thread): marshals ONE apply closure back onto the event
  loop (bind + queue/backoff/event bookkeeping must run where the store
  and workqueue live), then mirrors the ledger into host numpy off-loop.

Thread discipline (ktpu-lint R1 extends to these workers): stage threads
never touch the asyncio loop except through `call_soon_threadsafe`
(wrapped by LoopCalls), and never block on `time.sleep` — all waits are
`threading.Event.wait`/condition timeouts, so shutdown is prompt.

Host StateDB/EncodeCache arrays are guarded by the scheduler's
`_state_lock` (an RLock): the loop mutates them from informer handlers
and encode, the dispatch thread reads them in flush(), and the commit
thread scatters into them in commit_batch().
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque

import numpy as np

from kubernetes_tpu.obs.profiling import record_readback
from kubernetes_tpu.obs.tracing import stage

log = logging.getLogger(__name__)

# stage workers park on their wake event at most this long between
# shutdown-flag checks; bounds stop() latency without polling hot
_IDLE_WAIT_S = 0.2


class LoopCalls:
    """Thread-safe closure marshalling onto the asyncio event loop.

    Stage threads push loop-affine work (store writes, workqueue ops)
    here; the loop runs it via `call_soon_threadsafe`. The pending deque
    is also drainable DIRECTLY by the loop thread (`drain()`), which is
    what makes the synchronous stop() path and mid-coroutine progress
    forcing possible without deadlocking on a busy loop.
    """

    def __init__(self):
        self._calls: deque = deque()
        self._loop = None

    def bind(self, loop) -> None:
        self._loop = loop

    def push(self, fn) -> None:
        """Enqueue `fn` to run on the loop thread (callable from any
        thread). If the loop is gone (teardown), the closure waits in the
        deque for a direct drain()."""
        self._calls.append(fn)
        loop = self._loop
        if loop is not None:
            try:
                loop.call_soon_threadsafe(self.drain)
            except RuntimeError:
                pass  # loop closed: drained directly by the stop() path

    def drain(self) -> None:
        """Run every pending closure (loop thread only)."""
        while True:
            try:
                fn = self._calls.popleft()
            except IndexError:
                return
            try:
                fn()
            except Exception:  # noqa: BLE001 — one closure must not
                log.exception("marshalled loop call failed")  # kill the rest

    def clear(self) -> None:
        self._calls.clear()


class EventShard:
    """Worker shard that coalesces per-batch event bursts off the loop.

    The driver buffers (obj, type, reason, message) entries per solved
    batch; this shard builds the Event objects (name formatting, metadata
    construction — the measured bulk of the 27-43 us/pod events cost) in
    a worker thread, then installs each (type, reason) group through ONE
    bulk store create marshalled back onto the loop, where the store and
    its watchers live. Recorder state (`_known`) is only ever touched on
    the loop (install path), so no locking is added to the recorder.
    """

    def __init__(self, recorder, calls: LoopCalls):
        self._recorder = recorder
        self._calls = calls
        self._pending: deque = deque()   # (entries, attempt) batches
        self._wake = threading.Event()
        self._progress = threading.Event()
        self._stopped = False
        self._thread: threading.Thread | None = None
        # loop-owned counters (submit and install both run on the loop)
        self.outstanding = 0
        self.installed_batches = 0
        self.built_entries = 0

    # ---- loop side ----

    def submit(self, entries: list[tuple], attempt: int = 0) -> None:
        """Hand one batch of (obj, type, reason, message) entries to the
        shard (loop thread)."""
        self.outstanding += 1
        self._pending.append((entries, attempt))
        self._ensure_thread()
        self._wake.set()

    def _install(self, built_groups, entries, attempt) -> None:
        """Publish pre-built event groups (runs on the loop)."""
        with stage("ktpu.events.install") as st:
            try:
                for sub, built, keys, event_type, reason in built_groups:
                    self._recorder.install_many(sub, built, keys,
                                                event_type, reason)
                self.installed_batches += 1
            except Exception:  # noqa: BLE001 — events are best-effort
                if attempt < 3:
                    log.warning("event install failed (attempt %d); "
                                "retrying", attempt + 1, exc_info=True)
                    self.submit(entries, attempt + 1)
                else:
                    log.error("event install failed %d times; dropping %d "
                              "events", attempt + 1, len(entries))
        self._recorder_metrics_hook(st.elapsed)
        self.outstanding -= 1

    # overridable seam: the scheduler points this at
    # metrics.add_phase("events_async", ...) so the loop-side install cost
    # stays visible in the phase breakdown
    def _recorder_metrics_hook(self, seconds: float) -> None:
        pass

    async def drain(self, timeout: float = 5.0) -> None:
        """Await until every submitted batch is installed (loop thread,
        loop running) — the request-response seam for tests and the
        pipeline-drained path."""
        import asyncio

        deadline = time.monotonic() + timeout
        while self.outstanding and time.monotonic() < deadline:
            self._calls.drain()
            if self.outstanding:
                await asyncio.sleep(0.001)
        self._calls.drain()

    def drain_sync(self, timeout: float = 5.0) -> None:
        """Force every outstanding batch through (stop() path; the loop
        may be busy inside stop() or already closed, so marshalled
        installs are executed directly and not-yet-built batches are
        recorded inline)."""
        deadline = time.monotonic() + timeout
        while self.outstanding and time.monotonic() < deadline:
            self._calls.drain()
            if not self.outstanding:
                break
            try:
                entries, _attempt = self._pending.popleft()
            except IndexError:
                # the worker holds a batch: wait for it to marshal
                self._progress.wait(0.002)
                self._progress.clear()
                continue
            try:
                self._recorder.record_grouped(entries)
            except Exception:  # noqa: BLE001 — best-effort at teardown
                log.warning("event drain failed; dropping %d events",
                            len(entries), exc_info=True)
            finally:
                self.outstanding -= 1
        self._calls.drain()

    def stop(self) -> None:
        self._stopped = True
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=1.0)

    def kill(self) -> None:
        """Hard abort (crash simulation): drop queued batches."""
        self._stopped = True
        self._pending.clear()
        self._wake.set()

    # ---- worker thread ----

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._events_stage, name="ktpu-events-stage",
                daemon=True)
            self._thread.start()

    def _events_stage(self) -> None:
        """Stage worker: pure Event construction off the loop. Touches
        the loop only via LoopCalls (call_soon_threadsafe)."""
        from kubernetes_tpu.utils.events import _group_entries

        while not self._stopped:
            if not self._wake.wait(timeout=_IDLE_WAIT_S):
                continue
            self._wake.clear()
            while not self._stopped:
                try:
                    entries, attempt = self._pending.popleft()
                except IndexError:
                    break
                built_groups = []
                with stage("ktpu.events.build"):
                    for event_type, reason, sub in _group_entries(entries):
                        built, keys = self._recorder.build_many(
                            sub, event_type, reason)
                        built_groups.append((sub, built, keys, event_type,
                                             reason))
                self.built_entries += len(entries)
                self._calls.push(
                    lambda g=built_groups, e=entries, a=attempt:
                    self._install(g, e, a))
                self._progress.set()


class _BatchWork:
    """One batch's state as it moves through the stages.

    `span` is the batch's trace span, carried EXPLICITLY on the queue
    item (contextvars do not cross the stage-thread boundaries): stage
    work is recorded retroactively against it, and whichever path
    finishes the batch — commit, solve-failure landing, or a kill-path
    _drop — owns ending it. `seq` is the scheduler's batch id (assigned at
    encode), the `batch` metadata of every stage span of this batch."""

    __slots__ = ("pods", "live_keys", "blobs", "flags", "schedule_fn",
                 "victims", "vslots", "gang_groups", "result",
                 "assignments", "rows", "preempt_rows", "victim_counts",
                 "error", "solve_span", "active_counted", "span",
                 "explain_rows", "seq")

    def __init__(self, pods, live_keys, blobs, flags, schedule_fn,
                 victims, vslots, gang_groups, seq=None):
        self.seq = seq
        self.pods = pods
        self.live_keys = live_keys
        self.blobs = blobs
        self.flags = flags
        self.schedule_fn = schedule_fn
        self.victims = victims
        self.vslots = vslots
        self.gang_groups = gang_groups
        self.result = None
        self.assignments = None
        self.rows = None
        self.preempt_rows = None
        self.victim_counts = None
        self.error = None
        self.solve_span = 0.0
        self.active_counted = False
        self.span = None
        self.explain_rows = None


class StagedPipeline:
    """dispatch | settle | commit stage threads behind the loop's encode.

    Bounded by `depth` (in-flight batches gated at submit via
    wait_capacity) and by the scheduler's blob free-list. FIFO end to
    end: each stage is a single thread draining its own deque.
    """

    def __init__(self, sched, depth: int):
        self.sched = sched
        self.depth = depth
        self._calls: LoopCalls = sched._loop_calls
        self._dispatch_q: deque = deque()
        self._settle_q: deque = deque()
        self._commit_q: deque = deque()
        self._dispatch_wake = threading.Event()
        self._settle_wake = threading.Event()
        self._commit_wake = threading.Event()
        self._progress = threading.Event()
        # dispatched-but-uncommitted count: the ledger-dirty barrier (a
        # dirty flush would re-upload host truth missing in-flight
        # charges, so dispatch waits for downstream to empty first)
        self._active = 0
        self._dcond = threading.Condition()
        self._threads: list[threading.Thread] = []
        self._stopped = False
        self.killed = False
        # loop-owned accounting
        self.inflight = 0
        self.submitted = 0
        self.completed = 0
        self.dropped = 0
        # occupancy: seconds each stage worked, plus two waits — dispatch
        # held at the ledger-dirty barrier, and an apply closure queued
        # for the loop (commit thread)
        self.busy = {"dispatch": 0.0, "settle": 0.0, "commit": 0.0,
                     "apply": 0.0, "barrier_wait": 0.0, "apply_wait": 0.0}
        self._qmax = {"dispatch": 0, "settle": 0, "commit": 0}
        self._started: float | None = None

    # ---- loop side ----

    def submit(self, work: _BatchWork) -> None:
        if self.killed:
            self._drop(work)  # a submitter that raced the kill
            return
        if self._started is None:
            self._started = time.perf_counter()
        self.submitted += 1
        self.inflight += 1
        self._dispatch_q.append(work)
        self._qmax["dispatch"] = max(self._qmax["dispatch"],
                                     len(self._dispatch_q))
        self._ensure_threads()
        self._dispatch_wake.set()

    async def wait_capacity(self, batch: int | None = None) -> None:
        """Block (yielding to the loop) until a pipeline slot frees up.
        The yields are what let marshalled apply closures run, so waiting
        here IS making progress."""
        import asyncio

        if self.inflight < self.depth:
            return
        with stage("ktpu.loop.wait_capacity", batch=batch):
            while self.inflight >= self.depth:
                self._calls.drain()
                if self.inflight >= self.depth:
                    await asyncio.sleep(0.0005)

    async def drain(self, timeout: float = 60.0) -> None:
        """Await until every submitted batch fully commits (loop
        running)."""
        import asyncio

        deadline = time.monotonic() + timeout
        while self.inflight > 0 and time.monotonic() < deadline:
            self._calls.drain()
            if self.inflight > 0:
                await asyncio.sleep(0.0005)
        self._calls.drain()

    def drain_sync(self, timeout: float = 30.0) -> None:
        """Drain from the loop thread without a running loop (stop()
        path): executes marshalled closures directly while the stage
        threads finish their in-flight work."""
        deadline = time.monotonic() + timeout
        while self.inflight > 0 and time.monotonic() < deadline:
            self._calls.drain()
            if self.inflight > 0:
                self._progress.wait(0.002)
                self._progress.clear()
        self._calls.drain()
        if self.inflight > 0:
            log.error("staged pipeline drain timed out with %d batches "
                      "in flight", self.inflight)

    def _finish(self, work: _BatchWork, scheduled: int) -> None:
        """Last hop, on the loop: close out one batch's accounting."""
        self.inflight -= 1
        self.completed += 1
        self.sched._staged_settled += scheduled

    def shutdown(self) -> None:
        self._stopped = True
        for ev in (self._dispatch_wake, self._settle_wake,
                   self._commit_wake):
            ev.set()
        with self._dcond:
            self._dcond.notify_all()
        for t in self._threads:
            t.join(timeout=1.0)

    def kill(self) -> None:
        """Hard abort (crash simulation): every stage drops its in-flight
        work on the floor — unapplied batches simply never bind, which is
        the crash-consistency contract (a restarted scheduler re-schedules
        them from the store's truth)."""
        self.killed = True
        self._stopped = True
        # drain-and-drop every queued batch HERE: a stage thread parked on
        # its wake event exits without another queue pass, which would
        # strand queued work (and leak its batch span as a forever-open
        # orphan in /debug/traces). popleft is safe against a concurrently
        # draining stage thread — each item is popped exactly once.
        for q in (self._dispatch_q, self._settle_q, self._commit_q):
            while True:
                try:
                    work = q.popleft()
                except IndexError:
                    break
                self._drop(work)
        for ev in (self._dispatch_wake, self._settle_wake,
                   self._commit_wake):
            ev.set()
        with self._dcond:
            self._dcond.notify_all()

    def snapshot(self) -> dict:
        """Per-stage occupancy + queue-depth high-water marks for bench
        `extras` — what fraction of the wall each stage was busy, i.e.
        where the next wall is."""
        wall = (time.perf_counter() - self._started) \
            if self._started is not None else 0.0
        return {
            "depth": self.depth,
            "submitted": self.submitted,
            "completed": self.completed,
            "dropped": self.dropped,
            "wall_s": round(wall, 3),
            "stage_busy_frac": {
                k: (round(v / wall, 4) if wall > 0 else 0.0)
                for k, v in sorted(self.busy.items())},
            "queue_depth_max": dict(self._qmax),
        }

    def reset_stats(self) -> None:
        """Start a fresh occupancy window (harness warmup boundary)."""
        self._started = time.perf_counter()
        for k in self.busy:
            self.busy[k] = 0.0
        for k in self._qmax:
            self._qmax[k] = 0
        self.submitted = self.completed = self.dropped = 0

    # ---- worker threads ----

    def _ensure_threads(self) -> None:
        if self._threads and all(t.is_alive() for t in self._threads):
            return
        self._threads = [
            threading.Thread(target=self._dispatch_stage,
                             name="ktpu-dispatch-stage", daemon=True),
            threading.Thread(target=self._settle_stage,
                             name="ktpu-settle-stage", daemon=True),
            threading.Thread(target=self._commit_stage,
                             name="ktpu-commit-stage", daemon=True),
        ]
        for t in self._threads:
            t.start()

    def _drop(self, work: _BatchWork) -> None:
        self.dropped += 1
        if work.span is not None:
            work.span.end("aborted")  # no orphan spans on the kill path
        if work.active_counted:
            with self._dcond:
                self._active -= 1
                self._dcond.notify_all()

    def _dispatch_stage(self) -> None:
        """Stage worker: ledger flush + compiled solve + output-ledger
        adoption, FIFO. Loop access only through LoopCalls."""
        sched = self.sched
        while not self._stopped:
            # the device's only feeder parks here: no batch to dispatch
            with stage("ktpu.dispatch.idle"):
                woke = self._dispatch_wake.wait(timeout=_IDLE_WAIT_S)
            if not woke:
                continue
            self._dispatch_wake.clear()
            while True:
                try:
                    work = self._dispatch_q.popleft()
                except IndexError:
                    break
                if self.killed:
                    self._drop(work)
                    continue
                # ledger-dirty barrier: host truth changed (external bind,
                # rejected binding rollback, a delete) — the re-upload
                # must not overwrite charges still in flight downstream
                with self._dcond:
                    if (self._active > 0 and not self.killed
                            and sched.statedb.ledger_dirty):
                        with stage("ktpu.dispatch.barrier_wait",
                                   batch=work.seq,
                                   busy=(self.busy, "barrier_wait")):
                            while (self._active > 0 and not self.killed
                                   and sched.statedb.ledger_dirty):
                                self._dcond.wait(0.05)
                    if self.killed:
                        self.dropped += 1
                        if work.span is not None:
                            work.span.end("aborted")
                        continue
                    self._active += 1
                    work.active_counted = True
                metrics = sched.metrics
                # flush + solve + adopt: the dispatch stage's work
                with stage("ktpu.dispatch.solve", batch=work.seq,
                           metrics=metrics, phase="dispatch",
                           busy=(self.busy, "dispatch"),
                           work_span=work.span, tid="dispatch") as st:
                    try:
                        with stage("ktpu.dispatch.flush", batch=work.seq,
                                   metrics=metrics, phase="flush",
                                   cpu=True), sched._state_lock:
                            state = sched.statedb.flush()
                        result = None
                        last: Exception | None = None
                        for attempt in (1, 2):
                            try:
                                result = self._solve(work, state)
                                break
                            except Exception as e:  # noqa: BLE001
                                last = e
                                metrics.solve_failure_inc()
                                if attempt == 1:
                                    metrics.solve_retry_inc()
                                    log.warning(
                                        "device solve failed (attempt "
                                        "1/2): %s; retrying", e)
                        if result is None:
                            work.error = last
                        else:
                            sched._rr = result.rr_end
                            with sched._state_lock:
                                sched.statedb.adopt_result(result)
                            try:
                                result.assignments.copy_to_host_async()
                            except AttributeError:
                                pass
                            work.result = result
                    except Exception as e:  # noqa: BLE001 — flush/adopt
                        work.error = e
                    if work.error is not None:
                        st.status = "error"
                work.solve_span = st.elapsed
                if work.error is None:
                    metrics.algorithm_latency.append(st.elapsed)
                if self.killed:
                    # the settle thread may already be gone: an append now
                    # would strand the batch (and orphan its span)
                    self._drop(work)
                    continue
                self._settle_q.append(work)
                self._qmax["settle"] = max(self._qmax["settle"],
                                           len(self._settle_q))
                self._settle_wake.set()
                self._progress.set()

    def _solve(self, work: _BatchWork, state):
        """One compiled solve in this (dispatch) thread. With
        solve_timeout_s set, the call runs under a watchdog deadline in a
        helper thread — a wedged device costs one abandoned thread, not a
        wedged pipeline."""
        sched = self.sched
        fblob, iblob = work.blobs
        hook = sched.solve_fault_hook
        if not sched.solve_timeout_s:
            if hook is not None:
                hook(list(work.live_keys))
            return work.schedule_fn(state, fblob, iblob, sched._rr,
                                    work.victims)
        box: dict = {}

        def call():
            # the fault hook runs INSIDE the deadline (a hook-simulated
            # wedged device must trip the watchdog, not stall the stage)
            try:
                if hook is not None:
                    hook(list(work.live_keys))
                r = work.schedule_fn(state, fblob, iblob, sched._rr,
                                     work.victims)
                np.asarray(r.assignments)  # force completion in-deadline
                box["r"] = r
            except Exception as e:  # noqa: BLE001
                box["e"] = e

        t = threading.Thread(target=call, daemon=True,
                             name="ktpu-solve-watchdog")
        t.start()
        t.join(sched.solve_timeout_s)
        if "r" in box:
            return box["r"]
        if "e" in box:
            raise box["e"]
        raise TimeoutError(
            f"device solve exceeded {sched.solve_timeout_s}s deadline")

    def _settle_stage(self) -> None:
        """Stage worker: blocking device->host readback + row-list
        conversion — the transport wait the loop used to eat."""
        sched = self.sched
        while not self._stopped:
            if not self._settle_wake.wait(timeout=_IDLE_WAIT_S):
                continue
            self._settle_wake.clear()
            while True:
                try:
                    work = self._settle_q.popleft()
                except IndexError:
                    break
                if self.killed:
                    self._drop(work)
                    continue
                if work.error is None:
                    with stage("ktpu.settle.readback", batch=work.seq,
                               metrics=sched.metrics, phase="settle_wait",
                               busy=(self.busy, "settle"),
                               work_span=work.span, tid="settle") as st:
                        try:
                            n = len(work.pods)
                            work.assignments = np.asarray(
                                work.result.assignments)
                            work.rows = work.assignments[:n].tolist()
                            read = [work.assignments]
                            if work.vslots is not None:
                                preempt = np.asarray(
                                    work.result.preempt_node)
                                victims = np.asarray(
                                    work.result.victim_count)
                                work.preempt_rows = preempt[:n].tolist()
                                work.victim_counts = victims[:n].tolist()
                                read += [preempt, victims]
                            if (work.flags.explain and
                                    work.result.explain_counts is not None):
                                explain = np.asarray(
                                    work.result.explain_counts)
                                work.explain_rows = explain[:n].tolist()
                                read.append(explain)
                            record_readback(*read)
                        except Exception as e:  # noqa: BLE001 — transport
                            # routed into solve-failure recovery
                            work.error = e
                            st.status = "error"
                if self.killed:
                    self._drop(work)  # commit thread may already be gone
                    continue
                self._commit_q.append(work)
                self._qmax["commit"] = max(self._qmax["commit"],
                                           len(self._commit_q))
                self._commit_wake.set()
                self._progress.set()

    def _commit_stage(self) -> None:
        """Stage worker: marshal the loop-affine apply (bind + queue +
        event bookkeeping) onto the loop, wait for its verdicts, then
        mirror the ledger into host numpy here, off the loop."""
        sched = self.sched
        while not self._stopped:
            if not self._commit_wake.wait(timeout=_IDLE_WAIT_S):
                continue
            self._commit_wake.clear()
            while True:
                try:
                    work = self._commit_q.popleft()
                except IndexError:
                    break
                if self.killed:
                    self._drop(work)
                    continue
                if work.error is not None:
                    # solve failed twice: hand the batch to the loop's
                    # recovery path (bisect/quarantine/serial fallback)
                    self._calls.push(
                        lambda w=work: sched._on_staged_solve_failure(w))
                    with self._dcond:
                        self._active -= 1
                        self._dcond.notify_all()
                    self._calls.push(
                        lambda w=work: self._finish(w, 0))
                    self._progress.set()
                    continue
                started = threading.Event()
                done = threading.Event()
                box: dict = {}

                def apply(work=work, started=started, done=done, box=box):
                    started.set()
                    try:
                        with stage("ktpu.apply", batch=work.seq,
                                   metrics=sched.metrics, phase="bind",
                                   cpu=True, busy=(self.busy, "apply")):
                            try:
                                box["out"] = sched._apply_batch(
                                    work.result, work.pods, work.live_keys,
                                    work.blobs, work.flags, work.rows,
                                    work.preempt_rows, work.victim_counts,
                                    work.gang_groups, work.vslots, None,
                                    explain_rows=work.explain_rows,
                                    span=work.span)
                            except Exception:  # noqa: BLE001
                                log.exception("staged apply failed; "
                                              "requeueing the batch")
                                sched._requeue_keys(work.live_keys)
                                sched.statedb.mark_ledger_dirty()
                    finally:
                        done.set()

                self._calls.push(apply)
                # the batch's closure queued behind the loop's other work
                with stage("ktpu.commit.apply_wait", batch=work.seq,
                           busy=(self.busy, "apply_wait")):
                    while not started.wait(timeout=0.1):
                        if self.killed:
                            break
                while not done.wait(timeout=0.1):
                    if self.killed:
                        break
                if not done.is_set():
                    self._drop(work)
                    continue
                scheduled = 0
                out = box.get("out")
                with stage("ktpu.commit.ledger", batch=work.seq,
                           metrics=sched.metrics, phase="commit", cpu=True,
                           busy=(self.busy, "commit"),
                           work_span=work.span, tid="commit"):
                    if out is not None:
                        scheduled, committed, any_rejected = out
                        try:
                            sched._commit_ledger(
                                work.result, work.blobs[0], committed,
                                any_rejected, work.flags, adopted=True)
                        except Exception:  # noqa: BLE001
                            log.exception("staged ledger commit failed; "
                                          "marking dirty")
                            sched.statedb.mark_ledger_dirty()
                    sched._release_blobs(work.blobs)
                if work.span is not None:
                    work.span.end("ok")
                with self._dcond:
                    self._active -= 1
                    self._dcond.notify_all()
                self._calls.push(
                    lambda w=work, n=scheduled: self._finish(w, n))
                self._progress.set()
