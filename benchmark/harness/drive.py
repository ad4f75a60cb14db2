"""One run of one cell: build the cluster, serve the cell's traffic into
the scheduler's own loop, measure a window, check what it produced.

The system under test is kubernetes_tpu's in-process control plane: an
ObjectStore (the apiserver + etcd analog) and the Scheduler with its
informers, workqueue, StateDB, staged pipeline and jitted solver. The
benchmark is a client of the store: it creates pods, watches binds and
deletes residents through the store's own verbs, in small chunks so
that it never holds the shared event loop for long.
"""

from __future__ import annotations

import asyncio
import gc
import math
import statistics
import sys
import threading
import time
import traceback
from collections import deque
from contextlib import nullcontext

import numpy as np

from benchmark.harness.check import replay_check, sample_names
from benchmark.harness.workload import PodSequence, node_dicts, prefill

CHUNK = 64   # client writes per turn of the event loop


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """jax.monitoring listeners: programs compiled or loaded from the
    persistent cache, by function name, and how many of those loads hit
    the cache (the rest compiled)."""

    def __init__(self):
        import jax.monitoring as mon

        self.programs: dict[str, int] = {}
        self.hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event.endswith("backend_compile_duration"):
            name = kw.get("fun_name", "?")
            self.programs[name] = self.programs.get(name, 0) + 1

    def _on_event(self, event: str, **kw) -> None:
        if event.endswith("compilation_cache/cache_hits"):
            self.hits += 1

    def snapshot(self) -> tuple[dict, int]:
        return dict(self.programs), self.hits


class GcPauses:
    """gc.callbacks: the process's collector pauses by generation."""

    def __init__(self):
        self.t0 = 0.0
        self.pauses: dict[int, list[float]] = {0: [], 1: [], 2: []}
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.t0 = time.perf_counter()
        else:
            self.pauses[info["generation"]].append(time.perf_counter()
                                                   - self.t0)

    def reset(self) -> None:
        for v in self.pauses.values():
            v.clear()

    def summary(self) -> str:
        return ", ".join(f"gen{g} {len(v)} pauses, max {max(v, default=0) * 1e3:.1f}"
                         f" ms, total {sum(v) * 1e3:.1f} ms"
                         for g, v in self.pauses.items())


def since(now: tuple[dict, int], before: tuple[dict, int]):
    return ({k: v - before[0].get(k, 0) for k, v in now[0].items()
             if v > before[0].get(k, 0)}, now[1] - before[1])


def arrival_gaps(rate: float, n: int, seed: int) -> np.ndarray:
    """n Poisson inter-arrival gaps at `rate`: the exponential's n
    quantiles (the same multiset for every seed), in a seeded order."""
    q = (np.arange(n) + 0.5) / n
    return np.random.default_rng([seed, 3]).permutation(-np.log1p(-q) / rate)


def warm_row_scatter(sched) -> int:
    """Run StateDB's dirty-row scatter once at every power-of-two row count
    a flush can take (flush scatters up to num_nodes / 4 rows), discarding
    the result: which count a flush takes depends on the churn of the
    moment, so the traffic alone reaches a new one in any window. Returns
    the counts warmed."""
    import jax

    db = sched.statedb
    with sched._state_lock:
        dev = db._device
        if dev is None:
            return 0
        k = 1
        while k * 4 <= db.caps.num_nodes:
            jax.block_until_ready(db._scatter_rows(dev, list(range(k))))
            k *= 2
    return k.bit_length() - 1


class Watermarks:
    """For each solved batch, the watermark of its StateDB flush -- the
    resourceVersion up to which the scheduler's pod informer had applied
    the store's pod events to the state the batch was solved on -- with
    the batch's pods and rows, in the order the batches were bound.

    The flush runs under the scheduler's state lock, so no informer
    handler changes the state while the watermark is read there: it is
    the informer's last resourceVersion, or, where events wait parked
    behind the lock (FIFO: once one parks, every later one does), the one
    before the first of them. The flush is paired with its batch through
    the result the same thread's adopt_result takes, and that result with
    the batch's pods at its _apply_batch (the bind), so a failed solve or
    an out-of-order stage cannot shift the pairing. One tuple per batch
    on the timed path. Every batch from the scheduler's start is kept: a
    batch flushed in the warm-up can bind in the window; the close's
    ledger read-back flushes with no batch and pairs with nothing."""

    def __init__(self, sched):
        self.batches: list[tuple[int | None, list, list]] = []
        local = threading.local()
        solved: dict[int, tuple[int | None, object]] = {}
        db, informer = sched.statedb, sched.pod_informer
        parked = sched._deferred_events
        flush, adopt, apply = db.flush, db.adopt_result, sched._apply_batch

        def flushed():
            state = flush()
            local.wm = (parked[0][1].resource_version - 1 if parked
                        else informer._last_rv)
            return state

        def adopted(result):
            adopt(result)
            solved[id(result)] = (getattr(local, "wm", None), result)

        def applied(result, pods, live_keys, blobs, flags, rows, *a, **kw):
            wm, _ = solved.pop(id(result), (None, None))
            self.batches.append((wm, live_keys, rows))
            return apply(result, pods, live_keys, blobs, flags, rows, *a,
                         **kw)

        db.flush, db.adopt_result = flushed, adopted
        sched._apply_batch = applied

    def pins(self) -> tuple[dict, dict]:
        """(pod name -> watermark of the last batch that placed it, pod
        name -> watermark of the last batch that left it unplaced)."""
        placed: dict = {}
        unplaced: dict = {}
        for wm, keys, rows in self.batches:
            for key, row in zip(keys, rows):
                (placed if row >= 0 else unplaced)[key.rpartition("/")[2]] \
                    = wm
        return placed, unplaced


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile over all values."""
    vals = sorted(values)
    return vals[max(0, math.ceil(q / 100.0 * len(vals)) - 1)]


class Run:
    def __init__(self, cfg: dict, traffic: dict, seed: int, seconds: float,
                 trace_dir: str | None, t_process: float, fault=None):
        self.cfg, self.traffic = cfg, traffic
        self.seed, self.seconds, self.trace_dir = seed, seconds, trace_dir
        self.t_process = t_process
        self.fault = fault
        self.batch = cfg["capacities"]["batch_pods"]
        # the run's store history, as the benchmark's watch saw it
        self.log: list[tuple[int, str, str, str | None]] = []
        self.shapes: dict = {}
        self.created_at: dict[str, float] = {}
        self.due: dict[str, float] = {}
        self.bound_at: dict[str, float] = {}
        self.node_of: dict[str, str] = {}
        self.rebinds = 0
        self.lateness: list[float] = []
        self.pending = 0          # created, not yet bound
        self.to_create = 0        # saturated top-up owed
        self.to_delete: deque = deque()
        self.deletes = [0, 0]     # issued by the client, seen on the watch
        self.backlog: list[tuple[float, int]] = []
        self.creating = True

    # ---- the client: create, watch, churn ----------------------------

    def _create(self, n: int, due: list[float] | None = None) -> None:
        now = time.perf_counter()
        for k in range(n):
            name, i = self.seq.next()
            self.shapes[name] = self.seq.shape(i)
            self.created_at[name] = now
            if due is not None:
                self.due[name] = due[k]
                self.lateness.append(now - due[k])
            self.store.create(self.seq.pod(name, i))
        self.pending += n

    def _on_events(self, events: list) -> None:
        now = time.perf_counter()
        for ev in events:
            name = ev.obj.metadata.name
            rv = ev.resource_version
            if ev.type == "ADDED":
                self.log.append((rv, "A", name, None))
            elif ev.type == "DELETED":
                self.log.append((rv, "D", name, None))
                self.deletes[1] += 1
                self.node_of.pop(name, None)
            else:
                node = ev.obj.spec.node_name
                prev = self.node_of.get(name)
                if node and prev is None:
                    self.node_of[name] = node
                    self.bound_at[name] = now
                    self.log.append((rv, "B", name, node))
                    self.residents.append(name)
                    self.pending -= 1
                    # steady churn: the oldest resident goes, one per bind
                    self.to_delete.append(self.residents.popleft())
                elif node and prev != node:
                    self.rebinds += 1
        if self.creating and self.traffic["arrival"] == "saturated":
            want = self.traffic["backlog_batches"] * self.batch
            self.to_create = max(self.to_create, want - self.pending)
        if self.to_delete or self.to_create:
            self.wake.set()

    async def _watch(self) -> None:
        import jax

        from kubernetes_tpu.apiserver.store import WatchEvent

        q = self.stream._queue
        while True:
            ev = await self.stream.next()
            if ev is None:
                return
            events = [ev]
            while True:
                try:
                    ev = q.get_nowait()
                except asyncio.QueueEmpty:
                    break
                if not isinstance(ev, WatchEvent):
                    raise RuntimeError("benchmark watch evicted")
                events.append(ev)
            with jax.profiler.TraceAnnotation("bench.watch"):
                self._on_events(events)

    async def _client(self) -> None:
        """Deletes owed to churn and the saturated top-up, CHUNK writes
        per turn of the loop."""
        import jax

        while True:
            await self.wake.wait()
            self.wake.clear()
            while self.to_delete or (self.to_create and self.creating):
                with jax.profiler.TraceAnnotation("bench.client"):
                    for _ in range(min(CHUNK, len(self.to_delete))):
                        self.store.delete("Pod", self.to_delete.popleft(),
                                          "default")
                        self.deletes[0] += 1
                    n = min(CHUNK, self.to_create) if self.creating else 0
                    if n:
                        self._create(n)
                        self.to_create -= n
                await asyncio.sleep(0)

    async def _arrivals(self, t0: float, rate: float) -> None:
        """Open loop: every pod is created at its due time, late or not."""
        import jax

        tr = self.traffic
        n = int(rate * (tr["warmup_s"] + self.seconds + 70)) + 1
        due = t0 + np.cumsum(arrival_gaps(rate, n, self.seed))
        i = 0
        while self.creating and i < n:
            j = min(int(np.searchsorted(due, time.perf_counter(),
                                        side="right")), i + CHUNK)
            if j > i:
                with jax.profiler.TraceAnnotation("bench.generator"):
                    self._create(j - i, list(due[i:j]))
                i = j
                await asyncio.sleep(0)
            elif i < n:
                await asyncio.sleep(max(0.0, due[i] - time.perf_counter()))

    async def _sample_backlog(self, t0: float, t1: float) -> None:
        """The pending backlog every 0.25 s of the window, for its trend."""
        while time.perf_counter() < t1:
            self.backlog.append((time.perf_counter() - t0, self.pending))
            await asyncio.sleep(0.25)

    async def _loop(self) -> None:
        """Scheduler.run()'s body, with a span around each pass."""
        import jax

        while self.looping:
            try:
                with jax.profiler.TraceAnnotation("bench.schedule_pending"):
                    await self.sched.schedule_pending(wait=0.5)
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 — run()'s contract
                log("scheduling pass failed; retrying\n"
                    + traceback.format_exc())
                await asyncio.sleep(0.05)

    async def _read_ledger(self, nodes: list[dict]) -> dict:
        """The scheduler's device ledger once it has taken in every write
        of the run (its pod count equals the store's bound pods, or 15 s
        have passed): pods, cpu (milli) and memory (bytes) requested on
        each node, in node-list order; -1 for a node without a row."""
        from kubernetes_tpu.state.layout import Resource

        db = self.sched.statedb
        t0 = time.perf_counter()
        while True:
            with self.sched._state_lock:
                req = np.asarray(db.flush().requested, np.float64)
            if round(req[:, Resource.PODS].sum()) == len(self.residents) \
                    or time.perf_counter() - t0 > 15.0:
                break
            await asyncio.sleep(0.05)
        rows = np.array([db.table.row_of.get(n["metadata"]["name"], -1)
                         for n in nodes])
        got = np.where(rows[:, None] >= 0, req[rows], -1.0)
        return {"pods": np.rint(got[:, Resource.PODS]).astype(np.int64),
                "cpu": np.rint(got[:, Resource.CPU]).astype(np.int64),
                "mem": np.rint(got[:, Resource.MEMORY] * 2**20
                               ).astype(np.int64),
                "wait_s": time.perf_counter() - t0}

    # ---- the run ------------------------------------------------------

    def _counters(self) -> dict:
        snap = self.sched.metrics.snapshot()
        stages = (self.sched._staged.snapshot()
                  if self.sched._staged is not None else {})
        return {"phase_us_per_pod": snap.get("phase_us_per_pod", {}),
                "scheduled": snap["scheduled"], "batches": snap["batches"],
                "failed_pods": snap["failed"],
                "binding_errors": snap["binding_errors"],
                "stage_busy_frac": stages.get("stage_busy_frac", {})}

    async def main(self) -> dict:
        import jax

        from kubernetes_tpu.api.objects import Node
        from kubernetes_tpu.apiserver import ObjectStore
        from kubernetes_tpu.scheduler import Scheduler
        from kubernetes_tpu.scheduler.driver import SchedulerMetrics
        from kubernetes_tpu.state import Capacities

        cfg, tr = self.cfg, self.traffic
        marks = {"start": time.monotonic() - self.t_process}
        compiles = CompileCounter()
        gc_pauses = GcPauses()
        loop = asyncio.get_running_loop()
        self.wake = asyncio.Event()
        nodes = node_dicts(cfg)
        res = PodSequence(tr["pod"], self.seed, "res")
        pre = prefill(nodes, cfg["resident_pods_per_node"], res, self.seed)
        self.prefill = [(name, node, res.shape(i)) for name, i, node in pre]
        marks["prefill_placed"] = time.monotonic() - self.t_process
        # the served apiserver's watch cache (cmd/apiserver.py
        # --watch-cache-size): its history ring keeps that many events,
        # and the objects they hold, alive in this one process
        self.store = ObjectStore(watch_window=cfg["watch_cache_size"])
        self.store.create_many([Node.from_dict(n) for n in nodes])
        self.store.create_many([res.pod(name, i, node)
                                for name, i, node in pre])
        del pre
        marks["store_built"] = time.monotonic() - self.t_process
        self.residents = deque(name for name, _, _ in self.prefill)
        self.stream = self.store.watch("Pod")
        self.seq = PodSequence(tr["pod"], self.seed, "w")
        self.sched = Scheduler(self.store, caps=Capacities(
            num_nodes=cfg["capacities"]["num_nodes"], batch_pods=self.batch))
        if self.fault is not None:
            self.fault(self.sched)
        self.watermarks = Watermarks(self.sched)
        await self.sched.start()
        marks["informers_synced"] = time.monotonic() - self.t_process
        tasks = [loop.create_task(self._watch()),
                 loop.create_task(self._client())]
        self.looping = True
        loop_task = loop.create_task(self._loop())

        # warm-up: the cell's own pods until its programs have run, then
        # (open loop) the arrivals alone until they are in steady state
        if tr["arrival"] == "saturated":
            self.to_create = tr["backlog_batches"] * self.batch
            self.wake.set()
        else:
            self._create(tr["warmup_binds"])
        while len(self.bound_at) < tr["warmup_binds"]:
            await asyncio.sleep(0.05)
        marks["scatter_counts_warmed"] = warm_row_scatter(self.sched)
        if tr["arrival"] != "saturated":
            tasks.append(loop.create_task(
                self._arrivals(time.perf_counter(), tr["rate_per_s"])))
            await asyncio.sleep(tr["warmup_s"])
        c_warm = compiles.snapshot()

        # the window
        self.sched.metrics = SchedulerMetrics()
        if self.sched._staged is not None:
            self.sched._staged.reset_stats()
        if self.trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        gc_pauses.reset()
        t_w = time.perf_counter()
        setup_s = time.monotonic() - self.t_process
        rv_w = self.store.resource_version
        pending_w = self.pending
        span = min(self.seconds, tr["trace_s"]) if self.trace_dir \
            else self.seconds
        tasks.append(loop.create_task(
            self._sample_backlog(t_w, t_w + self.seconds)))
        with (jax.profiler.TraceAnnotation("bench.window")
              if self.trace_dir else nullcontext()):
            await asyncio.sleep(span)
        counters = self._counters()       # over the traced span, if traced
        counters["counted_s"] = time.perf_counter() - t_w
        counters["gc"] = gc_pauses.summary()
        if self.trace_dir:
            jax.profiler.stop_trace()
        await asyncio.sleep(max(0.0, t_w + self.seconds - time.perf_counter()))
        t_end = time.perf_counter()
        rv_end = self.store.resource_version
        c_window = since(compiles.snapshot(), c_warm)
        pending_end = self.pending

        # close: no new pods; every pod created is served (answers due in
        # the window may come late), then the store goes quiet
        self.creating = False
        due_in = [n for n, d in self.due.items() if t_w <= d < t_end]
        t_wait = time.perf_counter()
        while (self.pending or self.to_delete
               or self.deletes[0] != self.deletes[1]
               or self.sched.inflight_batches) \
                and time.perf_counter() - t_wait < 60.0:
            await asyncio.sleep(0.05)
        close_s = time.perf_counter() - t_wait
        self.looping = False
        await loop_task
        ledger = await self._read_ledger(nodes)
        self.sched.stop()
        # the window's "no node fits" verdicts on the generator's pods,
        # each at its event's (latest) resourceVersion
        reasons: dict[str, int] = {}
        verdicts = []
        for ev in self.store.list("Event", copy_objects=False):
            if ev.reason == "FailedScheduling":
                msg = ev.message[:160]
                reasons[msg] = reasons.get(msg, 0) + 1
                rv = int(ev.metadata.resource_version)
                name = ev.involved_object.get("name")
                if name in self.shapes and rv_w < rv <= rv_end:
                    verdicts.append((rv, name))
        if reasons:
            log(f"FailedScheduling events (whole run): {reasons}")
        await asyncio.sleep(0.1)
        self.stream.stop()
        for t in tasks:
            t.cancel()
        for got in await asyncio.gather(*tasks, return_exceptions=True):
            if isinstance(got, Exception) and \
                    not isinstance(got, asyncio.CancelledError):
                raise got
        mem = (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use", 0)
        self.sched = self.store = None
        gc.collect()
        return dict(counters, setup_s=setup_s, marks=marks,
                    window_s=t_end - t_w, t_w=t_w, t_end=t_end,
                    compiles_warmup=c_warm, compiles_window=c_window,
                    memory_peak_bytes=int(mem), due_in=due_in,
                    ledger=ledger, close_s=close_s,
                    backlog_slope=backlog_slope(self.backlog),
                    verdicts=verdicts,
                    pending=(pending_w, pending_end))


def backlog_slope(samples: list[tuple[float, int]]) -> float:
    """Least-squares trend of the pending backlog over the window, pods/s."""
    if len(samples) < 2:
        return 0.0
    t, n = np.array(samples, np.float64).T
    return float(np.polyfit(t, n, 1)[0])


def ledger_mismatch(ledger: dict, end: dict) -> int:
    """Nodes whose ledger (pods, cpu, memory) differs from the reference's
    end state."""
    return int(np.sum((ledger["pods"] != end["pods"])
                      | (ledger["cpu"] != end["cpu"])
                      | (ledger["mem"] != end["mem"])))


def run_cell(cfg, traffic, seed, seconds, trace_dir, t_process,
             fault=None) -> dict:
    run = Run(cfg, traffic, seed, seconds, trace_dir, t_process, fault)
    got = asyncio.run(run.main())
    t_w, t_end = got["t_w"], got["t_end"]
    if traffic["arrival"] == "saturated":
        done = [n for n, t in run.bound_at.items() if t_w <= t < t_end]
        attempted = len(done)
        failed = got["failed_pods"] + got["binding_errors"]
        sample_from = done
        e2e = {"pods_per_s": len(done) / got["window_s"]}
    else:
        due_in = got["due_in"]
        lat = [(run.bound_at[n] - run.due[n]) * 1e3
               for n in due_in if n in run.bound_at]
        attempted = len(due_in)
        failed = attempted - len(lat)
        sample_from = [n for n in due_in if n in run.bound_at]
        e2e = {"bind_p50_ms": statistics.median(lat) if lat else math.inf,
               "bind_p99_ms": percentile(lat, 99) if lat else math.inf}
        late = run.lateness
        log(f"generator lateness over {len(late)} arrivals: p50 "
            f"{percentile(late, 50) * 1e3:.3f} ms, p99 "
            f"{percentile(late, 99) * 1e3:.3f} ms, max "
            f"{max(late) * 1e3:.3f} ms")
    for when in ("warmup", "window"):
        progs, hits = got["compiles_" + when]
        log(f"programs in the {when}: {sum(progs.values())} {progs}, "
            f"{hits} from the cache, {sum(progs.values()) - hits} compiled"
            + (" (expected 0)" if when == "window" else ""))
    log(f"collector pauses over the counted span: {got['gc']}")
    log(f"host us/pod {got['phase_us_per_pod']}; stage busy "
        f"{got['stage_busy_frac']}")
    log(f"window {got['window_s']:.3f} s: {got['scheduled']} pods scheduled "
        f"in {got['batches']} batches over the counted "
        f"{got['counted_s']:.3f} s; pending {got['pending'][0]} -> "
        f"{got['pending'][1]}, trend {got['backlog_slope']:.1f} pods/s; setup {got['setup_s']:.3f} s "
        f"{ {k: round(v, 3) for k, v in got['marks'].items()} }")

    # correctness, once the program's state is gone
    longest = max(sample_from, key=lambda n: run.bound_at[n]
                  - run.created_at[n]) if sample_from else None
    placed, unplaced = run.watermarks.pins()
    samples = {n: placed.get(n) for n in sample_names(
        sample_from, traffic["check_sample"], seed,
        [longest] if longest else [])}
    verdicts = [(rv, n, unplaced.get(n)) for rv, n in sample_names(
        got["verdicts"], traffic["check_sample"] // 2, seed)]
    checked = replay_check(node_dicts(cfg), run.prefill, run.log, run.shapes,
                           samples, verdicts)
    log(f"reference: {checked['checked']} sampled decisions in "
        f"{checked['seconds']:.3f} s at their batches' watermarks "
        f"({len(run.watermarks.batches)} batches recorded); deletes past "
        f"the watermark per sample median "
        f"{statistics.median(checked['past_watermark'] or [0])}; "
        f"{checked['at_earlier_cut']} matched only at the cut one delete "
        f"earlier; {checked['verdicts_checked']} of "
        f"{len(got['verdicts'])} FailedScheduling verdicts in the window "
        f"checked")
    checks = {
        "decision_mismatch": (checked["decision_mismatch"], 0),
        "verdict_mismatch": (checked["verdict_mismatch"], 0),
        "unchecked_samples": (len(samples) + len(verdicts)
                              - checked["checked"]
                              - checked["verdicts_checked"], 0),
        "rebinds": (run.rebinds, 0),
        "overcommit_binds": (checked["overcommit"], 0),
        "anti_affinity_breaks": (checked["anti_affinity"], 0),
        "ledger_mismatch_nodes": (ledger_mismatch(got["ledger"],
                                                  checked["end"]), 0),
        "unbound_due": (failed if traffic["arrival"] != "saturated" else 0,
                        0),
    }
    log(f"close: store quiet after {got['close_s']:.3f} s, ledger read "
        f"after {got['ledger']['wait_s']:.3f} s")
    correct = bool(samples) and all(v <= lim for v, lim in checks.values())
    return {"got": got, "e2e": e2e, "attempted": attempted,
            "failed": failed, "checks": checks, "correct": correct}
