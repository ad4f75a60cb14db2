"""Day-in-the-life soak: play a trace tape against the full control plane.

One compressed "day" (a :class:`~kubernetes_tpu.scenario.traces.Tape`)
runs against the whole stack at once — scheduler + cluster-autoscaler +
descheduler + monitor over hollow kubelets — with every verb routed
through a seeded FaultPlane and audited by the RaceDetector +
LoopStallWatchdog. Where every other bench config is a synthetic burst,
this is sustained mixed churn: diurnal arrivals, gangs, priorities,
deletes, node flaps/drains/adds, watch expiry — the
``test/integration/scheduler_perf`` successor ROADMAP item 5 calls for.

The result is a :class:`SoakResult` whose ``violations`` list is the
gate surface (`bench[soak]` fails on any entry) and whose ``pressure``
float is a graded how-close-to-breaking signal for the adversarial
scenario search (search.py): 0..1 approaches the gates, >1 means at
least one is breached.

Memory ceilings are first-class: the driver samples RSS, live WAL
records (compaction must hold under churn), monitor TSDB series, the
scheduler's jit-variant cache, and watch-history occupancy into gauges —
all must be flat after warmup.
"""

from __future__ import annotations

import asyncio
import os
import tempfile
import time
from dataclasses import dataclass, field

from kubernetes_tpu.obs import REGISTRY
from kubernetes_tpu.scenario.traces import (
    BROWNOUT,
    DELETE,
    NODE_ADD,
    NODE_DRAIN,
    NODE_FLAP,
    SUBMIT,
    SUBMIT_GANG,
    WATCH_EXPIRE,
    WATCHER_DROP,
    Event,
    Tape,
    TraceConfig,
    make_tape,
)

_EVENTS = REGISTRY.counter(
    "scenario_events_applied_total",
    "Trace-tape events applied by the soak driver", labels=("kind",))
_RSS = REGISTRY.gauge(
    "soak_rss_bytes", "Driver-process resident set during the soak")
_WAL = REGISTRY.gauge(
    "soak_wal_records", "Live WAL records (post-compaction) during the soak")
_SERIES = REGISTRY.gauge(
    "soak_tsdb_series", "Embedded-monitor TSDB series during the soak")
_JIT = REGISTRY.gauge(
    "soak_jit_cache_variants", "Scheduler jit-cache variants during the soak")
_WATCHN = REGISTRY.gauge(
    "soak_watch_history_events", "Watch-history window occupancy during "
    "the soak")


def _rss_bytes() -> int:
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return 0


@dataclass
class SoakResult:
    nodes: int
    ticks: int
    seed: int
    pods_submitted: int
    bound: int
    double_binds: int
    racy_writes: int
    loop_stalls: int
    max_stall_ms: float
    p50_ms: float
    p99_ms: float
    converged: bool
    pending_at_end: int
    faults_injected: int
    node_flaps: int
    drains: int
    adds: int
    orphans_gced: int
    scaleups: int
    desched_moves: int
    rss_warm_bytes: int
    rss_peak_bytes: int
    rss_growth_frac: float
    wal_records: int
    compactions: int
    tsdb_series: int
    jit_variants: int
    watch_history: int
    events_applied: int
    seconds: float
    event_errors: list = field(default_factory=list)
    violations: list = field(default_factory=list)
    pressure: float = 0.0

    def __str__(self) -> str:
        verdict = "clean" if not self.violations \
            else "; ".join(self.violations)
        return (f"soak N={self.nodes} T={self.ticks} seed={self.seed}: "
                f"{self.bound}/{self.pods_submitted} bound, "
                f"p99 {self.p99_ms:.0f}ms, rss +"
                f"{100 * self.rss_growth_frac:.0f}% after warmup, "
                f"{self.compactions} WAL compactions "
                f"({self.wal_records} live records), "
                f"{self.scaleups} scaleups, {self.desched_moves} moves, "
                f"{self.node_flaps} flaps — {verdict}")


async def _run_soak(tape: Tape, *, tick_seconds: float,
                    snapshot_every: int, p99_bound_ms: float,
                    rss_slack_frac: float, warmup_frac: float,
                    error_rate: float, race_detect: bool,
                    heartbeat_every: float, resync_every: float,
                    autoscaler_every: int, descheduler_every: int,
                    scrape_every: int, wal_path: str,
                    converge_timeout_s: float) -> SoakResult:
    from kubernetes_tpu.agent.hollow import HollowCluster, HollowKubelet
    from kubernetes_tpu.api.objects import Node, Pod
    from kubernetes_tpu.apiserver import ObjectStore
    from kubernetes_tpu.apiserver.store import (
        Conflict,
        NotFound,
        TooManyRequests,
    )
    from kubernetes_tpu.autoscaler import ClusterAutoscaler
    from kubernetes_tpu.cloudprovider import FakeCloud
    from kubernetes_tpu.descheduler import Descheduler
    from kubernetes_tpu.gang import (
        GROUP_MIN_ANNOTATION,
        GROUP_NAME_ANNOTATION,
    )
    from kubernetes_tpu.obs.monitor import Monitor
    from kubernetes_tpu.perf.harness import (
        freeze_drill_heap,
        thaw_drill_heap,
    )
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.state import Capacities
    from kubernetes_tpu.testing.faults import FaultPlane
    from kubernetes_tpu.testing.races import LoopStallWatchdog, RaceDetector

    cfg = tape.config
    counts = tape.counts()
    freeze_drill_heap()

    cap = {"cpu": cfg.node_cpu, "memory": cfg.node_memory, "pods": "110"}
    total_pods = tape.pods_submitted()
    inner = ObjectStore(
        watch_window=max(1 << 15, 8 * (total_pods + cfg.nodes)),
        persist_path=wal_path, snapshot_every=snapshot_every)
    # initial fleet pre-registered through the inner store (setup is not
    # the thing under test; the kubelets' register() finds the Nodes)
    for i in range(cfg.nodes):
        name = f"soak-{i:05d}"
        inner.create(Node.from_dict({
            "metadata": {"name": name,
                         "labels": {"kubernetes.io/hostname": name}},
            "status": {"allocatable": dict(cap), "capacity": dict(cap)}}))

    plane = FaultPlane(inner, seed=cfg.seed, error_rate=error_rate)
    store = RaceDetector(plane) if race_detect else plane
    # the stall watchdog is armed only after warmup (first-batch jit
    # compiles are real but not a day-in-the-life pathology) and paused
    # around the synchronous probe-solve scans the driver itself steps —
    # same spirit as freeze_drill_heap: measure the control plane's own
    # loop holds, not known-blocking windows the drill schedules
    watchdog: LoopStallWatchdog | None = None
    stalls: list[float] = []

    def pause_watchdog() -> None:
        nonlocal watchdog
        if watchdog is not None:
            stalls.extend(watchdog.stop())
            watchdog = None

    def resume_watchdog() -> None:
        nonlocal watchdog
        if race_detect and watchdog is None:
            watchdog = LoopStallWatchdog().start()

    cluster = HollowCluster(store, n_nodes=0, heartbeat_every=heartbeat_every,
                            capacity=cap, resync_every=resync_every)

    def register_kubelet(name: str) -> HollowKubelet:
        kubelet = HollowKubelet(store, name,
                                heartbeat_every=heartbeat_every,
                                capacity=cap)
        cluster.add(kubelet)
        plane.attach_kubelet(name, kubelet)
        return kubelet

    for i in range(cfg.nodes):
        register_kubelet(f"soak-{i:05d}")
    await cluster.start()

    async def adopt(name: str) -> None:
        # a Node appeared that no agent owns (autoscaler scale-up or a
        # trace node-add): give it a kubelet so its pods go Running.
        # Registration runs through the audited, fault-injecting store —
        # a real kubelet retries transient apiserver errors, so adoption
        # does too (the fault sequence is op-count based: the retry's
        # ops draw fresh positions)
        for attempt in range(3):
            kubelet = register_kubelet(name)
            try:
                await kubelet.start()
                return
            except (TooManyRequests, Conflict):
                cluster.kubelets.pop(name, None)
                plane.kubelets.pop(name, None)
                kubelet.stop()
                if attempt == 2:
                    raise
                await asyncio.sleep(0.01)

    max_nodes = cfg.nodes + counts.get(NODE_ADD, 0) + cfg.autoscale_max
    widest = max((e.width for e in tape.events if e.kind == SUBMIT_GANG),
                 default=1)
    caps = Capacities(
        num_nodes=1 << max(6, (max_nodes - 1).bit_length()),
        batch_pods=min(2048, max(64, 2 * widest, total_pods // 8)))
    loop = asyncio.get_running_loop()
    sched = Scheduler(store, caps=caps)
    driver = loop.create_task(sched.run())

    cloud = FakeCloud()
    cloud.add_node_group("soak-pool", 0, cfg.autoscale_max,
                         cpu=cfg.node_cpu, memory=cfg.node_memory,
                         pods="110")
    # the autoscaler's own loop is parked (scan_interval huge) and the
    # driver steps run_once() at fixed ticks instead: its probe solves
    # block the loop by design, so stepping them inside watchdog-paused
    # windows keeps the stall gate about everything else — and stepping
    # is deterministic in tape time, which replay wants anyway
    autoscaler = ClusterAutoscaler(
        store, cloud, caps=caps, scan_interval=3600.0,
        scaleup_cooldown=0.0,
        # the day is compressed: real-time scale-down idle windows never
        # elapse, so park scale-down and let drains do the shrinking
        scaledown_cooldown=3600.0, unneeded_time=3600.0)
    await autoscaler.start()

    desched = Descheduler(store, caps=caps, scan_interval=3600.0,
                          max_moves=2, cooldown=0.0, rollback_after=30.0)
    await desched.start()

    monitor = Monitor(store=None, interval=3600.0, alert_for_s=0.0)
    monitor.add_local_target("scheduler",
                             lambda: sched.metrics.registry.render())

    def _pod_dict(name: str, ev, gang: str | None = None) -> dict:
        meta: dict = {"name": name}
        if gang:
            meta["annotations"] = {GROUP_NAME_ANNOTATION: gang,
                                   GROUP_MIN_ANNOTATION: str(ev.width)}
        return {"metadata": meta,
                "spec": {"priority": ev.priority,
                         "containers": [{"name": "app", "resources": {
                             "requests": {"cpu": f"{ev.cpu_m}m",
                                          "memory": f"{ev.mem_mi}Mi"}}}]}}

    recover_at: dict[int, list[str]] = {}
    event_errors: list[str] = []
    applied = 0

    async def apply(ev, t: int) -> None:
        if ev.kind == SUBMIT:
            inner.create(Pod.from_dict(_pod_dict(ev.name, ev)))
        elif ev.kind == SUBMIT_GANG:
            for k in range(ev.width):
                inner.create(Pod.from_dict(
                    _pod_dict(f"{ev.name}-{k}", ev, gang=ev.name)))
        elif ev.kind == DELETE:
            names = [ev.name] if ev.width <= 1 \
                else [f"{ev.name}-{k}" for k in range(ev.width)]
            for nm in names:
                try:
                    inner.delete("Pod", nm, "default")
                except NotFound:
                    pass  # e.g. its node drained first
        elif ev.kind == NODE_FLAP:
            if ev.name in plane.kubelets:
                plane.flap_node(ev.name)
                recover_at.setdefault(t + max(1, ev.down), []) \
                    .append(ev.name)
        elif ev.kind == NODE_DRAIN:
            kubelet = cluster.kubelets.pop(ev.name, None)
            if kubelet is None:
                return  # already drained by an earlier event
            plane.kubelets.pop(ev.name, None)
            kubelet.stop()
            for p in list(inner.list("Pod", copy_objects=False)):
                if p.spec.node_name == ev.name:
                    try:
                        inner.delete("Pod", p.metadata.name,
                                     p.metadata.namespace)
                    except NotFound:
                        pass
            try:
                inner.delete("Node", ev.name, "default")
            except NotFound:
                pass
        elif ev.kind == NODE_ADD:
            if ev.name not in cluster.kubelets:
                await adopt(ev.name)
        elif ev.kind == WATCH_EXPIRE:
            plane.expire_watch_history()
        elif ev.kind == WATCHER_DROP:
            plane.drop_watchers()
        elif ev.kind == BROWNOUT:
            # the tape carries the whole ramp as explicit rows, so a
            # brownout needs no timer state here: set-and-forget, the
            # final row of the window restores the baseline
            plane.error_rate = ev.rate if ev.rate > 0 else error_rate

    by_tick: dict[int, list] = {}
    for ev in tape.events:
        by_tick.setdefault(ev.tick, []).append(ev)

    samples: list[dict] = []

    def sample(t: int) -> None:
        s = {"tick": t, "rss": _rss_bytes(), "wal": inner._wal_records,
             "series": monitor.tsdb.series_count(),
             "jit": len(sched._schedule_fns),
             "watch": len(inner._history)}
        samples.append(s)
        _RSS.labels().set(s["rss"])
        _WAL.labels().set(s["wal"])
        _SERIES.labels().set(s["series"])
        _JIT.labels().set(s["jit"])
        _WATCHN.labels().set(s["watch"])

    def unconverged() -> list:
        return [p for p in inner.list("Pod", copy_objects=False)
                if not (p.spec.node_name
                        and p.status.phase == "Running")]

    orphans_gced = 0

    def gc_orphans() -> None:
        # PodGC parity (pkg/controller/podgc): force-delete pods bound
        # to a Node object that no longer exists. A drain can race an
        # in-flight solve — the bind lands a beat after the drain swept
        # the node's pods, leaving a pod no kubelet will ever ack.
        nonlocal orphans_gced
        node_names = {nd.metadata.name
                      for nd in inner.list("Node", copy_objects=False)}
        for p in list(inner.list("Pod", copy_objects=False)):
            if p.spec.node_name and p.spec.node_name not in node_names:
                try:
                    inner.delete("Pod", p.metadata.name,
                                 p.metadata.namespace)
                    orphans_gced += 1
                except NotFound:
                    pass

    # phase 0 (unmeasured warmup): walk the whole bind path once per jit
    # variant the day can demand. Variant space here is BatchFlags'
    # {gang} x {preempt} (pod specs are otherwise uniform, so every other
    # gate is constant across batches) — submit each combination alone
    # and converge before the next, so each warmup batch is homogeneous
    # and compiles exactly its own variant before the watchdog arms and
    # the memory/latency baselines start. A variant first seen mid-day
    # would read as a ~100ms+ compile stall the control plane never
    # caused.
    async def warm(base: str, width: int, prio: int) -> list[str]:
        if width == 1:
            ev = Event(0, SUBMIT, base, cpu_m=100, mem_mi=100,
                       priority=prio)
            inner.create(Pod.from_dict(_pod_dict(f"{base}-0", ev)))
            names = [f"{base}-0"]
        else:
            ev = Event(0, SUBMIT_GANG, base, cpu_m=100, mem_mi=100,
                       width=width, priority=prio)
            names = [f"{base}-{k}" for k in range(width)]
            for nm in names:
                inner.create(Pod.from_dict(_pod_dict(nm, ev, gang=base)))
        async with asyncio.timeout(converge_timeout_s):
            while unconverged():
                await asyncio.sleep(0.02)
        return names

    def delete_pods(names: list[str]) -> None:
        for nm in names:
            try:
                inner.delete("Pod", nm, "default")
            except NotFound:
                pass

    # a preempt variant traces twice: with a victim table, and without
    # one when no evictable pod is accounted anywhere -- the empty
    # cluster the day starts on once the warm pods below are deleted.
    # Warm that shape first: each preempt unit alone on an empty
    # cluster, deleted and forgotten by the scheduler before the next
    for base, width in (("soak-warmpe", 1), ("soak-warmgpe", 2)):
        names = await warm(base, width, 1000)
        delete_pods(names)
        async with asyncio.timeout(converge_timeout_s):
            while any(sched.statedb.is_accounted(f"default/{nm}")
                      for nm in names):
                await asyncio.sleep(0.02)
    warm_names: list[str] = []
    warm_units = [("soak-warm0", 1, 0), ("soak-warmp", 1, 1000),
                  ("soak-warmg", 2, 0), ("soak-warmgp", 2, 1000)]
    for base, width, prio in warm_units:
        warm_names += await warm(base, width, prio)
    delete_pods(warm_names)
    for run_once in (autoscaler.run_once, desched.run_once):
        try:
            run_once()
        except Exception:
            pass  # injected store fault mid-scan: the next scan retries
    sched.metrics.e2e_latency.clear()
    # second freeze: warmup just allocated the jit artifacts and compile
    # garbage; a gen2 pass over them mid-day reads as a ~130ms stall the
    # control plane never caused
    freeze_drill_heap()
    resume_watchdog()

    def step_scan(run_once) -> None:
        # probe solves block the loop by design — pause the stall gate
        # for exactly this window (see the watchdog comment above). A
        # scan that trips an injected store fault is simply skipped: the
        # real controllers retry on their next loop iteration, so the
        # stepped equivalent is "this scan saw a flaky apiserver".
        pause_watchdog()
        try:
            run_once()
        except Exception:
            pass
        finally:
            resume_watchdog()

    def step_scans() -> None:
        step_scan(autoscaler.run_once)
        step_scan(desched.run_once)

    t_start = time.perf_counter()
    for t in range(cfg.ticks):
        for name in recover_at.pop(t, ()):
            if name in plane.kubelets:
                plane.recover_node(name)
        for ev in by_tick.get(t, ()):
            try:
                await apply(ev, t)
                applied += 1
                _EVENTS.labels(ev.kind).inc()
            except Exception as exc:  # a tape must never crash the driver
                event_errors.append(f"tick {t} {ev.kind} {ev.name}: "
                                    f"{exc!r}")
        if autoscaler_every and t % autoscaler_every == 0:
            step_scan(autoscaler.run_once)
        for node in inner.list("Node", copy_objects=False):
            if node.metadata.name not in cluster.kubelets:
                await adopt(node.metadata.name)
        if descheduler_every and t and t % descheduler_every == 0:
            step_scan(desched.run_once)
        gc_orphans()
        if scrape_every and t % scrape_every == 0:
            await monitor.scrape_once()
            sample(t)
        await asyncio.sleep(tick_seconds)

    # end of day: recover every still-flapped node, then the whole
    # cluster must converge — every live pod bound exactly once + Running
    for t in sorted(recover_at):
        for name in recover_at[t]:
            if name in plane.kubelets:
                plane.recover_node(name)

    converged = True
    try:
        async with asyncio.timeout(converge_timeout_s):
            waited = 0
            while unconverged():
                await asyncio.sleep(0.05)
                waited += 1
                if waited % 20 == 0:
                    step_scans()
                    gc_orphans()
                    for node in inner.list("Node", copy_objects=False):
                        if node.metadata.name not in cluster.kubelets:
                            await adopt(node.metadata.name)
    except TimeoutError:
        converged = False
    pending = unconverged()
    await monitor.scrape_once()
    sample(cfg.ticks)
    seconds = time.perf_counter() - t_start

    snap = sched.metrics.snapshot()
    driver.cancel()
    sched.stop()
    autoscaler.stop()
    desched.stop()
    cluster.stop()
    thaw_drill_heap()
    pause_watchdog()  # folds the final segment into `stalls`

    double = sum(1 for v in plane.bind_counts.values() if v > 1)
    racy = len(store.racy_writes) if race_detect else 0
    warm_n = max(1, int(len(samples) * warmup_frac))
    rss_warm = max((s["rss"] for s in samples[:warm_n]), default=0)
    rss_peak = max((s["rss"] for s in samples), default=0)
    growth = (rss_peak - rss_warm) / rss_warm if rss_warm else 0.0
    jit_warm, jit_end = samples[warm_n - 1]["jit"], samples[-1]["jit"]
    series_warm = samples[warm_n - 1]["series"]
    series_end = samples[-1]["series"]
    p50 = float(snap.get("e2e_p50_ms", 0.0))
    p99 = float(snap.get("e2e_p99_ms", 0.0))

    violations: list[str] = []
    if double:
        violations.append(f"{double} double-binds")
    if racy:
        violations.append(f"{racy} racy writes")
    if stalls:
        violations.append(f"{len(stalls)} loop stalls >100ms "
                          f"(max {1e3 * max(stalls):.0f}ms)")
    if event_errors:
        violations.append(f"{len(event_errors)} tape events failed "
                          f"(first: {event_errors[0]})")
    if not converged:
        stuck = ", ".join(sorted(
            f"{p.metadata.name}:{p.status.phase or '?'}"
            f"@{p.spec.node_name or 'unbound'}" for p in pending)[:5])
        violations.append(f"{len(pending)} pods unbound or not Running "
                          f"at end of day ({stuck})")
    if rss_warm and growth > rss_slack_frac:
        violations.append(
            f"rss ceiling: +{100 * growth:.0f}% after warmup "
            f"(slack {100 * rss_slack_frac:.0f}%)")
    if snapshot_every and inner._wal_records > snapshot_every:
        violations.append(f"wal unbounded: {inner._wal_records} live "
                          f"records > snapshot_every={snapshot_every}")
    if jit_end > jit_warm + 3:
        violations.append(f"jit cache grew after warmup: "
                          f"{jit_warm} -> {jit_end} variants")
    if series_end > max(series_warm + 8, int(series_warm * 1.25)):
        violations.append(f"tsdb series grew after warmup: "
                          f"{series_warm} -> {series_end}")
    if p99_bound_ms > 0 and p99 > p99_bound_ms:
        violations.append(f"scheduler e2e p99 {p99:.0f}ms > "
                          f"{p99_bound_ms:.0f}ms bound")

    # graded closeness-to-breaking for the scenario search: soft margins
    # below 1.0, then a step + count once gates actually break
    pressure = max(p99 / (p99_bound_ms if p99_bound_ms > 0 else 1e4),
                   (growth / rss_slack_frac) if rss_warm else 0.0)
    if violations:
        pressure = max(pressure, 1.0) + float(len(violations))

    return SoakResult(
        nodes=cfg.nodes, ticks=cfg.ticks, seed=cfg.seed,
        pods_submitted=total_pods, bound=len(plane.bind_counts),
        double_binds=double, racy_writes=racy,
        loop_stalls=len(stalls),
        max_stall_ms=1e3 * max(stalls, default=0.0),
        p50_ms=p50, p99_ms=p99,
        converged=converged, pending_at_end=len(pending),
        faults_injected=plane.stats.injected_total,
        node_flaps=sum(1 for f in plane.stats.node_flaps
                       if f["kind"] == "down"),
        drains=counts.get(NODE_DRAIN, 0), adds=counts.get(NODE_ADD, 0),
        orphans_gced=orphans_gced,
        scaleups=autoscaler.scaleups, desched_moves=desched.moves,
        rss_warm_bytes=rss_warm, rss_peak_bytes=rss_peak,
        rss_growth_frac=growth,
        wal_records=inner._wal_records, compactions=inner.compactions,
        tsdb_series=series_end, jit_variants=jit_end,
        watch_history=samples[-1]["watch"],
        events_applied=applied, seconds=seconds,
        event_errors=event_errors, violations=violations,
        pressure=pressure)


def run_soak(config: TraceConfig | None = None, *,
             tape: Tape | None = None, mutations=(),
             tick_seconds: float = 0.05, snapshot_every: int = 2000,
             p99_bound_ms: float = 0.0, rss_slack_frac: float = 0.35,
             warmup_frac: float = 0.4, error_rate: float = 0.01,
             race_detect: bool = True, heartbeat_every: float = 0.5,
             resync_every: float = 0.25, autoscaler_every: int = 2,
             descheduler_every: int = 10, scrape_every: int = 4,
             converge_timeout_s: float = 120.0) -> SoakResult:
    """Blocking entry point: generate (or take) a tape and play the day.

    ``p99_bound_ms=0`` leaves the latency gate disarmed (smoke tier);
    the full bench arms it. The WAL lives in a temp dir for the run —
    compaction behavior is what's under test, not the artifact."""
    if tape is None:
        tape = make_tape(config or TraceConfig(), mutations)
    with tempfile.TemporaryDirectory(prefix="ktpu-soak-") as td:
        return asyncio.run(_run_soak(
            tape, tick_seconds=tick_seconds, snapshot_every=snapshot_every,
            p99_bound_ms=p99_bound_ms, rss_slack_frac=rss_slack_frac,
            warmup_frac=warmup_frac, error_rate=error_rate,
            race_detect=race_detect, heartbeat_every=heartbeat_every,
            resync_every=resync_every, autoscaler_every=autoscaler_every,
            descheduler_every=descheduler_every,
            scrape_every=scrape_every,
            wal_path=os.path.join(td, "soak.wal"),
            converge_timeout_s=converge_timeout_s))
