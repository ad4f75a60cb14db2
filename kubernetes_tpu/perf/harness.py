"""Throughput harness — the scheduler_perf equivalent.

Mirrors the reference's integration benchmark
(test/integration/scheduler_perf/scheduler_test.go:71-100
schedulePods: spin up an in-process control plane, pre-create fake nodes,
pump templated pods in, and measure sustained pods scheduled/sec; hard-fail
thresholds at :35-38). Here the control plane is the in-memory store +
informers and the scheduler is the batched device solver; the measured
number is end-to-end (encode + device solve + bind + watch confirmation).
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field

from kubernetes_tpu.apiserver import ObjectStore
from kubernetes_tpu.models.policy import DEFAULT_POLICY, Policy
from kubernetes_tpu.perf.fixtures import make_nodes, make_pods
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.state import Capacities


@dataclass
class ThroughputResult:
    scheduled: int
    seconds: float
    pods_per_sec: float
    batches: int
    metrics: dict
    # per-phase registry histogram snapshot of the timed wave
    # ({phase: {count, sum_ms, p50_ms, p99_ms}}) — bench.py's
    # --metrics-snapshot payload
    phase_hist: dict = field(default_factory=dict)
    # staged-pipeline occupancy over the timed wave (stage_busy_frac +
    # queue-depth high-water marks); empty when KTPU_STAGED_PIPELINE=0
    pipeline: dict = field(default_factory=dict)
    # mesh runs: per-shard live-row occupancy + StateDB flush transfer
    # counters (bench[sharded] extras); empty without a mesh
    sharding: dict = field(default_factory=dict)

    def __str__(self) -> str:
        return (f"{self.scheduled} pods in {self.seconds:.2f}s = "
                f"{self.pods_per_sec:.0f} pods/s over {self.batches} batches")


def freeze_drill_heap() -> None:
    """Pre-drill GC hygiene shared by every stall-gated drill (chaos,
    overload, rolling-restart, scenario soak): collect whatever earlier
    configs left behind, then freeze the surviving heap out of the
    collector's reach. A gen2 pass walking co-resident heaps (a previous
    config's object graphs, jax caches) holds the GIL 50-220ms from
    whichever thread trips the allocation threshold — long enough to
    flake the 100ms loop-stall gate with a pause the drill's own loop
    never caused. After the freeze, gen2 passes only walk what the drill
    itself allocates (which IS control-plane behavior)."""
    import gc
    gc.collect()
    gc.freeze()


def thaw_drill_heap() -> None:
    """Undo freeze_drill_heap once the stall-sensitive window is over."""
    import gc
    gc.unfreeze()


async def _run(n_nodes: int, n_pods: int, caps: Capacities, policy: Policy,
               warmup_pods: int, node_kwargs: dict, pod_kwargs: dict,
               mesh=None, n_services: int = 0,
               store: ObjectStore | None = None) -> ThroughputResult:
    if store is None:
        store = ObjectStore(
            watch_window=max(1 << 18, 4 * (n_pods + n_nodes)))
    if n_services:
        from kubernetes_tpu.perf.fixtures import make_services
        for svc in make_services(n_services):
            store.create(svc)
    for node in make_nodes(n_nodes, **node_kwargs):
        store.create(node)
    sched = Scheduler(store, caps=caps, policy=policy, mesh=mesh)
    await sched.start()

    async def drain(expect: int) -> int:
        done = 0
        idle = 0
        while done < expect and idle < 3:
            got = await sched.schedule_pending(wait=0.5)
            done += got
            # a dispatched-but-unsettled batch is progress, not idleness
            busy = got > 0 or sched.inflight_batches > 0
            idle = 0 if busy else idle + 1
        return done

    if warmup_pods:
        for pod in make_pods(warmup_pods, name_prefix="warm", **pod_kwargs):
            store.create(pod)
        await asyncio.sleep(0)
        await drain(warmup_pods)
        # reclaim warmup capacity so the timed wave sees a clean cluster
        for pod in store.list("Pod", copy_objects=False):
            store.delete("Pod", pod.metadata.name, pod.metadata.namespace)
        await asyncio.sleep(0)
        while await sched.schedule_pending(wait=0.05):
            pass
        # the timed wave's metrics must not include warmup samples
        from kubernetes_tpu.scheduler.driver import SchedulerMetrics
        sched.metrics = SchedulerMetrics()
        if sched._staged is not None:
            sched._staged.reset_stats()
        # collect the warmup wave's garbage NOW: a gen2 pass triggered
        # mid-wave (walking every suite's surviving objects when several
        # share the process) otherwise lands its pause in whichever stage
        # thread tripped the allocation threshold, polluting the phase gates
        import gc
        gc.collect()

    for pod in make_pods(n_pods, **pod_kwargs):
        store.create(pod)
    await asyncio.sleep(0)

    batches_before = sched.metrics.batches
    t0 = time.perf_counter()
    done = await drain(n_pods)
    dt = time.perf_counter() - t0
    result = ThroughputResult(
        scheduled=done,
        seconds=dt,
        pods_per_sec=done / dt if dt > 0 else 0.0,
        batches=sched.metrics.batches - batches_before,
        metrics=sched.metrics.snapshot(),
        phase_hist=sched.metrics.phase_histograms(),
        pipeline=(sched._staged.snapshot()
                  if sched._staged is not None else {}),
        sharding=({
            "devices": mesh.size,
            "shard_rows": sched.statedb.shard_occupancy(),
            "flush_rows_total": sched.statedb.flush_rows_total,
            "flush_transfers_total": sched.statedb.flush_transfers_total,
            "flush_full_total": sched.statedb.flush_full_total,
        } if mesh is not None else {}),
    )
    sched.stop()
    return result


@dataclass
class DeviceSolveResult:
    """Steady-state compiled-solver throughput with device-resident state:
    the solve alone, without the host plane the e2e figure carries."""

    n_nodes: int
    batch_pods: int
    iters: int
    ms_per_solve: float
    pods_per_sec: float

    def __str__(self) -> str:
        return (f"device solve N={self.n_nodes} P={self.batch_pods}: "
                f"{self.ms_per_solve:.2f} ms/solve = "
                f"{self.pods_per_sec:.0f} pods/s")


def run_device_solve(
    n_nodes: int,
    batch_pods: int = 4096,
    iters: int = 16,
    policy: Policy = DEFAULT_POLICY,
    node_kwargs: dict | None = None,
    pod_kwargs: dict | None = None,
    mesh=None,
) -> DeviceSolveResult:
    """Time the compiled solver alone: encode one batch, then dispatch it
    `iters` times against device-resident state and block once at the end.
    The chained-dispatch shape matches the driver's steady state (PERF.md's
    'device-only solve' rows)."""
    import numpy as np

    from kubernetes_tpu.state.pod_batch import packed_batch_flags

    store = ObjectStore()
    for node in make_nodes(n_nodes, **(node_kwargs or {})):
        store.create(node)
    num = 1 << max(6, (n_nodes - 1).bit_length())
    caps = Capacities(num_nodes=num, batch_pods=batch_pods)
    sched = Scheduler(store, caps=caps, policy=policy, mesh=mesh)
    for node in store.list("Node", copy_objects=False):
        sched.statedb.upsert_node(node)
    fblob, iblob = sched._next_blobs()
    for i, pod in enumerate(make_pods(batch_pods, **(pod_kwargs or {}))):
        sched.encode_cache.encode_packed_into(fblob, iblob, i, pod)
    flags = packed_batch_flags(fblob, iblob, batch_pods,
                               sched.statedb.table, caps)
    fn = sched._get_schedule_fn(flags)
    state = sched.statedb.flush()
    rr = np.uint32(0)
    import jax

    # pin the packed batch on device once: this measures the solver, not
    # the per-call blob upload (which the e2e figure already carries);
    # under a mesh the batch replicates to every device up front
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        repl = NamedSharding(mesh, PartitionSpec())
        fblob, iblob = (jax.device_put(fblob, repl),
                        jax.device_put(iblob, repl))
    else:
        fblob, iblob = jax.device_put(fblob), jax.device_put(iblob)
    warm = fn(state, fblob, iblob, rr)   # compile + device warmup
    np.asarray(warm.assignments)
    rr = warm.rr_end                     # device-resident, chained like the
    t0 = time.perf_counter()             # driver's steady state
    last = None
    for _ in range(iters):
        last = fn(state, fblob, iblob, rr)
        rr = last.rr_end
    np.asarray(last.assignments)
    dt = time.perf_counter() - t0
    return DeviceSolveResult(
        n_nodes=n_nodes, batch_pods=batch_pods, iters=iters,
        ms_per_solve=1e3 * dt / iters,
        pods_per_sec=iters * batch_pods / dt if dt > 0 else 0.0)


@dataclass
class PreemptionResult:
    """Priority/preemption drill: saturate the cluster with low-priority
    filler, then drive a high-priority wave through the nominate-evict-
    rebind flow and measure how fast displaced capacity turns into bound
    high-priority pods."""

    n_nodes: int
    fillers: int
    wave: int
    bound_wave: int
    attempts: int
    victims: int
    seconds: float
    preemption_latency_ms: float
    victims_per_sec: float

    def __str__(self) -> str:
        return (f"preemption N={self.n_nodes}: {self.bound_wave}/{self.wave} "
                f"high-prio pods landed in {self.seconds:.2f}s via "
                f"{self.victims} victims ({self.victims_per_sec:.0f} "
                f"victims/s, p50 latency {self.preemption_latency_ms:.1f}ms)")


async def _run_preemption(n_nodes: int, wave: int,
                          fillers_per_node: int,
                          mesh=None) -> PreemptionResult:
    """Saturate every node's CPU with globalDefault-priority filler, then
    create a wave of pods whose PriorityClass outranks the filler and whose
    request only fits after an eviction. Each wave pod must take the full
    unschedulable -> solver victim pick -> evict + nominate -> requeue ->
    bind path, so the timed wave exercises all three preemption layers."""
    from kubernetes_tpu.api.objects import PriorityClass
    from kubernetes_tpu.apiserver.admission import default_chain

    store = ObjectStore(admission=default_chain(),
                        watch_window=max(1 << 18, 16 * n_nodes))
    store.create(PriorityClass.from_dict({
        "metadata": {"name": "bench-filler"}, "value": 0,
        "globalDefault": True,
        "description": "preemptible bench filler"}))
    store.create(PriorityClass.from_dict({
        "metadata": {"name": "bench-critical"}, "value": 100_000,
        "description": "preempting bench wave"}))
    for node in make_nodes(n_nodes, cpu="4", memory="8Gi"):
        store.create(node)

    num = 1 << max(6, (n_nodes - 1).bit_length())
    caps = Capacities(num_nodes=num,
                      batch_pods=min(2048, max(64, n_nodes)))
    sched = Scheduler(store, caps=caps, mesh=mesh)
    await sched.start()

    async def drain(expect: int) -> int:
        done = 0
        idle = 0
        while done < expect and idle < 6:
            got = await sched.schedule_pending(wait=0.2)
            done += got
            busy = got > 0 or sched.inflight_batches > 0
            idle = 0 if busy else idle + 1
        return done

    # two fillers per node leave 4000m - 2*1900m = 200m free: any wave
    # pod with a 2-core request is unschedulable until a filler is evicted
    n_fill = fillers_per_node * n_nodes
    for pod in make_pods(n_fill, cpu="1900m", memory="256Mi",
                         name_prefix="filler"):
        store.create(pod)
    await asyncio.sleep(0)
    filled = await drain(n_fill)
    if filled < n_fill:
        sched.stop()
        raise RuntimeError(
            f"preemption bench: only {filled}/{n_fill} fillers bound")

    # the timed wave's metrics must not include the filler phase
    from kubernetes_tpu.scheduler.driver import SchedulerMetrics
    sched.metrics = SchedulerMetrics()

    for pod in make_pods(wave, cpu="2", memory="512Mi",
                         name_prefix="crit",
                         priority_class_name="bench-critical"):
        store.create(pod)
    await asyncio.sleep(0)
    t0 = time.perf_counter()
    bound = await drain(wave)
    dt = time.perf_counter() - t0
    snap = sched.metrics.snapshot()
    pre = snap.get("preemption", {})
    sched.stop()
    return PreemptionResult(
        n_nodes=n_nodes, fillers=n_fill, wave=wave, bound_wave=bound,
        attempts=pre.get("attempts", 0), victims=pre.get("victims", 0),
        seconds=dt,
        # each wave pod's e2e sample spans first-seen -> bound, i.e. the
        # whole preemption cycle including the post-eviction requeue
        preemption_latency_ms=snap.get("e2e_p50_ms", 0.0),
        victims_per_sec=pre.get("victims", 0) / dt if dt > 0 else 0.0)


def run_preemption(n_nodes: int = 512, wave: int | None = None,
                   fillers_per_node: int = 2, mesh=None) -> PreemptionResult:
    """Blocking entry point for the priority/preemption drill."""
    if wave is None:
        wave = max(8, n_nodes // 4)
    return asyncio.run(_run_preemption(n_nodes, wave, fillers_per_node,
                                       mesh=mesh))


@dataclass
class RecoveryResult:
    nodes: int
    killed: int
    pods: int
    stranded: int
    seconds_to_recover: float
    # zone-disruption observability (node_controller.go handleDisruption):
    # the killed zone's state observed DURING the outage, and after
    zone_state_during: str = ""
    zone_state_after: str = ""

    def __str__(self) -> str:
        return (f"killed {self.killed}/{self.nodes} nodes ({self.stranded} "
                f"stranded pods): all {self.pods} pods Running on live "
                f"nodes in {self.seconds_to_recover:.2f}s "
                f"(killed zone {self.zone_state_during or '?'} -> "
                f"{self.zone_state_after or '?'})")


async def _run_recovery(n_nodes: int, n_pods: int,
                        kill_frac: float) -> RecoveryResult:
    """Chaos mode: hollow cluster under RS load, kill a node fraction
    CONCENTRATED IN ONE ZONE (so the kill crosses the unhealthy-zone
    threshold and the per-zone disruption machinery engages), and measure
    wall time until every pod is Running on a live node again (the
    kubemark-style failure drill — node lifecycle controller detects,
    evicts; ReplicaSet recreates; scheduler re-places; hollow kubelets
    ack). Heartbeat cadence scales with cluster size so a 5k+-node drill
    does not melt the host plane under heartbeat writes alone."""
    from kubernetes_tpu.agent.hollow import HollowCluster
    from kubernetes_tpu.api.objects import ReplicaSet
    from kubernetes_tpu.controllers import ControllerManager

    heartbeat = max(0.5, n_nodes / 2000.0)
    grace = max(1.5, 2.5 * heartbeat)
    store = ObjectStore(watch_window=max(1 << 18, 16 * (n_pods + n_nodes)))
    cluster = HollowCluster(store, n_nodes=n_nodes,
                            heartbeat_every=heartbeat, zones=3,
                            capacity={"cpu": "32", "memory": "64Gi",
                                      "pods": "110"})
    await cluster.start()
    mgr = ControllerManager(
        store,
        node_lifecycle_kwargs=dict(
            monitor_period=0.2, grace_period=grace, eviction_timeout=0.5,
            eviction_rate=1e9, secondary_eviction_rate=1e9),
        # /10 cut into /24s covers 16k hollow nodes (the default /16's
        # 256 starves a headline-scale drill)
        node_ipam_kwargs=dict(cluster_cidr="10.0.0.0/10"))
    await mgr.start()
    num = 1 << max(6, (n_nodes - 1).bit_length())
    sched = Scheduler(store, caps=Capacities(
        num_nodes=num, batch_pods=min(2048, max(64, n_pods // 2))))
    await sched.start()
    driver = asyncio.get_running_loop().create_task(sched.run())

    store.create(ReplicaSet.from_dict({
        "metadata": {"name": "load", "namespace": "default"},
        "spec": {"replicas": n_pods,
                 "selector": {"matchLabels": {"app": "load"}},
                 "template": {"metadata": {"labels": {"app": "load"}},
                              "spec": {"containers": [{"name": "c",
                                       "resources": {"requests": {
                                           "cpu": "100m",
                                           "memory": "64Mi"}}}]}}}}))

    def running_off(dead_nodes=frozenset()):
        return sum(1 for p in store.list("Pod", copy_objects=False)
                   if p.status.phase == "Running"
                   and p.spec.node_name not in dead_nodes)

    async with asyncio.timeout(120):
        while running_off() < n_pods:
            await asyncio.sleep(0.1)

    by_node: dict[str, int] = {}
    for p in store.list("Pod", copy_objects=False):
        by_node[p.spec.node_name] = by_node.get(p.spec.node_name, 0) + 1
    # victims all come from zone-0 (node i is in zone i%3): killing
    # kill_frac of the CLUSTER takes 3*kill_frac of the zone — at the
    # default 10% that is 30%... so take 60% of zone-0 or the requested
    # cluster fraction, whichever is larger, to cross the 55% unhealthy
    # threshold and flip the zone's disruption state
    zone0 = [k.node_name for k in cluster.kubelets.values()
             if k.labels.get("failure-domain.beta.kubernetes.io/zone")
             == "zone-0"]
    n_kill = max(max(1, int(kill_frac * n_nodes)),
                 int(0.6 * len(zone0)))
    n_kill = min(n_kill, len(zone0))
    victims = sorted(zone0, key=lambda n: by_node.get(n, 0),
                     reverse=True)[:n_kill]
    stranded = sum(by_node.get(v, 0) for v in victims)
    t0 = time.perf_counter()
    cluster.stop(victims)
    dead = frozenset(victims)
    zone_during = ""
    async with asyncio.timeout(600):
        while running_off(dead) < n_pods:
            state = mgr.node_lifecycle.zone_states.get("zone-0", "")
            if state and state != "Normal":
                zone_during = state  # disruption machinery engaged
            await asyncio.sleep(0.1)
    seconds = time.perf_counter() - t0
    zone_after = mgr.node_lifecycle.zone_states.get("zone-0", "")
    sched.stop()
    driver.cancel()
    mgr.stop()
    cluster.stop()
    return RecoveryResult(nodes=n_nodes, killed=len(victims), pods=n_pods,
                          stranded=stranded, seconds_to_recover=seconds,
                          zone_state_during=zone_during,
                          zone_state_after=zone_after)


def run_recovery(n_nodes: int = 200, n_pods: int = 600,
                 kill_frac: float = 0.1) -> RecoveryResult:
    """Blocking entry point for the chaos/recovery drill."""
    return asyncio.run(_run_recovery(n_nodes, n_pods, kill_frac))


@dataclass
class ChaosResult:
    """Convergence-under-chaos drill: a workload scheduled through a
    seeded FaultPlane (store 429s/Conflicts), with a forced watch expiry +
    watcher drop + scheduler crash mid-workload. The cluster must
    converge — every pod bound exactly once and Running — and the figure
    is how fast it does after the disruption."""

    nodes: int
    pods: int
    seed: int
    bound: int
    double_binds: int
    faults_injected: int
    recovery_ms: float
    converged: bool
    # populated only when the drill runs under the RaceDetector/watchdog
    # (race_detect=True); the contract is all three stay zero
    racy_writes: int = 0
    loop_stalls: int = 0
    max_stall_ms: float = 0.0
    # the embedded Monitor's SLO verdict: SchedulerDown must fire during
    # the induced outage and resolve once the restarted scheduler scrapes
    # healthy again
    slo_alert_fired: bool = False
    slo_alert_resolved: bool = False
    monitor_scrapes: int = 0

    def __str__(self) -> str:
        return (f"chaos N={self.nodes} P={self.pods} seed={self.seed}: "
                f"{self.bound}/{self.pods} bound "
                f"({self.double_binds} double-binds, "
                f"{self.faults_injected} faults injected), recovered in "
                f"{self.recovery_ms:.0f}ms, SLO alert "
                f"fired={self.slo_alert_fired} "
                f"resolved={self.slo_alert_resolved}")


async def _run_chaos(n_nodes: int, n_pods: int, seed: int,
                     error_rate: float,
                     race_detect: bool = False) -> ChaosResult:
    """Every control-plane verb (scheduler, hollow kubelets, informers)
    goes through one seeded FaultPlane; observation reads go to the inner
    store so the observer never draws injection. Mid-workload the plane
    expires the watch history, evicts every watcher, and the scheduler
    crashes (driver task cancelled, informers stopped, in-flight device
    results dropped) and restarts cold.

    With race_detect, the whole drill additionally runs under the
    RaceDetector (every verb audited for lost-update writes) and the
    event-loop stall watchdog — the runtime proof behind lint rules
    R1/R5: zero racy writes, zero stalls past the 100ms threshold."""
    from kubernetes_tpu.agent.hollow import HollowCluster
    from kubernetes_tpu.api.objects import Node
    from kubernetes_tpu.testing.faults import FaultPlane
    from kubernetes_tpu.testing.races import LoopStallWatchdog, RaceDetector

    freeze_drill_heap()

    cap = {"cpu": "16", "memory": "32Gi", "pods": "110"}
    inner = ObjectStore(watch_window=max(1 << 16, 8 * (n_pods + n_nodes)))
    # nodes pre-registered through the inner store: setup is not the thing
    # under test (the kubelets' get finds them, so registration never
    # draws an injected create failure at start)
    for i in range(n_nodes):
        inner.create(Node.from_dict({
            "metadata": {"name": f"hollow-{i}",
                         "labels": {"kubernetes.io/hostname": f"hollow-{i}"}},
            "status": {"allocatable": dict(cap), "capacity": dict(cap)}}))
    plane = FaultPlane(inner, seed=seed, error_rate=error_rate)
    # detector outside the plane: components' verbs draw injection AND are
    # audited; the detector's own bucket peeks bypass both
    store = RaceDetector(plane) if race_detect else plane
    watchdog = LoopStallWatchdog().start() if race_detect else None
    cluster = HollowCluster(store, n_nodes=n_nodes, heartbeat_every=0.5,
                            capacity=cap, resync_every=0.2)
    await cluster.start()
    num = 1 << max(6, (n_nodes - 1).bit_length())
    caps = Capacities(num_nodes=num,
                      batch_pods=min(256, max(64, n_pods)))
    loop = asyncio.get_running_loop()
    sched = Scheduler(store, caps=caps)
    driver = loop.create_task(sched.run())

    # embedded monitoring plane, deterministically stepped (scrape_once at
    # fixed drill points, not the jittered background loop): the scheduler
    # is a local render target through a mutable holder, so the crash
    # window scrapes as a failure (up=0) and SchedulerDown must fire, then
    # resolve after the restart. store=None: the monitor must not write
    # (the RaceDetector audit stays about the control plane under test).
    from kubernetes_tpu.obs.monitor import Monitor

    schedref = {"sched": sched}

    def scheduler_exposition() -> str:
        s = schedref["sched"]
        if s is None:
            raise ConnectionError("scheduler crashed")
        return s.metrics.registry.render()

    monitor = Monitor(store=None, interval=0.5, alert_for_s=0.0)
    monitor.add_local_target("scheduler", scheduler_exposition)

    for pod in make_pods(n_pods, cpu="100m", memory="64Mi",
                         name_prefix="chaos"):
        inner.create(pod)

    def crash_scheduler() -> None:
        # hard kill: no stop() — in-flight device results are dropped on
        # the floor, assumed-but-unconfirmed state is lost. kill() also
        # aborts the staged stage threads mid-batch: solved-but-unapplied
        # work must vanish (crash-consistency), never bind post-mortem
        # through a still-queued loop closure
        driver.cancel()
        sched.kill()
        schedref["sched"] = None

    async with asyncio.timeout(180):
        while len(plane.bind_counts) < max(1, n_pods // 3):
            await asyncio.sleep(0.02)
    await monitor.scrape_once()  # healthy baseline: up{job="scheduler"}=1
    crash_scheduler()
    plane.expire_watch_history()
    plane.drop_watchers()
    # the outage window: the dead scheduler scrapes as down and the SLO
    # alert must transition to firing before the replacement comes up
    await monitor.scrape_once()
    t0 = time.perf_counter()
    sched = Scheduler(store, caps=caps)
    schedref["sched"] = sched
    driver = loop.create_task(sched.run())

    def converged() -> bool:
        pods = inner.list("Pod", copy_objects=False)
        return (len(pods) >= n_pods
                and all(p.spec.node_name and p.status.phase == "Running"
                        for p in pods))

    async with asyncio.timeout(300):
        while not converged():
            await asyncio.sleep(0.05)
    recovery_ms = 1e3 * (time.perf_counter() - t0)
    # post-convergence scrape: the restarted scheduler answers again, so
    # the outage alert must resolve
    await monitor.scrape_once()
    driver.cancel()
    sched.stop()
    cluster.stop()
    thaw_drill_heap()
    stalls = watchdog.stop() if watchdog is not None else []
    double = sum(1 for v in plane.bind_counts.values() if v > 1)
    return ChaosResult(
        nodes=n_nodes, pods=n_pods, seed=seed,
        bound=len(plane.bind_counts), double_binds=double,
        faults_injected=plane.stats.injected_total,
        recovery_ms=recovery_ms,
        converged=double == 0 and len(plane.bind_counts) >= n_pods,
        racy_writes=len(store.racy_writes) if race_detect else 0,
        loop_stalls=len(stalls),
        max_stall_ms=1e3 * max(stalls, default=0.0),
        slo_alert_fired=monitor.fired("SchedulerDown"),
        slo_alert_resolved=monitor.resolved("SchedulerDown"),
        monitor_scrapes=3)


def run_chaos(n_nodes: int = 128, n_pods: int = 200, seed: int = 1234,
              error_rate: float = 0.05,
              race_detect: bool = False) -> ChaosResult:
    """Blocking entry point for the convergence-under-chaos drill."""
    return asyncio.run(_run_chaos(n_nodes, n_pods, seed, error_rate,
                                  race_detect=race_detect))


@dataclass
class AutoscalerResult:
    """Scale-up drill: a burst of pods lands on an empty (or undersized)
    cluster and the autoscaler must grow a node group until everything
    binds. The headline figure is wall time from burst to all-bound
    (scaleup_convergence_ms); the secondary one is the what-if probe cost
    (ms/solve on the simulator's device program)."""

    pods: int
    nodes_added: int
    group_max: int
    seconds: float
    scaleup_convergence_ms: float
    sim_solves: int
    sim_ms_per_solve: float

    def __str__(self) -> str:
        return (f"autoscaler: {self.pods} pods bound after adding "
                f"{self.nodes_added}/{self.group_max} nodes in "
                f"{self.seconds:.2f}s ({self.sim_solves} probe solves, "
                f"{self.sim_ms_per_solve:.2f} ms/solve)")


async def _run_autoscaler(n_pods: int, group_max: int,
                          pod_cpu: str) -> AutoscalerResult:
    from kubernetes_tpu.autoscaler import ClusterAutoscaler
    from kubernetes_tpu.cloudprovider import FakeCloud

    store = ObjectStore(watch_window=max(1 << 16, 16 * n_pods))
    cloud = FakeCloud()
    cloud.add_node_group("bench-pool", 0, group_max,
                         cpu="16", memory="32Gi", pods="110")
    num = 1 << max(6, (group_max - 1).bit_length())
    sched = Scheduler(store, caps=Capacities(
        num_nodes=num, batch_pods=min(1024, max(64, n_pods // 2))))
    loop = asyncio.get_running_loop()
    driver = loop.create_task(sched.run())
    autoscaler = ClusterAutoscaler(
        store, cloud,
        caps=Capacities(num_nodes=num, batch_pods=min(256, max(64, n_pods))),
        scan_interval=0.05, scaleup_cooldown=0.0,
        scaledown_cooldown=3600.0, unneeded_time=3600.0,
        max_expansion=min(8, group_max))
    await autoscaler.start()

    for pod in make_pods(n_pods, cpu=pod_cpu, memory="128Mi",
                         name_prefix="burst"):
        store.create(pod)

    def all_bound() -> bool:
        pods = store.list("Pod", copy_objects=False)
        return len(pods) >= n_pods and all(p.spec.node_name for p in pods)

    t0 = time.perf_counter()
    async with asyncio.timeout(300):
        while not all_bound():
            await asyncio.sleep(0.02)
    dt = time.perf_counter() - t0
    sim = autoscaler.simulator
    autoscaler.stop()
    driver.cancel()
    sched.stop()
    return AutoscalerResult(
        pods=n_pods, nodes_added=autoscaler.scaleups,
        group_max=group_max, seconds=dt,
        scaleup_convergence_ms=1e3 * dt,
        sim_solves=sim.solve_count,
        sim_ms_per_solve=(1e3 * sim.solve_seconds / sim.solve_count
                          if sim.solve_count else 0.0))


def run_autoscaler(n_pods: int = 256, group_max: int = 16,
                   pod_cpu: str = "500m") -> AutoscalerResult:
    """Blocking entry point for the autoscaler scale-up drill."""
    return asyncio.run(_run_autoscaler(n_pods, group_max, pod_cpu))


@dataclass
class DefragResult:
    """Gang-defragmentation drill: a cluster fragmented by skewed fillers
    (every node's headroom below one gang pod's request, aggregate free
    space ample) receives a Pending gang that cannot schedule; the
    descheduler must plan and execute a minimal move set until the gang
    lands and every displaced pod rebinds. The headline figure is wall
    time from descheduler start to gang-schedulability restored
    (defrag_convergence_ms); the RaceDetector audits the whole drill."""

    nodes: int
    gang: int
    max_moves: int
    seed: int
    start_unschedulable: bool   # the gang was unbound before the planner
    dry_run_planned: int        # moves a dry-run pass WOULD have made
    dry_run_moves: int          # must stay 0
    moves: int
    rollbacks: int
    gangs_defragged: int
    defrag_convergence_ms: float
    sim_solves: int
    sim_ms_per_solve: float
    double_binds: int
    racy_writes: int
    converged: bool

    def __str__(self) -> str:
        return (f"defrag N={self.nodes} gang={self.gang} seed={self.seed}: "
                f"{self.moves} move(s) (budget {self.max_moves}), gang "
                f"landed in {self.defrag_convergence_ms:.0f}ms "
                f"({self.sim_solves} probe solves, "
                f"{self.sim_ms_per_solve:.2f} ms/solve, "
                f"{self.double_binds} double-binds, "
                f"{self.racy_writes} racy writes)")


async def _run_defrag(n_nodes: int, gang_size: int, max_moves: int,
                      seed: int) -> DefragResult:
    from kubernetes_tpu.api.objects import Node, Pod
    from kubernetes_tpu.descheduler import Descheduler
    from kubernetes_tpu.gang import (
        GROUP_MIN_ANNOTATION,
        GROUP_NAME_ANNOTATION,
    )
    from kubernetes_tpu.testing.races import RaceDetector

    import numpy as np

    rng = np.random.RandomState(seed)
    inner = ObjectStore(watch_window=max(1 << 16, 8 * n_nodes))
    # the fragmented shape: 4-cpu nodes, one 2-cpu filler each (headroom 2
    # everywhere), a seeded quarter additionally carrying a 500m skew pod
    # (headroom 1.5) — no node fits a 3-cpu gang pod, aggregate free space
    # is ~2 cpu per node. Fillers are created pre-bound (setup is not the
    # thing under test; their later rebinds ARE, and count exactly once).
    skewed = set(rng.choice(n_nodes, size=n_nodes // 4, replace=False))
    for i in range(n_nodes):
        name = f"frag-{i:06d}"
        inner.create(Node.from_dict({
            "metadata": {"name": name,
                         "labels": {"kubernetes.io/hostname": name}},
            "status": {"allocatable": {"cpu": "4", "memory": "8Gi",
                                       "pods": "110"},
                       "conditions": [{"type": "Ready",
                                       "status": "True"}]}}))
        inner.create(Pod.from_dict({
            "metadata": {"name": f"fill-{i:06d}"},
            "spec": {"containers": [{"name": "c", "resources": {
                "requests": {"cpu": "2", "memory": "256Mi"}}}],
                "nodeName": name}}))
        if i in skewed:
            inner.create(Pod.from_dict({
                "metadata": {"name": f"skew-{i:06d}"},
                "spec": {"containers": [{"name": "c", "resources": {
                    "requests": {"cpu": "500m", "memory": "64Mi"}}}],
                    "nodeName": name}}))
    store = RaceDetector(inner)
    num = 1 << max(6, (n_nodes - 1).bit_length())
    caps = Capacities(num_nodes=num, batch_pods=64)
    loop = asyncio.get_running_loop()
    sched = Scheduler(store, caps=caps)
    driver = loop.create_task(sched.run())

    ann = {GROUP_NAME_ANNOTATION: "defrag-gang",
           GROUP_MIN_ANNOTATION: str(gang_size)}
    for j in range(gang_size):
        inner.create(Pod.from_dict({
            "metadata": {"name": f"gang-{j:03d}", "annotations": dict(ann)},
            "spec": {"containers": [{"name": "c", "resources": {
                "requests": {"cpu": "3", "memory": "512Mi"}}}]}}))

    def gang_pods():
        return [p for p in inner.list("Pod", copy_objects=False)
                if p.metadata.name.startswith("gang-")]

    # let the scheduler take its shot: the gang must NOT land on the
    # fragmented cluster (that unschedulability is the drill's premise)
    await asyncio.sleep(max(0.75, n_nodes / 20000))
    start_unschedulable = all(not p.spec.node_name for p in gang_pods())

    # scan_interval parks the background loop; the drill steps run_once
    # itself so pass timing is deterministic
    descheduler = Descheduler(
        store, caps=Capacities(num_nodes=num,
                               batch_pods=max(64, gang_size + max_moves)),
        scan_interval=3600.0, max_moves=max_moves,
        cooldown=3600.0, rollback_after=60.0, dry_run=True)
    await descheduler.start()
    # dry-run first: the plan is computed and counted, nothing moves
    descheduler.run_once()
    dry_run_planned = descheduler.planned_moves
    dry_run_moves = descheduler.moves

    descheduler.dry_run = False
    t0 = time.perf_counter()

    def landed() -> bool:
        return descheduler.gangs_defragged >= 1

    async with asyncio.timeout(300):
        while not landed():
            descheduler.run_once()
            await asyncio.sleep(0.05)
    dt = time.perf_counter() - t0

    def all_bound() -> bool:
        return all(p.spec.node_name
                   for p in inner.list("Pod", copy_objects=False))

    async with asyncio.timeout(60):
        while not all_bound():
            await asyncio.sleep(0.02)
    sim = descheduler.simulator
    descheduler.stop()
    driver.cancel()
    sched.stop()
    double = sum(1 for v in store.bind_counts.values() if v > 1)
    bound_gang = sum(1 for p in gang_pods() if p.spec.node_name)
    return DefragResult(
        nodes=n_nodes, gang=gang_size, max_moves=max_moves, seed=seed,
        start_unschedulable=start_unschedulable,
        dry_run_planned=dry_run_planned, dry_run_moves=dry_run_moves,
        moves=descheduler.moves, rollbacks=descheduler.rollbacks,
        gangs_defragged=descheduler.gangs_defragged,
        defrag_convergence_ms=1e3 * dt,
        sim_solves=sim.solve_count,
        sim_ms_per_solve=(1e3 * sim.solve_seconds / sim.solve_count
                          if sim.solve_count else 0.0),
        double_binds=double,
        racy_writes=len(store.racy_writes),
        converged=(bound_gang >= gang_size
                   and descheduler.moves <= max_moves
                   and dry_run_moves == 0))


def run_defrag(n_nodes: int = 128, gang_size: int = 8, max_moves: int = 8,
               seed: int = 1234) -> DefragResult:
    """Blocking entry point for the gang-defragmentation drill."""
    return asyncio.run(_run_defrag(n_nodes, gang_size, max_moves, seed))


def run_throughput(
    n_nodes: int,
    n_pods: int,
    caps: Capacities | None = None,
    policy: Policy = DEFAULT_POLICY,
    warmup_pods: int | None = None,
    node_kwargs: dict | None = None,
    pod_kwargs: dict | None = None,
    mesh=None,
    n_services: int = 0,
    store: ObjectStore | None = None,
) -> ThroughputResult:
    """Blocking entry point: returns sustained scheduling throughput.
    `store` (empty; default a fresh ObjectStore) lets a caller inspect the
    bound pods afterwards."""
    if caps is None:
        num_nodes = 1 << max(6, (n_nodes - 1).bit_length())
        # large batches amortize the fixed per-batch dispatch/readback
        # cost; 4096 was the sweet spot of the retired chip records — 8192
        # crossed an XLA layout cliff at 16k nodes (not measured on
        # today's code)
        caps = Capacities(num_nodes=num_nodes,
                          batch_pods=min(4096, max(64, n_pods // 6)))
    if warmup_pods is None:
        warmup_pods = min(2 * caps.batch_pods, n_pods)
    return asyncio.run(_run(n_nodes, n_pods, caps, policy, warmup_pods,
                            node_kwargs or {}, pod_kwargs or {}, mesh,
                            n_services=n_services, store=store))


@dataclass
class OverloadResult:
    """Noisy-tenant overload drill: a tenant floods the HTTP apiserver at a
    multiple of the scheduler's own request rate while a workload
    schedules through it. APF must keep the scheduler flow's latency
    bounded (p99 within 5x the unloaded baseline), every pod must bind
    exactly once, and the flood must be shed with honest 429s — the API
    plane stays alive instead of melting uniformly."""

    nodes: int
    pods: int
    seed: int
    flood_multiplier: float
    bound: int
    double_binds: int
    # p99s are SERVER-side seat-to-response latencies for the scheduler's
    # flow schema (FlowController.latency_samples) — what the API plane
    # actually did to the scheduler, unpolluted by client-process GIL
    # contention from the flood threads sharing the drill process
    p99_unloaded_ms: float
    p99_loaded_ms: float
    flood_requests: int
    flood_rejected: int
    sched_rps: float
    converged: bool
    racy_writes: int = 0
    loop_stalls: int = 0
    max_stall_ms: float = 0.0
    dispatched: dict = field(default_factory=dict)
    rejected: dict = field(default_factory=dict)

    @property
    def p99_bounded(self) -> bool:
        """The drill's latency contract: loaded p99 within 5x unloaded,
        with a 100ms floor so a millisecond-scale unloaded baseline on a
        busy CI box can't fail the drill on scheduler-jitter noise (at
        drill scale the 5x term dominates)."""
        return self.p99_loaded_ms <= max(5 * self.p99_unloaded_ms, 100.0)

    def __str__(self) -> str:
        return (f"overload N={self.nodes} P={self.pods} "
                f"x{self.flood_multiplier:.0f} flood: {self.bound}/"
                f"{self.pods} bound, sched p99 {self.p99_unloaded_ms:.1f}ms"
                f" -> {self.p99_loaded_ms:.1f}ms, flood "
                f"{self.flood_rejected}/{self.flood_requests} shed")


def _p99_ms(samples) -> float:
    if not samples:
        return 0.0
    ordered = sorted(samples)
    return 1e3 * ordered[int(0.99 * (len(ordered) - 1))]


def run_overload(n_nodes: int = 64, n_pods: int = 256, seed: int = 2026,
                 flood_multiplier: float = 50.0, race_detect: bool = True,
                 warm_pods: int = 32, probes: int = 40) -> OverloadResult:
    """Blocking entry point for the noisy-tenant overload drill.

    Topology is the deployment shape (tests/http_util.py): the APIServer —
    APF + watch cache on, over a seeded FaultPlane (and RaceDetector +
    loop-stall watchdog when race_detect) — runs its own event loop in a
    background thread; the scheduler drives it over TCP as
    system:kube-scheduler, and `FaultPlane.flood` fires the tenant's
    seeded traffic storm from client threads."""
    import random as _random
    import socket as _socket
    import threading

    from kubernetes_tpu.api.objects import Node
    from kubernetes_tpu.apiserver.auth import TokenAuthenticator, UserInfo
    from kubernetes_tpu.apiserver.http import APIServer, RemoteStore
    from kubernetes_tpu.apiserver.store import TooManyRequests
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.testing.faults import FaultPlane
    from kubernetes_tpu.testing.races import LoopStallWatchdog, RaceDetector

    cap = {"cpu": "16", "memory": "32Gi", "pods": "110"}
    inner = ObjectStore(watch_window=max(1 << 16, 8 * (n_pods + n_nodes)))
    for i in range(n_nodes):
        inner.create(Node.from_dict({
            "metadata": {"name": f"ovl-{i}",
                         "labels": {"kubernetes.io/hostname": f"ovl-{i}"}},
            "status": {"allocatable": dict(cap), "capacity": dict(cap)}}))
    plane = FaultPlane(inner, seed=seed)
    server_store = RaceDetector(plane) if race_detect else plane
    auth = TokenAuthenticator({
        "sched-token": UserInfo("system:kube-scheduler",
                                ("system:authenticated",)),
        "tenant-token": UserInfo("tenant-a", ("system:authenticated",))})

    started = threading.Event()
    holder: dict = {}

    freeze_drill_heap()

    def serve() -> None:
        async def main():
            server = APIServer(server_store, authenticator=auth,
                               max_in_flight=64, watch_cache=True)
            await server.start()
            watchdog = LoopStallWatchdog().start() if race_detect else None
            holder["server"] = server
            holder["loop"] = asyncio.get_running_loop()
            holder["shutdown"] = asyncio.Event()
            started.set()
            await holder["shutdown"].wait()
            holder["stalls"] = watchdog.stop() if watchdog else []
            await server.stop()

        asyncio.run(main())

    thread = threading.Thread(target=serve, daemon=True)
    thread.start()
    if not started.wait(30):
        raise RuntimeError("overload drill: APIServer thread failed to start")
    server = holder["server"]
    host, port = server.host, server.port

    flood_stop = threading.Event()
    flood_lock = threading.Lock()
    flood_counts = {"requests": 0, "rejected": 0}
    flood_threads: list[threading.Thread] = []
    flood_rate = {"rps": 20.0}

    def flood_hook(flow: str, mult: float, rng: _random.Random) -> None:
        # one thread per ~100 target rps, each pacing its share with
        # seeded jitter so the burst pattern replays from the fault seed
        rate = max(20.0, flood_rate["rps"]) * mult
        n_threads = min(8, max(1, round(rate / 100)))

        def storm(thread_seed: int) -> None:
            r = _random.Random(thread_seed)
            per = rate / n_threads
            req = (f"GET /api/v1/pods HTTP/1.1\r\nHost: {host}\r\n"
                   "Authorization: Bearer tenant-token\r\n"
                   "Accept: application/json\r\n"
                   "Connection: close\r\n\r\n").encode()
            while not flood_stop.is_set():
                status = 0
                try:
                    with _socket.create_connection((host, port),
                                                   timeout=10) as sock:
                        sock.sendall(req)
                        head = b""
                        while b"\r\n\r\n" not in head and len(head) < 65536:
                            chunk = sock.recv(65536)
                            if not chunk:
                                break
                            head += chunk
                        # drain and DISCARD the body undecoded: the flood
                        # must cost the SERVER — a real tenant parses its
                        # responses on the tenant's machine, and json-
                        # decoding 8 threads' worth of big lists in this
                        # process would starve the serving loop's GIL and
                        # corrupt the stall measurement
                        while sock.recv(65536):
                            pass
                    status = int(head.split(None, 2)[1])
                except Exception:
                    pass
                with flood_lock:
                    flood_counts["requests"] += 1
                    if status == 429:
                        flood_counts["rejected"] += 1
                flood_stop.wait(r.uniform(0.5, 1.5) / per)

        for _ in range(n_threads):
            t = threading.Thread(target=storm,
                                 args=(rng.randrange(1 << 32),),
                                 daemon=True)
            t.start()
            flood_threads.append(t)

    plane.flood_hook = flood_hook

    async def drive() -> OverloadResult:
        # small bind batches on purpose: one bulk bind is a single
        # synchronous store op on the serving loop, and the drill's
        # zero->100ms-stall contract bounds how long any one op may run
        caps = Capacities(num_nodes=1 << max(6, (n_nodes - 1).bit_length()),
                          batch_pods=min(64, max(16, n_pods)))
        sched_client = RemoteStore(host, port, token="sched-token")
        creator = RemoteStore(host, port, token="sched-token")
        sched = Scheduler(sched_client, caps=caps)
        loop = asyncio.get_running_loop()
        driver = loop.create_task(sched.run())

        def create_with_retry(pod) -> None:
            while True:
                try:
                    creator.create(pod)
                    return
                except TooManyRequests as e:
                    # runs under asyncio.to_thread — never on the event loop
                    time.sleep(max(0.05, getattr(e, "retry_after", 0.0)))  # ktpu: allow[blocking-in-async]

        async def wait_bound(expect: int, timeout_s: float) -> bool:
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                pods = await asyncio.to_thread(creator.list, "Pod")
                if sum(1 for p in pods if p.spec.node_name) >= expect:
                    return True
                await asyncio.sleep(0.1)
            return False

        # the scheduler flow's server-side latency samples — every create/
        # list/bind the scheduler identity makes lands here, so each phase
        # has the same request mix and the two p99s compare like for like
        def sched_samples() -> list[float]:
            return list(server.flow.latency_samples.get("system", ()))

        # ---- phase A: unloaded baseline (convergence polling while the
        # warm workload binds, then idle probes) ----
        t_warm = time.perf_counter()
        for pod in make_pods(warm_pods, cpu="100m", memory="64Mi",
                             name_prefix="warm"):
            await asyncio.to_thread(create_with_retry, pod)
        warm_ok = await wait_bound(warm_pods, 120)
        warm_s = max(time.perf_counter() - t_warm, 1e-3)
        flood_rate["rps"] = max(
            20.0, server.flow.dispatched.get("system", 0) / warm_s)
        probe = RemoteStore(host, port, token="sched-token")
        for _ in range(probes):
            await asyncio.to_thread(probe.list, "Pod")
            await asyncio.sleep(0.01)
        n_unloaded = len(sched_samples())

        # ---- phase B: the storm ----
        plane.flood("tenant-a", flood_multiplier)
        for pod in make_pods(n_pods, cpu="100m", memory="64Mi",
                             name_prefix="ovl"):
            await asyncio.to_thread(create_with_retry, pod)
        conv = await wait_bound(warm_pods + n_pods, 240)
        for _ in range(probes):
            await asyncio.to_thread(probe.list, "Pod")
        samples = sched_samples()
        unloaded, loaded = samples[:n_unloaded], samples[n_unloaded:]
        flood_stop.set()
        for t in flood_threads:
            t.join(timeout=5)
        driver.cancel()
        sched.stop()

        double = sum(1 for v in plane.bind_counts.values() if v > 1)
        return OverloadResult(
            nodes=n_nodes, pods=warm_pods + n_pods, seed=seed,
            flood_multiplier=flood_multiplier,
            bound=len(plane.bind_counts), double_binds=double,
            p99_unloaded_ms=_p99_ms(unloaded),
            p99_loaded_ms=_p99_ms(loaded),
            flood_requests=flood_counts["requests"],
            flood_rejected=flood_counts["rejected"],
            sched_rps=flood_rate["rps"],
            converged=(warm_ok and conv and double == 0
                       and len(plane.bind_counts) >= warm_pods + n_pods),
            racy_writes=len(server_store.racy_writes) if race_detect else 0,
            dispatched=dict(server.flow.dispatched),
            rejected=dict(server.flow.rejected))

    try:
        result = asyncio.run(drive())
    finally:
        flood_stop.set()
        holder["loop"].call_soon_threadsafe(holder["shutdown"].set)
        thread.join(timeout=15)
        thaw_drill_heap()
    stalls = holder.get("stalls", [])
    result.loop_stalls = len(stalls)
    result.max_stall_ms = 1e3 * max(stalls, default=0.0)
    return result


@dataclass
class RollingRestartResult:
    """Rolling-restart chaos drill: 3 stateless apiserver replicas over one
    shared store serve a live scheduler + informer + watcher workload while
    every replica is killed once mid-flight — hard (SIGKILL-style transport
    aborts) and graceful (drain: readyz 503, in-flight finishes, watchers
    get the terminal DRAIN frame) — then restarted. The control plane must
    come out exactly-once and gapless: every pod bound once, zero racy
    read-modify-writes, zero loop stalls past 100ms, and the dedicated
    watcher's resourceVersion stream equal to the store's authoritative
    Pod history — no gap, no duplicate — across every failover."""

    nodes: int
    pods: int
    seed: int
    replicas: int
    bound: int
    double_binds: int
    failovers: int
    failover_p99_ms: float
    resumes: int          # informer resume-from-rv successes (cheap path)
    relists: int          # informer full relists during the drill
    watch_resumes: int    # dedicated watcher's transport-level resumes
    watch_events: int
    watch_gaps: int
    watch_dupes: int
    converged: bool
    racy_writes: int = 0
    loop_stalls: int = 0
    max_stall_ms: float = 0.0
    replica_faults: list = field(default_factory=list)

    @property
    def gate(self) -> bool:
        """The drill's whole contract in one bool (the bench's gate)."""
        return (self.converged and self.double_binds == 0
                and self.racy_writes == 0 and self.loop_stalls == 0
                and self.watch_gaps == 0 and self.watch_dupes == 0
                and self.watch_resumes >= 1)

    def __str__(self) -> str:
        return (f"rolling-restart R={self.replicas} N={self.nodes} "
                f"P={self.pods}: {self.bound}/{self.pods} bound, "
                f"{len(self.replica_faults)} faults, "
                f"{self.failovers} failovers p99 "
                f"{self.failover_p99_ms:.1f}ms, resumes/relists "
                f"{self.resumes}/{self.relists}, watch "
                f"{self.watch_events} events {self.watch_gaps} gaps "
                f"{self.watch_dupes} dupes")


def run_rolling_restart(n_nodes: int = 16, n_pods: int = 96,
                        seed: int = 2027, replicas: int = 3,
                        race_detect: bool = True) -> RollingRestartResult:
    """Blocking entry point for the rolling-restart HA drill.

    Topology: a ReplicaSet of `replicas` APIServers (watch cache on) over
    ONE seeded FaultPlane (plus RaceDetector + loop-stall watchdog when
    `race_detect`) on a background serving loop; the scheduler, a pod
    creator, and a dedicated resourceVersion-recording watcher all drive
    it over TCP through replica-aware RemoteStores. Replica injuries fire
    through the FaultPlane's seeded action schedule — op-indexed, so each
    one lands at the same point of the workload on replay — at the 1/4,
    1/2 and 3/4 pod-creation milestones: hard kill, graceful drain, hard
    kill. Each victim is restarted on its original port before the next
    injury, the rolling shape."""
    import threading

    from kubernetes_tpu.api.objects import Node
    from kubernetes_tpu.apiserver.auth import TokenAuthenticator, UserInfo
    from kubernetes_tpu.apiserver.store import AlreadyExists, TooManyRequests
    from kubernetes_tpu.client.informer import _metrics
    from kubernetes_tpu.testing.faults import FaultPlane
    from kubernetes_tpu.testing.races import LoopStallWatchdog, RaceDetector
    from kubernetes_tpu.testing.replicas import ReplicaSet

    cap = {"cpu": "16", "memory": "32Gi", "pods": "110"}
    inner = ObjectStore(watch_window=max(1 << 16, 8 * (n_pods + n_nodes)))
    for i in range(n_nodes):
        inner.create(Node.from_dict({
            "metadata": {"name": f"ha-{i}",
                         "labels": {"kubernetes.io/hostname": f"ha-{i}"}},
            "status": {"allocatable": dict(cap), "capacity": dict(cap)}}))
    plane = FaultPlane(inner, seed=seed)
    server_store = RaceDetector(plane) if race_detect else plane
    auth = TokenAuthenticator({
        "sched-token": UserInfo("system:kube-scheduler",
                                ("system:authenticated",))})

    freeze_drill_heap()

    rs = ReplicaSet(server_store, n=replicas, watch_cache=True,
                    authenticator=auth).start()
    for i, control in enumerate(rs.controls()):
        plane.attach_replica(i, control)
    watchdog_box: dict = {}
    if race_detect:
        rs._call(lambda: watchdog_box.update(
            dog=LoopStallWatchdog().start()))

    async def drive() -> RollingRestartResult:
        caps = Capacities(num_nodes=1 << max(6, (n_nodes - 1).bit_length()),
                          batch_pods=min(64, max(16, n_pods)))
        sched_client = rs.client(token="sched-token")
        creator = rs.client(token="sched-token")
        watcher_client = rs.client(token="sched-token")
        mx = _metrics("Pod")
        relists0, resumes0 = mx[3].value, mx[4].value
        sched = Scheduler(sched_client, caps=caps)
        loop = asyncio.get_running_loop()
        driver = loop.create_task(sched.run())

        # the coherence witness: one logical watch across the whole
        # replica set, recording every (type, resourceVersion) it delivers
        observed: list[tuple[str, int]] = []
        watcher = watcher_client.watch_resilient("Pod", since=0)
        watch_stop = asyncio.Event()

        async def observe() -> None:
            while not watch_stop.is_set():
                try:
                    ev = await watcher.next(timeout=0.5)
                except ConnectionError:
                    return  # every endpoint stayed dead past the deadline
                if ev is not None:
                    observed.append((ev.type, ev.resource_version))

        observer = loop.create_task(observe())

        def create_with_retry(pod) -> None:
            while True:
                try:
                    creator.create(pod)
                    return
                except AlreadyExists:
                    # a failover replay: the first send landed before its
                    # replica died — the shared store already has the pod,
                    # which is exactly the exactly-once contract
                    return
                except TooManyRequests as e:
                    # runs under asyncio.to_thread — never on the event loop
                    time.sleep(max(0.05, getattr(e, "retry_after", 0.0)))  # ktpu: allow[blocking-in-async]

        async def wait_bound(expect: int, timeout_s: float) -> bool:
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                pods = await asyncio.to_thread(creator.list, "Pod")
                if sum(1 for p in pods if p.spec.node_name) >= expect:
                    return True
                await asyncio.sleep(0.1)
            return False

        async def wait_fault(count: int) -> None:
            # the scheduled injury fires inside a store tick on the
            # serving loop; wait until it has actually landed before
            # restarting the victim
            deadline = time.monotonic() + 30
            while len(plane.stats.replica_faults) < count \
                    and time.monotonic() < deadline:
                await asyncio.sleep(0.01)

        async def restart_replica(idx: int) -> None:
            # a draining victim closes its listener early but stops late:
            # wait for the port to free before rebinding it
            deadline = time.monotonic() + 15
            while rs.servers[idx]._server is not None \
                    and time.monotonic() < deadline:
                await asyncio.sleep(0.01)
            await asyncio.to_thread(rs.restart, idx)

        # injuries at pod-creation milestones, fired via the seeded action
        # schedule (op-indexed: the next store op pulls the trigger)
        milestones = {
            n_pods // 4: ("kill", 0),
            n_pods // 2: ("drain", 1),
            (3 * n_pods) // 4: ("kill", 2),
        }
        faults_seen = 0
        for i, pod in enumerate(make_pods(n_pods, cpu="100m",
                                          memory="64Mi",
                                          name_prefix="ha")):
            injury = milestones.get(i)
            if injury is not None:
                kind, victim = injury
                if kind == "kill":
                    plane.schedule(
                        plane.stats.ops + 1,
                        lambda p, v=victim: p.kill_replica(v),
                        f"kill-replica-{victim}")
                else:
                    plane.schedule(
                        plane.stats.ops + 1,
                        lambda p, v=victim: p.drain_replica(v),
                        f"drain-replica-{victim}")
                faults_seen += 1
                await asyncio.to_thread(create_with_retry, pod)
                await wait_fault(faults_seen)
                await restart_replica(victim)
            else:
                await asyncio.to_thread(create_with_retry, pod)
        conv = await wait_bound(n_pods, 240)

        # fence the coherence check at a fixed revision, then let the
        # watcher catch up to it before comparing against the store's
        # authoritative history
        fence_rv = inner.resource_version
        deadline = time.monotonic() + 30
        while (watcher.last_rv or 0) < fence_rv \
                and time.monotonic() < deadline \
                and not observer.done():
            await asyncio.sleep(0.05)
        watch_stop.set()
        watcher.stop()
        observer.cancel()
        driver.cancel()
        sched.stop()

        expected = [e.resource_version for e in inner._history
                    if e.kind == "Pod" and e.resource_version <= fence_rv]
        got = [rv for _, rv in observed if rv <= fence_rv]
        gaps = len(set(expected) - set(got))
        dupes = len(got) - len(set(got))
        double = sum(1 for v in plane.bind_counts.values() if v > 1)
        samples = (list(sched_client.failover_samples)
                   + list(creator.failover_samples)
                   + list(watcher_client.failover_samples))
        return RollingRestartResult(
            nodes=n_nodes, pods=n_pods, seed=seed, replicas=replicas,
            bound=len(plane.bind_counts), double_binds=double,
            failovers=(sched_client.failover_total
                       + creator.failover_total
                       + watcher_client.failover_total),
            failover_p99_ms=_p99_ms([s / 1e3 for s in samples]),
            resumes=int(mx[4].value - resumes0),
            relists=int(mx[3].value - relists0),
            watch_resumes=watcher.resumes,
            watch_events=len(got), watch_gaps=gaps, watch_dupes=dupes,
            converged=(conv and double == 0
                       and len(plane.bind_counts) >= n_pods),
            racy_writes=len(server_store.racy_writes) if race_detect else 0,
            replica_faults=list(plane.stats.replica_faults))

    try:
        result = asyncio.run(drive())
    finally:
        stalls = rs._call(watchdog_box["dog"].stop) \
            if watchdog_box else []
        rs.stop()
        thaw_drill_heap()
    result.loop_stalls = len(stalls)
    result.max_stall_ms = 1e3 * max(stalls, default=0.0)
    return result


@dataclass
class StoreHAResult:
    """Store-HA chaos drill: N *replicated stores* (each a ReplicatedStore
    + apiserver + WAL stream + lease candidacy, apiserver/replication.py)
    serve a live scheduler + coherence-watcher workload while the PRIMARY
    store is killed mid-flight — the last-SPOF failure the stateless
    rolling-restart drill could never inject. A standby must win the
    lease, replay its WAL prefix, mint the next fencing epoch and take
    the write load; the old primary is then resurrected believing it
    still rules, and its first write must come back FencedWrite with the
    new primary's endpoint — zero writes accepted under the stale epoch,
    zero split-brain. The witness watch stream must stay gapless and
    duplicate-free across the failover (shared rv sequence + FailoverWatch
    since=last_rv resume), and every pod binds exactly once."""

    nodes: int
    pods: int
    seed: int
    replicas: int
    bound: int
    double_binds: int
    promotions: int              # epoch mints past the bootstrap election
    promotion_p99_ms: float      # primary-kill to standby-serving
    epoch: int                   # ruling epoch at drill end
    fenced_rejections: int       # writes the fencing guard turned away
    fenced_leaks: int            # writes ACCEPTED under a stale epoch (0!)
    stale_resurrect_fenced: bool  # the resurrected primary was fenced
    records_streamed: int
    snapshots_sent: int
    snapshots_discarded: int
    watch_events: int
    watch_gaps: int
    watch_dupes: int
    watch_resumes: int
    converged: bool
    racy_writes: int = 0
    loop_stalls: int = 0
    max_stall_ms: float = 0.0
    replica_faults: list = field(default_factory=list)

    @property
    def gate(self) -> bool:
        """The drill's whole contract in one bool (the bench's gate)."""
        return (self.converged and self.double_binds == 0
                and self.fenced_leaks == 0 and self.stale_resurrect_fenced
                and self.promotions >= 1
                and self.watch_gaps == 0 and self.watch_dupes == 0
                and self.racy_writes == 0 and self.loop_stalls == 0)

    def __str__(self) -> str:
        return (f"store-ha R={self.replicas} N={self.nodes} P={self.pods}: "
                f"{self.bound}/{self.pods} bound, "
                f"{self.promotions} promotions p99 "
                f"{self.promotion_p99_ms:.1f}ms epoch {self.epoch}, "
                f"{self.fenced_rejections} fenced "
                f"{self.fenced_leaks} leaks, "
                f"streamed {self.records_streamed} records "
                f"{self.snapshots_sent} snaps, watch "
                f"{self.watch_events} events {self.watch_gaps} gaps "
                f"{self.watch_dupes} dupes")


def run_store_ha(n_nodes: int = 8, n_pods: int = 48, seed: int = 2031,
                 replicas: int = 3,
                 race_detect: bool = True) -> StoreHAResult:
    """Blocking entry point for the store-HA (fenced failover) drill.

    Topology: a StoreReplicaSet of `replicas` replicated stores over one
    coordination quorum wrapped in a seeded FaultPlane (plus RaceDetector
    + loop-stall watchdog when `race_detect` — elector renew/CAS traffic
    ticks the plane continuously, so the op-indexed action schedule fires
    at deterministic points of the lease protocol). The scheduler, a pod
    creator and a resourceVersion-recording witness drive the data plane
    over TCP through primary-chasing RemoteStores. At the 1/3 milestone
    the ruling primary store is KILLED (state and beliefs frozen); at 2/3
    it is resurrected still believing it rules, and a client pinned to it
    proves the fence: FencedWrite carrying the new epoch + endpoint, no
    state mutated, and the deposed primary demotes and rejoins as a
    standby."""
    from kubernetes_tpu.api.objects import Node
    from kubernetes_tpu.apiserver.auth import TokenAuthenticator, UserInfo
    from kubernetes_tpu.apiserver.http import RemoteStore
    from kubernetes_tpu.apiserver.store import (
        AlreadyExists,
        FencedWrite,
        NotFound,
        TooManyRequests,
    )
    from kubernetes_tpu.testing.faults import FaultPlane
    from kubernetes_tpu.testing.races import LoopStallWatchdog, RaceDetector
    from kubernetes_tpu.testing.replicas import StoreReplicaSet

    coord_inner = ObjectStore()
    plane = FaultPlane(coord_inner, seed=seed)
    coord = RaceDetector(plane) if race_detect else plane
    auth = TokenAuthenticator({
        "sched-token": UserInfo("system:kube-scheduler",
                                ("system:authenticated",))})

    freeze_drill_heap()

    sg = StoreReplicaSet(
        coord, n=replicas,
        watch_window=max(1 << 16, 8 * (n_pods + n_nodes)),
        lease_duration=0.6, renew_deadline=0.45, retry_period=0.05,
        server_kwargs={"authenticator": auth}).start()
    for i, control in enumerate(sg.controls()):
        plane.attach_store_replica(i, control)
    watchdog_box: dict = {}

    async def drive() -> StoreHAResult:
        caps = Capacities(num_nodes=1 << max(6, (n_nodes - 1).bit_length()),
                          batch_pods=min(64, max(16, n_pods)))
        sched_client = sg.client(token="sched-token")
        creator = sg.client(token="sched-token")
        watcher_client = sg.client(token="sched-token")
        cap = {"cpu": "16", "memory": "32Gi", "pods": "110"}

        def create_with_retry(obj, deadline_s: float = 30.0) -> None:
            deadline = time.monotonic() + deadline_s
            while True:
                try:
                    creator.create(obj)
                    return
                except AlreadyExists:
                    return  # failover replay: exactly-once held
                except TooManyRequests as e:
                    # thread context (asyncio.to_thread), never the loop
                    time.sleep(max(0.05, getattr(e, "retry_after", 0.0)))  # ktpu: allow[blocking-in-async]
                except ConnectionError:
                    # promotion blackout: NO primary rules for a lease
                    # interval — unlike the stateless drill there is no
                    # other replica that can take the write, so ride it
                    # out (FencedWrite chases internally; what surfaces
                    # here is the every-endpoint-refused window)
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(0.1)  # ktpu: allow[blocking-in-async]

        for i in range(n_nodes):
            await asyncio.to_thread(create_with_retry, Node.from_dict({
                "metadata": {"name": f"sha-{i}",
                             "labels": {"kubernetes.io/hostname":
                                        f"sha-{i}"}},
                "status": {"allocatable": dict(cap),
                           "capacity": dict(cap)}}))

        sched = Scheduler(sched_client, caps=caps)
        loop = asyncio.get_running_loop()
        driver = loop.create_task(sched.run())

        # the coherence witness: one logical Pod watch across the whole
        # group, recording (type, rv, key, bound?) for the gapless gate
        # AND the exactly-once-bind gate (a split-brained double bind
        # would surface as two bound-MODIFIEDs for one key)
        observed: list[tuple[str, int, str, bool]] = []
        watcher = watcher_client.watch_resilient("Pod", since=0)
        watch_stop = asyncio.Event()

        async def observe() -> None:
            while not watch_stop.is_set():
                try:
                    ev = await watcher.next(timeout=0.5)
                except ConnectionError:
                    return  # every endpoint stayed dead past the deadline
                if ev is not None:
                    key = (f"{ev.obj.metadata.namespace or 'default'}/"
                           f"{ev.obj.metadata.name}")
                    observed.append(
                        (ev.type, ev.resource_version, key,
                         bool(ev.obj.spec.node_name)))

        observer = loop.create_task(observe())

        async def wait_bound(expect: int, timeout_s: float) -> bool:
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                try:
                    pods = await asyncio.to_thread(creator.list, "Pod")
                except ConnectionError:
                    await asyncio.sleep(0.2)
                    continue
                if sum(1 for p in pods if p.spec.node_name) >= expect:
                    return True
                await asyncio.sleep(0.1)
            return False

        async def wait_fault(count: int) -> None:
            deadline = time.monotonic() + 30
            while len(plane.stats.replica_faults) < count \
                    and time.monotonic() < deadline:
                await asyncio.sleep(0.01)

        # warm the solver's jit variants BEFORE arming the stall watchdog:
        # first-call XLA compile can hold the GIL past the 100ms stall
        # threshold, which would charge a one-time compile cost against
        # the failover drill's loop-health contract
        n_warm = 2
        for pod in make_pods(n_warm, cpu="100m", memory="64Mi",
                             name_prefix="warm"):
            await asyncio.to_thread(create_with_retry, pod)
        await wait_bound(n_warm, 120)
        if race_detect:
            # the store-group loop legitimately fsyncs WAL compactions and
            # shares the GIL with solver jit on the driver loop, so give it
            # headroom over the 100ms default; real blocking bugs in the
            # replication path (sync reads, time.sleep) stall far longer
            sg._call(lambda: watchdog_box.update(
                dog=LoopStallWatchdog(threshold_s=0.25).start()))

        victim = sg.primary_index()
        kill_at = max(1, n_pods // 3)
        resurrect_at = max(kill_at + 1, (2 * n_pods) // 3)
        stale_fenced = False
        stale_fence_epoch = 0
        proof = make_pods(1, cpu="100m", memory="64Mi",
                          name_prefix="stale-proof")[0]
        faults_seen = 0
        for i, pod in enumerate(make_pods(n_pods, cpu="100m",
                                          memory="64Mi",
                                          name_prefix="sha")):
            if i == kill_at:
                # op-indexed on the COORDINATION plane: the elector's next
                # renew/CAS pulls the trigger, same point every replay
                plane.schedule(
                    plane.stats.ops + 1,
                    lambda p, v=victim: p.kill_store_replica(v),
                    f"kill-store-primary-{victim}")
                faults_seen += 1
                await asyncio.to_thread(create_with_retry, pod)
                await wait_fault(faults_seen)
                # a standby must promote before writes flow again;
                # create_with_retry above already rode the blackout
            elif i == resurrect_at:
                plane.schedule(
                    plane.stats.ops + 1,
                    lambda p, v=victim: p.resurrect_store_replica(v),
                    f"resurrect-store-{victim}")
                faults_seen += 1
                await asyncio.to_thread(create_with_retry, pod)
                await wait_fault(faults_seen)
                # the resurrectee still believes it is primary at the old
                # epoch: a client pinned to it must get FencedWrite, and
                # its state must stay untouched (verified below via the
                # everywhere-absent proof pod)
                stale = sg.replicas[victim]
                deadline = time.monotonic() + 10
                while stale.killed and time.monotonic() < deadline:
                    await asyncio.sleep(0.01)
                pinned = RemoteStore(stale.host, stale.api_port,
                                     token="sched-token")

                def poke():
                    try:
                        pinned.create(proof)
                        return "accepted"
                    except FencedWrite as e:
                        return ("fenced", e.epoch)
                    except ConnectionError:
                        return "conn"

                outcome = await asyncio.to_thread(poke)
                if isinstance(outcome, tuple):
                    stale_fenced = True
                    stale_fence_epoch = outcome[1]
            else:
                await asyncio.to_thread(create_with_retry, pod)
        conv = await wait_bound(n_warm + n_pods, 240)

        # fence the coherence check at the ruling primary's revision,
        # then let the witness catch up before comparing histories
        p_idx = sg.wait_for_primary(10)
        primary = sg.replicas[p_idx].store
        fence_rv = primary.resource_version
        deadline = time.monotonic() + 30
        while (watcher.last_rv or 0) < fence_rv \
                and time.monotonic() < deadline \
                and not observer.done():
            await asyncio.sleep(0.05)
        watch_stop.set()
        watcher.stop()
        observer.cancel()
        driver.cancel()
        sched.stop()

        # the fenced-leak proof: the stale write must exist NOWHERE — not
        # on the ruling primary, not on the resurrectee's own copy
        leaks = 0
        for replica in sg.replicas:
            try:
                replica.store.get("Pod", proof.metadata.name)
                leaks += 1
            except NotFound:
                pass
        if not stale_fenced:
            leaks += 1  # the poke was swallowed or accepted: count it

        expected = [e.resource_version for e in primary._history
                    if e.kind == "Pod" and e.resource_version <= fence_rv]
        got = [rv for _, rv, _, _ in observed if rv <= fence_rv]
        gaps = len(set(expected) - set(got))
        dupes = len(got) - len(set(got))
        # exactly-once binds, judged from the AUTHORITATIVE timeline (the
        # ruling primary's history): a split-brained double bind would
        # show as a second unbound->bound transition for one key, or a
        # bound pod silently moving nodes. Post-bind MODIFIEDs that keep
        # the assignment (trace-annotation stamps on sampled batches,
        # condition writes) are not binds. The witness is the wrong judge
        # for this — it may legitimately have observed a bind the dead
        # primary acked but never replicated (the async-replication ack
        # window); that bind is not in the surviving timeline and the
        # scheduler's retry is the recovery, not a bug.
        bind_counts: dict[str, int] = {}
        last_node: dict[str, str] = {}
        for e in primary._history:
            if e.kind != "Pod" or e.resource_version > fence_rv:
                continue
            key = (f"{e.obj.metadata.namespace or 'default'}/"
                   f"{e.obj.metadata.name}")
            if e.type == "DELETED":
                last_node.pop(key, None)
                continue
            node = e.obj.spec.node_name or ""
            prev = last_node.get(key, "")
            if node and (not prev or node != prev):
                bind_counts[key] = bind_counts.get(key, 0) + 1
            last_node[key] = node
        double = sum(1 for v in bind_counts.values() if v > 1)
        bound_final = sum(
            1 for p in primary.list("Pod")
            if p.spec.node_name and p.metadata.name.startswith("sha-"))
        return StoreHAResult(
            nodes=n_nodes, pods=n_pods, seed=seed, replicas=replicas,
            bound=bound_final, double_binds=double,
            promotions=sum(1 for _, ep in sg.promotions if ep >= 2),
            promotion_p99_ms=_p99_ms(
                [s / 1e3 for s in sg.promotion_samples_ms]),
            epoch=max((r.store.epoch for r in sg.replicas), default=0),
            fenced_rejections=sum(
                r.store.fenced_writes for r in sg.replicas),
            fenced_leaks=leaks,
            stale_resurrect_fenced=(stale_fenced
                                    and stale_fence_epoch >= 2),
            records_streamed=sum(r.records_sent for r in sg.replicas),
            snapshots_sent=sum(r.snapshots_sent for r in sg.replicas),
            snapshots_discarded=sum(
                r.snapshots_discarded for r in sg.replicas),
            watch_events=len(got), watch_gaps=gaps, watch_dupes=dupes,
            watch_resumes=watcher.resumes,
            converged=(conv and bound_final >= n_pods),
            racy_writes=len(coord.racy_writes) if race_detect else 0,
            replica_faults=list(plane.stats.replica_faults))

    try:
        result = asyncio.run(drive())
    finally:
        stalls = sg._call(watchdog_box["dog"].stop) \
            if watchdog_box else []
        sg.stop()
        thaw_drill_heap()
    result.loop_stalls = len(stalls)
    result.max_stall_ms = 1e3 * max(stalls, default=0.0)
    return result


@dataclass
class FanoutResult:
    """Watch-cache fan-out drill: N subscribers, M store events, and the
    proof that the store did O(M) work — `store_fanout_puts` counts one
    queue put per event (the cache's single subscription), not N*M."""

    watchers: int
    events: int
    store_fanout_puts: int
    deliveries: int
    events_per_sec: float
    evicted: int

    def __str__(self) -> str:
        return (f"fanout W={self.watchers} E={self.events}: store did "
                f"{self.store_fanout_puts} puts, cache delivered "
                f"{self.deliveries} ({self.events_per_sec:.0f}/s, "
                f"{self.evicted} evicted)")


async def _run_watch_fanout(watchers: int, events: int) -> FanoutResult:
    from kubernetes_tpu.api.objects import Node
    from kubernetes_tpu.apiserver.watchcache import WatchCache

    store = ObjectStore(watch_window=max(1 << 14, 4 * events))
    cache = WatchCache(store).start()
    subs = [cache.watch("Node") for _ in range(watchers)]
    base = store.fanout_puts
    t0 = time.perf_counter()
    store.create(Node.from_dict({"metadata": {"name": "fan"}}))
    for i in range(events - 1):
        store.guaranteed_update(
            "Node", "fan", "default",
            lambda n, i=i: n.metadata.labels.update({"tick": str(i)}))

    async def drain(sub) -> int:
        got = 0
        while got < events:
            ev = await sub.next(timeout=10.0)
            if ev is None:
                break
            got += 1
        return got

    counts = await asyncio.gather(*(drain(s) for s in subs))
    dt = max(time.perf_counter() - t0, 1e-9)
    cache.stop()
    return FanoutResult(
        watchers=watchers, events=events,
        store_fanout_puts=store.fanout_puts - base,
        deliveries=sum(counts),
        events_per_sec=sum(counts) / dt,
        evicted=cache.evictions)


def run_watch_fanout(watchers: int = 10_000,
                     events: int = 100) -> FanoutResult:
    """Blocking entry point for the watch-cache fan-out drill."""
    return asyncio.run(_run_watch_fanout(watchers, events))


@dataclass
class FanoutXLResult:
    """Sharded fan-out scale drill (bench[fanout-xl]): 100k sink watchers
    on shard threads vs the single-loop fallback, in one process. The
    contracts proven here: deliveries/s ≥ gate× the single-loop baseline,
    store puts exactly O(events), zero slow-consumer evictions at nominal
    rate, encode-once (frames_encoded == events while frames_delivered ==
    deliveries), a witness stream gapless/dup-free against store history
    at a fence rv, and scheduler e2e p99 unperturbed while the flood
    runs."""

    watchers: int
    events: int               # burst + nominal store events
    shards: int
    store_fanout_puts: int
    deliveries: int           # sharded sink deliveries (burst + nominal)
    events_per_sec: float     # burst-phase sink deliveries/s
    baseline_watchers: int
    baseline_deliveries: int
    baseline_events_per_sec: float  # single-loop (shards=0) queue mode
    speedup: float
    evicted: int
    frames_encoded: int       # registry delta over the sharded phases
    frames_delivered: int
    encode_ratio: float       # delivered / encoded
    witness_events: int
    witness_gaps: int
    witness_dupes: int
    sched_p99_base_ms: float      # batch e2e p99, scheduler alone
    sched_p99_flood_ms: float     # same workload under the nominal flood
    sched_pods_per_sec_base: float
    sched_pods_per_sec_flood: float

    def __str__(self) -> str:
        return (f"fanout-xl W={self.watchers} E={self.events} "
                f"S={self.shards}: {self.deliveries} deliveries "
                f"({self.events_per_sec:.0f}/s, {self.speedup:.1f}x the "
                f"single-loop {self.baseline_events_per_sec:.0f}/s), "
                f"store {self.store_fanout_puts} puts, "
                f"{self.evicted} evicted, encode ratio "
                f"{self.encode_ratio:.0f}:1, witness "
                f"{self.witness_events} events {self.witness_gaps} gaps "
                f"{self.witness_dupes} dupes, sched p99 "
                f"{self.sched_p99_base_ms:.1f}->"
                f"{self.sched_p99_flood_ms:.1f}ms")


async def _sched_round(n_nodes: int, n_pods: int) -> tuple[float, float]:
    """One scheduler workload round on its own store: returns
    (pods_per_sec, batch-e2e p99 ms). The fanout-xl perturbation probe —
    same process, loop and GIL as the flood, separate store."""
    store = ObjectStore()
    for node in make_nodes(n_nodes):
        store.create(node)
    caps = Capacities(num_nodes=1 << max(4, (n_nodes - 1).bit_length()),
                      batch_pods=min(64, max(8, n_pods)))
    sched = Scheduler(store, caps=caps)
    await sched.start()
    for pod in make_pods(n_pods, cpu="100m", memory="64Mi",
                         name_prefix="xl"):
        store.create(pod)
    await asyncio.sleep(0)
    samples: list[float] = []
    done = 0
    idle = 0
    t0 = time.perf_counter()
    while done < n_pods and idle < 5:
        tb = time.perf_counter()
        got = await sched.schedule_pending(wait=0.2)
        if got:
            samples.append(time.perf_counter() - tb)
            done += got
            idle = 0
        else:
            idle = 0 if sched.inflight_batches > 0 else idle + 1
    dt = max(time.perf_counter() - t0, 1e-9)
    sched.stop()
    return done / dt, _p99_ms(samples)


async def _run_fanout_xl(watchers: int, events: int, nominal_events: int,
                         baseline_watchers: int, sched_nodes: int,
                         sched_pods: int) -> FanoutXLResult:
    from array import array

    from kubernetes_tpu.api.objects import Node
    from kubernetes_tpu.apiserver import watchcache as wc

    mx = wc._metrics()

    def tick(store, label: str, i: int) -> None:
        store.guaranteed_update(
            "Node", "fan", "default",
            lambda n, i=i: n.metadata.labels.update({label: str(i)}))

    # ---- phase 0: scheduler alone — the perturbation baseline ----
    pps_base, p99_base = await _sched_round(sched_nodes, sched_pods)

    # ---- phase 1: single-loop baseline (the KTPU_FANOUT_SHARDS=0
    # fallback, queue mode — exactly the pre-shard bench[fanout] shape) ----
    base_store = ObjectStore(watch_window=max(1 << 12, 4 * events))
    base_cache = wc.WatchCache(base_store, shards=0).start()
    base_subs = [base_cache.watch("Node")
                 for _ in range(baseline_watchers)]

    async def drain(sub) -> int:
        got = 0
        while got < events:
            ev = await sub.next(timeout=10.0)
            if ev is None:
                break
            got += 1
        return got

    tb0 = time.perf_counter()
    base_store.create(Node.from_dict({"metadata": {"name": "fan"}}))
    for i in range(events - 1):
        tick(base_store, "tick", i)
    base_counts = await asyncio.gather(*(drain(s) for s in base_subs))
    base_dt = max(time.perf_counter() - tb0, 1e-9)
    base_deliveries = sum(base_counts)
    base_rate = base_deliveries / base_dt
    await base_cache.aclose()

    # ---- phase 2: sharded burst at full scale ----
    total_events = events + nominal_events
    store = ObjectStore(watch_window=max(1 << 12, 4 * total_events + 64))
    cache = wc.WatchCache(store).start()
    if not cache.sharded:
        raise RuntimeError(
            "bench[fanout-xl] needs KTPU_FANOUT_SHARDS >= 1")
    counts = array("q", [0] * watchers)
    handles = []
    for i in range(watchers):
        def sink(frame, _i=i, _counts=counts):
            _counts[_i] += 1
            frame.json_bytes()  # the wire bytes all sinks share
        handles.append(cache.watch_sink("Node", sink=sink))

    rv0 = store.resource_version
    witness = cache.watch(None)  # coherence witness, queue mode
    puts0 = store.fanout_puts
    enc0 = mx[1].labels().value
    dlv0 = mx[2].labels().value
    observed: list[tuple[str, int]] = []

    async def observe() -> None:
        while True:
            ev = await witness.next(timeout=2.0)
            if ev is None:
                if witness._stopped:
                    return
                continue
            observed.append((ev.type, ev.resource_version))

    observer = asyncio.get_running_loop().create_task(observe())

    async def settle(expect: int, timeout_s: float) -> None:
        deadline = time.monotonic() + timeout_s
        while sum(counts) < expect and time.monotonic() < deadline:
            await asyncio.sleep(0.005)

    t0 = time.perf_counter()
    store.create(Node.from_dict({"metadata": {"name": "fan"}}))
    for i in range(events - 1):
        tick(store, "tick", i)
    await settle(watchers * events, 120.0)
    dt = max(time.perf_counter() - t0, 1e-9)
    burst_deliveries = sum(counts)
    rate = burst_deliveries / dt

    # ---- phase 3: nominal-rate flood + concurrent scheduler round ----
    async def paced() -> None:
        for i in range(nominal_events):
            tick(store, "nom", i)
            await asyncio.sleep(0.05)

    (pps_flood, p99_flood), _ = await asyncio.gather(
        _sched_round(sched_nodes, sched_pods), paced())
    await settle(watchers * total_events, 60.0)

    # ---- fence + witness coherence (the bench[ha] diff shape) ----
    fence = store.resource_version
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if observed and observed[-1][1] >= fence:
            break
        await asyncio.sleep(0.02)
    witness.stop()
    observer.cancel()
    try:
        await observer
    except asyncio.CancelledError:
        pass

    expected = [e.resource_version for e in store._history
                if rv0 < e.resource_version <= fence]
    got = [rv for _, rv in observed if rv <= fence]
    gaps = len(set(expected) - set(got))
    dupes = len(got) - len(set(got))

    deliveries = sum(counts)
    encoded = int(mx[1].labels().value - enc0)
    delivered = int(mx[2].labels().value - dlv0)
    puts = store.fanout_puts - puts0
    shards_n = cache.shards_n
    evicted = cache.evictions
    for h in handles:
        h.stop()
    await cache.aclose()
    return FanoutXLResult(
        watchers=watchers, events=total_events, shards=shards_n,
        store_fanout_puts=puts, deliveries=deliveries,
        events_per_sec=rate,
        baseline_watchers=baseline_watchers,
        baseline_deliveries=base_deliveries,
        baseline_events_per_sec=base_rate,
        speedup=rate / max(base_rate, 1e-9),
        evicted=evicted,
        frames_encoded=encoded, frames_delivered=delivered,
        encode_ratio=delivered / max(encoded, 1),
        witness_events=len(got), witness_gaps=gaps, witness_dupes=dupes,
        sched_p99_base_ms=p99_base, sched_p99_flood_ms=p99_flood,
        sched_pods_per_sec_base=pps_base,
        sched_pods_per_sec_flood=pps_flood)


def run_fanout_xl(watchers: int = 100_000, events: int = 12,
                  nominal_events: int = 8,
                  baseline_watchers: int = 10_000,
                  sched_nodes: int = 32,
                  sched_pods: int = 128) -> FanoutXLResult:
    """Blocking entry point for the sharded fan-out scale drill."""
    return asyncio.run(_run_fanout_xl(watchers, events, nominal_events,
                                      baseline_watchers, sched_nodes,
                                      sched_pods))


@dataclass
class MonitorBenchResult:
    """Monitoring-plane overhead drill: a Monitor scrapes a fleet of real
    ObsServers (each over its own churning registry) at a fixed interval
    while instant queries run against the TSDB. The contract: zero scrape
    failures, and the TSDB stays bounded — the series count stops growing
    once the fleet's label space is discovered (no per-scrape series
    leak)."""

    targets: int
    seconds: float
    interval: float
    scrapes: int
    scrape_failures: int
    samples_ingested: int
    samples_per_sec: float
    scrape_p99_ms: float
    query_p99_ms: float
    tsdb_series: int
    tsdb_samples: int
    series_stable: bool

    def __str__(self) -> str:
        return (f"monitor T={self.targets} @{self.interval}s x"
                f"{self.seconds:.0f}s: {self.scrapes} scrapes "
                f"({self.scrape_failures} failed), "
                f"{self.samples_per_sec:.0f} samples/s, scrape p99 "
                f"{self.scrape_p99_ms:.1f}ms, query p99 "
                f"{self.query_p99_ms:.2f}ms, {self.tsdb_series} series "
                f"({'stable' if self.series_stable else 'GROWING'})")


async def _run_monitor_bench(n_targets: int, seconds: float,
                             interval: float,
                             retention_samples: int = 120,
                             seed: int = 7) -> MonitorBenchResult:
    import random as _random

    from kubernetes_tpu.obs.http import ObsServer
    from kubernetes_tpu.obs.metrics import Registry
    from kubernetes_tpu.obs.monitor import Monitor

    rng = _random.Random(seed)
    servers: list[ObsServer] = []
    churners: list[tuple] = []
    for i in range(n_targets):
        reg = Registry()
        reqs = reg.counter("bench_requests_total", "synthetic traffic",
                           labels=("code",))
        lat = reg.histogram("bench_request_duration_seconds",
                            "synthetic latency")
        srv = ObsServer(registry=reg)
        await srv.start()
        servers.append(srv)
        churners.append((reqs, lat))
    monitor = Monitor(store=None, interval=interval,
                      retention_samples=retention_samples,
                      include_builtin_rules=False)
    for i, srv in enumerate(servers):
        monitor.add_static_target(f"bench-{i}", srv.url)

    stop = asyncio.Event()

    async def churn() -> None:
        # keep every target's exposition moving between scrapes so counter
        # deltas and histogram fills are real, not a static page re-read.
        # Every code label ticks every round: the fleet's full label space
        # exists from the first scrape, so the stability gate below is a
        # real leak detector, not label-discovery noise
        while not stop.is_set():
            for reqs, lat in churners:
                for code in ("200", "429", "500"):
                    reqs.labels(code).inc(rng.randrange(1, 20))
                lat.observe(rng.random() / 10)
            await asyncio.sleep(interval / 4)

    churn_task = asyncio.get_running_loop().create_task(churn())
    scrape_ms: list[float] = []
    query_ms: list[float] = []
    series_mid = 0
    t_end = time.perf_counter() + seconds
    n_scrapes = 0
    while time.perf_counter() < t_end:
        t0 = time.perf_counter()
        await monitor.scrape_once()
        scrape_ms.append(1e3 * (time.perf_counter() - t0))
        n_scrapes += 1
        for expr in (f'rate(bench_requests_total[{4 * interval}s])',
                     'histogram_quantile(0.99, '
                     f'bench_request_duration_seconds_bucket'
                     f'[{4 * interval}s])',
                     'sum by (code) (bench_requests_total)'):
            q0 = time.perf_counter()
            monitor.query(expr)
            query_ms.append(1e3 * (time.perf_counter() - q0))
        if n_scrapes == 2:
            # by the second scrape every target's full label space has
            # been seen: growth beyond this point is a series leak
            series_mid = monitor.tsdb.series_count()
        await asyncio.sleep(
            max(0.0, interval - (time.perf_counter() - t0)))
    stop.set()
    churn_task.cancel()
    for srv in servers:
        await srv.stop()

    failures = sum(
        child.value
        for _v, child in monitor._mx_failures.children())
    ingested = monitor._mx_samples.labels().value
    return MonitorBenchResult(
        targets=n_targets, seconds=seconds, interval=interval,
        scrapes=n_scrapes, scrape_failures=int(failures),
        samples_ingested=int(ingested),
        samples_per_sec=ingested / max(seconds, 1e-9),
        scrape_p99_ms=sorted(scrape_ms)[int(0.99 * (len(scrape_ms) - 1))]
        if scrape_ms else 0.0,
        query_p99_ms=sorted(query_ms)[int(0.99 * (len(query_ms) - 1))]
        if query_ms else 0.0,
        tsdb_series=monitor.tsdb.series_count(),
        tsdb_samples=monitor.tsdb.sample_count(),
        series_stable=(series_mid > 0
                       and monitor.tsdb.series_count() <= series_mid))


def run_monitor_bench(n_targets: int = 5, seconds: float = 10.0,
                      interval: float = 1.0,
                      retention_samples: int = 120,
                      seed: int = 7) -> MonitorBenchResult:
    """Blocking entry point for the monitoring-plane overhead drill."""
    return asyncio.run(_run_monitor_bench(n_targets, seconds, interval,
                                          retention_samples, seed=seed))


@dataclass
class MultiProcResult:
    """Multi-process control-plane drill (bench[multiproc]): a store-owner
    process feeding N worker processes over the shared-memory event ring,
    A/B'd against the in-process sharded topology at the same shape. The
    contracts: every worker's sinks see every event as the owner's
    encode-once wire bytes (owner frames_encoded == ring appends == store
    resourceVersion; worker re-encodes == 0), a SIGKILL'd worker is
    reaped + respawned without replaying delivered frames or double-
    binding a pod, the cross-process witness stream is gapless/dup-free
    against the owner's authoritative history at a fence rv, and the
    monitoring plane discovers every worker's per-process /metrics and
    scrapes the fleet with zero failures."""

    workers: int
    shards: int
    watchers: int             # total bench sinks across the fleet
    events: int               # Node burst events
    inproc_deliveries: int
    inproc_events_per_sec: float
    deliveries: int           # cross-process aggregate sink deliveries
    events_per_sec: float
    speedup: float            # cross-process rate / in-process rate
    ring_appends: int
    store_events: int         # owner store resourceVersion delta
    owner_frames_encoded: int
    worker_frames_encoded: int  # sum across workers — must stay 0
    pods: int
    bound: int
    double_binds: int
    bind_conflicts: int       # replayed binds answered Conflict
    kills: int
    respawns: int
    reaped: list = field(default_factory=list)
    failovers: int = 0
    witness_events: int = 0
    witness_gaps: int = 0
    witness_dupes: int = 0
    monitor_targets: int = 0
    scrapes: int = 0
    scrape_failures: int = 0

    @property
    def gate(self) -> bool:
        """Correctness contract in one bool (speedup gates separately —
        it is a perf target, not a correctness invariant)."""
        return (self.ring_appends == self.store_events
                and self.owner_frames_encoded == self.ring_appends
                and self.worker_frames_encoded == 0
                and self.deliveries >= self.watchers * self.events
                and self.bound == self.pods and self.double_binds == 0
                and self.witness_gaps == 0 and self.witness_dupes == 0
                and self.respawns >= 1 and 0 in self.reaped
                and self.monitor_targets >= self.workers
                and self.scrape_failures == 0)

    def __str__(self) -> str:
        return (f"multiproc W={self.workers}x{self.watchers // max(self.workers, 1)} "
                f"E={self.events} S={self.shards}: {self.deliveries} "
                f"deliveries ({self.events_per_sec:.0f}/s, "
                f"{self.speedup:.2f}x in-process "
                f"{self.inproc_events_per_sec:.0f}/s), ring "
                f"{self.ring_appends} appends / {self.store_events} events, "
                f"worker re-encodes {self.worker_frames_encoded}, "
                f"{self.bound}/{self.pods} bound "
                f"({self.double_binds} double, {self.bind_conflicts} "
                f"replay-conflicts), witness {self.witness_events} events "
                f"{self.witness_gaps} gaps {self.witness_dupes} dupes, "
                f"monitor {self.monitor_targets} targets "
                f"{self.scrape_failures} failed scrapes")


def _worker_metric(host: str, port: int, name: str) -> float:
    """Blocking: read one unlabeled counter from a worker's /metrics."""
    import urllib.request

    with urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=5.0) as resp:
        text = resp.read().decode("utf-8", "replace")
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and not line.startswith("#"):
            rest = line[len(name):]
            if rest[:1] not in ("", " ", "{", "\t"):
                continue  # a longer family sharing the prefix
            total += float(rest.rsplit(None, 1)[-1])
    return total


async def _inproc_fanout_round(watchers: int, events: int,
                               shards: int) -> tuple[int, float]:
    """The A side: today's single-process topology (KTPU_WORKER_PROCS=0)
    at the drill's shape — sharded fan-out, all sinks in one process."""
    from array import array

    from kubernetes_tpu.api.objects import Node
    from kubernetes_tpu.apiserver import watchcache as wc

    store = ObjectStore(watch_window=max(1 << 12, 4 * events))
    cache = wc.WatchCache(store, shards=shards).start()
    counts = array("q", [0] * watchers)
    handles = []
    for i in range(watchers):
        def sink(frame, _i=i, _counts=counts):
            _counts[_i] += 1
            frame.json_bytes()
        handles.append(cache.watch_sink("Node", sink=sink))
    t0 = time.perf_counter()
    store.create(Node.from_dict({"metadata": {"name": "fan"}}))
    for i in range(events - 1):
        store.guaranteed_update(
            "Node", "fan", "default",
            lambda n, i=i: n.metadata.labels.update({"tick": str(i)}))
    deadline = time.monotonic() + 60
    expect = watchers * events
    while sum(counts) < expect and time.monotonic() < deadline:
        await asyncio.sleep(0.005)
    dt = max(time.perf_counter() - t0, 1e-9)
    deliveries = sum(counts)
    for h in handles:
        h.stop()
    await cache.aclose()
    return deliveries, deliveries / dt


def run_multiproc(workers: int = 2, per_worker_watchers: int = 100,
                  events: int = 20, n_pods: int = 24,
                  shards: int | None = None,
                  ring_capacity: int = 1 << 20) -> MultiProcResult:
    """Blocking entry point for the multi-process control-plane drill.

    Five phases: (1) in-process sharded baseline at the same total-sink
    shape; (2) cross-process burst — Node events appended once by the
    owner, fanned out by every worker's shard threads, aggregate delivery
    rate read from each worker's own /metrics; (3) rolling worker-kill
    bind drill — SIGKILL mid-binds, owner reaps the ring slot, the
    respawn resumes without replaying delivered frames, replayed binds
    answer Conflict (exactly-once); (4) cross-process witness diff
    against the owner's authoritative history at a fence rv; (5) the
    monitoring plane discovers every worker's /metrics through the
    advertised Endpoints and scrapes the fleet."""
    from kubernetes_tpu.api.objects import Node
    from kubernetes_tpu.apiserver.store import (
        AlreadyExists,
        Binding,
        Conflict,
        TooManyRequests,
    )
    from kubernetes_tpu.obs.monitor import Monitor
    from kubernetes_tpu.testing.replicas import MultiProcCluster

    total_watchers = workers * per_worker_watchers
    cap = {"cpu": "16", "memory": "32Gi", "pods": "110"}
    n_bind_nodes = 4

    cluster = MultiProcCluster(
        n=workers, shards=shards, ring_capacity=ring_capacity,
        bench_watchers=per_worker_watchers, bench_kind="Node",
        advertise=True).start()

    async def drive() -> MultiProcResult:
        # ---- phase 1: in-process baseline, same total shape ----
        shards_n = shards if shards is not None else 2
        inproc_deliveries, inproc_rate = await _inproc_fanout_round(
            total_watchers, events, shards_n)

        client = cluster.client()
        witness_client = cluster.client()
        ports = [p for _, p in cluster.endpoints]
        host = cluster.host

        def delivered_sum(alive_ports) -> float:
            return sum(_worker_metric(
                host, p, "watchcache_frames_delivered_total")
                for p in alive_ports)

        # the cross-process witness: a resilient Pod watch through the
        # worker fleet, recording every (type, rv) across the kill
        observed: list[tuple[str, int]] = []
        watcher = witness_client.watch_resilient("Pod", since=0)
        watch_stop = asyncio.Event()

        async def observe() -> None:
            while not watch_stop.is_set():
                try:
                    ev = await watcher.next(timeout=0.5)
                except ConnectionError:
                    return
                if ev is not None:
                    observed.append((ev.type, ev.resource_version))

        observer = asyncio.get_running_loop().create_task(observe())

        # bind targets, created before the measured burst so their fan-out
        # doesn't pollute the delivery ledger
        for i in range(n_bind_nodes):
            await asyncio.to_thread(client.create, Node.from_dict({
                "metadata": {"name": f"mp-{i}",
                             "labels": {"kubernetes.io/hostname": f"mp-{i}"}},
                "status": {"allocatable": dict(cap),
                           "capacity": dict(cap)}}))
        # quiesce: wait until the node-creation fan-out stops moving
        prev = -1.0
        while True:
            cur = await asyncio.to_thread(delivered_sum, ports)
            if cur == prev:
                break
            prev = cur
            await asyncio.sleep(0.05)
        base_delivered = prev

        # ---- phase 2: cross-process burst ----
        expect = total_watchers * events
        t0 = time.perf_counter()
        await asyncio.to_thread(
            client.create, Node.from_dict({"metadata": {"name": "fan"}}))
        for i in range(events - 1):
            await asyncio.to_thread(
                client.guaranteed_update, "Node", "fan", "default",
                lambda n, i=i: n.metadata.labels.update({"tick": str(i)}))
        deadline = time.monotonic() + 120
        delivered = 0.0
        while time.monotonic() < deadline:
            delivered = await asyncio.to_thread(delivered_sum, ports)
            if delivered - base_delivered >= expect:
                break
            await asyncio.sleep(0.005)
        dt = max(time.perf_counter() - t0, 1e-9)
        deliveries = int(delivered - base_delivered)
        rate = deliveries / dt
        worker_encoded = int(sum(await asyncio.gather(*(
            asyncio.to_thread(_worker_metric, host, p,
                              "watchcache_frames_encoded_total")
            for p in ports))))

        # ---- phase 3: rolling worker-kill bind drill ----
        def create_with_retry(pod) -> None:
            while True:
                try:
                    client.create(pod)
                    return
                except AlreadyExists:
                    return  # failover replay: exactly-once held
                except TooManyRequests as e:
                    time.sleep(max(0.05, getattr(e, "retry_after", 0.0)))  # ktpu: allow[blocking-in-async]

        acks: dict[str, int] = {}
        conflicts = 0

        def bind_with_retry(name: str, node: str) -> None:
            nonlocal conflicts
            for _ in range(64):
                try:
                    client.bind(Binding(pod_name=name, namespace="default",
                                        target_node=node))
                    acks[name] = acks.get(name, 0) + 1
                    return
                except Conflict:
                    # the first send landed before its worker died: the
                    # authoritative store already holds the bind, and the
                    # replay is refused — the exactly-once evidence
                    conflicts += 1
                    return
                except ConnectionError:
                    time.sleep(0.02)  # ktpu: allow[blocking-in-async]
            raise RuntimeError(f"bind of {name} never reached the owner")

        pods = list(make_pods(n_pods, cpu="100m", memory="64Mi",
                              name_prefix="mp"))
        kills = 0
        for i, pod in enumerate(pods):
            await asyncio.to_thread(create_with_retry, pod)
            if i == n_pods // 2:
                # SIGKILL mid-binds: no drain frame, no shm detach — the
                # owner's liveness sweep must reclaim the ring slot
                await asyncio.to_thread(cluster.kill_worker, 0)
                kills += 1
            await asyncio.to_thread(bind_with_retry, pod.metadata.name,
                                    f"mp-{i % n_bind_nodes}")
        reaped = await asyncio.to_thread(cluster.reap_dead)
        await asyncio.to_thread(cluster.respawn_worker, 0)
        bound = sum(
            1 for p in await asyncio.to_thread(client.list, "Pod")
            if p.spec.node_name)
        double = sum(1 for v in acks.values() if v > 1)

        # ---- phase 4: witness coherence at a fence rv ----
        fence = cluster.store.resource_version
        deadline = time.monotonic() + 30
        while (watcher.last_rv or 0) < fence \
                and time.monotonic() < deadline \
                and not observer.done():
            await asyncio.sleep(0.05)
        watch_stop.set()
        watcher.stop()
        observer.cancel()
        try:
            await observer
        except asyncio.CancelledError:
            pass
        expected = [e.resource_version for e in cluster.store._history
                    if e.kind == "Pod" and e.resource_version <= fence]
        got = [rv for _, rv in observed if rv <= fence]
        gaps = len(set(expected) - set(got))
        dupes = len(got) - len(set(got))

        # ---- phase 5: fleet scrape over discovered worker targets ----
        monitor = Monitor(store=cluster.client(), interval=0.5,
                          include_builtin_rules=False)
        targets = [t for t in monitor.targets() if t.job == "apiserver"]
        scrapes = 0
        for _ in range(3):
            await monitor.scrape_once()
            scrapes += 1
        failures = int(sum(
            child.value for _v, child in monitor._mx_failures.children()))

        owner = cluster.owner
        return MultiProcResult(
            workers=workers, shards=cluster.specs[0].shards or 0,
            watchers=total_watchers, events=events,
            inproc_deliveries=inproc_deliveries,
            inproc_events_per_sec=inproc_rate,
            deliveries=deliveries, events_per_sec=rate,
            speedup=rate / max(inproc_rate, 1e-9),
            ring_appends=owner.ring.appends,
            store_events=cluster.store.resource_version,
            owner_frames_encoded=owner.frames_encoded,
            worker_frames_encoded=worker_encoded,
            pods=n_pods, bound=bound, double_binds=double,
            bind_conflicts=conflicts, kills=kills,
            respawns=cluster.respawns, reaped=reaped,
            failovers=(client.failover_total
                       + witness_client.failover_total),
            witness_events=len(got), witness_gaps=gaps,
            witness_dupes=dupes,
            monitor_targets=len(targets), scrapes=scrapes,
            scrape_failures=failures)

    try:
        return asyncio.run(drive())
    finally:
        cluster.stop()


@dataclass
class SolverSvcResult:
    """Solver-as-a-service drill: M tenant control planes — one speaking
    the stock extender wire protocol with full node objects, the rest the
    native batch-solve endpoint — share ONE continuous-batching device
    program. Gates (all armed, even in --smoke): every pod binds exactly
    once per tenant under the RaceDetector, zero cross-tenant assignments,
    a noisy tenant's flood moves the stock-wire victim's p99 by at most
    5x, and the multi-tenant aggregate throughput at least matches a
    single tenant pushing the same total shape through the same service
    (the continuous-batching claim, measured)."""

    tenants: int
    nodes_per_tenant: int
    pods_per_tenant: int
    seed: int
    bound: int
    expected_bound: int
    double_binds: int
    isolation_violations: int     # service counter (refused row decodes)
    cross_tenant_assignments: int  # audit: assigned node not the tenant's
    # victim = the stock-extender-wire tenant; SERVER-side seat-to-response
    # latencies from its per-tenant sample ring, unloaded vs noisy flood
    p99_unloaded_ms: float
    p99_loaded_ms: float
    flood_requests: int
    flood_rejected: int
    solo_pods_per_sec: float
    agg_pods_per_sec: float
    steps: int
    occupancy_max: int
    converged: bool
    racy_writes: int = 0

    @property
    def p99_bounded(self) -> bool:
        """Same contract as the overload drill: loaded p99 within 5x
        unloaded, 100ms floor for scheduler-jitter noise at CI scale."""
        return self.p99_loaded_ms <= max(5 * self.p99_unloaded_ms, 100.0)

    @property
    def batching_wins(self) -> bool:
        return self.agg_pods_per_sec >= self.solo_pods_per_sec

    def __str__(self) -> str:
        return (f"solver-svc M={self.tenants} N={self.nodes_per_tenant}/t "
                f"P={self.pods_per_tenant}/t: {self.bound}/"
                f"{self.expected_bound} bound, victim p99 "
                f"{self.p99_unloaded_ms:.1f}ms -> {self.p99_loaded_ms:.1f}"
                f"ms under flood ({self.flood_rejected}/"
                f"{self.flood_requests} shed), "
                f"agg {self.agg_pods_per_sec:.0f} vs solo "
                f"{self.solo_pods_per_sec:.0f} pods/s, {self.steps} steps")


def _svc_post(base: str, path: str, payload: dict,
              timeout: float = 30.0) -> tuple[int, dict | list]:
    import json as _json
    import urllib.error
    import urllib.request

    req = urllib.request.Request(
        base + path, data=_json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, _json.loads(r.read() or b"{}")
    except urllib.error.HTTPError as e:
        body = e.read()
        try:
            return e.code, _json.loads(body or b"{}")
        except ValueError:
            return e.code, {}


def run_solver_svc(n_tenants: int = 4, nodes_per_tenant: int = 32,
                   pods_per_tenant: int = 96, seed: int = 2026,
                   req_pods: int = 8, batch_pods: int = 64,
                   window_ms: float = 2.0, seats: int = 2,
                   queue_wait_s: float = 0.02, flood_threads: int = 12,
                   race_detect: bool = True) -> SolverSvcResult:
    """Blocking entry point for the solver-as-a-service drill.

    Topology: ONE SolverService + SolverFrontend on this thread's event
    loop; tenant control planes are client threads over real TCP.
    tenant-0 is an unmodified extender consumer (HTTPExtender:
    filter -> prioritize -> bind per pod, full node objects on the wire);
    tenants 1..M-1 speak the native /solve endpoint with bind=True.
    Every tenant registers the SAME node names (adversarial), each with
    its own RaceDetector-wrapped ObjectStore. Phases: solo baseline
    (one tenant, the whole native shape, sequential) -> multi-tenant
    concurrent (the aggregate gate) -> victim unloaded p99 -> victim p99
    under a noisy tenant's native flood (the fairness gate)."""
    import threading

    from kubernetes_tpu.extender.client import ExtenderConfig, HTTPExtender
    from kubernetes_tpu.solversvc.core import SolverService, _svc_metrics
    from kubernetes_tpu.solversvc.server import SolverFrontend
    from kubernetes_tpu.solversvc.tenancy import split_tenant
    from kubernetes_tpu.testing.races import RaceDetector

    n_tenants = max(2, n_tenants)
    native = [f"tenant-{i}" for i in range(1, n_tenants)]
    victim = "tenant-0"
    solo = "solo"
    # pow-2 capacity for every tenant's namespaced node rows + solo's
    total_nodes = (n_tenants + (n_tenants - 1)) * nodes_per_tenant
    cap_nodes = 1
    while cap_nodes < total_nodes:
        cap_nodes *= 2
    caps = Capacities(num_nodes=max(64, cap_nodes), batch_pods=batch_pods)
    # pre-compile EVERY pod bucket the drill can hit (coalesced solve
    # groups bucket at next-pow-2 of their summed rows): a mid-flood
    # compile stall would pollute the victim's loaded p99 with XLA time
    buckets = []
    b = 4
    while b <= batch_pods:
        buckets.append(b)
        b *= 2

    svc = SolverService(caps=caps, window_s=window_ms / 1000.0,
                        total_seats=seats, queue_wait_s=queue_wait_s)
    mx = _svc_metrics()
    steps0 = int(mx["steps"].labels().value)

    stores: dict[str, ObjectStore] = {}
    for name in (victim, solo, *native):
        store: object = ObjectStore()
        if race_detect:
            store = RaceDetector(store)
        stores[name] = store
        svc.register_tenant(name, store=store)

    nodes = make_nodes(nodes_per_tenant, cpu="16", memory="64Gi")
    solo_nodes = make_nodes((n_tenants - 1) * nodes_per_tenant,
                            cpu="16", memory="64Gi")
    nodes_by_name = {n.metadata.name: n for n in nodes}

    def pods_for(prefix: str, count: int) -> list:
        return make_pods(count, cpu="20m", memory="32Mi",
                         name_prefix=prefix)

    flood_stop = threading.Event()
    flood_counts = {"requests": 0, "rejected": 0}
    flood_lock = threading.Lock()

    async def drive() -> SolverSvcResult:
        frontend = SolverFrontend(svc, warmup_buckets=tuple(buckets))
        await frontend.start()
        base = frontend.url
        try:
            return await phases(base)
        finally:
            await frontend.stop()

    async def phases(base: str) -> SolverSvcResult:
        # node state sync: native tenants + solo over the wire, all with
        # the SAME node names; the victim's nodes ride its filter calls
        for name in native:
            await asyncio.to_thread(
                _svc_post, base, f"/tenants/{name}/state",
                {"nodes": [n.to_dict() for n in nodes]})
        await asyncio.to_thread(
            _svc_post, base, f"/tenants/{solo}/state",
            {"nodes": [n.to_dict() for n in solo_nodes]})

        def native_requests(tenant: str, pods: list) -> int:
            """Closed loop: one solve request of req_pods in flight at a
            time — a control plane draining its queue. Returns binds."""
            ok = 0
            for i in range(0, len(pods), req_pods):
                chunk = pods[i:i + req_pods]
                stores[tenant].create_many(chunk)
                status, body = _svc_post(
                    base, f"/tenants/{tenant}/solve",
                    {"pods": [p.to_dict() for p in chunk], "bind": True})
                if status == 200 and isinstance(body, dict):
                    ok += sum(1 for b in body.get("bound", ()) if b)
            return ok

        # ---- phase A: solo baseline (same total native shape, 1 tenant)
        solo_pods = pods_for("solo", (n_tenants - 1) * pods_per_tenant)
        t0 = time.perf_counter()
        solo_bound = await asyncio.to_thread(native_requests, solo,
                                             solo_pods)
        solo_dt = time.perf_counter() - t0
        svc.drop_tenant(solo)

        # ---- phase B: the same shape split over M-1 concurrent tenants
        per_tenant = {name: pods_for(f"{name}-p", pods_per_tenant)
                      for name in native}
        t0 = time.perf_counter()
        bound_counts = await asyncio.gather(*(
            asyncio.to_thread(native_requests, name, per_tenant[name])
            for name in native))
        multi_dt = time.perf_counter() - t0
        native_bound = int(sum(bound_counts))

        # ---- phase C: victim over the stock extender wire, unloaded
        ext = HTTPExtender(ExtenderConfig(
            url_prefix=f"{base}/tenants/{victim}",
            filter_verb="filter", prioritize_verb="prioritize",
            weight=1, node_cache_capable=False))
        names = list(nodes_by_name)

        from kubernetes_tpu.extender.client import ExtenderError

        def shed_retry(call):
            # a stock scheduler retries a shed extender callout; the
            # server-side latency ring only records seated requests, so
            # retries don't pollute the p99 measurement
            for _ in range(40):
                try:
                    return call()
                except ExtenderError as e:
                    if "HTTP 429" not in str(e):
                        raise
                    # client-thread backoff, never on a loop
                    time.sleep(0.05)  # ktpu: allow[blocking-in-async]
            return call()

        def victim_wave(prefix: str, count: int) -> int:
            ok = 0
            for pod in pods_for(prefix, count):
                stores[victim].create(pod)
                passed, _failed = shed_retry(
                    lambda: ext.filter(pod, names, nodes_by_name))
                if not passed:
                    continue
                scores = shed_retry(
                    lambda: ext.prioritize(pod, passed, nodes_by_name))
                best = max(passed, key=lambda n: scores.get(n, 0.0))
                status, body = _svc_post(
                    base, f"/tenants/{victim}/bind",
                    {"PodName": pod.metadata.name,
                     "PodNamespace": pod.metadata.namespace or "default",
                     "Node": best})
                if status == 200 and not body.get("Error"):
                    ok += 1
            return ok

        victim_t = svc.tenants[victim]
        bound_a = await asyncio.to_thread(victim_wave, "vic-a",
                                          pods_per_tenant)
        unloaded = list(victim_t.latency)  # server-side seconds

        # ---- phase D: same wave under a noisy native tenant's flood
        def flood(worker: int) -> None:
            fpods = [p.to_dict()
                     for p in pods_for(f"flood{worker}", req_pods)]
            while not flood_stop.is_set():
                status, _body = _svc_post(
                    base, f"/tenants/{native[0]}/solve",
                    {"pods": fpods, "bind": False})
                with flood_lock:
                    flood_counts["requests"] += 1
                    if status == 429:
                        flood_counts["rejected"] += 1

        # real threads, NOT asyncio.to_thread: on a small box the default
        # executor has ~cpu+4 workers and the flood would starve the
        # victim's own executor slot (and anything else sharing the pool)
        flood_workers = [threading.Thread(target=flood, args=(i,),
                                          daemon=True)
                         for i in range(flood_threads)]
        for w in flood_workers:
            w.start()
        bound_b = await asyncio.to_thread(victim_wave, "vic-b",
                                          pods_per_tenant)
        flood_stop.set()
        while any(w.is_alive() for w in flood_workers):
            await asyncio.sleep(0.02)
        loaded = list(victim_t.latency)[len(unloaded):]

        # ---- audit: exactly-once binds + zero cross-tenant assignments
        bound = native_bound + solo_bound + bound_a + bound_b
        expected = ((n_tenants - 1) * pods_per_tenant * 2
                    + 2 * pods_per_tenant)
        double = 0
        racy = 0
        if race_detect:
            for store in stores.values():
                double += store.double_binds
                racy += len(store.racy_writes)
        cross = 0
        for name in (victim, *native):
            t = svc.tenants[name]
            own = {split_tenant(k)[1] for k in t.nodes}
            cross += sum(1 for node in t.assignments.values()
                         if node not in own)

        return SolverSvcResult(
            tenants=n_tenants, nodes_per_tenant=nodes_per_tenant,
            pods_per_tenant=pods_per_tenant, seed=seed,
            bound=bound, expected_bound=expected, double_binds=double,
            isolation_violations=int(mx["isolation"].labels().value),
            cross_tenant_assignments=cross,
            p99_unloaded_ms=_p99_ms(unloaded),
            p99_loaded_ms=_p99_ms(loaded),
            flood_requests=flood_counts["requests"],
            flood_rejected=flood_counts["rejected"],
            solo_pods_per_sec=len(solo_pods) / max(solo_dt, 1e-9),
            agg_pods_per_sec=sum(len(p) for p in per_tenant.values())
            / max(multi_dt, 1e-9),
            steps=int(mx["steps"].labels().value) - steps0,
            occupancy_max=int(mx["occupancy"].labels().value),
            converged=(bound == expected and double == 0 and cross == 0),
            racy_writes=racy)

    try:
        return asyncio.run(drive())
    finally:
        flood_stop.set()


@dataclass
class FederationResult:
    """Federation drill: one hub control plane (health + sync +
    GlobalPlanner) over N in-process member control planes, a mixed
    globally-placed workload set (incl. one gang), and a mid-run member
    saturation (its nodes vanish; its NodeGroup has zero headroom).
    Gates: every workload's replicas land across clusters exactly once
    (member copies sum to the hub total and match the plan, no
    duplicates), the planner records >= 1 spillover for the saturated
    member and drains its demand to siblings, the whole thing converges
    within budget, and the RaceDetector sees zero racy hub writes."""

    clusters: int
    pods: int
    seed: int
    workloads: int
    planned: int                 # workloads holding a complete plan
    placed: int                  # replicas ensured on members, post-drain
    exactly_once: bool           # sums match the hub totals + the plans
    duplicate_placements: int
    spillovers: int              # planner spillover events recorded
    victim_drained: bool         # saturated member ended at 0 replicas
    cycles: int
    solves: int
    solve_p50_ms: float
    converged: bool
    racy_writes: int = 0

    @property
    def gate(self) -> bool:
        return (self.converged and self.exactly_once
                and self.duplicate_placements == 0
                and self.spillovers >= 1 and self.victim_drained
                and self.racy_writes == 0)

    def __str__(self) -> str:
        return (f"fed C={self.clusters} P={self.pods}: "
                f"{self.planned}/{self.workloads} planned, "
                f"{self.placed} replicas placed "
                f"({'exactly-once' if self.exactly_once else 'DUPED'}), "
                f"{self.spillovers} spillovers "
                f"(victim {'drained' if self.victim_drained else 'WEDGED'}),"
                f" {self.cycles} cycles {self.solves} solves "
                f"~{self.solve_p50_ms:.1f}ms")


def run_federation(n_clusters: int = 4, n_pods: int = 24, seed: int = 2032,
                   race_detect: bool = True) -> FederationResult:
    """Blocking entry point for the federation global-planning drill.

    Topology: hub ObjectStore (RaceDetector-wrapped) running the full
    FederationControlPlane with the GlobalPlanner; N member ObjectStores,
    each a few nodes plus a NodeGroup pinned at max size (headroom 0 —
    saturation cannot be autoscaled away). Workloads: ~n_pods replicas
    split over several `placement: global` ReplicaSets, one of them a
    gang. Mid-run, member 0's nodes are deleted: its next capacity report
    shows zero free, the planner's charge trips spillover, the member's
    row is masked, demand re-plans onto siblings, and the sync controller
    rescales the victim's copies to zero."""
    import random

    from kubernetes_tpu.api.objects import Node, NodeGroup, ReplicaSet
    from kubernetes_tpu.apiserver.store import NotFound
    from kubernetes_tpu.federation.kubefed import (
        FederationControlPlane,
        join,
    )
    from kubernetes_tpu.federation.planner import (
        PLACEMENT_ANNOTATION,
        PLACEMENT_GLOBAL,
        parse_plan,
    )
    from kubernetes_tpu.gang import GROUP_MIN_ANNOTATION, GROUP_NAME_ANNOTATION
    from kubernetes_tpu.testing.races import RaceDetector

    n_clusters = max(3, n_clusters)
    rng = random.Random(seed)
    hub_inner = ObjectStore()
    hub = RaceDetector(hub_inner) if race_detect else hub_inner
    members = {f"member-{i}": ObjectStore() for i in range(n_clusters)}
    victim = "member-0"

    # every member can hold the WHOLE workload set on its own (spillover
    # must be able to drain anywhere), via a few fat nodes
    nodes_per = 2
    cpu_per_node = max(4, n_pods)  # cores; replicas request 500m each
    for name, store in members.items():
        for j in range(nodes_per):
            store.create(Node.from_dict({
                "metadata": {"name": f"{name}-n{j}",
                             "labels": {"kubernetes.io/hostname":
                                        f"{name}-n{j}"}},
                "status": {
                    "allocatable": {"cpu": str(cpu_per_node),
                                    "memory": f"{4 * cpu_per_node}Gi",
                                    "pods": "110"},
                    "capacity": {"cpu": str(cpu_per_node),
                                 "memory": f"{4 * cpu_per_node}Gi",
                                 "pods": "110"},
                    "conditions": [{"type": "Ready", "status": "True"}]}}))
        # pool pinned at max: zero autoscaler headroom, so a saturated
        # member spills instead of pretending it can grow
        store.create(NodeGroup.from_dict({
            "metadata": {"name": f"{name}-pool"},
            "spec": {"minSize": nodes_per, "maxSize": nodes_per},
            "status": {"targetSize": nodes_per,
                       "readyNodes": nodes_per}}))

    def client_factory(cluster):
        store = members.get(cluster.metadata.name)
        if store is None:
            raise ConnectionError(cluster.metadata.name)
        return store

    # mixed workload set: one gang + several plain ReplicaSets summing to
    # ~n_pods replicas, all placement=global
    gang_size = max(3, min(8, n_pods // 4))
    remaining = max(1, n_pods - gang_size)
    sizes = []
    while remaining > 0:
        s = min(remaining, rng.randint(2, 6))
        sizes.append(s)
        remaining -= s
    workloads = []
    for i, size in enumerate(sizes):
        workloads.append(ReplicaSet.from_dict({
            "metadata": {"name": f"fedw-{i}", "annotations": {
                PLACEMENT_ANNOTATION: PLACEMENT_GLOBAL}},
            "spec": {"replicas": size, "template": {
                "metadata": {"labels": {"app": f"fedw-{i}"}},
                "spec": {"containers": [{"name": "c", "resources": {
                    "requests": {"cpu": "500m", "memory": "256Mi"}}}]}}}}))
    workloads.append(ReplicaSet.from_dict({
        "metadata": {"name": "fedw-gang", "annotations": {
            PLACEMENT_ANNOTATION: PLACEMENT_GLOBAL,
            GROUP_NAME_ANNOTATION: "fedw-gang",
            GROUP_MIN_ANNOTATION: str(gang_size)}},
        "spec": {"replicas": gang_size, "template": {
            "metadata": {"labels": {"app": "fedw-gang"}},
            "spec": {"containers": [{"name": "c", "resources": {
                "requests": {"cpu": "500m", "memory": "256Mi"}}}]}}}}))
    total = sum(w.replicas for w in workloads)

    batch = 1
    while batch < max(16, total):
        batch *= 2
    plane = FederationControlPlane(
        hub, client_factory, health_period=0.1,
        planner=True, plan_interval=0.1,
        planner_caps=Capacities(num_nodes=max(8, n_clusters),
                                batch_pods=min(64, batch)))
    planner = plane.planner

    freeze_drill_heap()

    async def drive() -> FederationResult:
        for name in members:
            join(hub, name)
        for w in workloads:
            hub.create(w)
        await plane.start()
        for cluster in plane.clusters.items():
            plane.health.enqueue(cluster.metadata.name)

        def member_counts(wname: str) -> dict[str, int]:
            out = {}
            for cname, store in members.items():
                try:
                    out[cname] = store.get("ReplicaSet", wname).replicas
                except NotFound:
                    pass
            return out

        def settled(require_victim_zero: bool) -> bool:
            for w in workloads:
                try:
                    fresh = hub.get("ReplicaSet", w.metadata.name)
                except NotFound:
                    return False
                plan = parse_plan(fresh)
                if plan is None or int(plan.get("unplaced", 0)) > 0:
                    return False
                if require_victim_zero and \
                        plan["clusters"].get(victim, 0) > 0:
                    return False
                got = member_counts(w.metadata.name)
                for cname in members:
                    if got.get(cname, 0) != plan["clusters"].get(cname, 0):
                        return False
                if sum(got.values()) != w.replicas:
                    return False
            return True

        async def wait_settled(require_victim_zero: bool,
                               timeout_s: float) -> bool:
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                if settled(require_victim_zero):
                    return True
                await asyncio.sleep(0.05)
            return False

        phase1 = await wait_settled(False, 120.0)

        # saturate the victim: its nodes vanish (kernel panic, preemption,
        # a zone outage) while its NodeGroup stays pinned at max size —
        # the next capacity report shows zero free and zero headroom
        for j in range(nodes_per):
            members[victim].delete("Node", f"{victim}-n{j}")
        phase2 = await wait_settled(True, 120.0)

        dupes = 0
        placed = 0
        exactly_once = True
        for w in workloads:
            got = member_counts(w.metadata.name)
            placed += sum(got.values())
            if sum(got.values()) != w.replicas:
                exactly_once = False
            if sum(got.values()) > w.replicas:
                dupes += 1
        planned = sum(
            1 for w in workloads
            if parse_plan(hub.get("ReplicaSet", w.metadata.name)))
        victim_total = sum(
            member_counts(w.metadata.name).get(victim, 0)
            for w in workloads)
        solve_ms = (1e3 * planner.solve_seconds / planner.solve_count
                    if planner.solve_count else 0.0)
        plane.stop()
        return FederationResult(
            clusters=n_clusters, pods=total, seed=seed,
            workloads=len(workloads), planned=planned, placed=placed,
            exactly_once=exactly_once, duplicate_placements=dupes,
            spillovers=planner.spillovers,
            victim_drained=(victim_total == 0),
            cycles=planner.cycles, solves=planner.solve_count,
            solve_p50_ms=solve_ms,
            converged=(phase1 and phase2),
            racy_writes=len(hub.racy_writes) if race_detect else 0)

    try:
        result = asyncio.run(drive())
    finally:
        thaw_drill_heap()
    return result
