"""Scheduler driver: informers -> batch solver -> bindings.

The host loop replacing the reference's `Scheduler.Run`/`scheduleOne`
(plugin/pkg/scheduler/scheduler.go:149,253) and its factory wiring
(factory/factory.go:118 NewConfigFactory: informers feeding a FIFO of
unscheduled pods, error path with exponential backoff :897). Differences are
the point of the re-design:

- pods are popped in FIFO order but scheduled as a *batch* in one device
  program (ops/solver.py) instead of one blocking scheduleOne per pod;
- assume + bind: each assignment is accounted optimistically in StateDB
  (cache.AssumePod analog), then bound through the store; a failed bind
  rolls the assumption back (ForgetPod, scheduler.go:224) and requeues with
  backoff;
- unschedulable pods requeue with exponential backoff and emit
  FailedScheduling events (scheduler.go:174,248 event parity).
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import os
import sys
import threading
import time
from collections import deque

import numpy as np

from kubernetes_tpu.api.objects import Binding, Pod
from kubernetes_tpu.apiserver.store import (
    Conflict,
    NotFound,
    ObjectStore,
    TooManyRequests,
    WatchEvent,
)
from kubernetes_tpu.client.informer import Informer
from kubernetes_tpu.client.workqueue import Backoff, BackoffQueue
from kubernetes_tpu.gang import (
    DEFAULT_SCHEDULE_TIMEOUT_S,
    annotation_min,
    pod_group_key,
)
from kubernetes_tpu.models.policy import DEFAULT_POLICY, Policy
from kubernetes_tpu.obs import metrics as obs_metrics
from kubernetes_tpu.obs.collector import install as install_collector_policy
from kubernetes_tpu.obs.profiling import COMPILES, record_readback
from kubernetes_tpu.obs.tracing import (
    TRACE_ANNOTATION,
    TRACER,
    pod_trace_context,
    stage,
    watch_gc,
)
from kubernetes_tpu.ops.solver import EXPLAIN_STAGES, schedule_batch
from kubernetes_tpu.state import Capacities
from kubernetes_tpu.state.encode_cache import EncodeCache
from kubernetes_tpu.state.layout import CapacityError
from kubernetes_tpu.state.statedb import StateDB
from kubernetes_tpu.utils.events import EventRecorder
from kubernetes_tpu.utils.trace import StepTimer

log = logging.getLogger(__name__)


# queue-key namespace for gang groups: pod keys are "ns/name" (DNS-1123
# names cannot contain ":"), so the prefix cannot collide
_GANG_KEY_PREFIX = "gang:"

# requeue delay for a quarantined poison pod: long enough that one bad pod
# cannot re-poison every batch, short enough that a transient cause clears
QUARANTINE_BACKOFF_S = 30.0


class _SolveFailed(RuntimeError):
    """The device solve failed twice for one batch (raised internally to
    route schedule_pending into bisect/quarantine recovery)."""

# FailedScheduling reason per EXPLAIN_STAGES column — the reference's
# predicate names as they appear in failedPredicateMap events
# (findNodesThatFit, core/generic_scheduler.go:163)
EXPLAIN_REASONS = ("MatchNodeSelector", "Insufficient resources",
                   "PodFitsHostPorts", "NoDiskConflict", "MaxVolumeCount",
                   "MatchInterPodAffinity")


def render_unschedulable(counts, total_nodes: int) -> str | None:
    """Render one pod's explain breakdown (cumulative survivor counts
    down EXPLAIN_STAGES) into the reference's FailedScheduling message
    shape — "0/15000 nodes available: 11992 Insufficient tpu, 8
    PodFitsHostPorts". Returns None unless the final survivor count is
    zero (a schedulable pod is not a render candidate)."""
    counts = [int(c) for c in counts]
    if counts[-1] != 0:
        return None
    parts = []
    prev = total_nodes
    for i, stage in enumerate(EXPLAIN_STAGES):
        rejected = prev - counts[i]
        if rejected > 0:
            parts.append(f"{rejected} {EXPLAIN_REASONS[i]}")
        prev = counts[i]
    msg = f"0/{total_nodes} nodes available"
    return msg + (": " + ", ".join(parts) if parts else "")


# ExponentialBuckets(1000, 2, 15) in microseconds (reference metrics.go:36)
LATENCY_BUCKETS_US = obs_metrics.exponential_buckets(1000.0, 2.0, 15)
# phase spans run ~10us (cache-hit encode) to tens of seconds (cold solve)
PHASE_BUCKETS_S = obs_metrics.exponential_buckets(1e-5, 2.0, 22)


class _LatencyWindow(deque):
    """Bounded sample window (seconds) whose append also observes a
    registry histogram in microseconds — the reference's fixed-bucket
    Prometheus histograms; the window keeps snapshot() percentiles exact.
    Call sites alias `.append`, so the mirror lives here."""

    def __init__(self, hist, extra=None):
        super().__init__(maxlen=8192)
        self._hist = hist
        self._extra = extra

    def append(self, seconds: float) -> None:
        self._hist.observe(1e6 * seconds)
        if self._extra is not None:
            self._extra(seconds)
        super().append(seconds)


class SchedulerMetrics:
    """Counters/latency mirrors of the reference's Prometheus metrics
    (plugin/pkg/scheduler/metrics/metrics.go:31-50), backed by an obs
    registry. Each instance owns a PRIVATE registry by default: tests and
    the perf harness construct many schedulers per process and assert
    exact per-instance counts, so scheduler families must not accumulate
    across instances. The scheduler's /metrics endpoint renders this
    registry plus the process-global one (workqueue/informer families)."""

    def __init__(self, registry: obs_metrics.Registry | None = None):
        self.registry = registry if registry is not None \
            else obs_metrics.Registry()
        r = self.registry
        self._c_scheduled = r.counter(
            "scheduler_pods_scheduled_total", "Pods successfully bound.")
        self._c_failed = r.counter(
            "scheduler_pods_failed_total",
            "Scheduling attempts that failed.")
        self._c_binding_errors = r.counter(
            "scheduler_binding_errors_total", "Bind writes rejected.")
        self._c_batches = r.counter(
            "scheduler_batches_total", "Solver batches dispatched.")
        self._c_jit_hits = r.counter(
            "scheduler_jit_cache_hits_total",
            "Batches served by an already-compiled solver variant.")
        self._c_jit_misses = r.counter(
            "scheduler_jit_cache_misses_total",
            "Batches that compiled a new solver variant (BatchFlags).")
        self._c_solve_batches = r.counter(
            "scheduler_solve_batches_total",
            "Solver batches by the row count they were solved at (the "
            "rung of the batch ladder).", ("rows",))
        self._c_solve_padding = r.counter(
            "scheduler_solve_padding_rows_total",
            "Padding rows solved: rung rows past each batch's pods.")
        self._c_gang_placed = r.counter(
            "scheduler_gang_groups_placed_total",
            "Gangs whose quorum placed and bound atomically.")
        self._c_gang_reverted = r.counter(
            "scheduler_gang_groups_reverted_total",
            "Gangs the solver reverted below quorum (no member bound).")
        self._c_gang_timeouts = r.counter(
            "scheduler_gang_groups_timeout_total",
            "Gangs that timed out waiting for quorum; members released.")
        self._c_preempt_attempts = r.counter(
            "scheduler_preemption_attempts_total",
            "Preemption attempts (pods with a victim-set verdict).")
        self._c_preempt_victims = r.counter(
            "scheduler_preemption_victims_total",
            "Pods evicted to make room for higher-priority pods.")
        self._c_preempt_success = r.counter(
            "scheduler_preemption_success_total",
            "Preemptions that evicted their victims and nominated a node.")
        self._c_solve_failures = r.counter(
            "scheduler_solve_failures_total",
            "Device solve attempts that raised or timed out.")
        self._c_solve_retries = r.counter(
            "scheduler_solve_retries_total",
            "Batches re-dispatched after a failed device solve.")
        self._c_quarantined = r.counter(
            "scheduler_pods_quarantined_total",
            "Pods quarantined after bisection isolated them as the cause "
            "of persistent solve failures.")
        self._c_serial_fallback = r.counter(
            "scheduler_serial_fallback_pods_total",
            "Pods placed by the degraded serial host path while the "
            "device solver was failing.")
        self._h_phase = r.histogram(
            "scheduler_phase_duration_seconds",
            "Per-batch scheduling phase durations "
            "(encode/flush/dispatch/solve/settle_wait/bind/commit).",
            ("phase",), buckets=PHASE_BUCKETS_S)
        # pipeline saturation gauges, refreshed at scrape time from
        # StagedPipeline.snapshot() so the monitor can watch the same
        # busy fractions the bench extras report
        self._g_stage_busy = r.gauge(
            "scheduler_pipeline_stage_busy_frac",
            "Fraction of the wall each pipeline stage was busy since "
            "the last stats reset.", ("stage",))
        self._g_queue_hw = r.gauge(
            "scheduler_pipeline_queue_high_water",
            "Queue-depth high-water mark per pipeline stage queue.",
            ("stage",))
        self._g_pipe_depth = r.gauge(
            "scheduler_pipeline_depth",
            "Batches currently in flight in the staged pipeline.")
        self._scheduled = 0
        self._failed = 0
        self._binding_errors = 0
        self._batches = 0
        self.gang_placed = 0
        self.gang_reverted = 0
        self.gang_timeouts = 0
        self.preempt_attempts = 0
        self.preempt_victims = 0
        self.preempt_success = 0
        self.solve_failures = 0
        self.solve_retries = 0
        self.quarantined = 0
        self.serial_fallback = 0
        # batches per rung (rows -> count) and padding rows solved
        self.solve_batches: dict[int, int] = {}
        self.solve_padding_rows = 0
        # bounded windows (the registry histograms are cumulative; the
        # windows keep the recent-sample percentiles snapshot() reports)
        self.e2e_latency = _LatencyWindow(r.histogram(
            "e2e_scheduling_latency_microseconds",
            "E2e scheduling latency (queue arrival to bind).",
            buckets=LATENCY_BUCKETS_US))
        self.algorithm_latency = _LatencyWindow(
            r.histogram("scheduling_algorithm_latency_microseconds",
                        "Scheduling algorithm (device solve) latency.",
                        buckets=LATENCY_BUCKETS_US),
            extra=lambda s: self.add_phase("solve", s))
        self.binding_latency = _LatencyWindow(r.histogram(
            "binding_latency_microseconds", "Binding latency per pod.",
            buckets=LATENCY_BUCKETS_US))
        # cumulative host-plane phase costs (seconds): the per-phase
        # breakdown of where a batch's host time goes, plus accrued waits
        # (queue_wait, in_flight, gc_pause) that are no batch phase
        self.phase_s: dict = {}
        self.phase_pods = 0

    # counter attributes stay plain-int readable/writable (tests assert
    # `metrics.scheduled == 40`); writes mirror the delta to the registry
    @property
    def scheduled(self) -> int:
        return self._scheduled

    @scheduled.setter
    def scheduled(self, value: int) -> None:
        if value > self._scheduled:
            self._c_scheduled.inc(value - self._scheduled)
        self._scheduled = value

    @property
    def failed(self) -> int:
        return self._failed

    @failed.setter
    def failed(self, value: int) -> None:
        if value > self._failed:
            self._c_failed.inc(value - self._failed)
        self._failed = value

    @property
    def binding_errors(self) -> int:
        return self._binding_errors

    @binding_errors.setter
    def binding_errors(self, value: int) -> None:
        if value > self._binding_errors:
            self._c_binding_errors.inc(value - self._binding_errors)
        self._binding_errors = value

    @property
    def batches(self) -> int:
        return self._batches

    @batches.setter
    def batches(self, value: int) -> None:
        if value > self._batches:
            self._c_batches.inc(value - self._batches)
        self._batches = value

    def jit_hit(self) -> None:
        self._c_jit_hits.inc()

    def jit_miss(self) -> None:
        self._c_jit_misses.inc()

    def solve_rows_inc(self, rows: int, pods: int) -> None:
        self.solve_batches[rows] = self.solve_batches.get(rows, 0) + 1
        self.solve_padding_rows += rows - pods
        self._c_solve_batches.labels(str(rows)).inc()
        self._c_solve_padding.inc(rows - pods)

    def gang_placed_inc(self) -> None:
        self.gang_placed += 1
        self._c_gang_placed.inc()

    def gang_reverted_inc(self) -> None:
        self.gang_reverted += 1
        self._c_gang_reverted.inc()

    def gang_timeout_inc(self) -> None:
        self.gang_timeouts += 1
        self._c_gang_timeouts.inc()

    def preempt_attempt_inc(self) -> None:
        self.preempt_attempts += 1
        self._c_preempt_attempts.inc()

    def preempt_victims_add(self, n: int) -> None:
        self.preempt_victims += n
        self._c_preempt_victims.inc(n)

    def preempt_success_inc(self) -> None:
        self.preempt_success += 1
        self._c_preempt_success.inc()

    def solve_failure_inc(self) -> None:
        self.solve_failures += 1
        self._c_solve_failures.inc()

    def solve_retry_inc(self) -> None:
        self.solve_retries += 1
        self._c_solve_retries.inc()

    def quarantine_inc(self) -> None:
        self.quarantined += 1
        self._c_quarantined.inc()

    def serial_fallback_inc(self) -> None:
        self.serial_fallback += 1
        self._c_serial_fallback.inc()

    def add_phase(self, name: str, seconds: float) -> None:
        self.phase_s[name] = self.phase_s.get(name, 0.0) + seconds
        self._h_phase.labels(name).observe(seconds)

    def accrue(self, name: str, seconds: float) -> None:
        """Add to phase_s without a phase-histogram observation: a sum
        of per-pod waits or of collector pauses is no batch phase (the
        workqueue's and the gc counter's own families carry those)."""
        self.phase_s[name] = self.phase_s.get(name, 0.0) + seconds

    def export_pipeline(self, snap: dict | None) -> None:
        """Mirror a StagedPipeline.snapshot() into the saturation
        gauges — called at /metrics scrape time."""
        if not snap:
            return
        for stage, frac in (snap.get("stage_busy_frac") or {}).items():
            self._g_stage_busy.labels(stage).set(float(frac))
        for stage, depth in (snap.get("queue_depth_max") or {}).items():
            self._g_queue_hw.labels(stage).set(float(depth))
        self._g_pipe_depth.set(float(snap.get("depth", 0)))

    def phase_histograms(self) -> dict:
        """Per-phase histogram snapshot {phase: {count, sum_ms, p50_ms,
        p99_ms}} — the bench.py --metrics-snapshot payload, quantiles
        estimated from the registry buckets (histogram_quantile shape)."""
        out: dict = {}
        for (phase,), child in self._h_phase.children():
            out[phase] = {
                "count": child.count,
                "sum_ms": round(1e3 * child.sum, 3),
                "p50_ms": round(1e3 * child.quantile(0.5), 3),
                "p99_ms": round(1e3 * child.quantile(0.99), 3),
            }
        return out

    def snapshot(self) -> dict:
        lat = sorted(self.e2e_latency) or [0.0]
        out = {
            "scheduled": self.scheduled,
            "failed": self.failed,
            "binding_errors": self.binding_errors,
            "batches": self.batches,
            "e2e_p50_ms": 1e3 * lat[len(lat) // 2],
            "e2e_p99_ms": 1e3 * lat[min(len(lat) - 1, int(len(lat) * 0.99))],
        }
        if self.phase_pods:
            out["phase_us_per_pod"] = {
                k: round(1e6 * v / self.phase_pods, 2)
                for k, v in sorted(self.phase_s.items())}
        if self.solve_batches:
            out["solve_rows"] = {
                "batches": dict(sorted(self.solve_batches.items())),
                "padding_rows": self.solve_padding_rows}
        if self.gang_placed or self.gang_reverted or self.gang_timeouts:
            out["gang"] = {"placed": self.gang_placed,
                           "reverted": self.gang_reverted,
                           "timeouts": self.gang_timeouts}
        if self.preempt_attempts:
            out["preemption"] = {"attempts": self.preempt_attempts,
                                 "victims": self.preempt_victims,
                                 "success": self.preempt_success}
        if self.solve_failures or self.quarantined or self.serial_fallback:
            out["faults"] = {"solve_failures": self.solve_failures,
                             "solve_retries": self.solve_retries,
                             "quarantined": self.quarantined,
                             "serial_fallback": self.serial_fallback}
        return out


def store_encode_context(store: ObjectStore, policy: Policy = DEFAULT_POLICY,
                         local_volumes_enabled=False):
    """EncodeContext backed by the object store — the PVInfo/PVCInfo and
    Service/RC/RS/StatefulSet listers the reference's predicate/priority
    factories receive (factory/plugins.go PluginFactoryArgs)."""
    from kubernetes_tpu.state.context import EncodeContext

    def getter(kind):
        def get(name, namespace="default"):
            try:
                return store.get(kind, name, namespace)
            except NotFound:
                return None
        return get

    get_pvc_ = getter("PersistentVolumeClaim")
    get_pv_ = getter("PersistentVolume")
    get_node_ = getter("Node")
    # read-only listers: skip the defensive deep clone (the encoders never
    # mutate — at 15k nodes / 30k pods a cloning list per encode miss was
    # the single largest host cost after device transfers)
    return EncodeContext(
        get_pvc=lambda ns, name: get_pvc_(name, ns),
        get_pv=lambda name: get_pv_(name),
        local_volumes_enabled=local_volumes_enabled,
        get_services=lambda ns: store.list("Service", ns, copy_objects=False),
        get_rcs=lambda ns: store.list("ReplicationController", ns,
                                      copy_objects=False),
        get_rss=lambda ns: store.list("ReplicaSet", ns, copy_objects=False),
        get_sss=lambda ns: store.list("StatefulSet", ns, copy_objects=False),
        list_pods=lambda ns: store.list("Pod", ns, copy_objects=False),
        get_node=lambda name: get_node_(name),
        service_affinity_labels=policy.service_affinity_labels(),
        service_anti=bool(policy.service_anti_priorities),
    )


# JAX tracing on a worker thread keeps the GIL for whole switch
# intervals at a time: with the default 5 ms, tracing one solver program
# stalled the event loop for 0.2-1 s on the CPU. A variant's first call
# traces every rung of the ladder, so it runs with a finer interval,
# restored after. The lock keeps two warm-ups from interleaving the
# save and the restore.
_WARM_SWITCH_INTERVAL_S = 0.0005
_WARM_LOCK = threading.Lock()


class _LadderProgram:
    """One solver variant (BatchFlags) at every rung of the batch ladder
    (state/pod_batch.solve_rungs): a call runs the program compiled for
    its blobs' row count. The first call with a victim table, and the
    first without one, dispatch their own batch and then run every other
    rung once on all-padding blobs, so the variant's later batches,
    whatever their size, compile nothing. That first call happens on the
    thread that dispatches the solve, never on the event loop."""

    def __init__(self, programs: dict):
        self.programs = programs      # rows -> instrumented jit program
        self._warm: set[bool] = set()

    def __call__(self, state, fblob, iblob, rr, victims=None):
        shape = victims is None
        rows = fblob.shape[0]
        if shape in self._warm:
            return self.programs[rows](state, fblob, iblob, rr, victims)
        with _WARM_LOCK:
            interval = sys.getswitchinterval()
            sys.setswitchinterval(_WARM_SWITCH_INTERVAL_S)
            try:
                out = self.programs[rows](state, fblob, iblob, rr, victims)
                if shape not in self._warm:
                    for other, fn in self.programs.items():
                        if other != rows:
                            fn(state,
                               np.zeros((other, fblob.shape[1]), np.float32),
                               np.zeros((other, iblob.shape[1]), np.int32),
                               rr, victims)
                    self._warm.add(shape)
            finally:
                sys.setswitchinterval(interval)
        return out

    def lower(self, state, fblob, iblob, rr, victims=None):
        return self.programs[fblob.shape[0]].lower(state, fblob, iblob, rr,
                                                   victims)


# back-compat alias (pre-spreading name)
def store_volume_context(store: ObjectStore, local_volumes_enabled=False):
    return store_encode_context(store,
                                local_volumes_enabled=local_volumes_enabled)


class Scheduler:
    def __init__(
        self,
        store: ObjectStore,
        caps: Capacities | None = None,
        policy: Policy = DEFAULT_POLICY,
        mesh=None,
        scheduler_name: str = "default-scheduler",
        batch_wait: float = 0.002,
        enable_preemption: bool = True,
        explain: bool | None = None,
    ):
        from kubernetes_tpu.utils.compilation_cache import enable

        enable()  # persistent XLA cache before this plane's first compile

        self.store = store
        self.caps = caps or Capacities()
        if mesh is not None and self.caps.num_nodes % mesh.size:
            # GSPMD shards the node axis evenly: round the row budget up to
            # the next mesh multiple (the extra rows stay unassigned — same
            # sentinel shape shard_state pads direct callers with)
            import dataclasses as _dc

            from kubernetes_tpu.parallel.mesh import padded_num_nodes
            self.caps = _dc.replace(
                self.caps,
                num_nodes=padded_num_nodes(self.caps.num_nodes, mesh.size))
        policy = policy.with_env_overrides()  # KUBE_MAX_PD_VOLS (defaults.go)
        self.policy = policy
        self.scheduler_name = scheduler_name
        self.batch_wait = batch_wait

        self.volume_ctx = store_encode_context(store, policy)
        self.statedb = StateDB(self.caps, mesh=mesh, volume_ctx=self.volume_ctx)
        self.encode_cache = EncodeCache(self.caps, self.statedb.table,
                                        volume_ctx=self.volume_ctx)
        from kubernetes_tpu.models.policy import build_policy_rows

        self._prows = build_policy_rows(policy, self.statedb.table, self.caps)
        self.queue = BackoffQueue(name="scheduler")
        self.backoff = Backoff(initial=0.05, max_duration=5.0)
        self.metrics = SchedulerMetrics()
        # per-pod add-to-pop and pop-to-done waits, and collector pauses,
        # accrue into whichever metrics object is current
        self.queue.wait_sink = lambda name, s: self.metrics.accrue(name, s)
        watch_gc(self)
        # batch id: assigned at encode, the `batch` of every stage span
        self._batch_seq = 0
        self.events = EventRecorder(store)
        self._assumed: set[str] = set()
        self._enqueue_time: dict[str, float] = {}
        self._rr = np.uint32(0)
        # packed transport blob free-list: acquired at batch assembly,
        # released once the batch's ledger commits (in-flight batches'
        # blobs stay referenced — commit reads accounting rows from them)
        self._blob_pool: deque = deque()
        # host StateDB/EncodeCache guard: the loop mutates them from
        # informer handlers and encode, the staged dispatch thread reads
        # them in flush(), the commit thread scatters in commit_batch()
        self._state_lock = threading.RLock()
        # informer events waiting for _state_lock: handlers run on the
        # event loop, but the staged commit thread can hold the lock for
        # the length of a ledger commit — a blocking acquire there would
        # stall the whole loop (heartbeats, other informers, watchdogs)
        # for that window. Contended events park here and replay in FIFO
        # order once the lock frees (per-object ordering preserved: once
        # anything is queued, everything queues behind it).
        self._deferred_events: deque = deque()
        self._drain_handle: asyncio.TimerHandle | None = None
        # deferred event buffer, (obj, type, reason, message): recording
        # is off the batch-critical path, coalesced per solved batch and
        # flushed when the loop next idles (the EventBroadcaster's
        # buffered-channel shape, record/event.go:78); stop() flushes
        # synchronously so no event is ever dropped
        self._pending_events: list[tuple[Pod, str, str, str]] = []
        self._event_flush_scheduled = False
        # node name -> keys of bound pods seen on it (indexed even before
        # the node itself is known, so a late node event re-accounts them);
        # replaces the O(nodes x pods) informer sweep per node event
        self._pods_by_node: dict[str, set[str]] = {}
        self._pod_node: dict[str, str] = {}
        # gang staging: annotated members wait here until their group
        # reaches quorum, then the whole group enters ONE batch (never
        # split — the solver's revert window is a contiguous in-batch run)
        self._gang_members: dict[str, set[str]] = {}
        self._gang_of_pod: dict[str, str] = {}
        self._gang_first_seen: dict[str, float] = {}
        self._gang_min_hint: dict[str, int] = {}
        # priority preemption: nominated-node capacity holds + the flag
        # (BatchFlags.preempt additionally gates the pass per batch, so a
        # priority-free workload never compiles the preemption program)
        from kubernetes_tpu.preemption import NominatedNodes

        self.enable_preemption = enable_preemption
        self.nominated = NominatedNodes()

        self.node_informer = Informer(store, "Node")
        self.pod_informer = Informer(store, "Pod")
        self.podgroup_informer = Informer(store, "PodGroup")
        self.node_informer.add_handler(self._on_node_event)
        self.pod_informer.add_handler(self._on_pod_event)
        self.podgroup_informer.add_handler(self._on_podgroup_event)
        # workload objects feed cached pod encodings (spreading entries):
        # any change invalidates the encode cache (the reference invalidates
        # its equivalence cache from the same informers, factory.go:160-250)
        self.workload_informers = [
            Informer(store, kind)
            for kind in ("Service", "ReplicationController", "ReplicaSet",
                         "StatefulSet")]
        for informer in self.workload_informers:
            informer.add_handler(self._on_workload_event)

        self.mesh = mesh
        self._schedule_fns: dict = {}
        # policy-configured external extenders (core/extender.go:40): when
        # present, scheduling runs per pod — device evaluation first, then
        # each extender's Filter/Prioritize (the reference's composition
        # points, generic_scheduler.go:211-228,381-401)
        from kubernetes_tpu.extender.client import HTTPExtender

        self._extenders = [HTTPExtender(c) for c in policy.extenders]
        self._pod_eval_fn = None
        self._stopped = False
        # Pipelining: dispatch batch k+1 while batch k's result is still in
        # flight on the device, hiding dispatch/readback round-trip
        # latency. Safe only when pod
        # encoding is placement-independent: ServiceAffinity backfills and
        # ServiceAntiAffinity totals read current placements at encode time,
        # so those policies force the synchronous path.
        self._pipeline = not (policy.service_affinity_labels()
                              or policy.service_anti_priorities)
        # in-flight batches, oldest first; depth >1 hides the per-batch
        # dispatch/readback round trip behind the next batch's solve
        import os

        self.pipeline_depth = int(
            os.environ.get("KTPU_PIPELINE_DEPTH", "4") or 4)
        self._inflight_q: deque = deque()
        # staged stage-per-thread pipeline (scheduler/pipeline.py):
        # encode on the loop | dispatch | settle | commit+bind in worker
        # threads. The default batch path when encoding is
        # placement-independent; KTPU_STAGED_PIPELINE=0 falls back to the
        # single-loop pipelined driver
        from kubernetes_tpu.scheduler.pipeline import (
            EventShard,
            LoopCalls,
            StagedPipeline,
        )

        self._loop_calls = LoopCalls()
        staged_on = self._pipeline and (
            os.environ.get("KTPU_STAGED_PIPELINE", "1") != "0")
        self._staged = StagedPipeline(self, self.pipeline_depth) \
            if staged_on else None
        self._event_shard = EventShard(self.events, self._loop_calls) \
            if staged_on else None
        if self._event_shard is not None:
            self._event_shard._recorder_metrics_hook = \
                lambda s: self.metrics.add_phase("events_async", s)
        # settled-count accumulator + failed-batch payloads filled by the
        # staged pipeline's loop-side closures; schedule_pending drains
        self._staged_settled = 0
        self._staged_failures: list = []
        # solve-failure hardening (the batched analog of the reference's
        # MakeDefaultErrorFunc: an algorithm error must never kill the
        # scheduling loop). With a timeout set, each dispatch+readback runs
        # in a worker thread under a watchdog deadline — trading pipelined
        # dispatch for boundedness against a wedged device
        self.solve_timeout_s = float(
            os.environ.get("KTPU_SOLVE_TIMEOUT_S", "0") or 0) or None
        # testing seam: called with the batch's pod keys right before every
        # dispatch (FaultPlane.solve_hook injects failures through it)
        self.solve_fault_hook = None
        self.quarantine_backoff_s = QUARANTINE_BACKOFF_S
        self._quarantined: set[str] = set()
        # "why pending" explainability: compile the explain variant and
        # render per-predicate FailedScheduling reasons for every
        # unschedulable pod. An operator switch (KTPU_EXPLAIN / ctor arg),
        # NEVER batch-content derived — see BatchFlags.explain
        self.explain = explain if explain is not None \
            else os.environ.get("KTPU_EXPLAIN", "") in ("1", "true")

    @staticmethod
    def _variant_key(flags) -> str:
        """Human-readable jit-variant label for the compile registry:
        the active BatchFlags gates joined, 'baseline' when none."""
        on = [f.name for f in dataclasses.fields(flags)
              if getattr(flags, f.name)]
        return "+".join(on) or "baseline"

    def _get_schedule_fn(self, flags):
        """Compiled solver variant for this batch's content gates — a
        handful of variants in practice — at every rung of the batch
        ladder: one program per (BatchFlags, rows), each registered as
        `<variant>@p<rows>`."""
        import jax

        fn = self._schedule_fns.get(flags)
        if fn is not None:
            self.metrics.jit_hit()
        else:
            self.metrics.jit_miss()
            from kubernetes_tpu.state.pod_batch import (solve_rungs,
                                                        unpack_batch)

            caps, policy, prows = self.caps, self.policy, self._prows
            if self.mesh is not None:
                from kubernetes_tpu.parallel.mesh import make_sharded_scheduler
                jfn = make_sharded_scheduler(self.mesh, policy, caps=caps,
                                             prows=prows, flags=flags,
                                             packed=True)
            else:
                jfn = jax.jit(
                    lambda s, fb, ib, rr, v=None: schedule_batch(
                        s, unpack_batch(fb, ib, caps), rr, policy,
                        caps=caps, prows=prows, flags=flags, victims=v))
            # compile registry (obs/profiling.py): first-call compile
            # seconds + cost_analysis per rung ride the cache entry
            key = self._variant_key(flags)
            fn = _LadderProgram({
                rows: COMPILES.instrument(f"{key}@p{rows}", jfn)
                for rows in solve_rungs(caps.batch_pods)})
            self._schedule_fns[flags] = fn
        return fn

    def _solve_blobs(self, fblob, iblob, n: int):
        """The rows of a batch's pooled blob pair that its solve reads:
        the smallest rung of the ladder that holds its n pods. The rows
        past the pods are zeroed (a reused blob still holds the previous
        batch's encodings, valid flags in particular); the views are
        contiguous, no copy."""
        from kubernetes_tpu.state.pod_batch import solve_rows

        rows = solve_rows(n, self.caps.batch_pods)
        fblob[n:rows] = 0.0
        iblob[n:rows] = 0
        self.metrics.solve_rows_inc(rows, n)
        return fblob[:rows], iblob[:rows]

    def _on_workload_event(self, event: WatchEvent) -> None:
        self.encode_cache.generation += 1

    # ---- informer handlers ----

    # retry cadence for the deferred-event drain; short enough that a
    # parked event lands within a tick of the commit thread releasing
    # the lock, long enough not to busy-spin the loop against it
    _DRAIN_RETRY_S = 0.002

    def _on_node_event(self, event: WatchEvent) -> None:
        self._handle_locked(self._apply_node_event, event)

    def _on_pod_event(self, event: WatchEvent) -> None:
        self._handle_locked(self._apply_pod_event, event)

    def _handle_locked(self, apply, event: WatchEvent) -> None:
        """Run an informer-event application under _state_lock without
        ever blocking the event loop on it: if the lock is contended
        (commit thread mid-ledger-commit) or earlier events are already
        parked, defer and drain in order once it frees."""
        if self._deferred_events \
                or not self._state_lock.acquire(blocking=False):
            self._deferred_events.append((apply, event))
            self._schedule_drain()
            return
        try:
            apply(event)
        finally:
            self._state_lock.release()

    def _schedule_drain(self) -> None:
        if self._drain_handle is None:
            self._drain_handle = asyncio.get_running_loop().call_later(
                self._DRAIN_RETRY_S, self._drain_deferred)

    def _drain_deferred(self) -> None:
        self._drain_handle = None
        # re-acquire per event so the commit/dispatch threads can
        # interleave, and bound the work per loop callback — a long
        # backlog drains across several callbacks instead of recreating
        # the stall this path exists to avoid
        budget = 256
        while self._deferred_events and budget > 0:
            if not self._state_lock.acquire(blocking=False):
                break
            try:
                apply, event = self._deferred_events.popleft()
                apply(event)
            except Exception:  # noqa: BLE001 — parity with Informer._dispatch
                log.exception("deferred informer event failed")
            finally:
                self._state_lock.release()
            budget -= 1
        if self._deferred_events:
            self._schedule_drain()

    def _apply_node_event(self, event: WatchEvent) -> None:
        node = event.obj
        with self._state_lock:
            if event.type == "DELETED":
                self.statedb.remove_node(node.metadata.name)
                return
            self.statedb.upsert_node(node)
            # re-account bound pods the state missed: pods whose
            # MODIFIED/ADDED event raced ahead of this node's, or whose
            # accounting was dropped by a node delete+recreate — via the
            # node->pods index, not an O(all pods) informer sweep
            for key in self._pods_by_node.get(node.metadata.name, ()):
                if self.statedb.is_accounted(key) or key in self._assumed:
                    continue
                ns, name = key.split("/", 1)
                pod = self.pod_informer.get(name, ns)
                if pod is not None \
                        and pod.spec.node_name == node.metadata.name:
                    self.statedb.add_pod(pod)

    def _wants(self, pod: Pod) -> bool:
        return pod.spec.scheduler_name == self.scheduler_name

    @property
    def inflight_batches(self) -> int:
        """Dispatched-but-unsettled batches (pipeline depth in use)."""
        staged = self._staged.inflight if self._staged is not None else 0
        return len(self._inflight_q) + staged

    def _unindex_pod(self, key: str) -> None:
        prev = self._pod_node.pop(key, None)
        if prev is not None:
            pods = self._pods_by_node.get(prev)
            if pods is not None:
                pods.discard(key)
                if not pods:
                    del self._pods_by_node[prev]

    def _apply_pod_event(self, event: WatchEvent) -> None:
        pod: Pod = event.obj
        key = pod.key
        if event.type == "DELETED":
            self._assumed.discard(key)
            self._quarantined.discard(key)
            self._enqueue_time.pop(key, None)
            self._unindex_pod(key)
            self._gang_forget(key)
            with self._state_lock:
                self.statedb.remove_pod(key)
                self.encode_cache.forget(key)
            return
        if pod.spec.node_name:
            if self._pod_node.get(key) != pod.spec.node_name:
                self._unindex_pod(key)
                self._pod_node[key] = pod.spec.node_name
                self._pods_by_node.setdefault(
                    pod.spec.node_name, set()).add(key)
            self._enqueue_time.pop(key, None)
            self._quarantined.discard(key)  # bound after all: not poison
            self._gang_forget(key)
            with self._state_lock:
                self.encode_cache.forget(key)
                if key in self._assumed:
                    # our own binding confirmed by the watch
                    self._assumed.discard(key)
                else:
                    # bound elsewhere; if the node is unknown the
                    # node-event handler re-accounts it once it appears
                    self.statedb.add_pod(pod)
        elif self._wants(pod):
            self._enqueue_time.setdefault(key, time.monotonic())
            # encode-on-watch: fingerprint + class encode now, while the
            # previous batch is on the wire/device, so batch assembly on
            # the critical path is a key lookup + two row memcpys
            try:
                with self._state_lock:
                    self.encode_cache.premake(pod)
            except CapacityError:
                # over-capacity pods still enqueue: batch assembly re-raises
                # and its per-pod failure path records the FailedScheduling
                # event instead of wedging the informer handler
                pass
            except (Conflict, TooManyRequests):
                # transient apiserver fault inside encode-on-watch (the
                # encode context lists Services/workloads through the
                # store): premake is only a cache warmer — the pod MUST
                # still enqueue, or a throttled list silently drops it
                # from scheduling forever; batch assembly re-encodes
                pass
            # gang members wait in staging until their group reaches
            # quorum — the extender path is per-pod and cannot place a
            # group atomically, so it schedules them individually
            if not self._extenders and self._stage_gang_member(key, pod):
                return
            self.queue.add(key)

    # ---- gang scheduling (all-or-nothing groups) ----

    def _on_podgroup_event(self, event: WatchEvent) -> None:
        """A PodGroup write can change a group's quorum: re-check whether
        the staged members now satisfy it."""
        group = event.obj
        gkey = f"{group.metadata.namespace}/{group.metadata.name}"
        members = self._gang_members.get(gkey)
        if members and len(members) >= self._gang_quorum(gkey):
            self.queue.add(_GANG_KEY_PREFIX + gkey)

    def _gang_quorum(self, gkey: str) -> int:
        """minMember for a group: the PodGroup object when it exists, else
        the largest group-min annotation seen on a member, else 1."""
        ns, name = gkey.split("/", 1)
        group = self.podgroup_informer.get(name, ns)
        if group is not None:
            return max(1, group.min_member)
        return max(1, self._gang_min_hint.get(gkey, 1))

    def _gang_timeout(self, gkey: str) -> float:
        ns, name = gkey.split("/", 1)
        group = self.podgroup_informer.get(name, ns)
        if group is not None and group.schedule_timeout_seconds:
            return float(group.schedule_timeout_seconds)
        return DEFAULT_SCHEDULE_TIMEOUT_S

    def _gang_forget(self, key: str) -> None:
        """Drop one pod from gang staging (deleted, bound, or released)."""
        gkey = self._gang_of_pod.pop(key, None)
        if gkey is None:
            return
        members = self._gang_members.get(gkey)
        if members is not None:
            members.discard(key)
            if not members:
                del self._gang_members[gkey]
                self._gang_first_seen.pop(gkey, None)
                self._gang_min_hint.pop(gkey, None)

    def _stage_gang_member(self, key: str, pod: Pod) -> bool:
        """Stage a gang-annotated pending pod; enqueue its GROUP (not the
        pod) once quorum is staged. Returns False for non-gang pods."""
        gkey = pod_group_key(pod)
        if gkey is None:
            return False
        prev = self._gang_of_pod.get(key)
        if prev is not None and prev != gkey:
            self._gang_forget(key)  # annotation changed: move groups
        self._gang_of_pod[key] = gkey
        members = self._gang_members.setdefault(gkey, set())
        members.add(key)
        self._gang_first_seen.setdefault(gkey, time.monotonic())
        hint = annotation_min(pod)
        if hint is not None:
            self._gang_min_hint[gkey] = max(
                self._gang_min_hint.get(gkey, 1), hint)
        if len(members) >= self._gang_quorum(gkey):
            self.queue.add(_GANG_KEY_PREFIX + gkey)
        return True

    def _check_gang_timeouts(self) -> None:
        """Release groups that never reached quorum within their schedule
        timeout: members go back to the queue as individual pods (the
        PodGroup's phase flips to Timeout via gang/controller.py)."""
        if not self._gang_first_seen:
            return
        now = time.monotonic()
        for gkey in list(self._gang_first_seen):
            timeout = self._gang_timeout(gkey)
            if now - self._gang_first_seen[gkey] < timeout:
                continue
            members = self._gang_members.get(gkey, set())
            if len(members) >= self._gang_quorum(gkey):
                continue  # at quorum: the group key is queued, not stuck
            self.metrics.gang_timeout_inc()
            for key in sorted(members):
                ns, name = key.split("/", 1)
                pod = self.pod_informer.get(name, ns)
                if pod is not None:
                    self.events.record(
                        pod, "Warning", "FailedScheduling",
                        f"pod group {gkey} did not reach quorum within "
                        f"{timeout:.0f}s; scheduling individually")
                self.queue.add(key)
            for key in list(members):
                self._gang_forget(key)
            self._gang_first_seen.pop(gkey, None)

    def _admit_gang(self, qkey: str, fblob, iblob, pods: list[Pod],
                    live_keys: list[str], gang_cols: list[tuple[int, int]],
                    gang_groups: dict) -> None:
        """Admit a quorate group into the current batch — whole or not at
        all (the solver's revert window is a contiguous in-batch run, so a
        group is never split across batches)."""
        gkey = qkey[len(_GANG_KEY_PREFIX):]
        self.queue.done(qkey)
        members: list[tuple[str, Pod]] = []
        for key in sorted(self._gang_members.get(gkey, ())):
            ns, name = key.split("/", 1)
            pod = self.pod_informer.get(name, ns)
            if pod is None or pod.spec.node_name:
                self._gang_forget(key)  # deleted or bound since staging
                self._enqueue_time.pop(key, None)
                continue
            members.append((key, pod))
        quorum = self._gang_quorum(gkey)
        if len(members) < quorum:
            return  # wait for more members (or the timeout sweep)
        if len(members) > self.caps.batch_pods:
            # can never fit one batch: release the members individually
            # rather than stalling the group forever
            for key, pod in members:
                self._gang_forget(key)
                self.metrics.failed += 1
                self.events.record(
                    pod, "Warning", "FailedScheduling",
                    f"pod group {gkey} has {len(members)} members but "
                    f"batch capacity is {self.caps.batch_pods}; a group "
                    f"cannot be split across solver batches")
                self.queue.add(key)
            return
        if len(pods) + len(members) > self.caps.batch_pods:
            self.queue.add(qkey)  # whole group in the NEXT batch
            return
        start = len(pods)
        seq = len(gang_groups) + 1  # batch-local id; 0 = non-gang
        positions: list[int] = []
        for key, pod in members:
            try:
                self.encode_cache.encode_packed_into(fblob, iblob,
                                                     len(pods), pod)
            except CapacityError as e:
                # un-admit the group (rows past len(pods) are re-zeroed by
                # the caller's tail wipe) and release its members — the
                # oversized member can never encode
                del pods[start:]
                del live_keys[start:]
                del gang_cols[start:]
                for mkey, mpod in members:
                    self._gang_forget(mkey)
                    self.queue.add(mkey)
                self.metrics.failed += 1
                self.events.record(
                    pod, "Warning", "FailedScheduling",
                    f"pod group {gkey}: member exceeds scheduler "
                    f"capacities: {e}")
                return
            positions.append(len(pods))
            pods.append(pod)
            live_keys.append(key)
            gang_cols.append((seq, quorum))
        gang_groups[seq] = (gkey, quorum, positions)

    # ---- lifecycle ----

    @property
    def synced(self) -> bool:
        """Both core informers completed their initial list — the
        scheduler's /readyz signal."""
        return (self.node_informer._synced.is_set()
                and self.pod_informer._synced.is_set())

    @property
    def solver_degraded(self) -> bool:
        """True while any pod is quarantined for poisoning device solves —
        the /healthz degraded signal (alive and scheduling, but some work
        is parked; liveness must NOT fail, a restart wouldn't help)."""
        return bool(self._quarantined)

    async def start(self) -> None:
        self.node_informer.start()
        self.pod_informer.start()
        self.podgroup_informer.start()
        for informer in self.workload_informers:
            informer.start()
        await self.node_informer.wait_for_sync()
        await self.pod_informer.wait_for_sync()
        await self.podgroup_informer.wait_for_sync()
        # full collections follow heap growth from here on (once a process)
        install_collector_policy()

    def _flush_events(self) -> None:
        """Record buffered per-batch events — Scheduled bursts plus the
        batch path's FailedScheduling tail — coalesced into one bulk
        store write per (type, reason) group (runs when the event loop
        next idles, or synchronously from stop()). In staged mode the
        event shard builds the objects off-loop first; otherwise a
        failing store keeps the entries for the next flush (bounded
        retries) instead of silently dropping them."""
        self._event_flush_scheduled = False
        if not self._pending_events:
            return
        entries, self._pending_events = self._pending_events, []
        shard = self._event_shard
        if shard is not None and not shard._stopped:
            shard.submit(entries)
            return
        with stage("ktpu.events.install", metrics=self.metrics,
                   phase="events_async"):
            try:
                self.events.record_grouped(entries)
                self._event_flush_failures = 0
            except Exception:  # noqa: BLE001 — events must not kill the loop
                self._event_flush_failures = getattr(
                    self, "_event_flush_failures", 0) + 1
                if self._event_flush_failures <= 3:
                    log.warning("event flush failed (attempt %d); retrying "
                                "on next flush", self._event_flush_failures,
                                exc_info=True)
                    self._pending_events = entries + self._pending_events
                else:
                    log.error("event flush failed %d times; dropping %d "
                              "events", self._event_flush_failures,
                              len(entries))

    async def _drain_events_async(self) -> None:
        """Make every buffered/sharded event visible (request-response
        seam: runs only when the pipeline is drained, so tests observe
        events as soon as schedule_pending returns idle)."""
        if self._pending_events:
            self._flush_events()
        shard = self._event_shard
        if shard is not None and shard.outstanding \
                and (self._staged is None or self._staged.inflight == 0):
            await shard.drain()

    def stop(self) -> None:
        self._stopped = True
        if self._drain_handle is not None:
            self._drain_handle.cancel()
            self._drain_handle = None
        # flush parked informer events with a blocking acquire — stop()
        # may block, and state must reflect every delivered event
        while self._deferred_events:
            apply, event = self._deferred_events.popleft()
            with self._state_lock:
                try:
                    apply(event)
                except Exception:  # noqa: BLE001
                    log.exception("deferred informer event failed in stop")
        if self._staged is not None:
            self._staged.drain_sync()
        self._settle_inflight()
        if self._event_shard is not None:
            self._flush_events()  # routes the buffer through the shard
            self._event_shard.stop()
            self._event_shard.drain_sync()
        self._flush_events()
        if self._staged is not None:
            self._staged.shutdown()
        self.queue.close()
        self.node_informer.stop()
        self.pod_informer.stop()
        self.podgroup_informer.stop()
        for informer in self.workload_informers:
            informer.stop()

    def kill(self) -> None:
        """Hard abort — the chaos drill's crash simulation. Every stage
        drops its in-flight work unapplied: batches that never bound are
        simply rescheduled by the restarted instance from store truth
        (crash-only contract; stop() is the graceful drain)."""
        self._stopped = True
        if self._staged is not None:
            self._staged.kill()
        if self._event_shard is not None:
            self._event_shard.kill()
        self._loop_calls.clear()
        self._pending_events = []
        if self._drain_handle is not None:
            self._drain_handle.cancel()
            self._drain_handle = None
        self._deferred_events.clear()
        for entry in self._inflight_q:
            timer = entry[6]
            if getattr(timer, "trace_span", None) is not None:
                timer.trace_span.end("aborted")
        self._inflight_q.clear()
        self.queue.close()
        self.node_informer.stop()
        self.pod_informer.stop()
        self.podgroup_informer.stop()
        for informer in self.workload_informers:
            informer.stop()

    async def run(self) -> None:
        """Schedule until stopped (wait.Until(scheduleOne) analog). A
        scheduling pass that raises (store 429s, transport failure) is
        logged and retried with backoff — the loop itself is crash-only
        state, so surviving beats dying and losing the queue."""
        await self.start()
        run_key = "__run_loop__"
        while not self._stopped:
            try:
                await self.schedule_pending(wait=0.5)
                self.backoff.reset(run_key)
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 — the loop survives anything
                log.exception("scheduling pass failed; backing off")
                await asyncio.sleep(self.backoff.next_delay(run_key))

    # ---- one batch ----

    def _acquire_blobs(self):
        """Packed transport blob pair from the free-list (allocates when
        empty — in-flight gating bounds steady-state allocation to
        depth+2 pairs; a leak on an exception path just reallocates)."""
        try:
            return self._blob_pool.popleft()
        except IndexError:
            from kubernetes_tpu.state.pod_batch import _layout

            _lay, f_width, i_width = _layout(self.caps)
            p = self.caps.batch_pods
            return (np.zeros((p, f_width), np.float32),
                    np.zeros((p, i_width), np.int32))

    def _release_blobs(self, blobs) -> None:
        """Return a blob pair, or its row views, once its batch's ledger
        commit has read the accounting rows (callable from the commit
        stage thread — deque append is atomic)."""
        if len(self._blob_pool) < self.pipeline_depth + 2:
            self._blob_pool.append(tuple(
                b if b.base is None else b.base for b in blobs))

    def _next_blobs(self):
        """Back-compat acquire-without-release (tests' scratch blobs):
        the pair stays pooled, so sequential callers may see the same
        arrays."""
        blobs = self._acquire_blobs()
        self._release_blobs(blobs)
        return blobs

    async def schedule_pending(self, wait: float | None = None) -> int:
        """Pop up to a batch of pending pods, schedule, bind. Returns the
        number of pods scheduled (in pipeline mode: settled this call)."""
        self._check_gang_timeouts()
        if len(self.nominated):
            self.nominated.expire(time.monotonic())
        settled = 0
        if self._staged is not None:
            self._loop_calls.bind(asyncio.get_running_loop())
            self._loop_calls.drain()
            settled = self._take_staged_settled()
            if self._staged_failures:
                settled += await self._drain_staged_failures()
        inflight = self._inflight_q or (
            self._staged is not None and self._staged.inflight)
        effective_wait = 0 if inflight else wait
        with stage("ktpu.queue.get_batch"):
            keys = await self.queue.get_batch(self.caps.batch_pods,
                                              wait=effective_wait)
        if not keys:
            settled += await self._asettle_inflight()
            if self._staged is not None and self._staged.inflight:
                # yield so marshalled apply closures make progress, then
                # collect whatever settled meanwhile
                await asyncio.sleep(0.001)
                self._loop_calls.drain()
                settled += self._take_staged_settled()
            return settled
        try:
            return settled + await self._schedule_batch(keys)
        except asyncio.CancelledError:
            raise
        except Exception:
            # level-triggered hardening: a popped key must never be lost to
            # an exception — the informer won't re-announce an unchanged
            # pending pod, so re-add every key before propagating (done()
            # first: add() on a processing key only marks it dirty)
            self._requeue_keys(keys)
            raise

    def _encode_batch(self, keys: list[str]):
        """Encode the popped keys into a packed blob pair; returns (fblob,
        iblob, pods, live_keys, gang_groups). Deleted or already-bound
        pods drop out; a pod over capacity fails on its own."""
        fblob, iblob = self._acquire_blobs()
        pods: list[Pod] = []
        live_keys: list[str] = []
        # per-row (gang_id, gang_min) parallel to pods, (0, 0) = non-gang;
        # gang_groups: batch-local id -> (group key, quorum, row positions)
        gang_cols: list[tuple[int, int]] = []
        gang_groups: dict[int, tuple[str, int, list[int]]] = {}
        # the lock serializes encode-side interning against the staged
        # dispatch thread's flush (which applies pending refreshes)
        with self._state_lock:
            epoch_before = self.statedb.table.pod_row_epoch
            for key in keys:
                if key.startswith(_GANG_KEY_PREFIX):
                    self._admit_gang(key, fblob, iblob, pods, live_keys,
                                     gang_cols, gang_groups)
                    continue
                ns, name = key.split("/", 1)
                pod = self.pod_informer.get(name, ns)
                if pod is None or pod.spec.node_name:
                    self._enqueue_time.pop(key, None)
                    self.queue.done(key)  # deleted or already bound: drop
                    continue
                try:
                    self.encode_cache.encode_packed_into(fblob, iblob,
                                                         len(pods), pod)
                except CapacityError as e:
                    # per-pod failure must not wedge the batch
                    # (MakeDefaultErrorFunc parity, factory.go:897)
                    self._fail(key, pod,
                               f"pod exceeds scheduler capacities: {e}")
                    continue
                pods.append(pod)
                live_keys.append(key)
                gang_cols.append((0, 0))
            if pods and self.statedb.table.pod_row_epoch != epoch_before:
                # a later pod in this batch interned new podsel/avoid
                # entries: earlier pods' match/carry rows (encoded,
                # possibly cached, against the smaller universe) miss
                # them — re-encode every row against the final universes
                # (epoch is in the cache key, so stale cached rows cannot
                # be served)
                for i, pod in enumerate(pods):
                    self.encode_cache.encode_packed_into(fblob, iblob, i,
                                                         pod)
        if gang_groups:
            # gang columns go in AFTER encoding: cached packed rows carry
            # zeros (a batch-local group id cannot be cached), and the
            # epoch re-encode above would have reset earlier writes
            from kubernetes_tpu.state.pod_batch import blob_col

            gid_col = blob_col(fblob, iblob, "gang_id", self.caps)
            gmin_col = blob_col(fblob, iblob, "gang_min", self.caps)
            for i, (gid, gmin) in enumerate(gang_cols):
                if gid:
                    gid_col[i] = gid
                    gmin_col[i] = gmin
        return fblob, iblob, pods, live_keys, gang_groups

    async def _schedule_batch(self, keys: list[str]) -> int:
        self._batch_seq += 1
        seq = self._batch_seq
        batch_span = None
        # host-phase costs accrue THREAD CPU time (see _apply_batch): wall
        # time on the loop includes GIL waits on concurrent stage threads
        with stage("ktpu.encode", batch=seq, metrics=self.metrics,
                   phase="encode", cpu=True, tid="encode") as st:
            fblob, iblob, pods, live_keys, gang_groups = \
                self._encode_batch(keys)
            if pods and not self._extenders:
                # the batch span: adopted from the first pod that carries
                # a sampled trace.ktpu.io/context annotation (stitching
                # the client/apiserver spans), else a rate-sampled root.
                # Explicit handoff — the span rides the queue item across
                # stage threads and ends at commit.
                batch_span = st.work_span = self._begin_batch_span(pods)
                st.attrs = {"pods": len(pods)}
        if not pods:
            self._release_blobs((fblob, iblob))
            return await self._asettle_inflight()
        self.metrics.phase_pods += len(pods)

        if self._extenders:
            try:
                return await self._schedule_with_extenders(pods, live_keys,
                                                           fblob, iblob)
            finally:
                self._release_blobs((fblob, iblob))
        fblob, iblob = self._solve_blobs(fblob, iblob, len(pods))
        if self._staged is not None and not self._stopped:
            return await self._schedule_batch_staged(
                pods, live_keys, fblob, iblob, gang_groups, batch_span, seq)

        timer = StepTimer(f"scheduling batch of {len(pods)}",
                          trace_span=batch_span)
        from kubernetes_tpu.state.pod_batch import packed_batch_flags

        flags = packed_batch_flags(fblob, iblob, len(pods),
                                   self.statedb.table, self.caps)
        if self.explain:
            flags = dataclasses.replace(flags, explain=True)
        schedule_fn = self._get_schedule_fn(flags)
        victims, vslots = self._build_victims(flags)
        settled = 0
        if self._inflight_q and (not self._pipeline
                                 or self.statedb.ledger_dirty):
            # a dirty flush would re-upload host truth that misses the
            # in-flight batches' charges: settle them first
            settled += await self._asettle_inflight()
        with stage("ktpu.dispatch.flush", batch=seq, metrics=self.metrics,
                   phase="flush", cpu=True):
            state = self.statedb.flush()
        timer.step("encode + flush")

        t0 = time.monotonic()
        failed = None
        with stage("ktpu.dispatch.solve", batch=seq, metrics=self.metrics,
                   phase="dispatch"):
            try:
                result = await self._dispatch_guarded(
                    schedule_fn, state, fblob, iblob, victims, live_keys)
            except _SolveFailed as e:
                failed = e
            else:
                self._rr = result.rr_end
                try:
                    # start the device->host copy now; by settle time
                    # (after the next dispatch) it is usually already on
                    # the host
                    result.assignments.copy_to_host_async()
                except AttributeError:
                    pass
        if failed is not None:
            self._release_blobs((fblob, iblob))
            batch_span.end("error")
            return settled + await self._recover_solve_failure(
                pods, live_keys, gang_groups, failed)
        timer.step("device dispatch")
        # start the blocking host fetch NOW in a worker thread: by settle
        # time (up to pipeline_depth dispatches later) the round trip has
        # already been paid in the background — profiling showed the event
        # loop idling ~0.3s per batch in select() when the fetch thread
        # only started at settle
        fetch = asyncio.get_running_loop().create_task(
            asyncio.to_thread(np.asarray, result.assignments))
        # retrieve (and discard) failures so an entry popped by the sync
        # stop() path can't leave an un-retrieved task exception behind;
        # the settle path handles the error itself via a fresh fetch
        fetch.add_done_callback(
            lambda t: None if t.cancelled() else t.exception())
        # pipeline only under sustained load (more pods already queued →
        # another call is imminent); a drained queue settles synchronously
        # so small/interactive workloads keep request-response semantics
        if self._pipeline and len(self.queue) > 0:
            # adopt the (lazy, device-side) output ledger now so the next
            # batch chains on it without a synchronization; settle the
            # oldest batches while this one computes
            self.statedb.adopt_result(result)
            self._inflight_q.append((result, pods, live_keys, (fblob, iblob),
                                     flags, t0, timer, True, fetch,
                                     gang_groups, vslots))
            while len(self._inflight_q) > self.pipeline_depth:
                settled += await self._asettle_one()
            return settled
        self._inflight_q.append((result, pods, live_keys, (fblob, iblob),
                                 flags, t0, timer, False, fetch, gang_groups,
                                 vslots))
        return settled + await self._asettle_inflight()

    # ---- staged stage-per-thread path (scheduler/pipeline.py) ----

    def _begin_batch_span(self, pods: list[Pod]):
        """Begin the batch's root/joined span. Explicit handoff (the span
        crosses the dispatch/settle/commit threads on the queue item), so
        ownership of end() is the commit/error/drop path's — tracked in
        the tracer's open-span table meanwhile."""
        parent = None
        for pod in pods:
            ctx = pod_trace_context(pod)
            if ctx is not None:
                parent = ctx
                break
        span = TRACER.begin_span("schedule.batch", parent=parent,
                                 tid="scheduler")
        span.set_attr("pods", len(pods))
        return span

    async def _schedule_batch_staged(self, pods: list[Pod],
                                     live_keys: list[str], fblob, iblob,
                                     gang_groups: dict,
                                     batch_span=None, seq=None) -> int:
        """Hand one encoded batch to the staged pipeline: flush + solve +
        readback + ledger commit run in stage threads while this loop
        encodes the next batch (unconditional prefetch — the overlap the
        single-loop path only got under queue pressure). With the queue
        drained the call degrades to request-response: it awaits the
        pipeline so callers observe their pods bound on return."""
        from kubernetes_tpu.state.pod_batch import packed_batch_flags

        from kubernetes_tpu.scheduler.pipeline import _BatchWork

        flags = packed_batch_flags(fblob, iblob, len(pods),
                                   self.statedb.table, self.caps)
        if self.explain:
            flags = dataclasses.replace(flags, explain=True)
        schedule_fn = self._get_schedule_fn(flags)
        with self._state_lock:
            victims, vslots = self._build_victims(flags)
        work = _BatchWork(pods, live_keys, (fblob, iblob), flags,
                          schedule_fn, victims, vslots, gang_groups, seq)
        work.span = batch_span
        self._loop_calls.bind(asyncio.get_running_loop())
        await self._staged.wait_capacity(seq)
        self._staged.submit(work)
        settled = self._take_staged_settled()
        if len(self.queue) == 0:
            await self._staged.drain()
            settled += self._take_staged_settled()
            if self._staged_failures:
                settled += await self._drain_staged_failures()
            await self._drain_events_async()
        return settled

    def _take_staged_settled(self) -> int:
        n, self._staged_settled = self._staged_settled, 0
        return n

    def _requeue_keys(self, keys: list[str]) -> None:
        """Level-triggered hardening for a batch whose apply failed: no
        popped key may be lost (done() first — add() on a processing key
        only marks it dirty)."""
        for key in keys:
            self.queue.done(key)
            self.queue.add(key)

    def _on_staged_solve_failure(self, work) -> None:
        """Loop-side landing for a batch whose solve failed twice in the
        dispatch stage: park the payload; the next schedule_pending
        drains the pipeline and runs the bisect/quarantine/serial-host
        recovery ladder on it."""
        self.statedb.mark_ledger_dirty()
        self._release_blobs(work.blobs)
        if work.span is not None:
            work.span.end("error")
        self._staged_failures.append(
            (work.pods, work.live_keys, work.gang_groups, work.error))

    async def _drain_staged_failures(self) -> int:
        await self._staged.drain()
        settled = self._take_staged_settled()
        payloads, self._staged_failures = self._staged_failures, []
        for pods, live_keys, gang_groups, error in payloads:
            settled += await self._recover_solve_failure(
                pods, live_keys, gang_groups, error)
        return settled

    async def _schedule_with_extenders(self, pods: list[Pod],
                                       live_keys: list[str],
                                       fblob, iblob) -> int:
        """Serial per-pod scheduling with extender composition: device
        evaluation (the full policy's predicates+priorities, the same
        _pod_eval the batch solver scans) -> each extender's Filter veto ->
        Prioritize scores added to the device's weighted sum ->
        round-robin selectHost -> bind. Later pods see earlier assumptions
        (scheduleOne's serial contract) because the ledger re-flushes per
        pod. Extender errors fail the pod's attempt and requeue with
        backoff (generic_scheduler.go:211-228)."""
        import jax

        from kubernetes_tpu.extender.client import ExtenderError
        from kubernetes_tpu.ops.solver import evaluate_pod
        from kubernetes_tpu.state.pod_batch import unpack_batch

        if self._pod_eval_fn is None:
            caps, policy, prows = self.caps, self.policy, self._prows

            def _eval(state, fb, ib, i):
                batch = unpack_batch(fb, ib, caps)
                row = jax.tree.map(lambda a: a[i], batch)
                return evaluate_pod(state, row, policy, caps=caps,
                                    prows=prows)

            self._pod_eval_fn = jax.jit(_eval)
        scheduled = 0
        full_mode = any(not e.config.node_cache_capable
                        for e in self._extenders)
        for i, (key, pod) in enumerate(zip(live_keys, pods)):
            # per-pod flush: pod k+1 must see pod k's assumption
            state = self.statedb.flush()
            feasible, score = self._pod_eval_fn(state, fblob, iblob, i)
            feasible = np.asarray(feasible)
            score = np.asarray(score)
            name_of = self.statedb.table.name_of
            rows: dict[str, int] = {}
            names: list[str] = []
            for row in np.flatnonzero(feasible):
                node_name = name_of[int(row)]
                if node_name is not None:
                    names.append(node_name)
                    rows[node_name] = int(row)
            if not names:
                # nothing feasible device-side: FitError before any
                # extender round trip (findNodesThatFit returns early)
                self._fail(key, pod, "no nodes available to schedule pods")
                continue
            nodes_by_name = None
            if full_mode:
                nodes_by_name = {n: obj for n in names
                                 if (obj := self.node_informer.get(n))
                                 is not None}
            try:
                ext_scores: dict[str, float] = {}
                for ext in self._extenders:
                    names = (await asyncio.to_thread(
                        ext.filter, pod, names, nodes_by_name))[0]
                    if not names:
                        break
                if names:
                    for ext in self._extenders:
                        for node_name, sc in (await asyncio.to_thread(
                                ext.prioritize, pod, names,
                                nodes_by_name)).items():
                            ext_scores[node_name] = \
                                ext_scores.get(node_name, 0.0) + sc
            except ExtenderError as e:
                self._fail(key, pod, f"extender error: {e}")
                continue
            names = [n for n in names if n in rows]
            if not names:
                self._fail(key, pod, "no nodes available to schedule pods")
                continue
            totals = [(float(score[rows[n]]) + ext_scores.get(n, 0.0), n)
                      for n in names]
            best = max(total for total, _ in totals)
            ties = [n for total, n in totals if total == best]
            choice = ties[int(self._rr) % len(ties)]
            self._rr = np.uint32(int(self._rr) + 1)
            try:
                self.store.bind(Binding(pod_name=pod.metadata.name,
                                        namespace=pod.metadata.namespace,
                                        target_node=choice))
            except (Conflict, NotFound, TooManyRequests) as e:
                self.metrics.binding_errors += 1
                self._fail(key, pod, f"binding rejected: {e}")
                continue
            self._assumed.add(key)
            self.statedb.add_pod(pod, choice)
            scheduled += 1
            self.queue.done(key)
            self.backoff.reset(key)
            enqueued = self._enqueue_time.pop(key, None)
            if enqueued is not None:
                self.metrics.e2e_latency.append(
                    time.monotonic() - enqueued)
            self.events.record(pod, "Normal", "Scheduled",
                               f"Successfully assigned {key} to {choice}")
        self.metrics.scheduled += scheduled
        self.metrics.batches += 1
        return scheduled

    # ---- solve-failure hardening ----
    #
    # The degradation ladder for a failing device solve:
    #   1. retry the dispatch once (transient transport/compiler faults);
    #   2. on a second failure, settle the pipeline, then BISECT the batch
    #      with probe solves to isolate pods whose presence fails the
    #      solve — those are quarantined (event + long unschedulable
    #      requeue);
    #   3. the healthy remainder degrades to the serial HOST placement
    #      path (capacity-only greedy fit over the StateDB ledger) so the
    #      cluster keeps making progress while the device path is down;
    #   4. if bisection finds no poison (the fault cleared), everything
    #      requeues for a normal batch.
    # All of it is host-side: the compiled solver program is untouched
    # (the HLO pin test in tests/test_faults.py proves bit-identity).

    async def _call_solve(self, schedule_fn, state, fblob, iblob, victims,
                          live_keys: list[str]):
        """One dispatch. With solve_timeout_s set, dispatch AND readback
        complete inside the deadline in a worker thread (a wedged device
        otherwise hangs the readback forever); the event loop keeps
        serving informers during the solve either way."""
        if self.solve_timeout_s:
            hook = self.solve_fault_hook

            def call():
                if hook is not None:
                    hook(list(live_keys))
                result = schedule_fn(state, fblob, iblob, self._rr, victims)
                np.asarray(result.assignments)  # force completion in-deadline
                return result

            # NOTE: on timeout the worker thread is abandoned, not killed —
            # a truly wedged dispatch leaks one thread (the watchdog's cost)
            return await asyncio.wait_for(asyncio.to_thread(call),
                                          self.solve_timeout_s)
        if self.solve_fault_hook is not None:
            self.solve_fault_hook(list(live_keys))
        # dispatch in a worker thread: tracing/compiling a new BatchFlags
        # variant (and the whole solve on CPU backends) is synchronous in
        # the runtime and would hold the event loop for the duration —
        # informers/heartbeats stall, which the LoopStallWatchdog flags.
        # The device result stays lazy; readback still overlaps via the
        # fetch task downstream.
        return await asyncio.to_thread(
            schedule_fn, state, fblob, iblob, self._rr, victims)

    async def _dispatch_guarded(self, schedule_fn, state, fblob, iblob,
                                victims, live_keys: list[str]):
        """Dispatch with one retry; raises _SolveFailed after the second
        failure (scheduleOne survives algorithm errors through
        MakeDefaultErrorFunc — the batched analog must survive a failing
        or wedged device solve)."""
        last: Exception | None = None
        for attempt in (1, 2):
            try:
                return await self._call_solve(schedule_fn, state, fblob,
                                              iblob, victims, live_keys)
            except asyncio.CancelledError:
                raise
            except Exception as e:  # noqa: BLE001 — incl. TimeoutError
                last = e
                self.metrics.solve_failure_inc()
                if attempt == 1:
                    self.metrics.solve_retry_inc()
                    log.warning("device solve failed (attempt 1/2): %s; "
                                "retrying", e)
        raise _SolveFailed(str(last)) from last

    async def _recover_solve_failure(self, pods: list[Pod],
                                     live_keys: list[str],
                                     gang_groups: dict,
                                     error: Exception) -> int:
        """Persistent solve failure for one batch: drain the pipeline,
        requeue gang groups whole (all-or-nothing survives degradation),
        bisect the rest for poison pods, and place the healthy remainder
        via the serial host path."""
        log.error("device solve failed after retry for a %d-pod batch "
                  "(%s); bisecting", len(pods), error)
        settled = await self._asettle_inflight()
        # the failed dispatch may have half-consumed device state: force
        # the next flush to re-upload host truth
        self.statedb.mark_ledger_dirty()
        gang_rows: set[int] = set()
        for gkey, _quorum, positions in gang_groups.values():
            gang_rows.update(positions)
            # a gang is never split or serial-bound: the whole group
            # requeues with backoff and re-enters a future batch
            qkey = _GANG_KEY_PREFIX + gkey
            self.queue.add_after(qkey, self.backoff.next_delay(qkey))
        items = [(k, p) for i, (k, p) in enumerate(zip(live_keys, pods))
                 if i not in gang_rows]
        poison = await self._bisect_poison(items)
        if not poison:
            # probes pass now: the failure was transient after all —
            # requeue everything for a normal batched retry
            for key, _pod in items:
                self.queue.done(key)
                self.queue.add_after(key, self.backoff.next_delay(key))
            return settled
        poison_keys = {k for k, _ in poison}
        for key, pod in poison:
            self._quarantine(key, pod)
        survivors = [(k, p) for k, p in items if k not in poison_keys]
        return settled + self._schedule_serial_host(survivors)

    async def _bisect_poison(
            self, items: list[tuple[str, Pod]]) -> list[tuple[str, Pod]]:
        """Pods whose presence makes the solve fail, found by recursive
        probe solves — O(k log n) probes for k poison pods."""
        if not items:
            return []
        if await self._probe_solve(items):
            return []
        if len(items) == 1:
            return list(items)
        mid = len(items) // 2
        return (await self._bisect_poison(items[:mid])
                + await self._bisect_poison(items[mid:]))

    async def _probe_solve(self, items: list[tuple[str, Pod]]) -> bool:
        """True when a device solve over exactly these pods completes.
        Reuses the compiled variant cache; the probe's output ledger is
        never adopted (the ledger was already marked dirty), so results
        are discarded without side effects."""
        from kubernetes_tpu.state.pod_batch import packed_batch_flags

        blobs = self._acquire_blobs()
        try:
            keys = [k for k, _ in items]
            fblob, iblob = blobs
            with self._state_lock:
                for i, (_key, pod) in enumerate(items):
                    self.encode_cache.encode_packed_into(fblob, iblob, i, pod)
                if len(items) < self.caps.batch_pods:
                    fblob[len(items):] = 0.0
                    iblob[len(items):] = 0
                flags = packed_batch_flags(fblob, iblob, len(items),
                                           self.statedb.table, self.caps)
                schedule_fn = self._get_schedule_fn(flags)
                state = self.statedb.flush()
            result = await self._call_solve(schedule_fn, state, fblob,
                                            iblob, None, keys)
            await asyncio.to_thread(np.asarray, result.assignments)
            self.statedb.mark_ledger_dirty()  # never adopt probe output
            return True
        except asyncio.CancelledError:
            raise
        except Exception:  # noqa: BLE001 — a failed probe is an answer
            self.metrics.solve_failure_inc()
            return False
        finally:
            self._release_blobs(blobs)

    def _quarantine(self, key: str, pod: Pod) -> None:
        """Poison pod: surface the verdict as an event and park it with a
        long unschedulable requeue so one bad pod cannot re-poison every
        batch; a later delete/bind clears the quarantine."""
        self.metrics.quarantine_inc()
        self._quarantined.add(key)
        self.metrics.failed += 1
        self.queue.done(key)
        self.queue.add_after(key, self.quarantine_backoff_s)
        log.error("pod %s quarantined: device solve fails whenever it is "
                  "in the batch", key)
        self.events.record(
            pod, "Warning", "FailedScheduling",
            f"pod quarantined: device solve fails whenever this pod is in "
            f"the batch; retrying in {self.quarantine_backoff_s:.0f}s")

    def _schedule_serial_host(self, items: list[tuple[str, Pod]]) -> int:
        """Degraded placement: greedy first-fit over the StateDB host
        ledger (capacity predicate only — no device program involved).
        Keeps the healthy remainder of a poisoned batch moving while the
        device path is down; pods that don't fit requeue with normal
        backoff and re-enter the full solver once it recovers."""
        if not items:
            return 0
        from kubernetes_tpu.state.cluster_state import pod_requests

        host = self.statedb.host
        name_of = self.statedb.table.name_of
        scheduled = 0
        for key, pod in items:
            req = pod_requests(pod)
            free = host.allocatable - host.requested
            fits = np.flatnonzero(host.valid & np.all(free >= req, axis=1))
            choice = None
            n = len(fits)
            start = int(self._rr) % n if n else 0
            for off in range(n):
                row = int(fits[(start + off) % n])
                node_name = name_of[row]
                if node_name is not None:
                    choice = node_name
                    break
            self._rr = np.uint32(int(self._rr) + 1)
            if choice is None:
                self._fail(key, pod, "no nodes available to schedule pods "
                                     "(degraded host path)")
                continue
            try:
                self.store.bind(Binding(pod_name=pod.metadata.name,
                                        namespace=pod.metadata.namespace,
                                        target_node=choice))
            except (Conflict, NotFound, TooManyRequests) as e:
                self.metrics.binding_errors += 1
                self._fail(key, pod, f"binding rejected: {e}")
                continue
            self._assumed.add(key)
            self.statedb.add_pod(pod, choice)
            self.metrics.serial_fallback_inc()
            scheduled += 1
            self.queue.done(key)
            self.backoff.reset(key)
            enqueued = self._enqueue_time.pop(key, None)
            if enqueued is not None:
                self.metrics.e2e_latency.append(time.monotonic() - enqueued)
            self.events.record(
                pod, "Normal", "Scheduled",
                f"Successfully assigned {key} to {choice} "
                f"(degraded host path)")
        self.metrics.scheduled += scheduled
        self.metrics.batches += 1
        return scheduled

    def _settle_inflight(self) -> int:
        """Settle every in-flight batch, oldest first (synchronous —
        the stop() path)."""
        settled = 0
        while self._inflight_q:
            settled += self._settle_one()
        return settled

    async def _asettle_inflight(self) -> int:
        settled = 0
        while self._inflight_q:
            settled += await self._asettle_one()
        # fully drained: make deferred events visible before returning, so
        # non-pipelined callers keep request-response semantics (under
        # sustained pipelined load the call_soon flush runs instead)
        await self._drain_events_async()
        return settled

    async def _asettle_one(self) -> int:
        """Async settle: the readback was started in a worker thread AT
        DISPATCH, so by now (up to pipeline_depth dispatches later) the
        transport round trip has usually already completed — this await
        is a cache hit in steady state; the event loop keeps running
        informers / encoding during any residual wait."""
        if not self._inflight_q:
            return 0
        entry = self._inflight_q[0]
        t0 = time.monotonic()
        try:
            assignments = await entry[8]
        except asyncio.CancelledError:
            if not entry[8].cancelled():
                raise  # WE were cancelled, not the fetch task
            assignments = None  # fetch cancelled: re-read below
        except Exception:  # noqa: BLE001 — transient transport failure
            # a poisoned prefetch must not wedge the queue forever: the
            # old per-settle fetch retried fresh every attempt; do the same
            log.warning("prefetch failed; re-reading assignments",
                        exc_info=True)
            assignments = None
        if assignments is None:
            assignments = await asyncio.to_thread(
                np.asarray, entry[0].assignments)
        waited = time.monotonic() - t0
        self.metrics.add_phase("settle_wait", waited)
        if not self._inflight_q or self._inflight_q[0] is not entry:
            return 0  # settled by stop() while we waited
        return self._settle_one(assignments, waited=waited)

    def _settle_one(self, assignments: np.ndarray | None = None,
                    waited: float | None = None) -> int:
        """Read back the oldest in-flight solve, bind its assignments, and
        commit the ledger (the synchronous tail of schedule_pending)."""
        if not self._inflight_q:
            return 0
        (result, pods, live_keys, blobs, flags, t0, timer,
         adopted, fetch, gang_groups, vslots) = self._inflight_q.popleft()
        if assignments is None and fetch.done() \
                and not fetch.cancelled() and fetch.exception() is None:
            assignments = fetch.result()  # prefetch already landed
        # NOTE: never cancel() an unfinished fetch here — a concurrently
        # suspended _asettle_one is awaiting it, and cancellation would
        # propagate into that coroutine; the duplicate synchronous read
        # below is harmless
        t_wait = time.monotonic()
        if assignments is None:
            assignments = np.asarray(result.assignments)
            record_readback(assignments)
            self.metrics.add_phase("settle_wait",
                                   time.monotonic() - t_wait)
        # synchronous batches observe the true dispatch-to-ready span; for a
        # pipelined batch only the readback wait is observable (the full
        # span would count the successor's host work as algorithm time) —
        # when the readback ran in _asettle_one's thread, `waited` carries
        # that span here
        if adopted:
            residual = waited if waited is not None \
                else time.monotonic() - t_wait
        else:
            residual = time.monotonic() - t0
        self.metrics.algorithm_latency.append(residual)
        timer.step("device solve")

        rows = assignments[:len(pods)].tolist()
        # preemption verdicts ride the same result; resolve them only when
        # this batch actually carried a victim table
        preempt_rows = victim_counts = None
        if vslots is not None:
            preempt = np.asarray(result.preempt_node)
            victims = np.asarray(result.victim_count)
            record_readback(preempt, victims)
            preempt_rows = preempt[:len(pods)].tolist()
            victim_counts = victims[:len(pods)].tolist()
        explain_rows = None
        if flags.explain and result.explain_counts is not None:
            explain = np.asarray(result.explain_counts)
            record_readback(explain)
            explain_rows = explain[:len(pods)].tolist()
        with stage("ktpu.apply", metrics=self.metrics, phase="bind",
                   cpu=True):
            scheduled, committed, any_rejected = self._apply_batch(
                result, pods, live_keys, blobs, flags, rows, preempt_rows,
                victim_counts, gang_groups, vslots, timer,
                explain_rows=explain_rows, span=timer.trace_span)
        with stage("ktpu.commit.ledger", metrics=self.metrics,
                   phase="commit", cpu=True):
            self._commit_ledger(result, blobs[0], committed, any_rejected,
                                flags, adopted)
            self._release_blobs(blobs)
        timer.step("bind + commit")
        timer.log_if_long(0.1 * len(pods))
        return scheduled

    def _apply_batch(self, result, pods: list[Pod], live_keys: list[str],
                     blobs, flags, rows: list[int],
                     preempt_rows: list[int] | None,
                     victim_counts: list[int] | None, gang_groups: dict,
                     vslots, timer=None, explain_rows=None,
                     span=None) -> tuple[int, list, bool]:
        """Act on one solved batch's host-side verdicts: settle gangs,
        partition assigned rows from rejections, bulk-bind through the
        store, and buffer the per-pod events. Runs ON the event loop (in
        staged mode the commit thread marshals it here via LoopCalls) so
        every store write stays loop-serialized. Returns (scheduled,
        committed, any_rejected) for _commit_ledger. Callers time it as
        the `bind` phase in THREAD CPU time: with stage threads
        overlapping the loop, wall time includes GIL waits on a concurrent
        solve's trace/compile — CPU time is the stable drift signal the
        phase gates watch (wall == cpu when uncontended)."""
        scheduled = 0
        committed: list[tuple[Pod, str, int]] = []
        any_rejected = False
        t_bind = time.monotonic()
        # partition the batch: assigned rows to bind vs solver rejections
        name_of = self.statedb.table.name_of
        event_entries: list[tuple[Pod, str, str, str]] = []
        taken_victims: set[str] = set()
        # settle gangs at the GROUP level first: a reverted group requeues
        # as one unit with group backoff (its members' -1 rows are the
        # solver's revert, not individual rejections); a placed group's
        # below-quorum stragglers fall through to individual failure
        gang_handled: set[str] = set()
        for _seq, (gkey, quorum, positions) in gang_groups.items():
            placed = sum(1 for p in positions if rows[p] >= 0)
            if placed >= quorum:
                self.metrics.gang_placed_inc()
                qkey = _GANG_KEY_PREFIX + gkey
                self.backoff.reset(qkey)
                self._gang_first_seen.pop(gkey, None)
                for p in positions:
                    if rows[p] < 0:
                        # straggler past quorum: the gang is satisfied, the
                        # leftover member schedules (and fails) on its own
                        self._gang_forget(live_keys[p])
                continue
            self.metrics.gang_reverted_inc()
            qkey = _GANG_KEY_PREFIX + gkey
            for p in positions:
                gang_handled.add(live_keys[p])
                self.metrics.failed += 1
                event_entries.append(
                    (pods[p], "Warning", "FailedScheduling",
                     f"pod group {gkey} placed {placed}/{quorum} members; "
                     f"group reverted (all-or-nothing)"))
            # gang preemption composes all-or-nothing: the solver emits
            # verdicts only when EVERY unplaced member found a victim set,
            # so either the whole group's victims are evicted or none are
            if preempt_rows is not None:
                unplaced = [p for p in positions if rows[p] < 0]
                if unplaced and all(preempt_rows[p] >= 0 for p in unplaced):
                    for p in unplaced:
                        if not self._preempt_one(
                                live_keys[p], pods[p], preempt_rows[p],
                                victim_counts[p], vslots, taken_victims):
                            break
            self.queue.add_after(qkey, self.backoff.next_delay(qkey))
        to_bind: list[tuple[int, str, Pod, str]] = []
        now_mono = time.monotonic()
        holds_active = len(self.nominated) > 0
        total_nodes = (sum(1 for n in name_of if n is not None)
                       if explain_rows is not None else 0)
        for i, (key, pod) in enumerate(zip(live_keys, pods)):
            row = rows[i]
            if row < 0:
                if key in gang_handled:
                    continue  # group-level requeue already recorded
                if preempt_rows is not None and preempt_rows[i] >= 0 \
                        and self._preempt_one(key, pod, preempt_rows[i],
                                              victim_counts[i], vslots,
                                              taken_victims):
                    # nominated + victims evicted: retry once they vanish
                    self.queue.done(key)
                    self.queue.add_after(key, 0.05)
                    continue
                message = "no nodes available to schedule pods"
                if explain_rows is not None:
                    rendered = render_unschedulable(explain_rows[i],
                                                    total_nodes)
                    if rendered is not None:
                        message = rendered
                self._fail_batch(key, pod, message, event_entries)
                continue
            node_name = name_of[row]
            if node_name is None:
                any_rejected = True  # the vanished node left a ledger charge
                self._fail_batch(key, pod, "assigned node vanished",
                                 event_entries)
                continue
            if holds_active and self.nominated.blocks(
                    node_name, int(pod.spec.priority), now_mono):
                # the solver saw the victims' freed room, but it is being
                # held for a nominated higher-priority preemptor — backing
                # off here is what makes the eviction actually pay off
                any_rejected = True
                self._fail_batch(key, pod,
                                 f"node {node_name} capacity is held for a "
                                 f"nominated higher-priority pod",
                                 event_entries)
                continue
            to_bind.append((i, key, pod, node_name))

        # one bulk store transaction for the whole batch's bindings (the
        # serial per-pod path was the measured e2e wall, PERF.md); stores
        # without the bulk verb (RemoteStore) fall back per pod
        bind_many = getattr(self.store, "bind_many", None)
        if to_bind and bind_many is not None:
            try:
                errs = bind_many(
                    [Binding(pod_name=pod.metadata.name,
                             namespace=pod.metadata.namespace,
                             target_node=node_name)
                     for _i, _k, pod, node_name in to_bind])[1]
            except Exception as e:  # noqa: BLE001 — e.g. a store 429
                # the whole transaction failed before any per-pod verdicts:
                # every pod takes the bind-rejected path (requeue + event)
                log.warning("bulk bind failed: %s", e)
                errs = [e] * len(to_bind)
        elif to_bind:
            errs = []
            for _i, _key, pod, node_name in to_bind:
                try:
                    self.store.bind(Binding(pod_name=pod.metadata.name,
                                            namespace=pod.metadata.namespace,
                                            target_node=node_name))
                    errs.append(None)
                except (Conflict, NotFound, TooManyRequests) as e:
                    errs.append(e)
        else:
            errs = []

        now = time.monotonic()
        assumed_add = self._assumed.add
        queue_done = self.queue.done
        backoff_reset = self.backoff.reset
        enq_pop = self._enqueue_time.pop
        e2e_append = self.metrics.e2e_latency.append
        for (i, key, pod, node_name), err in zip(to_bind, errs):
            if gang_groups:
                # settled either way: eagerly unstage (the watch event
                # confirming the bind would do it too, but later)
                self._gang_forget(key)
            if err is not None:
                # the solver's ledger charged this pod; drop that ledger below
                any_rejected = True
                self.metrics.binding_errors += 1
                self._fail_batch(key, pod, f"binding rejected: {err}",
                                 event_entries)
                continue
            assumed_add(key)
            committed.append((pod, node_name, i))
            scheduled += 1
            queue_done(key)
            backoff_reset(key)
            self.nominated.release(key)
            enq = enq_pop(key, None)
            if enq is not None:
                e2e_append(now - enq)
            event_entries.append(
                (pod, "Normal", "Scheduled",
                 f"Successfully assigned {key} to {node_name}"))
        if span is not None and span.sampled and committed:
            # sampled batch: pods created without a client traceparent get
            # the batch's context stamped at bind time, so the kubelet's
            # sync span still joins the stitched trace (1% of batches —
            # off the headline path)
            self._stamp_trace_annotations(committed, span)
        if event_entries:
            self._pending_events.extend(event_entries)
            if not self._event_flush_scheduled:
                try:
                    asyncio.get_running_loop().call_soon(self._flush_events)
                    self._event_flush_scheduled = True
                except RuntimeError:   # sync stop() path: no running loop
                    self._flush_events()
        dt_bind = time.monotonic() - t_bind
        if scheduled:
            # per-pod binding latency (the batch amortizes one write loop)
            self.metrics.binding_latency.append(dt_bind / scheduled)
        self.metrics.scheduled += scheduled
        self.metrics.batches += 1
        if self.metrics.batches % 128 == 0:
            self.backoff.gc()
        return scheduled, committed, any_rejected

    def _stamp_trace_annotations(self, committed: list, span) -> None:
        """Stamp the batch's trace context onto just-bound pods that lack
        one (annotation trace.ktpu.io/context — the kubelet joins it)."""
        tp = span.context.to_traceparent()
        for pod, _node_name, _i in committed:
            ann = pod.metadata.annotations or {}
            if TRACE_ANNOTATION in ann:
                continue

            def _mutate(obj):
                new = dict(obj.metadata.annotations or {})
                new.setdefault(TRACE_ANNOTATION, tp)
                obj.metadata.annotations = new

            try:
                self.store.guaranteed_update(
                    "Pod", pod.metadata.name, pod.metadata.namespace,
                    _mutate, retries=4)
            except Exception:  # noqa: BLE001 — tracing must never fail a bind
                log.debug("trace annotation stamp failed for %s/%s",
                          pod.metadata.namespace, pod.metadata.name,
                          exc_info=True)

    def _commit_ledger(self, result, fblob, committed: list,
                       any_rejected: bool, flags, adopted: bool) -> None:
        """Fold one applied batch into the StateDB ledgers. Safe OFF the
        loop (the staged commit thread calls it directly): everything runs
        under the host-state lock, against host/device arrays the loop
        never mutates mid-batch. Callers time it as the `commit` phase, in
        CPU time (in the commit thread the dispatch thread may be tracing
        a new solver variant, GIL-heavy)."""
        with self._state_lock:
            if any_rejected:
                # the solver output charges pods whose binding failed: keep
                # the host truth (accounting only bound pods) and force a
                # re-upload instead of adopting the device ledger
                # (ForgetPod analog)
                self.statedb.commit_batch(result, fblob, committed,
                                          replace_device=False)
                self.statedb.mark_ledger_dirty()
            else:
                # clean batch: adopt the full device ledger, no transfer
                # either way (a pipelined batch already adopted at dispatch —
                # replacing now would regress the device ledger past its
                # successor)
                from kubernetes_tpu.ops.solver import ledger_coverage

                self.statedb.commit_batch(
                    result, fblob, committed, replace_device=not adopted,
                    coverage=ledger_coverage(self.policy, flags))

    def _build_victims(self, flags):
        """Victim-candidate table for this batch: the StateDB's accounted
        pods joined with informer priorities, PDB-evictable bits read from
        the store. Returns (None, None) when the pass is off — preemption
        disabled, no priority spread in the batch (flags.preempt), or no
        evictable candidate anywhere — so the pre-preemption program runs
        unchanged."""
        if not (self.enable_preemption and flags.preempt):
            return None, None
        from kubernetes_tpu.preemption import build_victim_table

        pods_by_key: dict[str, Pod] = {}
        for key in self.statedb._accounted:
            ns, name = key.split("/", 1)
            victim = self.pod_informer.get(name, ns)
            if victim is not None:
                pods_by_key[key] = victim
        victims, vslots = build_victim_table(
            self.statedb, pods_by_key, store=self.store)
        if victims is None:
            return None, None
        return victims, vslots

    def _preempt_one(self, key: str, pod: Pod, node_row: int, k: int,
                     vslots: dict, taken: set) -> bool:
        """Act on one preemption verdict: evict the victim set through the
        PDB-checked eviction path, record status.nominatedNodeName on the
        preemptor, and hold the freed capacity. Returns True when the
        nomination stands. Already-evicted victims are never rolled back
        on a later refusal (the reference evicts asynchronously too) — the
        preemptor just retries against the partially-freed node."""
        from kubernetes_tpu.controllers.disruption import can_evict
        from kubernetes_tpu.preemption import resolve_victims

        self.metrics.preempt_attempt_inc()
        node_name = self.statedb.table.name_of[node_row]
        if node_name is None:
            return False  # verdict node vanished since the solve
        vkeys = resolve_victims(vslots, node_row, int(k),
                                int(pod.spec.priority), taken)
        if vkeys is None:
            return False  # table went stale: retry next batch
        evicted = 0
        for vkey in vkeys:
            vns, vname = vkey.split("/", 1)
            victim = self.pod_informer.get(vname, vns)
            if victim is None:
                continue  # already gone; its capacity is already free
            if not can_evict(self.store, victim):
                # a budget drained between table assembly and now: refuse
                # the rest (eviction-subresource 429 semantics)
                self.events.record(
                    pod, "Warning", "FailedPreemption",
                    f"eviction of {vkey} refused by disruption budget")
                return False
            try:
                self.store.delete("Pod", vname, vns)
            except (NotFound, Conflict):
                continue
            evicted += 1
            self.events.record(
                victim, "Normal", "Preempted",
                f"Preempted by {key} to make room on node {node_name}")

        def set_nominated(obj):
            obj.status.nominated_node_name = node_name
            return obj

        try:
            self.store.guaranteed_update(
                "Pod", pod.metadata.name, pod.metadata.namespace,
                set_nominated)
        except (NotFound, Conflict):
            return False  # preemptor vanished mid-preemption
        self.nominated.nominate(key, node_name, int(pod.spec.priority),
                                time.monotonic())
        self.metrics.preempt_victims_add(evicted)
        self.metrics.preempt_success_inc()
        self.events.record(
            pod, "Normal", "Preempting",
            f"Evicted {evicted} lower-priority pod(s) on {node_name}; "
            f"nominated")
        return True

    def _fail(self, key: str, pod: Pod, message: str) -> None:
        self.metrics.failed += 1
        self.queue.done(key)
        self.queue.add_after(key, self.backoff.next_delay(key))
        self.events.record(pod, "Warning", "FailedScheduling", message)

    def _fail_batch(self, key: str, pod: Pod, message: str,
                    buf: list) -> None:
        """_fail for the batch apply path: the event rides the batch's
        coalesced buffer (one bulk store write per (type, reason)) instead
        of a per-pod synchronous record."""
        self.metrics.failed += 1
        self.queue.done(key)
        self.queue.add_after(key, self.backoff.next_delay(key))
        buf.append((pod, "Warning", "FailedScheduling", message))
