"""SolverService: continuous batching of N tenants' solves into one program.

The inference-serving pattern applied to scheduling: tenant control
planes submit solve requests (single-pod extender verbs or native
multi-pod batch solves), a micro-batch window coalesces whatever
arrived into ONE padded device batch per step, and two persistent
program families answer them:

- ``evaluate``: `ops.solver.evaluate_pod` vmapped over the pod axis —
  per-node (feasible, score) vectors for filter/prioritize verbs;
- ``solve``: `ops.solver.schedule_batch` — assignments with gang
  all-or-nothing and preemption semantics for the native endpoint.

Shapes are pow-2 pod buckets over ONE shared StateDB (pow-2 node
growth by rebuild), so the jit cache is keyed by (bucket, flags) and a
shifting tenant mix never recompiles; every variant is registered with
the compile registry under a ``solversvc[...]`` name so `bench
--profile` attributes recompiles to the exact bucket.

Fairness is APF itself: a dedicated `solversvc` priority level in a
`FlowController` (apiserver/flowcontrol.py), one flow per tenant,
seat width from `solve_seats` — overload sheds with FlowRejected,
which the front end surfaces as an honest 429 + Retry-After.

Isolation is by construction (tenancy.py): everything in the shared
StateDB is tenant-namespaced at ingestion, and the step additionally
refuses (and counts) any assignment row whose node is not the
requesting tenant's — a counter that must read 0 forever.

Determinism seam (R4): the micro-batch window is driven by an injected
`utils.clock.Clock` — tests warp a ManualClock instead of sleeping;
`time.perf_counter` appears only in latency metrics.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import dataclasses
import logging
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from kubernetes_tpu.api.objects import Node, Pod
from kubernetes_tpu.apiserver.flowcontrol import (
    FlowController,
    FlowRejected,
    solve_seats,
)
from kubernetes_tpu.gang import annotation_min, pod_group_key
from kubernetes_tpu.models.policy import (
    DEFAULT_POLICY,
    Policy,
    build_policy_rows,
)
from kubernetes_tpu.obs.tracing import TRACER
from kubernetes_tpu.solversvc.tenancy import (
    check_tenant_name,
    namespace_node,
    namespace_pod,
    split_tenant,
    tenant_prefix,
)
from kubernetes_tpu.state.encode_cache import EncodeCache
from kubernetes_tpu.state.layout import Capacities
from kubernetes_tpu.state.pod_batch import (
    _layout,
    blob_col,
    packed_batch_flags,
    solve_rows,
    unpack_batch,
)
from kubernetes_tpu.state.statedb import StateDB
from kubernetes_tpu.utils.clock import SYSTEM_CLOCK, Clock

log = logging.getLogger(__name__)

_mx: dict | None = None


def _svc_metrics() -> dict:
    """solversvc_* families, registered on first use (all families created
    in this package carry the solversvc_ prefix — R6-lint enforced)."""
    global _mx
    if _mx is None:
        from kubernetes_tpu.obs import metrics as m

        _mx = {
            "requests": m.REGISTRY.counter(
                "solversvc_requests_total",
                "Solve-service requests, by tenant and verb.",
                ("tenant", "verb")),
            "rejected": m.REGISTRY.counter(
                "solversvc_rejected_total",
                "Requests shed by the fair queues (429), by tenant.",
                ("tenant",)),
            "steps": m.REGISTRY.counter(
                "solversvc_steps_total",
                "Continuous-batch steps executed."),
            "batched": m.REGISTRY.counter(
                "solversvc_batched_pods_total",
                "Pod rows coalesced into device batches, by program kind.",
                ("kind",)),
            "occupancy": m.REGISTRY.gauge(
                "solversvc_batch_occupancy",
                "Pod rows in the most recent batch step."),
            "tenants": m.REGISTRY.gauge(
                "solversvc_tenants", "Registered tenants."),
            "solve_seconds": m.REGISTRY.histogram(
                "solversvc_solve_seconds",
                "Device dispatch+readback per batch step, by program kind.",
                ("kind",)),
            "window_wait_seconds": m.REGISTRY.histogram(
                "solversvc_window_wait_seconds",
                "Submit-to-step wait (micro-batch window + queue)."),
            "isolation": m.REGISTRY.counter(
                "solversvc_isolation_violations_total",
                "Assignments refused because the node row belonged to "
                "another tenant (must stay 0)."),
            "jit_miss": m.REGISTRY.counter(
                "solversvc_jit_miss_total",
                "Fresh program compiles, by kind (bucket+flags misses).",
                ("kind",)),
        }
    return _mx


class _TenantUser:
    """Flow-control identity for a tenant (classify reads .name/.groups)."""

    __slots__ = ("name", "groups")

    def __init__(self, name: str):
        self.name = name
        self.groups = ("system:authenticated",)


@dataclass
class Tenant:
    """Per-tenant bookkeeping. All names here are NAMESPACED (prefixed)
    except the latency/bind mirrors the drill reads."""

    name: str
    store: Any = None
    nodes: set[str] = field(default_factory=set)
    node_objs: dict[str, Node] = field(default_factory=dict)
    node_fprint: dict[str, int] = field(default_factory=dict)
    # namespaced pod name -> namespaced Pod, from evaluate requests, so a
    # later extender bind can account usage (bounded: oldest dropped)
    recent_pods: dict[str, Pod] = field(default_factory=dict)
    # accounted (pod, node) pairs — replayed on node-bucket rebuild
    accounted: dict[str, tuple[Pod, str]] = field(default_factory=dict)
    assignments: dict[str, str] = field(default_factory=dict)  # original names
    bind_counts: dict[str, int] = field(default_factory=dict)  # original names
    latency: deque = field(default_factory=lambda: deque(maxlen=8192))
    requests: int = 0
    rejected: int = 0

    RECENT_MAX = 4096

    def remember(self, pod: Pod) -> None:
        self.recent_pods[pod.metadata.name] = pod
        while len(self.recent_pods) > self.RECENT_MAX:
            self.recent_pods.pop(next(iter(self.recent_pods)))


@dataclass
class EvalVerdict:
    """Per-node verdict for one pod — the extender Filter/Prioritize
    answer, in ORIGINAL (tenant-local) node names."""

    names: list[str]
    feasible: dict[str, bool]
    score: dict[str, int]


@dataclass
class SolveVerdict:
    """Native batch-solve answer, in ORIGINAL (tenant-local) names."""

    assignments: list[str | None]   # per pod, input order; None = unplaced
    bound: list[bool]
    errors: list[str]


@dataclass
class _Request:
    tenant: Tenant
    kind: str                       # "evaluate" | "solve"
    pods: list[Pod]                 # namespaced
    future: asyncio.Future
    seat: Any
    t_perf: float                   # perf_counter at submit (latency metrics)
    orig_names: list[str] | None = None    # evaluate: original candidates
    candidates: list[str] | None = None    # evaluate: namespaced candidates
    bind: bool = False              # solve: bind through the tenant store


def _variant_key(flags) -> str:
    on = [f.name for f in dataclasses.fields(flags) if getattr(flags, f.name)]
    return "+".join(on) or "baseline"


class SolverService:
    """The standing multi-tenant solve service (HTTP-free core; the wire
    front end is solversvc/server.py, the binary cmd/solversvc.py)."""

    def __init__(self, caps: Capacities | None = None,
                 policy: Policy = DEFAULT_POLICY, *,
                 clock: Clock = SYSTEM_CLOCK, window_s: float = 0.005,
                 flow: FlowController | None = None, total_seats: int = 32,
                 queue_wait_s: float = 2.0, min_bucket: int = 4):
        from kubernetes_tpu.utils.compilation_cache import enable

        enable()  # persistent XLA cache before this plane's first compile
        self.caps = caps or Capacities(num_nodes=256, batch_pods=64)
        self.policy = policy.with_env_overrides()
        self.clock = clock
        self.window_s = window_s
        self.min_bucket = max(1, min_bucket)
        self.tenants: dict[str, Tenant] = {}
        self.flow = flow or FlowController(total_seats,
                                           queue_wait_s=queue_wait_s)
        # a dedicated priority level: tenant solve traffic gets its own
        # seat budget and shuffle-sharded queues (one flow per tenant)
        self.flow.configure(
            levels={"solversvc": {"shares": 40, "queues": 16,
                                  "queueLengthLimit": 64, "handSize": 4}},
            schemas=[{"name": "solversvc", "priorityLevel": "solversvc",
                      "matchingPrecedence": 500,
                      "rules": [{"verbs": ["solve"],
                                 "resources": ["solves"]}]}])
        self._build_state(self.caps)
        self._pending: deque[_Request] = deque()
        self._arrival: asyncio.Event | None = None
        self._runner: asyncio.Task | None = None
        self._poll_s = max(window_s / 8, 0.0005)
        # dedicated single worker for device dispatch+readback: the
        # default executor is shared process-wide and can be saturated by
        # unrelated blocking work, which would wedge the serving loop
        # behind its own clients (observed on 1-vCPU CI)
        self._exec = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="solversvc-step")

    # ---- device state (rebuilt on node-bucket growth) ----

    def _build_state(self, caps: Capacities) -> None:
        self.caps = caps
        self.statedb = StateDB(caps)
        self.encode_cache = EncodeCache(caps, self.statedb.table)
        self._prows = build_policy_rows(self.policy, self.statedb.table,
                                        caps)
        self._eval_fns: dict[int, Any] = {}
        self._solve_fns: dict[tuple, Any] = {}
        _map, f_width, i_width = _layout(caps)
        self._fblob = np.zeros((caps.batch_pods, f_width), np.float32)
        self._iblob = np.zeros((caps.batch_pods, i_width), np.int32)

    def _ensure_node_capacity(self, extra: int) -> None:
        need = len(self.statedb.table.row_of) + extra
        if need <= self.caps.num_nodes:
            return
        new_n = 1 << (need - 1).bit_length()
        log.info("solversvc: growing node bucket %d -> %d rows",
                 self.caps.num_nodes, new_n)
        self._build_state(dataclasses.replace(self.caps, num_nodes=new_n))
        for t in self.tenants.values():
            for node in t.node_objs.values():
                self.statedb.upsert_node(node)
            for pod, node_name in t.accounted.values():
                self.statedb.add_pod(pod, node_name)

    # ---- tenants & state sync ----

    def _tenant(self, name: str) -> Tenant:
        t = self.tenants.get(name)
        if t is None:
            raise KeyError(f"unknown tenant {name!r}")
        return t

    def register_tenant(self, name: str, store: Any = None) -> Tenant:
        check_tenant_name(name)
        t = self.tenants.get(name)
        if t is None:
            t = self.tenants[name] = Tenant(name=name, store=store)
            _svc_metrics()["tenants"].set(len(self.tenants))
        elif store is not None:
            t.store = store
        return t

    def drop_tenant(self, name: str) -> None:
        t = self.tenants.pop(name, None)
        if t is None:
            return
        for node in list(t.nodes):
            self.statedb.remove_node(node)  # drops its accounted pods too
        _svc_metrics()["tenants"].set(len(self.tenants))

    def upsert_node(self, tenant: str, node: dict | Node) -> None:
        t = self._tenant(tenant)
        nsd = namespace_node(t.name, node)
        name = nsd.metadata.name
        fprint = hash(repr(sorted((nsd.to_dict() or {}).items())))
        if t.node_fprint.get(name) == fprint and name in t.nodes:
            return  # unchanged full-object resend (stock extender mode)
        self._ensure_node_capacity(0 if name in t.nodes else 1)
        self.statedb.upsert_node(nsd)
        t.nodes.add(name)
        t.node_objs[name] = nsd
        t.node_fprint[name] = fprint

    def remove_node(self, tenant: str, node_name: str) -> None:
        t = self._tenant(tenant)
        name = tenant_prefix(t.name, node_name)
        self.statedb.remove_node(name)
        t.nodes.discard(name)
        t.node_objs.pop(name, None)
        t.node_fprint.pop(name, None)
        for key in [k for k, (_, nn) in t.accounted.items() if nn == name]:
            del t.accounted[key]

    def account_pod(self, tenant: str, pod: dict | Pod,
                    node_name: str | None = None) -> bool:
        """Account a bound tenant pod against its node (usage sync)."""
        t = self._tenant(tenant)
        nsp = namespace_pod(t.name, pod)
        nn = tenant_prefix(t.name, node_name) if node_name \
            else nsp.spec.node_name
        if not nn:
            return False
        ok = self.statedb.add_pod(nsp, nn)
        if ok:
            t.accounted[nsp.key] = (nsp, nn)
        return ok

    def forget_pod(self, tenant: str, namespace: str, pod_name: str) -> None:
        t = self._tenant(tenant)
        key = (f"{tenant_prefix(t.name, namespace or 'default')}/"
               f"{tenant_prefix(t.name, pod_name)}")
        self.statedb.remove_pod(key)
        t.accounted.pop(key, None)

    # ---- request surfaces ----

    async def evaluate(self, tenant: str, pod: dict | Pod, *,
                       nodes: list | None = None,
                       node_names: list[str] | None = None) -> EvalVerdict:
        """Filter/Prioritize verdict for one pod. `nodes` (full objects,
        stock non-cache-capable mode) are synced into the tenant's state
        first; `node_names` resolve against already-synced state."""
        t = self._tenant(tenant)
        if nodes is not None:
            names = []
            for nd in nodes:
                self.upsert_node(t.name, nd)
                names.append(nd.metadata.name if isinstance(nd, Node)
                             else (nd.get("metadata") or {}).get("name", ""))
        else:
            names = list(node_names or [])
        nsp = namespace_pod(t.name, pod)
        t.remember(nsp)
        req = await self._submit(
            t, "evaluate", [nsp],
            orig_names=names,
            candidates=[tenant_prefix(t.name, n) for n in names])
        return req

    async def solve(self, tenant: str, pods: list, *,
                    bind: bool = False) -> SolveVerdict:
        """Native batch solve: gang/preemption-capable superset of the
        extender verbs. With bind=True, successful assignments bind
        through the tenant's store and are accounted."""
        t = self._tenant(tenant)
        if len(pods) > self.caps.batch_pods:
            raise ValueError(
                f"solve request of {len(pods)} pods exceeds the service "
                f"batch capacity {self.caps.batch_pods}")
        if not pods:
            return SolveVerdict([], [], [])
        nspods = [namespace_pod(t.name, p) for p in pods]
        for p in nspods:
            t.remember(p)
        return await self._submit(t, "solve", nspods, bind=bind)

    def bind(self, tenant: str, pod_name: str, namespace: str,
             node: str) -> str:
        """Extender bind verb. Returns "" or an error string. A bind
        routed to the wrong tenant — a node the tenant never registered —
        is REJECTED before touching any store (isolation invariant)."""
        t = self._tenant(tenant)
        _svc_metrics()["requests"].labels(t.name, "bind").inc()
        ns_node = tenant_prefix(t.name, node)
        if ns_node not in t.nodes:
            return (f"bind rejected: node {node!r} is not registered to "
                    f"tenant {t.name!r}")
        if t.store is not None:
            from kubernetes_tpu.api.objects import Binding
            from kubernetes_tpu.apiserver.store import Conflict, NotFound
            try:
                t.store.bind(Binding(pod_name=pod_name,
                                     namespace=namespace or "default",
                                     target_node=node))
            except (Conflict, NotFound) as e:
                return str(e)
        t.bind_counts[pod_name] = t.bind_counts.get(pod_name, 0) + 1
        t.assignments[pod_name] = node
        nsp = t.recent_pods.get(tenant_prefix(t.name, pod_name))
        if nsp is not None:
            if self.statedb.add_pod(nsp, ns_node):
                t.accounted[nsp.key] = (nsp, ns_node)
        return ""

    async def _submit(self, t: Tenant, kind: str, pods: list[Pod],
                      **extra) -> Any:
        mx = _svc_metrics()
        t.requests += 1
        mx["requests"].labels(t.name, kind).inc()
        try:
            seat = await self.flow.acquire(_TenantUser(t.name), "solve",
                                           "solves",
                                           width=solve_seats(len(pods)))
        except FlowRejected:
            t.rejected += 1
            mx["rejected"].labels(t.name).inc()
            raise
        if self._runner is None:
            self.flow.release(seat)
            raise RuntimeError("solversvc not started (call start())")
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        req = _Request(tenant=t, kind=kind, pods=pods, future=fut,
                       seat=seat, t_perf=time.perf_counter(), **extra)
        self._pending.append(req)
        self._arrival.set()
        return await fut

    # ---- the continuous batcher ----

    async def start(self) -> None:
        if self._runner is not None:
            return
        self._arrival = asyncio.Event()
        self._runner = asyncio.get_running_loop().create_task(self._run())

    async def stop(self) -> None:
        if self._runner is not None:
            self._runner.cancel()
            try:
                await self._runner
            except asyncio.CancelledError:
                pass
            self._runner = None
        while self._pending:
            req = self._pending.popleft()
            self._finish(req, exc=RuntimeError("solversvc stopped"))

    async def _run(self) -> None:
        while True:
            await self._arrival.wait()
            if not self._pending:
                self._arrival.clear()
                continue
            # the micro-batch window: wait out `window_s` on the INJECTED
            # clock (ManualClock in tests — no wall-clock in the decision)
            # unless the pod budget fills first
            deadline = self.clock.now() + self.window_s
            while (self.clock.now() < deadline
                   and sum(len(r.pods) for r in self._pending)
                   < self.caps.batch_pods):
                await asyncio.sleep(self._poll_s)
            batch: list[_Request] = []
            taken = 0
            while self._pending:
                req = self._pending[0]
                if batch and taken + len(req.pods) > self.caps.batch_pods:
                    break
                self._pending.popleft()
                batch.append(req)
                taken += len(req.pods)
            if not self._pending:
                self._arrival.clear()
            try:
                await self._step(batch)
            except Exception as e:  # noqa: BLE001 — the batcher must
                # survive any one batch's failure; its requests error out
                log.exception("solversvc step failed")
                for r in batch:
                    self._finish(r, exc=e)

    def _finish(self, r: _Request, result: Any = None,
                exc: Exception | None = None) -> None:
        if r.seat is not None:
            elapsed = time.perf_counter() - r.t_perf
            self.flow.note_latency(r.seat, elapsed)
            self.flow.release(r.seat)
            r.tenant.latency.append(elapsed)
            r.seat = None
        if not r.future.done():
            if exc is not None:
                r.future.set_exception(exc)
            else:
                r.future.set_result(result)

    async def _step(self, batch: list[_Request]) -> None:
        mx = _svc_metrics()
        mx["steps"].inc()
        mx["occupancy"].set(sum(len(r.pods) for r in batch))
        now = time.perf_counter()
        for r in batch:
            mx["window_wait_seconds"].observe(max(0.0, now - r.t_perf))
        evals = [r for r in batch if r.kind == "evaluate"]
        solves = [r for r in batch if r.kind == "solve"]
        with TRACER.start_span("solversvc.step", attrs={
                "requests": len(batch),
                "tenants": len({r.tenant.name for r in batch}),
                "evaluate_pods": sum(len(r.pods) for r in evals),
                "solve_pods": sum(len(r.pods) for r in solves)}):
            if evals:
                try:
                    await self._step_evaluate(evals)
                except Exception as e:  # noqa: BLE001 — fail only this group
                    log.exception("solversvc evaluate step failed")
                    for r in evals:
                        self._finish(r, exc=e)
            if solves:
                try:
                    await self._step_solve(solves)
                except Exception as e:  # noqa: BLE001 — fail only this group
                    log.exception("solversvc solve step failed")
                    for r in solves:
                        self._finish(r, exc=e)

    # ---- shape buckets & programs ----

    def _bucket(self, n: int) -> int:
        return solve_rows(n, self.caps.batch_pods, self.min_bucket)

    def _eval_fn(self, bucket: int):
        fn = self._eval_fns.get(bucket)
        if fn is None:
            import jax

            from kubernetes_tpu.obs.profiling import COMPILES
            from kubernetes_tpu.ops.solver import evaluate_pod

            caps, policy, prows = self.caps, self.policy, self._prows

            def program(state, fb, ib):
                rows = unpack_batch(fb, ib, caps)
                return jax.vmap(
                    lambda row: evaluate_pod(state, row, policy, caps=caps,
                                             prows=prows))(rows)

            fn = COMPILES.instrument(
                f"solversvc[evaluate,p{bucket}]", jax.jit(program))
            self._eval_fns[bucket] = fn
            _svc_metrics()["jit_miss"].labels("evaluate").inc()
        return fn

    def _solve_fn(self, bucket: int, flags):
        key = (bucket, flags)
        fn = self._solve_fns.get(key)
        if fn is None:
            import jax

            from kubernetes_tpu.obs.profiling import COMPILES
            from kubernetes_tpu.ops.solver import schedule_batch

            caps, policy, prows = self.caps, self.policy, self._prows
            fn = COMPILES.instrument(
                f"solversvc[solve,p{bucket}]+{_variant_key(flags)}",
                jax.jit(lambda s, fb, ib, rr: schedule_batch(
                    s, unpack_batch(fb, ib, caps), rr, policy, caps=caps,
                    prows=prows, flags=flags)))
            self._solve_fns[key] = fn
            _svc_metrics()["jit_miss"].labels("solve").inc()
        return fn

    def warmup(self, buckets: tuple[int, ...] = ()) -> None:
        """Pre-compile the evaluate+solve programs for the given pod
        buckets (default: the smallest) so first tenant traffic never
        waits out a compile — the extender-client 5s timeout story."""
        try:
            pod = Pod.from_dict({"metadata": {"name": "warmup",
                                              "namespace": "default"}})
            for want in tuple(buckets) or (self.min_bucket,):
                b = self._bucket(want)
                fblob, iblob = self._fblob[:b], self._iblob[:b]
                fblob[:] = 0.0
                iblob[:] = 0
                self.encode_cache.encode_packed_into(fblob, iblob, 0, pod)
                flags = packed_batch_flags(fblob, iblob, 1,
                                           self.statedb.table, self.caps)
                state = self.statedb.flush()
                np.asarray(self._eval_fn(b)(state, fblob, iblob)[0])
                np.asarray(self._solve_fn(b, flags)(
                    state, fblob, iblob, np.uint32(0)).assignments)
        except Exception:  # pragma: no cover — never block serving
            log.exception("solversvc warmup failed")

    # ---- device steps ----

    def _encode(self, reqs: list[_Request]) -> tuple:
        """(bucket, fblob view, iblob view, n, per-request offsets)."""
        n = sum(len(r.pods) for r in reqs)
        bucket = self._bucket(n)
        fblob, iblob = self._fblob[:bucket], self._iblob[:bucket]
        fblob[:] = 0.0
        iblob[:] = 0
        offsets, i = [], 0
        for r in reqs:
            offsets.append(i)
            for pod in r.pods:
                self.encode_cache.encode_packed_into(fblob, iblob, i, pod)
                i += 1
        return bucket, fblob, iblob, n, offsets

    async def _step_evaluate(self, reqs: list[_Request]) -> None:
        mx = _svc_metrics()
        bucket, fblob, iblob, n, offsets = self._encode(reqs)
        mx["batched"].labels("evaluate").inc(n)
        fn = self._eval_fn(bucket)
        state = self.statedb.flush()

        def run() -> tuple[np.ndarray, np.ndarray]:
            # dispatch AND read back off the event loop: the readback
            # blocks until the device finishes, and that wait must not
            # stall the serving loop (LoopStallWatchdog contract)
            out = fn(state, fblob, iblob)
            return np.asarray(out[0]), np.asarray(out[1])

        t0 = time.perf_counter()
        feasible, score = await asyncio.get_running_loop().run_in_executor(self._exec, run)
        mx["solve_seconds"].labels("evaluate").observe(
            time.perf_counter() - t0)
        row_of = self.statedb.table.row_of
        for r, off in zip(reqs, offsets):
            frow, srow = feasible[off], score[off]
            fmap: dict[str, bool] = {}
            smap: dict[str, int] = {}
            for orig, cand in zip(r.orig_names, r.candidates):
                row = row_of.get(cand)
                if row is None:
                    fmap[orig], smap[orig] = False, 0
                else:
                    fmap[orig] = bool(frow[row])
                    smap[orig] = int(srow[row])
            self._finish(r, EvalVerdict(names=list(r.orig_names),
                                        feasible=fmap, score=smap))

    async def _step_solve(self, reqs: list[_Request]) -> None:
        mx = _svc_metrics()
        bucket, fblob, iblob, n, offsets = self._encode(reqs)
        mx["batched"].labels("solve").inc(n)
        # gang columns per REQUEST (a gang can never span tenants or
        # requests): contiguous runs of one group key, quorum from the
        # annotation — the same all-or-nothing shape the driver admits
        gid_col = blob_col(fblob, iblob, "gang_id", self.caps)
        gmin_col = blob_col(fblob, iblob, "gang_min", self.caps)
        gid = 0
        for r, off in zip(reqs, offsets):
            i, pods = 0, r.pods
            while i < len(pods):
                gkey = pod_group_key(pods[i])
                if gkey is None:
                    i += 1
                    continue
                j = i
                while j < len(pods) and pod_group_key(pods[j]) == gkey:
                    j += 1
                gid += 1
                quorum = annotation_min(pods[i]) or (j - i)
                for row in range(i, j):
                    gid_col[off + row] = gid
                    gmin_col[off + row] = quorum
                i = j
        flags = packed_batch_flags(fblob, iblob, n, self.statedb.table,
                                   self.caps)
        fn = self._solve_fn(bucket, flags)
        state = self.statedb.flush()

        def run() -> np.ndarray:
            # dispatch + readback off the event loop (see _step_evaluate)
            result = fn(state, fblob, iblob, np.uint32(0))
            return np.asarray(result.assignments)[:n]

        t0 = time.perf_counter()
        assignments = await asyncio.get_running_loop().run_in_executor(self._exec, run)
        mx["solve_seconds"].labels("solve").observe(time.perf_counter() - t0)
        row_name = {row: name
                    for name, row in self.statedb.table.row_of.items()}
        for r, off in zip(reqs, offsets):
            self._resolve_solve(r, assignments, off, row_name)

    def _resolve_solve(self, r: _Request, assignments: np.ndarray,
                       off: int, row_name: dict[int, str]) -> None:
        t = r.tenant
        out: list[str | None] = []
        errors: list[str] = []
        bound: list[bool] = []
        for k, pod in enumerate(r.pods):
            row = int(assignments[off + k])
            node = row_name.get(row) if row >= 0 else None
            if node is None:
                out.append(None)
                errors.append("" if row < 0 else f"unknown node row {row}")
                bound.append(False)
                continue
            owner, orig_node = split_tenant(node)
            if owner != t.name:
                # impossible by construction (tenancy.py); refuse + count
                _svc_metrics()["isolation"].inc()
                out.append(None)
                errors.append(f"isolation violation: row {row} belongs to "
                              f"{owner!r}")
                bound.append(False)
                continue
            _, orig_pod = split_tenant(pod.metadata.name)
            _, orig_ns = split_tenant(pod.metadata.namespace)
            out.append(orig_node)
            err, did_bind = "", False
            if r.bind:
                err = self.bind(t.name, orig_pod, orig_ns, orig_node)
                did_bind = not err
            else:
                t.assignments[orig_pod] = orig_node
            errors.append(err)
            bound.append(did_bind)
        self._finish(r, SolveVerdict(assignments=out, bound=bound,
                                     errors=errors))
