"""Scheduler-extender HTTP endpoint: the stock-control-plane integration seam.

Speaks the reference's extender wire protocol so an *unmodified* Go
kube-scheduler can delegate filtering/prioritization to the TPU:
`HTTPExtender` POSTs JSON `ExtenderArgs{pod, nodes|nodenames}` to
URLPrefix+"/"+verb and expects `ExtenderFilterResult` / `HostPriorityList`
back (reference plugin/pkg/scheduler/core/extender.go:100 Filter, :143
Prioritize, :227-243 POST mechanics; wire types
plugin/pkg/scheduler/api/v1/types.go:148-204). The optional bind verb
(`ExtenderBindingArgs`) binds through this framework's store in standalone
deployments.

Two node-delivery modes, matching ExtenderConfig.NodeCacheCapable:
- node-cache-capable (names only): candidates resolve against the maintained
  StateDB — the intended production mode, where the extender watches the
  cluster itself and the Go scheduler ships only names.
- full objects: nodes in the request body are encoded on the fly into a
  scratch state (universe ids shared with the persistent table).

The HTTP layer is a minimal asyncio HTTP/1.1 server — requests are small
JSON POSTs on a trusted network, exactly how the reference treats extenders
(5s default timeout, extender.go:36).
"""

from __future__ import annotations

import asyncio
import json
import logging
from typing import Any

import jax
import numpy as np

from kubernetes_tpu.api.objects import Node, Pod
from kubernetes_tpu.apiserver.flowcontrol import FlowRejected
from kubernetes_tpu.models.policy import DEFAULT_POLICY, Policy, build_policy_rows
from kubernetes_tpu.ops.solver import evaluate_pod
from kubernetes_tpu.state import Capacities, encode_cluster
from kubernetes_tpu.state.layout import CapacityError
from kubernetes_tpu.state.pod_batch import empty_batch, encode_pod_into
from kubernetes_tpu.state.statedb import StateDB

log = logging.getLogger(__name__)

_UNBUILT = object()


def _row(batch, i=0):
    return jax.tree.map(lambda a: a[i], batch)


class ExtenderService:
    """Protocol logic, HTTP-free (reused by tests and the HTTP server).

    Both verbs run the CONFIGURED policy's complete predicate/priority set
    via ops.solver.evaluate_pod — the same `_pod_eval` the batch solver's
    scan step executes (one derivation, no drift): a stock Go scheduler
    delegating here gets interpod-affinity, volume, spreading and every
    policy-argument registration, not a hard-coded subset."""

    def __init__(self, caps: Capacities | None = None,
                 policy: Policy = DEFAULT_POLICY, statedb: StateDB | None = None,
                 store=None, solversvc=None, solversvc_buckets: tuple = ()):
        from kubernetes_tpu.utils.compilation_cache import enable

        enable()  # persistent XLA cache before the warmup compile
        self.caps = caps or Capacities()
        self.policy = policy.with_env_overrides()
        self.statedb = statedb
        self.store = store
        # co-located multi-tenant service (solversvc.SolverService): one
        # warmup() call compiles BOTH the per-cluster path and the
        # service's shape buckets before traffic arrives
        self.solversvc = solversvc
        self.solversvc_buckets = tuple(solversvc_buckets)
        # prows arrays are passed as traced args so per-request tables
        # (full-objects mode) don't recompile; policy/caps stay static
        self._eval = jax.jit(
            lambda state, pod_row, prows: evaluate_pod(
                state, pod_row, self.policy, caps=self.caps, prows=prows))
        # PolicyRows against the persistent statedb table are stable after
        # the first build; full-objects mode rebuilds per fresh table
        self._statedb_prows = _UNBUILT

    def warmup(self) -> None:
        """Compile the evaluation program before serving (first compile can
        exceed the reference client's 5s default timeout, extender.go:36).
        When a solversvc is attached, its pow-2 shape buckets pre-compile
        here too — the compile registry names each bucket variant
        (``solversvc[evaluate,pN]`` / ``solversvc[solve,pN]+flags``) so
        `bench --profile` attributes any recompile to the exact bucket."""
        try:
            dummy = Node.from_dict({
                "metadata": {"name": "warmup-node"},
                "status": {"allocatable": {"cpu": "1", "memory": "1Gi",
                                           "pods": "10"},
                           "conditions": [{"type": "Ready",
                                           "status": "True"}]}})
            self._evaluate(Pod.from_dict({"metadata": {"name": "warmup"}}),
                           [dummy], None)
        except Exception:  # never block serving on a warmup failure
            log.exception("extender warmup failed")
        if self.solversvc is not None:
            self.solversvc.warmup(self.solversvc_buckets)

    # ---- state resolution ----

    def _cached_state(self):
        if self.statedb is None:
            return None, None
        return self.statedb.flush(), self.statedb.table

    def _evaluate(self, pod: Pod, nodes: list[Node] | None,
                  node_names: list[str] | None):
        """Returns (names, feasible bool[N], scores f32[N], row_of)."""
        ctx = self.statedb.volume_ctx if self.statedb is not None else None
        if nodes is not None:
            state, batch, table = encode_cluster(nodes, [pod], self.caps,
                                                 ctx=ctx)
            # argument registrations intern Exists-requirements/topology
            # slots into the fresh table — refill membership afterwards
            prows = build_policy_rows(self.policy, table, self.caps)
            from kubernetes_tpu.state.cluster_state import apply_pending_refreshes
            apply_pending_refreshes(state, table)
            names = [n.metadata.name for n in nodes]
        else:
            state, table = self._cached_state()
            if state is None:
                raise ValueError("nodenames given but no statedb maintained")
            if self._statedb_prows is _UNBUILT:
                self._statedb_prows = build_policy_rows(
                    self.policy, table, self.caps)
            prows = self._statedb_prows
            batch = empty_batch(self.caps)
            encode_pod_into(batch, 0, pod, self.caps, table, ctx=ctx)
            # encoding may have interned new membership/selector/volsel
            # entries; flush() refills the affected columns and re-uploads
            # them (no-op when nothing is pending)
            state = self.statedb.flush()
            names = node_names or []
        feasible, score = self._eval(state, _row(batch), prows)
        return names, np.asarray(feasible), np.asarray(score), table.row_of

    # ---- verbs ----

    def filter(self, args: dict[str, Any]) -> dict[str, Any]:
        """ExtenderFilterResult for ExtenderArgs (extender.go:100)."""
        try:
            pod = Pod.from_dict(args.get("pod") or {})
            nodes, node_names = _parse_candidates(args)
            names, feasible, _, row_of = self._evaluate(pod, nodes, node_names)

            def ok(name: str) -> bool:
                row = row_of.get(name)
                return row is not None and bool(feasible[row])

            items = {n.metadata.name: n.to_dict() for n in nodes} \
                if nodes is not None else None
            return filter_payload(names, ok, items)
        except (ValueError, CapacityError, KeyError) as e:  # malformed args
            return {"error": f"{type(e).__name__}: {e}"}

    def prioritize(self, args: dict[str, Any]) -> list[dict[str, Any]]:
        """HostPriorityList for ExtenderArgs (extender.go:143). Scores are the
        default-policy weighted sum truncated to int (the Go scheduler
        multiplies by the configured extender weight)."""
        pod = Pod.from_dict(args.get("pod") or {})
        nodes, node_names = _parse_candidates(args)
        names, _, score, row_of = self._evaluate(pod, nodes, node_names)

        def score_of(name: str) -> int:
            row = row_of.get(name)
            return int(score[row]) if row is not None else 0

        return priority_payload(names, score_of)

    def bind(self, args: dict[str, Any]) -> dict[str, Any]:
        """ExtenderBindingResult for ExtenderBindingArgs — standalone mode
        binds through this framework's store."""
        if self.store is None:
            return {"Error": "bind not supported: no store configured"}
        from kubernetes_tpu.api.objects import Binding
        from kubernetes_tpu.apiserver.store import Conflict, NotFound
        try:
            self.store.bind(Binding(pod_name=args.get("PodName", ""),
                                    namespace=args.get("PodNamespace", "default"),
                                    target_node=args.get("Node", "")))
            return {"Error": ""}
        except (Conflict, NotFound) as e:
            return {"Error": str(e)}


def _parse_candidates(args: dict[str, Any]):
    if args.get("nodes") is not None:
        return [Node.from_dict(d) for d in args["nodes"].get("items") or []], None
    names = args.get("nodenames")
    return None, list(names or [])


# ---- wire payload shaping, shared by the per-cluster service above and
# the multi-tenant solversvc front end (one evaluation path, one protocol
# rendering — both end at ops.solver.evaluate_pod, single or vmapped) ----

FAILED_REASON = "node(s) didn't satisfy TPU predicates"


def filter_payload(names: list[str], feasible_of,
                   node_items: dict[str, dict] | None) -> dict[str, Any]:
    """ExtenderFilterResult from a per-name feasibility callable.
    `node_items` (name -> node dict) echoes full objects back in
    non-cache-capable mode; None renders the nodenames shape."""
    passed, failed = [], {}
    for name in names:
        if feasible_of(name):
            passed.append(name)
        else:
            failed[name] = FAILED_REASON
    if node_items is not None:
        result: dict[str, Any] = {"nodes": {
            "apiVersion": "v1", "kind": "NodeList",
            "items": [node_items[n] for n in passed]}}
    else:
        result = {"nodenames": passed}
    if failed:
        result["failedNodes"] = failed
    return result


def priority_payload(names: list[str], score_of) -> list[dict[str, Any]]:
    """HostPriorityList from a per-name score callable."""
    return [{"host": name, "score": int(score_of(name))} for name in names]


class ExtenderServer:
    """Minimal asyncio HTTP/1.1 wrapper around ExtenderService.

    Hardened like the reference treats its extenders: a configurable
    per-request deadline (default 5s — DefaultExtenderTimeout,
    extender.go:36) answered with 504 when evaluation overruns, and an
    honest 429 + Retry-After when a fair-queue front end (solversvc)
    sheds the request — `HTTPExtender` raises ExtenderError on either,
    so the stock scheduler's per-pod retry/backoff semantics compose."""

    def __init__(self, service: ExtenderService, host: str = "127.0.0.1",
                 port: int = 0, deadline_s: float = 5.0):
        self.service = service
        self.host = host
        self.port = port
        self.deadline_s = deadline_s
        self._server: asyncio.AbstractServer | None = None
        self._ready = False  # /readyz: true once warmup compiled

    def _warm(self) -> None:
        """Blocking pre-compile, run in an executor before serving
        (subclasses override to warm their own programs)."""
        self.service.warmup()

    async def start(self) -> None:
        await asyncio.get_running_loop().run_in_executor(None, self._warm)
        self._ready = True
        self._server = await asyncio.start_server(self._handle, self.host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
            self._server = None

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                request_line = await reader.readline()
                if not request_line:
                    return
                try:
                    method, path, _ = request_line.decode().split(None, 2)
                except ValueError:
                    await self._respond(writer, 400, {"error": "bad request line"})
                    return
                headers: dict[str, str] = {}
                while True:
                    line = await reader.readline()
                    if line in (b"\r\n", b"\n", b""):
                        break
                    name, _, value = line.decode().partition(":")
                    headers[name.strip().lower()] = value.strip()
                length = int(headers.get("content-length", 0))
                body = await reader.readexactly(length) if length else b""

                p = path.split("?", 1)[0].rstrip("/")
                if p in ("/metrics", "/readyz", "/livez"):
                    # text obs endpoints; /healthz keeps its JSON shape
                    # (the reference extender contract this server serves)
                    from kubernetes_tpu.obs import metrics as obs_metrics
                    from kubernetes_tpu.obs.http import (
                        http_head,
                        obs_response,
                    )

                    status, rbody, ctype = obs_response(
                        method, p, registry=obs_metrics.REGISTRY,
                        ready_checks={"warmed-up": lambda: self._ready})
                    writer.write(http_head(status, rbody, ctype))
                    await writer.drain()
                    return
                extra: dict[str, str] = {}
                try:
                    routed = await asyncio.wait_for(
                        self._route(method, path, body), self.deadline_s)
                    status, payload = routed[0], routed[1]
                    extra = routed[2] if len(routed) > 2 else {}
                except asyncio.TimeoutError:
                    status, payload = 504, {
                        "error": f"request exceeded the "
                                 f"{self.deadline_s:.0f}s deadline"}
                except FlowRejected as e:
                    # the fair queues shed this request: honest 429 with a
                    # drain-time hint — HTTPExtender surfaces it and the
                    # stock scheduler requeues the pod with backoff
                    status, payload = 429, {"error": str(e)}
                    extra = {"Retry-After": str(max(1, round(e.retry_after)))}
                keep = headers.get("connection", "keep-alive").lower() != "close"
                await self._respond(writer, status, payload, keep_alive=keep,
                                    extra_headers=extra)
                if not keep:
                    return
        except (asyncio.IncompleteReadError, ConnectionError):
            pass
        finally:
            writer.close()

    async def _route(self, method: str, path: str, body: bytes):
        """-> (status, payload) or (status, payload, extra_headers). Verb
        evaluation runs in an executor so the deadline can actually fire
        and device compute never stalls the serving loop."""
        path = path.rstrip("/")
        if method == "GET" and path in ("", "/healthz"):
            return 200, {"ok": True}
        if method != "POST":
            return 405, {"error": f"method {method} not allowed"}
        try:
            args = json.loads(body or b"{}")
        except json.JSONDecodeError as e:
            return 400, {"error": f"bad JSON: {e}"}
        if not isinstance(args, dict):
            return 400, {"error": "request body must be a JSON object"}
        verb = path.rsplit("/", 1)[-1]
        loop = asyncio.get_running_loop()
        if verb == "filter":
            return 200, await loop.run_in_executor(
                None, self.service.filter, args)
        if verb == "prioritize":
            return 200, await loop.run_in_executor(
                None, self.service.prioritize, args)
        if verb == "bind":
            return 200, await loop.run_in_executor(
                None, self.service.bind, args)
        return 404, {"error": f"unknown verb {verb!r}"}

    async def _respond(self, writer: asyncio.StreamWriter, status: int,
                       payload, keep_alive: bool = False,
                       extra_headers: dict[str, str] | None = None) -> None:
        body = json.dumps(payload).encode()
        reason = {200: "OK", 400: "Bad Request", 404: "Not Found",
                  405: "Method Not Allowed", 429: "Too Many Requests",
                  503: "Service Unavailable",
                  504: "Gateway Timeout"}.get(status, "Error")
        conn = "keep-alive" if keep_alive else "close"
        head = [f"HTTP/1.1 {status} {reason}",
                "Content-Type: application/json",
                f"Content-Length: {len(body)}"]
        for name, value in (extra_headers or {}).items():
            head.append(f"{name}: {value}")
        head.append(f"Connection: {conn}")
        writer.write(("\r\n".join(head) + "\r\n\r\n").encode() + body)
        await writer.drain()
