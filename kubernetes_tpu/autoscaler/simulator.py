"""ScaleSimulator: device-batched what-if solves over hypothetical clusters.

The autoscaler's core questions — "would these pending pods fit if the
cluster had k more nodes of shape X?" and "do this node's pods re-fit on
the remainder?" — are the scheduler's findNodesThatFit evaluated against a
cluster state that does not exist. The batched solver already answers
exactly that in one XLA program, so the simulator owns a PRIVATE
StateDB/EncodeCache twin of the scheduler's device state (fed from the same
informers, never shared — probe mutations must not race the real ledger),
mutates it with hypothetical rows (template nodes added, a candidate node
removed), and dispatches `schedule_batch` with `BatchFlags.scale_sim` set.

scale_sim is the only flag the driver never derives from batch content: it
defaults False everywhere else, so real scheduling batches compile the
bit-identical pre-autoscaler program (pinned by test) while probe programs
additionally emit `placed_per_node` — the per-row placement counts the
scale-up scorer reads off the hypothetical rows.

Like the driver, the simulator keeps ONE persistent StateDB + jit-fn cache:
rebuilding per probe would close over fresh PolicyRows constants and force
a recompile every loop iteration.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from dataclasses import dataclass

import numpy as np

from kubernetes_tpu.gang import annotation_min, pod_group_key
from kubernetes_tpu.models.policy import DEFAULT_POLICY, Policy
from kubernetes_tpu.state.encode_cache import EncodeCache
from kubernetes_tpu.state.layout import Capacities, CapacityError
from kubernetes_tpu.state.pod_batch import (
    _layout,
    blob_col,
    packed_batch_flags,
    unpack_batch,
)
from kubernetes_tpu.state.statedb import StateDB

log = logging.getLogger(__name__)

# hypothetical-row name prefix: "~" is illegal in DNS-1123 names, so a sim
# row can never collide with a real registered node
SIM_NODE_PREFIX = "~sim~"


@dataclass
class ScaleUpProbe:
    """One group's expansion what-if."""

    assignments: np.ndarray   # i32[n] per-pod node row (-1 = still unfit)
    newly_placed: int         # pods placed beyond the k=0 baseline
    used_nodes: int           # hypothetical rows that received >= 1 pod
    k: int                    # hypothetical rows offered


class ScaleSimulator:
    def __init__(self, caps: Capacities | None = None,
                 policy: Policy = DEFAULT_POLICY, volume_ctx=None,
                 mesh=None):
        from kubernetes_tpu.models.policy import build_policy_rows
        from kubernetes_tpu.utils.compilation_cache import enable

        enable()  # persistent XLA cache before this plane's first compile
        # probe fleets are small: default capacities sized for control-plane
        # what-ifs, not 50k-node scheduling batches (callers override).
        # mesh: run probe solves node-sharded like the scheduler's own
        # programs — what-ifs against 100k+-node state stay per-shard too
        self.caps = caps or Capacities(num_nodes=128, batch_pods=64)
        self.mesh = mesh
        if mesh is not None and self.caps.num_nodes % mesh.size:
            from kubernetes_tpu.parallel.mesh import padded_num_nodes
            self.caps = dataclasses.replace(
                self.caps,
                num_nodes=padded_num_nodes(self.caps.num_nodes, mesh.size))
        policy = policy.with_env_overrides()
        self.policy = policy
        self.statedb = StateDB(self.caps, mesh=mesh, volume_ctx=volume_ctx)
        self.encode_cache = EncodeCache(self.caps, self.statedb.table,
                                        volume_ctx=volume_ctx)
        self._prows = build_policy_rows(policy, self.statedb.table, self.caps)
        self._fns: dict = {}
        _layout_map, f_width, i_width = _layout(self.caps)
        self._fblob = np.zeros((self.caps.batch_pods, f_width), np.float32)
        self._iblob = np.zeros((self.caps.batch_pods, i_width), np.int32)
        # probe latency accounting (autoscaler_simulation_seconds source)
        self.solve_count = 0
        self.solve_seconds = 0.0

    # ---- real-cluster mirror (driven by the autoscaler's informers) ----

    def upsert_node(self, node) -> None:
        self.statedb.upsert_node(node)

    def remove_node(self, name: str) -> None:
        self.statedb.remove_node(name)

    def has_node(self, name: str) -> bool:
        return self.statedb.has_node(name)

    def add_pod(self, pod) -> bool:
        return self.statedb.add_pod(pod)

    def remove_pod(self, key: str) -> None:
        self.statedb.remove_pod(key)

    def is_accounted(self, key: str) -> bool:
        return self.statedb.is_accounted(key)

    # ---- probe solves ----

    def _get_fn(self, flags):
        import jax

        fn = self._fns.get(flags)
        if fn is None:
            from kubernetes_tpu.ops.solver import schedule_batch

            caps, policy, prows = self.caps, self.policy, self._prows
            if self.mesh is not None:
                from kubernetes_tpu.parallel.mesh import make_sharded_scheduler
                fn = make_sharded_scheduler(self.mesh, policy, caps=caps,
                                            prows=prows, flags=flags,
                                            packed=True)
            else:
                fn = jax.jit(
                    lambda s, fb, ib, rr: schedule_batch(
                        s, unpack_batch(fb, ib, caps), rr, policy,
                        caps=caps, prows=prows, flags=flags))
            self._fns[flags] = fn
        return fn

    def _solve(self, pods) -> tuple[np.ndarray, np.ndarray]:
        """One probe solve: (assignments i32[n], placed_per_node i32[N]).
        Pods beyond batch_pods are ignored (the probe answers for the head
        of the backlog; the loop converges over iterations)."""
        n = min(len(pods), self.caps.batch_pods)
        fblob, iblob = self._fblob, self._iblob
        fblob[:] = 0.0
        iblob[:] = 0
        for i in range(n):
            self.encode_cache.encode_packed_into(fblob, iblob, i, pods[i])
        # gang columns go in after encoding (batch-local ids are never
        # cached): contiguous runs of one group key are all-or-nothing,
        # mirroring the driver's admission shape — an oversized gang must
        # probe as a unit or the what-if would claim partial placements
        # the real scheduler will refuse
        gid_col = blob_col(fblob, iblob, "gang_id", self.caps)
        gmin_col = blob_col(fblob, iblob, "gang_min", self.caps)
        i = 0
        gid = 0
        while i < n:
            gkey = pod_group_key(pods[i])
            if gkey is None:
                i += 1
                continue
            j = i
            while j < n and pod_group_key(pods[j]) == gkey:
                j += 1
            gid += 1
            quorum = annotation_min(pods[i]) or (j - i)
            for row in range(i, j):
                gid_col[row] = gid
                gmin_col[row] = quorum
            i = j

        flags = dataclasses.replace(
            packed_batch_flags(fblob, iblob, n, self.statedb.table,
                               self.caps),
            scale_sim=True)
        fn = self._get_fn(flags)
        state = self.statedb.flush()
        t0 = time.perf_counter()
        result = fn(state, fblob, iblob, np.uint32(0))
        assignments = np.asarray(result.assignments)[:n]
        placed = np.asarray(result.placed_per_node)
        self.solve_seconds += time.perf_counter() - t0
        self.solve_count += 1
        return assignments, placed

    def solve_assignments(self, pods) -> list[str | None]:
        """One solve of the batch against the current (real + hypothetical)
        state: per-pod node NAME, None = unplaced. The federation
        GlobalPlanner's entry point — its rows are whole member clusters,
        so names (not row indices) are the meaningful unit. Pods beyond
        batch_pods are reported unplaced (callers re-batch the tail)."""
        if not pods:
            return []
        assignments, _placed = self._solve(pods)
        names: list[str | None] = [
            self.statedb.table.name_of[a] if a >= 0 else None
            for a in assignments.tolist()]
        names.extend([None] * (len(pods) - len(names)))
        return names

    def baseline_placed(self, pods) -> int:
        """k=0 probe: how many of the pending pods fit the cluster as-is."""
        if not pods:
            return 0
        assignments, _placed = self._solve(pods)
        return int((assignments >= 0).sum())

    def probe_scale_up(self, pods, template, k: int,
                       baseline: int | None = None) -> ScaleUpProbe | None:
        """What-if: add k clones of `template` and re-solve the pending
        batch. Returns None when the node table cannot host k more rows
        (capacity — the caller skips the group). State is restored before
        returning, success or not."""
        if baseline is None:
            baseline = self.baseline_placed(pods)
        sim_names = []
        sim_rows = []
        try:
            for j in range(k):
                node = template.clone()
                name = f"{SIM_NODE_PREFIX}{template.metadata.name}~{j}"
                node.metadata.name = name
                node.metadata.labels["kubernetes.io/hostname"] = name
                self.statedb.upsert_node(node)
                sim_names.append(name)
                sim_rows.append(self.statedb.table.row_of[name])
        except CapacityError:
            for name in sim_names:
                self.statedb.remove_node(name)
            return None
        try:
            assignments, placed = self._solve(pods)
        finally:
            for name in sim_names:
                self.statedb.remove_node(name)
        rows = np.asarray(sim_rows, np.int64)
        return ScaleUpProbe(
            assignments=assignments,
            newly_placed=max(0, int((assignments >= 0).sum()) - baseline),
            used_nodes=int((placed[rows] > 0).sum()),
            k=k)

    def probe_scale_down(self, node, pods) -> bool:
        """What-if: remove `node`'s rows and check every one of its pods
        re-fits on the remainder. `pods` is the node's current bound pod
        set (informer truth); their clones are encoded unbound (node_name
        stripped, or fits_host would pin them to the deleted row). State
        is restored before returning.

        Nodes holding more pods than `caps.batch_pods` are probed in
        chunks: each full chunk's placements are committed into the twin
        (so later chunks see the earlier charges) before the next solve —
        an honest multi-solve answer instead of the old blanket "not
        drainable"."""
        name = node.metadata.name
        if not self.statedb.has_node(name):
            return False
        stripped = []
        for pod in pods:
            clone = pod.clone()
            clone.spec.node_name = ""
            stripped.append(clone)
        self.statedb.remove_node(name)
        committed: list = []
        try:
            if not stripped:
                return True
            step = self.caps.batch_pods
            for start in range(0, len(stripped), step):
                chunk = stripped[start:start + step]
                assignments, _placed = self._solve(chunk)
                if not bool((assignments >= 0).all()):
                    return False
                if start + step >= len(stripped):
                    break
                # commit this chunk's placements so the next solve sees
                # the charges the re-fit just spent
                for clone, row in zip(chunk, assignments.tolist()):
                    clone.spec.node_name = self.statedb.table.name_of[row]
                    self.statedb.add_pod(clone)
                    committed.append(clone)
            return True
        finally:
            # revert: the committed clones share keys with the originals,
            # so drop them before re-adding; remove_node dropped the
            # node's accounted pods too
            for clone in committed:
                self.statedb.remove_pod(clone.key)
            self.statedb.upsert_node(node)
            for pod in pods:
                self.statedb.add_pod(pod)

    def probe_defrag(self, victims, gang_pods) -> bool:
        """What-if: evict `victims` (bound, non-gang pods) and check both
        halves of a defrag move — the pending gang reaches quorum on the
        freed space AND every victim re-fits elsewhere. One solve scores
        the joint batch: gang members first in one contiguous run (so the
        gang columns apply AND the gang claims the freed space before the
        displaced pods re-pack — batch order is placement order, and the
        whole point of the evictions is to seat the gang), victim clones
        after them with node_name stripped. State is restored before
        returning."""
        if len(victims) + len(gang_pods) > self.caps.batch_pods:
            return False
        batch = list(gang_pods)
        for pod in victims:
            clone = pod.clone()
            clone.spec.node_name = ""
            batch.append(clone)
        for pod in victims:
            self.statedb.remove_pod(pod.key)
        try:
            assignments, _placed = self._solve(batch)
            ng = len(gang_pods)
            if victims and not bool((assignments[ng:] >= 0).all()):
                return False
            if not gang_pods:
                return True
            quorum = annotation_min(gang_pods[0]) or ng
            return int((assignments[:ng] >= 0).sum()) >= quorum
        finally:
            for pod in victims:
                self.statedb.add_pod(pod)
