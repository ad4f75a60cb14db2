"""StateDB: host-canonical cluster state incrementally mirrored to device.

The stateful shell around `ClusterState` playing the role of the scheduler
cache (reference plugin/pkg/scheduler/schedulercache/cache.go): it aggregates
node objects + accounted pods (bound and assumed) into the SoA arrays, tracks
dirtiness at field-group granularity (the generation-counter analog,
node_info.go:60), and hands the device a fresh view only when something
actually changed.

Two commit paths keep the hot loop off the PCIe bus:
- `add_pod`/`remove_pod` mutate host numpy and mark the ledger dirty; the next
  `flush()` re-uploads ledger arrays (external writes: pods bound by other
  components, deletions, node changes).
- `commit_result(result, ...)` accepts the solver's *device-resident* full
  output ledger (resources, ports, inter-pod affinity counts, volume and
  attach counts) as the new truth — batch-to-batch chaining never leaves the
  device — while mirroring the same arithmetic into host numpy from the
  batch's pre-encoded rows; host and device stay equal without a transfer.

Assume/forget semantics (cache.go:109 AssumePod, scheduler.go:224 rollback):
the driver accounts an assignment optimistically via either path; a failed
bind calls `remove_pod` which both fixes host numpy and marks the ledger
dirty, forcing re-upload of the corrected truth.
"""

from __future__ import annotations

import numpy as np

import jax

from dataclasses import dataclass

from kubernetes_tpu.api.objects import Node, Pod
from kubernetes_tpu.state.cluster_state import (
    ClusterState,
    NodeTable,
    _fill_node_row,
    apply_pending_refreshes,
    carried_term_row,
    empty_state,
    intern_pod_affinity_terms,
    pod_match_row,
    pod_nonzero_requests,
    pod_requests,
)
from kubernetes_tpu.state.layout import Capacities


@dataclass
class AccountedPod:
    """Removal + refill record for one accounted pod."""

    node_name: str
    requests: np.ndarray
    nonzero: np.ndarray
    port_onehot: np.ndarray
    match_row: np.ndarray    # f32[UQ] at accounting time (refilled on growth)
    carry_row: np.ndarray    # f32[UE] carried-term multiplicities
    namespace: str
    labels: dict
    vol_any_row: np.ndarray | None = None   # f32[UV] conflict-atom counts
    vol_rw_row: np.ndarray | None = None
    att_row: np.ndarray | None = None       # f32[UA] attach atoms


class StateDB:
    def __init__(self, caps: Capacities, mesh=None, volume_ctx=None):
        self.caps = caps
        self.mesh = mesh
        self.volume_ctx = volume_ctx  # VolumeContext for claim resolution
        self.host: ClusterState = empty_state(caps)
        self.table = NodeTable(
            caps, shards=(mesh.size if mesh is not None else 1))
        self._accounted: dict[str, AccountedPod] = {}
        self._dirty_nodes = True    # static node fields changed
        self._dirty_ledger = True   # requested/nonzero/ports changed on host
        self._dirty_affinity = False  # podsel/term counts changed on host only
        self._device: ClusterState | None = None
        # exact ledger rows behind _dirty_ledger/_dirty_affinity: when the
        # set is known and small, flush() scatters just those rows into the
        # device ledger (one batched transfer) instead of re-uploading whole
        # [N, W] arrays; _dirty_rows_all falls back to the full path
        self._dirty_rows: set[int] = set()
        self._dirty_rows_all = False
        self._row_updaters: dict = {}   # (fields, K_padded) -> jitted scatter
        # flush transfer accounting (plain ints mirrored to the obs
        # registry): rows_total counts ledger rows uploaded, transfers_total
        # host->device upload operations, full_total whole-state uploads —
        # the "no full-cluster host materialization on the hot path" figure
        self.flush_rows_total = 0
        self.flush_transfers_total = 0
        self.flush_full_total = 0
        self.flush_bytes_total = 0
        from kubernetes_tpu.obs import REGISTRY
        self._m_rows = REGISTRY.counter(
            "statedb_flush_rows_total",
            "ledger rows uploaded to device by StateDB.flush")
        self._m_transfers = REGISTRY.counter(
            "statedb_flush_transfers_total",
            "host->device transfers issued by StateDB.flush")
        self._m_bytes = REGISTRY.counter(
            "statedb_flush_bytes_total",
            "host->device bytes uploaded by StateDB.flush (the upload "
            "side of the transfer ledger; readback is "
            "device_readback_bytes_total)")

    # ---- node lifecycle ----

    def upsert_node(self, node: Node) -> None:
        row = self.table.assign_row(node.metadata.name)
        _fill_node_row(self.host, self.table, row, node)
        self.table.bump(row)
        self._dirty_nodes = True

    def remove_node(self, name: str) -> None:
        if name not in self.table.row_of:
            return
        row = self.table.release_row(name)
        for key in [k for k, v in self._accounted.items()
                    if v.node_name == name]:
            del self._accounted[key]
        from kubernetes_tpu.state.cluster_state import NODE_AXIS_FIELDS
        for field in NODE_AXIS_FIELDS:
            arr = getattr(self.host, field)
            arr[row] = -1 if field == "topology" else 0
        self._dirty_nodes = True
        self._dirty_ledger = True

    def has_node(self, name: str) -> bool:
        return name in self.table.row_of

    # ---- pod accounting (bound + assumed) ----

    def _apply_pod(self, row: int, acc: AccountedPod, sign: int) -> None:
        self._dirty_rows.add(row)
        self.host.requested[row] += sign * acc.requests
        self.host.nonzero_requested[row] += sign * acc.nonzero
        self.host.port_count[row] += sign * acc.port_onehot
        self.host.podsel_count[row] += sign * acc.match_row
        self.host.term_count[row] += sign * acc.carry_row
        if acc.vol_any_row is not None:
            self.host.vol_any[row] += sign * acc.vol_any_row
            self.host.vol_rw[row] += sign * acc.vol_rw_row
        if acc.att_row is not None:
            self.host.attach_count[row] += sign * acc.att_row
        self.table.bump(row)

    def add_pod(self, pod: Pod, node_name: str | None = None, *,
                mirror_only: bool = False) -> bool:
        """Account a pod against its node. Returns False if the node is
        unknown (cache-miss pods are skipped, like the reference cache).
        Batch commits go through the vectorized `commit_batch` instead.

        mirror_only: host-side bookkeeping for a change already present in
        the device ledger — don't mark dirty.
        """
        node_name = node_name or pod.spec.node_name
        row = self.table.row_of.get(node_name)
        if row is None:
            return False
        if pod.key in self._accounted:
            return True  # already accounted (assume then confirm)
        eids, _ = intern_pod_affinity_terms(self.table, pod)
        vol_any_row = vol_rw_row = att_row = None
        if pod.spec.volumes:
            from kubernetes_tpu.state.volumes import EMPTY_CONTEXT

            vol_any_row, vol_rw_row = self.table.vol_rows(pod)
            att_row = self.table.attach_row(
                pod, self.volume_ctx or EMPTY_CONTEXT, permissive=True)
        acc = AccountedPod(
            node_name=node_name,
            requests=pod_requests(pod),
            nonzero=pod_nonzero_requests(pod),
            port_onehot=self.table.port_onehot(pod.host_ports()),
            match_row=pod_match_row(self.table, pod),
            carry_row=carried_term_row(self.table, eids),
            namespace=pod.metadata.namespace,
            labels=dict(pod.metadata.labels),
            vol_any_row=vol_any_row,
            vol_rw_row=vol_rw_row,
            att_row=att_row,
        )
        self._apply_pod(row, acc, +1)
        self._accounted[pod.key] = acc
        if not mirror_only:
            self._dirty_ledger = True
        return True

    def remove_pod(self, pod_key: str) -> None:
        acc = self._accounted.pop(pod_key, None)
        if acc is None:
            return
        row = self.table.row_of.get(acc.node_name)
        if row is None:
            return  # node vanished; its rows were zeroed already
        self._apply_pod(row, acc, -1)
        self._dirty_ledger = True

    def is_accounted(self, pod_key: str) -> bool:
        return pod_key in self._accounted

    @property
    def ledger_dirty(self) -> bool:
        """True when the next flush() will re-upload ledger/affinity/node
        arrays from host truth — a pipelined driver must settle any
        in-flight batch first, or its device-side charges get overwritten."""
        return (self._dirty_nodes or self._dirty_ledger or self._dirty_affinity
                or bool(self.table.pending_podsel_refresh))

    def adopt_result(self, result) -> None:
        """Chain the solver's (possibly still in-flight) full output ledger
        as the device truth without synchronizing — host mirroring happens
        at settle time via commit_result. Kernels a batch could not touch
        return the input arrays unchanged, so this is alias bookkeeping,
        not data movement."""
        if self._device is None:
            raise RuntimeError("adopt_result before flush")
        self._device = self._device.replace(
            requested=result.new_requested,
            nonzero_requested=result.new_nonzero,
            port_count=result.new_port_count,
            podsel_count=result.new_podsel,
            term_count=result.new_term,
            vol_any=result.new_vol_any,
            vol_rw=result.new_vol_rw,
            attach_count=result.new_attach)

    def mark_ledger_dirty(self) -> None:
        """Force the next flush() to re-upload the host ledger — used when the
        device-side ledger is known to carry charges the host truth does not
        (e.g. a solver assignment whose binding was rolled back). The stale
        device rows are unknown here, so the row-scatter fast path is off."""
        self._dirty_ledger = True
        self._dirty_rows_all = True

    # ---- device mirror ----

    def _refill_podsel(self) -> None:
        """Fill podsel_count columns for selector entries interned after pods
        were accounted (the accounted-pod analog of membership refills)."""
        if not self.table.pending_podsel_refresh:
            return
        from kubernetes_tpu.state.podaffinity import selector_matches

        for qid in self.table.pending_podsel_refresh:
            ns_key, canon = self.table.podsel_attrs[qid]
            for acc in self._accounted.values():
                if acc.match_row[qid]:
                    continue  # accounted after the intern: already counted
                if acc.namespace in ns_key and selector_matches(canon, acc.labels):
                    row = self.table.row_of.get(acc.node_name)
                    if row is not None:
                        self.host.podsel_count[row, qid] += 1.0
                        self._dirty_rows.add(row)
                        acc.match_row[qid] = 1.0
        self.table.pending_podsel_refresh.clear()
        self._dirty_affinity = True

    def _ledger_fields(self) -> tuple[str, ...]:
        """Ledger groups a dirty-ledger/affinity flush must refresh (the
        f32[N, W] arrays pod accounting mutates), in a stable order."""
        names = ["requested", "nonzero_requested", "port_count"]
        if self.table.vol_atoms:
            names += ["vol_any", "vol_rw"]
        if self.table.attach_atoms:
            names.append("attach_count")
        if self.table.podsels:
            names += ["podsel_count", "term_count"]
        return tuple(names)

    def _row_updater(self, fields: tuple[str, ...], k_padded: int):
        """Jitted per-shard row scatter: (device arrays, rows, packed
        values) -> updated arrays, keeping node-sharded layout under a
        mesh. Cached per (field set, padded row count) so steady-state
        flushes never recompile."""
        key = (fields, k_padded)
        fn = self._row_updaters.get(key)
        if fn is None:
            widths = [getattr(self.host, f).shape[1] for f in fields]

            def upd(arrays, rows, packed):
                out = []
                off = 0
                for arr, w in zip(arrays, widths):
                    out.append(arr.at[rows].set(packed[:, off:off + w]))
                    off += w
                return tuple(out)

            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                from kubernetes_tpu.parallel.mesh import NODE_AXIS
                nodes = NamedSharding(self.mesh, PartitionSpec(NODE_AXIS))
                repl = NamedSharding(self.mesh, PartitionSpec())
                fn = jax.jit(
                    upd,
                    in_shardings=(tuple(nodes for _ in fields), repl, repl),
                    out_shardings=tuple(nodes for _ in fields))
            else:
                fn = jax.jit(upd)
            self._row_updaters[key] = fn
        return fn

    def _scatter_rows(self, dev: ClusterState, rows: list[int]) -> ClusterState:
        """Coalesce the flush's dirty rows into ONE batched host->device
        transfer: gather every dirty ledger group's rows into a packed
        (K, sum W) matrix, upload it once, and scatter on device (per shard
        under a mesh — GSPMD routes each row update to its owning shard).
        K pads to the next power of two (duplicating row 0's update, which
        re-sets identical values) to bound compile-cache growth."""
        fields = self._ledger_fields()
        k = len(rows)
        kp = 1 << max(0, (k - 1).bit_length())
        idx = np.empty((kp,), np.int32)
        idx[:k] = rows
        idx[k:] = rows[0]
        packed = np.concatenate(
            [getattr(self.host, f)[idx] for f in fields], axis=1)
        fn = self._row_updater(fields, kp)
        new = fn(tuple(getattr(dev, f) for f in fields), idx, packed)
        self.flush_rows_total += k
        self.flush_transfers_total += 1
        self._m_rows.inc(k)
        self._m_transfers.inc()
        self._count_flush_bytes(int(packed.nbytes) + int(idx.nbytes))
        return dev.replace(**dict(zip(fields, new)))

    def flush(self) -> ClusterState:
        """Return the device view, re-uploading only what changed. Newly
        interned selector terms / requirements (from pod encoding) refill
        their membership columns first. Ledger dirtiness with a known,
        small row set takes the coalesced row-scatter path (one batched
        transfer); everything else re-uploads whole arrays."""
        self._refill_podsel()
        dirty_membership = apply_pending_refreshes(self.host, self.table)
        ledger_work = self._dirty_ledger or self._dirty_affinity
        rows = (sorted(self._dirty_rows)
                if ledger_work and not self._dirty_rows_all else None)
        can_scatter = (
            rows is not None and 0 < len(rows)
            and len(rows) * 4 <= self.caps.num_nodes)
        if self._device is None or self._dirty_nodes:
            dev = self._put(self.host)
            self.flush_full_total += 1
            self.flush_rows_total += self.caps.num_nodes
            self.flush_transfers_total += 1
            self._m_rows.inc(self.caps.num_nodes)
            self._m_transfers.inc()
        elif ledger_work or dirty_membership:
            dev = self._device
            if can_scatter and ledger_work:
                dev = self._scatter_rows(dev, rows)
            elif self._dirty_ledger:
                self.flush_full_total += 1
                self.flush_rows_total += self.caps.num_nodes
                self._m_rows.inc(self.caps.num_nodes)
                dev = dev.replace(
                    requested=self._put_arr(self.host.requested),
                    nonzero_requested=self._put_arr(self.host.nonzero_requested),
                    port_count=self._put_arr(self.host.port_count),
                )
                if self.table.vol_atoms:
                    dev = dev.replace(
                        vol_any=self._put_arr(self.host.vol_any),
                        vol_rw=self._put_arr(self.host.vol_rw))
                if self.table.attach_atoms:
                    dev = dev.replace(
                        attach_count=self._put_arr(self.host.attach_count))
            if not (can_scatter and ledger_work) and \
                    (self._dirty_ledger or self._dirty_affinity) and \
                    self.table.podsels:
                dev = dev.replace(
                    podsel_count=self._put_arr(self.host.podsel_count),
                    term_count=self._put_arr(self.host.term_count))
            if dirty_membership:
                dev = dev.replace(
                    sel_member=self._put_arr(self.host.sel_member),
                    req_member=self._put_arr(self.host.req_member),
                    topology=self._put_arr(self.host.topology),
                    volsel_member=self._put_arr(self.host.volsel_member),
                    attach_type=jax.device_put(np.asarray(self.host.attach_type)),
                    term_q=jax.device_put(np.asarray(self.host.term_q)),
                    term_tkey=jax.device_put(np.asarray(self.host.term_tkey)),
                    term_weight=jax.device_put(np.asarray(self.host.term_weight)),
                    term_kind=jax.device_put(np.asarray(self.host.term_kind)),
                    term_poison=jax.device_put(np.asarray(self.host.term_poison)),
                )
        else:
            return self._device
        self._device = dev
        self._dirty_nodes = False
        self._dirty_ledger = False
        self._dirty_affinity = False
        self._dirty_rows.clear()
        self._dirty_rows_all = False
        return dev

    def shard_occupancy(self) -> list[int]:
        """Live node rows per mesh shard (a single-element list without a
        mesh) — the bench[sharded] balance extra. Row addressing interleaves
        assignments across shards (NodeTable), so these stay within one of
        each other until nodes churn."""
        shards = self.mesh.size if self.mesh is not None else 1
        chunk = self.caps.num_nodes // shards
        counts = [0] * shards
        for row in self.table.row_of.values():
            counts[row // chunk] += 1
        return counts

    def commit_batch(self, result, fblob: np.ndarray,
                     committed: list[tuple[Pod, str, int]],
                     replace_device: bool = True,
                     coverage: tuple[bool, bool, bool] = (True, True, True),
                     ) -> None:
        """Adopt the solver's full output ledger as the device truth and
        mirror the same assignments into host numpy straight from the packed
        float blob (every mirrored ledger column is f32) — one vectorized
        scatter-add per ledger group instead of per-pod row arithmetic
        (no transfer either way, no re-matching).

        committed: (pod, node_name, batch_row_index) triples.

        replace_device=False commits the host mirror only — the pipelined
        driver already chained this batch's output via adopt_result() before
        dispatching its successor; re-replacing here would regress the
        device ledger to the older batch's arrays.

        coverage: solver.ledger_coverage(policy, flags) — rows that touch a
        group the compiled program passed through untracked must dirty that
        group for re-upload from host truth."""
        from kubernetes_tpu.state.pod_batch import _layout

        if self._device is None:
            raise RuntimeError("commit_batch before flush")
        if replace_device:
            self.adopt_result(result)
        live = [(pod, node_name, i) for pod, node_name, i in committed
                if pod.key not in self._accounted
                and node_name in self.table.row_of]
        if not live:
            return
        idx = np.fromiter((i for _, _, i in live), np.int64, len(live))
        rows = np.fromiter((self.table.row_of[n] for _, n, _ in live),
                           np.int64, len(live))
        layout, _f, _i = _layout(self.caps)
        gathered = fblob[idx]                       # (K, F) one fancy copy

        def colv(name):
            _blob, off, width, _trailing, _dtype = layout[name]
            return gathered[:, off:off + width]

        req = colv("requests")
        nz = colv("nonzero_requests")
        ports = colv("port_onehot")
        match = colv("pod_matches_q")
        carry = colv("pod_carries_e")
        want_rw = colv("vol_want_rw")
        vol_any = want_rw + colv("vol_want_ro")
        att = colv("att_onehot")

        host = self.host
        from kubernetes_tpu import native

        if native.scatter_add_cols is not None:
            # native path: one row-ordered pass per ledger group straight
            # from the gathered blob — no sort, no segmented reduction
            # (numpy's argsort+reduceat formulation below measured
            # ~17 µs/pod of the ~31 µs/pod commit phase at bench scale)
            def scat(dst, ref):
                _blob, off, width, _t, _d = layout[ref]
                if width == 0:
                    return 0
                return native.scatter_add_cols(dst, gathered, off, rows,
                                               width)

            scat(host.requested, "requests")
            scat(host.nonzero_requested, "nonzero_requests")
            scat(host.port_count, "port_onehot")
            scat(host.podsel_count, "pod_matches_q")
            scat(host.term_count, "pod_carries_e")
            if scat(host.vol_any, "vol_want_rw"):
                scat(host.vol_rw, "vol_want_rw")
            scat(host.vol_any, "vol_want_ro")
            scat(host.attach_count, "att_onehot")
        else:
            # one sort + segmented reduction over the WHOLE packed blob,
            # then per-group slices += at the unique rows — np.add.at is
            # 10-50× slower than reduceat on wide duplicate-heavy scatters
            order = np.argsort(rows, kind="stable")
            rows_sorted = rows[order]
            boundaries = np.flatnonzero(
                np.diff(rows_sorted, prepend=rows_sorted[0] - 1))
            uniq = rows_sorted[boundaries]
            sums = np.add.reduceat(gathered[order], boundaries, axis=0)

            def colsum(ref):
                _blob, off, width, _trailing, _dtype = layout[ref]
                return sums[:, off:off + width]

            host.requested[uniq] += colsum("requests")
            host.nonzero_requested[uniq] += colsum("nonzero_requests")
            host.port_count[uniq] += colsum("port_onehot")
            host.podsel_count[uniq] += colsum("pod_matches_q")
            host.term_count[uniq] += colsum("pod_carries_e")
            if vol_any.any():
                rw_sum = colsum("vol_want_rw")
                host.vol_any[uniq] += rw_sum + colsum("vol_want_ro")
                host.vol_rw[uniq] += rw_sum
            if att.any():
                host.attach_count[uniq] += colsum("att_onehot")
        gen0 = self.table._gen_counter
        self.table.generation[rows] = np.arange(
            gen0 + 1, gen0 + 1 + len(rows))
        self.table._gen_counter = gen0 + len(rows)

        accounted = self._accounted
        for k, (pod, node_name, _i) in enumerate(live):
            # labels shared, not copied: informer-cache objects are
            # read-only by contract, and this loop is on the e2e hot path
            accounted[pod.key] = AccountedPod(
                node_name, req[k], nz[k], ports[k], match[k], carry[k],
                pod.metadata.namespace, pod.metadata.labels,
                vol_any[k], want_rw[k], att[k])

        ipa_cov, vol_cov, attach_cov = coverage
        if not ipa_cov and (match.any() or carry.any()):
            self._dirty_affinity = True
            self._dirty_rows.update(rows.tolist())
        if not vol_cov and vol_any.any():
            self._dirty_ledger = True
            self._dirty_rows.update(rows.tolist())
        if not attach_cov and att.any():
            self._dirty_ledger = True
            self._dirty_rows.update(rows.tolist())

    def _count_flush_bytes(self, nbytes: int) -> None:
        self.flush_bytes_total += nbytes
        self._m_bytes.inc(nbytes)

    def _put(self, state: ClusterState) -> ClusterState:
        host = jax.tree.map(np.asarray, state)
        self._count_flush_bytes(sum(
            int(leaf.nbytes) for leaf in jax.tree_util.tree_leaves(host)))
        if self.mesh is not None:
            from kubernetes_tpu.parallel.mesh import shard_state
            return shard_state(state, self.mesh)
        # ONE batched transfer for the whole pytree — per-leaf puts pay a
        # per-call latency each
        return jax.device_put(host)

    def _put_arr(self, arr: np.ndarray):
        self.flush_transfers_total += 1
        self._m_transfers.inc()
        self._count_flush_bytes(int(np.asarray(arr).nbytes))
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from kubernetes_tpu.parallel.mesh import NODE_AXIS
            return jax.device_put(
                np.asarray(arr), NamedSharding(self.mesh, PartitionSpec(NODE_AXIS)))
        return jax.device_put(np.asarray(arr))
