"""Pending-pod batch encoding: P pods -> padded arrays for one solver call.

The reference schedules one pod at a time (scheduler.go:253 scheduleOne); here
a whole batch of pending pods is encoded as a padded (P, ...) pytree and
scheduled in one device program. Padding rows have valid=False and are ignored
by the solver.

Selector terms and host ports are interned into the cluster's universes
(cluster_state.NodeTable), producing one-hot rows that pair with the node
membership matrices for MXU matching. Encoding a pod can therefore grow the
universes — callers holding a device state must apply pending membership
refreshes (cluster_state.apply_pending_refreshes / StateDB.flush) before
scheduling the batch.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np
from flax import struct

from kubernetes_tpu.api.objects import Pod
from kubernetes_tpu.state.cluster_state import (
    ClusterState,
    NodeTable,
    apply_pending_refreshes,
    carried_term_row,
    intern_pod_affinity_terms,
    pod_match_row,
    pod_nonzero_requests,
    pod_requests,
)
from kubernetes_tpu.state.layout import Capacities, CapacityError, Effect, Resource, TolOp
from kubernetes_tpu.utils.hashing import hash32, hash_lanes, hash_lanes_many


@struct.dataclass
class PodBatch:
    valid: np.ndarray           # bool[P]
    requests: np.ndarray        # f32[P, R]
    nonzero_requests: np.ndarray  # f32[P, 2] (cpu, mem) scoring requests
    port_onehot: np.ndarray     # f32[P, UP] — interned host-port counts
    sel_onehot: np.ndarray      # f32[P, US] — required selector terms
    sel_count: np.ndarray       # f32[P] — number of required terms
    tol_key: np.ndarray         # u32[P, T] hash32(key), 0 = empty key (matches all)
    tol_val_lo: np.ndarray      # u32[P, T] hash lanes of the toleration *value*
    tol_val_hi: np.ndarray      # u32[P, T]
    tol_op: np.ndarray          # i32[P, T] TolOp codes, NONE = unused slot
    tol_effect: np.ndarray      # i32[P, T] Effect codes, NONE = all effects
    node_name_lo: np.ndarray    # u32[P] spec.nodeName hash lanes, 0 = unset
    node_name_hi: np.ndarray    # u32[P]
    best_effort: np.ndarray     # bool[P] BestEffort QoS (pressure-check exemption)
    # required node affinity: OR over terms, each term an AND over interned
    # requirements (one-hot into the requirement universe UR)
    naff_has: np.ndarray        # bool[P] — pod carries a required NodeSelector
    naff_onehot: np.ndarray     # f32[P, AT, UR]
    naff_count: np.ndarray      # f32[P, AT] — requirements in term t
    naff_ok: np.ndarray         # bool[P, AT] — term is live (non-empty, parsed)
    # preferred node affinity terms (NodeAffinityPriority)
    pref_onehot: np.ndarray     # f32[P, TP, UR]
    pref_count: np.ndarray      # f32[P, TP]
    pref_weight: np.ndarray     # f32[P, TP] — 0 for unused/invalid slots
    # inter-pod affinity (state/podaffinity.py; ops/interpod.py)
    pod_matches_q: np.ndarray   # f32[P, UQ] — pod matches selector entry q
    pod_carries_e: np.ndarray   # f32[P, UE] — carried-term multiplicities
    paff_q: np.ndarray          # i32[P, IA] required affinity: selector id, -1 unused
    paff_tkey: np.ndarray       # i32[P, IA] topo slot (TKEY_INVALID impossible
                                #            here — encoded via ipaff_fail)
    panti_q: np.ndarray         # i32[P, IA] required anti-affinity
    panti_tkey: np.ndarray      # i32[P, IA]
    ipaff_fail: np.ndarray      # bool[P] — a required term is unschedulable
                                #           (empty topologyKey / bad selector)
    ppref_q: np.ndarray         # i32[P, IP] preferred terms, -1 unused
    ppref_tkey: np.ndarray      # i32[P, IP] slot or TKEY_DEFAULT_UNION
    ppref_w: np.ndarray         # f32[P, IP] signed weight (anti negative)
    # volumes (state/volumes.py atom grammars)
    vol_want_rw: np.ndarray     # f32[P, UV] conflict atoms wanted read-write
    vol_want_ro: np.ndarray     # f32[P, UV] conflict atoms wanted read-only
    att_onehot: np.ndarray      # f32[P, UA] attach atoms (0/1, unique per pod)
    att_fail: np.ndarray        # bool[P] MaxPDVolumeCount resolution error
    vz_onehot: np.ndarray       # f32[P, US] zone/region selector terms from PVs
    vz_count: np.ndarray        # f32[P]
    vz_fail: np.ndarray         # bool[P] VolumeZone resolution error
    vs_onehot: np.ndarray       # f32[P, UVS] PV node-affinity selectors
    vs_count: np.ndarray        # f32[P]
    vs_fail: np.ndarray         # bool[P] VolumeNode resolution error
    # spreading / service state (state/spreading.py)
    spread_q: np.ndarray        # i32[P] controller-selector union entry, -1 none
    spread_svc_q: np.ndarray    # i32[P] services-only union (ServiceSpreading)
    svcanti_q: np.ndarray       # i32[P] first-service selector entry, -1 none
    svcanti_total: np.ndarray   # f32[P] matching same-namespace pods anywhere
    svcaff_onehot: np.ndarray   # f32[P, UR] ServiceAffinity requirement terms
    svcaff_count: np.ndarray    # f32[P]
    svcaff_fail: np.ndarray     # bool[P] backfill pod unbound: hard error
    # image locality / prefer-avoid
    img_onehot: np.ndarray      # f32[P, UI] container-image multiplicities
    avoid_onehot: np.ndarray    # f32[P, UO] controllerRef signature, if interned
    # gang scheduling (all-or-nothing groups; ops/solver.py group revert).
    # gang_id is a batch-local group index, 0 = not a gang member (zeroed
    # padding rows are therefore automatically non-gang). Members of one
    # group MUST be contiguous in the batch — the scan's revert window is a
    # contiguous run; the driver never splits a group across batches.
    gang_id: np.ndarray         # i32[P] batch-local group index, 0 = none
    gang_min: np.ndarray        # i32[P] group minMember quorum (0 when no gang)
    # pod priority (spec.priority, admission-resolved from the
    # PriorityClass); read by the preemption pass — a pod may only evict
    # victims of strictly lower priority
    priority: np.ndarray        # i32[P]

    @property
    def batch_pods(self) -> int:
        return self.valid.shape[0]


def empty_batch(caps: Capacities) -> PodBatch:
    p = caps.batch_pods
    return PodBatch(
        valid=np.zeros((p,), np.bool_),
        requests=np.zeros((p, Resource.COUNT), np.float32),
        nonzero_requests=np.zeros((p, 2), np.float32),
        port_onehot=np.zeros((p, caps.port_universe), np.float32),
        sel_onehot=np.zeros((p, caps.selector_universe), np.float32),
        sel_count=np.zeros((p,), np.float32),
        tol_key=np.zeros((p, caps.toleration_slots), np.uint32),
        tol_val_lo=np.zeros((p, caps.toleration_slots), np.uint32),
        tol_val_hi=np.zeros((p, caps.toleration_slots), np.uint32),
        tol_op=np.zeros((p, caps.toleration_slots), np.int32),
        tol_effect=np.zeros((p, caps.toleration_slots), np.int32),
        node_name_lo=np.zeros((p,), np.uint32),
        node_name_hi=np.zeros((p,), np.uint32),
        best_effort=np.zeros((p,), np.bool_),
        naff_has=np.zeros((p,), np.bool_),
        naff_onehot=np.zeros((p, caps.affinity_terms, caps.req_universe), np.float32),
        naff_count=np.zeros((p, caps.affinity_terms), np.float32),
        naff_ok=np.zeros((p, caps.affinity_terms), np.bool_),
        pref_onehot=np.zeros((p, caps.pref_terms, caps.req_universe), np.float32),
        pref_count=np.zeros((p, caps.pref_terms), np.float32),
        pref_weight=np.zeros((p, caps.pref_terms), np.float32),
        pod_matches_q=np.zeros((p, caps.podsel_universe), np.float32),
        pod_carries_e=np.zeros((p, caps.term_universe), np.float32),
        paff_q=np.full((p, caps.interpod_slots), -1, np.int32),
        paff_tkey=np.zeros((p, caps.interpod_slots), np.int32),
        panti_q=np.full((p, caps.interpod_slots), -1, np.int32),
        panti_tkey=np.zeros((p, caps.interpod_slots), np.int32),
        ipaff_fail=np.zeros((p,), np.bool_),
        ppref_q=np.full((p, caps.interpod_pref_slots), -1, np.int32),
        ppref_tkey=np.zeros((p, caps.interpod_pref_slots), np.int32),
        ppref_w=np.zeros((p, caps.interpod_pref_slots), np.float32),
        vol_want_rw=np.zeros((p, caps.volume_universe), np.float32),
        vol_want_ro=np.zeros((p, caps.volume_universe), np.float32),
        att_onehot=np.zeros((p, caps.attach_universe), np.float32),
        att_fail=np.zeros((p,), np.bool_),
        vz_onehot=np.zeros((p, caps.selector_universe), np.float32),
        vz_count=np.zeros((p,), np.float32),
        vz_fail=np.zeros((p,), np.bool_),
        vs_onehot=np.zeros((p, caps.volsel_universe), np.float32),
        vs_count=np.zeros((p,), np.float32),
        vs_fail=np.zeros((p,), np.bool_),
        spread_q=np.full((p,), -1, np.int32),
        spread_svc_q=np.full((p,), -1, np.int32),
        svcanti_q=np.full((p,), -1, np.int32),
        svcanti_total=np.zeros((p,), np.float32),
        svcaff_onehot=np.zeros((p, caps.req_universe), np.float32),
        svcaff_count=np.zeros((p,), np.float32),
        svcaff_fail=np.zeros((p,), np.bool_),
        img_onehot=np.zeros((p, caps.image_universe), np.float32),
        avoid_onehot=np.zeros((p, caps.avoid_universe), np.float32),
        gang_id=np.zeros((p,), np.int32),
        gang_min=np.zeros((p,), np.int32),
        priority=np.zeros((p,), np.int32),
    )


def _batch_layout(caps: Capacities):
    """Column layout for blob transport: field -> (blob, offset, width,
    trailing_shape, dtype). Uploading a batch as ~45 small arrays pays ~45
    per-transfer latencies; two contiguous blobs (one f32, one i32 that
    also carries u32 bitcast and bools) pay two."""
    proto = empty_batch(caps)
    layout = {}
    offsets = {"f": 0, "i": 0}
    for name in PodBatch.__dataclass_fields__:
        arr = getattr(proto, name)
        trailing = arr.shape[1:]
        width = int(np.prod(trailing)) if trailing else 1
        blob = "f" if arr.dtype == np.float32 else "i"
        layout[name] = (blob, offsets[blob], width, trailing, arr.dtype)
        offsets[blob] += width
    return layout, offsets["f"], offsets["i"]


_LAYOUTS: dict = {}


def _layout(caps: Capacities):
    lay = _LAYOUTS.get(caps)
    if lay is None:
        lay = _LAYOUTS[caps] = _batch_layout(caps)
    return lay


def pack_batch(batch: PodBatch, caps: Capacities,
               out: tuple[np.ndarray, np.ndarray] | None = None):
    """Host-side: pack a numpy PodBatch into (f32[P, F], i32[P, I]) blobs.
    Pass `out` to reuse transfer buffers across batches."""
    layout, f_width, i_width = _layout(caps)
    p = batch.batch_pods
    if out is None:
        out = (np.empty((p, f_width), np.float32),
               np.empty((p, i_width), np.int32))
    fblob, iblob = out
    for name, (blob, off, width, _trailing, dtype) in layout.items():
        arr = getattr(batch, name)
        flat = arr.reshape(p, width)
        if blob == "f":
            fblob[:, off:off + width] = flat
        elif dtype == np.uint32:
            iblob[:, off:off + width] = flat.view(np.int32)
        else:
            iblob[:, off:off + width] = flat
    return fblob, iblob


def pack_row(batch: PodBatch, i: int, caps: Capacities):
    """Pack one encoded batch row into (f32[F], i32[I]) row vectors — the
    unit the EncodeCache stores, so a cache hit is two memcpys instead of
    ~45 per-field assignments."""
    layout, f_width, i_width = _layout(caps)
    frow = np.empty((f_width,), np.float32)
    irow = np.empty((i_width,), np.int32)
    for name, (blob, off, width, _trailing, dtype) in layout.items():
        flat = getattr(batch, name)[i].reshape(width)
        if blob == "f":
            frow[off:off + width] = flat
        elif dtype == np.uint32:
            irow[off:off + width] = flat.view(np.int32)
        else:
            irow[off:off + width] = flat
    return frow, irow


def blob_col(fblob, iblob, name: str, caps: Capacities, n: int | None = None):
    """Host-side view of one field's packed columns: [P(, W)] in storage
    dtype (u32 fields arrive bitcast as i32, bools as i32 0/1)."""
    layout, _f, _i = _layout(caps)
    blob, off, width, trailing, _dtype = layout[name]
    src = fblob if blob == "f" else iblob
    rows = src if n is None else src[:n]
    col = rows[:, off:off + width]
    return col.reshape((col.shape[0], *trailing)) if trailing else col[:, 0]


def packed_batch_flags(fblob, iblob, n: int, table, caps: Capacities):
    """BatchFlags from packed blobs (ops.solver.batch_flags equivalent for
    the blob-encoding driver path)."""
    from kubernetes_tpu.ops.solver import BatchFlags

    def any_(name):
        return bool(np.asarray(blob_col(fblob, iblob, name, caps, n)).any())

    def any_id(name):  # i32 id columns, -1 = unused
        return bool((np.asarray(blob_col(fblob, iblob, name, caps, n)) >= 0).any())

    from kubernetes_tpu.ops.solver import table_has_prefer_taints

    requests = np.asarray(blob_col(fblob, iblob, "requests", caps, n))
    return BatchFlags(
        ipa=bool(table.terms) or any_id("paff_q") or any_id("panti_q")
        or any_id("ppref_q") or any_("ipaff_fail"),
        spread=any_id("spread_q") or any_id("spread_svc_q"),
        svcanti=any_id("svcanti_q"),
        vol=any_("vol_want_rw") or any_("vol_want_ro"),
        attach=any_("att_onehot") or any_("att_fail"),
        tt=table_has_prefer_taints(table),
        na=bool((np.asarray(blob_col(fblob, iblob, "pref_weight", caps, n))
                 > 0).any()),
        ports=any_("port_onehot"),
        gpu=bool(requests[:, Resource.GPU].any()),
        storage=bool(requests[:, Resource.SCRATCH].any()
                     or requests[:, Resource.OVERLAY].any()),
        gang=bool((np.asarray(blob_col(fblob, iblob, "gang_id", caps, n))
                   > 0).any()),
        # absent (all-zero) priorities can never out-rank anything: the
        # preemption pass is provably neutral, so skip compiling it
        preempt=bool((np.asarray(blob_col(fblob, iblob, "priority", caps, n))
                      != 0).any()),
    )


def solve_rows(n: int, top: int, floor: int | None = None) -> int:
    """Rows a padded solve of n pods runs at: the smallest power of two
    that holds n, at least `floor`, at most `top`. `floor` defaults to
    top // 8, the scheduler's batch ladder (512, 1,024, 2,048, 4,096 at
    4,096 pods a batch); solversvc passes its own minimum bucket."""
    if floor is None:
        floor = top // 8
    return min(top, max(floor, 1 << max(0, int(n) - 1).bit_length()))


def solve_rungs(top: int) -> tuple[int, ...]:
    """Every row count solve_rows(n, top) gives, ascending: the rungs of
    the scheduler's batch ladder."""
    rungs = [solve_rows(1, top)]
    while rungs[-1] < top:
        rungs.append(solve_rows(rungs[-1] + 1, top))
    return tuple(rungs)


def unpack_batch(fblob, iblob, caps: Capacities) -> PodBatch:
    """Device-side (jit-traceable): rebuild the PodBatch pytree by slicing
    the blobs — pure views for XLA, no data movement."""
    import jax.numpy as jnp
    from jax import lax

    layout, _f, _i = _layout(caps)
    p = fblob.shape[0]
    fields = {}
    for name, (blob, off, width, trailing, dtype) in layout.items():
        src = fblob if blob == "f" else iblob
        col = src[:, off:off + width].reshape((p, *trailing))
        if dtype == np.uint32:
            col = lax.bitcast_convert_type(col, jnp.uint32)
        elif dtype == np.bool_:
            col = col != 0
        fields[name] = col
    return PodBatch(**fields)


def encode_pod_into(batch: PodBatch, i: int, pod: Pod, caps: Capacities,
                    table: NodeTable, ctx=None) -> None:
    batch.valid[i] = True
    batch.requests[i] = pod_requests(pod)
    batch.nonzero_requests[i] = pod_nonzero_requests(pod)
    batch.port_onehot[i] = table.port_onehot(pod.host_ports())

    batch.sel_onehot[i] = 0.0
    selector = pod.spec.node_selector
    for k, v in selector.items():
        batch.sel_onehot[i, table.intern_sel_term(k, v)] = 1.0
    batch.sel_count[i] = float(len(selector))

    tols = pod.spec.tolerations
    if len(tols) > caps.toleration_slots:
        raise CapacityError(f"pod {pod.key}: {len(tols)} tolerations > "
                            f"{caps.toleration_slots} slots")
    batch.tol_key[i] = 0
    batch.tol_val_lo[i] = 0
    batch.tol_val_hi[i] = 0
    batch.tol_op[i] = TolOp.NONE
    batch.tol_effect[i] = Effect.NONE
    # one native batch call hashes every toleration value (hash_lanes_many)
    value_lanes = hash_lanes_many([tol.value for tol in tols])
    for t, tol in enumerate(tols):
        batch.tol_key[i, t] = hash32(tol.key) if tol.key else 0
        batch.tol_val_lo[i, t], batch.tol_val_hi[i, t] = value_lanes[t]
        batch.tol_op[i, t] = TolOp.EXISTS if tol.operator == "Exists" else TolOp.EQUAL
        batch.tol_effect[i, t] = Effect.NAMES.get(tol.effect, Effect.NONE)

    if pod.spec.node_name:
        lo, hi = hash_lanes(pod.spec.node_name)
        batch.node_name_lo[i] = lo
        batch.node_name_hi[i] = hi
    else:
        batch.node_name_lo[i] = 0
        batch.node_name_hi[i] = 0
    batch.best_effort[i] = pod.is_best_effort()
    batch.priority[i] = pod.spec.priority
    _encode_node_affinity(batch, i, pod, caps, table)
    _encode_interpod_affinity(batch, i, pod, caps, table)
    _encode_volumes(batch, i, pod, caps, table, ctx)
    _encode_workloads(batch, i, pod, caps, table, ctx)


def _encode_workloads(batch: PodBatch, i: int, pod: Pod, caps: Capacities,
                      table: NodeTable, ctx) -> None:
    """Spreading entries, service (anti-)affinity, image locality and
    prefer-avoid rows (state/spreading.py, state/volumes.py)."""
    from kubernetes_tpu.state.context import EMPTY_CONTEXT
    from kubernetes_tpu.state.layout import ReqOp
    from kubernetes_tpu.state.spreading import (
        first_service_entry,
        service_affinity_terms,
        spread_entry,
    )
    from kubernetes_tpu.state.volumes import pod_controller_ref

    ctx = ctx or EMPTY_CONTEXT
    batch.spread_q[i] = spread_entry(pod, ctx, table)
    batch.spread_svc_q[i] = spread_entry(pod, ctx, table, services_only=True)
    batch.svcanti_q[i], batch.svcanti_total[i] = \
        first_service_entry(pod, ctx, table)
    # these entries were interned AFTER pod_matches_q was filled; the pod
    # matches its own union/service entries by construction (they are built
    # from selectors that select it), so set the columns directly — the
    # in-batch ledger needs them to count same-batch placements
    for q in (batch.spread_q[i], batch.spread_svc_q[i], batch.svcanti_q[i]):
        if q >= 0:
            batch.pod_matches_q[i, q] = 1.0

    batch.svcaff_onehot[i] = 0.0
    batch.svcaff_count[i] = 0.0
    batch.svcaff_fail[i] = False
    if ctx.service_affinity_labels:
        terms = service_affinity_terms(pod, ctx, ctx.service_affinity_labels)
        if terms is None:
            batch.svcaff_fail[i] = True
        else:
            rids = {table.intern_requirement(k, ReqOp.IN, (v,))
                    for k, v in terms}
            for rid in rids:
                batch.svcaff_onehot[i, rid] = 1.0
            batch.svcaff_count[i] = float(len(rids))

    # image locality: interned lookups only — an image on no node scores 0
    # everywhere, and interning it here keeps the row valid if a node
    # reports it later (img_size columns refill at node encode)
    batch.img_onehot[i] = 0.0
    for c in pod.spec.containers:
        if c.image:
            batch.img_onehot[i, table.intern_image(c.image)] += 1.0

    # prefer-avoid: lookup only — signatures are interned by node
    # annotations; an unseen signature cannot be avoided by any node
    batch.avoid_onehot[i] = 0.0
    sig = pod_controller_ref(pod)
    if sig is not None:
        oid = table.avoids.get(sig)
        if oid is not None:
            batch.avoid_onehot[i, oid] = 1.0


def _encode_volumes(batch: PodBatch, i: int, pod: Pod, caps: Capacities,
                    table: NodeTable, ctx) -> None:
    """Conflict/attach/zone/node-affinity rows for the pod's volumes. The
    per-predicate fail bits mirror the reference's error returns: each bit
    only takes effect when the corresponding predicate is in the policy."""
    from kubernetes_tpu.state.volumes import (
        EMPTY_CONTEXT,
        VolumeError,
        pod_volume_node_selectors,
        pod_zone_terms,
    )

    batch.vol_want_rw[i] = 0.0
    batch.vol_want_ro[i] = 0.0
    batch.att_onehot[i] = 0.0
    batch.att_fail[i] = False
    batch.vz_onehot[i] = 0.0
    batch.vz_count[i] = 0.0
    batch.vz_fail[i] = False
    batch.vs_onehot[i] = 0.0
    batch.vs_count[i] = 0.0
    batch.vs_fail[i] = False
    if not pod.spec.volumes:
        return
    ctx = ctx or EMPTY_CONTEXT

    any_row, rw_row = table.vol_rows(pod)
    batch.vol_want_rw[i] = rw_row
    batch.vol_want_ro[i] = any_row - rw_row

    try:
        batch.att_onehot[i] = table.attach_row(pod, ctx)
    except VolumeError:
        batch.att_fail[i] = True

    try:
        terms = {term: None for term in pod_zone_terms(pod, ctx)}  # dedup
        for key, value in terms:
            batch.vz_onehot[i, table.intern_sel_term(key, value)] = 1.0
        batch.vz_count[i] = float(len(terms))
    except VolumeError:
        batch.vz_fail[i] = True

    try:
        vsids = {table.intern_volsel(sel)
                 for sel in pod_volume_node_selectors(pod, ctx)}
        for vsid in vsids:
            batch.vs_onehot[i, vsid] = 1.0
        batch.vs_count[i] = float(len(vsids))
    except VolumeError:
        batch.vs_fail[i] = True


def _encode_interpod_affinity(batch: PodBatch, i: int, pod: Pod,
                              caps: Capacities, table: NodeTable) -> None:
    """Encode the pod's own pod-(anti-)affinity terms and (provisionally) its
    match/carry rows. The rows depend on the *final* universe contents, so
    batch encoders must re-run fill_batch_affinity after every pod has
    interned its terms; the inline fill here keeps the single-pod path
    (extender) correct without a second call."""
    from kubernetes_tpu.state.layout import TKEY_INVALID
    from kubernetes_tpu.state.podaffinity import PARSE_ERROR

    eids, terms = intern_pod_affinity_terms(table, pod)

    fail = False
    for lst, q_arr, tk_arr in ((terms.aff_req, batch.paff_q, batch.paff_tkey),
                               (terms.anti_req, batch.panti_q, batch.panti_tkey)):
        if len(lst) > caps.interpod_slots:
            raise CapacityError(
                f"pod {pod.key}: {len(lst)} required pod-affinity terms > "
                f"{caps.interpod_slots} slots")
        q_arr[i] = -1
        for t_idx, t in enumerate(lst):
            tk = table.tkey_code(t.topology_key, required=True)
            if tk == TKEY_INVALID or t.selector == PARSE_ERROR:
                # empty topologyKey or unparseable selector on a required
                # term: the pod cannot schedule anywhere
                # (predicates.go:1014,1162,1191-1196)
                fail = True
                continue
            q_arr[i, t_idx] = table.intern_podsel(t.namespaces, t.selector)
            tk_arr[i, t_idx] = tk
    batch.ipaff_fail[i] = fail

    pref = ([(t, +1.0) for t in terms.aff_pref]
            + [(t, -1.0) for t in terms.anti_pref])
    pref = [(t, sign) for t, sign in pref if t.weight != 0]
    if len(pref) > caps.interpod_pref_slots:
        raise CapacityError(
            f"pod {pod.key}: {len(pref)} preferred pod-affinity terms > "
            f"{caps.interpod_pref_slots} slots")
    batch.ppref_q[i] = -1
    for t_idx, (t, sign) in enumerate(pref):
        batch.ppref_q[i, t_idx] = table.intern_podsel(t.namespaces, t.selector)
        batch.ppref_tkey[i, t_idx] = table.tkey_code(t.topology_key,
                                                     required=False)
        batch.ppref_w[i, t_idx] = sign * float(t.weight)

    batch.pod_matches_q[i] = pod_match_row(table, pod)
    batch.pod_carries_e[i] = carried_term_row(table, eids)


def fill_batch_affinity(batch: PodBatch, pods: Sequence[Pod],
                        table: NodeTable) -> None:
    """Recompute match/carry rows once the universes are final (terms
    interned by later pods in the batch, or by assigned pods)."""
    if not table.podsels and not table.terms:
        return  # no affinity anywhere: rows are already all-zero
    for i, pod in enumerate(pods):
        eids, _ = intern_pod_affinity_terms(table, pod)
        batch.pod_matches_q[i] = pod_match_row(table, pod)
        batch.pod_carries_e[i] = carried_term_row(table, eids)


def fill_batch_avoid(batch: PodBatch, pods: Sequence[Pod],
                     table: NodeTable) -> None:
    """Recompute prefer-avoid rows once node annotations have interned their
    signatures (avoid atoms only come from nodes; a batch encoded before its
    nodes would miss them)."""
    if not table.avoids:
        return
    from kubernetes_tpu.state.volumes import pod_controller_ref

    for i, pod in enumerate(pods):
        batch.avoid_onehot[i] = 0.0
        sig = pod_controller_ref(pod)
        if sig is not None:
            oid = table.avoids.get(sig)
            if oid is not None:
                batch.avoid_onehot[i, oid] = 1.0


def _valid_requirement(expr: dict) -> bool:
    """Mirror labels.NewRequirement validation (selector.go): operator must be
    known; In/NotIn need >=1 value; Exists/DoesNotExist need none; Gt/Lt need
    exactly one."""
    from kubernetes_tpu.state.layout import ReqOp

    op = expr.get("operator", "")
    values = expr.get("values") or []
    if op in (ReqOp.IN, ReqOp.NOT_IN):
        return len(values) >= 1
    if op in (ReqOp.EXISTS, ReqOp.DOES_NOT_EXIST):
        return len(values) == 0
    if op in (ReqOp.GT, ReqOp.LT):
        return len(values) == 1
    return False


def _encode_node_affinity(batch: PodBatch, i: int, pod: Pod, caps: Capacities,
                          table: NodeTable) -> None:
    from kubernetes_tpu.api.objects import parse_node_affinity

    req_terms, preferred = parse_node_affinity(pod.spec.affinity)
    batch.naff_onehot[i] = 0.0
    batch.naff_count[i] = 0.0
    batch.naff_ok[i] = False
    batch.naff_has[i] = req_terms is not None
    if req_terms is not None:
        if len(req_terms) > caps.affinity_terms:
            raise CapacityError(
                f"pod {pod.key}: {len(req_terms)} nodeSelectorTerms > "
                f"{caps.affinity_terms} slots")
        # a parse error in ANY term makes the whole term list match nothing
        # (nodeMatchesNodeSelectorTerms returns false on error,
        # predicates.go:628-631)
        poisoned = any(not _valid_requirement(e) for exprs in req_terms
                       for e in exprs)
        if not poisoned:
            for t, exprs in enumerate(req_terms):
                if not exprs:
                    continue  # empty term: labels.Nothing, matches no node
                # count distinct interned ids: duplicate expressions in a term
                # collapse to one one-hot column
                rids = {table.intern_requirement(
                    e.get("key", ""), e["operator"], tuple(e.get("values") or ()))
                    for e in exprs}
                for rid in rids:
                    batch.naff_onehot[i, t, rid] = 1.0
                batch.naff_count[i, t] = float(len(rids))
                batch.naff_ok[i, t] = True

    batch.pref_onehot[i] = 0.0
    batch.pref_count[i] = 0.0
    batch.pref_weight[i] = 0.0
    if preferred:
        if len(preferred) > caps.pref_terms:
            raise CapacityError(
                f"pod {pod.key}: {len(preferred)} preferred terms > "
                f"{caps.pref_terms} slots")
        for t, (weight, exprs) in enumerate(preferred):
            # weight<=0 skipped (node_affinity.go skips 0; API validation
            # forbids negatives); empty/invalid expressions never match, so
            # the slot contributes nothing
            if weight <= 0 or not exprs or any(not _valid_requirement(e)
                                               for e in exprs):
                continue
            rids = {table.intern_requirement(
                e.get("key", ""), e["operator"], tuple(e.get("values") or ()))
                for e in exprs}
            for rid in rids:
                batch.pref_onehot[i, t, rid] = 1.0
            batch.pref_count[i, t] = float(len(rids))
            batch.pref_weight[i, t] = float(weight)


def encode_pods(pods: Sequence[Pod], caps: Capacities, table: NodeTable,
                state: ClusterState | None = None, ctx=None) -> PodBatch:
    """Encode a batch against the cluster's universes. When `state` is given,
    membership columns for newly interned terms are refilled in place."""
    if len(pods) > caps.batch_pods:
        raise CapacityError(f"{len(pods)} pods > batch capacity {caps.batch_pods}")
    batch = empty_batch(caps)
    for i, pod in enumerate(pods):
        encode_pod_into(batch, i, pod, caps, table, ctx=ctx)
    fill_batch_affinity(batch, pods, table)
    if state is not None:
        apply_pending_refreshes(state, table)
    return batch


def encode_cluster(nodes, pods, caps: Capacities, assigned_pods=(), ctx=None):
    """One-shot fixture encoding: nodes (+ assigned pods) + pending pods with
    a shared universe, membership fully consistent. Returns
    (state, batch, table)."""
    from kubernetes_tpu.state.cluster_state import encode_nodes

    table = NodeTable(caps)
    batch = encode_pods(pods, caps, table, ctx=ctx)
    state, _ = encode_nodes(nodes, caps, assigned_pods=assigned_pods,
                            table=table, ctx=ctx)
    # assigned pods may have interned new selector entries, and nodes new
    # avoid signatures: refresh the batch rows against the final universes
    fill_batch_affinity(batch, pods, table)
    fill_batch_avoid(batch, pods, table)
    apply_pending_refreshes(state, table)
    table.pending_podsel_refresh.clear()  # counts were built post-interning
    return state, batch, table
