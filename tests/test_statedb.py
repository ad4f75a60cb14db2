"""StateDB incremental mirror: accounting, dirtiness, rollback."""

import jax
import numpy as np
import pytest

from kubernetes_tpu.api.objects import Node, Pod
from kubernetes_tpu.state import Capacities, ClusterState, Resource
from kubernetes_tpu.state.cluster_state import intern_pod_affinity_terms
from kubernetes_tpu.state.statedb import StateDB

CAPS = Capacities(num_nodes=8, batch_pods=4)


def mk_node(name, cpu="4"):
    return Node.from_dict({
        "metadata": {"name": name},
        "status": {"allocatable": {"cpu": cpu, "memory": "8Gi", "pods": "110"},
                   "conditions": [{"type": "Ready", "status": "True"}]}})


def mk_pod(name, node=None, cpu="500m", port=None):
    c = {"name": "c", "resources": {"requests": {"cpu": cpu}}}
    if port:
        c["ports"] = [{"containerPort": 80, "hostPort": port}]
    spec = {"containers": [c]}
    if node:
        spec["nodeName"] = node
    return Pod.from_dict({"metadata": {"name": name}, "spec": spec})


def test_pod_accounting_roundtrip():
    db = StateDB(CAPS)
    db.upsert_node(mk_node("n0"))
    row = db.table.row_of["n0"]
    assert db.add_pod(mk_pod("a", node="n0", port=8080))
    assert db.host.requested[row, Resource.CPU] == 500
    assert db.host.port_count[row, db.table.ports[8080]] == 1.0
    db.remove_pod("default/a")
    assert db.host.requested[row, Resource.CPU] == 0
    assert db.host.port_count[row].sum() == 0


def test_unknown_node_pod_skipped():
    db = StateDB(CAPS)
    assert not db.add_pod(mk_pod("a", node="ghost"))


def test_double_add_is_idempotent():
    db = StateDB(CAPS)
    db.upsert_node(mk_node("n0"))
    row = db.table.row_of["n0"]
    db.add_pod(mk_pod("a", node="n0"))
    db.add_pod(mk_pod("a", node="n0"))
    assert db.host.requested[row, Resource.CPU] == 500


def test_node_update_preserves_accounting():
    db = StateDB(CAPS)
    db.upsert_node(mk_node("n0", cpu="4"))
    db.add_pod(mk_pod("a", node="n0"))
    db.upsert_node(mk_node("n0", cpu="8"))
    row = db.table.row_of["n0"]
    assert db.host.allocatable[row, Resource.CPU] == 8000
    assert db.host.requested[row, Resource.CPU] == 500


def test_remove_node_zeroes_rows_and_drops_pods():
    db = StateDB(CAPS)
    db.upsert_node(mk_node("n0"))
    row = db.table.row_of["n0"]
    db.add_pod(mk_pod("a", node="n0"))
    db.remove_node("n0")
    assert not db.host.valid[row]
    assert db.host.requested[row].sum() == 0
    assert not db.is_accounted("default/a")
    # re-adding the node reuses the row cleanly
    db.upsert_node(mk_node("n1"))
    assert db.table.row_of["n1"] == row


def test_flush_caches_until_dirty():
    db = StateDB(CAPS)
    db.upsert_node(mk_node("n0"))
    dev1 = db.flush()
    dev2 = db.flush()
    assert dev1 is dev2  # clean: same device object
    db.add_pod(mk_pod("a", node="n0"))
    dev3 = db.flush()
    assert dev3 is not dev2
    row = db.table.row_of["n0"]
    assert float(np.asarray(dev3.requested)[row, Resource.CPU]) == 500
    # ledger-only flush reuses static arrays
    assert dev3.sel_member is dev2.sel_member


def test_commit_batch_keeps_host_and_device_equal():
    from kubernetes_tpu.ops.solver import SolverResult
    from kubernetes_tpu.state.encode_cache import EncodeCache
    from kubernetes_tpu.state.pod_batch import _layout

    db = StateDB(CAPS)
    db.upsert_node(mk_node("n0"))
    dev = db.flush()
    pod = mk_pod("a")
    # encode the pod into packed blobs, the commit transport
    _lay, f_width, i_width = _layout(CAPS)
    fblob = np.zeros((CAPS.batch_pods, f_width), np.float32)
    iblob = np.zeros((CAPS.batch_pods, i_width), np.int32)
    EncodeCache(CAPS, db.table).encode_packed_into(fblob, iblob, 0, pod)
    new_req = np.asarray(dev.requested).copy()
    row = db.table.row_of["n0"]
    new_req[row, Resource.CPU] += 500
    new_req[row, Resource.PODS] += 1
    import jax
    result = SolverResult(
        assignments=None, scores=None, feasible_counts=None,
        new_requested=jax.device_put(new_req),
        new_nonzero=dev.nonzero_requested, new_port_count=dev.port_count,
        rr_end=None, new_podsel=dev.podsel_count, new_term=dev.term_count,
        new_vol_any=dev.vol_any, new_vol_rw=dev.vol_rw,
        new_attach=dev.attach_count)
    db.commit_batch(result, fblob, [(pod, "n0", 0)])
    assert db.host.requested[row, Resource.CPU] == 500
    dev2 = db.flush()  # must NOT re-upload: ledger is already device truth
    np.testing.assert_allclose(np.asarray(dev2.requested), new_req)
    assert db.is_accounted("default/a")


# ---- the upload contract: a flush's device view is a snapshot ----------

# (8192, 8) host arrays: the shape at which a CPU device_put was seen
# reading its numpy source after returning in 200 of 200 tries
UPLOAD_CAPS = Capacities(
    num_nodes=8192, batch_pods=4, selector_universe=8, taint_universe=8,
    port_universe=8, req_universe=8, podsel_universe=8, term_universe=8,
    topology_slots=8, volume_universe=8, attach_universe=8,
    image_universe=8, avoid_universe=8, volsel_universe=8)
ZONE = "failure-domain.beta.kubernetes.io/zone"


def _affinity_pod(name, node=None):
    """A pod carrying a required zone pod-affinity term: interns a pod
    selector (podsel_count) and a carried term (term_count, term_*)."""
    spec = {"containers": [{"name": "c"}], "affinity": {"podAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [{
            "labelSelector": {"matchLabels": {"security": name}},
            "topologyKey": ZONE}]}}}
    if node:
        spec["nodeName"] = node
    return Pod.from_dict({"metadata": {"name": name,
                                       "labels": {"security": "S1"}},
                          "spec": spec})


def _dirty_full(db, i):
    db.upsert_node(mk_node(f"n{i}"))       # a node change: the full _put


def _dirty_ledger(db, i):
    db.add_pod(mk_pod(f"p{i}", node="n0"))
    db.mark_ledger_dirty()                 # whole ledger and affinity counts


def _dirty_membership(db, i):
    # a pending pod's encode interning a new node-selector term and pod
    # selector / carried term: membership columns and term attributes
    db.table.intern_sel_term("disk", f"d{i}")
    intern_pod_affinity_terms(db.table, _affinity_pod(f"S{i}"))


@pytest.mark.parametrize("dirty", [_dirty_full, _dirty_ledger,
                                   _dirty_membership],
                         ids=["full_put", "put_arr_ledger_affinity",
                              "membership"])
def test_flush_view_ignores_later_host_mutation(dirty):
    """A flushed device view must hold the host arrays as they were at the
    flush: the informer handlers mutate them in place as soon as the state
    lock is released, before the transfer has necessarily read them."""
    db = StateDB(UPLOAD_CAPS)
    db.upsert_node(mk_node("n0"))
    db.add_pod(_affinity_pod("S0", node="n0"))
    db.flush()
    for i in range(1, 7):
        dirty(db, i)
        dev = db.flush()
        snap = jax.tree.map(np.array, db.host)
        for name in ClusterState.__dataclass_fields__:
            arr = getattr(db.host, name)
            if arr.dtype == np.bool_:
                np.logical_not(arr, out=arr)
            else:
                arr += 1
        jax.block_until_ready(dev)
        moved = [name for name in ClusterState.__dataclass_fields__
                 if not np.array_equal(np.asarray(getattr(dev, name)),
                                       getattr(snap, name))]
        assert not moved, f"flush {i}: device view moved with the host " \
            f"arrays in {moved}"
        for name in ClusterState.__dataclass_fields__:
            getattr(db.host, name)[...] = getattr(snap, name)


def test_whole_array_flush_has_its_own_phase():
    """`ktpu.flush.upload_full` wraps the whole-array uploads only: inside
    a stage that holds metrics it adds the `upload_full` phase for a
    re-upload of the whole ledger, and nothing for a row scatter."""
    from kubernetes_tpu.obs.tracing import stage
    from kubernetes_tpu.scheduler.driver import SchedulerMetrics

    db = StateDB(CAPS)
    for i in range(4):
        db.upsert_node(mk_node(f"n{i}"))
    m = SchedulerMetrics()
    with stage("ktpu.dispatch.flush", metrics=m, phase="flush"):
        db.flush()
    assert "upload_full" in m.phase_s          # the first, full upload
    m = SchedulerMetrics()
    db.add_pod(mk_pod("a", node="n0"))          # 1 of 8 rows: scatter
    with stage("ktpu.dispatch.flush", metrics=m, phase="flush"):
        db.flush()
    assert "flush" in m.phase_s and "upload_full" not in m.phase_s
    db.mark_ledger_dirty()                      # rows unknown: whole arrays
    with stage("ktpu.dispatch.flush", metrics=m, phase="flush"):
        dev = db.flush()
    assert "upload_full" in m.phase_s
    np.testing.assert_array_equal(np.asarray(dev.requested),
                                  db.host.requested)
