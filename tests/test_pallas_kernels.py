"""Pallas fused static-mask kernel: bit-parity with the composed XLA path
and end-to-end solver parity under KTPU_PALLAS=1. The program always
compiles the kernel for the TPU; off the chip these tests ask for Pallas's
TPU interpret mode themselves (the `tpu_interpret` fixture)."""

import os

import jax
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from kubernetes_tpu.models.policy import DEFAULT_POLICY
from kubernetes_tpu.ops import predicates as preds
from kubernetes_tpu.ops.solver import schedule_batch
from kubernetes_tpu.state import Capacities, encode_cluster

from tests.test_solver import mk_node, mk_pod

CAPS = Capacities(num_nodes=128, batch_pods=16)


@pytest.fixture(autouse=True)
def tpu_interpret():
    """Run every kernel traced in a test under TPU interpret mode when no
    TPU is attached (on the chip the compiled kernel runs as-is)."""
    if jax.default_backend() == "tpu":
        yield
        return
    with pltpu.force_tpu_interpret_mode():
        yield


def fixture():
    from kubernetes_tpu.api.objects import Node

    nodes = [mk_node(f"n{i}",
                     labels={"disk": "ssd"} if i % 3 == 0 else {},
                     taints=[{"key": "k", "value": "v",
                              "effect": "NoSchedule"}] if i % 5 == 0 else [])
             for i in range(40)]
    # condition bits must be exercised: memory pressure (rejects only
    # BestEffort pods), disk pressure and NotReady (reject everyone)
    nodes.append(Node.from_dict({
        "metadata": {"name": "mempressure"},
        "status": {"allocatable": {"cpu": "4", "memory": "8Gi",
                                   "pods": "110"},
                   "conditions": [{"type": "Ready", "status": "True"},
                                  {"type": "MemoryPressure",
                                   "status": "True"}]}}))
    nodes.append(Node.from_dict({
        "metadata": {"name": "diskpressure"},
        "status": {"allocatable": {"cpu": "4", "memory": "8Gi",
                                   "pods": "110"},
                   "conditions": [{"type": "Ready", "status": "True"},
                                  {"type": "DiskPressure",
                                   "status": "True"}]}}))
    nodes.append(Node.from_dict({
        "metadata": {"name": "notready"},
        "status": {"allocatable": {"cpu": "4", "memory": "8Gi",
                                   "pods": "110"},
                   "conditions": [{"type": "Ready", "status": "False"}]}}))
    pods = [
        mk_pod("plain", cpu="100m"),
        mk_pod("selects", nodeSelector={"disk": "ssd"}),
        mk_pod("tolerates", tolerations=[{
            "key": "k", "operator": "Equal", "value": "v",
            "effect": "NoSchedule"}]),
        mk_pod("pinned", nodeName="n7"),
        mk_pod("besteffort"),
    ]
    return encode_cluster(nodes, pods, CAPS)


def test_fused_mask_matches_composed_xla():
    from kubernetes_tpu.ops.pallas_kernels import fused_static_mask

    state, batch, _table = fixture()
    import jax.numpy as jnp

    untol = jax.vmap(lambda p: 1.0 - preds._tolerated_universe(state, p)
                     .astype(jnp.float32))(batch)
    fused = fused_static_mask(
        state, batch.sel_onehot, batch.sel_count, untol,
        batch.best_effort, batch.node_name_lo, batch.node_name_hi)

    want = jax.vmap(lambda p: (
        state.valid
        & preds.node_schedulable(state, p)
        & preds.fits_host(state, p)
        & (state.sel_member @ p.sel_onehot >= p.sel_count)
        & preds.tolerates_node_taints(state, p)
        & preds.check_node_condition(state, p)
        & preds.check_memory_pressure(state, p)
        & preds.check_disk_pressure(state, p)))(batch)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(want))


def test_solver_parity_with_pallas_enabled():
    """Same fixture through schedule_batch with and without the fused
    kernel: assignments and scores must be identical."""
    state, batch, _table = fixture()
    saved = os.environ.pop("KTPU_PALLAS", None)  # force-plain baseline
    try:
        baseline = schedule_batch(state, batch, 0, DEFAULT_POLICY,
                                  caps=CAPS)
        os.environ["KTPU_PALLAS"] = "1"
        fused = schedule_batch(state, batch, 0, DEFAULT_POLICY, caps=CAPS)
    finally:
        if saved is None:
            os.environ.pop("KTPU_PALLAS", None)
        else:
            os.environ["KTPU_PALLAS"] = saved
    np.testing.assert_array_equal(np.asarray(baseline.assignments),
                                  np.asarray(fused.assignments))
    np.testing.assert_array_equal(np.asarray(baseline.scores),
                                  np.asarray(fused.scores))
    np.testing.assert_array_equal(np.asarray(baseline.feasible_counts),
                                  np.asarray(fused.feasible_counts))
