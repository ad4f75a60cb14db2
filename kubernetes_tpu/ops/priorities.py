"""Vectorized priority (scoring) kernels: f32[N] scores in [0, MaxPriority].

Each kernel re-expresses one reference PriorityMap/PriorityReduce pair
(signature plugin/pkg/scheduler/algorithm/types.go:36-42) as a vector op over
all nodes. The reference computes integer scores with int64 division; we
reproduce the truncation with explicit floor so scores match exactly on
integer-valued inputs.

Covered (reference plugin/pkg/scheduler/algorithm/priorities/):
- LeastRequestedPriority     (least_requested.go)        -> least_requested
- BalancedResourceAllocation (balanced_resource_allocation.go) -> balanced_allocation
- TaintTolerationPriority    (taint_toleration.go)       -> taint_toleration
- EqualPriority              (core/generic_scheduler.go:416) -> equal

SelectorSpread / InterPodAffinity / NodeAffinity arrive with the spreading and
affinity op sets (they need service/owner state and affinity-term encodings).

The per-pod function is vmapped over the batch; the per-priority goroutine
fan-out + reduce of the reference (generic_scheduler.go:352-364) becomes plain
vector arithmetic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from kubernetes_tpu.ops.predicates import count_untolerated_prefer_taints
from kubernetes_tpu.state.cluster_state import ClusterState
from kubernetes_tpu.state.layout import MAX_PRIORITY, Resource
from kubernetes_tpu.state.pod_batch import PodBatch

# The reference computes scores with exact int64 division; we use f32. When
# the true quotient is an exact integer, f32 rounding can land epsilon *below*
# it and floor() would lose a whole point. Nudging by FLOOR_EPS (far below the
# quotient granularity 10/capacity for any realistic node size) restores exact
# parity on representable inputs.
FLOOR_EPS = 1e-6

# Precision of every dot whose operand carries counts or sizes rather than
# 0/1 memberships: DEFAULT may round f32 operands to bf16 on the TPU's MXU
# (integers exact only to 256); HIGHEST keeps the full f32 operand.
EXACT = jax.lax.Precision.HIGHEST


def _unused_score(requested: jnp.ndarray, capacity: jnp.ndarray) -> jnp.ndarray:
    """calculateUnusedScore (least_requested.go:40): ((cap-req)*10)/cap with
    int64 truncation; 0 when cap == 0 or req > cap."""
    safe_cap = jnp.where(capacity == 0, 1.0, capacity)
    score = jnp.floor((capacity - requested) * MAX_PRIORITY / safe_cap + FLOOR_EPS)
    return jnp.where((capacity == 0) | (requested > capacity), 0.0, score)


def least_requested(state: ClusterState, pod: PodBatch, nonzero_requested=None) -> jnp.ndarray:
    """LeastRequestedPriorityMap: favor nodes with more free cpu+mem after
    placing the pod, using non-zero scoring requests."""
    nz = state.nonzero_requested if nonzero_requested is None else nonzero_requested
    total_cpu = nz[:, 0] + pod.nonzero_requests[0]
    total_mem = nz[:, 1] + pod.nonzero_requests[1]
    cpu_score = _unused_score(total_cpu, state.allocatable[:, Resource.CPU])
    mem_score = _unused_score(total_mem, state.allocatable[:, Resource.MEMORY])
    return jnp.floor((cpu_score + mem_score) / 2.0 + FLOOR_EPS)


def balanced_allocation(state: ClusterState, pod: PodBatch, nonzero_requested=None) -> jnp.ndarray:
    """BalancedResourceAllocation: favor nodes where cpu and mem utilization
    fractions are closest; 0 if either fraction exceeds 1."""
    nz = state.nonzero_requested if nonzero_requested is None else nonzero_requested
    cap_cpu = state.allocatable[:, Resource.CPU]
    cap_mem = state.allocatable[:, Resource.MEMORY]
    safe_cpu = jnp.where(cap_cpu == 0, 1.0, cap_cpu)
    safe_mem = jnp.where(cap_mem == 0, 1.0, cap_mem)
    cpu_frac = (nz[:, 0] + pod.nonzero_requests[0]) / safe_cpu
    mem_frac = (nz[:, 1] + pod.nonzero_requests[1]) / safe_mem
    diff = jnp.abs(cpu_frac - mem_frac)
    score = jnp.trunc((1.0 - diff) * MAX_PRIORITY + FLOOR_EPS)
    bad = (cpu_frac >= 1.0) | (mem_frac >= 1.0) | (cap_cpu == 0) | (cap_mem == 0)
    return jnp.where(bad, 0.0, score)


def taint_toleration_from_counts(counts: jnp.ndarray, feasible: jnp.ndarray) -> jnp.ndarray:
    """The reduce half of TaintToleration (taint_toleration.go:73-96) from
    precomputed per-node intolerable counts: (1 - count/max)*MaxPriority
    truncated, all-MaxPriority when max == 0.

    The reference reduce runs over the *filtered* node list
    (generic_scheduler.go:121 passes filteredNodes to PrioritizeNodes), so the
    max is taken over `feasible` nodes.
    """
    counts = jnp.where(feasible, counts.astype(jnp.float32), 0.0)
    max_count = jnp.max(counts)
    return jnp.where(
        max_count > 0,
        jnp.trunc((1.0 - counts / jnp.maximum(max_count, 1.0)) * MAX_PRIORITY + FLOOR_EPS),
        float(MAX_PRIORITY),
    )


def taint_toleration(state: ClusterState, pod: PodBatch, feasible=None) -> jnp.ndarray:
    """TaintToleration map+reduce: fewer untolerated PreferNoSchedule taints
    is better; normalized against the per-pod max count."""
    counts = count_untolerated_prefer_taints(state, pod)
    return taint_toleration_from_counts(
        counts, state.valid if feasible is None else feasible)


def node_affinity_counts(state: ClusterState, pod: PodBatch) -> jnp.ndarray:
    """The map half of NodeAffinityPriority (node_affinity.go
    CalculateNodeAffinityPriorityMap): per node, the total weight of preferred
    scheduling terms whose selector matches the node's labels. One matmul per
    pod: `pref_onehot[TP, UR] @ req_member[N, UR].T`, a term matches when all
    its requirements do."""
    term_sat = pod.pref_onehot @ state.req_member.T            # f32[TP, N]
    matches = (term_sat >= pod.pref_count[:, None]) & (pod.pref_weight[:, None] > 0)
    return jnp.sum(jnp.where(matches, pod.pref_weight[:, None], 0.0), axis=0)


def normalized_from_counts(counts: jnp.ndarray, feasible: jnp.ndarray) -> jnp.ndarray:
    """NormalizeReduce-style reduce (node_affinity.go
    CalculateNodeAffinityPriorityReduce): score = int(MaxPriority * count /
    maxCount) over the filtered node list; all zero when maxCount == 0."""
    counts = jnp.where(feasible, counts.astype(jnp.float32), 0.0)
    max_count = jnp.max(counts)
    return jnp.where(
        max_count > 0,
        jnp.trunc(counts * MAX_PRIORITY / jnp.maximum(max_count, 1.0) + FLOOR_EPS),
        0.0,
    )


def node_affinity(state: ClusterState, pod: PodBatch, feasible=None) -> jnp.ndarray:
    """NodeAffinityPriority map+reduce."""
    counts = node_affinity_counts(state, pod)
    return normalized_from_counts(
        counts, state.valid if feasible is None else feasible)


def equal(state: ClusterState, pod: PodBatch) -> jnp.ndarray:
    """EqualPriority (generic_scheduler.go:416): weight-1 constant score."""
    return jnp.ones(state.valid.shape[0], dtype=jnp.float32)


def _used_score(requested: jnp.ndarray, capacity: jnp.ndarray) -> jnp.ndarray:
    """calculateUsedScore (most_requested.go:51): (req*10)/cap truncated;
    0 when cap == 0 or req > cap."""
    safe_cap = jnp.where(capacity == 0, 1.0, capacity)
    score = jnp.floor(requested * MAX_PRIORITY / safe_cap + FLOOR_EPS)
    return jnp.where((capacity == 0) | (requested > capacity), 0.0, score)


def most_requested(state: ClusterState, pod: PodBatch,
                   nonzero_requested=None) -> jnp.ndarray:
    """MostRequestedPriorityMap (most_requested.go:32): the bin-packing
    mirror of LeastRequested — favor nodes with higher cpu+mem utilization
    after placing the pod."""
    nz = state.nonzero_requested if nonzero_requested is None else nonzero_requested
    total_cpu = nz[:, 0] + pod.nonzero_requests[0]
    total_mem = nz[:, 1] + pod.nonzero_requests[1]
    cpu_score = _used_score(total_cpu, state.allocatable[:, Resource.CPU])
    mem_score = _used_score(total_mem, state.allocatable[:, Resource.MEMORY])
    return jnp.floor((cpu_score + mem_score) / 2.0 + FLOOR_EPS)


# ImageLocality size bounds (balanced_resource_allocation.go:33-35)
MIN_IMG_SIZE = 23.0 * 1024 * 1024
MAX_IMG_SIZE = 1000.0 * 1024 * 1024


def image_locality(state: ClusterState, pod: PodBatch) -> jnp.ndarray:
    """ImageLocalityPriorityMap (image_locality.go:32): bucket the summed
    bytes of the pod's images already present on the node into [0, 10]. One
    matvec: sums = img_size[N, UI] @ img_onehot[UI]."""
    sums = jnp.dot(state.img_size, pod.img_onehot, precision=EXACT)
    mid = jnp.floor(MAX_PRIORITY * (sums - MIN_IMG_SIZE)
                    / (MAX_IMG_SIZE - MIN_IMG_SIZE) + FLOOR_EPS) + 1.0
    return jnp.where(sums < MIN_IMG_SIZE, 0.0,
                     jnp.where(sums >= MAX_IMG_SIZE, float(MAX_PRIORITY), mid))


def node_prefer_avoid(state: ClusterState, pod: PodBatch) -> jnp.ndarray:
    """CalculateNodePreferAvoidPodsPriorityMap (node_prefer_avoid_pods.go:29):
    0 on nodes whose preferAvoidPods annotation names the pod's RC/RS
    controller, MaxPriority elsewhere (registered at weight 10000 so it
    dominates, defaults.go:225)."""
    hit = state.avoid_member @ pod.avoid_onehot
    return jnp.where(hit > 0, 0.0, float(MAX_PRIORITY))


def node_label_score(state: ClusterState, onehot_row: jnp.ndarray,
                     presence: bool) -> jnp.ndarray:
    """CalculateNodeLabelPriorityMap (node_label.go:44): MaxPriority when the
    label's presence matches the preference. Pod-independent — computed once
    per batch from the PolicyRows Exists-requirement row."""
    exists = (state.req_member @ onehot_row) > 0
    match = exists if presence else ~exists
    return jnp.where(match, float(MAX_PRIORITY), 0.0)
