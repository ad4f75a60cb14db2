"""Spreading priorities: SelectorSpread and ServiceAntiAffinity.

Re-expresses CalculateSpreadPriority (selector_spreading.go:100-188) and
CalculateAntiAffinityPriority (:210-270) over the interned pod-selector
universe: the pod carries ONE union entry id (match-any over its controller
selectors, built in state/spreading.py), per-node matching-pod counts live in
the scan-carried AffinityLedger (so earlier in-batch assignments are visible,
matching the serial assume semantics), and zone aggregation rides the virtual
GetZoneKey topology slot (layout.TOPO_SPREAD_ZONE).

Both reduces run over the *filtered* node list (PrioritizeNodes receives
filteredNodes, generic_scheduler.go:121) — hence the `feasible` mask inputs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from kubernetes_tpu.ops.interpod import AffinityLedger
from kubernetes_tpu.ops.priorities import EXACT, FLOOR_EPS
from kubernetes_tpu.state.cluster_state import ClusterState
from kubernetes_tpu.state.layout import MAX_PRIORITY, TOPO_SPREAD_ZONE

# zoneWeighting (selector_spreading.go:36)
ZONE_WEIGHT = 2.0 / 3.0


def selector_spread(state: ClusterState, spread_q, ledger: AffinityLedger,
                    feasible, domain_universe: int,
                    topo_onehot=None) -> jnp.ndarray:
    """f32[N] SelectorSpread scores for one pod (spread_q: traced i32 scalar,
    -1 = no matching controllers -> uniform MaxPriority,
    selector_spreading.go:157 initializes every fScore to MaxPriority and
    the selector-less path never lowers it)."""
    qc = jnp.clip(spread_q, 0)
    counts = ledger.podsel_count[:, qc]                   # f32[N]
    masked = jnp.where(feasible, counts, 0.0)
    max_node = jnp.max(masked)

    dom = state.topology[:, TOPO_SPREAD_ZONE]             # i32[N]
    has_zone = dom >= 0
    onehot = (jax.nn.one_hot(dom, domain_universe)        # [N, D], -1 -> 0row
              if topo_onehot is None else topo_onehot[TOPO_SPREAD_ZONE])
    zc = jnp.dot(onehot.T, masked, precision=EXACT)       # [D] per-zone counts
    zc_node = jnp.dot(onehot, zc, precision=EXACT)        # [N]
    have_zones = jnp.any(feasible & has_zone)
    max_zone = jnp.max(zc)

    node_score = jnp.where(
        max_node > 0,
        MAX_PRIORITY * (max_node - counts) / jnp.maximum(max_node, 1.0),
        float(MAX_PRIORITY))
    # maxCountByZone == 0 with haveZones is 0/0 in the reference (undefined
    # int(NaN)); deterministically: all zones equally empty -> MaxPriority
    zone_score = jnp.where(
        max_zone > 0,
        MAX_PRIORITY * (max_zone - zc_node) / jnp.maximum(max_zone, 1.0),
        float(MAX_PRIORITY))
    blended = jnp.where(
        have_zones & has_zone,
        node_score * (1.0 - ZONE_WEIGHT) + ZONE_WEIGHT * zone_score,
        node_score)
    score = jnp.trunc(blended + FLOOR_EPS)
    return jnp.where(spread_q < 0, float(MAX_PRIORITY), score)


def service_anti_affinity(state: ClusterState, svcanti_q, total,
                          ledger: AffinityLedger, feasible, slot,
                          domain_universe: int, topo_onehot=None) -> jnp.ndarray:
    """f32[N] ServiceAntiAffinity scores for one pod and one configured
    label (slot: traced i32 from PolicyRows). Labeled nodes score by how few
    same-service pods share their label value — counted over feasible
    labeled nodes only (getNodeClassificationByLabels runs on the filtered
    list, selector_spreading.go:232); unlabeled nodes score 0."""
    qc = jnp.clip(svcanti_q, 0)
    counts = jnp.where(svcanti_q >= 0, ledger.podsel_count[:, qc], 0.0)
    dom = state.topology[:, slot]                         # i32[N]
    labeled = dom >= 0
    contrib = jnp.where(feasible & labeled, counts, 0.0)
    onehot = (jax.nn.one_hot(dom, domain_universe)
              if topo_onehot is None else topo_onehot[slot])
    per_dom = jnp.dot(onehot.T, contrib, precision=EXACT)
    dom_count = jnp.dot(onehot, per_dom, precision=EXACT)  # [N]
    score = jnp.where(
        total > 0,
        jnp.trunc(MAX_PRIORITY * (total - dom_count)
                  / jnp.maximum(total, 1.0) + FLOOR_EPS),
        float(MAX_PRIORITY))
    # in-batch assume increments can push dom_count past the encode-time
    # total; the reference recomputes both from the same snapshot and can
    # never go negative — clamp to preserve that invariant
    score = jnp.maximum(score, 0.0)
    return jnp.where(labeled, score, 0.0)
