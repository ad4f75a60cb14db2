"""Inter-pod (anti-)affinity kernels: the O(pods x nodes x terms) case.

Re-expresses the reference's PodAffinityChecker (predicates.go:982
InterPodAffinityMatches, :1139 satisfiesExistingPodsAntiAffinity, :1181
satisfiesPodsAffinityAntiAffinity) and CalculateInterPodAffinityPriority
(interpod_affinity.go) as tensor ops over interned universes:

- selectors -> pod-selector universe UQ; `podsel_count[N, UQ]` counts matching
  pods per node; `total_q[UQ]` counts matching pods anywhere.
- existing-pod terms -> carried-term universe UE with per-entry attributes
  (selector id, topology slot, signed weight, kind); `term_count[N, UE]`
  counts carriers per node.
- topology domains -> per-slot domain ids in `topology[N, K]`; domain-level
  aggregates `dom_*[K, D, U]` turn "matching pod exists in my topology
  domain" into a gather instead of an O(N^2) comparison.

Hostname short-circuit: slot 0 domains are per-node (hostname label values
are assumed unique per node, which the encoder guarantees when the label is
absent), so hostname-scoped counts read the node-level arrays directly and
the domain axis D only needs to cover zone/region/custom-key cardinalities.

The empty-topologyKey preferred-term case ("same in any default failure
domain", priorityutil.Topologies) is computed exactly by inclusion-exclusion:
union = hostC*(1-has_zone)*(1-has_region) + zoneC + regionC - zoneRegionC,
using the virtual composite (zone, region) slot (layout.TOPO_ZONE_REGION).

All counts flow through the solver scan so earlier in-batch assignments are
visible to later pods, matching the serial scheduleOne semantics.

Exactness: the counts are integers carried in f32, exact to 2**24. A dot at
DEFAULT precision may round its f32 operands to bf16 on the TPU's MXU,
which holds integers exactly only to 256, so every dot with a count operand
runs at `EXACT` (HIGHEST) precision; one-hot x one-hot dots stay at DEFAULT.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import struct

from kubernetes_tpu.ops.priorities import EXACT, FLOOR_EPS
from kubernetes_tpu.state.cluster_state import ClusterState
from kubernetes_tpu.state.layout import (
    MAX_PRIORITY,
    TKEY_DEFAULT_UNION,
    TKEY_INVALID,
    TOPO_HOSTNAME,
    TOPO_REGION,
    TOPO_ZONE,
    TOPO_ZONE_REGION,
    TermKind,
)
from kubernetes_tpu.state.pod_batch import PodBatch


@struct.dataclass
class AffinityLedger:
    """The scan-carried inter-pod affinity state. Term-universe fields are
    None (empty pytree) when only the podsel consumers (SelectorSpread /
    ServiceAntiAffinity) are active — they read node-level podsel counts
    only."""

    podsel_count: jnp.ndarray   # f32[N, UQ]
    total_q: jnp.ndarray        # f32[UQ]
    term_count: object = None   # f32[N, UE] | None
    dom_podsel: object = None   # f32[K, D, UQ] | None
    dom_term: object = None     # f32[K, D, UE] | None
    total_e: object = None      # f32[UE] | None


def domain_aggregates(topology: jnp.ndarray, counts: jnp.ndarray,
                      domain_universe: int) -> jnp.ndarray:
    """f32[K, D, U]: per-domain sums of per-node counts. one_hot maps the
    -1 (no label) sentinel to an all-zero row, excluding those nodes."""
    onehot = jax.nn.one_hot(topology, domain_universe, axis=-1)  # [N, K, D]
    return jnp.einsum("nkd,nu->kdu", onehot, counts, precision=EXACT)


def topology_onehot(topology: jnp.ndarray, domain_universe: int) -> jnp.ndarray:
    """f32[K, N, D]: one-hot of each node's domain id per topology slot
    (-1 sentinel -> zero row). Pod-independent — compute once per batch and
    thread through the per-pod kernels so domain->node broadcasts become MXU
    matmuls instead of device gathers (dynamic gathers serialize on the TPU
    scalar core and dominated the round-1 solve)."""
    return jnp.transpose(jax.nn.one_hot(topology, domain_universe, axis=-1),
                         (1, 0, 2))


def make_ledger(state: ClusterState, domain_universe: int,
                with_terms: bool = True) -> AffinityLedger:
    if not with_terms:
        return AffinityLedger(
            podsel_count=state.podsel_count,
            total_q=jnp.sum(state.podsel_count, axis=0),
        )
    return AffinityLedger(
        podsel_count=state.podsel_count,
        term_count=state.term_count,
        dom_podsel=domain_aggregates(state.topology, state.podsel_count,
                                     domain_universe),
        dom_term=domain_aggregates(state.topology, state.term_count,
                                   domain_universe),
        total_q=jnp.sum(state.podsel_count, axis=0),
        total_e=jnp.sum(state.term_count, axis=0),
    )


def _slot_counts(topo_onehot: jnp.ndarray, node_counts: jnp.ndarray,
                 dom_counts: jnp.ndarray) -> jnp.ndarray:
    """f32[K, N, U]: for every topology slot k, the count of matches in node
    n's k-domain. Slot 0 (hostname) reads node-level counts directly; the
    rest broadcast domain aggregates back to nodes in ONE batched
    [K,N,D]x[K,D,U] contraction (the -1 sentinel's zero one-hot row masks
    automatically). K separate [N,D]@[D,U] matmuls at U≈32 ran at ~25%
    lane efficiency each and were the measured device wall of the interpod
    config (PERF.md r4); the batched einsum tiles the K axis together."""
    out = jnp.einsum("knd,kdu->knu", topo_onehot, dom_counts,
                     precision=EXACT)
    return out.at[0].set(node_counts)


def _union_counts(topology: jnp.ndarray, slot_counts: jnp.ndarray,
                  node_counts: jnp.ndarray) -> jnp.ndarray:
    """f32[N, U]: matches in the union of the default failure domains
    (inclusion-exclusion; see module docstring)."""
    has_zone = (topology[:, TOPO_ZONE] >= 0)[:, None]
    has_region = (topology[:, TOPO_REGION] >= 0)[:, None]
    host_part = node_counts * (~has_zone) * (~has_region)
    return (host_part + slot_counts[TOPO_ZONE] + slot_counts[TOPO_REGION]
            - slot_counts[TOPO_ZONE_REGION])


def _counts_by_tkey(tkey: jnp.ndarray, slot_counts: jnp.ndarray,
                    union: jnp.ndarray) -> jnp.ndarray:
    """f32[N, U]: per-entry counts selected by each entry's topology code
    (tkey: i32[U]). TKEY_INVALID selects 0; TKEY_DEFAULT_UNION the union."""
    k_slots = slot_counts.shape[0]
    out = jnp.where(tkey[None, :] == TKEY_DEFAULT_UNION, union, 0.0)
    for k in range(k_slots):
        out = out + jnp.where(tkey[None, :] == k, slot_counts[k], 0.0)
    return out


def _scalar_count(q, tkey, slots, union_all) -> jnp.ndarray:
    """f32[N]: count for one (q, tkey) own-term slot (q, tkey traced
    scalars; q >= 0). slots: the f32[K, N, U] stack from _slot_counts —
    indexing it replaces the old per-term [N,D]@[D] matvecs (the stack is
    already computed for the carried-term selections, so XLA CSE shares
    it)."""
    k_slots = slots.shape[0]
    out = jnp.where(tkey == TKEY_DEFAULT_UNION, union_all[:, q], 0.0)
    for k in range(k_slots):
        out = out + jnp.where(tkey == k, slots[k, :, q], 0.0)
    return out


def interpod_feasible(state: ClusterState, pod, ledger: AffinityLedger,
                      topo_onehot=None) -> jnp.ndarray:
    """bool[N]: InterPodAffinityMatches for one pod against every node."""
    topology = state.topology
    if topo_onehot is None:
        topo_onehot = topology_onehot(topology, ledger.dom_podsel.shape[1])
    n = topology.shape[0]

    # -- existing pods' required anti-affinity (predicates.go:1139) --
    term_q = state.term_q
    match_e = jnp.where(term_q >= 0,
                        pod.pod_matches_q[jnp.clip(term_q, 0)], 0.0)  # f32[UE]
    anti = state.term_kind == TermKind.ANTI_REQ
    active = anti & (match_e > 0)
    # a carried required-anti term with an unparseable selector poisons all
    # scheduling while any carrier exists (error path, predicates.go:1156)
    poisoned = jnp.any(anti & state.term_poison & (ledger.total_e > 0))

    slot_e = _slot_counts(topo_onehot, ledger.term_count, ledger.dom_term)
    union_e = _union_counts(topology, slot_e, ledger.term_count)
    cnt_e = _counts_by_tkey(state.term_tkey, slot_e, union_e)      # [N, UE]
    # empty topologyKey on a required anti term rejects every node while a
    # carrier exists (predicates.go:1162-1165)
    invalid_term = (state.term_tkey == TKEY_INVALID) & (ledger.total_e > 0)
    violations = jnp.sum(jnp.where(active[None, :],
                                   cnt_e + invalid_term[None, :], 0.0), axis=1)
    ok = (violations == 0) & ~poisoned

    slot_q = _slot_counts(topo_onehot, ledger.podsel_count,
                          ledger.dom_podsel)
    union_q = _union_counts(topology, slot_q, ledger.podsel_count)

    # -- the pod's own required affinity terms (predicates.go:1189) --
    for t in range(pod.paff_q.shape[0]):
        q = pod.paff_q[t]
        used = q >= 0
        qc = jnp.clip(q, 0)
        cnt = _scalar_count(qc, pod.paff_tkey[t], slot_q, union_q)
        exists = ledger.total_q[qc] > 0
        self_match = pod.pod_matches_q[qc] > 0
        # term holds if a matching pod is in this node's domain; else only
        # the first-pod-of-collection escape applies (predicates.go:1193)
        term_ok = (cnt > 0) | (~exists & self_match)
        ok = ok & (~used | term_ok)

    # -- the pod's own required anti-affinity terms (predicates.go:1221) --
    for t in range(pod.panti_q.shape[0]):
        q = pod.panti_q[t]
        used = q >= 0
        qc = jnp.clip(q, 0)
        cnt = _scalar_count(qc, pod.panti_tkey[t], slot_q, union_q)
        ok = ok & (~used | (cnt == 0))

    return ok & ~pod.ipaff_fail & jnp.ones((n,), bool)


def interpod_counts(state: ClusterState, pod, ledger: AffinityLedger,
                    hard_weight: float, topo_onehot=None) -> jnp.ndarray:
    """f32[N]: the weighted-count map of CalculateInterPodAffinityPriority —
    the pod's own preferred terms plus the symmetric contributions of
    existing pods' terms (hard affinity weighted by hard_weight)."""
    topology = state.topology
    if topo_onehot is None:
        topo_onehot = topology_onehot(topology, ledger.dom_podsel.shape[1])

    slot_q = _slot_counts(topo_onehot, ledger.podsel_count, ledger.dom_podsel)
    union_q = _union_counts(topology, slot_q, ledger.podsel_count)
    counts = jnp.zeros((topology.shape[0],), jnp.float32)

    for t in range(pod.ppref_q.shape[0]):
        q = pod.ppref_q[t]
        used = q >= 0
        qc = jnp.clip(q, 0)
        cnt = _scalar_count(qc, pod.ppref_tkey[t], slot_q, union_q)
        counts = counts + jnp.where(used, pod.ppref_w[t] * cnt, 0.0)

    # symmetric: existing pods' terms matching this pod
    term_q = state.term_q
    match_e = jnp.where(term_q >= 0,
                        pod.pod_matches_q[jnp.clip(term_q, 0)], 0.0)
    eff_w = state.term_weight + hard_weight * (
        state.term_kind == TermKind.AFF_REQ).astype(jnp.float32)
    slot_e = _slot_counts(topo_onehot, ledger.term_count, ledger.dom_term)
    union_e = _union_counts(topology, slot_e, ledger.term_count)
    cnt_e = _counts_by_tkey(state.term_tkey, slot_e, union_e)
    counts = counts + jnp.sum(cnt_e * (match_e * eff_w)[None, :], axis=1)
    return counts


def interpod_score(counts: jnp.ndarray, feasible: jnp.ndarray) -> jnp.ndarray:
    """The reduce: fScore = MaxPriority * (c - min) / (max - min) with min and
    max initialized to 0 (interpod_affinity.go:214-233), truncated to int."""
    masked = jnp.where(feasible, counts, 0.0)
    max_c = jnp.maximum(jnp.max(masked), 0.0)
    min_c = jnp.minimum(jnp.min(masked), 0.0)
    spread = max_c - min_c
    score = jnp.trunc(MAX_PRIORITY * (counts - min_c)
                      / jnp.maximum(spread, 1.0) + FLOOR_EPS)
    return jnp.where(spread > 0, score, 0.0)


def ledger_add(ledger: AffinityLedger, state: ClusterState, pod, node,
               add: jnp.ndarray, with_terms: bool = True) -> AffinityLedger:
    """Account an assignment into the affinity ledger (add is 1.0 or 0.0)."""
    q_row = add * pod.pod_matches_q
    if not with_terms:
        return AffinityLedger(
            podsel_count=ledger.podsel_count.at[node].add(q_row),
            total_q=ledger.total_q + q_row,
        )
    e_row = add * pod.pod_carries_e
    doms = state.topology[node]                       # i32[K]
    k_idx = jnp.arange(doms.shape[0])
    mask = (doms >= 0) & (k_idx != TOPO_HOSTNAME)
    dmask = mask.astype(jnp.float32)[:, None]
    return AffinityLedger(
        podsel_count=ledger.podsel_count.at[node].add(q_row),
        term_count=ledger.term_count.at[node].add(e_row),
        dom_podsel=ledger.dom_podsel.at[k_idx, jnp.clip(doms, 0)].add(
            dmask * q_row[None, :]),
        dom_term=ledger.dom_term.at[k_idx, jnp.clip(doms, 0)].add(
            dmask * e_row[None, :]),
        total_q=ledger.total_q + q_row,
        total_e=ledger.total_e + e_row,
    )
