"""Batched assignment solver: the device-side replacement for `scheduleOne`.

The reference schedules strictly one pod at a time — `scheduleOne`
(plugin/pkg/scheduler/scheduler.go:253) pops a pod, runs findNodesThatFit +
PrioritizeNodes + selectHost over all nodes, assumes the result into the cache
(scheduler.go:188), and repeats — so pod K sees the resource claims of pods
0..K-1. This solver reproduces those semantics exactly while moving all the
work to the device:

- **Phase A (parallel over P x N)**: every assignment-independent predicate
  and score term evaluates for the whole batch at once via vmap — the
  expensive irregular matching (selectors, taints, conditions, host names).
- **Phase B (lax.scan over P, vector over N)**: a scan carries the running
  (requested, nonzero_requested, ports) ledger; each step evaluates only the
  assignment-*dependent* terms (resource fit, in-batch port conflicts,
  utilization scores), picks argmax with the reference's round-robin
  tie-break (selectHost, generic_scheduler.go:144-157), and scatters the
  pod's claims into the ledger — the batched analog of cache.AssumePod.

Scores are computed exactly as the reference's int64 math (floor-division
semantics in the priority kernels), so argmax decisions match the serial
scheduler decision-for-decision; parity is enforced against a pure-Python
serial reference in tests/serial_reference.py.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from flax import struct

from kubernetes_tpu.models.policy import (
    DEFAULT_POLICY,
    Policy,
    active_label_presence,
    active_label_priorities,
    active_service_anti,
)
from kubernetes_tpu.ops import interpod
from kubernetes_tpu.ops import predicates as preds
from kubernetes_tpu.ops import priorities as prios
from kubernetes_tpu.ops import spread as spreadops
from kubernetes_tpu.state.cluster_state import ClusterState
from kubernetes_tpu.state.layout import MAX_PRIORITY, Resource
from kubernetes_tpu.state.pod_batch import PodBatch

# Domain-axis size for inter-pod affinity aggregates; must equal the encoding
# Capacities.domain_universe (pass caps to schedule_batch to override).
DEFAULT_DOMAIN_UNIVERSE = 64


@dataclass(frozen=True)
class BatchFlags:
    """Batch-content gates: what this batch can actually exercise.

    The policy decides which kernels are *configured*; these flags record
    which of them the current batch (plus accounted state) can possibly
    affect, so the compiled program skips provably-neutral work. Each flag
    set False asserts a fact about the inputs under which the skipped
    kernel's contribution is exactly neutral (constant score shifts are
    re-added as scalars), keeping decisions bit-identical to ALL_ACTIVE.

    This is the batched analog of the reference's per-predicate
    short-circuits (e.g. the len(newVolumes)==0 quick return,
    predicates.go:296): the reference skips per pod at run time, a compiled
    tensor program must skip per batch at trace time. Hashable — part of
    the jit key; the driver computes it per batch (few distinct values in
    practice, so a handful of program variants).
    """

    ipa: bool = True      # own interpod terms in batch, or carried terms
    spread: bool = True   # any spread_q / spread_svc_q entry
    svcanti: bool = True  # any svcanti_q entry
    vol: bool = True      # any disk-conflict atom wanted
    attach: bool = True   # any attachable-volume atom (or resolve failure)
    tt: bool = True       # any PreferNoSchedule taint interned (TaintToleration
                          # counts can be nonzero) — else uniform MaxPriority
    na: bool = True       # any preferred node-affinity term in batch
    ports: bool = True    # any host port wanted: with none, PodFitsHostPorts
                          # is constant-true (conflicts = count @ 0) whatever
                          # the ledger — skip the kernel and the ledger update
    gpu: bool = True      # any GPU request in batch: with none, the GPU fit
                          # column never changes through the scan — fold into
                          # the assignment-independent Phase A fit
    storage: bool = True  # any scratch/overlay request in batch: same —
                          # the storage fallthrough logic (predicates.go:
                          # 590-605) becomes assignment-independent
    gang: bool = True     # any gang member (gang_id > 0) in batch: with none
                          # the group-revert carry extension is dead weight —
                          # whole-ledger selects per scan step — so the gate
                          # keeps the non-gang program untaxed
    preempt: bool = True  # any nonzero pod priority in batch: all-zero
                          # priorities can never out-rank a victim, so the
                          # victim-selection pass is provably neutral and the
                          # pre-preemption program compiles unchanged (the
                          # pass also needs a VictimTable — absent one,
                          # schedule_batch skips it at trace time regardless)
    explain: bool = False  # explainability probe: additionally emit the
                          # per-predicate cumulative survivor counts from
                          # _pod_eval's feasible-mask chain (i32[P, 6] over
                          # EXPLAIN_STAGES) so the driver can render
                          # reference-parity FailedScheduling reasons
                          # (findNodesThatFit's failedPredicateMap,
                          # core/generic_scheduler.go:163). Like scale_sim
                          # this defaults OFF and is never derived from
                          # batch content (packed_batch_flags leaves it
                          # False) — explain-off batches compile the
                          # bit-identical pre-explain program, and the
                          # extra per-step sums are traced only into
                          # programs the operator requests (KTPU_EXPLAIN).
    scale_sim: bool = False  # autoscaler probe solve: additionally emit the
                          # per-node placed count (how many batch pods landed
                          # on each node row) so a what-if simulation can
                          # score hypothetical rows. Unlike every flag above
                          # this one defaults OFF and is never derived from
                          # batch content (packed_batch_flags leaves it
                          # False) — real scheduling batches compile the
                          # bit-identical pre-autoscaler program, and the
                          # extra segment-sum is traced only into programs
                          # the autoscaler itself requests.


ALL_ACTIVE = BatchFlags()

# Stage labels for the BatchFlags.explain breakdown — the order of
# _pod_eval's feasible-mask chain. Column i holds the survivor count
# AFTER stage i; a gated-off stage repeats the previous count (it
# rejected nobody). "static" folds Phase A (selectors, taints,
# conditions, host name, ports-free fit, and — under the gpu/storage
# hoist — the static resource columns).
EXPLAIN_STAGES = ("static", "resources", "ports", "disk", "attach",
                  "interpod")


@dataclass(frozen=True)
class PolicyGates:
    """The compile-time kernel gates for one (policy, flags) pair — the
    single derivation consumed both by `schedule_batch` (what the compiled
    program tracks) and by `ledger_coverage` (what the driver may chain
    device-side at commit time). Weights are post-gating: a flag-neutralized
    kernel has weight 0 here and its constant contribution in const_score."""

    use_resources: bool
    use_ports: bool
    dyn_gpu: bool      # GPU fit must track the in-batch ledger
    dyn_storage: bool  # scratch/overlay fit must track the in-batch ledger
    w_lr: float
    w_mr: float
    w_ba: float
    w_tt: float
    w_na: float
    w_ip: float
    w_ss: float
    w_ssp: float
    svcanti: tuple
    use_ipa: bool
    use_svcanti: bool
    use_terms: bool
    use_ip_ledger: bool
    use_nodisk: bool
    attach_maxes: tuple
    const_score: float


def policy_gates(policy: Policy, flags: BatchFlags) -> PolicyGates:
    use_ipa = policy.has_predicate("MatchInterPodAffinity") and flags.ipa
    w_ss = policy.weight("SelectorSpreadPriority")
    w_ssp = policy.weight("ServiceSpreadingPriority")
    w_tt = policy.weight("TaintTolerationPriority")
    w_na = policy.weight("NodeAffinityPriority")
    svcanti = active_service_anti(policy)
    # flag-gated neutral terms: with every spread_q == -1, SelectorSpread
    # scores a uniform MaxPriority (selector_spreading.go:157) — a constant
    # shift that cannot change argmax but must stay in the reported score
    const_score = 0.0
    if w_ss and not flags.spread:
        const_score += w_ss * float(MAX_PRIORITY)
        w_ss = 0
    if w_ssp and not flags.spread:
        const_score += w_ssp * float(MAX_PRIORITY)
        w_ssp = 0
    # no PreferNoSchedule taint interned: every count is 0, the reduce
    # yields uniform MaxPriority (taint_toleration.go:90 maxCount==0 path)
    if w_tt and not flags.tt:
        const_score += w_tt * float(MAX_PRIORITY)
        w_tt = 0
    # no preferred node-affinity term in the batch: counts are all 0 and the
    # NormalizeReduce maxCount==0 path scores every node 0 — drop the kernel
    if w_na and not flags.na:
        w_na = 0
    w_ip = policy.weight("InterPodAffinityPriority") if flags.ipa else 0
    use_svcanti = bool(svcanti) and flags.svcanti
    use_terms = use_ipa or bool(w_ip)   # carried-term ledger structures
    return PolicyGates(
        use_resources=policy.has_predicate("GeneralPredicates",
                                           "PodFitsResources"),
        # no host port wanted anywhere in the batch: conflicts = count @ 0
        # == 0 on every node whatever the ledger — the predicate is
        # constant-true and the port ledger passes through untouched
        use_ports=policy.has_predicate("GeneralPredicates",
                                       "PodFitsHostPorts",
                                       "PodFitsPorts") and flags.ports,
        dyn_gpu=flags.gpu,
        dyn_storage=flags.storage,
        w_lr=policy.weight("LeastRequestedPriority"),
        w_mr=policy.weight("MostRequestedPriority"),
        w_ba=policy.weight("BalancedResourceAllocation"),
        w_tt=w_tt, w_na=w_na, w_ip=w_ip, w_ss=w_ss, w_ssp=w_ssp,
        svcanti=svcanti,
        use_ipa=use_ipa,
        use_svcanti=use_svcanti,
        use_terms=use_terms,
        use_ip_ledger=(use_terms or bool(w_ss) or bool(w_ssp) or use_svcanti),
        use_nodisk=policy.has_predicate("NoDiskConflict") and flags.vol,
        attach_maxes=policy.attach_maxes() if flags.attach else (),
        const_score=const_score,
    )


def ledger_coverage(policy: Policy, flags: BatchFlags) -> tuple[bool, bool, bool]:
    """(ipa, vol, attach): which state-ledger groups a program compiled with
    this (policy, flags) pair actually tracks through its scan carry —
    derived from the same PolicyGates the program itself compiles with. The
    driver uses this at commit time: a pod whose accounting rows touch an
    *untracked* group must dirty the host mirror so the next flush re-uploads
    truth the device pass-through ledger does not contain."""
    g = policy_gates(policy, flags)
    return g.use_ip_ledger, bool(g.use_nodisk), bool(g.attach_maxes)


def batch_flags(batch: PodBatch, n_pods: int, table) -> BatchFlags:
    """Compute the gates for `n_pods` encoded rows of a host-side batch
    against the current NodeTable (carried terms live in the state)."""
    import numpy as np

    def any_(arr):
        return bool(np.asarray(arr[:n_pods]).any())

    return BatchFlags(
        ipa=bool(table.terms) or any_(batch.paff_q >= 0)
        or any_(batch.panti_q >= 0) or any_(batch.ppref_q >= 0)
        or any_(batch.ipaff_fail),
        spread=any_(batch.spread_q >= 0) or any_(batch.spread_svc_q >= 0),
        svcanti=any_(batch.svcanti_q >= 0),
        vol=any_(batch.vol_want_rw) or any_(batch.vol_want_ro),
        attach=any_(batch.att_onehot) or any_(batch.att_fail),
        tt=table_has_prefer_taints(table),
        na=any_(batch.pref_weight > 0),
        ports=any_(batch.port_onehot),
        gpu=any_(batch.requests[:, Resource.GPU]),
        storage=any_(batch.requests[:, Resource.SCRATCH])
        or any_(batch.requests[:, Resource.OVERLAY]),
        gang=any_(batch.gang_id > 0),
        preempt=any_(batch.priority != 0),
    )


def table_has_prefer_taints(table) -> bool:
    """True when any interned taint can produce a nonzero TaintToleration
    count (the map input is taint_prefer_member, populated only by
    PreferNoSchedule taints)."""
    return any(effect == "PreferNoSchedule" for _k, _v, effect in table.taints)


@struct.dataclass
class VictimTable:
    """Per-node preemption candidates — the bound-pods tensor the victim-
    selection pass scans (the batched analog of selectNodesForPreemption's
    per-node pod lists, generic_scheduler.go). Built host-side by
    kubernetes_tpu/preemption/victims.py from the StateDB accounting:

    - slots within a node are sorted ASCENDING by (priority, pod key), so
      "evict lowest-priority victims first" is a prefix of the slot axis
      and (node, k) identifies the victim set reproducibly on the host;
    - `ok` is False for empty slots and for pods any covering
      PodDisruptionBudget refuses to disrupt (disruptionsAllowed <= 0) —
      the pass never selects a PDB-protected victim;
    - `prio` is INT32_MAX on empty slots so they sort last.
    """

    prio: jnp.ndarray   # i32[N, S] victim priority (INT32_MAX = empty slot)
    req: jnp.ndarray    # f32[N, S, R] victim resource requests (device units)
    ok: jnp.ndarray     # bool[N, S] evictable (PDB allows; slot occupied)


@struct.dataclass
class SolverResult:
    assignments: jnp.ndarray   # i32[P] node row, -1 = unschedulable (or padding)
    scores: jnp.ndarray        # f32[P] winning node's score (0 when unassigned)
    feasible_counts: jnp.ndarray  # i32[P] nodes that passed all predicates
    new_requested: jnp.ndarray     # f32[N, R] ledger after the batch
    new_nonzero: jnp.ndarray       # f32[N, 2]
    new_port_count: jnp.ndarray    # f32[N, UP]
    rr_end: jnp.ndarray        # u32 round-robin counter after the batch
    # full post-batch state ledger: kernels the batch could not touch pass
    # the input arrays through unchanged (an alias, no device copy), so the
    # driver can chain EVERY batch device-to-device with no host re-upload
    new_podsel: jnp.ndarray    # f32[N, UQ]
    new_term: jnp.ndarray      # f32[N, UE]
    new_vol_any: jnp.ndarray   # f32[N, UV]
    new_vol_rw: jnp.ndarray    # f32[N, UV]
    new_attach: jnp.ndarray    # f32[N, UA]
    # preemption verdicts for pods the scan left unassigned: the node whose
    # minimal victim set the pass chose (-1 = none found / pass off) and the
    # victim count k — the first k ok-slots of that node's VictimTable row.
    # Constant (-1, 0) when the pass is compiled out, so gated and
    # ALL_ACTIVE programs stay field-for-field comparable.
    preempt_node: jnp.ndarray = None   # i32[P]
    victim_count: jnp.ndarray = None   # i32[P]
    # autoscaler probe output (BatchFlags.scale_sim): batch pods placed per
    # node row. None — an empty pytree leaf, zero HLO — on every real
    # scheduling program; the simulator reads its hypothetical rows from it.
    placed_per_node: jnp.ndarray = None  # i32[N]
    # explainability output (BatchFlags.explain): cumulative survivor
    # counts down _pod_eval's feasible chain, one column per
    # EXPLAIN_STAGES entry. None — zero HLO — on every explain-off
    # program; the driver diffs adjacent columns into per-predicate
    # reject counts for FailedScheduling messages.
    explain_counts: jnp.ndarray = None  # i32[P, len(EXPLAIN_STAGES)]


@struct.dataclass
class Carry:
    """Scan-carried assume ledger: every assignment-dependent count. Fields
    gated off by the policy stay None (None is an empty pytree, so the scan
    carry structure remains static per policy).

    requested and nonzero stay SEPARATE arrays on purpose: fusing them into
    one [N, R+2] ledger (one scatter per claim instead of two) measured 4x
    SLOWER (365 ms vs 91 ms per 4,096-pod solve) — the static column slices
    feeding the predicates break XLA's in-place while-loop buffer aliasing,
    so every step copies the whole ledger instead of scattering in place."""

    requested: jnp.ndarray
    nonzero: jnp.ndarray
    port_count: jnp.ndarray
    rr: jnp.ndarray
    ipa: object = None          # AffinityLedger | None
    vol_any: object = None      # f32[N, UV] | None
    vol_rw: object = None
    attach_count: object = None  # f32[N, UA] | None
    # gang group-revert extension (BatchFlags.gang; None when gated off).
    # gang_snap holds the whole live ledger (incl. rr) as of the current
    # group's entry; a group that exits with fewer than gang_min_cur placed
    # members restores it wholesale — the batched analog of forgetting every
    # AssumePod of a gang that cannot complete. Whole-ledger selects per
    # step are the known cost (see the fused-ledger note above); they are
    # only ever compiled into gang-gated programs.
    gang_snap: object = None     # ledger tuple | None
    gang_cur: object = None      # i32 current group id, 0 = not in a group
    gang_placed: object = None   # i32 members assigned in the current group
    gang_min_cur: object = None  # i32 current group's quorum


def _live_ledger(c: Carry):
    """The revertible ledger as one pytree — every assignment-dependent
    count a gang revert must restore, the round-robin counter included (a
    reverted member's rr bump must not survive, or tie-breaks downstream of
    a failed gang would diverge from the serial oracle)."""
    return (c.requested, c.nonzero, c.port_count, c.rr,
            c.ipa, c.vol_any, c.vol_rw, c.attach_count)


def _static_mask(state: ClusterState, pod, policy: Policy,
                 base_mask=None) -> jnp.ndarray:
    """Assignment-independent predicate conjunction for one pod: bool[N].

    The unschedulable filter is NOT policy-gated: the reference applies it in
    the scheduler's node lister regardless of configured predicates
    (factory.go getNodeConditionPredicate). `base_mask` carries the
    pod-independent policy-argument predicates (CheckNodeLabelPresence).
    """
    ok = state.valid & preds.node_schedulable(state, pod)
    if base_mask is not None:
        ok = ok & base_mask
    if policy.service_affinity_predicates and policy.has_predicate(
            *[n for n, _ in policy.service_affinity_predicates]):
        ok = ok & preds.service_affinity(state, pod)
    if policy.has_predicate("GeneralPredicates", "PodFitsHost", "HostName"):
        ok = ok & preds.fits_host(state, pod)
    if policy.has_predicate("GeneralPredicates", "MatchNodeSelector"):
        ok = ok & preds.match_node_selector(state, pod)
    if policy.has_predicate("PodToleratesNodeTaints"):
        ok = ok & preds.tolerates_node_taints(state, pod)
    if policy.has_predicate("CheckNodeCondition"):
        ok = ok & preds.check_node_condition(state, pod)
    if policy.has_predicate("CheckNodeMemoryPressure"):
        ok = ok & preds.check_memory_pressure(state, pod)
    if policy.has_predicate("CheckNodeDiskPressure"):
        ok = ok & preds.check_disk_pressure(state, pod)
    if policy.has_predicate("NoVolumeZoneConflict"):
        ok = ok & preds.volume_zone(state, pod)
    if policy.has_predicate("NoVolumeNodeConflict"):
        ok = ok & preds.volume_node(state, pod)
    return ok


def _use_fused_static(policy: Policy, state, batch) -> bool:
    """The Pallas fused static kernel applies selector/taint/condition/
    host checks unconditionally — sound only when the policy registers all
    of them and adds no base-mask predicates; tile shapes must divide the
    padded capacities. Opt-in via KTPU_PALLAS=1 (see PERF.md). The sharded
    path passes allow_fused=False — Mosaic custom calls have no GSPMD
    partitioning rule, so the kernel must never trace under a mesh."""
    import os

    from kubernetes_tpu.utils.features import enabled

    if os.environ.get("KTPU_PALLAS") != "1" \
            and not enabled("PallasFusedScoring"):
        return False
    return (
        state.valid.shape[0] % 128 == 0    # lane width (tiles adapt above)
        and batch.valid.shape[0] % 8 == 0  # f32 sublane width
        and policy.has_predicate("GeneralPredicates", "PodFitsHost",
                                 "HostName")
        and policy.has_predicate("GeneralPredicates", "MatchNodeSelector")
        and policy.has_predicate("PodToleratesNodeTaints")
        and policy.has_predicate("CheckNodeCondition")
        and policy.has_predicate("CheckNodeMemoryPressure")
        and policy.has_predicate("CheckNodeDiskPressure")
        and not policy.service_affinity_predicates
        and not active_label_presence(policy))


def _static_rest(state: ClusterState, pod, policy: Policy,
                 base_mask=None) -> jnp.ndarray:
    """The static terms the fused kernel does NOT cover: required
    node-affinity (a (T × UR × N) contraction) and the volume zone/node
    predicates. AND-combined with the kernel output."""
    ok = preds.node_affinity_ok(state, pod)
    if base_mask is not None:
        ok = ok & base_mask
    if policy.has_predicate("NoVolumeZoneConflict"):
        ok = ok & preds.volume_zone(state, pod)
    if policy.has_predicate("NoVolumeNodeConflict"):
        ok = ok & preds.volume_node(state, pod)
    return ok


def _static_score(state: ClusterState, pod, policy: Policy,
                  base_score=None) -> jnp.ndarray:
    """Assignment-independent score terms for one pod: f32[N]. `base_score`
    carries the pod-independent terms (NodeLabel priorities)."""
    score = jnp.zeros(state.valid.shape[0], jnp.float32)
    if base_score is not None:
        score = score + base_score
    w = policy.weight("EqualPriority")
    if w:
        score = score + w * prios.equal(state, pod)
    w = policy.weight("ImageLocalityPriority")
    if w:
        score = score + w * prios.image_locality(state, pod)
    w = policy.weight("NodePreferAvoidPodsPriority")
    if w:
        score = score + w * prios.node_prefer_avoid(state, pod)
    return score


def _base_rows(state: ClusterState, policy: Policy, prows,
               g: PolicyGates):
    """Pod-independent policy-argument rows (CheckNodeLabelPresence mask,
    NodeLabel priority scores, gated-neutral constant shifts) — computed once
    per batch/evaluation, broadcast over pods."""
    base_mask = None
    base_score = None
    if g.const_score:
        base_score = jnp.full(state.valid.shape[0], g.const_score, jnp.float32)
    if prows is not None:
        if active_label_presence(policy):
            base_mask = preds.label_presence_ok(
                state, prows.pres_onehot, prows.pres_count, prows.abs_onehot)
        nl = active_label_priorities(policy)
        if nl:
            if base_score is None:
                base_score = jnp.zeros(state.valid.shape[0], jnp.float32)
            for i, (_label, presence, weight) in enumerate(nl):
                base_score = base_score + weight * prios.node_label_score(
                    state, prows.nlp_onehot[i], presence)
        if g.svcanti and not g.use_svcanti:
            # every svcanti_q == -1 and svcanti_total == 0: counts are zero,
            # so labeled nodes score MaxPriority and unlabeled 0 — a
            # pod-independent surface, hoisted out of the scan
            if base_score is None:
                base_score = jnp.zeros(state.valid.shape[0], jnp.float32)
            for i, (_label, sa_weight) in enumerate(g.svcanti):
                labeled = state.topology[:, prows.svcanti_slot[i]] >= 0
                base_score = base_score + sa_weight * jnp.where(
                    labeled, float(MAX_PRIORITY), 0.0)
    return base_mask, base_score


def _init_carry(state: ClusterState, g: PolicyGates, rr_start,
                domain_universe: int, use_gang: bool = False) -> Carry:
    """The assume ledger as of batch start — the accounted cluster state."""
    carry = Carry(
        requested=state.requested,
        nonzero=state.nonzero_requested,
        port_count=state.port_count,
        rr=jnp.asarray(rr_start, jnp.uint32),
        ipa=(interpod.make_ledger(state, domain_universe,
                                  with_terms=g.use_terms)
             if g.use_ip_ledger else None),
        vol_any=state.vol_any if g.use_nodisk else None,
        vol_rw=state.vol_rw if g.use_nodisk else None,
        attach_count=state.attach_count if g.attach_maxes else None,
    )
    if use_gang:
        carry = carry.replace(
            gang_snap=_live_ledger(carry),
            gang_cur=jnp.int32(0),
            gang_placed=jnp.int32(0),
            gang_min_cur=jnp.int32(0),
        )
    return carry


def _pod_eval(state: ClusterState, g: PolicyGates, carry: Carry, pod,
              s_mask, s_score, p_counts, na_count, topo_onehot, prows,
              hard_w: float, domain_universe: int, explain: bool = False):
    """One pod's full-policy (feasible[N], score[N], breakdown) against an
    assume ledger — THE evaluation semantics, shared verbatim by the
    solver's scan step and the extender's Filter/Prioritize verbs (extender
    parity with in-batch scheduling is by construction, not by
    re-implementation). `breakdown` is the i32[len(EXPLAIN_STAGES)]
    cumulative survivor count down the mask chain when `explain`, else
    None — the trail list below holds plain aliases of `feasible`, so an
    explain-off trace sees zero extra ops."""
    feasible = s_mask
    trail = [feasible]
    if g.use_resources:
        feasible = feasible & preds.fits_resources_dyn(
            state, pod, carry.requested, g.dyn_gpu, g.dyn_storage)
    trail.append(feasible)
    if g.use_ports:
        feasible = feasible & preds.fits_host_ports(
            state, pod, port_count=carry.port_count)
    trail.append(feasible)
    if g.use_nodisk:
        feasible = feasible & preds.no_disk_conflict(
            state, pod, vol_any=carry.vol_any, vol_rw=carry.vol_rw)
    trail.append(feasible)
    if g.attach_maxes:
        feasible = feasible & preds.max_attach_ok(
            state, pod, g.attach_maxes, attach_count=carry.attach_count)
    trail.append(feasible)
    if g.use_ipa:
        feasible = feasible & interpod.interpod_feasible(
            state, pod, carry.ipa, topo_onehot)
    trail.append(feasible)
    breakdown = None
    if explain:
        breakdown = jnp.stack(
            [jnp.sum(m.astype(jnp.int32)) for m in trail])

    score = s_score
    if g.w_lr:
        score = score + g.w_lr * prios.least_requested(
            state, pod, nonzero_requested=carry.nonzero)
    if g.w_mr:
        score = score + g.w_mr * prios.most_requested(
            state, pod, nonzero_requested=carry.nonzero)
    if g.w_ba:
        score = score + g.w_ba * prios.balanced_allocation(
            state, pod, nonzero_requested=carry.nonzero)
    if g.w_tt:
        score = score + g.w_tt * prios.taint_toleration_from_counts(
            p_counts, feasible)
    if g.w_na:
        score = score + g.w_na * prios.normalized_from_counts(
            na_count, feasible)
    if g.w_ip:
        ip_counts = interpod.interpod_counts(state, pod, carry.ipa, hard_w,
                                             topo_onehot)
        score = score + g.w_ip * interpod.interpod_score(ip_counts, feasible)
    if g.w_ss:
        score = score + g.w_ss * spreadops.selector_spread(
            state, pod.spread_q, carry.ipa, feasible, domain_universe,
            topo_onehot)
    if g.w_ssp:
        score = score + g.w_ssp * spreadops.selector_spread(
            state, pod.spread_svc_q, carry.ipa, feasible, domain_universe,
            topo_onehot)
    if g.use_svcanti:
        for i, (_label, sa_weight) in enumerate(g.svcanti):
            score = score + sa_weight * spreadops.service_anti_affinity(
                state, pod.svcanti_q, pod.svcanti_total, carry.ipa,
                feasible, prows.svcanti_slot[i], domain_universe,
                topo_onehot)
    return feasible, score, breakdown


def _select_host(masked_score: jnp.ndarray, feasible: jnp.ndarray, rr: jnp.ndarray):
    """selectHost parity (generic_scheduler.go:144): among max-score feasible
    nodes, pick the (rr % ties)-th in node order.

    The tie count is read off the cumsum's last element rather than a
    separate sum (one less serial reduction in the scan step), and the
    cumsum runs in f32 — the VPU's native dtype, exact for counts < 2^24.
    A two-level reshape select ([N/128, 128] row-reduce + 128-wide rank
    find) measured SLOWER (99 ms vs 88 ms per 4,096-pod solve at N=16k):
    the 1-D->2-D retile of the tie vector costs more than the flat
    reduce-window cumsum it saves."""
    best = jnp.max(masked_score)
    ties = feasible & (masked_score == best)
    cum = jnp.cumsum(ties.astype(jnp.float32))
    ntie = cum[-1].astype(jnp.int32)
    k = (rr % jnp.maximum(ntie, 1).astype(jnp.uint32)).astype(jnp.int32)
    # cum is nondecreasing and steps exactly at tie positions: the first
    # index reaching k+1 IS the (k+1)-th tie
    node = jnp.argmax(cum >= (k + 1).astype(jnp.float32)).astype(jnp.int32)
    return node, best, ntie


def schedule_batch(
    state: ClusterState,
    batch: PodBatch,
    rr_start,
    policy: Policy = DEFAULT_POLICY,
    caps=None,
    prows=None,
    flags: BatchFlags = ALL_ACTIVE,
    allow_fused: bool = True,
    victims: VictimTable | None = None,
) -> SolverResult:
    """Schedule a whole pending batch in one device program.

    Pure function; jit with `policy`, `flags` (and `caps`, if given) static.
    `prows` carries the PolicyRows for argument-carrying registrations (None
    when the policy has none — models/policy.py build_policy_rows). Returns
    per-pod assignments plus the post-batch resource ledger for the host to
    commit (assume semantics).

    `victims` (a VictimTable) enables the preemption pass: pods the scan
    leaves unassigned get a per-node minimal-victim-set search and a
    pickOneNodeForPreemption node choice reported via
    (preempt_node, victim_count). The pass is traced only when BOTH
    flags.preempt is set AND a table is given — a batch with no priorities,
    or a driver with nothing evictable, compiles the exact pre-preemption
    program.
    """
    # normalize to jnp arrays: un-jitted callers pass host numpy, and numpy
    # arrays cannot be indexed by traced scalars inside the scan
    state = jax.tree.map(jnp.asarray, state)
    batch = jax.tree.map(jnp.asarray, batch)
    use_preempt = flags.preempt and victims is not None
    if use_preempt:
        victims = jax.tree.map(jnp.asarray, victims)

    g = policy_gates(policy, flags)
    # only the gates the remaining inline code reads; _base_rows/_init_carry/
    # _pod_eval consume the rest straight from g
    w_tt, w_na, use_ports, svcanti = g.w_tt, g.w_na, g.use_ports, g.svcanti
    use_terms, use_ip_ledger = g.use_terms, g.use_ip_ledger
    use_nodisk, attach_maxes = g.use_nodisk, g.attach_maxes
    use_gang = flags.gang
    if prows is None and (svcanti or active_label_presence(policy)
                          or active_label_priorities(policy)):
        raise ValueError(
            "policy carries argument registrations (labelsPresence / "
            "labelPreference / serviceAntiAffinity) but no PolicyRows were "
            "given — build them with models.policy.build_policy_rows")
    hard_w = float(policy.hard_pod_affinity_weight)
    domain_universe = caps.domain_universe if caps else DEFAULT_DOMAIN_UNIVERSE

    # pod-independent policy-argument rows (CheckNodeLabelPresence mask,
    # NodeLabel priority scores) — computed once, broadcast over the batch
    base_mask, base_score = _base_rows(state, policy, prows, g)

    # ---- Phase A: batched over (P, N) ----
    if allow_fused and _use_fused_static(policy, state, batch):
        from kubernetes_tpu.ops.pallas_kernels import fused_static_mask

        untol = jax.vmap(
            lambda p: 1.0 - preds._tolerated_universe(state, p)
            .astype(jnp.float32))(batch)
        fused = fused_static_mask(
            state, batch.sel_onehot, batch.sel_count, untol,
            batch.best_effort, batch.node_name_lo, batch.node_name_hi)
        static_mask = fused & jax.vmap(
            lambda p: _static_rest(state, p, policy, base_mask))(batch)
    else:
        static_mask = jax.vmap(
            lambda p: _static_mask(state, p, policy, base_mask))(batch)
    static_score = jax.vmap(
        lambda p: _static_score(state, p, policy, base_score))(batch)

    # resource columns the batch cannot touch (gpu/storage under the
    # BatchFlags gates) hold against the batch-start ledger for the whole
    # batch: hoist their compares out of the scan into the static mask
    if g.use_resources and not (g.dyn_gpu and g.dyn_storage):
        static_mask = static_mask & jax.vmap(
            lambda p: preds.fits_resources_static(
                state, p, g.dyn_gpu, g.dyn_storage))(batch)

    if w_tt:
        prefer_counts = jax.vmap(
            lambda p: preds.count_untolerated_prefer_taints(state, p))(batch)
    if w_na:
        na_counts = jax.vmap(
            lambda p: prios.node_affinity_counts(state, p))(batch)

    # domain->node broadcast matrix, shared by every interpod/spread kernel
    # (pod-independent; hoisted so scan steps do matmuls, not gathers)
    topo_onehot = (interpod.topology_onehot(state.topology, domain_universe)
                   if use_ip_ledger else None)

    # ---- Phase B: scan over the pod axis, vector over nodes ----
    # Every scan-xs leaf costs one dynamic-slice per step inside the compiled
    # while loop (~1 us each on TPU — the dominant per-pod cost when the xs
    # is the ~45-leaf PodBatch pytree; PERF.md round 5). So the step consumes
    # the batch as TWO packed blob rows (pod fields become static slices that
    # fuse into the step body) plus one combined (mask, score) row: the
    # static mask rides the score row as -inf.
    # the static mask AND the pod-valid bit ride the static-score row as
    # -inf: one fused (P, N) xs leaf instead of three per-step reads. A
    # padding row is all--inf, so its tie count is 0 and it can never be
    # assigned — the step needs no separate `valid` test (its feasible
    # count reads 0, which is also the honest verdict for a non-pod).
    masked_static = jnp.where(batch.valid[:, None] & static_mask,
                              static_score, -jnp.inf)
    xs_list = [batch, masked_static]
    if w_tt:
        xs_list.append(prefer_counts)
    if w_na:
        xs_list.append(na_counts)
    zero_i = jnp.zeros((1,), jnp.int32)
    zero_f = jnp.zeros((1,), jnp.float32)

    def step(carry: Carry, xs):
        pod, ms_row = xs[0], xs[1]
        rest = list(xs[2:])
        p_counts = rest.pop(0) if w_tt else zero_i
        na_count = rest.pop(0) if w_na else zero_f
        if use_gang:
            # group boundary crossing: first settle the group being left —
            # below quorum, restore its entry snapshot (forget every member
            # charge, rr included) — then, if this pod opens a new group,
            # snapshot the settled ledger as its revert point
            gid = pod.gang_id
            boundary = gid != carry.gang_cur
            revert = boundary & (carry.gang_cur > 0) \
                & (carry.gang_placed < carry.gang_min_cur)
            ledger = jax.tree.map(
                lambda cur, snap: jnp.where(revert, snap, cur),
                _live_ledger(carry), carry.gang_snap)
            entering = boundary & (gid > 0)
            snap = jax.tree.map(
                lambda led, sn: jnp.where(entering, led, sn),
                ledger, carry.gang_snap)
            requested, nonzero, port_count, rr, ipa, vol_any, vol_rw, \
                attach_count = ledger
            carry = Carry(
                requested=requested, nonzero=nonzero,
                port_count=port_count, rr=rr, ipa=ipa, vol_any=vol_any,
                vol_rw=vol_rw, attach_count=attach_count,
                gang_snap=snap, gang_cur=gid,
                gang_placed=jnp.where(entering, jnp.int32(0),
                                      carry.gang_placed),
                gang_min_cur=jnp.where(entering, pod.gang_min,
                                       carry.gang_min_cur))
        s_mask = ms_row > -jnp.inf
        feasible, score, breakdown = _pod_eval(
            state, g, carry, pod, s_mask, ms_row, p_counts, na_count,
            topo_onehot, prows, hard_w, domain_universe,
            explain=flags.explain)

        masked = jnp.where(feasible, score, -jnp.inf)
        node, best, ntie = _select_host(masked, feasible, carry.rr)
        assigned = ntie > 0   # a padding row is all--inf: ntie == 0
        node_idx = jnp.where(assigned, node, -1)

        add = jnp.where(assigned, 1.0, 0.0)
        new_carry = Carry(
            requested=carry.requested.at[node].add(add * pod.requests),
            nonzero=carry.nonzero.at[node].add(add * pod.nonzero_requests),
            port_count=(carry.port_count.at[node].add(add * pod.port_onehot)
                        if use_ports else carry.port_count),
            rr=carry.rr + jnp.where(assigned, jnp.uint32(1), jnp.uint32(0)),
            ipa=(interpod.ledger_add(carry.ipa, state, pod, node, add,
                                     with_terms=use_terms)
                 if use_ip_ledger else None),
            vol_any=(carry.vol_any.at[node].add(
                add * (pod.vol_want_rw + pod.vol_want_ro))
                if use_nodisk else None),
            vol_rw=(carry.vol_rw.at[node].add(add * pod.vol_want_rw)
                    if use_nodisk else None),
            attach_count=(carry.attach_count.at[node].add(add * pod.att_onehot)
                          if attach_maxes else None),
            gang_snap=carry.gang_snap,
            gang_cur=carry.gang_cur,
            gang_placed=(carry.gang_placed
                         + jnp.where(assigned & (carry.gang_cur > 0),
                                     jnp.int32(1), jnp.int32(0))
                         if use_gang else None),
            gang_min_cur=carry.gang_min_cur,
        )
        # the feasible row is emitted whole and summed AFTER the scan (an
        # in-step scalar sum measured SLOWER: the reduction does not fuse
        # into the select chain, while the row's dynamic-update-slice is one
        # 16 KB write), and the two scalar outputs ride one [2] f32 vector —
        # each ys leaf costs its own dynamic-update-slice per step (node
        # index is exact in f32: < 2^24)
        packed = jnp.stack([node_idx.astype(jnp.float32),
                            jnp.where(assigned, best, 0.0)])
        if flags.explain:
            return new_carry, (packed, feasible, breakdown)
        return new_carry, (packed, feasible)

    init = _init_carry(state, g, rr_start, domain_universe, use_gang=use_gang)
    if flags.explain:
        final, (packed_out, feas_rows, explain_rows) = jax.lax.scan(
            step, init, tuple(xs_list))
    else:
        final, (packed_out, feas_rows) = jax.lax.scan(
            step, init, tuple(xs_list))
        explain_rows = None
    nodes = packed_out[:, 0].astype(jnp.int32)
    scores = packed_out[:, 1]
    counts = jnp.sum(feas_rows.astype(jnp.int32), axis=1)

    if use_gang:
        # close out the group still open at scan end (the step only settles
        # groups on a boundary crossing; the last group has none)
        revert_last = (final.gang_cur > 0) \
            & (final.gang_placed < final.gang_min_cur)
        requested, nonzero, port_count, rr, ipa, vol_any, vol_rw, \
            attach_count = jax.tree.map(
                lambda cur, snap: jnp.where(revert_last, snap, cur),
                _live_ledger(final), final.gang_snap)
        final = final.replace(
            requested=requested, nonzero=nonzero, port_count=port_count,
            rr=rr, ipa=ipa, vol_any=vol_any, vol_rw=vol_rw,
            attach_count=attach_count)
        # mask every member of a below-quorum group out of the result: the
        # scan already forgot their ledger charges, and no partial gang may
        # reach bind. Groups are contiguous runs of equal gang_id, so
        # boundary-cumsum segment ids + one segment_sum of the per-row
        # assigned bits give each group's placed count without an O(P^2)
        # member-by-member comparison.
        gid_col = batch.gang_id
        seg = jnp.cumsum(jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             (gid_col[1:] != gid_col[:-1]).astype(jnp.int32)]))
        placed_per_seg = jax.ops.segment_sum(
            (nodes >= 0).astype(jnp.int32), seg,
            num_segments=gid_col.shape[0])
        group_failed = (gid_col > 0) & (placed_per_seg[seg] < batch.gang_min)
        nodes = jnp.where(group_failed, -1, nodes)
        scores = jnp.where(group_failed, 0.0, scores)

    if use_preempt:
        preempt_node, victim_count = _preemption_pass(
            state, batch, masked_static, nodes, final.requested, victims,
            use_gang)
    else:
        preempt_node = jnp.full(nodes.shape, -1, jnp.int32)
        victim_count = jnp.zeros(nodes.shape, jnp.int32)

    # autoscaler probe: per-node placed counts (unassigned rows scatter to
    # row 0 but contribute 0). Off — the default — leaves the field None,
    # so the program is the byte-identical pre-autoscaler HLO.
    placed_per_node = None
    if flags.scale_sim:
        placed_per_node = jax.ops.segment_sum(
            (nodes >= 0).astype(jnp.int32), jnp.maximum(nodes, 0),
            num_segments=state.valid.shape[0])

    # explainability probe: per-pod cumulative survivor counts down the
    # predicate chain. Off — the default — leaves the field None, so the
    # program is the byte-identical pre-explain HLO.
    explain_counts = explain_rows if flags.explain else None

    return SolverResult(
        assignments=nodes,
        scores=scores,
        feasible_counts=counts,
        new_requested=final.requested,
        new_nonzero=final.nonzero,
        new_port_count=final.port_count,
        rr_end=final.rr,
        new_podsel=(final.ipa.podsel_count if use_ip_ledger
                    else state.podsel_count),
        new_term=(final.ipa.term_count if use_ip_ledger and use_terms
                  else state.term_count),
        new_vol_any=final.vol_any if use_nodisk else state.vol_any,
        new_vol_rw=final.vol_rw if use_nodisk else state.vol_rw,
        new_attach=final.attach_count if attach_maxes else state.attach_count,
        preempt_node=preempt_node,
        victim_count=victim_count,
        placed_per_node=placed_per_node,
        explain_counts=explain_counts,
    )


class _PodRequests:
    """Minimal pod shim for preds.fits_resources_dyn, which reads only
    `.requests` — lets the preemption pass reuse the exact
    predicates.go:556 fit composition without scanning the full batch
    pytree a second time."""

    __slots__ = ("requests",)

    def __init__(self, requests):
        self.requests = requests


def _preemption_pass(state: ClusterState, batch: PodBatch, masked_static,
                     nodes, base_requested, victims: VictimTable,
                     use_gang: bool):
    """Batched victim selection for pods the scan left unassigned.

    Mirrors the reference preemption flow (generic_scheduler.go
    selectNodesForPreemption / pickOneNodeForPreemption) over the
    VictimTable: for each participating pod, on every statically-feasible
    node, find the minimal k such that evicting the k lowest-priority
    evictable candidates (priority strictly below the preemptor's, PDBs
    respected via `ok`) makes PodFitsResources pass against the post-batch
    ledger; then pick the node lexicographically minimizing
    (highest victim priority, victim count, node index).

    A second scan over the pod axis carries in-batch preemption bookings —
    chosen victims are marked taken and the preemptor's requests are
    charged against the freed node, so two preemptors in one batch never
    double-book the same freed capacity. Gang groups are all-or-nothing:
    if ANY participating member of a group finds no victim set, the whole
    group's bookings revert at the group boundary and its verdicts are
    masked out — no evictions happen for a gang that cannot fully land.

    Returns (preempt_node i32[P] (-1 = none), victim_count i32[P]).
    Resource-only semantics: the freed capacity re-check covers the
    resource fit; the preemptor still reschedules through the full solver
    after the evictions land, so the other dynamic predicates (ports,
    disk conflicts) are enforced at placement time, not here.
    """
    n_nodes = base_requested.shape[0]
    n_slots = victims.prio.shape[1]
    imin = jnp.iinfo(jnp.int32).min
    imax = jnp.iinfo(jnp.int32).max
    participate = batch.valid & (nodes < 0)
    static_ok = masked_static > -jnp.inf
    node_iota = jnp.arange(n_nodes, dtype=jnp.int32)
    ks = jnp.arange(n_slots + 1, dtype=jnp.float32)

    def pstep(carry, xs):
        extra, taken, snap_e, snap_t, cur, bad = carry
        req_p, prio_p, part, s_ok, gid = xs
        # gang boundary: settle the group being left (revert its bookings
        # if any member failed), then snapshot for a newly entered group
        boundary = gid != cur
        revert = boundary & (cur > 0) & bad
        extra = jnp.where(revert, snap_e, extra)
        taken = jnp.where(revert, snap_t, taken)
        entering = boundary & (gid > 0)
        snap_e = jnp.where(entering, extra, snap_e)
        snap_t = jnp.where(entering, taken, snap_t)
        bad = bad & ~boundary

        # candidates: evictable, not already booked by an earlier
        # preemptor, strictly lower priority than this pod
        cand = victims.ok & ~taken & (victims.prio < prio_p)
        cand_f = cand.astype(jnp.float32)
        rank = jnp.cumsum(cand_f, axis=1)              # f32[N, S], 1-based
        count = rank[:, -1]                            # f32[N]
        freed_cum = jnp.cumsum(cand_f[:, :, None] * victims.req, axis=1)
        ledger = base_requested + extra
        # ledgers after evicting the first 0..S candidates: [S+1, N, R]
        adj = jnp.concatenate(
            [ledger[None], ledger[None] - jnp.moveaxis(freed_cum, 1, 0)],
            axis=0)
        shim = _PodRequests(req_p)
        fit_k = jax.vmap(
            lambda led: preds.fits_resources_dyn(state, shim, led))(adj)
        # k beyond the candidate count frees nothing more — exclude it so
        # "minimal k" is well-defined and (node, k) names real victims
        ok_k = fit_k & (ks[:, None] <= count[None, :]) & s_ok[None, :]
        feas = jnp.any(ok_k, axis=0)                   # bool[N]
        k_n = jnp.argmax(ok_k, axis=0).astype(jnp.int32)  # first feasible k
        chosen = cand & (rank <= k_n[:, None].astype(jnp.float32))
        # highest victim priority of the minimal set (imin when k == 0:
        # a no-eviction node dominates every evicting one)
        top_prio = jnp.max(jnp.where(chosen, victims.prio, imin), axis=1)
        # pickOneNodeForPreemption: lexicographic min over
        # (top victim priority, victim count, node index)
        tp = jnp.where(feas, top_prio, imax)
        m1 = feas & (tp == jnp.min(tp))
        kk = jnp.where(m1, k_n, imax)
        m2 = m1 & (kk == jnp.min(kk))
        node = jnp.argmax(m2).astype(jnp.int32)
        found = jnp.any(feas)
        act = part & found

        k_sel = k_n[node]
        freed_sel = jnp.where(
            k_sel > 0, freed_cum[node, jnp.maximum(k_sel - 1, 0)], 0.0)
        add = jnp.where(act, 1.0, 0.0)
        extra = extra.at[node].add(add * (req_p - freed_sel))
        taken = taken | (chosen & (node_iota == node)[:, None] & act)
        bad = bad | (part & ~found & (gid > 0))
        out = jnp.stack([jnp.where(act, node, jnp.int32(-1)),
                         jnp.where(act, k_sel, jnp.int32(0))])
        return (extra, taken, snap_e, snap_t, gid, bad), out

    zero_extra = jnp.zeros_like(base_requested)
    zero_taken = jnp.zeros((n_nodes, n_slots), bool)
    init = (zero_extra, zero_taken, zero_extra, zero_taken,
            jnp.int32(0), jnp.bool_(False))
    _, packed = jax.lax.scan(
        pstep, init,
        (batch.requests, batch.priority, participate, static_ok,
         batch.gang_id))
    preempt_node = packed[:, 0]
    victim_count = packed[:, 1]

    if use_gang:
        # all-or-nothing over each group's PARTICIPANTS: if any failed to
        # find a victim set, the scan already reverted the group's
        # bookings — mask its verdicts so the driver evicts nothing
        gid_col = batch.gang_id
        seg = jnp.cumsum(jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             (gid_col[1:] != gid_col[:-1]).astype(jnp.int32)]))
        n_part = jax.ops.segment_sum(
            participate.astype(jnp.int32), seg,
            num_segments=gid_col.shape[0])
        n_found = jax.ops.segment_sum(
            (participate & (preempt_node >= 0)).astype(jnp.int32), seg,
            num_segments=gid_col.shape[0])
        group_bad = (gid_col > 0) & (n_found[seg] < n_part[seg])
        preempt_node = jnp.where(group_bad, -1, preempt_node)
        victim_count = jnp.where(group_bad, 0, victim_count)
    return preempt_node, victim_count


def evaluate_pod(
    state: ClusterState,
    pod,
    policy: Policy = DEFAULT_POLICY,
    caps=None,
    prows=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Full-policy (feasible bool[N], score f32[N]) for ONE encoded pod row
    against the accounted cluster state — the extender's Filter/Prioritize
    surface (core/extender.go:100,143).

    Runs the exact `_pod_eval` the solver's scan step runs, with the assume
    ledger initialized from `state` and no in-batch predecessors — i.e. the
    verdict the solver would reach scheduling this pod next. Pure; jit with
    `policy` (and `caps`) static. Always compiled ALL_ACTIVE: the extender
    serves one pod per request, so batch-content gating buys nothing and
    full faithfulness costs nothing.
    """
    state = jax.tree.map(jnp.asarray, state)
    pod = jax.tree.map(jnp.asarray, pod)
    g = policy_gates(policy, ALL_ACTIVE)
    if prows is None and (g.svcanti or active_label_presence(policy)
                          or active_label_priorities(policy)):
        raise ValueError(
            "policy carries argument registrations (labelsPresence / "
            "labelPreference / serviceAntiAffinity) but no PolicyRows were "
            "given — build them with models.policy.build_policy_rows")
    hard_w = float(policy.hard_pod_affinity_weight)
    domain_universe = caps.domain_universe if caps else DEFAULT_DOMAIN_UNIVERSE

    base_mask, base_score = _base_rows(state, policy, prows, g)
    s_mask = _static_mask(state, pod, policy, base_mask)
    s_score = _static_score(state, pod, policy, base_score)
    p_counts = (preds.count_untolerated_prefer_taints(state, pod)
                if g.w_tt else jnp.zeros((1,), jnp.int32))
    na_count = (prios.node_affinity_counts(state, pod)
                if g.w_na else jnp.zeros((1,), jnp.float32))
    topo_onehot = (interpod.topology_onehot(state.topology, domain_universe)
                   if g.use_ip_ledger else None)
    carry = _init_carry(state, g, 0, domain_universe)
    feasible, score, _ = _pod_eval(
        state, g, carry, pod, s_mask, s_score, p_counts, na_count,
        topo_onehot, prows, hard_w, domain_universe)
    return feasible, score
