"""Inter-pod (anti-)affinity parity: predicate (predicates.go:982
InterPodAffinityMatches incl. existing-pod anti-affinity symmetry) and
InterPodAffinityPriority (interpod_affinity.go incl. symmetric weighting),
against the Go-faithful serial reference, with in-batch visibility through
the solver scan."""

import jax
import numpy as np
import pytest

from kubernetes_tpu.api.objects import Node, Pod
from kubernetes_tpu.models.policy import Policy
from kubernetes_tpu.ops.solver import schedule_batch
from kubernetes_tpu.state import Capacities, encode_cluster
from tests.serial_reference import SerialScheduler

CAPS = Capacities(num_nodes=8, batch_pods=8)

jit_schedule = jax.jit(schedule_batch, static_argnames=("policy", "caps"))

IPA_POLICY = Policy(
    predicates=("GeneralPredicates", "MatchInterPodAffinity"),
    priorities=(("LeastRequestedPriority", 1),),
)
IPA_PRIO_POLICY = Policy(
    predicates=("GeneralPredicates", "MatchInterPodAffinity"),
    priorities=(("InterPodAffinityPriority", 1),),
)

ZONE = "failure-domain.beta.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"


def mk_node(name, zone=None, cpu="8"):
    labels = {}
    if zone:
        labels[ZONE] = zone
    return Node.from_dict({
        "metadata": {"name": name, "labels": labels},
        "status": {"allocatable": {"cpu": cpu, "memory": "16Gi", "pods": "110"},
                   "conditions": [{"type": "Ready", "status": "True"}]},
    })


def mk_pod(name, labels=None, affinity=None, node=None, namespace="default"):
    d = {"metadata": {"name": name, "namespace": namespace,
                      "labels": labels or {}},
         "spec": {"containers": [{"name": "c"}]}}
    if affinity:
        d["spec"]["affinity"] = affinity
    pod = Pod.from_dict(d)
    if node:
        pod.spec.node_name = node
    return pod


def aff(required=None, anti_required=None, preferred=None, anti_preferred=None):
    out = {}
    if required or preferred:
        out["podAffinity"] = {}
        if required:
            out["podAffinity"]["requiredDuringSchedulingIgnoredDuringExecution"] = required
        if preferred:
            out["podAffinity"]["preferredDuringSchedulingIgnoredDuringExecution"] = preferred
    if anti_required or anti_preferred:
        out["podAntiAffinity"] = {}
        if anti_required:
            out["podAntiAffinity"]["requiredDuringSchedulingIgnoredDuringExecution"] = anti_required
        if anti_preferred:
            out["podAntiAffinity"]["preferredDuringSchedulingIgnoredDuringExecution"] = anti_preferred
    return out


def term(match_labels, topology_key=ZONE, namespaces=None):
    t = {"labelSelector": {"matchLabels": match_labels},
         "topologyKey": topology_key}
    if namespaces:
        t["namespaces"] = namespaces
    return t


def solve(nodes, pods, assigned=(), policy=IPA_POLICY, caps=CAPS):
    state, batch, table = encode_cluster(nodes, pods, caps,
                                         assigned_pods=assigned)
    result = jit_schedule(state, batch, 0, policy, caps)
    names = []
    for i in range(len(pods)):
        idx = int(result.assignments[i])
        names.append(table.name_of[idx] if idx >= 0 else None)
    return names


NODES = [mk_node("a1", "z1"), mk_node("a2", "z1"),
         mk_node("b1", "z2"), mk_node("b2", "z2")]


class TestAffinityPredicate:
    def test_zone_affinity_follows_existing(self):
        web = mk_pod("web", {"app": "web"}, node="a1")
        pod = mk_pod("p", affinity=aff(required=[term({"app": "web"})]))
        names = solve(NODES, [pod], assigned=[web])
        assert names[0] in ("a1", "a2")  # any z1 node

    def test_hostname_affinity_pins_node(self):
        web = mk_pod("web", {"app": "web"}, node="b1")
        pod = mk_pod("p", affinity=aff(required=[term({"app": "web"}, HOST)]))
        assert solve(NODES, [pod], assigned=[web]) == ["b1"]

    def test_no_match_anywhere_self_match_escape(self):
        # first pod of a collection: term matches the pod itself and no other
        # pod matches anywhere -> schedulable (predicates.go:1193-1205)
        pod = mk_pod("p", {"app": "web"},
                     affinity=aff(required=[term({"app": "web"})]))
        assert solve(NODES, [pod])[0] is not None

    def test_no_match_no_self_match_unschedulable(self):
        pod = mk_pod("p", {"app": "other"},
                     affinity=aff(required=[term({"app": "web"})]))
        assert solve(NODES, [pod]) == [None]

    def test_match_exists_elsewhere_blocks_other_zones(self):
        # a matching pod exists in z1, so the self-match escape is OFF and
        # only z1 nodes qualify even for a self-matching pod
        web = mk_pod("web", {"app": "web"}, node="a2")
        pod = mk_pod("p", {"app": "web"},
                     affinity=aff(required=[term({"app": "web"})]))
        assert solve(NODES, [pod], assigned=[web])[0] in ("a1", "a2")

    def test_empty_topology_key_required_fails(self):
        web = mk_pod("web", {"app": "web"}, node="a1")
        pod = mk_pod("p", affinity=aff(required=[term({"app": "web"}, "")]))
        assert solve(NODES, [pod], assigned=[web]) == [None]

    def test_namespace_scoping(self):
        other_ns = mk_pod("web", {"app": "web"}, node="a1", namespace="other")
        pod = mk_pod("p", affinity=aff(required=[term({"app": "web"})]))
        # term defaults to the incoming pod's namespace: no match
        assert solve(NODES, [pod], assigned=[other_ns]) == [None]
        pod2 = mk_pod("p2", affinity=aff(
            required=[term({"app": "web"}, namespaces=["other"])]))
        assert solve(NODES, [pod2], assigned=[other_ns])[0] in ("a1", "a2")


class TestAntiAffinityPredicate:
    def test_own_anti_avoids_zone(self):
        web = mk_pod("web", {"app": "web"}, node="a1")
        pod = mk_pod("p", affinity=aff(anti_required=[term({"app": "web"})]))
        assert solve(NODES, [pod], assigned=[web])[0] in ("b1", "b2")

    def test_own_anti_hostname_spreads(self):
        web = mk_pod("web", {"app": "web"}, node="a1")
        pod = mk_pod("p", affinity=aff(anti_required=[term({"app": "web"}, HOST)]))
        assert solve(NODES, [pod], assigned=[web])[0] in ("a2", "b1", "b2")

    def test_existing_pod_anti_affinity_symmetry(self):
        # an EXISTING pod's anti-affinity term blocks incoming matching pods
        # from its domain (predicates.go:1139 satisfiesExistingPodsAntiAffinity)
        guard = mk_pod("guard", {"app": "guard"},
                       affinity=aff(anti_required=[term({"app": "web"})]),
                       node="a1")
        pod = mk_pod("p", {"app": "web"})
        assert solve(NODES, [pod], assigned=[guard])[0] in ("b1", "b2")

    def test_in_batch_anti_affinity(self):
        # each replica carries anti-affinity to its own label: the scan must
        # expose earlier in-batch placements to later pods
        pods = [mk_pod(f"p{i}", {"app": "db"},
                       affinity=aff(anti_required=[term({"app": "db"}, HOST)]))
                for i in range(5)]
        names = solve(NODES, pods)
        placed = [n for n in names if n]
        assert len(placed) == 4 and len(set(placed)) == 4
        assert names[4] is None  # only 4 hosts exist

    def test_in_batch_affinity_stacks(self):
        pods = [mk_pod(f"p{i}", {"app": "web"},
                       affinity=aff(required=[term({"app": "web"}, HOST)]))
                for i in range(3)]
        names = solve(NODES, pods)
        assert names[0] is not None
        assert names[1] == names[0] and names[2] == names[0]


class TestInterPodPriority:
    def test_preferred_affinity_attracts(self):
        web = mk_pod("web", {"app": "web"}, node="a1")
        pod = mk_pod("p", affinity=aff(preferred=[
            {"weight": 100, "podAffinityTerm": term({"app": "web"})}]))
        names = solve(NODES, [pod], assigned=[web], policy=IPA_PRIO_POLICY)
        assert names[0] in ("a1", "a2")

    def test_preferred_anti_repels(self):
        web = mk_pod("web", {"app": "web"}, node="a1")
        pod = mk_pod("p", affinity=aff(anti_preferred=[
            {"weight": 100, "podAffinityTerm": term({"app": "web"})}]))
        names = solve(NODES, [pod], assigned=[web], policy=IPA_PRIO_POLICY)
        assert names[0] in ("b1", "b2")

    def test_hard_affinity_symmetry_attracts(self):
        # existing pod REQUIRES affinity to app=web; an incoming app=web pod
        # is pulled toward its domain by hardPodAffinityWeight
        anchor = mk_pod("anchor", {"app": "db"},
                        affinity=aff(required=[term({"app": "web"})]),
                        node="b1")
        pod = mk_pod("p", {"app": "web"})
        names = solve(NODES, [pod], assigned=[anchor], policy=IPA_PRIO_POLICY)
        assert names[0] in ("b1", "b2")

    def test_empty_topology_key_preferred_anti_uses_default_domains(self):
        web = mk_pod("web", {"app": "web"}, node="a1")
        pod = mk_pod("p", affinity=aff(anti_preferred=[
            {"weight": 100, "podAffinityTerm": term({"app": "web"}, "")}]))
        names = solve(NODES, [pod], assigned=[web], policy=IPA_PRIO_POLICY)
        assert names[0] in ("b1", "b2")


class TestStateDBAffinity:
    def test_refill_does_not_double_count(self):
        # a pod interning its own selector must be counted exactly once even
        # after the pending-refill pass runs (review regression)
        from kubernetes_tpu.state.statedb import StateDB
        db = StateDB(CAPS)
        for n in NODES:
            db.upsert_node(n)
        db.flush()
        pod = mk_pod("db0", {"app": "db"},
                     affinity=aff(anti_required=[term({"app": "db"}, HOST)]))
        db.add_pod(pod, "a1")
        state = db.flush()
        qid = next(iter(db.table.podsels.values()))
        row = db.table.row_of["a1"]
        assert float(np.asarray(state.podsel_count)[row, qid]) == 1.0
        db.remove_pod(pod.key)
        state = db.flush()
        assert float(np.asarray(state.podsel_count)[row, qid]) == 0.0

    def test_custom_topology_keys_get_distinct_slots(self):
        from kubernetes_tpu.state.cluster_state import NodeTable
        table = NodeTable(CAPS)
        s1 = table.intern_topo_key("rack")
        s2 = table.intern_topo_key("power")
        assert s1 != s2 and s1 >= 4 and s2 >= 4
        assert table.intern_topo_key("rack") == s1


def _random_interpod_cluster(rng, n_nodes=8, n_assigned=6, n_pods=12):
    apps = ["web", "db", "cache"]
    nodes = [mk_node(f"n{i}", zone=f"z{rng.randint(3)}",
                     cpu=f"{rng.randint(4, 9)}") for i in range(n_nodes)]

    def random_aff():
        if rng.rand() < 0.45:
            return None
        kind = rng.choice(["req", "anti", "pref", "antipref"])
        tkey = rng.choice([ZONE, HOST])
        t = term({"app": rng.choice(apps)}, tkey)
        if kind == "req":
            return aff(required=[t])
        if kind == "anti":
            return aff(anti_required=[t])
        w = int(rng.randint(1, 100))
        if kind == "pref":
            return aff(preferred=[{"weight": w, "podAffinityTerm": t}])
        return aff(anti_preferred=[{"weight": w, "podAffinityTerm": t}])

    assigned = []
    for i in range(n_assigned):
        p = mk_pod(f"a{i}", {"app": rng.choice(apps)}, affinity=random_aff(),
                   node=f"n{rng.randint(n_nodes)}")
        assigned.append(p)
    pods = []
    for i in range(n_pods):
        p = mk_pod(f"p{i}", {"app": rng.choice(apps)}, affinity=random_aff())
        if rng.rand() < 0.6:
            p.spec.containers[0].requests = {"cpu": f"{rng.choice([500, 1000])}m"}
        pods.append(p)
    return nodes, assigned, pods


FULL_POLICY = Policy(
    predicates=("GeneralPredicates", "MatchInterPodAffinity"),
    priorities=(("LeastRequestedPriority", 1),
                ("BalancedResourceAllocation", 1),
                ("TaintTolerationPriority", 1),
                ("InterPodAffinityPriority", 1)),
)


@pytest.mark.parametrize("seed", range(6))
def test_solver_serial_parity_interpod(seed):
    rng = np.random.RandomState(seed + 500)
    nodes, assigned, pods = _random_interpod_cluster(rng)
    ref = SerialScheduler(nodes, assigned, with_interpod=True)
    expected = ref.schedule(pods)
    caps = Capacities(num_nodes=8, batch_pods=16)
    got = solve(nodes, pods, assigned=assigned, policy=FULL_POLICY, caps=caps)
    assert got == expected


# ---- the k8s-5k zone-affinity deployment's mechanism ------------------

S1, S2 = {"security": "S1"}, {"security": "S2"}
# the Kubernetes docs' pods/pod-with-pod-affinity.yaml terms
EXAMPLE_AFF = aff(
    required=[term(S1)],
    anti_preferred=[{"weight": 100, "podAffinityTerm": term(S2)}])


def _example_pod(name, role, node=None):
    if role == "example":
        return mk_pod(name, {"app": "with-pod-affinity"},
                      affinity=EXAMPLE_AFF, node=node)
    return mk_pod(name, S1 if role == "s1" else S2, node=node)


def _zone_affinity_cluster(rng, per_zone=2):
    """3 zones of `per_zone` nodes; each zone holds 257-264 residents of
    each role (past the 256 to which a bf16 count operand holds integers),
    on random nodes of the zone."""
    nodes = [Node.from_dict({
        "metadata": {"name": f"z{z}n{i}", "labels": {ZONE: f"z{z}",
                                                     HOST: f"z{z}n{i}"}},
        "status": {"allocatable": {"cpu": "64", "memory": "256Gi",
                                   "pods": "2000"},
                   "conditions": [{"type": "Ready", "status": "True"}]}})
        for z in range(3) for i in range(per_zone)]
    residents = []
    for z in range(3):
        counts = {role: 257 + rng.randint(8)
                  for role in ("s1", "s2", "example")}
        for role, n in counts.items():
            for _ in range(n):
                node = f"z{z}n{rng.randint(per_zone)}"
                residents.append(_example_pod(f"r{len(residents)}", role,
                                              node=node))
    return nodes, residents


# seeds on which rounding the zone counts to bf16 moves a decision
@pytest.mark.parametrize("seed", [0, 1, 3])
def test_zone_affinity_deployment_parity_under_deletes(seed):
    """The k8s-5k / zone-affinity-saturated mechanism at small size:
    StateDB's flushes (whole-array re-uploads of the ledger and affinity
    counts when deletes dirty most rows) feeding the solver, batch after
    batch with resident deletes in between, against the serial scheduler
    on the same pods. Every decision must be the serial scheduler's
    exactly: k8s-5k.json states integer scores held exactly, and a
    zone's counts of each role here (257-264) are past the 256 to which a bf16
    count operand holds integers, so any rounding of them shows."""
    from kubernetes_tpu.state import encode_pods
    from kubernetes_tpu.state.statedb import StateDB

    rng = np.random.RandomState(seed + 2800)
    nodes, residents = _zone_affinity_cluster(rng)
    caps = Capacities(num_nodes=8, batch_pods=8)
    db = StateDB(caps)
    for n in nodes:
        db.upsert_node(n)
    for p in residents:
        assert db.add_pod(p)
    db.flush()
    rr = 0
    roles = ("s1", "s2", "example")
    for rnd in range(6):
        # the informer's deletes since the last flush
        for _ in range(int(rng.randint(4, 24))):
            gone = residents.pop(rng.randint(len(residents)))
            db.remove_pod(gone.key)
        pods = [_example_pod(f"b{rnd}-{i}", roles[rng.randint(3)])
                for i in range(caps.batch_pods)]
        ref = SerialScheduler(nodes, residents, with_interpod=True)
        ref.rr = rr
        expected = ref.schedule(pods)
        batch = encode_pods(pods, caps, db.table)
        state = db.flush()
        result = jit_schedule(state, batch, rr, FULL_POLICY, caps)
        got = [db.table.name_of[int(i)] if int(i) >= 0 else None
               for i in result.assignments[:len(pods)]]
        assert got == expected, f"round {rnd}"
        rr = int(result.rr_end)
        assert rr == ref.rr
        for pod, node in zip(pods, got):
            if node is not None:
                pod.spec.node_name = node
                db.add_pod(pod)
                residents.append(pod)
