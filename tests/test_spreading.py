"""SelectorSpread / ServiceAntiAffinity / ImageLocality / NodePreferAvoid /
MostRequested / NodeLabel / CheckNodeLabelPresence / ServiceAffinity tests —
unit tables plus randomized serial parity (reference selector_spreading.go,
image_locality.go, node_prefer_avoid_pods.go, most_requested.go,
node_label.go, predicates.go:737,821)."""

import json

import numpy as np
import pytest

from kubernetes_tpu.api.objects import Node, Pod, ReplicaSet, Service
from kubernetes_tpu.models.policy import Policy, build_policy_rows
from kubernetes_tpu.ops.solver import schedule_batch
from kubernetes_tpu.state import Capacities, encode_cluster
from kubernetes_tpu.state.cluster_state import apply_pending_refreshes
from kubernetes_tpu.state.context import EncodeContext

from tests.serial_reference import SerialScheduler

CAPS = Capacities(num_nodes=8, batch_pods=16)
ZONE = "failure-domain.beta.kubernetes.io/zone"

BASE_PREDS = ("GeneralPredicates", "PodToleratesNodeTaints",
              "CheckNodeCondition")
BASE_PRIOS = (("LeastRequestedPriority", 1), ("BalancedResourceAllocation", 1),
              ("TaintTolerationPriority", 1))


def mk_node(name, labels=None, pods="110", cpu="32", mem="128Gi",
            images=None, annotations=None):
    return Node.from_dict({
        "metadata": {"name": name, "labels": labels or {},
                     "annotations": annotations or {}},
        "status": {"allocatable": {"cpu": cpu, "memory": mem, "pods": pods},
                   "conditions": [{"type": "Ready", "status": "True"}],
                   "images": images or []},
    })


def mk_pod(name, labels=None, node_name="", cpu="100m", namespace="default",
           image="", owner=None, node_selector=None):
    containers = [{"name": "c", "resources": {"requests": {"cpu": cpu}}}]
    if image:
        containers[0]["image"] = image
    return Pod.from_dict({
        "metadata": {"name": name, "namespace": namespace, "uid": f"u-{name}",
                     "labels": labels or {},
                     "ownerReferences": [owner] if owner else []},
        "spec": {"nodeName": node_name, "containers": containers,
                 "nodeSelector": node_selector or {}},
    })


def mk_ctx(services=(), rcs=(), rss=(), sss=(), all_pods=(), nodes=(),
           sa_labels=(), service_anti=False):
    node_map = {n.metadata.name: n for n in nodes}
    return EncodeContext(
        get_services=lambda ns: [s for s in services
                                 if s.metadata.namespace == ns],
        get_rcs=lambda ns: [r for r in rcs if r.metadata.namespace == ns],
        get_rss=lambda ns: [r for r in rss if r.metadata.namespace == ns],
        get_sss=lambda ns: [r for r in sss if r.metadata.namespace == ns],
        list_pods=lambda ns: [p for p in all_pods
                              if p.metadata.namespace == ns],
        get_node=lambda name: node_map.get(name),
        service_affinity_labels=tuple(sa_labels),
        service_anti=service_anti,
    )


def solve(nodes, pending, policy, assigned=(), ctx=None, caps=CAPS):
    state, batch, table = encode_cluster(nodes, pending, caps,
                                         assigned_pods=assigned, ctx=ctx)
    prows = build_policy_rows(policy, table, caps)
    apply_pending_refreshes(state, table)
    result = schedule_batch(state, batch, np.uint32(0), policy=policy,
                            caps=caps, prows=prows)
    rows = np.asarray(result.assignments)
    return [table.name_of[r] if r >= 0 else None
            for r in rows[: len(pending)]]


def svc(name="svc", selector=None, namespace="default"):
    return Service.from_dict({
        "metadata": {"name": name, "namespace": namespace},
        "spec": {"selector": selector or {"app": "web"}}})


class TestSelectorSpread:
    POLICY = Policy(predicates=BASE_PREDS,
                    priorities=BASE_PRIOS + (("SelectorSpreadPriority", 2),))

    def test_spreads_service_pods(self):
        nodes = [mk_node(f"n{i}") for i in range(3)]
        web = dict(labels={"app": "web"})
        assigned = [mk_pod("a0", node_name="n0", **web),
                    mk_pod("a1", node_name="n0", **web),
                    mk_pod("a2", node_name="n1", **web)]
        all_pods = assigned + [mk_pod("p", **web)]
        ctx = mk_ctx(services=[svc()], all_pods=all_pods)
        got = solve(nodes, [mk_pod("p", **web)], self.POLICY,
                    assigned=assigned, ctx=ctx)
        assert got == ["n2"]

    def test_zone_weighting(self):
        # n0,n1 in zone A (3 pods total), n2 in zone B (1 pod): zone
        # weighting (2/3) pulls the new pod to zone B even though n1 and
        # n2 tie on node-local count
        nodes = [mk_node("n0", labels={ZONE: "a"}),
                 mk_node("n1", labels={ZONE: "a"}),
                 mk_node("n2", labels={ZONE: "b"})]
        web = dict(labels={"app": "web"})
        assigned = [mk_pod("a0", node_name="n0", **web),
                    mk_pod("a1", node_name="n0", **web),
                    mk_pod("a2", node_name="n1", **web),
                    mk_pod("a3", node_name="n2", **web)]
        ctx = mk_ctx(services=[svc()], all_pods=assigned)
        got = solve(nodes, [mk_pod("p", **web)], self.POLICY,
                    assigned=assigned, ctx=ctx)
        assert got == ["n2"]

    def test_in_batch_spreading(self):
        # 3 pods of one replica set in a single batch spread over 3 nodes
        nodes = [mk_node(f"n{i}") for i in range(3)]
        rs = ReplicaSet.from_dict({
            "metadata": {"name": "rs", "namespace": "default"},
            "spec": {"selector": {"matchLabels": {"app": "rs"}}}})
        pending = [mk_pod(f"p{i}", labels={"app": "rs"}) for i in range(3)]
        ctx = mk_ctx(rss=[rs], all_pods=pending)
        got = solve(nodes, pending, self.POLICY, ctx=ctx)
        assert sorted(got) == ["n0", "n1", "n2"]

    @pytest.mark.parametrize("seed", [1, 5])
    def test_randomized_parity(self, seed):
        rng = np.random.RandomState(seed)
        zones = ["a", "b", ""]
        nodes = [mk_node(f"n{i}", pods="8",
                         labels={ZONE: zones[i % 3]} if zones[i % 3] else {})
                 for i in range(5)]
        services = [svc("s1", {"app": "web"}), svc("s2", {"tier": "db"})]
        rs = ReplicaSet.from_dict({
            "metadata": {"name": "rs", "namespace": "default"},
            "spec": {"selector": {"matchLabels": {"app": "web"}}}})

        def rand_labels():
            out = {}
            if rng.rand() < 0.6:
                out["app"] = "web"
            if rng.rand() < 0.3:
                out["tier"] = "db"
            return out

        assigned = [mk_pod(f"a{i}", labels=rand_labels(),
                           node_name=f"n{rng.randint(5)}") for i in range(8)]
        pending = [mk_pod(f"p{i}", labels=rand_labels()) for i in range(10)]
        ctx = mk_ctx(services=services, rss=[rs], all_pods=assigned + pending)

        serial = SerialScheduler(
            nodes, assigned, volume_ctx=ctx,
            extra_priorities=frozenset({"SelectorSpreadPriority"}))
        # serial oracle weighs spread at 1; use weight-1 policy
        policy = Policy(predicates=BASE_PREDS,
                        priorities=BASE_PRIOS + (("SelectorSpreadPriority", 1),))
        want = serial.schedule(pending)
        got = solve(nodes, pending, policy, assigned=assigned, ctx=ctx)
        assert got == want


class TestImageLocality:
    POLICY = Policy(predicates=BASE_PREDS,
                    priorities=BASE_PRIOS + (("ImageLocalityPriority", 3),))

    def test_prefers_node_with_image(self):
        big = [{"names": ["app:v1"], "sizeBytes": 700 * 1024 * 1024}]
        nodes = [mk_node("n0"), mk_node("n1", images=big)]
        got = solve(nodes, [mk_pod("p", image="app:v1")], self.POLICY)
        assert got == ["n1"]

    def test_small_image_scores_zero(self):
        tiny = [{"names": ["app:v1"], "sizeBytes": 10 * 1024 * 1024}]
        nodes = [mk_node("n0"), mk_node("n1", images=tiny)]
        # below minImgSize both nodes score 0: round-robin picks n0 first
        got = solve(nodes, [mk_pod("p", image="app:v1")], self.POLICY)
        assert got == ["n0"]


AVOID = json.dumps({"preferAvoidPods": [{"podSignature": {
    "podController": {"kind": "ReplicaSet", "uid": "rs-1"}}}]})


class TestNodePreferAvoidPods:
    POLICY = Policy(predicates=BASE_PREDS,
                    priorities=BASE_PRIOS
                    + (("NodePreferAvoidPodsPriority", 10000),))

    def test_avoids_annotated_node(self):
        nodes = [mk_node("n0", annotations={
            "scheduler.alpha.kubernetes.io/preferAvoidPods": AVOID}),
            mk_node("n1", cpu="1")]  # worse on resources, still wins
        owner = {"kind": "ReplicaSet", "uid": "rs-1", "controller": True,
                 "name": "rs"}
        got = solve(nodes, [mk_pod("p", owner=owner)], self.POLICY)
        assert got == ["n1"]

    def test_other_controller_unaffected(self):
        nodes = [mk_node("n0", annotations={
            "scheduler.alpha.kubernetes.io/preferAvoidPods": AVOID}),
            mk_node("n1", cpu="1")]
        owner = {"kind": "ReplicaSet", "uid": "rs-2", "controller": True,
                 "name": "other"}
        got = solve(nodes, [mk_pod("p", owner=owner)], self.POLICY)
        assert got == ["n0"]


class TestMostRequested:
    POLICY = Policy(predicates=BASE_PREDS,
                    priorities=(("MostRequestedPriority", 1),))

    def test_packs_onto_used_node(self):
        nodes = [mk_node("n0", cpu="4", mem="8Gi"),
                 mk_node("n1", cpu="4", mem="8Gi")]
        assigned = [mk_pod("a", node_name="n1", cpu="2")]
        got = solve(nodes, [mk_pod("p", cpu="500m")], self.POLICY,
                    assigned=assigned)
        assert got == ["n1"]


class TestNodeLabelPriority:
    def test_prefers_labeled_node(self):
        policy = Policy(
            predicates=BASE_PREDS,
            priorities=BASE_PRIOS + (("SsdFirst", 5),),
            label_priorities=(("SsdFirst", "disk-ssd", True),))
        nodes = [mk_node("n0"), mk_node("n1", labels={"disk-ssd": "yes"})]
        got = solve(nodes, [mk_pod("p")], policy)
        assert got == ["n1"]

    def test_absence_preference(self):
        policy = Policy(
            predicates=BASE_PREDS,
            priorities=BASE_PRIOS + (("NoSpot", 5),),
            label_priorities=(("NoSpot", "spot", False),))
        nodes = [mk_node("n0", labels={"spot": "true"}), mk_node("n1")]
        got = solve(nodes, [mk_pod("p")], policy)
        assert got == ["n1"]


class TestCheckNodeLabelPresence:
    def test_required_label(self):
        policy = Policy(
            predicates=BASE_PREDS + ("RegionRequired",),
            priorities=BASE_PRIOS,
            label_presence_predicates=(("RegionRequired", ("region",), True),))
        nodes = [mk_node("n0"), mk_node("n1", labels={"region": "r1"})]
        got = solve(nodes, [mk_pod("p")], policy)
        assert got == ["n1"]

    def test_forbidden_label(self):
        policy = Policy(
            predicates=BASE_PREDS + ("NoRetiring",),
            priorities=BASE_PRIOS,
            label_presence_predicates=(("NoRetiring", ("retiring",), False),))
        nodes = [mk_node("n0", labels={"retiring": "soon"}), mk_node("n1")]
        got = solve(nodes, [mk_pod("p")], policy)
        assert got == ["n1"]


class TestServiceAffinity:
    POLICY = Policy(
        predicates=BASE_PREDS + ("ServiceAffinityRegion",),
        priorities=BASE_PRIOS,
        service_affinity_predicates=(("ServiceAffinityRegion", ("region",)),))

    def test_follows_first_service_pod(self):
        nodes = [mk_node("n0", labels={"region": "r1"}),
                 mk_node("n1", labels={"region": "r2"}),
                 mk_node("n2", labels={"region": "r1"})]
        web = {"app": "web"}
        first = mk_pod("a0", labels=web, node_name="n0")
        all_pods = [first]
        ctx = mk_ctx(services=[svc()], all_pods=all_pods, nodes=nodes,
                     sa_labels=("region",))
        # n1 is emptier but the service is pinned to region r1
        assigned = [first]
        pending = [mk_pod("p", labels=web)]
        got = solve(nodes, pending, self.POLICY, assigned=assigned, ctx=ctx)
        assert got in (["n0"], ["n2"])
        # pinned nodeSelector wins over inference
        pending = [mk_pod("q", labels=web, node_selector={"region": "r2"})]
        got = solve(nodes, pending, self.POLICY, assigned=assigned, ctx=ctx)
        assert got == ["n1"]


    def test_first_service_pod_schedules_unconstrained(self):
        """Regression (ADVICE r1 high): the backfill lister holds only
        assigned pods (factory.go:139); the service's first pod used to
        backfill from itself (unbound) -> hard error -> livelock."""
        nodes = [mk_node("n0", labels={"region": "r1"}),
                 mk_node("n1", labels={"region": "r2"})]
        web = {"app": "web"}
        pending = [mk_pod("p", labels=web)]
        ctx = mk_ctx(services=[svc()], all_pods=pending, nodes=nodes,
                     sa_labels=("region",))
        got = solve(nodes, pending, self.POLICY, assigned=(), ctx=ctx)
        assert got[0] in ("n0", "n1")


class TestServiceSelectorNilVsEmpty:
    def test_empty_map_selector_matches_all_nil_matches_none(self):
        """service_expansion.go:45-50: nil selectors match nothing; a
        non-nil empty map selects everything."""
        from kubernetes_tpu.state.spreading import pod_controller_selectors

        empty = Service.from_dict({
            "metadata": {"name": "s", "namespace": "default"},
            "spec": {"selector": {}}})
        absent = Service.from_dict({
            "metadata": {"name": "t", "namespace": "default"},
            "spec": {}})
        assert empty.selector == {}
        assert absent.selector is None
        ctx = mk_ctx(services=[empty, absent])
        sels = pod_controller_selectors(mk_pod("p"), ctx, services_only=True)
        assert sels == [()]  # the empty canon (match-all); nil skipped


class TestServiceAntiAffinity:
    POLICY = Policy(
        predicates=BASE_PREDS,
        priorities=(("RackSpread", 1),),
        service_anti_priorities=(("RackSpread", "rack"),))

    def test_spreads_across_label_values(self):
        nodes = [mk_node("n0", labels={"rack": "r1"}),
                 mk_node("n1", labels={"rack": "r1"}),
                 mk_node("n2", labels={"rack": "r2"})]
        web = {"app": "web"}
        assigned = [mk_pod("a0", labels=web, node_name="n0")]
        all_pods = assigned + [mk_pod("p", labels=web)]
        ctx = mk_ctx(services=[svc()], all_pods=all_pods, service_anti=True)
        got = solve(nodes, [mk_pod("p", labels=web)], self.POLICY,
                    assigned=assigned, ctx=ctx)
        assert got == ["n2"]


class TestDriverSpreading:
    def test_in_batch_spread_through_driver(self):
        """Regression: the driver path (encode cache, no fill_batch_affinity
        pass) must still give pods their own union-entry match so the scan
        ledger sees same-batch placements."""
        import asyncio

        from kubernetes_tpu.apiserver.store import ObjectStore
        from kubernetes_tpu.scheduler.driver import Scheduler

        async def run():
            store = ObjectStore()
            for i in range(3):
                store.create(mk_node(f"n{i}"))
            store.create(ReplicaSet.from_dict({
                "metadata": {"name": "rs", "namespace": "default"},
                "spec": {"selector": {"matchLabels": {"app": "rs"}}}}))
            policy = Policy(
                predicates=BASE_PREDS,
                priorities=BASE_PRIOS + (("SelectorSpreadPriority", 2),))
            sched = Scheduler(store, caps=Capacities(num_nodes=4,
                                                     batch_pods=4),
                              policy=policy)
            await sched.start()
            for i in range(3):
                store.create(mk_pod(f"p{i}", labels={"app": "rs"}))
            total = 0
            for _ in range(40):
                total += await sched.schedule_pending(wait=0.05)
                if total >= 3:
                    break
            sched.stop()
            return {p.metadata.name: p.spec.node_name
                    for p in store.list("Pod")}

        bound = asyncio.run(run())
        assert sorted(bound.values()) == ["n0", "n1", "n2"], bound


class TestPolicyJson:
    def test_argument_round_trip(self):
        policy = Policy.from_json(json.dumps({
            "kind": "Policy", "apiVersion": "v1",
            "predicates": [
                {"name": "GeneralPredicates"},
                {"name": "ZoneRequired", "argument": {"labelsPresence": {
                    "labels": ["zone"], "presence": True}}},
                {"name": "Affinity", "argument": {"serviceAffinity": {
                    "labels": ["region"]}}},
            ],
            "priorities": [
                {"name": "RackSpread", "weight": 2, "argument": {
                    "serviceAntiAffinity": {"label": "rack"}}},
                {"name": "SsdFirst", "weight": 3, "argument": {
                    "labelPreference": {"label": "ssd", "presence": True}}},
            ],
        }))
        assert policy.label_presence_predicates == (
            ("ZoneRequired", ("zone",), True),)
        assert policy.service_affinity_predicates == (
            ("Affinity", ("region",)),)
        assert policy.service_anti_priorities == (("RackSpread", "rack"),)
        assert policy.label_priorities == (("SsdFirst", "ssd", True),)
        assert policy.service_affinity_labels() == ("region",)
        rt = Policy.from_json(policy.to_json())
        assert rt == policy


class TestExactCounts:
    """Counts past bf16's exact-integer range (256). A dot at DEFAULT
    precision may round f32 operands to bf16 on the TPU's MXU; CPU
    arithmetic is exact either way, so the exact form (HIGHEST) is pinned
    in the lowered program text, and the semantics by serial parity."""

    # distinct universe widths, so each dot's operand shapes name its site
    CAPS = Capacities(num_nodes=48, batch_pods=8, domain_universe=40,
                      image_universe=24, podsel_universe=32,
                      term_universe=16)
    POLICY = Policy(
        predicates=BASE_PREDS + ("MatchInterPodAffinity",),
        priorities=BASE_PRIOS + (("SelectorSpreadPriority", 1),
                                 ("ImageLocalityPriority", 1),
                                 ("InterPodAffinityPriority", 1),
                                 ("RackSpread", 1)),
        service_anti_priorities=(("RackSpread", "rack"),))

    def dots(self) -> list[tuple[str, tuple, tuple, tuple]]:
        import re

        import jax

        caps = self.CAPS
        nodes = [mk_node(f"n{i}", labels={ZONE: f"z{i % 3}", "rack": "r"})
                 for i in range(4)]
        state, batch, table = encode_cluster(nodes, [mk_pod("p")], caps,
                                             ctx=mk_ctx(service_anti=True))
        prows = build_policy_rows(self.POLICY, table, caps)
        apply_pending_refreshes(state, table)
        text = jax.jit(lambda s, b, rr: schedule_batch(
            s, b, rr, self.POLICY, caps=caps, prows=prows)).lower(
            state, batch, np.uint32(0)).as_text()

        def dims(t):
            return tuple(int(d) for d in t.split("x")[:-1])

        out = []
        for line in text.splitlines():
            if "dot_general" not in line:
                continue
            prec = re.search(r"precision = \[(\w+), \w+\]", line).group(1)
            lhs, rhs, res = re.search(
                r": \(tensor<([^>]*)>, tensor<([^>]*)>\) -> tensor<([^>]*)>",
                line).groups()
            out.append((prec, dims(lhs), dims(rhs), dims(res)))
        return out

    def test_count_operand_dots_lower_at_highest(self):
        c = self.CAPS
        n, d, k = c.num_nodes, c.domain_universe, c.topology_slots
        sites = {
            # spread.py: per-zone / per-label-value sums and broadcasts
            "domain sums": ((d, n), (n,), (d,)),
            "domain broadcast": ((n, d), (d,), (n,)),
            # priorities.py image_locality: image sizes in bytes, per pod
            "image sizes": ((n, c.image_universe),
                            (c.batch_pods, c.image_universe),
                            (n, c.batch_pods)),
            # interpod.py domain_aggregates / _slot_counts, per universe
            "aggregate podsel": ((n, k, d), (n, c.podsel_universe),
                                 (k, d, c.podsel_universe)),
            "aggregate terms": ((n, k, d), (n, c.term_universe),
                                (k, d, c.term_universe)),
            "slot podsel": ((k, n, d), (k, d, c.podsel_universe),
                            (k, n, c.podsel_universe)),
            "slot terms": ((k, n, d), (k, d, c.term_universe),
                           (k, n, c.term_universe)),
        }
        found = self.dots()
        for site, shape in sites.items():
            precs = [p for p, *s in found if tuple(s) == shape]
            assert precs, f"{site}: no dot {shape} in the program"
            assert set(precs) == {"HIGHEST"}, (site, precs)
        # one-hot x one-hot memberships stay at DEFAULT (the cheap form)
        assert any(p == "DEFAULT" for p, *_ in found)

    def test_parity_with_domain_counts_past_256(self):
        rng = np.random.RandomState(7)
        zones = ["a", "b", ""]
        nodes = [mk_node(f"n{i}", pods="400",
                         labels={ZONE: zones[i % 3]} if zones[i % 3] else {})
                 for i in range(5)]
        web = {"app": "web"}
        assigned = [mk_pod(f"a{i}", labels=web,
                           node_name=f"n{rng.randint(5)}")
                    for i in range(1200)]
        per_zone = {z: sum(1 for p in assigned
                           if zones[int(p.spec.node_name[1:]) % 3] == z)
                    for z in ("a", "b")}
        assert min(per_zone.values()) > 256, per_zone
        pending = [mk_pod(f"p{i}", labels=web) for i in range(12)]
        ctx = mk_ctx(services=[svc("s1", web)], all_pods=assigned + pending)
        serial = SerialScheduler(
            nodes, assigned, volume_ctx=ctx,
            extra_priorities=frozenset({"SelectorSpreadPriority"}))
        policy = Policy(predicates=BASE_PREDS,
                        priorities=BASE_PRIOS + (("SelectorSpreadPriority", 1),))
        want = serial.schedule(pending)
        got = solve(nodes, pending, policy, assigned=assigned, ctx=ctx)
        assert got == want
