"""The vectorized reference equals its node-by-node spec, and the spec
agrees with the repo's serial reference where both apply."""

import numpy as np
import pytest

from benchmark.harness.reference import Cluster, pod_shape, serial_pick
from benchmark.harness.workload import PodSequence, node_dicts, pod_dict

WEB_CACHE = [
    {"app": "cache",
     "anti_required": [{"app": "cache", "topology": "hostname"}]},
    {"app": "web",
     "affinity_preferred": [{"app": "cache", "topology": "zone",
                             "weight": 100}],
     "anti_preferred": [{"app": "web", "topology": "zone", "weight": 50}]}]
MIXES = {
    "default": {"cpu": "100m", "memory": "250Mi"},
    "web-cache": {"cpu": "100m", "memory": "250Mi", "services": 3,
                  "roles": WEB_CACHE},
    # bigger pods, hostname-scoped preferred terms of both signs
    "big": {"cpu": "700m", "memory": "1500Mi", "services": 2, "roles": [
        {"app": "db",
         "anti_required": [{"app": "db", "topology": "hostname"}],
         "anti_preferred": [{"app": "api", "topology": "hostname",
                             "weight": 30}]},
        {"app": "api",
         "affinity_preferred": [{"app": "db", "topology": "hostname",
                                 "weight": 20},
                                {"app": "api", "topology": "zone",
                                 "weight": 5}]}]},
    # preferred anti-affinity one way only: "own" is the scheduled pod's
    # term (app-a avoids app-b), "symmetric" a resident's term matching
    # the scheduled pod (app-b pods avoided by resident app-a pods)
    "anti-own": {"cpu": "100m", "memory": "250Mi", "roles": [
        {"app": "a", "anti_preferred": [{"app": "b", "topology": "zone",
                                         "weight": 40}]},
        {"app": "b"}, {"app": "b"}]},
    # the docs' pod-affinity example beside the pods it names (the mix of
    # traffic/zone-affinity-saturated.json), and required affinity at
    # hostname scope
    "pod-affinity": {"cpu": "100m", "memory": "250Mi", "roles": [
        {"app": "s1"}, {"app": "s2"},
        {"app": "ex",
         "affinity_required": [{"app": "s1", "topology": "zone"}],
         "anti_preferred": [{"app": "s2", "topology": "zone",
                             "weight": 100}]}]},
    "host-affinity": {"cpu": "100m", "memory": "250Mi", "roles": [
        {"app": "db", "affinity_required": [{"app": "db",
                                             "topology": "hostname"}]},
        {"app": "web", "affinity_required": [{"app": "db",
                                              "topology": "hostname"}]}]},
}


@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("seed", range(4))
def test_vectorized_equals_spec(mix, seed):
    cfg = {"nodes": 12, "zones": 3,
           "node": {"cpu": "4", "memory": "8Gi", "pods": "110"}}
    nodes = node_dicts(cfg)
    seq = PodSequence(MIXES[mix], seed, "p")
    rng = np.random.default_rng(seed)
    ref = Cluster(nodes)
    placed = []
    for rr in range(60):
        name, i = seq.next()
        pod = pod_dict(name, i, MIXES[mix])
        want = serial_pick(nodes, placed, pod, rr + seed)
        got = ref.pick(pod_shape(pod), rr + seed)
        assert got == want, (rr, name)
        if got is None:
            continue
        placed.append((pod, got))
        ref.add(name, nodes[got]["metadata"]["name"], pod_shape(pod))
        if placed and rng.random() < 0.3:     # churn: drop a resident
            k = int(rng.integers(len(placed)))
            gone, _ = placed.pop(k)
            ref.remove(gone["metadata"]["name"])


@pytest.mark.parametrize("mix", ["web-cache", "big", "anti-own",
                                 "pod-affinity", "host-affinity"])
def test_spec_agrees_with_repo_serial_reference(mix):
    """Cross-check against tests/serial_reference.py (read here only, the
    benchmark never imports it)."""
    serial = pytest.importorskip("tests.serial_reference")
    from kubernetes_tpu.api.objects import Node, Pod

    cfg = {"nodes": 9, "zones": 3,
           "node": {"cpu": "4", "memory": "8Gi", "pods": "110"}}
    nodes = node_dicts(cfg)
    seq = PodSequence(MIXES[mix], 3, "p")
    pods = [pod_dict(*seq.next(), MIXES[mix]) for _ in range(40)]
    theirs = serial.SerialScheduler([Node.from_dict(n) for n in nodes],
                                    with_interpod=True)
    placed = []
    for pod in pods:     # selectHost's counter moves only when it picks
        mine = serial_pick(nodes, placed, pod, len(placed))
        want = theirs.schedule_one(Pod.from_dict(pod))
        assert (None if mine is None else nodes[mine]["metadata"]["name"]) \
            == want
        if mine is not None:
            placed.append((pod, mine))


@pytest.mark.parametrize("direction", ["own", "symmetric"])
def test_preferred_anti_affinity(direction):
    """One zone holds app-b pods, the others none. A scheduled app-a pod
    (its own term) and a scheduled app-b pod (resident app-a pods' term)
    each leave that zone, in the vectorized form and in the spec, and
    the count is the term's weight times the matching pods, subtracted."""
    nodes = node_dicts({"nodes": 6, "zones": 3,
                        "node": {"cpu": "4", "memory": "8Gi", "pods": "110"}})
    mix = MIXES["anti-own"]
    a, b = pod_dict("a", 0, mix), pod_dict("b", 1, mix)
    if direction == "own":
        residents, pod = [("r0", b, 0), ("r1", b, 3)], a
    else:
        residents, pod = [("r0", a, 0), ("r1", a, 3), ("r2", b, 1)], b
    ref = Cluster(nodes)
    for name, p, j in residents:
        ref.add(name, nodes[j]["metadata"]["name"], pod_shape(p))
    _, count = ref.domain_terms(pod_shape(pod))
    assert count.tolist() == [-80, 0, 0, -80, 0, 0]   # zone-0: nodes 0, 3
    placed = [(p, j) for _, p, j in residents]
    for rr in range(6):
        got = ref.pick(pod_shape(pod), rr)
        assert got == serial_pick(nodes, placed, pod, rr)
        assert got % 3 != 0


def test_required_affinity():
    """A pod with required zone affinity to app-s1 fits nowhere while no
    s1 pod exists (it does not match its own term), then only in s1's
    zone; an s1 pod scheduled next is drawn to the zone of the residents
    whose required term it meets, by the symmetric weight 1 a resident,
    in the vectorized form and in the spec."""
    nodes = node_dicts({"nodes": 6, "zones": 3,
                        "node": {"cpu": "4", "memory": "8Gi", "pods": "110"}})
    mix = MIXES["pod-affinity"]
    s1, ex = pod_dict("s1", 0, mix), pod_dict("ex", 2, mix)
    ref = Cluster(nodes)
    assert ref.pick(pod_shape(ex), 0) is None
    assert serial_pick(nodes, [], ex, 0) is None
    ref.add("r0", "node-1", pod_shape(s1))
    placed = [(s1, 1)]
    for rr in range(4):
        got = ref.pick(pod_shape(ex), rr)
        assert got == serial_pick(nodes, placed, ex, rr)
        assert got % 3 == 1                     # zone-1: nodes 1, 4
    for k, j in enumerate((2, 5)):
        ref.add(f"e{k}", f"node-{j}", pod_shape(ex))
        placed.append((ex, j))
    _, count = ref.domain_terms(pod_shape(s1))
    assert count.tolist() == [0, 0, 2, 0, 0, 2]  # zone-2: nodes 2, 5


def test_first_pod_matching_its_own_affinity_term():
    """A db pod whose required hostname affinity selects db pods goes
    anywhere while none exists, then only beside one; a web pod needing
    a db fits nowhere before the first db."""
    nodes = node_dicts({"nodes": 4, "zones": 2,
                        "node": {"cpu": "4", "memory": "8Gi", "pods": "110"}})
    mix = MIXES["host-affinity"]
    db, web = pod_dict("db", 0, mix), pod_dict("web", 1, mix)
    ref = Cluster(nodes)
    assert ref.pick(pod_shape(web), 0) is None
    first = ref.pick(pod_shape(db), 1)
    assert first == serial_pick(nodes, [], db, 1) is not None
    ref.add("d0", nodes[first]["metadata"]["name"], pod_shape(db))
    for rr in range(3):
        for pod in (db, web):
            got = ref.pick(pod_shape(pod), rr)
            assert got == serial_pick(nodes, [(db, first)], pod, rr) == first
