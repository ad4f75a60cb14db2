"""Cluster and pod fixtures (copied from the repo's scheduler_perf
analog, kubernetes_tpu/perf/fixtures.py, as plain v1 dicts so the
reference can read them without the program), the resident prefill and
the seeded pod sequence every traffic mix draws from."""

from __future__ import annotations

import numpy as np

from benchmark.harness.reference import HOST, ZONE, pod_shape


def node_dicts(cfg: dict) -> list[dict]:
    """make_nodes: uniform Ready nodes, zone = i % zones."""
    node, zones = cfg["node"], cfg["zones"]
    return [{
        "metadata": {"name": f"node-{i}", "labels": {
            "kubernetes.io/hostname": f"node-{i}",
            "failure-domain.beta.kubernetes.io/zone": f"zone-{i % zones}",
            "failure-domain.beta.kubernetes.io/region": "region-1"}},
        "spec": {"taints": []},
        "status": {"allocatable": {"cpu": node["cpu"],
                                   "memory": node["memory"],
                                   "pods": node["pods"]},
                   "conditions": [{"type": "Ready", "status": "True"}]},
    } for i in range(cfg["nodes"])]


TOPOLOGY = {"hostname": HOST, "zone": ZONE}


def _term(service: int, term: dict) -> dict:
    return {"labelSelector": {"matchLabels": {
                "app": f"{term['app']}-{service}"}},
            "topologyKey": TOPOLOGY[term["topology"]]}


def _preferred(service: int, terms: list) -> list:
    return [{"weight": t["weight"], "podAffinityTerm": _term(service, t)}
            for t in terms]


def pod_dict(name: str, i: int, mix: dict) -> dict:
    """make_pods' template for pattern index i. A mix with `roles` runs
    `services` copies of them: pattern i is role i % len(roles) of service
    i // len(roles), labelled app=<role app>-<service>, its terms selecting
    app=<term app>-<service> of its own service (`affinity_required` /
    `anti_required`: required pod affinity / anti-affinity;
    `affinity_preferred` / `anti_preferred`: preferred pod affinity /
    anti-affinity with their weights), in `topology` hostname or zone. A
    mix without roles is one plain pod."""
    meta: dict = {"name": name, "namespace": "default"}
    spec: dict = {"containers": [{
        "name": "app", "image": "k8s.gcr.io/pause:3.0",
        "resources": {"requests": {"cpu": mix["cpu"],
                                   "memory": mix["memory"]}}}]}
    roles = mix.get("roles")
    if roles:
        role, service = roles[i % len(roles)], i // len(roles)
        meta["labels"] = {"app": f"{role['app']}-{service}"}
        anti: dict = {}
        if role.get("anti_required"):
            anti["requiredDuringSchedulingIgnoredDuringExecution"] = [
                _term(service, t) for t in role["anti_required"]]
        if role.get("anti_preferred"):
            anti["preferredDuringSchedulingIgnoredDuringExecution"] = \
                _preferred(service, role["anti_preferred"])
        pod_aff: dict = {}
        if role.get("affinity_required"):
            pod_aff["requiredDuringSchedulingIgnoredDuringExecution"] = [
                _term(service, t) for t in role["affinity_required"]]
        if role.get("affinity_preferred"):
            pod_aff["preferredDuringSchedulingIgnoredDuringExecution"] = \
                _preferred(service, role["affinity_preferred"])
        affinity: dict = {}
        if pod_aff:
            affinity["podAffinity"] = pod_aff
        if anti:
            affinity["podAntiAffinity"] = anti
        if affinity:
            spec["affinity"] = affinity
    return {"metadata": meta, "spec": spec}


class PodSequence:
    """The mix's pods in a seeded order: the pattern indices repeat in
    blocks of `period` (every pattern of the mix once per block), each
    block shuffled by the seed. Every seed offers the same pods in
    another order. Pods are named <prefix>-<n>."""

    def __init__(self, mix: dict, seed: int, prefix: str):
        self.mix = mix
        self.prefix = prefix
        self.period = mix.get("services", 1) * len(mix.get("roles") or [1])
        self.rng = np.random.default_rng([seed, len(prefix)])
        self.block: list[int] = []
        self.n = 0
        self._shapes: dict[int, object] = {}
        self._templates: dict[int, object] = {}

    def next(self) -> tuple[str, int]:
        if not self.block:
            self.block = list(self.rng.permutation(self.period))
        i = int(self.block.pop())
        name = f"{self.prefix}-{self.n}"
        self.n += 1
        return name, i

    def shape(self, i: int):
        s = self._shapes.get(i)
        if s is None:
            s = self._shapes[i] = pod_shape(pod_dict("x", i, self.mix))
        return s

    def pod(self, name: str, i: int, node: str | None = None):
        """The program's Pod object for pattern i, as a client hands it
        over: fresh metadata, the pattern's spec (immutable once stored,
        as every watcher of the store treats it)."""
        import copy

        from kubernetes_tpu.api.objects import Pod

        t = self._templates.get(i)
        if t is None:
            t = self._templates[i] = Pod.from_dict(pod_dict("x", i, self.mix))
        meta = copy.copy(t.metadata)
        meta.name = name
        spec = t.spec
        if node is not None:
            spec = copy.copy(spec)
            spec.node_name = node
        return Pod(metadata=meta, spec=spec, status=t.status)


def prefill(nodes: list[dict], per_node: int, seq: PodSequence,
            seed: int) -> list[tuple[dict, str]]:
    """Resident pods, pre-bound: `per_node` on every node, placed by a
    seeded round-robin that skips a node where the pod would break a
    required hostname anti-affinity term, its own or a resident's, or its
    own required affinity term (the guarantees hold from the first
    moment). A pod whose affinity no resident can meet yet waits until
    one can. Returns [(name, pattern, node name)] oldest first -- the
    churn's delete order."""
    n = len(nodes)
    zone = [nd["metadata"]["labels"][ZONE] for nd in nodes]
    order = np.random.default_rng([seed, 7]).permutation(n)
    count = [0] * n
    labels_on: list[set] = [set() for _ in range(n)]
    terms_on: list[set] = [set() for _ in range(n)]
    labels_in = {z: set() for z in zone}
    out: list = []
    cursor = 0

    def affinity_met(t, s, j) -> bool:
        here = labels_on[j] if t.topology == HOST else labels_in[zone[j]]
        if any(t.matches(dict(lab)) for lab in here):
            return True
        return not any(t.matches(dict(lab)) for z in labels_in.values()
                       for lab in z) and t.matches(s.label_dict)

    def place(name, i) -> bool:
        nonlocal cursor
        s = seq.shape(i)
        mine = s.label_dict
        for step in range(n):
            j = int(order[(cursor + step) % n])
            if count[j] >= per_node or any(t.matches(mine)
                                           for t in terms_on[j]) or any(
                    t.matches(dict(lab)) for t in s.anti_req
                    for lab in labels_on[j]) or not all(
                    affinity_met(t, s, j) for t in s.aff_req):
                continue
            cursor = (cursor + step + 1) % n
            count[j] += 1
            labels_on[j].add(s.labels)
            labels_in[zone[j]].add(s.labels)
            terms_on[j].update(s.anti_req)
            out.append((name, i, nodes[j]["metadata"]["name"]))
            return True
        return False

    waiting: list = []
    while len(out) + len(waiting) < per_node * n:
        name, i = seq.next()
        if any(t.topology != HOST for t in seq.shape(i).anti_req):
            raise NotImplementedError("prefill places hostname terms only")
        if not place(name, i):
            waiting.append((name, i))
            continue
        progress = True
        while waiting and progress:
            progress = False
            for w in list(waiting):
                if place(*w):
                    waiting.remove(w)
                    progress = True
    if waiting:
        raise ValueError(f"prefill could not place {len(waiting)} pods")
    return out
