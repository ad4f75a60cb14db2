"""Plain reference scheduler: the serial scheduleOne semantics over a
whole cluster, evaluated with exact integer arithmetic in numpy.

Independent of the program: it reads the v1-style dicts the traffic
generator made (never the program's objects, encodings or tables) and
reimplements what `tests/serial_reference.py` specifies for the terms
the benchmark's mixes use -- the resource fit (pods, cpu, memory),
required pod affinity and anti-affinity, preferred pod affinity and
anti-affinity (both directions, anti-affinity's weight subtracted), the
symmetric weight of a resident's required affinity term, LeastRequested,
BalancedResourceAllocation and InterPodAffinity
priorities, and selectHost's round-robin tie-break in node-list order.
Every other priority of the default policy is a constant over identical
nodes (no taints, no node affinity, no services, no avoid annotations)
and cannot move a decision. A pod spec outside that subset raises:
widening a mix means widening this file, in a benchmark PR.

`serial_pick` is the same decision written node by node over plain
Python objects (the spec, kept for tests/test_reference.py);
`Cluster.evaluate` is the vectorized form the correctness check runs.
"""

from __future__ import annotations

from dataclasses import dataclass
from fractions import Fraction

import numpy as np

MAX_PRIORITY = 10
HOST = "kubernetes.io/hostname"
ZONE = "failure-domain.beta.kubernetes.io/zone"
TOPOLOGIES = (HOST, ZONE)
DEFAULT_NONZERO_CPU = 100                 # milli (non_zero.go)
DEFAULT_NONZERO_MEM = 200 * 1024 * 1024   # bytes
HARD_WEIGHT = 1     # HardPodAffinitySymmetricWeight, the v1.8 default

_SUFFIX = {"": 1, "m": Fraction(1, 1000), "k": 10**3, "M": 10**6,
           "G": 10**9, "T": 10**12, "Ki": 2**10, "Mi": 2**20,
           "Gi": 2**30, "Ti": 2**40}


def quantity(text: str) -> Fraction:
    """Exact value of a Kubernetes quantity string ("100m", "250Mi")."""
    text = str(text).strip()
    for suffix in ("Ki", "Mi", "Gi", "Ti", "m", "k", "M", "G", "T"):
        if text.endswith(suffix):
            return Fraction(text[:-len(suffix)]) * _SUFFIX[suffix]
    return Fraction(text)


def _milli(q) -> int:
    return int(quantity(q) * 1000) if q else 0


def _units(q) -> int:
    return int(quantity(q)) if q else 0


@dataclass(frozen=True)
class Term:
    """One pod (anti-)affinity term: a matchLabels selector in a topology.
    A preferred term carries its signed weight: + affinity, - anti-affinity."""
    selector: tuple          # sorted ((key, value), ...)
    topology: str
    weight: int = 0

    def matches(self, labels: dict) -> bool:
        return all(labels.get(k) == v for k, v in self.selector)


@dataclass(frozen=True)
class PodShape:
    """What the reference reads of one pod spec."""
    cpu: int                 # milli, requested
    mem: int                 # bytes, requested
    nz_cpu: int              # non-zero defaults applied (priorities)
    nz_mem: int
    labels: tuple            # sorted ((key, value), ...)
    anti_req: tuple = ()     # Terms
    pref: tuple = ()         # preferred Terms, signed weight
    aff_req: tuple = ()      # required affinity Terms

    @property
    def label_dict(self) -> dict:
        return dict(self.labels)


def _term(term: dict, weight: int = 0) -> Term:
    sel = term.get("labelSelector") or {}
    if set(sel) - {"matchLabels"} or term.get("namespaces"):
        raise NotImplementedError(f"selector outside the reference: {term}")
    topo = term.get("topologyKey", "")
    if topo not in TOPOLOGIES:
        raise NotImplementedError(f"topology outside the reference: {topo}")
    return Term(tuple(sorted((sel.get("matchLabels") or {}).items())),
                topo, weight)


def pod_shape(pod: dict) -> PodShape:
    """Parse a v1 pod dict; raises on any field the reference lacks."""
    spec = pod["spec"]
    for key in ("nodeSelector", "tolerations", "volumes"):
        if spec.get(key):
            raise NotImplementedError(f"pod field outside the reference: {key}")
    cpu = mem = nz_cpu = nz_mem = 0
    for c in spec["containers"]:
        if any(p.get("hostPort") for p in c.get("ports") or ()):
            raise NotImplementedError("host ports are outside the reference")
        req = (c.get("resources") or {}).get("requests") or {}
        c_cpu, c_mem = _milli(req.get("cpu")), _units(req.get("memory"))
        cpu += c_cpu
        mem += c_mem
        nz_cpu += c_cpu or DEFAULT_NONZERO_CPU
        nz_mem += c_mem or DEFAULT_NONZERO_MEM
    aff = spec.get("affinity") or {}
    if aff.get("nodeAffinity"):
        raise NotImplementedError("node affinity is outside the reference")
    pa, anti = aff.get("podAffinity") or {}, aff.get("podAntiAffinity") or {}
    anti_req, aff_req = (tuple(_term(t) for t in terms.get(
        "requiredDuringSchedulingIgnoredDuringExecution") or ())
        for terms in (anti, pa))
    pref = tuple(_term(t["podAffinityTerm"], sign * int(t["weight"]))
                 for sign, terms in ((1, pa), (-1, anti))
                 for t in terms.get(
                     "preferredDuringSchedulingIgnoredDuringExecution") or ()
                 if int(t["weight"]))
    labels = tuple(sorted(((pod["metadata"].get("labels")) or {}).items()))
    return PodShape(cpu, mem, nz_cpu, nz_mem, labels, anti_req, pref,
                    aff_req)


def node_alloc(node: dict) -> tuple[int, int, int]:
    alloc = node["status"]["allocatable"]
    return _milli(alloc.get("cpu")), _units(alloc.get("memory")), \
        _units(alloc.get("pods"))


# ---- the spec: one node at a time over plain objects ----------------------


def _least_requested(nz_cpu, nz_mem, cap_cpu, cap_mem) -> int:
    def unused(req, cap):
        if cap == 0 or req > cap:
            return 0
        return ((cap - req) * MAX_PRIORITY) // cap
    return (unused(nz_cpu, cap_cpu) + unused(nz_mem, cap_mem)) // 2


def _balanced(nz_cpu, nz_mem, cap_cpu, cap_mem) -> int:
    if cap_cpu == 0 or cap_mem == 0:
        return 0
    cf, mf = Fraction(nz_cpu, cap_cpu), Fraction(nz_mem, cap_mem)
    if cf >= 1 or mf >= 1:
        return 0
    return int((1 - abs(cf - mf)) * MAX_PRIORITY)


def serial_pick(nodes: list[dict], placed: list[tuple[dict, int]],
                pod: dict, rr: int) -> int | None:
    """selectHost for `pod` given `placed` [(pod dict, node index)]:
    predicates, int-math priorities, then the (rr % ties)-th best node in
    node-list order (the transcription of tests/serial_reference.py's
    SerialScheduler.schedule_one for this subset). None = no node fits."""
    me = pod_shape(pod)
    mine = me.label_dict
    shapes = [(pod_shape(p), i) for p, i in placed]
    topo = [{HOST: n["metadata"]["labels"][HOST],
             ZONE: n["metadata"]["labels"][ZONE]} for n in nodes]
    fits, counts = [], []
    for j, node in enumerate(nodes):
        cap_cpu, cap_mem, cap_pods = node_alloc(node)
        here = [s for s, i in shapes if i == j]
        if len(here) + 1 > cap_pods:
            continue
        if (me.cpu or me.mem) and not (
                cap_cpu >= me.cpu + sum(s.cpu for s in here)
                and cap_mem >= me.mem + sum(s.mem for s in here)):
            continue
        ok = True
        for s, i in shapes:       # existing pods' required anti-affinity
            for t in s.anti_req:
                if t.matches(mine) and topo[i][t.topology] == topo[j][t.topology]:
                    ok = False
        for t in me.anti_req:     # the pod's own required anti-affinity
            for s, i in shapes:
                if t.matches(s.label_dict) and \
                        topo[i][t.topology] == topo[j][t.topology]:
                    ok = False
        for t in me.aff_req:      # the pod's own required affinity
            hits = [i for s, i in shapes if t.matches(s.label_dict)]
            if not any(topo[i][t.topology] == topo[j][t.topology]
                       for i in hits) and (hits or not t.matches(mine)):
                ok = False        # only the first pod matching itself escapes
        if not ok:
            continue
        count = 0
        for s, i in shapes:
            same = {k: topo[i][k] == topo[j][k] for k in TOPOLOGIES}
            for t in me.pref:
                if t.matches(s.label_dict) and same[t.topology]:
                    count += t.weight
            for t in s.pref:
                if t.matches(mine) and same[t.topology]:
                    count += t.weight
            for t in s.aff_req:
                if t.matches(mine) and same[t.topology]:
                    count += HARD_WEIGHT
        fits.append(j)
        counts.append(count)
    if not fits:
        return None
    ip_max, ip_min = max(0, max(counts)), min(0, min(counts))
    scores = []
    for j, count in zip(fits, counts):
        cap_cpu, cap_mem, _ = node_alloc(nodes[j])
        here = [s for s, i in shapes if i == j]
        nzc = me.nz_cpu + sum(s.nz_cpu for s in here)
        nzm = me.nz_mem + sum(s.nz_mem for s in here)
        ip = 0
        if ip_max - ip_min > 0:
            ip = int(MAX_PRIORITY * (count - ip_min) / (ip_max - ip_min))
        scores.append(_least_requested(nzc, nzm, cap_cpu, cap_mem)
                      + _balanced(nzc, nzm, cap_cpu, cap_mem) + ip)
    best = max(scores)
    ties = [j for j, s in zip(fits, scores) if s == best]
    return ties[rr % len(ties)]


# ---- the vectorized form ---------------------------------------------------


class Cluster:
    """Aggregate cluster state over identical-shape bookkeeping: per node
    pod counts and request sums, per node and per zone counts of each
    label signature and of each resident term. `add`/`remove` keep them;
    `evaluate` scores one pod on every node at once. Node order is the
    node list's order (ties break in it)."""

    def __init__(self, nodes: list[dict]):
        n = len(nodes)
        self.names = [nd["metadata"]["name"] for nd in nodes]
        self.index = {name: j for j, name in enumerate(self.names)}
        zones = [nd["metadata"]["labels"][ZONE] for nd in nodes]
        zone_ids = {z: i for i, z in enumerate(dict.fromkeys(zones))}
        self.zone_of = np.array([zone_ids[z] for z in zones], np.int64)
        self.n_zones = len(zone_ids)
        alloc = np.array([node_alloc(nd) for nd in nodes], np.int64)
        self.cap_cpu, self.cap_mem, self.cap_pods = alloc.T.copy()
        self.pods = np.zeros(n, np.int64)
        self.cpu = np.zeros(n, np.int64)
        self.mem = np.zeros(n, np.int64)
        self.nzc = np.zeros(n, np.int64)
        self.nzm = np.zeros(n, np.int64)
        # interned label signatures and term selectors (few per mix)
        self._sigs: dict = {}
        self._sels: dict = {}
        self.sig_node = np.zeros((n, 0), np.int64)
        self.sig_zone = np.zeros((self.n_zones, 0), np.int64)
        # resident terms: anti_req and aff_req counts and signed pref
        # weight, per topology
        self.anti, self.aff, self.pref = (
            {HOST: np.zeros((n, 0), np.int64),
             ZONE: np.zeros((self.n_zones, 0), np.int64)} for _ in range(3))
        self.where: dict[str, tuple[int, PodShape]] = {}
        self._match = np.zeros((0, 0), bool)   # [sig, sel]

    def _sig(self, labels: tuple) -> int:
        if labels not in self._sigs:
            self._sigs[labels] = len(self._sigs)
            self.sig_node = np.pad(self.sig_node, ((0, 0), (0, 1)))
            self.sig_zone = np.pad(self.sig_zone, ((0, 0), (0, 1)))
            self._rebuild_match()
        return self._sigs[labels]

    def _sel(self, selector: tuple) -> int:
        if selector not in self._sels:
            self._sels[selector] = len(self._sels)
            for table in (self.anti, self.aff, self.pref):
                for k in table:
                    table[k] = np.pad(table[k], ((0, 0), (0, 1)))
            self._rebuild_match()
        return self._sels[selector]

    def _rebuild_match(self) -> None:
        m = np.zeros((len(self._sigs), len(self._sels)), bool)
        for labels, i in self._sigs.items():
            d = dict(labels)
            for sel, k in self._sels.items():
                m[i, k] = all(d.get(a) == b for a, b in sel)
        self._match = m

    def _domain(self, topo: str, j: int) -> int:
        return j if topo == HOST else int(self.zone_of[j])

    def _apply(self, j: int, s: PodShape, sign: int) -> None:
        self.pods[j] += sign
        self.cpu[j] += sign * s.cpu
        self.mem[j] += sign * s.mem
        self.nzc[j] += sign * s.nz_cpu
        self.nzm[j] += sign * s.nz_mem
        g = self._sig(s.labels)
        self.sig_node[j, g] += sign
        self.sig_zone[self.zone_of[j], g] += sign
        for table, terms in ((self.anti, s.anti_req), (self.aff, s.aff_req)):
            for t in terms:
                k = self._sel(t.selector)      # may widen the tables first
                table[t.topology][self._domain(t.topology, j), k] += sign
        for t in s.pref:
            k = self._sel(t.selector)
            self.pref[t.topology][self._domain(t.topology, j), k] += \
                sign * t.weight

    def add(self, name: str, node: str, shape: PodShape) -> None:
        j = self.index[node]
        self.where[name] = (j, shape)
        self._apply(j, shape, +1)

    def remove(self, name: str) -> tuple[int, PodShape]:
        j, shape = self.where.pop(name)
        self._apply(j, shape, -1)
        return j, shape

    def _count_matching(self, topo: str, sel: int) -> np.ndarray:
        """Pods matching selector `sel` in each node's `topo` domain."""
        col = self._match[:, sel].astype(np.int64)
        per = (self.sig_node if topo == HOST else self.sig_zone) @ col
        return per if topo == HOST else per[self.zone_of]

    def node_terms(self, s: PodShape) -> tuple[np.ndarray, np.ndarray]:
        """Node-local part on every node: (feasible by resources and by
        hostname anti-affinity both ways, LeastRequested + Balanced)."""
        for t in s.anti_req + s.pref + s.aff_req:
            self._sel(t.selector)
        g = self._sig(s.labels)       # may widen the tables first
        mine = self._match[g]            # selectors matching s
        feas = self.pods + 1 <= self.cap_pods
        cc, cm = self.cap_cpu, self.cap_mem
        if s.cpu or s.mem:
            feas &= (self.cpu + s.cpu <= cc) & (self.mem + s.mem <= cm)
        if mine.any():
            feas &= ~(self.anti[HOST][:, mine].sum(1) > 0)
        for t in s.anti_req:
            if t.topology == HOST:
                k = self._sel(t.selector)
                col = self._match[:, k]
                feas &= ~(self.sig_node[:, col].sum(1) > 0)
        nzc, nzm = self.nzc + s.nz_cpu, self.nzm + s.nz_mem
        lr_c = np.where((cc == 0) | (nzc > cc), 0,
                        ((cc - nzc) * MAX_PRIORITY) // np.maximum(cc, 1))
        lr_m = np.where((cm == 0) | (nzm > cm), 0,
                        ((cm - nzm) * MAX_PRIORITY) // np.maximum(cm, 1))
        prod = cc * cm
        bal = (MAX_PRIORITY * (prod - np.abs(nzc * cm - nzm * cc))) \
            // np.maximum(prod, 1)
        bal = np.where((cc == 0) | (cm == 0) | (nzc >= cc) | (nzm >= cm),
                       0, bal)
        return feas, (lr_c + lr_m) // 2 + bal

    def domain_terms(self, s: PodShape) -> tuple[np.ndarray, np.ndarray]:
        """Zone-level feasibility and the pod's own required affinity [N],
        and the InterPodAffinity weighted count [N] (own preferred terms,
        residents' preferred terms and, at HARD_WEIGHT, their required
        affinity terms)."""
        for t in s.anti_req + s.pref + s.aff_req:
            self._sel(t.selector)
        g = self._sig(s.labels)       # may widen the tables first
        mine = self._match[g]
        ok = np.ones(len(self.names), bool)
        if mine.any():
            ok &= ~(self.anti[ZONE][:, mine].sum(1)[self.zone_of] > 0)
        for t in s.anti_req:
            if t.topology == ZONE:
                ok &= ~(self._count_matching(ZONE, self._sel(t.selector)) > 0)
        for t in s.aff_req:
            k = self._sel(t.selector)
            col = self._match[:, k]
            if self.sig_zone[:, col].sum() > 0:
                ok &= self._count_matching(t.topology, k) > 0
            elif not mine[k]:     # none anywhere, and not the first of them
                ok[:] = False
        count = np.zeros(len(self.names), np.int64)
        for t in s.pref:
            count += t.weight * self._count_matching(t.topology,
                                                     self._sel(t.selector))
        if mine.any():
            count += self.pref[HOST][:, mine].sum(1)
            count += self.pref[ZONE][:, mine].sum(1)[self.zone_of]
            count += HARD_WEIGHT * self.aff[HOST][:, mine].sum(1)
            count += HARD_WEIGHT * \
                self.aff[ZONE][:, mine].sum(1)[self.zone_of]
        return ok, count

    def evaluate(self, s: PodShape) -> tuple[np.ndarray, np.ndarray]:
        """(feasible[N], score[N]) of pod shape `s` on every node."""
        feas, base = self.node_terms(s)
        ok, count = self.domain_terms(s)
        feas &= ok
        return feas, base + interpod_score(count, feas)

    def interpod_ok(self, j: int, s: PodShape) -> bool:
        """Required anti-affinity of pod shape `s` on node j alone (both
        directions), against the current aggregates."""
        for t in s.anti_req:
            k = self._sel(t.selector)
            col = self._match[:, k]
            here = self.sig_node[j] if t.topology == HOST \
                else self.sig_zone[self.zone_of[j]]
            if (here[col] > 0).any():
                return False
        g = self._sig(s.labels)
        mine = self._match[g]
        return not (mine.any() and (
            (self.anti[HOST][j, mine] > 0).any()
            or (self.anti[ZONE][self.zone_of[j], mine] > 0).any()))

    def fits_now(self, j: int) -> bool:
        """Node j within its allocatable (pods, cpu, memory)."""
        return bool(self.pods[j] <= self.cap_pods[j]
                    and self.cpu[j] <= self.cap_cpu[j]
                    and self.mem[j] <= self.cap_mem[j])

    def pick(self, s: PodShape, rr: int) -> int | None:
        feas, score = self.evaluate(s)
        return select_host(feas, score, rr)


def interpod_score(count: np.ndarray, feas: np.ndarray) -> np.ndarray:
    """CalculateInterPodAffinityPriority's reduce: int(10 * (c - min) /
    (max - min)) over the feasible nodes, min <= 0 <= max."""
    if not (count.any() and feas.any()):
        return np.zeros_like(count)
    ip_max = max(0, int(count[feas].max()))
    ip_min = min(0, int(count[feas].min()))
    if ip_max - ip_min <= 0:
        return np.zeros_like(count)
    return (MAX_PRIORITY * (count - ip_min)) // (ip_max - ip_min)


def select_host(feas: np.ndarray, score: np.ndarray, rr: int) -> int | None:
    if not feas.any():
        return None
    best = score[feas].max()
    ties = np.flatnonzero(feas & (score == best))
    return int(ties[rr % len(ties)])

