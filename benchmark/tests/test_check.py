"""The pinned cut: a sampled decision is held at its batch's recorded
watermark (or the cut one delete earlier) and at no later cut; a
verdict at both of those cuts; the watermark recorder pairs
each flush with its own batch however the staged pipeline interleaves
them; the replay's end state is the store's."""

import threading
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.harness import check
from benchmark.harness.drive import Watermarks
from benchmark.harness.reference import Cluster
from benchmark.harness.workload import PodSequence, node_dicts

# a web/cache mix in 3 services, test data whose zone counts deletes move
MIX = {"cpu": "100m", "memory": "250Mi", "services": 3, "roles": [
    {"app": "cache",
     "anti_required": [{"app": "cache", "topology": "hostname"}]},
    {"app": "web",
     "affinity_preferred": [{"app": "cache", "topology": "zone",
                             "weight": 100}],
     "anti_preferred": [{"app": "web", "topology": "zone", "weight": 50}]}]}
NODES = node_dicts({"nodes": 30, "zones": 3,
                    "node": {"cpu": "4", "memory": "8Gi", "pods": "110"}})
A_RV, D_RV, B_RV = 1000, 1001, 5000


def scene(seed, per_node=8, deletes=160):
    """Residents placed at random, a web pod p created, then `deletes`
    residents deleted (rv D_RV, D_RV + 1, ...), oldest first. Returns
    (prefill, p's name and shape, the deletes, picks) where picks[c] is
    the reference's node for p with the first c deletes applied."""
    seq = PodSequence(MIX, seed, "r")
    rng = np.random.default_rng(seed)
    prefill = []
    for _ in range(per_node * len(NODES)):
        name, i = seq.next()
        prefill.append((name, f"node-{int(rng.integers(len(NODES)))}",
                        seq.shape(i)))
    shape = seq.shape(1)                   # a web pod of service 0
    gone = [prefill[k] for k in sorted(
        rng.choice(len(prefill), deletes, replace=False))]
    ref = Cluster(NODES)
    for name, node, s in prefill:
        ref.add(name, node, s)
    picks = [ref.pick(shape, 0)]
    for name, _, _ in gone:
        ref.remove(name)
        picks.append(ref.pick(shape, 0))
    return prefill, shape, gone, picks


def replay(prefill, shape, gone, want, watermark, verdict=False):
    log = [(A_RV, "A", "p", None)]
    log += [(D_RV + k, "D", name, None) for k, (name, _, _) in
            enumerate(gone)]
    if verdict:
        return check.replay_check(NODES, prefill, log, {"p": shape}, {},
                                  [(B_RV, "p", watermark)])
    log.append((B_RV, "B", "p", f"node-{want}"))
    return check.replay_check(NODES, prefill, log, {"p": shape},
                              {"p": watermark})


@pytest.mark.parametrize("seed", range(12))
def test_pinned_cut(seed):
    """At watermark c (the first c deletes applied): the reference's pick
    there is accepted; the pick one delete earlier is accepted (the
    informer sets its rv before its handler runs); the pick one delete
    past the watermark is rejected where it differs from both."""
    prefill, shape, gone, picks = scene(seed)
    cuts = [c for c in range(1, len(gone))
            if picks[c + 1] not in (picks[c], picks[c - 1])]
    assert cuts, "no delete moves the pick"
    c = cuts[len(cuts) // 2]
    w = D_RV + c - 1                       # rv of the c-th delete
    at_w = replay(prefill, shape, gone, picks[c], w)
    assert (at_w["checked"], at_w["decision_mismatch"],
            at_w["at_earlier_cut"]) == (1, 0, 0)
    assert at_w["past_watermark"] == [len(gone) - c]
    if picks[c - 1] != picks[c]:
        earlier = replay(prefill, shape, gone, picks[c - 1], w)
        assert (earlier["decision_mismatch"],
                earlier["at_earlier_cut"]) == (0, 1)
    past = replay(prefill, shape, gone, picks[c + 1], w)
    assert past["decision_mismatch"] == 1
    unpinned = replay(prefill, shape, gone, picks[c], None)
    assert unpinned["checked"] == 0           # counted as unchecked
    # the state is restored: the end state is every delete applied, p bound
    assert at_w["end"]["pods"].sum() == len(prefill) - len(gone) + 1


def test_verdict_at_watermark():
    """A "no node fits" verdict is wrong when a node fits at both cuts.
    Three full 2-pod nodes; node-0's two residents go at
    rv 2 and 3, one of node-1's at rv 4: from the watermark 3 on node-0
    has room at both cuts; at the watermark 2 not at the earlier one."""
    seq = PodSequence({"cpu": "100m", "memory": "250Mi"}, 1, "r")
    shape = seq.shape(seq.next()[1])
    nodes = node_dicts({"nodes": 3, "zones": 3,
                        "node": {"cpu": "4", "memory": "8Gi", "pods": "2"}})
    prefill = [(f"r{k}", f"node-{k % 3}", shape) for k in range(6)]
    log = [(1, "A", "p", None), (2, "D", "r0", None), (3, "D", "r3", None),
           (4, "D", "r1", None)]

    def verdict(w):
        return check.replay_check(nodes, prefill, log, {"p": shape}, {},
                                  [(9, "p", w)])
    assert [verdict(w)["verdict_mismatch"] for w in (4, 3, 2, 1)] == \
        [1, 1, 0, 0]
    assert verdict(None)["verdicts_checked"] == 0


def test_verdict_with_required_affinity_at_both_cuts():
    """Where a delete can make a node feasible, the two cuts disagree: the
    only s1 pod goes at rv 2, and a pod needing s1 in its zone fits at the
    earlier cut of watermark 2 but not at the watermark itself, so "no
    node fits" stands there; it is wrong only where both cuts hold s1."""
    mix = {"cpu": "100m", "memory": "250Mi", "roles": [
        {"app": "s1"},
        {"app": "ex", "affinity_required": [{"app": "s1",
                                             "topology": "zone"}]}]}
    seq = PodSequence(mix, 1, "r")
    s1, ex = seq.shape(0), seq.shape(1)
    nodes = node_dicts({"nodes": 3, "zones": 3,
                        "node": {"cpu": "4", "memory": "8Gi", "pods": "110"}})
    log = [(1, "A", "p", None), (2, "D", "r0", None)]

    def verdict(w):
        return check.replay_check(nodes, [("r0", "node-0", s1)], log,
                                  {"p": ex}, {}, [(9, "p", w)])
    assert [verdict(w)["verdict_mismatch"] for w in (1, 2, 3)] == [1, 0, 0]


class _Informer:
    _last_rv = 0


def fake_scheduler():
    sched = SimpleNamespace(
        statedb=SimpleNamespace(flush=lambda: "state",
                                adopt_result=lambda result: None),
        pod_informer=_Informer(), _deferred_events=deque(), applied=[])
    sched._apply_batch = lambda result, *rest, **kw: \
        sched.applied.append(result)
    return sched


def ev(rv):
    return (None, SimpleNamespace(resource_version=rv))


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_watermarks_pair_flush_with_batch(depth):
    """The staged pipeline's order: up to `depth` batches flushed and
    solved on the dispatch thread before the first binds, a flush on
    another thread (the close's ledger read) between a flush and its
    adopt, and events parked behind the state lock. Each batch gets the
    watermark of its own flush."""
    sched = fake_scheduler()
    marks = Watermarks(sched)
    dispatch = ThreadPoolExecutor(1)    # one thread, as the stage
    results = [object() for _ in range(6)]
    want = []
    try:
        for b, result in enumerate(results):
            rv = 10 * (b + 1)
            sched.pod_informer._last_rv = rv
            sched._deferred_events.clear()
            if b % 2:                       # parked from rv - 3 on
                sched._deferred_events.extend([ev(rv - 3), ev(rv - 1)])
            want.append(rv - 4 if b % 2 else rv)
            dispatch.submit(sched.statedb.flush).result()
            sched.pod_informer._last_rv = 999
            sched._deferred_events.clear()
            assert threading.current_thread() is threading.main_thread()
            sched.statedb.flush()           # another thread's flush
            dispatch.submit(sched.statedb.adopt_result, result).result()
            if b + 1 >= depth:              # binds lag the dispatch
                k = b + 1 - depth
                sched._apply_batch(results[k], [], [f"default/p{k}"],
                                   None, None, [k % 2 - 1])
        for k in range(len(results) - depth + 1, len(results)):
            sched._apply_batch(results[k], [], [f"default/p{k}"], None,
                               None, [k % 2 - 1])
    finally:
        dispatch.shutdown()
    assert sched.applied == results
    assert [wm for wm, _, _ in marks.batches] == want
    placed, unplaced = marks.pins()
    assert placed == {f"p{k}": want[k] for k in range(6) if k % 2}
    assert unplaced == {f"p{k}": want[k] for k in range(6) if not k % 2}


def test_replay_end_state_is_the_store_state():
    nodes = node_dicts({"nodes": 6, "zones": 3,
                        "node": {"cpu": "4", "memory": "8Gi", "pods": "110"}})
    seq = PodSequence({"cpu": "100m", "memory": "250Mi"}, 1, "p")
    shape = seq.shape(seq.next()[1])
    prefill = [(f"r{k}", f"node-{k % 6}", shape) for k in range(12)]
    log = [(1, "A", "w0", None), (2, "B", "w0", "node-2"),
           (3, "D", "r0", None), (4, "D", "r5", None)]
    out = check.replay_check(nodes, prefill, log, {"w0": shape}, {"w0": 1})
    assert out["end"]["pods"].tolist() == [1, 2, 3, 2, 2, 1]
    assert out["end"]["cpu"].tolist() == [100 * p for p in
                                          [1, 2, 3, 2, 2, 1]]
    assert out["end"]["mem"][2] == 3 * 250 * 2**20
