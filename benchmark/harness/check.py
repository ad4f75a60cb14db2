"""Decide `correct`: replay the run's store history into the plain
reference and hold the timed path's answers against it.

What the scheduler saw when it solved a batch is one prefix of the
store's history: every bind before the batch's own (earlier batches and
the batch's earlier rows are in its ledger) and every delete its pod
informer had applied at the batch's flush. The run records that point,
the watermark, at each flush (drive.Watermarks): the informer's last
resourceVersion, or the one before the first event parked behind the
state lock. So for a sampled pod the reference puts back every delete
past its batch's watermark and asks whether selectHost (feasible, best
score, round-robin tie in node order with rr = binds before p) lands
on the node the program chose. The informer sets its resourceVersion
before its handler runs, so the delete at the watermark itself may not
have been applied yet: the cut one delete earlier, with that delete put
back too, is also accepted, and no other. A sampled pod that neither
cut places on the program's node is a mismatch.

A FailedScheduling verdict ("no node fits") is held at the watermark of
the last batch that left the pod unplaced, at the same two cuts: the
state it was solved on is the store's history at the verdict's event
with the deletes past that watermark put back, or with the delete at
the watermark put back too (binds only ever come earlier). If some node
fits the pod at both cuts, the verdict is wrong. A sample or verdict
without a recorded watermark is not checked, and counts as unchecked.

The hard guarantees are checked on every bind of the run, against the
store's own history (a superset of what any snapshot lacked can only
make a node fuller): bound at most once, no node over its allocatable,
no required anti-affinity term broken.
"""

from __future__ import annotations

import bisect
import time

import numpy as np

from benchmark.harness.reference import Cluster


def replay_check(nodes, prefill, log, shapes, samples: dict,
                 verdicts=(), deadline_s: float = 120.0) -> dict:
    """nodes: node dicts in creation order; prefill: [(name, node, shape)];
    log: [(rv, code, name, node)] in rv order, code 'A' (pending pod
    created), 'B' (bound to node), 'D' (deleted); shapes: name -> shape
    of every pod the generator created; samples: name -> watermark of the
    batch that bound it (None: not recorded), the binds to verify;
    verdicts: [(rv, name, watermark)] FailedScheduling events to verify."""
    t0 = time.monotonic()
    ref = Cluster(nodes)
    for name, node, shape in prefill:
        ref.add(name, node, shape)
    dels_rv: list[int] = []
    dels: list[tuple[str, int, object]] = []
    rr = 0
    out = {"decision_mismatch": 0, "verdict_mismatch": 0, "overcommit": 0,
           "anti_affinity": 0, "checked": 0, "verdicts_checked": 0,
           "at_earlier_cut": 0, "past_watermark": []}
    merged = sorted([*log, *((rv, "F", name, w) for rv, name, w in verdicts)]
                    ) if verdicts else log
    for rv, code, name, arg in merged:      # arg: B's node, F's watermark
        if code == "F":
            if arg is not None:
                lo = bisect.bisect_right(dels_rv, arg)
                at_w = dels[lo - 1] if lo and dels_rv[lo - 1] == arg else None
                out["verdict_mismatch"] += _fits_somewhere(ref, shapes[name],
                                                           dels[lo:], at_w)
                out["verdicts_checked"] += 1
        elif code == "D":
            j, shape = ref.remove(name)
            dels_rv.append(rv)
            dels.append((name, j, shape))
        elif code == "B":
            shape, node = shapes[name], arg
            j = ref.index[node]
            w = samples.get(name)
            if w is not None:
                lo = bisect.bisect_right(dels_rv, w)
                at_w = dels[lo - 1] if lo and dels_rv[lo - 1] == w else None
                cut = _pinned_cut(ref, shape, j, rr, dels[lo:], at_w)
                out["checked"] += 1
                out["past_watermark"].append(len(dels) - lo)
                out["decision_mismatch"] += cut is None
                out["at_earlier_cut"] += cut == "earlier"
                if time.monotonic() - t0 > deadline_s:
                    raise TimeoutError("reference check over its deadline")
            if not ref.interpod_ok(j, shape):
                out["anti_affinity"] += 1
            ref.add(name, node, shape)
            if not ref.fits_now(j):
                out["overcommit"] += 1
            rr += 1
    out["end"] = {"pods": ref.pods.copy(), "cpu": ref.cpu.copy(),
                  "mem": ref.mem.copy()}
    out["seconds"] = time.monotonic() - t0
    return out


def _fits_somewhere(ref: Cluster, shape, past, at_w) -> bool:
    """Whether some node fits `shape` at the watermark (the deletes `past`
    put back) and, where there is a delete `at_w` at the watermark, with
    it put back too: at both cuts the snapshot behind the verdict could
    have been. Restores `ref`."""
    back = list(past)
    for name, j, s in back:
        ref.add(name, ref.names[j], s)
    try:
        for extra in [None] + ([at_w] if at_w else []):
            if extra:
                name, j, s = extra
                ref.add(name, ref.names[j], s)
                back.append(extra)
            feas, _ = ref.node_terms(shape)
            ok, _ = ref.domain_terms(shape)
            if not (feas & ok).any():
                return False
        return True
    finally:
        for name, _, _ in back:
            ref.remove(name)


def _pinned_cut(ref: Cluster, shape, want: int, rr: int, past,
                at_w) -> str | None:
    """Whether selectHost picks `want` at the watermark: with the deletes
    `past` (those after it; all applied in `ref` on entry) put back, or
    else with the delete `at_w` (the one at the watermark, or None) put
    back too. Returns "watermark", "earlier" or None; restores `ref`."""
    back = list(past)
    for name, j, s in back:
        ref.add(name, ref.names[j], s)
    try:
        if ref.pick(shape, rr) == want:
            return "watermark"
        if at_w is None:
            return None
        name, j, s = at_w
        ref.add(name, ref.names[j], s)
        back.append(at_w)
        return "earlier" if ref.pick(shape, rr) == want else None
    finally:
        for name, _, _ in back:
            ref.remove(name)


def sample_names(candidates: list, k: int, seed: int,
                 always: list = ()) -> set:
    """k of `candidates` drawn from the seed, plus `always`."""
    rng = np.random.default_rng([seed, 11])
    k = min(k, len(candidates))
    picked = rng.choice(len(candidates), size=k, replace=False) if k else []
    return {candidates[i] for i in picked} | set(always)

