"""Chip smoke: the scheduler's main path, end to end, on a locally attached TPU.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: the node-sharded path only

One process owns the chip and runs every phase; nothing here starts a child
that touches JAX. Every phase raises on failure (the process then exits
non-zero with the traceback); nothing is caught and logged. Information goes
on earlier lines; the last line of standard output is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

One chip, at the bench.py headline size (15,000 nodes in 3 zones, 30,000
pending pods; capacities N=16,384, P=4,096 from run_throughput's defaults):

1. headline: `perf.harness.run_throughput` (Scheduler + staged pipeline over
   an in-memory ObjectStore); afterwards, from the store, every pod is bound
   exactly once, with zero binding errors and no node overcommitted.
2. parity: one encoded batch in three shapes (headline, spread, interpod)
   through the driver's jitted solver on the TPU and on the CPU backend of
   the same process, from the same encoded state — assignments, scores and
   feasible counts must be identical.
3. extender: the HTTP extender in node-cache-capable mode over the 15k-node
   StateDB answers filter/prioritize requests consistently with the batch
   solve of the same pod.
4. pallas: `ops.pallas_kernels.fused_static_mask`, compiled for the chip at
   N=16,384, P=4,096, equals the XLA static mask.

Four chips (`--chips 4`), at the bench.py sharded shape (100,000 nodes padded
to N=131,072): the node-sharded `schedule_batch` against the single-device
program on the same encoded state (bit-identical), a `Scheduler(mesh=...)`
wave of 16,384 pods bound exactly once, and the node-axis arrays' shards on
4 distinct devices.
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import json
import sys
import time
import urllib.request

import numpy as np

HEADLINE_NODES = 15_000
HEADLINE_PODS = 30_000
INTERPOD_NODES = 5_000
N_SERVICES = 16  # bench.py spread config: services select 16 app groups
SPREAD_POD_KWARGS = {"app_groups": N_SERVICES}
INTERPOD_POD_KWARGS = {"app_groups": 8, "anti_affinity_every": 16,
                       "pref_affinity_every": 2}  # bench.py interpod config
SHARDED_NODES = 100_000
SHARDED_PODS = 16_384


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def pow2_nodes(n_nodes: int) -> int:
    """run_throughput's node capacity for n_nodes."""
    return 1 << max(6, (n_nodes - 1).bit_length())


# ---- phase 1: headline end to end -------------------------------------


def headline(n_nodes: int, n_pods: int) -> None:
    from kubernetes_tpu.apiserver import ObjectStore
    from kubernetes_tpu.obs.profiling import COMPILES
    from kubernetes_tpu.perf.harness import run_throughput

    store = ObjectStore(watch_window=max(1 << 18, 4 * (n_pods + n_nodes)))
    binds = watch_binds(store)
    t0 = time.perf_counter()
    r = run_throughput(n_nodes, n_pods, node_kwargs={"zones": 3},
                       store=store)
    wall = time.perf_counter() - t0
    say(f"headline: {r}; wall {wall:.1f}s incl. set-up; metrics "
        f"{json.dumps(r.metrics, sort_keys=True)}")
    say(f"headline: compiles {json.dumps(COMPILES.totals(), default=str)}")
    check_bound(store, binds, r, n_pods)


def watch_binds(store) -> collections.Counter:
    """Count, per pod, the writes that bind it: transitions of
    spec.nodeName from unset (or from another node) to a node. Later writes
    to a bound pod that keep its node — the trace annotation a sampled
    batch stamps on its pods — are not binds."""
    binds: collections.Counter = collections.Counter()
    last_node: dict[str, str] = {}

    def tap(ev) -> None:
        if ev.kind != "Pod":
            return
        key = ev.obj.key
        if ev.type == "DELETED":
            last_node.pop(key, None)
            return
        node = ev.obj.spec.node_name or ""
        if node and node != last_node.get(key, ""):
            binds[key] += 1
        last_node[key] = node

    store.event_taps.append(tap)
    return binds


def check_bound(store, binds, result, n_pods: int) -> None:
    """Every pod bound exactly once, no binding errors or degraded path,
    no node's bound requests above its allocatable."""
    from kubernetes_tpu.state.cluster_state import pod_requests, resource_rows

    pods = store.list("Pod", copy_objects=False)
    check(len(pods) == n_pods, f"{len(pods)} pods in the store, want {n_pods}")
    unbound = [p.key for p in pods if not p.spec.node_name]
    check(not unbound, f"{len(unbound)} pods unbound, e.g. {unbound[:3]}")
    check(result.scheduled == n_pods,
          f"scheduler reports {result.scheduled} bound, want {n_pods}")
    twice = {k: c for k, c in binds.items() if c != 1}
    check(not twice, f"pods bound more than once: {list(twice.items())[:3]}")
    check(all(binds[p.key] == 1 for p in pods), "a bound pod has no bind")
    check(result.metrics["binding_errors"] == 0,
          f"binding errors: {result.metrics['binding_errors']}")
    check("faults" not in result.metrics,
          f"solve failures or serial fallback: {result.metrics.get('faults')}")
    alloc = {n.metadata.name: resource_rows(n.status.effective_allocatable())
             for n in store.list("Node", copy_objects=False)}
    used = {name: np.zeros_like(a) for name, a in alloc.items()}
    for p in pods:
        used[p.spec.node_name] += pod_requests(p)
    over = [name for name in alloc if (used[name] > alloc[name]).any()]
    check(not over, f"{len(over)} nodes overcommitted, e.g. {over[:3]}")
    busiest = max(used.values(), key=lambda u: u[0])
    say(f"bound check: {len(pods)} pods bound once each, 0 binding errors, "
        f"0 overcommitted of {len(alloc)} nodes (busiest holds "
        f"{int(busiest[0])} pods)")


# ---- phase 2: chip-vs-CPU parity of the compiled program ---------------


class Encoded:
    """One batch encoded by the driver's own path: a Scheduler (not
    started) over a store holding nodes, services and already-bound pods;
    the batch packed through its EncodeCache; the solver variant from its
    jit cache."""

    def __init__(self, n_nodes: int, caps, pod_kwargs: dict,
                 n_bound: int, n_services: int = 0):
        from kubernetes_tpu.apiserver import ObjectStore
        from kubernetes_tpu.perf.fixtures import (make_nodes, make_pods,
                                                  make_services)
        from kubernetes_tpu.scheduler import Scheduler

        self.store = ObjectStore()
        for svc in make_services(n_services):
            self.store.create(svc)
        nodes = make_nodes(n_nodes, zones=3)
        for node in nodes:
            self.store.create(node)
        self.sched = Scheduler(self.store, caps=caps)
        self.caps = self.sched.caps
        for node in self.store.list("Node", copy_objects=False):
            self.sched.statedb.upsert_node(node)
        # bound pods fill the first two thirds of the nodes, so counts per
        # domain run past bf16's exact-integer range (256) and a third of
        # the cluster stays empty
        filled = max(1, 2 * n_nodes // 3)
        for i, pod in enumerate(make_pods(n_bound, name_prefix="bound",
                                          **pod_kwargs)):
            pod.spec.node_name = nodes[i % filled].metadata.name
            self.store.create(pod)
            self.sched.statedb.add_pod(pod)
        self.pending = make_pods(self.caps.batch_pods, **pod_kwargs)
        self.fblob, self.iblob, self.flags = self.encode(self.pending)
        self.fn = self.sched._get_schedule_fn(self.flags)

    def encode(self, pods):
        """Packed blobs for `pods` (rows past them invalid) and the batch's
        content flags; `state` is re-flushed, since encoding may intern
        selectors whose node membership the device state must carry."""
        from kubernetes_tpu.state.pod_batch import (empty_batch, pack_batch,
                                                    packed_batch_flags)

        fblob, iblob = pack_batch(empty_batch(self.caps), self.caps)
        for i, pod in enumerate(pods):
            self.sched.encode_cache.encode_packed_into(fblob, iblob, i, pod)
        flags = packed_batch_flags(fblob, iblob, len(pods),
                                   self.sched.statedb.table, self.caps)
        self.state = self.sched.statedb.flush()
        return fblob, iblob, flags

    def max_domain_count(self) -> int:
        """Largest matching-pod count of any (zone, selector) pair."""
        from kubernetes_tpu.state.layout import TOPO_SPREAD_ZONE

        counts = np.asarray(self.state.podsel_count)
        zone = np.asarray(self.state.topology)[:, TOPO_SPREAD_ZONE]
        per = np.zeros((int(zone.max()) + 2, counts.shape[1]))
        np.add.at(per, zone + 1, counts)
        return int(per[1:].max())


FIELDS = ("assignments", "scores", "feasible_counts")


def solve_on(enc: Encoded, device, fblob=None, iblob=None, fn=None) -> dict:
    import jax

    fn = fn or enc.fn
    args = jax.device_put(
        (enc.state, enc.fblob if fblob is None else fblob,
         enc.iblob if iblob is None else iblob), device)
    t0 = time.perf_counter()
    out = fn(*args, np.uint32(0))
    got = {f: np.asarray(getattr(out, f)) for f in FIELDS}
    return {"seconds": time.perf_counter() - t0, **got}


def check_same(what: str, a: dict, b: dict) -> None:
    """Bit-identical solver outputs, or the first differing rows."""
    for f in FIELDS:
        diff = np.flatnonzero(a[f] != b[f])
        check(diff.size == 0,
              f"{what}: {f} differs at {diff.size} rows, first "
              f"{diff[:5].tolist()}: {a[f][diff[:5]].tolist()} vs "
              f"{b[f][diff[:5]].tolist()}")


def parity(name: str, enc: Encoded, chip, cpu) -> None:
    on_chip = solve_on(enc, chip)
    on_cpu = solve_on(enc, cpu)
    check_same(f"parity[{name}] chip vs CPU", on_chip, on_cpu)
    placed = int((on_chip["assignments"] >= 0).sum())
    check(placed > 0, f"parity[{name}]: the batch placed no pod")
    say(f"parity[{name}]: identical on chip and CPU — N={enc.caps.num_nodes} "
        f"P={enc.caps.batch_pods}, {placed} placed, max per-zone selector "
        f"count {enc.max_domain_count()}; first call (compile + run) chip "
        f"{on_chip['seconds']:.1f}s, cpu {on_cpu['seconds']:.1f}s")


# ---- phase 3: the extender over HTTP ----------------------------------


def probe_pods():
    from kubernetes_tpu.perf.fixtures import make_pods

    plain, big, zonal = (make_pods(1, name_prefix=p)[0]
                         for p in ("probe-plain", "probe-big", "probe-zonal"))
    # fits only the nodes Encoded left empty
    big.spec.containers[0].requests["cpu"] = "3850m"
    zonal.spec.node_selector = {
        "failure-domain.beta.kubernetes.io/zone": "zone-1"}
    return [plain, big, zonal]


def post(url: str, payload: dict):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        check(resp.status == 200, f"{url}: HTTP {resp.status}")
        return json.loads(resp.read())


async def extender(enc: Encoded, chip) -> None:
    from kubernetes_tpu.extender.server import ExtenderServer, ExtenderService

    service = ExtenderService(caps=enc.caps, policy=enc.sched.policy,
                              statedb=enc.sched.statedb)
    server = ExtenderServer(service, deadline_s=120.0)
    await server.start()
    table = enc.sched.statedb.table
    names = sorted(table.row_of)
    try:
        for pod in probe_pods():
            args = {"pod": pod.to_dict(), "nodenames": names}
            filt = await asyncio.to_thread(post, server.url + "/filter", args)
            prio = await asyncio.to_thread(post, server.url + "/prioritize",
                                           args)
            check("error" not in filt, f"extender filter: {filt.get('error')}")
            passed = set(filt["nodenames"])
            score = {h["host"]: h["score"] for h in prio}
            fblob, iblob, flags = enc.encode([pod])
            batch = solve_on(enc, chip, fblob, iblob,
                             enc.sched._get_schedule_fn(flags))
            row = int(batch["assignments"][0])
            want = int(batch["feasible_counts"][0])
            what = f"extender[{pod.metadata.name}]"
            check(len(passed) == want,
                  f"{what}: filter passed {len(passed)}, batch feasible {want}")
            check(row >= 0, f"{what}: batch solve left the pod unassigned")
            node = table.name_of[row]
            best = max(score[n] for n in passed)
            check(node in passed, f"{what}: batch node {node} filtered out")
            check(score[node] == best == int(batch["scores"][0]),
                  f"{what}: batch node {node} scores {score[node]} over "
                  f"HTTP, best {best}, batch {batch['scores'][0]}")
            say(f"{what}: filter {len(passed)}/{len(names)} = batch feasible;"
                f" batch node {node} holds the best score {best}")
    finally:
        await server.stop()


# ---- phase 4: the Pallas kernel compiled for the chip ------------------


def pallas(n_nodes: int, caps) -> None:
    import jax

    from kubernetes_tpu.ops import predicates as preds
    from kubernetes_tpu.ops.pallas_kernels import fused_static_mask
    from kubernetes_tpu.perf.fixtures import make_nodes, make_pods
    from kubernetes_tpu.state import encode_cluster

    nodes = make_nodes(n_nodes, zones=3, labels_per_node=2, taint_every=16)
    half = caps.batch_pods // 2
    pods = (make_pods(half, name_prefix="tol", selector_every=7,
                      tolerate=True)
            + make_pods(caps.batch_pods - half, name_prefix="sel",
                        selector_every=3))
    pods[5].spec.node_name = nodes[42].metadata.name
    state, batch, _ = encode_cluster(nodes, pods, caps)
    state, batch = jax.device_put((state, batch))

    @jax.jit
    def both(state, batch):
        untol = jax.vmap(lambda p: 1.0 - preds._tolerated_universe(state, p)
                         .astype(jax.numpy.float32))(batch)
        fused = fused_static_mask(
            state, batch.sel_onehot, batch.sel_count, untol,
            batch.best_effort, batch.node_name_lo, batch.node_name_hi)
        xla = jax.vmap(lambda p: (
            state.valid
            & preds.node_schedulable(state, p)
            & preds.fits_host(state, p)
            & (state.sel_member @ p.sel_onehot >= p.sel_count)
            & preds.tolerates_node_taints(state, p)
            & preds.check_node_condition(state, p)
            & preds.check_memory_pressure(state, p)
            & preds.check_disk_pressure(state, p)))(batch)
        return fused, xla

    hlo = both.lower(state, batch).compile().as_text()
    check("tpu_custom_call" in hlo, "pallas: no Mosaic kernel in the program")
    t0 = time.perf_counter()
    fused, xla = (np.asarray(a) for a in both(state, batch))
    dt = time.perf_counter() - t0
    diff = int((fused != xla).sum())
    check(diff == 0, f"pallas: fused mask differs from XLA at {diff} pairs")
    say(f"pallas: compiled fused_static_mask == XLA mask over "
        f"{fused.shape[0]}x{fused.shape[1]} pairs ({int(fused.sum())} "
        f"feasible); run {dt:.2f}s")


# ---- four chips: the node-sharded path --------------------------------


def sharded(devices, n_nodes: int, n_pods: int) -> None:
    """Sharded vs single-device program on one encoded state, shards on
    distinct devices, then a Scheduler(mesh=...) wave bound exactly once."""
    import gc

    from kubernetes_tpu.apiserver import ObjectStore
    from kubernetes_tpu.parallel.mesh import (make_mesh,
                                              make_sharded_scheduler,
                                              shard_state)
    from kubernetes_tpu.perf.harness import run_throughput
    from kubernetes_tpu.state import Capacities

    mesh = make_mesh(devices)
    # bench.py's sharded batch, through run_throughput's default
    caps = Capacities(num_nodes=pow2_nodes(n_nodes),
                      batch_pods=min(4096, max(64, n_pods // 6)))
    enc = Encoded(n_nodes, caps, {}, n_bound=n_nodes // 2)
    single = solve_on(enc, devices[0])
    fn = make_sharded_scheduler(mesh, enc.sched.policy, caps=enc.caps,
                                prows=enc.sched._prows, flags=enc.flags,
                                packed=True)
    state = shard_state(enc.state, mesh)
    t0 = time.perf_counter()
    out = fn(state, enc.fblob, enc.iblob, np.uint32(0))
    split = {f: np.asarray(getattr(out, f)) for f in FIELDS}
    dt = time.perf_counter() - t0
    check_same("sharded vs single-device", split, single)
    rows = caps.num_nodes // len(devices)
    for name, arr in (("state.valid", state.valid),
                      ("state.requested", state.requested),
                      ("out.new_requested", out.new_requested)):
        shards = arr.addressable_shards
        on = {s.device for s in shards}
        check(len(on) == len(devices) and set(devices) == on,
              f"sharded: {name} lives on {sorted(str(d) for d in on)}")
        check(all(s.data.shape[0] == rows for s in shards),
              f"sharded: {name} shard rows "
              f"{[s.data.shape[0] for s in shards]}, want {rows} each")
    placed = int((split["assignments"] >= 0).sum())
    check(placed > 0, "sharded: the batch placed no pod")
    say(f"sharded: {len(devices)}-device program == single-device program "
        f"at N={caps.num_nodes} P={caps.batch_pods} ({placed} placed); "
        f"node-axis arrays split {rows} rows per device over "
        f"{[str(d) for d in devices]}; single-device first call "
        f"{single['seconds']:.1f}s, sharded {dt:.1f}s")
    del enc, state, out
    gc.collect()

    store = ObjectStore(watch_window=max(1 << 18, 4 * (n_pods + n_nodes)))
    binds = watch_binds(store)
    r = run_throughput(n_nodes, n_pods, node_kwargs={"zones": 3},
                       mesh=mesh, store=store)
    say(f"sharded wave: {r}; shard rows {r.sharding.get('shard_rows')}")
    check(r.sharding.get("devices") == len(devices),
          f"sharded wave ran on {r.sharding.get('devices')} devices")
    check(all(n > 0 for n in r.sharding["shard_rows"]),
          f"sharded wave: empty shard in {r.sharding['shard_rows']}")
    check_bound(store, binds, r, n_pods)


# ---- entry point --------------------------------------------------------


def one_chip(chip) -> None:
    import jax

    from kubernetes_tpu.state import Capacities

    cpu = jax.devices("cpu")[0]
    caps = Capacities(num_nodes=pow2_nodes(HEADLINE_NODES), batch_pods=4096)
    headline(HEADLINE_NODES, HEADLINE_PODS)

    enc = Encoded(HEADLINE_NODES, caps, {}, n_bound=HEADLINE_PODS)
    parity("headline", enc, chip, cpu)
    asyncio.run(extender(enc, chip))
    del enc

    enc = Encoded(HEADLINE_NODES, caps, SPREAD_POD_KWARGS,
                  n_bound=HEADLINE_PODS, n_services=N_SERVICES)
    check(enc.max_domain_count() > 256, "spread: per-zone counts too small "
          "to test bf16 exactness")
    parity("spread", enc, chip, cpu)
    del enc

    enc = Encoded(INTERPOD_NODES, Capacities(
        num_nodes=pow2_nodes(INTERPOD_NODES), batch_pods=4096),
        INTERPOD_POD_KWARGS, n_bound=2 * INTERPOD_NODES)
    check(enc.max_domain_count() > 256, "interpod: per-zone counts too "
          "small to test bf16 exactness")
    parity("interpod", enc, chip, cpu)
    del enc

    pallas(HEADLINE_NODES, caps)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the node-sharded path on 4 chips")
    args = ap.parse_args(argv)

    import os

    # the parity phase needs the CPU backend beside the chip
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"

    import jax

    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"no TPU attached: JAX's default devices are {devices}")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} chips, JAX sees "
          f"{len(devices)}")

    from kubernetes_tpu import native
    from kubernetes_tpu.api import wire
    from kubernetes_tpu.utils import compilation_cache

    cache = compilation_cache.enable()
    say(f"devices {devices}; compile cache {cache}; native kernels "
        f"{native.active()}; protobuf wire {wire.available()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded(devices[:4], SHARDED_NODES, SHARDED_PODS)
    else:
        one_chip(devices[0])
    say(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
