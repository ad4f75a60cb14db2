"""Device-mesh sharding of the scheduler computation.

The reference's only intra-scheduler parallelism is a 16-goroutine fan-out
over nodes (`workqueue.Parallelize(16, len(nodes), checkNode)`,
core/generic_scheduler.go:204,352). The TPU-native equivalent shards the
**node axis** of the cluster-state tensors across a `jax.sharding.Mesh` so
predicates/priorities evaluate on all chips at once over ICI; cross-chip
argmax/normalization reductions (the analog of the priority Reduce goroutines,
:353-364) become XLA collectives inserted by GSPMD.

Axis mapping from the ML-parallelism vocabulary to this domain (SURVEY.md
SS2.8/SS5.7): the node axis plays the role of sequence/tensor parallelism (the
dimension that outgrows one chip — 15k+ nodes), and the pod-batch axis plays
data parallelism for the embarrassingly parallel phase A. Phase B's scan is
sequential by construction (serial-equivalence), so its per-step vector work
shards over nodes only.

Multi-host scale-out (DCN between slices) uses the same specs: `make_mesh`
accepts any device list, and jax.distributed initialization supplies the
global device set.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubernetes_tpu.models.policy import DEFAULT_POLICY, Policy
from kubernetes_tpu.ops.solver import schedule_batch
from kubernetes_tpu.state.cluster_state import ClusterState
from kubernetes_tpu.state.pod_batch import PodBatch

NODE_AXIS = "nodes"


def make_mesh(devices=None) -> Mesh:
    """1-D mesh over all (or given) devices, node axis sharded across it."""
    devices = np.asarray(devices if devices is not None else jax.devices())
    return Mesh(devices.reshape(-1), (NODE_AXIS,))


def state_sharding(mesh: Mesh) -> ClusterState:
    """Pytree of NamedShardings: node-axis arrays shard dim 0 across the
    mesh; cluster-global arrays (taint-universe attributes) replicate."""
    from kubernetes_tpu.state.cluster_state import NODE_AXIS_FIELDS

    sharded = NamedSharding(mesh, P(NODE_AXIS))
    repl = NamedSharding(mesh, P())
    return ClusterState(**{
        f: sharded if f in NODE_AXIS_FIELDS else repl
        for f in ClusterState.__dataclass_fields__})


def batch_sharding(mesh: Mesh) -> PodBatch:
    """Pod batches are replicated: every chip sees the whole pending batch
    (they are small — the node axis is the big one)."""
    spec = NamedSharding(mesh, P())
    return jax.tree.map(lambda _: spec, PodBatch(
        **{f: 0 for f in PodBatch.__dataclass_fields__}))


def padded_num_nodes(num_nodes: int, mesh_size: int) -> int:
    """Smallest multiple of mesh_size >= num_nodes — the node-axis shape a
    mesh of that size can shard evenly."""
    return -(-num_nodes // mesh_size) * mesh_size


def pad_state(state: ClusterState, mesh: Mesh) -> ClusterState:
    """Pad the node axis with sentinel rows (valid=False, zero allocatable,
    topology=-1 — the empty_state row shape) up to the next mesh multiple.
    Sentinel rows fail the validity predicate, so they can never receive a
    pod and never contribute to scoring: the padded program's decisions are
    bit-identical to the unpadded one's."""
    from kubernetes_tpu.state.cluster_state import NODE_AXIS_FIELDS

    target = padded_num_nodes(state.num_nodes, mesh.size)
    pad = target - state.num_nodes
    if pad == 0:
        return state

    def pad_field(name: str, arr):
        arr = np.asarray(arr)
        fill = -1 if name == "topology" else 0
        return np.pad(arr, [(0, pad)] + [(0, 0)] * (arr.ndim - 1),
                      constant_values=fill)

    return state.replace(**{f: pad_field(f, getattr(state, f))
                            for f in NODE_AXIS_FIELDS})


def shard_state(state: ClusterState, mesh: Mesh) -> ClusterState:
    """Node-shard a host state onto the mesh, through an owned copy
    (`statedb.put_owned`): the caller may go on mutating its arrays."""
    from kubernetes_tpu.state.statedb import put_owned

    return put_owned(pad_state(state, mesh), state_sharding(mesh))


def shard_batch(batch: PodBatch, mesh: Mesh) -> PodBatch:
    return jax.device_put(batch, batch_sharding(mesh))


def make_sharded_scheduler(mesh: Mesh, policy: Policy = DEFAULT_POLICY,
                           caps=None, prows=None, flags=None, packed=False):
    """jit schedule_batch with node-axis sharding constraints.

    Returns fn(state, batch, rr) -> SolverResult whose ledger outputs stay
    node-sharded (so batch-to-batch chaining never gathers to one chip).
    `prows` (PolicyRows, replicated) is closed over as a constant — it is
    fixed for the life of the policy. `flags` (BatchFlags) gates
    batch-content-neutral kernels out of the compiled program. With
    `packed=True` the returned fn takes (state, fblob, iblob, rr) — the
    two-blob transport of pod_batch.pack_batch, replicated like the batch.
    """
    from kubernetes_tpu.ops.solver import ALL_ACTIVE, SolverResult

    if flags is None:
        flags = ALL_ACTIVE

    st = state_sharding(mesh)
    bt = batch_sharding(mesh)
    repl = NamedSharding(mesh, P())
    nodes_spec = NamedSharding(mesh, P(NODE_AXIS))
    out_shardings = SolverResult(
        assignments=repl, scores=repl, feasible_counts=repl,
        new_requested=nodes_spec, new_nonzero=nodes_spec,
        new_port_count=nodes_spec, rr_end=repl,
        new_podsel=nodes_spec, new_term=nodes_spec,
        new_vol_any=nodes_spec, new_vol_rw=nodes_spec,
        new_attach=nodes_spec,
        preempt_node=repl, victim_count=repl,
        # scale_sim probes: per-node placement counts stay node-sharded
        # (optional fields are None in non-probe programs; a sharding on a
        # None output is an empty pytree-prefix match, so one out_shardings
        # covers every flag combination)
        placed_per_node=nodes_spec,
    )
    if packed:
        from kubernetes_tpu.state.pod_batch import unpack_batch

        # victims (a VictimTable or None) shards its node axis: prio[N,S],
        # req[N,S,R] and ok[N,S] all lead with the node dim, and the
        # in_shardings leaf is a pytree prefix, valid for both structures
        vic = nodes_spec
        jfn = jax.jit(
            lambda state, fblob, iblob, rr, victims: schedule_batch(
                state, unpack_batch(fblob, iblob, caps), rr, policy,
                caps=caps, prows=prows, flags=flags, allow_fused=False,
                victims=victims),
            in_shardings=(st, repl, repl, repl, vic),
            out_shardings=out_shardings,
        )

        def packed_fn(state, fblob, iblob, rr, victims=None):
            return jfn(state, fblob, iblob, rr, victims)

        # the jit surface (AOT compiles, HLO pins) stays reachable
        packed_fn.lower = (lambda state, fblob, iblob, rr, victims=None:
                           jfn.lower(state, fblob, iblob, rr, victims))
        return packed_fn
    return jax.jit(
        lambda state, batch, rr: schedule_batch(state, batch, rr, policy,
                                                caps=caps, prows=prows,
                                                flags=flags,
                                                allow_fused=False),
        in_shardings=(st, bt, repl),
        out_shardings=out_shardings,
    )
