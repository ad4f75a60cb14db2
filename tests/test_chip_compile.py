"""The main path's programs compile for a described TPU v5e, at real widths.

No chip is attached: the TPU compiler builds for a `v5e:2x2` topology that is
described, not present (on-chip-measurement guide, section 2). What this
catches before any chip time is spent: a Pallas kernel the Mosaic compiler
refuses (tiling, VMEM), a program that does not fit one chip's 16 GB, and a
sharded program the partitioner cannot place. Nothing runs, so nothing here
says anything about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and every xdist worker imports
this file. The persistent compilation cache is off around these compiles
(an entry compiled for a described chip cannot be read back without one).
"""

import os

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from kubernetes_tpu.models.policy import DEFAULT_POLICY, build_policy_rows
from kubernetes_tpu.ops.solver import BatchFlags, schedule_batch
from kubernetes_tpu.state import Capacities
from kubernetes_tpu.state.cluster_state import NodeTable, empty_state
from kubernetes_tpu.state.pod_batch import empty_batch, pack_batch, unpack_batch

HBM_BYTES = 16 * 1024**3  # one v5e chip
# bench.py's headline: 15,000 nodes -> run_throughput's N=16,384, P=4,096
HEADLINE = Capacities(num_nodes=16_384, batch_pods=4_096)
# the headline batch's content gates (no affinity, spread, ports, ...)
HEADLINE_FLAGS = BatchFlags(**{f: False for f in BatchFlags.__dataclass_fields__})


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def shapes(tree, sharding):
    """ShapeDtypeStructs of `tree`'s arrays; `sharding` is one sharding
    for every leaf or a matching pytree of them."""
    if not isinstance(sharding, (SingleDeviceSharding, NamedSharding)):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                              sharding=s), tree, sharding)
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=sharding), tree)


def solver_program(caps, flags, policy=DEFAULT_POLICY):
    """The driver's packed solver variant (scheduler/driver.py
    _get_schedule_fn) and its example arguments (host arrays)."""
    prows = build_policy_rows(policy, NodeTable(caps), caps)
    fn = jax.jit(lambda s, fb, ib, rr: schedule_batch(
        s, unpack_batch(fb, ib, caps), rr, policy, caps=caps, prows=prows,
        flags=flags))
    fblob, iblob = pack_batch(empty_batch(caps), caps)
    return fn, (empty_state(caps), fblob, iblob, np.uint32(0))


def fits_one_chip(compiled) -> int:
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB > one chip's 16 GB"
    return used


def test_pallas_static_mask_compiles_for_v5e(topo):
    from kubernetes_tpu.ops.pallas_kernels import fused_static_mask

    caps = HEADLINE
    one = SingleDeviceSharding(topo.devices[0])
    b = empty_batch(caps)
    untol = np.zeros((caps.batch_pods, caps.taint_universe), np.float32)
    args = shapes((empty_state(caps), b.sel_onehot, b.sel_count, untol,
                   b.best_effort, b.node_name_lo, b.node_name_hi), one)
    compiled = fused_static_mask.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    fits_one_chip(compiled)


def test_headline_schedule_batch_compiles_for_v5e(topo):
    fn, args = solver_program(HEADLINE, HEADLINE_FLAGS)
    compiled = fn.lower(
        *shapes(args, SingleDeviceSharding(topo.devices[0]))).compile()
    fits_one_chip(compiled)


def test_sharded_schedule_batch_compiles_for_four_v5e_chips(topo):
    from kubernetes_tpu.parallel.mesh import make_mesh, make_sharded_scheduler
    from kubernetes_tpu.parallel.mesh import state_sharding

    caps = Capacities(num_nodes=4_096, batch_pods=256)
    mesh = make_mesh(topo.devices[:4])
    fn = make_sharded_scheduler(mesh, DEFAULT_POLICY, caps=caps,
                                flags=HEADLINE_FLAGS, packed=True)
    _, (state, fblob, iblob, rr) = solver_program(caps, HEADLINE_FLAGS)
    repl = NamedSharding(mesh, PartitionSpec())
    compiled = fn.lower(shapes(state, state_sharding(mesh)),
                        *shapes((fblob, iblob, rr), repl)).compile()
    hlo = compiled.as_text()
    assert "all-reduce" in hlo or "all-gather" in hlo  # node axis really split
    fits_one_chip(compiled)
