"""Pallas TPU kernels for the solver's hot tensor ops.

`fused_static_mask` computes the default-policy static feasibility
conjunction — selector membership, hard-taint toleration, node conditions,
host-name pinning, validity — for a whole (P × N) batch in ONE pass over
node tiles: both matmuls hit the MXU from VMEM and every comparison/AND
fuses behind them, so the (P, N) intermediates that the composed XLA
kernels materialize in HBM (selector counts, taint violations, per-check
masks) never leave the chip. Mirrors ops/predicates.py semantics exactly
(predicates.go:686, :1241, :1306 and the lister's unschedulable filter);
parity is pinned against the XLA path in tests. The kernel always
compiles for the TPU; a test that runs it elsewhere asks for Pallas's TPU
interpret mode itself (`pltpu.force_tpu_interpret_mode()`), the program
never picks it.

Opt-in: the solver uses it when KTPU_PALLAS=1 and the policy's static set
matches what the kernel fuses (solver._use_fused_static). Node-affinity
terms stay in XLA and AND in afterwards — they ride a (T × UR × N) contraction
the fused two-matmul shape doesn't cover.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from kubernetes_tpu.state.layout import Condition

# tile sizes trade VMEM footprint against grid-step count; at (128, 256)
# a 16k-node / 4k-pod mask is (4096/128)*(16384/256) = 2048 grid steps
# with ~0.5 MB of VMEM-resident operands per step (512-wide node tiles
# tripped the scoped-vmem limit under Mosaic's double buffering)
NODE_TILE = 256
POD_TILE = 128

_HARD_BITS = (Condition.NOT_READY | Condition.NETWORK_UNAVAILABLE
              | Condition.OUT_OF_DISK | Condition.DISK_PRESSURE
              | Condition.UNSCHEDULABLE)


def _kernel(sel_onehot, sel_count, untol, best_effort, pod_lo, pod_hi,
            sel_member, hard_member, node_bits, name_lo, name_hi, out):
    # selector: satisfied-term counts via MXU, then the >= count compare
    sat = jax.lax.dot_general(
        sel_onehot[:], sel_member[:],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (TP, TN)
    ok = sat >= sel_count[:]                          # (TP,1) broadcasts

    # taints: untolerated hard-taint hits must be zero
    viol = jax.lax.dot_general(
        untol[:], hard_member[:],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ok &= viol == 0.0

    # conditions: hard bits reject everyone; MemoryPressure rejects only
    # BestEffort pods; bit 0x80000000 marks an invalid (padding) row
    bits = node_bits[:].reshape(1, -1)                # (TN,1) -> (1, TN)
    ok &= (bits & _HARD_BITS) == 0
    mem = (bits & Condition.MEMORY_PRESSURE) != 0
    ok &= ~(mem & (best_effort[:] != 0))
    ok &= (bits & jnp.int32(-2147483648)) == 0        # invalid-row bit

    # spec.nodeName pinning: unset (0) matches everywhere
    lo = pod_lo[:]                                    # (TP, 1) i32
    hi = pod_hi[:]
    pinned = lo != 0
    match = ((lo == name_lo[:].reshape(1, -1))
             & (hi == name_hi[:].reshape(1, -1)))
    ok &= match | ~pinned

    out[:] = ok.astype(jnp.float32)


@jax.jit
def fused_static_mask(state, sel_onehot, sel_count, untol, best_effort,
                      node_name_lo, node_name_hi) -> jnp.ndarray:
    """bool[P, N]: valid & schedulable & conditions & selector & taints &
    host-name for every (pod, node) pair.

    `untol` is the per-pod untolerated-taint-universe row
    (predicates._tolerated_universe complement), f32[P, UT]. P must be a
    multiple of 8 and N of 128 (the padded capacities guarantee this).
    """
    p = sel_onehot.shape[0]
    n = state.valid.shape[0]
    # adapt tiles to small padded capacities (tests run at N=128, P=16);
    # callers guarantee n % 128 == 0 and p % 8 == 0
    tile_n = NODE_TILE if n % NODE_TILE == 0 else n
    tile_p = POD_TILE if p % POD_TILE == 0 else p
    # node-level bits: condition mask + the invalid-row marker in the sign
    # bit (one i32 per node keeps SMEM/VMEM traffic minimal)
    node_bits = (state.conditions.astype(jnp.int32)
                 | jnp.where(state.valid, 0, jnp.int32(-2147483648)))
    grid = (p // tile_p, n // tile_n)
    spec = pl.BlockSpec
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((p, n), jnp.float32),
        grid=grid,
        in_specs=[
            spec((tile_p, sel_onehot.shape[1]), lambda i, j: (i, 0)),
            spec((tile_p, 1), lambda i, j: (i, 0)),
            spec((tile_p, untol.shape[1]), lambda i, j: (i, 0)),
            spec((tile_p, 1), lambda i, j: (i, 0)),
            spec((tile_p, 1), lambda i, j: (i, 0)),
            spec((tile_p, 1), lambda i, j: (i, 0)),
            spec((tile_n, sel_onehot.shape[1]), lambda i, j: (j, 0)),
            spec((tile_n, untol.shape[1]), lambda i, j: (j, 0)),
            spec((tile_n, 1), lambda i, j: (j, 0)),
            spec((tile_n, 1), lambda i, j: (j, 0)),
            spec((tile_n, 1), lambda i, j: (j, 0)),
        ],
        out_specs=spec((tile_p, tile_n), lambda i, j: (i, j)),
    )(
        sel_onehot,
        sel_count.reshape(p, 1),
        untol,
        best_effort.astype(jnp.int32).reshape(p, 1),
        node_name_lo.astype(jnp.int32).reshape(p, 1),
        node_name_hi.astype(jnp.int32).reshape(p, 1),
        state.sel_member,
        state.taint_hard_member,
        node_bits.reshape(n, 1),
        state.name_lo.astype(jnp.int32).reshape(n, 1),
        state.name_hi.astype(jnp.int32).reshape(n, 1),
    )
    return out != 0.0
