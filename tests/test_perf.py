"""Throughput CI gates — the reference's enforced scheduler_perf thresholds
(test/integration/scheduler_perf/scheduler_test.go:35-38: fail < 30 pods/s,
warn < 100 pods/s) applied to the small SchedulingBasic-style config. Runs on
the CPU test backend, which sustains orders of magnitude more."""

import pytest

from kubernetes_tpu.perf.harness import run_throughput
from kubernetes_tpu.state import Capacities


def test_scheduling_basic_throughput_floor():
    # SchedulingBasic: 100 nodes / 300 pods (scaled-down density config)
    result = run_throughput(
        100, 300, caps=Capacities(num_nodes=128, batch_pods=128))
    assert result.scheduled == 300
    assert result.pods_per_sec >= 100, f"below warn threshold: {result}"


def test_throughput_with_feature_mix():
    result = run_throughput(
        60, 200,
        caps=Capacities(num_nodes=64, batch_pods=64),
        node_kwargs={"zones": 3, "labels_per_node": 2, "taint_every": 10},
        pod_kwargs={"selector_every": 7, "tolerate": True},
    )
    # tainted nodes exist and some pods carry selectors; everything that fits
    # must still schedule at full speed
    assert result.scheduled == 200
    assert result.pods_per_sec >= 100, f"below warn threshold: {result}"


def test_interpod_config_throughput_and_latency_floor():
    """Scaled-down InterPodAffinity BASELINE config with throughput AND
    latency gates (VERDICT r2 #9): regressions in the O(P x N x terms) path
    or per-batch latency fail CI instead of shipping silently. CPU backend
    sustains ~1200 pods/s here; floors leave ~5x headroom for CI noise."""
    result = run_throughput(
        200, 400,
        node_kwargs={"zones": 3},
        pod_kwargs={"app_groups": 4, "anti_affinity_every": 16,
                    "pref_affinity_every": 4})
    assert result.scheduled == 400
    assert result.pods_per_sec >= 200, f"interpod throughput: {result}"
    assert result.metrics["e2e_p50_ms"] < 2000, result.metrics
    assert result.metrics["e2e_p99_ms"] < 4000, result.metrics


def test_spread_config_throughput_and_latency_floor():
    """Scaled-down SelectorSpread (PodTopologySpread analog) BASELINE config
    with services; gates both pods/s and p50/p99 (CPU sustains ~1900)."""
    result = run_throughput(
        300, 600,
        node_kwargs={"zones": 3},
        pod_kwargs={"app_groups": 4},
        n_services=4)
    assert result.scheduled == 600
    assert result.pods_per_sec >= 300, f"spread throughput: {result}"
    assert result.metrics["e2e_p50_ms"] < 2000, result.metrics
    assert result.metrics["e2e_p99_ms"] < 4000, result.metrics


def test_host_phase_cost_gates():
    """Host-phase drift gates (VERDICT r3 weak #6): per-phase
    host cost in us/pod is stable run-to-run (unlike e2e throughput), so
    these floors catch 2-3x regressions the coarse pods/s gates would
    pass. Measured on the CPU CI backend: bind ~8, commit ~11, encode ~13
    us/pod after the r4 bulk-bind work."""
    result = run_throughput(
        300, 1200, caps=Capacities(num_nodes=512, batch_pods=256),
        node_kwargs={"zones": 3})
    assert result.scheduled == 1200
    phases = result.metrics["phase_us_per_pod"]
    # host phases accrue thread CPU time (stage threads overlap the loop,
    # so wall time would count GIL waits on a concurrent solve's
    # trace/compile); the summed host cost is the stable drift signal —
    # ~35 us/pod, so 150 catches a 2x regression of the whole plane or
    # ~10x of any single phase
    total = (phases["bind"] + phases["commit"] + phases["encode"]
             + phases["flush"])
    assert total < 150, phases
    assert phases["commit"] < 40, phases
    assert phases["encode"] < 50, phases


def test_device_solve_floor():
    """Compiled-solver throughput gate on the stable device-only number
    (~30k pods/s on the CPU CI backend at this shape; 3x headroom)."""
    from kubernetes_tpu.perf.harness import run_device_solve

    result = run_device_solve(300, batch_pods=256, iters=6)
    assert result.pods_per_sec >= 10_000, result


@pytest.mark.parametrize("sample_rate", [0.0, 1.0])
def test_chip_smoke_bind_witness_counts_binds_not_writes(sample_rate):
    """chip_smoke's exactly-once check, at a tiny size on the CPU. A sampled
    batch stamps a trace annotation on its already-bound pods: that write
    keeps the node and must not count as a second bind."""
    import chip_smoke
    from kubernetes_tpu.obs.tracing import TRACER

    prev = TRACER.sample_rate
    TRACER.sample_rate = sample_rate
    try:
        chip_smoke.headline(200, 400)
    finally:
        TRACER.sample_rate = prev
