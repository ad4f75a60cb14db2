#!/usr/bin/env python
"""Headline benchmark suite: the BASELINE.md scheduler_perf-style configs,
run end-to-end through the full framework (in-memory apiserver -> informers
-> encode -> batched device solve -> bind -> watch confirmation).

Configs (BASELINE.json):
- headline: NodeResourcesFit/LeastAllocated shape, 15k nodes / 30k pods
  (the north-star scale; BENCH_NODES/BENCH_PODS override)
- interpod: InterPodAffinity-heavy, 5k nodes (required hostname
  anti-affinity + preferred zone affinity over app groups)
- spread:   SelectorSpread (PodTopologySpread analog), 3 zones,
  15k nodes / 30k pods with services selecting the app groups

Baseline: the reference kube-scheduler's enforced scheduler_perf threshold
is 30 pods/s at >=1000 fake nodes (hard test failure below it;
test/integration/scheduler_perf/scheduler_test.go:35-38 and BASELINE.md).
vs_baseline = headline value / 30.

Prints exactly ONE JSON line on stdout (headline metric + per-config
extras). Diagnostics go to stderr. Env overrides: BENCH_NODES, BENCH_PODS,
BENCH_TIMEOUT_S, BENCH_CONFIGS (comma list of
headline,interpod,spread,gang,preemption,recovery,chaos,overload,device),
BENCH_GANG_NODES / BENCH_GANG_PODS / BENCH_GANG_SIZE (gang config shape,
default 50k nodes / 24576 pods in 8-wide groups), BENCH_PREEMPT_NODES
(preemption drill size, default 512 nodes saturated with low-priority
filler), BENCH_CHAOS_NODES / BENCH_CHAOS_SEED (convergence-under-chaos
drill: seeded FaultPlane + watch expiry + scheduler crash; reports
chaos_recovery_ms), BENCH_OVERLOAD_NODES / BENCH_OVERLOAD_PODS /
BENCH_OVERLOAD_MULT / BENCH_OVERLOAD_SEED + BENCH_FANOUT_WATCHERS /
BENCH_FANOUT_EVENTS (noisy-tenant APF drill + watch-cache fan-out;
reports overload_p99_ms and watch_fanout_events_per_sec),
BENCH_SOLVERSVC_TENANTS / BENCH_SOLVERSVC_NODES / BENCH_SOLVERSVC_PODS /
BENCH_SOLVERSVC_BATCH_PODS / BENCH_SOLVERSVC_FLOOD (solver-as-a-service
drill: M tenant control planes — one on the stock extender wire — share
one continuous-batching device program; reports per-tenant victim p99
under a noisy flood, aggregate vs solo pods/s, and errors on any
cross-tenant assignment or double bind),
BENCH_E2E_GATE (headline pods/s hard floor at >=1000 nodes, default
15000 — pins the staged host pipeline the way BENCH_DEVICE_GATE pins the
compiled program; 0 disables, and --smoke defaults it off). The headline
extras also carry the staged pipeline's per-stage busy fractions and
inter-stage queue high-water marks (headline_pipeline_*).
BENCH_MONITOR_TARGETS / BENCH_MONITOR_SECONDS / BENCH_MONITOR_INTERVAL
shape the monitoring-plane drill (a Monitor scraping a live ObsServer
fleet; reports scrape p99, samples/s ingested, query p99, and errors on
any scrape failure or unbounded TSDB growth). BENCH_HA_NODES /
BENCH_HA_PODS / BENCH_HA_SEED / BENCH_HA_REPLICAS /
BENCH_HA_FAILOVER_P99_MS shape the rolling-restart HA drill (N stateless
apiserver replicas over one store, each killed once mid-workload — hard
and graceful — while scheduler + informers + a coherence watcher run;
errors on any double-bind, watch gap/duplicate, failover p99 past the
bound, or relists outnumbering resume-from-rv recoveries).
BENCH_DEFRAG_NODES / BENCH_DEFRAG_GANG / BENCH_DEFRAG_MAX_MOVES /
BENCH_DEFRAG_SEED shape the descheduler drill (full default 50k nodes,
8-wide gang): a seeded fragmented cluster where a Pending gang is
unschedulable despite ample aggregate capacity; the descheduler (run
under the RaceDetector store) must first plan in dry-run with zero
executed moves, then restore gang schedulability within the move budget;
errors on non-convergence, dry-run moves, double-binds, or racy writes
(reports defrag_convergence_ms and the probe-solve cost).

The opt-in `sharded` config (BENCH_CONFIGS=...,sharded) runs
headline/gang/preemption plus a device-solve gate with the node axis
GSPMD-sharded across every attached device (BENCH_SHARDED_NODES default
100000, BENCH_SHARDED_PODS, BENCH_SHARDED_GANG_PODS,
BENCH_SHARDED_PREEMPT_NODES, BENCH_SHARDED_DEVICE_PODS,
BENCH_SHARDED_GATE device floor — 0 disables). BENCH_SHARDED_FORCE_HOST=1
(the --smoke default) forces 8 virtual CPU devices via XLA_FLAGS so the
whole multi-chip path runs in CI; extras carry per-shard occupancy and
the StateDB flush-transfer counters proving the hot path never uploads
full-cluster host arrays.

--metrics-snapshot (or BENCH_METRICS_SNAPSHOT=1) embeds the scheduler's
per-phase registry histograms (encode/flush/dispatch/solve/bind/commit:
count, sum_ms, p50_ms, p99_ms) in extras for each throughput config.

--smoke (or BENCH_SMOKE=1) shrinks every config to seconds-scale CI
shapes (hundreds of nodes, no device gate) so the whole bench path —
including the autoscaler config — runs inside a tier-1 test and drift
breaks the suite instead of the next real bench run. Explicit env
overrides still win.

--trace-out PATH (or BENCH_TRACE_OUT) forces trace sampling to 1.0
(KTPU_TRACE_SAMPLE stays overridable) and writes every finished span as
Chrome trace-event JSON — load it in Perfetto / chrome://tracing for one
row per pipeline stage/thread (client, apiserver, encode, dispatch,
settle, commit, kubelet).

--profile (or BENCH_PROFILE=1) runs the continuous profiling plane
(obs/profiling.py) across the whole bench: the sampling host profiler
rides every config and its collapsed flamegraph stacks land in
--profile-out PATH (BENCH_PROFILE_OUT, default bench_profile.collapsed);
the compile registry collects per-variant compile seconds and
cost_analysis flops/bytes; and RESULT.bottleneck names the dominant
stage per config (headline from pipeline busy fractions, defrag from
probe-solve vs plan/execute split) with busy fractions, transfer bytes
and compile-cost totals attached — "name the next wall" as a gated
artifact.
"""

import faulthandler
import json
import os
import signal
import sys

RESULT: dict = {
    "metric": "pods_scheduled_per_sec_15k_nodes",
    "value": None,
    "unit": "pods/s",
    "vs_baseline": None,
}


def _die_with_timeout(signum, frame):
    faulthandler.dump_traceback(file=sys.stderr)
    RESULT["error"] = (f"benchmark timed out after "
                       f"{os.environ.get('BENCH_TIMEOUT_S', '1800')}s")
    print(json.dumps(RESULT), flush=True)
    os._exit(2)


def _flag_value(flag: str) -> str | None:
    """--flag value and --flag=value forms, None when absent."""
    argv = sys.argv[1:]
    for i, a in enumerate(argv):
        if a == flag:
            return argv[i + 1] if i + 1 < len(argv) else None
        if a.startswith(flag + "="):
            return a.split("=", 1)[1]
    return None


def main() -> None:
    smoke = "--smoke" in sys.argv[1:] or \
        os.environ.get("BENCH_SMOKE", "") in ("1", "true")
    profile = "--profile" in sys.argv[1:] or \
        os.environ.get("BENCH_PROFILE", "") in ("1", "true")
    profile_out = _flag_value("--profile-out") or \
        os.environ.get("BENCH_PROFILE_OUT") or "bench_profile.collapsed"
    trace_out = _flag_value("--trace-out") or \
        os.environ.get("BENCH_TRACE_OUT") or None
    if trace_out:
        # the trace artifact is the point of this run: sample every root
        # (set before any kubernetes_tpu import; an explicit env wins)
        os.environ.setdefault("KTPU_TRACE_SAMPLE", "1")
    if smoke:
        # CI shapes: every default shrinks to seconds-scale; explicit env
        # overrides still take precedence below
        os.environ.setdefault("BENCH_NODES", "200")
        os.environ.setdefault("BENCH_PODS", "400")
        os.environ.setdefault("BENCH_GANG_NODES", "256")
        os.environ.setdefault("BENCH_GANG_PODS", "64")
        os.environ.setdefault("BENCH_PREEMPT_NODES", "32")
        os.environ.setdefault("BENCH_CHAOS_NODES", "32")
        os.environ.setdefault("BENCH_AUTOSCALER_PODS", "64")
        os.environ.setdefault("BENCH_OVERLOAD_NODES", "16")
        os.environ.setdefault("BENCH_OVERLOAD_PODS", "32")
        os.environ.setdefault("BENCH_OVERLOAD_MULT", "10")
        os.environ.setdefault("BENCH_FANOUT_WATCHERS", "500")
        os.environ.setdefault("BENCH_FANOUT_EVENTS", "20")
        os.environ.setdefault("BENCH_FANOUT_XL_WATCHERS", "2000")
        os.environ.setdefault("BENCH_FANOUT_XL_EVENTS", "5")
        os.environ.setdefault("BENCH_FANOUT_XL_NOMINAL", "3")
        os.environ.setdefault("BENCH_FANOUT_XL_BASE_WATCHERS", "500")
        os.environ.setdefault("BENCH_FANOUT_XL_SCHED_NODES", "8")
        os.environ.setdefault("BENCH_FANOUT_XL_SCHED_PODS", "16")
        os.environ.setdefault("BENCH_FANOUT_XL_GATE", "0")  # CI: no gate
        os.environ.setdefault("BENCH_SOLVERSVC_TENANTS", "4")
        os.environ.setdefault("BENCH_SOLVERSVC_NODES", "8")
        os.environ.setdefault("BENCH_SOLVERSVC_PODS", "16")
        os.environ.setdefault("BENCH_SOLVERSVC_BATCH_PODS", "32")
        os.environ.setdefault("BENCH_SOLVERSVC_FLOOD", "8")
        os.environ.setdefault("BENCH_MONITOR_TARGETS", "3")
        os.environ.setdefault("BENCH_MONITOR_SECONDS", "2")
        os.environ.setdefault("BENCH_MONITOR_INTERVAL", "0.2")
        os.environ.setdefault("BENCH_HA_NODES", "8")
        os.environ.setdefault("BENCH_HA_PODS", "24")
        os.environ.setdefault("BENCH_DEFRAG_NODES", "24")
        os.environ.setdefault("BENCH_DEFRAG_GANG", "4")
        os.environ.setdefault("BENCH_DEFRAG_MAX_MOVES", "4")
        os.environ.setdefault("BENCH_DEVICE_GATE", "0")  # CPU CI: no gate
        os.environ.setdefault("BENCH_E2E_GATE", "0")     # seconds-scale run
        os.environ.setdefault("BENCH_SHARDED_NODES", "64")
        os.environ.setdefault("BENCH_SHARDED_PODS", "96")
        os.environ.setdefault("BENCH_SHARDED_GANG_PODS", "32")
        os.environ.setdefault("BENCH_SHARDED_PREEMPT_NODES", "16")
        os.environ.setdefault("BENCH_SHARDED_DEVICE_PODS", "64")
        os.environ.setdefault("BENCH_SHARDED_GATE", "0")  # CPU CI: no gate
        os.environ.setdefault("BENCH_SHARDED_FORCE_HOST", "1")
        os.environ.setdefault("BENCH_MULTIPROC_WORKERS", "2")
        os.environ.setdefault("BENCH_MULTIPROC_WATCHERS", "50")
        os.environ.setdefault("BENCH_MULTIPROC_EVENTS", "10")
        os.environ.setdefault("BENCH_MULTIPROC_PODS", "12")
        # 1-vCPU CI: worker processes contend for one core, so the
        # cross-process rate cannot beat in-process — correctness gates
        # stay armed, the perf gate does not
        os.environ.setdefault("BENCH_MULTIPROC_GATE", "0")
        os.environ.setdefault("BENCH_SOAK_NODES", "8")
        os.environ.setdefault("BENCH_SOAK_TICKS", "36")
        os.environ.setdefault("BENCH_SOAK_RATE", "1.5")
        os.environ.setdefault("BENCH_SOAK_TICK_S", "0.02")
        os.environ.setdefault("BENCH_SOAK_P99_MS", "0")  # CI: latency
        # gate off (seconds-scale ticks make p99 meaningless on CPU);
        # the exactly-once/race/stall/memory-ceiling gates stay armed
        os.environ.setdefault("BENCH_SOAK_SNAPSHOT_EVERY", "150")
        os.environ.setdefault("BENCH_SOAK_RSS_SLACK", "0.6")
        os.environ.setdefault("BENCH_STOREHA_NODES", "8")
        os.environ.setdefault("BENCH_STOREHA_PODS", "36")
        os.environ.setdefault("BENCH_FED_CLUSTERS", "3")
        os.environ.setdefault("BENCH_FED_PODS", "16")
        os.environ.setdefault(
            "BENCH_CONFIGS",
            "headline,gang,preemption,autoscaler,sharded,monitor,defrag,"
            "solver-svc,soak,store-ha,fed")
        os.environ.setdefault("BENCH_TIMEOUT_S", "600")
    timeout = int(os.environ.get("BENCH_TIMEOUT_S", "1800"))
    signal.signal(signal.SIGALRM, _die_with_timeout)
    signal.alarm(timeout)

    n_nodes = int(os.environ.get("BENCH_NODES", "15000"))
    n_pods = int(os.environ.get("BENCH_PODS", "30000"))
    configs = os.environ.get(
        "BENCH_CONFIGS",
        "headline,interpod,spread,gang,preemption,recovery,chaos,overload,"
        "device,autoscaler,monitor,ha,fanout-xl,multiproc,defrag,"
        "solver-svc,store-ha,fed")
    configs = [c.strip() for c in configs.split(",") if c.strip()]
    metrics_snapshot = "--metrics-snapshot" in sys.argv[1:] or \
        os.environ.get("BENCH_METRICS_SNAPSHOT", "") in ("1", "true")

    # the sharded config needs >=2 devices; BENCH_SHARDED_FORCE_HOST=1
    # (default in --smoke) runs the whole bench on 8 virtual CPU devices —
    # a CPU rehearsal, never a chip run, so the platform is pinned to the
    # CPU too rather than mixing a chip with host devices. Must land in
    # the environment before jax is imported anywhere in this process.
    if "sharded" in configs and \
            os.environ.get("BENCH_SHARDED_FORCE_HOST", "") in ("1", "true"):
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8").strip()

    import jax

    from kubernetes_tpu.perf.harness import run_throughput

    if profile:
        # the profiling plane rides the whole bench: sampler thread on,
        # compile registry collecting cost_analysis per jit variant
        from kubernetes_tpu.obs import profiling

        profiling.PROFILER.start(cost_analysis=True)
        RESULT["bottleneck"] = {}

    print(f"bench: devices={jax.devices()} nodes={n_nodes} pods={n_pods} "
          f"configs={configs}", file=sys.stderr, flush=True)

    baseline = 30.0  # reference hard-fail floor at >=1000-node configs
    extras: dict = {}

    if "headline" in configs:
        r = run_throughput(n_nodes, n_pods, node_kwargs={"zones": 3})
        print(f"bench[headline]: {r} | {r.metrics}", file=sys.stderr,
              flush=True)
        RESULT["metric"] = f"pods_scheduled_per_sec_{n_nodes // 1000}k_nodes"
        RESULT["value"] = round(r.pods_per_sec, 1)
        RESULT["vs_baseline"] = round(r.pods_per_sec / baseline, 2)
        extras["headline_e2e_p50_ms"] = round(r.metrics["e2e_p50_ms"], 1)
        extras["headline_e2e_p99_ms"] = round(r.metrics["e2e_p99_ms"], 1)
        if "phase_us_per_pod" in r.metrics:
            extras["headline_phase_us_per_pod"] = r.metrics["phase_us_per_pod"]
        if r.pipeline:
            # where the next wall is: fraction of the timed wave each stage
            # thread was busy + queue-depth high-water marks between stages
            extras["headline_pipeline_busy_frac"] = \
                r.pipeline["stage_busy_frac"]
            extras["headline_pipeline_queue_max"] = \
                r.pipeline["queue_depth_max"]
            extras["headline_pipeline_depth"] = r.pipeline["depth"]
        # e2e regression gate on the headline figure itself (the device
        # gate only pins the compiled program; this one pins the host
        # pipeline too). Default floor is ~75% of the recorded staged-
        # driver rate, so a host-side regression that eats the pipeline
        # win trips the bench even when the device program is untouched.
        e2e_floor = float(os.environ.get("BENCH_E2E_GATE", "15000"))
        if e2e_floor > 0 and n_nodes >= 1000:
            extras["e2e_gate_floor_pods_per_sec"] = e2e_floor
            extras["e2e_gate_ok"] = bool(r.pods_per_sec >= e2e_floor)
            if not extras["e2e_gate_ok"]:
                RESULT["error"] = (
                    f"e2e regression: headline {r.pods_per_sec:.0f} pods/s "
                    f"< gate {e2e_floor:.0f}")
        if metrics_snapshot:
            extras["headline_phase_hist"] = r.phase_hist
        if profile:
            # dominant stage over the timed wave: pipeline busy seconds
            # when staged, phase CPU seconds otherwise
            from kubernetes_tpu.obs import profiling

            busy = (r.pipeline or {}).get("stage_busy_frac") or {}
            if busy:
                costs = {k: v * r.seconds for k, v in busy.items()}
            else:
                costs = {k: v * r.scheduled / 1e6 for k, v in
                         r.metrics.get("phase_us_per_pod", {}).items()}
            RESULT["bottleneck"]["headline"] = profiling.bottleneck_report(
                "headline", costs,
                stage_busy_frac=busy or None,
                queue_depth_max=(r.pipeline or {}).get("queue_depth_max"),
                transfer_bytes=r.transfers,
                compile_totals=profiling.COMPILES.totals(),
                wall_s=r.seconds)

    if "interpod" in configs:
        interpod_nodes = min(n_nodes, 5000)
        r = run_throughput(
            interpod_nodes, 8192,
            node_kwargs={"zones": 3},
            pod_kwargs={"app_groups": 8, "anti_affinity_every": 16,
                        "pref_affinity_every": 2})
        print(f"bench[interpod]: {r} | {r.metrics}", file=sys.stderr,
              flush=True)
        extras["interpod_5k_pods_per_sec"] = round(r.pods_per_sec, 1)
        extras["interpod_vs_baseline"] = round(r.pods_per_sec / baseline, 2)
        if metrics_snapshot:
            extras["interpod_phase_hist"] = r.phase_hist

    if "spread" in configs:
        r = run_throughput(
            15000, 30000,
            node_kwargs={"zones": 3},
            pod_kwargs={"app_groups": 16},
            n_services=16)
        print(f"bench[spread]: {r} | {r.metrics}", file=sys.stderr,
              flush=True)
        extras["spread_15k_pods_per_sec"] = round(r.pods_per_sec, 1)
        extras["spread_vs_baseline"] = round(r.pods_per_sec / baseline, 2)
        extras["spread_e2e_p50_ms"] = round(r.metrics["e2e_p50_ms"], 1)
        if "phase_us_per_pod" in r.metrics:
            extras["spread_phase_us_per_pod"] = r.metrics["phase_us_per_pod"]
        if metrics_snapshot:
            extras["spread_phase_hist"] = r.phase_hist

    if "gang" in configs:
        # gang scheduling at TPU-pod scale: 50k nodes, every pod a member
        # of an 8-wide all-or-nothing group (the multi-host-slice shape) —
        # measures the group-revert solver + group-aware driver end to end
        gang_nodes = int(os.environ.get("BENCH_GANG_NODES", "50000"))
        gang_pods = int(os.environ.get("BENCH_GANG_PODS", "24576"))
        gang_size = int(os.environ.get("BENCH_GANG_SIZE", "8"))
        gang_pods -= gang_pods % gang_size  # no trailing partial group
        r = run_throughput(gang_nodes, gang_pods,
                           node_kwargs={"zones": 3},
                           pod_kwargs={"gang_size": gang_size})
        print(f"bench[gang]: {r} | {r.metrics}", file=sys.stderr, flush=True)
        key = f"gang_{gang_nodes // 1000}k_pods_per_sec"
        extras[key] = round(r.pods_per_sec, 1)
        extras["gang_vs_baseline"] = round(r.pods_per_sec / baseline, 2)
        gang_stats = r.metrics.get("gang", {})
        extras["gang_groups_placed"] = gang_stats.get("placed", 0)
        extras["gang_groups_reverted"] = gang_stats.get("reverted", 0)
        expected_groups = gang_pods // gang_size
        if gang_stats.get("placed", 0) + gang_stats.get("reverted", 0) \
                < expected_groups:
            RESULT["error"] = (
                f"gang bench: only "
                f"{gang_stats.get('placed', 0) + gang_stats.get('reverted', 0)}"
                f"/{expected_groups} groups settled")
        if metrics_snapshot:
            extras["gang_phase_hist"] = r.phase_hist

    if "preemption" in configs:
        from kubernetes_tpu.perf.harness import run_preemption

        # priority/preemption drill: saturate CPU with globalDefault-
        # priority filler, then land a higher-PriorityClass wave through
        # the full unschedulable -> victim-select -> evict+nominate ->
        # rebind path (ROADMAP priority & preemption tentpole)
        pre_nodes = int(os.environ.get("BENCH_PREEMPT_NODES", "512"))
        r = run_preemption(pre_nodes)
        print(f"bench[preemption]: {r}", file=sys.stderr, flush=True)
        extras["preemption_latency_ms"] = round(r.preemption_latency_ms, 1)
        extras["victims_per_sec"] = round(r.victims_per_sec, 1)
        extras["preemption_wave_bound"] = r.bound_wave
        extras["preemption_victims"] = r.victims
        extras["preemption_attempts"] = r.attempts
        if r.bound_wave < r.wave:
            RESULT["error"] = (
                f"preemption bench: only {r.bound_wave}/{r.wave} "
                f"high-priority pods landed")
        elif r.victims == 0:
            RESULT["error"] = ("preemption bench: wave landed without any "
                               "evictions (cluster was not saturated)")

    if "recovery" in configs:
        from kubernetes_tpu.perf.harness import run_recovery

        # headline-scale failure drill (round 5 default 5k hollow nodes;
        # the kill concentrates in one zone so the per-zone disruption
        # machinery engages — the zone state is part of the record)
        rec_nodes = int(os.environ.get("BENCH_RECOVERY_NODES", "5000"))
        r = run_recovery(rec_nodes, 3 * rec_nodes, kill_frac=0.1)
        print(f"bench[recovery]: {r}", file=sys.stderr, flush=True)
        extras[f"recovery_seconds_zonekill_{rec_nodes}n"] = round(
            r.seconds_to_recover, 2)
        extras["recovery_killed_nodes"] = r.killed
        extras["recovery_stranded_pods"] = r.stranded
        extras["recovery_zone_state"] = r.zone_state_during
        if r.zone_state_during not in ("PartialDisruption",
                                       "FullDisruption"):
            RESULT["error"] = (
                "recovery drill: killed zone never left Normal "
                f"({r.zone_state_during!r})")

    if "chaos" in configs:
        from kubernetes_tpu.perf.harness import run_chaos

        # convergence-under-chaos drill: the whole control plane talks
        # through a seeded FaultPlane (5% store 429/Conflict), a forced
        # watch expiry + watcher drop + hard scheduler crash lands
        # mid-workload, and the cluster must converge with every pod
        # bound exactly once (tests/test_faults.py is the assert-heavy
        # twin; this row records the recovery figure on real hardware)
        chaos_nodes = int(os.environ.get("BENCH_CHAOS_NODES", "128"))
        chaos_seed = int(os.environ.get("BENCH_CHAOS_SEED", "1234"))
        # --with-race-detector: run the same drill under the RaceDetector
        # store proxy + event-loop stall watchdog (testing/races.py) and
        # fail the row on any racy write or >100ms stall — the runtime
        # half of the ktpu-lint contract, on real hardware
        race_detect = "--with-race-detector" in sys.argv[1:] or \
            os.environ.get("BENCH_RACE_DETECTOR", "") in ("1", "true")
        r = run_chaos(chaos_nodes, n_pods=max(200, 2 * chaos_nodes),
                      seed=chaos_seed, race_detect=race_detect)
        print(f"bench[chaos]: {r}", file=sys.stderr, flush=True)
        extras["chaos_recovery_ms"] = round(r.recovery_ms, 1)
        extras["chaos_faults_injected"] = r.faults_injected
        extras["chaos_seed"] = r.seed
        if race_detect:
            extras["chaos_racy_writes"] = r.racy_writes
            extras["chaos_loop_stalls"] = r.loop_stalls
            extras["chaos_max_stall_ms"] = round(r.max_stall_ms, 1)
        if not r.converged:
            RESULT["error"] = (
                f"chaos drill did not converge (seed {r.seed}): "
                f"{r.bound}/{r.pods} bound, "
                f"{r.double_binds} double-binds")
        elif race_detect and (r.racy_writes or r.loop_stalls):
            RESULT["error"] = (
                f"chaos drill under race detector (seed {r.seed}): "
                f"{r.racy_writes} racy writes, {r.loop_stalls} event-loop "
                f"stalls (max {r.max_stall_ms:.0f}ms)")

    if "overload" in configs:
        from kubernetes_tpu.perf.harness import run_overload, run_watch_fanout

        # noisy-tenant overload drill: a tenant floods the HTTP apiserver
        # at BENCH_OVERLOAD_MULT x the scheduler's own request rate while
        # a workload schedules through it over TCP. APF must keep the
        # scheduler flow's p99 within 5x the unloaded baseline and every
        # pod bound exactly once; --with-race-detector additionally runs
        # the server under the RaceDetector + loop-stall watchdog
        ovl_nodes = int(os.environ.get("BENCH_OVERLOAD_NODES", "64"))
        ovl_pods = int(os.environ.get("BENCH_OVERLOAD_PODS", "256"))
        ovl_mult = float(os.environ.get("BENCH_OVERLOAD_MULT", "50"))
        ovl_seed = int(os.environ.get("BENCH_OVERLOAD_SEED", "2026"))
        race_detect = "--with-race-detector" in sys.argv[1:] or \
            os.environ.get("BENCH_RACE_DETECTOR", "") in ("1", "true")
        r = run_overload(ovl_nodes, ovl_pods, seed=ovl_seed,
                         flood_multiplier=ovl_mult,
                         race_detect=race_detect)
        print(f"bench[overload]: {r}", file=sys.stderr, flush=True)
        extras["overload_p99_ms"] = round(r.p99_loaded_ms, 2)
        extras["overload_p99_unloaded_ms"] = round(r.p99_unloaded_ms, 2)
        extras["overload_flood_requests"] = r.flood_requests
        extras["overload_flood_rejected"] = r.flood_rejected
        extras["overload_sched_rps"] = round(r.sched_rps, 1)
        extras["overload_seed"] = r.seed
        if race_detect:
            extras["overload_racy_writes"] = r.racy_writes
            extras["overload_loop_stalls"] = r.loop_stalls
            extras["overload_max_stall_ms"] = round(r.max_stall_ms, 1)
        if not r.converged:
            RESULT["error"] = (
                f"overload drill did not converge (seed {r.seed}): "
                f"{r.bound}/{r.pods} bound, "
                f"{r.double_binds} double-binds")
        elif not r.p99_bounded:
            RESULT["error"] = (
                f"overload drill: scheduler-flow p99 {r.p99_loaded_ms:.1f}"
                f"ms breached 5x unloaded baseline "
                f"({r.p99_unloaded_ms:.1f}ms)")
        elif race_detect and (r.racy_writes or r.loop_stalls):
            RESULT["error"] = (
                f"overload drill under race detector (seed {r.seed}): "
                f"{r.racy_writes} racy writes, {r.loop_stalls} event-loop "
                f"stalls (max {r.max_stall_ms:.0f}ms)")

        # watch-cache fan-out twin: N watchers, M events, and the store
        # must do exactly M queue puts (one subscription, the cache fans
        # out) — the O(watchers) -> O(1) write-path claim, measured
        fan_watchers = int(os.environ.get("BENCH_FANOUT_WATCHERS", "10000"))
        fan_events = int(os.environ.get("BENCH_FANOUT_EVENTS", "100"))
        fr = run_watch_fanout(fan_watchers, fan_events)
        print(f"bench[fanout]: {fr}", file=sys.stderr, flush=True)
        extras["watch_fanout_events_per_sec"] = round(fr.events_per_sec, 1)
        extras["watch_fanout_store_puts"] = fr.store_fanout_puts
        extras["watch_fanout_deliveries"] = fr.deliveries
        if fr.store_fanout_puts != fan_events:
            RESULT["error"] = (
                f"watch fanout: store did {fr.store_fanout_puts} puts for "
                f"{fan_events} events (the cache is not the only "
                f"subscriber)")

    if "solver-svc" in configs:
        from kubernetes_tpu.perf.harness import run_solver_svc

        # solver-as-a-service drill: M tenant control planes (tenant-0 an
        # unmodified extender consumer over the wire, the rest native
        # /solve clients) share ONE continuous-batching device program.
        # Gates stay armed even in --smoke: exactly-once binds per tenant
        # under the RaceDetector, zero cross-tenant assignments, a noisy
        # tenant's flood moves the victim's p99 by at most 5x, and the
        # multi-tenant aggregate throughput at least matches one tenant
        # pushing the same total shape through the same warmed service
        svc_tenants = int(os.environ.get("BENCH_SOLVERSVC_TENANTS", "4"))
        svc_nodes = int(os.environ.get("BENCH_SOLVERSVC_NODES", "32"))
        svc_pods = int(os.environ.get("BENCH_SOLVERSVC_PODS", "96"))
        svc_batch = int(os.environ.get("BENCH_SOLVERSVC_BATCH_PODS", "64"))
        svc_flood = int(os.environ.get("BENCH_SOLVERSVC_FLOOD", "12"))
        svc_seed = int(os.environ.get("BENCH_SOLVERSVC_SEED", "2026"))
        race_detect = "--with-race-detector" in sys.argv[1:] or \
            os.environ.get("BENCH_RACE_DETECTOR", "") in ("1", "true")
        rs = run_solver_svc(
            n_tenants=svc_tenants, nodes_per_tenant=svc_nodes,
            pods_per_tenant=svc_pods, seed=svc_seed, batch_pods=svc_batch,
            flood_threads=svc_flood, race_detect=race_detect)
        print(f"bench[solver-svc]: {rs}", file=sys.stderr, flush=True)
        extras["solversvc_agg_pods_per_sec"] = round(rs.agg_pods_per_sec, 1)
        extras["solversvc_solo_pods_per_sec"] = \
            round(rs.solo_pods_per_sec, 1)
        extras["solversvc_victim_p99_ms"] = round(rs.p99_loaded_ms, 2)
        extras["solversvc_victim_p99_unloaded_ms"] = \
            round(rs.p99_unloaded_ms, 2)
        extras["solversvc_flood_requests"] = rs.flood_requests
        extras["solversvc_flood_rejected"] = rs.flood_rejected
        extras["solversvc_steps"] = rs.steps
        extras["solversvc_isolation_violations"] = rs.isolation_violations
        extras["solversvc_seed"] = rs.seed
        if race_detect:
            extras["solversvc_racy_writes"] = rs.racy_writes
        if not rs.converged:
            RESULT["error"] = (
                f"solver-svc drill did not converge (seed {rs.seed}): "
                f"{rs.bound}/{rs.expected_bound} bound, "
                f"{rs.double_binds} double-binds, "
                f"{rs.cross_tenant_assignments} cross-tenant assignments")
        elif rs.isolation_violations:
            RESULT["error"] = (
                f"solver-svc drill: {rs.isolation_violations} isolation "
                f"violations decoded from the shared batch")
        elif not rs.p99_bounded:
            RESULT["error"] = (
                f"solver-svc drill: victim p99 {rs.p99_loaded_ms:.1f}ms "
                f"under flood breached 5x unloaded baseline "
                f"({rs.p99_unloaded_ms:.1f}ms)")
        elif not rs.batching_wins:
            RESULT["error"] = (
                f"solver-svc drill: aggregate {rs.agg_pods_per_sec:.0f} "
                f"pods/s under {rs.tenants} tenants fell below the "
                f"single-tenant headline {rs.solo_pods_per_sec:.0f} at "
                f"the same total shape")
        elif race_detect and rs.racy_writes:
            RESULT["error"] = (
                f"solver-svc drill under race detector (seed {rs.seed}): "
                f"{rs.racy_writes} racy writes")

    if "ha" in configs:
        from kubernetes_tpu.perf.harness import run_rolling_restart

        # rolling-restart HA drill: BENCH_HA_REPLICAS stateless apiservers
        # over ONE shared store serve a live scheduler + informer +
        # coherence-watcher workload while every replica is killed once
        # mid-flight (hard aborts and a graceful drain) and restarted.
        # Contract: every pod bound exactly once, the watcher's rv stream
        # gapless and duplicate-free against the store's own history,
        # failover p99 under BENCH_HA_FAILOVER_P99_MS, and resume-from-rv
        # recoveries at least matching full relists
        ha_nodes = int(os.environ.get("BENCH_HA_NODES", "16"))
        ha_pods = int(os.environ.get("BENCH_HA_PODS", "96"))
        ha_seed = int(os.environ.get("BENCH_HA_SEED", "2027"))
        ha_replicas = int(os.environ.get("BENCH_HA_REPLICAS", "3"))
        ha_p99_bound = float(
            os.environ.get("BENCH_HA_FAILOVER_P99_MS", "2000"))
        race_detect = "--with-race-detector" in sys.argv[1:] or \
            os.environ.get("BENCH_RACE_DETECTOR", "") in ("1", "true")
        r = run_rolling_restart(ha_nodes, ha_pods, seed=ha_seed,
                                replicas=ha_replicas,
                                race_detect=race_detect)
        print(f"bench[ha]: {r}", file=sys.stderr, flush=True)
        extras["ha_replicas"] = r.replicas
        extras["ha_replica_faults"] = len(r.replica_faults)
        extras["ha_failovers"] = r.failovers
        extras["ha_failover_p99_ms"] = round(r.failover_p99_ms, 2)
        extras["ha_resumes"] = r.resumes
        extras["ha_relists"] = r.relists
        extras["ha_watch_resumes"] = r.watch_resumes
        extras["ha_watch_events"] = r.watch_events
        extras["ha_seed"] = r.seed
        if race_detect:
            extras["ha_racy_writes"] = r.racy_writes
            extras["ha_loop_stalls"] = r.loop_stalls
            extras["ha_max_stall_ms"] = round(r.max_stall_ms, 1)
        if not r.converged:
            RESULT["error"] = (
                f"ha drill did not converge (seed {r.seed}): "
                f"{r.bound}/{r.pods} bound, {r.double_binds} double-binds")
        elif r.watch_gaps or r.watch_dupes:
            RESULT["error"] = (
                f"ha drill watch incoherence (seed {r.seed}): "
                f"{r.watch_gaps} gaps, {r.watch_dupes} duplicates across "
                f"{r.watch_events} events")
        elif r.failover_p99_ms > ha_p99_bound:
            RESULT["error"] = (
                f"ha drill: failover p99 {r.failover_p99_ms:.1f}ms past "
                f"the {ha_p99_bound:.0f}ms bound")
        elif r.resumes < r.relists:
            RESULT["error"] = (
                f"ha drill: relists ({r.relists}) outnumbered resume-"
                f"from-rv recoveries ({r.resumes}) — failover is paying "
                f"full relist prices")
        elif race_detect and (r.racy_writes or r.loop_stalls):
            RESULT["error"] = (
                f"ha drill under race detector (seed {r.seed}): "
                f"{r.racy_writes} racy writes, {r.loop_stalls} event-loop "
                f"stalls (max {r.max_stall_ms:.0f}ms)")

    if "store-ha" in configs:
        from kubernetes_tpu.perf.harness import run_store_ha

        # store-HA (fenced failover) drill: BENCH_STOREHA_REPLICAS
        # *replicated stores* (WAL-streamed hot standbys,
        # apiserver/replication.py) serve a live scheduler + coherence
        # witness while the PRIMARY store is killed mid-workload — the
        # last SPOF the stateless `ha` drill can't touch — and later
        # resurrected still believing it rules. Contract: a standby
        # promotes under the lease and mints the next fencing epoch
        # (p99 under BENCH_STOREHA_PROMOTION_P99_MS), every pod binds
        # exactly once, ZERO writes are accepted under the stale epoch
        # (the resurrected primary's first write comes back FencedWrite),
        # and the witness rv stream stays gapless and duplicate-free
        # across the failover
        sha_nodes = int(os.environ.get("BENCH_STOREHA_NODES", "8"))
        sha_pods = int(os.environ.get("BENCH_STOREHA_PODS", "48"))
        sha_seed = int(os.environ.get("BENCH_STOREHA_SEED", "2031"))
        sha_replicas = int(os.environ.get("BENCH_STOREHA_REPLICAS", "3"))
        sha_p99_bound = float(
            os.environ.get("BENCH_STOREHA_PROMOTION_P99_MS", "5000"))
        race_detect = "--with-race-detector" in sys.argv[1:] or \
            os.environ.get("BENCH_RACE_DETECTOR", "") in ("1", "true")
        r = run_store_ha(sha_nodes, sha_pods, seed=sha_seed,
                         replicas=sha_replicas, race_detect=race_detect)
        print(f"bench[store-ha]: {r}", file=sys.stderr, flush=True)
        extras["store_ha_replicas"] = r.replicas
        extras["store_ha_promotions"] = r.promotions
        extras["store_ha_promotion_p99_ms"] = round(r.promotion_p99_ms, 2)
        extras["store_ha_epoch"] = r.epoch
        extras["store_ha_fenced_rejections"] = r.fenced_rejections
        extras["store_ha_fenced_leaks"] = r.fenced_leaks
        extras["store_ha_records_streamed"] = r.records_streamed
        extras["store_ha_snapshots_sent"] = r.snapshots_sent
        extras["store_ha_snapshots_discarded"] = r.snapshots_discarded
        extras["store_ha_watch_events"] = r.watch_events
        extras["store_ha_watch_resumes"] = r.watch_resumes
        extras["store_ha_seed"] = r.seed
        if race_detect:
            extras["store_ha_racy_writes"] = r.racy_writes
            extras["store_ha_loop_stalls"] = r.loop_stalls
            extras["store_ha_max_stall_ms"] = round(r.max_stall_ms, 1)
        if not r.converged:
            RESULT["error"] = (
                f"store-ha drill did not converge (seed {r.seed}): "
                f"{r.bound}/{r.pods} bound")
        elif r.double_binds:
            RESULT["error"] = (
                f"store-ha drill (seed {r.seed}): {r.double_binds} pods "
                f"bound more than once across the failover")
        elif r.fenced_leaks or not r.stale_resurrect_fenced:
            RESULT["error"] = (
                f"store-ha drill (seed {r.seed}): fencing breached — "
                f"{r.fenced_leaks} stale-epoch writes accepted "
                f"(stale primary fenced: {r.stale_resurrect_fenced})")
        elif r.promotions < 1:
            RESULT["error"] = (
                f"store-ha drill (seed {r.seed}): primary killed but no "
                f"standby promoted")
        elif r.watch_gaps or r.watch_dupes:
            RESULT["error"] = (
                f"store-ha drill watch incoherence (seed {r.seed}): "
                f"{r.watch_gaps} gaps, {r.watch_dupes} duplicates across "
                f"{r.watch_events} events")
        elif r.promotion_p99_ms > sha_p99_bound:
            RESULT["error"] = (
                f"store-ha drill: promotion p99 {r.promotion_p99_ms:.1f}ms "
                f"past the {sha_p99_bound:.0f}ms bound")
        elif race_detect and (r.racy_writes or r.loop_stalls):
            RESULT["error"] = (
                f"store-ha drill under race detector (seed {r.seed}): "
                f"{r.racy_writes} racy writes, {r.loop_stalls} event-loop "
                f"stalls (max {r.max_stall_ms:.0f}ms)")

    if "fed" in configs:
        from kubernetes_tpu.perf.harness import run_federation

        # federation global-planning drill: a hub control plane (health +
        # sync + GlobalPlanner) over BENCH_FED_CLUSTERS in-process member
        # control planes places a mixed `placement: global` workload set
        # (incl. one gang) via the stock device solver, then member 0 is
        # saturated mid-run (nodes gone, NodeGroup pinned at max — zero
        # autoscaler headroom). Contract: every workload's replicas land
        # across clusters exactly once (member copies sum to the hub
        # total and match the plan), the planner records >= 1 spillover
        # and drains the victim to zero, convergence within the bench
        # timeout, and zero racy hub writes under the RaceDetector
        fed_clusters = int(os.environ.get("BENCH_FED_CLUSTERS", "4"))
        fed_pods = int(os.environ.get("BENCH_FED_PODS", "24"))
        fed_seed = int(os.environ.get("BENCH_FED_SEED", "2032"))
        race_detect = "--with-race-detector" in sys.argv[1:] or \
            os.environ.get("BENCH_RACE_DETECTOR", "") in ("1", "true")
        r = run_federation(fed_clusters, fed_pods, seed=fed_seed,
                           race_detect=race_detect)
        print(f"bench[fed]: {r}", file=sys.stderr, flush=True)
        extras["fed_clusters"] = r.clusters
        extras["fed_workloads"] = r.workloads
        extras["fed_planned"] = r.planned
        extras["fed_placed"] = r.placed
        extras["fed_spillovers"] = r.spillovers
        extras["fed_cycles"] = r.cycles
        extras["fed_solves"] = r.solves
        extras["fed_solve_ms"] = round(r.solve_p50_ms, 2)
        extras["fed_seed"] = r.seed
        if race_detect:
            extras["fed_racy_writes"] = r.racy_writes
        if not r.converged:
            RESULT["error"] = (
                f"fed drill did not converge (seed {r.seed}): "
                f"{r.planned}/{r.workloads} planned, "
                f"{r.placed} replicas placed")
        elif not r.exactly_once or r.duplicate_placements:
            RESULT["error"] = (
                f"fed drill (seed {r.seed}): placement not exactly-once "
                f"({r.duplicate_placements} duplicated workloads)")
        elif r.spillovers < 1 or not r.victim_drained:
            RESULT["error"] = (
                f"fed drill (seed {r.seed}): saturated member did not "
                f"spill ({r.spillovers} spillovers, victim drained: "
                f"{r.victim_drained})")
        elif race_detect and r.racy_writes:
            RESULT["error"] = (
                f"fed drill under race detector (seed {r.seed}): "
                f"{r.racy_writes} racy hub writes")

    if "fanout-xl" in configs:
        from kubernetes_tpu.perf.harness import run_fanout_xl

        # sharded off-loop watch fan-out drill: BENCH_FANOUT_XL_WATCHERS
        # sink watchers on FanoutShard threads vs the single-loop
        # (KTPU_FANOUT_SHARDS=0) fallback in the same process. Gates
        # (BENCH_FANOUT_XL_GATE=0 disables the perf gates; the
        # correctness gates — O(events) store puts, zero evictions,
        # encode-once, witness coherence — are always armed):
        # deliveries/s >= gate x the single-loop baseline, and scheduler
        # batch-e2e p99 within 5x its unloaded self while the nominal
        # flood runs
        xl_watchers = int(
            os.environ.get("BENCH_FANOUT_XL_WATCHERS", "100000"))
        xl_events = int(os.environ.get("BENCH_FANOUT_XL_EVENTS", "12"))
        xl_nominal = int(os.environ.get("BENCH_FANOUT_XL_NOMINAL", "8"))
        xl_base = int(
            os.environ.get("BENCH_FANOUT_XL_BASE_WATCHERS", "10000"))
        xl_sched_nodes = int(
            os.environ.get("BENCH_FANOUT_XL_SCHED_NODES", "32"))
        xl_sched_pods = int(
            os.environ.get("BENCH_FANOUT_XL_SCHED_PODS", "128"))
        xl_gate = float(os.environ.get("BENCH_FANOUT_XL_GATE", "5"))
        xl_p99_mult = float(os.environ.get("BENCH_FANOUT_XL_P99X", "5"))
        r = run_fanout_xl(xl_watchers, xl_events,
                          nominal_events=xl_nominal,
                          baseline_watchers=xl_base,
                          sched_nodes=xl_sched_nodes,
                          sched_pods=xl_sched_pods)
        print(f"bench[fanout-xl]: {r}", file=sys.stderr, flush=True)
        extras["fanout_xl_watchers"] = r.watchers
        extras["fanout_xl_shards"] = r.shards
        extras["fanout_xl_deliveries"] = r.deliveries
        extras["fanout_xl_events_per_sec"] = round(r.events_per_sec, 1)
        extras["fanout_xl_baseline_events_per_sec"] = round(
            r.baseline_events_per_sec, 1)
        extras["fanout_xl_speedup"] = round(r.speedup, 2)
        extras["fanout_xl_store_puts"] = r.store_fanout_puts
        extras["fanout_xl_evicted"] = r.evicted
        extras["fanout_xl_frames_encoded"] = r.frames_encoded
        extras["fanout_xl_frames_delivered"] = r.frames_delivered
        extras["fanout_xl_encode_ratio"] = round(r.encode_ratio, 1)
        extras["fanout_xl_witness_events"] = r.witness_events
        extras["fanout_xl_sched_p99_base_ms"] = round(
            r.sched_p99_base_ms, 1)
        extras["fanout_xl_sched_p99_flood_ms"] = round(
            r.sched_p99_flood_ms, 1)
        if r.store_fanout_puts != r.events:
            RESULT["error"] = (
                f"fanout-xl: store did {r.store_fanout_puts} puts for "
                f"{r.events} events (the cache is not the only "
                f"subscriber)")
        elif r.evicted:
            RESULT["error"] = (
                f"fanout-xl: {r.evicted} slow-consumer evictions at "
                f"nominal rate (expected 0)")
        elif r.witness_gaps or r.witness_dupes:
            RESULT["error"] = (
                f"fanout-xl witness incoherence: {r.witness_gaps} gaps, "
                f"{r.witness_dupes} duplicates across "
                f"{r.witness_events} events at the fence rv")
        elif r.frames_encoded != r.events:
            RESULT["error"] = (
                f"fanout-xl: {r.frames_encoded} frames encoded for "
                f"{r.events} events — the encode-once contract is "
                f"broken")
        elif r.frames_delivered != r.deliveries + r.witness_events:
            RESULT["error"] = (
                f"fanout-xl: frames_delivered_total "
                f"{r.frames_delivered} != {r.deliveries} sink + "
                f"{r.witness_events} witness deliveries")
        elif xl_gate and r.speedup < xl_gate:
            RESULT["error"] = (
                f"fanout-xl: sharded delivery {r.events_per_sec:.0f}/s "
                f"is only {r.speedup:.1f}x the single-loop "
                f"{r.baseline_events_per_sec:.0f}/s (gate {xl_gate}x)")
        elif xl_gate and r.sched_p99_base_ms > 0 and \
                r.sched_p99_flood_ms > xl_p99_mult * r.sched_p99_base_ms:
            RESULT["error"] = (
                f"fanout-xl: scheduler batch-e2e p99 "
                f"{r.sched_p99_flood_ms:.1f}ms under flood breached "
                f"{xl_p99_mult}x its unloaded {r.sched_p99_base_ms:.1f}"
                f"ms")

    if "multiproc" in configs:
        from kubernetes_tpu.perf.harness import run_multiproc

        # multi-process control plane drill: a store-owner process feeds
        # BENCH_MULTIPROC_WORKERS real worker processes (pinned, own
        # serving loop + fan-out shards) through the shared-memory event
        # ring, A/B'd against the in-process sharded topology at the same
        # total-sink shape. The correctness gates are always armed —
        # encode-once across the process boundary (owner frames_encoded
        # == ring appends == store events, zero worker re-encodes),
        # exactly-once binds across a SIGKILL + respawn, a gapless
        # cross-process witness, and a zero-failure fleet scrape over
        # discovered per-worker /metrics. BENCH_MULTIPROC_GATE (default
        # 1: aggregate must at least match in-process; 0 disables) gates
        # the cross-process delivery rate
        mpw = int(os.environ.get("BENCH_MULTIPROC_WORKERS", "2"))
        mp_watchers = int(os.environ.get("BENCH_MULTIPROC_WATCHERS",
                                         "1000"))
        mp_events = int(os.environ.get("BENCH_MULTIPROC_EVENTS", "12"))
        mp_pods = int(os.environ.get("BENCH_MULTIPROC_PODS", "24"))
        mp_gate = float(os.environ.get("BENCH_MULTIPROC_GATE", "1"))
        r = run_multiproc(workers=mpw,
                          per_worker_watchers=mp_watchers,
                          events=mp_events, n_pods=mp_pods)
        print(f"bench[multiproc]: {r}", file=sys.stderr, flush=True)
        extras["multiproc_workers"] = r.workers
        extras["multiproc_watchers"] = r.watchers
        extras["multiproc_deliveries"] = r.deliveries
        extras["multiproc_events_per_sec"] = round(r.events_per_sec, 1)
        extras["multiproc_inproc_events_per_sec"] = round(
            r.inproc_events_per_sec, 1)
        extras["multiproc_speedup"] = round(r.speedup, 2)
        extras["multiproc_ring_appends"] = r.ring_appends
        extras["multiproc_worker_frames_encoded"] = r.worker_frames_encoded
        extras["multiproc_bound"] = r.bound
        extras["multiproc_bind_conflicts"] = r.bind_conflicts
        extras["multiproc_respawns"] = r.respawns
        extras["multiproc_failovers"] = r.failovers
        extras["multiproc_witness_events"] = r.witness_events
        extras["multiproc_monitor_targets"] = r.monitor_targets
        extras["multiproc_scrape_failures"] = r.scrape_failures
        if r.ring_appends != r.store_events:
            RESULT["error"] = (
                f"multiproc: {r.ring_appends} ring appends for "
                f"{r.store_events} store events — the owner is not "
                f"appending exactly once per event")
        elif r.owner_frames_encoded != r.ring_appends:
            RESULT["error"] = (
                f"multiproc: owner encoded {r.owner_frames_encoded} "
                f"frames for {r.ring_appends} ring appends — the "
                f"encode-once contract is broken at the writer")
        elif r.worker_frames_encoded:
            RESULT["error"] = (
                f"multiproc: workers re-encoded "
                f"{r.worker_frames_encoded} frames that crossed the ring "
                f"as wire bytes (expected 0)")
        elif r.deliveries < r.watchers * r.events:
            RESULT["error"] = (
                f"multiproc: {r.deliveries} sink deliveries for "
                f"{r.watchers} watchers x {r.events} events")
        elif r.bound != r.pods or r.double_binds:
            RESULT["error"] = (
                f"multiproc: {r.bound}/{r.pods} pods bound with "
                f"{r.double_binds} double-binds across the worker kill "
                f"(exactly-once broken)")
        elif r.witness_gaps or r.witness_dupes:
            RESULT["error"] = (
                f"multiproc witness incoherence: {r.witness_gaps} gaps, "
                f"{r.witness_dupes} duplicates across "
                f"{r.witness_events} events at the fence rv")
        elif not r.respawns or 0 not in r.reaped:
            RESULT["error"] = (
                f"multiproc: killed worker was not reaped+respawned "
                f"(reaped={r.reaped}, respawns={r.respawns})")
        elif r.monitor_targets < r.workers or r.scrape_failures:
            RESULT["error"] = (
                f"multiproc: monitor discovered {r.monitor_targets}/"
                f"{r.workers} worker targets with {r.scrape_failures} "
                f"scrape failures")
        elif mp_gate and r.speedup < mp_gate:
            RESULT["error"] = (
                f"multiproc: cross-process delivery "
                f"{r.events_per_sec:.0f}/s is only {r.speedup:.2f}x the "
                f"in-process {r.inproc_events_per_sec:.0f}/s "
                f"(gate {mp_gate}x)")

    if "autoscaler" in configs:
        from kubernetes_tpu.perf.harness import run_autoscaler

        # cluster-autoscaler drill: a pod burst lands on an empty node
        # group; the autoscaler's what-if probe solves must grow the group
        # until everything binds. Reports wall time to all-bound plus the
        # probe-solve cost (the device-batched simulation figure, PERF.md)
        as_pods = int(os.environ.get("BENCH_AUTOSCALER_PODS", "256"))
        as_max = int(os.environ.get("BENCH_AUTOSCALER_GROUP_MAX", "16"))
        r = run_autoscaler(n_pods=as_pods, group_max=as_max)
        print(f"bench[autoscaler]: {r}", file=sys.stderr, flush=True)
        extras["scaleup_convergence_ms"] = round(r.scaleup_convergence_ms, 1)
        extras["autoscaler_nodes_added"] = r.nodes_added
        extras["autoscaler_sim_solves"] = r.sim_solves
        extras["autoscaler_sim_ms_per_solve"] = round(r.sim_ms_per_solve, 2)
        if r.nodes_added == 0:
            RESULT["error"] = ("autoscaler bench: burst bound without any "
                               "scale-up (cluster was not empty)")

    if "defrag" in configs:
        from kubernetes_tpu.perf.harness import run_defrag

        # gang-defragmentation drill: a seeded cluster where every node
        # carries a filler pod (plus a skew pod on a quarter of them), so
        # a Pending gang is unschedulable despite ample aggregate free
        # capacity. The descheduler — run against a RaceDetector store —
        # must plan in dry-run WITHOUT executing, then evict a minimal
        # move set and restore gang schedulability inside the move budget
        # with exactly-once binds throughout
        df_nodes = int(os.environ.get("BENCH_DEFRAG_NODES", "50000"))
        df_gang = int(os.environ.get("BENCH_DEFRAG_GANG", "8"))
        df_moves = int(os.environ.get("BENCH_DEFRAG_MAX_MOVES", "8"))
        df_seed = int(os.environ.get("BENCH_DEFRAG_SEED", "1234"))
        defrag_tb0 = None
        if profile:
            from kubernetes_tpu.perf.harness import _transfer_counters

            defrag_tb0 = _transfer_counters()
        r = run_defrag(n_nodes=df_nodes, gang_size=df_gang,
                       max_moves=df_moves, seed=df_seed)
        print(f"bench[defrag]: {r}", file=sys.stderr, flush=True)
        extras["defrag_convergence_ms"] = round(r.defrag_convergence_ms, 1)
        extras["defrag_moves"] = r.moves
        extras["defrag_dry_run_planned"] = r.dry_run_planned
        extras["defrag_sim_solves"] = r.sim_solves
        extras["defrag_sim_ms_per_solve"] = round(r.sim_ms_per_solve, 2)
        extras["defrag_seed"] = r.seed
        if not r.start_unschedulable:
            RESULT["error"] = (
                f"defrag bench (seed {r.seed}): gang was schedulable "
                f"before any eviction (cluster was not fragmented)")
        elif r.dry_run_moves:
            RESULT["error"] = (
                f"defrag bench (seed {r.seed}): dry-run executed "
                f"{r.dry_run_moves} move(s) (expected 0)")
        elif not r.converged:
            RESULT["error"] = (
                f"defrag bench (seed {r.seed}): gang did not land "
                f"({r.gangs_defragged} defragged, {r.moves} moves, "
                f"{r.rollbacks} rollbacks)")
        elif r.double_binds or r.racy_writes:
            RESULT["error"] = (
                f"defrag bench (seed {r.seed}): {r.double_binds} "
                f"double-binds, {r.racy_writes} racy writes")
        if profile:
            # the defrag bill is probe solves vs everything else (plan
            # + evict + reschedule); PERF.md Round 13's 18×1369 ms story
            # becomes a gated verdict
            from kubernetes_tpu.obs import profiling
            from kubernetes_tpu.perf.harness import _transfer_counters

            tb1 = _transfer_counters()
            sim_s = r.sim_solves * r.sim_ms_per_solve / 1e3
            wall = r.defrag_convergence_ms / 1e3
            RESULT["bottleneck"]["defrag"] = profiling.bottleneck_report(
                "defrag",
                {"probe_solve": sim_s,
                 "plan_and_execute": max(0.0, wall - sim_s)},
                transfer_bytes={k: int(tb1[k] - defrag_tb0[k])
                                for k in defrag_tb0},
                compile_totals=profiling.COMPILES.totals(),
                wall_s=wall)

    if "soak" in configs:
        from kubernetes_tpu.scenario.soak import run_soak
        from kubernetes_tpu.scenario.traces import TraceConfig

        # day-in-the-life soak: a seeded trace tape (diurnal arrivals,
        # Borg-shaped gangs/priorities/lifetimes, deletes, node
        # flaps/drains/adds, watch faults) plays against the FULL control
        # plane — scheduler + autoscaler + descheduler + monitor — under
        # the RaceDetector + stall watchdog. Gates: every pod bound
        # exactly once, zero racy writes, zero >100ms stalls, flat memory
        # ceilings (RSS, WAL live records post-compaction, TSDB series,
        # jit variants) and, when armed, scheduler e2e p99. Any breach is
        # one-command reproducible from the printed replay seed;
        # scenario/search.py shrinks it to a minimal tape
        soak_nodes = int(os.environ.get("BENCH_SOAK_NODES", "15000"))
        soak_ticks = int(os.environ.get("BENCH_SOAK_TICKS", "288"))
        soak_seed = int(os.environ.get(
            "KTPU_SCENARIO_SEED",
            os.environ.get("BENCH_SOAK_SEED", "2026")))
        soak_rate = float(os.environ.get(
            "BENCH_SOAK_RATE", str(max(2.0, soak_nodes / 400))))
        soak_tick_s = float(os.environ.get("BENCH_SOAK_TICK_S", "0.25"))
        soak_p99 = float(os.environ.get("BENCH_SOAK_P99_MS", "2000"))
        soak_snapshot = int(os.environ.get(
            "BENCH_SOAK_SNAPSHOT_EVERY", "20000"))
        soak_slack = float(os.environ.get("BENCH_SOAK_RSS_SLACK", "0.35"))
        cfg = TraceConfig(
            seed=soak_seed, ticks=soak_ticks, nodes=soak_nodes,
            base_rate=soak_rate, flap_rate=0.05,
            autoscale_max=max(2, soak_nodes // 8),
            drain_every=max(2, soak_ticks // 6),
            add_every=max(2, soak_ticks // 5),
            watch_expire_ticks=(soak_ticks // 3,),
            watcher_drop_ticks=(2 * soak_ticks // 3,))
        r = run_soak(cfg, tick_seconds=soak_tick_s,
                     snapshot_every=soak_snapshot, p99_bound_ms=soak_p99,
                     rss_slack_frac=soak_slack)
        print(f"bench[soak]: {r}", file=sys.stderr, flush=True)
        extras["soak_seed"] = r.seed
        extras["soak_pods"] = r.pods_submitted
        extras["soak_bound"] = r.bound
        extras["soak_events_applied"] = r.events_applied
        extras["soak_p99_ms"] = round(r.p99_ms, 1)
        extras["soak_rss_growth_pct"] = round(100 * r.rss_growth_frac, 1)
        extras["soak_wal_compactions"] = r.compactions
        extras["soak_wal_records"] = r.wal_records
        extras["soak_tsdb_series"] = r.tsdb_series
        extras["soak_jit_variants"] = r.jit_variants
        extras["soak_scaleups"] = r.scaleups
        extras["soak_desched_moves"] = r.desched_moves
        extras["soak_node_flaps"] = r.node_flaps
        extras["soak_faults_injected"] = r.faults_injected
        extras["soak_violations"] = list(r.violations)
        if r.violations:
            RESULT["error"] = (f"soak gates breached (seed {r.seed}): "
                               + "; ".join(r.violations))
            # one-command repro: replay exactly this day
            print(f"bench[soak]: replay with KTPU_SCENARIO_SEED={r.seed} "
                  f"BENCH_CONFIGS=soak python bench.py"
                  + (" --smoke" if smoke else ""),
                  file=sys.stderr, flush=True)

    if "monitor" in configs:
        from kubernetes_tpu.perf.harness import run_monitor_bench

        # monitoring-plane overhead drill: the Monitor scrapes a fleet of
        # real ObsServers over churning registries at a fixed interval
        # while instant queries run against the TSDB. Contract: zero
        # scrape failures and a bounded TSDB (series count stable once
        # the fleet's label space is discovered)
        mon_targets = int(os.environ.get("BENCH_MONITOR_TARGETS", "5"))
        mon_seconds = float(os.environ.get("BENCH_MONITOR_SECONDS", "10"))
        mon_interval = float(os.environ.get("BENCH_MONITOR_INTERVAL", "1.0"))
        r = run_monitor_bench(mon_targets, mon_seconds, mon_interval)
        print(f"bench[monitor]: {r}", file=sys.stderr, flush=True)
        extras["monitor_scrape_p99_ms"] = round(r.scrape_p99_ms, 2)
        extras["monitor_samples_per_sec"] = round(r.samples_per_sec, 1)
        extras["monitor_query_p99_ms"] = round(r.query_p99_ms, 3)
        extras["monitor_tsdb_series"] = r.tsdb_series
        extras["monitor_tsdb_samples"] = r.tsdb_samples
        extras["monitor_scrape_failures"] = r.scrape_failures
        if r.scrape_failures:
            RESULT["error"] = (
                f"monitor bench: {r.scrape_failures} scrape failures over "
                f"{r.scrapes} rounds against a healthy fleet")
        elif not r.series_stable:
            RESULT["error"] = (
                f"monitor bench: TSDB series grew past the discovered "
                f"label space ({r.tsdb_series} series — per-scrape "
                f"series leak)")

    if "device" in configs:
        # steady-state compiled-solver throughput with device-resident
        # state: the solve alone, without the host plane.
        # Two shapes: P=4096 (the r3/r4 cross-round-comparable row) and
        # P=16384 (the deep-batch steady state after the round-5 op diet
        # removed the old P=8192 layout cliff).
        from kubernetes_tpu.perf.harness import run_device_solve

        r = run_device_solve(min(n_nodes, 15000), batch_pods=4096)
        print(f"bench[device]: {r}", file=sys.stderr, flush=True)
        extras["device_solve_pods_per_sec"] = round(r.pods_per_sec, 1)
        extras["device_solve_ms"] = round(r.ms_per_solve, 2)
        rd = run_device_solve(min(n_nodes, 15000), batch_pods=16384, iters=8)
        print(f"bench[device]: {rd}", file=sys.stderr, flush=True)
        extras["device_solve_deep_pods_per_sec"] = round(rd.pods_per_sec, 1)
        extras["device_solve_deep_ms"] = round(rd.ms_per_solve, 2)
        # device perf regression gate (bench-side, on the real chip — the
        # CPU-mesh pytest floor cannot see TPU regressions). The 50k floor
        # is BASELINE.json's device bar; no chip number exists for today's
        # code yet (PERF.md), so it is a target, not a recorded rate.
        gate_floor = float(os.environ.get("BENCH_DEVICE_GATE", "50000"))
        extras["device_gate_floor_pods_per_sec"] = gate_floor
        extras["device_gate_ok"] = bool(rd.pods_per_sec >= gate_floor)
        if not extras["device_gate_ok"]:
            RESULT["error"] = (
                f"device solve regression: deep {rd.pods_per_sec:.0f} pods/s "
                f"< gate {gate_floor:.0f}")

    if "sharded" in configs:
        # multi-chip GSPMD path at 100k+ nodes: headline/gang/preemption
        # end-to-end with the node axis sharded across every device, plus a
        # sharded device-solve gate. The StateDB flush counters prove the
        # hot path never re-materializes full-cluster host arrays
        # (flush_full_total stays at the setup uploads), and shard_rows
        # shows the interleaved row addressing keeping occupancy balanced.
        from kubernetes_tpu.parallel.mesh import make_mesh
        from kubernetes_tpu.perf.harness import run_device_solve, \
            run_preemption

        mesh = make_mesh()
        sh_nodes = int(os.environ.get("BENCH_SHARDED_NODES", "100000"))
        sh_pods = int(os.environ.get("BENCH_SHARDED_PODS", "16384"))
        r = run_throughput(sh_nodes, sh_pods, node_kwargs={"zones": 3},
                           mesh=mesh)
        print(f"bench[sharded]: {r} | {r.sharding}", file=sys.stderr,
              flush=True)
        extras["sharded_nodes"] = sh_nodes
        extras["sharded_devices"] = mesh.size
        extras["sharded_pods_per_sec"] = round(r.pods_per_sec, 1)
        extras["sharded_vs_baseline"] = round(r.pods_per_sec / baseline, 2)
        extras["sharded_shard_rows"] = r.sharding["shard_rows"]
        extras["sharded_flush_rows_total"] = r.sharding["flush_rows_total"]
        extras["sharded_flush_transfers_total"] = \
            r.sharding["flush_transfers_total"]
        extras["sharded_flush_full_total"] = r.sharding["flush_full_total"]
        if r.scheduled < sh_pods:
            RESULT["error"] = (
                f"sharded bench: only {r.scheduled}/{sh_pods} pods bound")
        # incremental flushes must scatter dirty rows, never re-upload the
        # cluster: full uploads are only legal during node registration
        elif r.sharding["flush_full_total"] > 4:
            RESULT["error"] = (
                f"sharded bench: {r.sharding['flush_full_total']} "
                "full-cluster host uploads on the hot path (dirty-row "
                "scatter flush regressed)")

        sh_gang_pods = int(os.environ.get("BENCH_SHARDED_GANG_PODS", "8192"))
        sh_gang_pods -= sh_gang_pods % 8
        rg = run_throughput(sh_nodes, sh_gang_pods,
                            node_kwargs={"zones": 3},
                            pod_kwargs={"gang_size": 8}, mesh=mesh)
        print(f"bench[sharded/gang]: {rg}", file=sys.stderr, flush=True)
        extras["sharded_gang_pods_per_sec"] = round(rg.pods_per_sec, 1)
        gang_stats = rg.metrics.get("gang", {})
        extras["sharded_gang_groups_placed"] = gang_stats.get("placed", 0)
        extras["sharded_gang_groups_reverted"] = gang_stats.get("reverted", 0)
        settled = gang_stats.get("placed", 0) + gang_stats.get("reverted", 0)
        if settled < sh_gang_pods // 8 and "error" not in RESULT:
            RESULT["error"] = (
                f"sharded gang: only {settled}/{sh_gang_pods // 8} "
                "groups settled")

        sh_pre = int(os.environ.get("BENCH_SHARDED_PREEMPT_NODES", "512"))
        rp = run_preemption(sh_pre, mesh=mesh)
        print(f"bench[sharded/preemption]: {rp}", file=sys.stderr, flush=True)
        extras["sharded_preemption_latency_ms"] = \
            round(rp.preemption_latency_ms, 1)
        extras["sharded_preemption_victims"] = rp.victims
        if rp.bound_wave < rp.wave and "error" not in RESULT:
            RESULT["error"] = (
                f"sharded preemption: only {rp.bound_wave}/{rp.wave} "
                "high-priority pods landed")

        sh_dev_pods = int(os.environ.get("BENCH_SHARDED_DEVICE_PODS", "4096"))
        rd = run_device_solve(sh_nodes, batch_pods=sh_dev_pods, iters=8,
                              mesh=mesh)
        print(f"bench[sharded/device]: {rd}", file=sys.stderr, flush=True)
        extras["sharded_device_pods_per_sec"] = round(rd.pods_per_sec, 1)
        extras["sharded_device_solve_ms"] = round(rd.ms_per_solve, 2)
        # the sharded device gate: at 100k+ nodes on real chips the sharded
        # program must beat the single-chip N ceiling's economics; CPU CI
        # disables it (BENCH_SHARDED_GATE=0 in --smoke)
        sh_gate = float(os.environ.get("BENCH_SHARDED_GATE", "50000"))
        extras["sharded_device_gate_floor_pods_per_sec"] = sh_gate
        extras["sharded_device_gate_ok"] = \
            bool(sh_gate <= 0 or rd.pods_per_sec >= sh_gate)
        if not extras["sharded_device_gate_ok"] and "error" not in RESULT:
            RESULT["error"] = (
                f"sharded device solve: {rd.pods_per_sec:.0f} pods/s "
                f"< gate {sh_gate:.0f} at N={sh_nodes}")

    if RESULT["value"] is None and extras:
        # headline config not selected: promote the first metric actually
        # run so a filtered invocation is distinguishable from a failed one
        gang_keys = [k for k in extras
                     if k.startswith("gang_") and k.endswith("_pods_per_sec")]
        for key in ("interpod_5k_pods_per_sec", "spread_15k_pods_per_sec",
                    "sharded_pods_per_sec", "solversvc_agg_pods_per_sec",
                    *gang_keys):
            if key in extras:
                RESULT["metric"] = key
                RESULT["value"] = extras[key]
                RESULT["vs_baseline"] = round(extras[key] / baseline, 2)
                break
    if trace_out:
        from kubernetes_tpu.obs.tracing import TRACER

        with open(trace_out, "w", encoding="utf-8") as f:
            f.write(TRACER.to_chrome())
        extras["trace_out"] = trace_out
        print(f"bench: wrote Chrome trace ({len(TRACER.finished())} "
              f"spans) to {trace_out}", file=sys.stderr, flush=True)
    if profile:
        from kubernetes_tpu.obs import profiling

        profiling.PROFILER.stop()
        with open(profile_out, "w", encoding="utf-8") as f:
            f.write(profiling.PROFILER.profile_text())
        extras["profile_out"] = profile_out
        extras["profile_samples"] = profiling.PROFILER.sampler.sample_count
        extras["profile_compile_variants"] = \
            profiling.COMPILES.totals()["variants"]
        print(f"bench: wrote collapsed stacks "
              f"({extras['profile_samples']} samples) to {profile_out}",
              file=sys.stderr, flush=True)

    RESULT["extras"] = extras
    print(json.dumps(RESULT), flush=True)


if __name__ == "__main__":
    main()
