"""Continuous profiling plane (obs/profiling.py): sampler determinism
under a ManualClock, stage-thread attribution through the staged
pipeline, per-variant compile accounting, the CPU-fallback device-memory
monitor, the /debug/pprof HTTP surface, a sampler overhead guard, and
the tier-1 `bench.py --smoke --profile` RESULT.bottleneck gate."""

import asyncio
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import pytest

from kubernetes_tpu.obs.metrics import Registry
from kubernetes_tpu.obs.profiling import (
    COMPILES,
    CompileRegistry,
    DeviceMemoryMonitor,
    ProfilingPlane,
    SamplingProfiler,
    bottleneck_report,
    record_readback,
)
from kubernetes_tpu.utils.clock import ManualClock


def fetch(url, timeout=5):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode(), r.headers.get("Content-Type")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(), e.headers.get("Content-Type")


async def afetch(url):
    return await asyncio.get_running_loop().run_in_executor(
        None, fetch, url)


class parked_thread:
    """A named thread parked on an Event: sample_once excludes its own
    CALLING thread (the daemon's walk never profiles itself), so direct
    deterministic calls need another thread to attribute."""

    def __init__(self, name="ktpu-test-parked"):
        self.name = name
        self._gate = threading.Event()
        self._thread = threading.Thread(
            target=self._gate.wait, args=(30.0,), name=name, daemon=True)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._gate.set()
        self._thread.join(2.0)


# ---- sampler: deterministic windows under ManualClock ----


def test_sampler_window_determinism_manual_clock():
    """sample_once stamps the injected clock; collapsed(seconds=, now=)
    selects exactly the samples inside the trailing window."""
    clock = ManualClock(100.0)
    prof = SamplingProfiler(interval_s=1.0, ring_s=60.0,
                            registry=Registry(), clock=clock)
    with parked_thread("ktpu-test-window") as park:
        for i in range(10):
            clock.set(100.0 + i)
            prof.sample_once()
    assert prof.sample_count == 10

    def count(text, thread):
        return sum(int(ln.rsplit(" ", 1)[1])
                   for ln in text.splitlines()
                   if ln.startswith(thread))

    # whole ring: the parked thread appears in all 10 samples
    assert count(prof.collapsed(now=109.0), park.name) == 10
    # trailing 4.5s at t=109 selects stamps {105..109} only
    assert count(prof.collapsed(seconds=4.5, now=109.0), park.name) == 5
    # a trailing window past every stamp is empty
    assert prof.collapsed(seconds=1.0, now=200.0) == ""
    # byte-stable output: same ring, same text
    assert prof.collapsed(now=109.0) == prof.collapsed(now=109.0)


def test_sampler_excludes_itself_and_names_threads():
    """The sampler's own walk never appears; a named parked thread is
    attributed under its thread name."""
    clock = ManualClock(0.0)
    prof = SamplingProfiler(interval_s=1.0, registry=Registry(),
                            clock=clock)
    gate = threading.Event()

    def parked():
        gate.wait(10.0)

    t = threading.Thread(target=parked, name="ktpu-test-parked",
                         daemon=True)
    t.start()
    try:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            stacks = prof.sample_once(now=1.0)
            if "ktpu-test-parked" in stacks:
                break
        assert "ktpu-test-parked" in stacks
        assert "parked" in stacks["ktpu-test-parked"]
        text = prof.collapsed()
        assert "ktpu-test-parked;" in text
        # the walk runs on the calling thread here, but the ring must
        # never contain the sampler daemon's own name
        assert "ktpu-profiler-sample" not in text
    finally:
        gate.set()
        t.join(2.0)


def test_sampler_thread_start_stop_idempotent():
    prof = SamplingProfiler(interval_s=0.005, registry=Registry())
    prof.start()
    prof.start()  # no second thread
    assert prof.running
    deadline = time.monotonic() + 5.0
    while prof.sample_count < 3 and time.monotonic() < deadline:
        time.sleep(0.01)
    prof.stop()
    assert not prof.running
    assert prof.sample_count >= 3
    prof.stop()  # idempotent


# ---- stage-thread attribution through the staged pipeline ----


def test_stage_thread_attribution():
    """The collapsed profile joins the StagedPipeline's named stage
    threads: after a staged schedule, one sample attributes
    ktpu-dispatch-stage / ktpu-settle-stage / ktpu-commit-stage."""
    from kubernetes_tpu.apiserver.store import ObjectStore
    from kubernetes_tpu.perf.fixtures import make_nodes, make_pods
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.state import Capacities

    async def run():
        store = ObjectStore()
        for node in make_nodes(4, cpu="16", memory="32Gi"):
            store.create(node)
        sched = Scheduler(store, caps=Capacities(num_nodes=16,
                                                 batch_pods=8))
        assert sched._staged is not None
        await sched.start()
        for pod in make_pods(8, cpu="100m", memory="64Mi"):
            store.create(pod)
        await asyncio.sleep(0)
        done = 0
        for _ in range(100):
            done += await sched.schedule_pending(wait=0.1)
            if done >= 8:
                break
        assert done >= 8
        prof = SamplingProfiler(interval_s=1.0, registry=Registry(),
                                clock=ManualClock(0.0))
        stacks = prof.sample_once(now=1.0)
        for stage in ("ktpu-dispatch-stage", "ktpu-settle-stage",
                      "ktpu-commit-stage"):
            assert stage in stacks, (stage, sorted(stacks))
            # parked stage threads fold to their stage loop frames
            assert "pipeline.py" in stacks[stage], stacks[stage]
        sched.stop()

    asyncio.run(run())


# ---- compile registry: per-variant accounting ----


def test_compile_registry_two_batchflags_variants():
    """Two BatchFlags gate sets -> two registry variants, each with
    compile seconds and (CPU backend) cost_analysis flops/bytes."""
    import jax
    import jax.numpy as jnp

    from kubernetes_tpu.ops.solver import BatchFlags
    from kubernetes_tpu.scheduler.driver import Scheduler

    import dataclasses

    all_off = {f.name: False for f in dataclasses.fields(BatchFlags)}
    base = BatchFlags(**all_off)
    gated = BatchFlags(**{**all_off, "ipa": True, "explain": True})
    k_base = Scheduler._variant_key(base)
    k_gated = Scheduler._variant_key(gated)
    assert k_base == "baseline"
    assert k_gated == "ipa+explain"

    reg = CompileRegistry(registry=Registry())
    reg.cost_analysis_enabled = True
    f1 = reg.instrument(k_base, jax.jit(lambda x: x * 2.0))
    f2 = reg.instrument(k_gated, jax.jit(lambda x: (x + 1.0).sum()))
    x = jnp.arange(8, dtype=jnp.float32)
    assert f1(x).shape == (8,)
    f1(x)  # cache hit: no re-compile
    assert float(f2(x)) == 36.0

    snap = reg.snapshot()
    assert set(snap) == {k_base, k_gated}
    assert snap[k_base]["calls"] == 2
    assert snap[k_gated]["calls"] == 1
    for rec in snap.values():
        assert rec["compile_seconds"] > 0.0
        assert rec["first_call_seconds"] > 0.0
        # CPU XLA provides cost_analysis through the AOT path
        assert rec["cost_analysis"] is True
        assert rec["flops"] is not None and rec["flops"] > 0.0
    totals = reg.totals()
    assert totals["variants"] == 2
    assert totals["compile_seconds_total"] > 0.0


def test_compile_registry_aot_fallback_is_safe():
    """A callable that can't AOT-lower still profiles (wall fallback)
    and keeps returning correct results."""
    reg = CompileRegistry(registry=Registry())
    reg.cost_analysis_enabled = True

    def plain(x):  # no .lower attribute -> _try_aot returns None
        return x + 1

    f = reg.instrument("plainfn", plain)
    assert f(1) == 2
    assert f(2) == 3
    rec = reg.snapshot()["plainfn"]
    assert rec["calls"] == 2
    assert rec["cost_analysis"] is False
    assert rec["compile_seconds"] > 0.0  # first-call wall fallback


def test_scheduler_variant_cache_feeds_global_registry():
    """A real scheduler drain registers its solver variant in the
    process-global COMPILES registry under the BatchFlags gate name."""
    from kubernetes_tpu.apiserver.store import ObjectStore
    from kubernetes_tpu.perf.fixtures import make_nodes, make_pods
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.state import Capacities

    async def run():
        store = ObjectStore()
        for node in make_nodes(2, cpu="16", memory="32Gi"):
            store.create(node)
        sched = Scheduler(store, caps=Capacities(num_nodes=8,
                                                 batch_pods=4))
        await sched.start()
        for pod in make_pods(4, cpu="100m", memory="64Mi"):
            store.create(pod)
        await asyncio.sleep(0)
        done = 0
        for _ in range(100):
            done += await sched.schedule_pending(wait=0.1)
            if done >= 4:
                break
        assert done >= 4
        sched.stop()

    asyncio.run(run())
    snap = COMPILES.snapshot()
    assert snap, "scheduler drain registered no compile variants"
    assert any(rec["calls"] >= 1 and rec["compile_seconds"] > 0.0
               for rec in snap.values()), snap


# ---- device memory: CPU fallback accounts StateDB blobs ----


def test_device_memory_cpu_fallback_statedb_accounting():
    import jax

    from kubernetes_tpu.state import Capacities
    from kubernetes_tpu.state.statedb import StateDB

    db = StateDB(Capacities(num_nodes=16, batch_pods=8))
    db.flush()
    assert db._device is not None

    r = Registry()
    mon = DeviceMemoryMonitor(registry=r)
    snap = mon.collect([db])
    expect = sum(int(leaf.nbytes) for leaf in
                 jax.tree_util.tree_leaves(db._device))
    assert expect > 0
    assert snap["statedb_bytes_total"] == expect
    assert sum(snap["statedb_bytes_by_dtype"].values()) == expect
    assert sum(snap["statedb_bytes_by_shape"].values()) == expect
    for dt, nbytes in snap["statedb_bytes_by_dtype"].items():
        assert r.get("device_memory_statedb_bytes") \
                .labels(dt).value == nbytes
    # the CPU backend reports no memory_stats: no limit series means the
    # DeviceMemoryHigh peak/limit join is empty — it can never fire here
    assert snap["backend_supported"] is False
    assert "device_memory_bytes_limit{" not in r.render()


def test_device_memory_scrape_opens_no_backend():
    """A scrape in a process that holds no device arrays (an apiserver
    worker, say) must not initialize a JAX backend: on a chip machine
    that would open the chip another process owns."""
    code = (
        "import jax\n"
        "from jax._src import xla_bridge\n"
        "from kubernetes_tpu.obs.metrics import Registry\n"
        "from kubernetes_tpu.obs.profiling import DeviceMemoryMonitor\n"
        "snap = DeviceMemoryMonitor(registry=Registry()).collect()\n"
        "assert snap['devices'] == {}, snap\n"
        "assert not xla_bridge.backends_are_initialized()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(Path(__file__).resolve().parent.parent))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


def test_statedb_flush_and_readback_transfer_counters():
    """flush() charges statedb_flush_bytes_total; record_readback
    charges device_readback_bytes_total."""
    import numpy as np

    from kubernetes_tpu.obs import REGISTRY
    from kubernetes_tpu.state import Capacities
    from kubernetes_tpu.state.statedb import StateDB

    db = StateDB(Capacities(num_nodes=16, batch_pods=8))
    before = db.flush_bytes_total
    db.flush()
    assert db.flush_bytes_total > before

    fam = REGISTRY.get("device_readback_bytes_total")
    base = fam.labels().value
    arr = np.zeros((4, 4), dtype=np.float32)
    assert record_readback(arr, arr) == 2 * arr.nbytes
    assert fam.labels().value == base + 2 * arr.nbytes
    assert record_readback() == 0


# ---- bottleneck report ----


def test_bottleneck_report_shape():
    rep = bottleneck_report(
        "headline",
        {"dispatch": 0.1, "settle": 0.6, "commit": 0.3},
        stage_busy_frac={"settle": 0.61},
        queue_depth_max={"settle": 4},
        transfer_bytes={"flush_bytes": 1024},
        compile_totals={"variants": 2},
        wall_s=1.0)
    assert rep["dominant"] == "settle"
    assert rep["cost_fractions"]["settle"] == 0.6
    assert list(rep["costs_seconds"]) == ["settle", "commit", "dispatch"]
    assert "readback" in rep["hint"]
    assert bottleneck_report("x", {})["dominant"] == "unknown"


# ---- HTTP surface: /debug/pprof + /debug/profile/device ----


def test_pprof_http_round_trip():
    """GET /debug/pprof/profile?seconds=N serves the ring as collapsed
    text without blocking; /debug/profile/device opens a capture window
    and reports busy (409) while one is open."""
    from kubernetes_tpu.obs.http import ObsServer

    async def run(tmp):
        clock = ManualClock(100.0)
        plane = ProfilingPlane(registry=Registry(), clock=clock)
        plane.capture.artifact_root = tmp
        with parked_thread("ktpu-test-pprof") as park:
            for i in range(6):
                plane.sampler.sample_once(now=100.0 + i)
        clock.set(105.0)
        srv = ObsServer(profiler=plane)
        await srv.start()
        try:
            status, body, ctype = await afetch(
                srv.url + "/debug/pprof/profile")
            assert status == 200 and ctype.startswith("text/plain")
            assert f"{park.name};" in body
            # seconds=2.5 at now=105 keeps stamps {103,104,105}
            status, body, _ = await afetch(
                srv.url + "/debug/pprof/profile?seconds=2.5")
            assert status == 200
            got = sum(int(ln.rsplit(" ", 1)[1])
                      for ln in body.splitlines()
                      if ln.startswith(park.name))
            assert got == 3

            status, body, _ = await afetch(
                srv.url + "/debug/profile/device?seconds=0.3")
            assert status == 200
            first = json.loads(body)
            assert first["status"] == "capturing"
            assert first["artifact_dir"].startswith(tmp)
            status, body, _ = await afetch(
                srv.url + "/debug/profile/device?seconds=0.3")
            assert status == 409
            assert json.loads(body)["status"] == "busy"
            plane.capture._stop.set()  # close the window promptly
            # stop_trace() serializes the trace; generous bound — the
            # in-process jit cache can make the write slow under load
            plane.capture.join(60.0)
            rec = plane.capture.captures[0]
            assert rec["status"] == "done", rec
            assert os.path.isdir(rec["artifact_dir"])
        finally:
            await srv.stop()

    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        asyncio.run(run(tmp))


def test_scheduler_server_serves_pprof_and_memory_gauges():
    """The scheduler's obs mux serves /debug/pprof (query string intact
    through _handle) and /metrics carries the device-memory and pipeline
    gauges refreshed at scrape time."""
    from kubernetes_tpu.apiserver.store import ObjectStore
    from kubernetes_tpu.perf.fixtures import make_nodes, make_pods
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.scheduler.server import SchedulerServer
    from kubernetes_tpu.state import Capacities

    async def run():
        store = ObjectStore()
        for node in make_nodes(2, cpu="16", memory="32Gi"):
            store.create(node)
        sched = Scheduler(store, caps=Capacities(num_nodes=8,
                                                 batch_pods=4))
        await sched.start()
        for pod in make_pods(4, cpu="100m", memory="64Mi"):
            store.create(pod)
        await asyncio.sleep(0)
        done = 0
        for _ in range(100):
            done += await sched.schedule_pending(wait=0.1)
            if done >= 4:
                break
        assert done >= 4
        srv = SchedulerServer(sched)
        await srv.start()
        try:
            status, body, ctype = await afetch(
                srv.url + "/debug/pprof/profile?seconds=60")
            assert status == 200, body[:200]
            assert ctype.startswith("text/plain")
            status, text, _ = await afetch(srv.url + "/metrics")
            assert status == 200
            assert "device_memory_statedb_bytes{" in text
            if sched._staged is not None:
                assert 'scheduler_pipeline_stage_busy_frac{' \
                    'stage="settle"}' in text
                assert "scheduler_pipeline_depth" in text
        finally:
            await srv.stop()
            sched.stop()

    asyncio.run(run())


# ---- overhead guard ----


def test_sampler_overhead_bounded():
    """A 10ms sampler must not halve host throughput: loose 2x guard so
    CI noise can't flake it; the real number lands in PERF.md."""

    def spin(seconds):
        n = 0
        deadline = time.perf_counter() + seconds
        while time.perf_counter() < deadline:
            n += 1
        return n

    spin(0.05)  # warm
    base = spin(0.4)
    prof = SamplingProfiler(interval_s=0.01, registry=Registry())
    prof.start()
    try:
        with_prof = spin(0.4)
    finally:
        prof.stop()
    assert prof.sample_count >= 5
    assert with_prof >= 0.5 * base, (with_prof, base)
    # the sampler publishes its own walk cost for the PERF.md record
    assert prof._m_walk.labels().count >= 5


# ---- tier-1 gate: bench --smoke --profile emits RESULT.bottleneck ----


def test_bench_smoke_profile_mode(tmp_path):
    """bench.py --smoke --profile must emit RESULT.bottleneck naming a
    dominant stage for headline + defrag and write the collapsed-stack
    artifact; drift in the profiling wiring breaks this, not a nightly."""
    repo = Path(__file__).resolve().parents[1]
    out = tmp_path / "bench_profile.collapsed"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["BENCH_CONFIGS"] = "headline,defrag"
    env["BENCH_NODES"] = "64"
    env["BENCH_PODS"] = "128"
    env["BENCH_PROFILE_OUT"] = str(out)
    proc = subprocess.run(
        [sys.executable, "bench.py", "--smoke", "--profile"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.strip()][-1]
    result = json.loads(line)
    assert "error" not in result, result

    bn = result["bottleneck"]
    head = bn["headline"]
    assert head["dominant"] in ("dispatch", "settle", "commit", "apply",
                                "encode", "solve")
    assert head["costs_seconds"][head["dominant"]] >= 0.0
    assert abs(sum(head["cost_fractions"].values()) - 1.0) < 0.01
    assert head["transfer_bytes"]["flush_bytes"] > 0
    assert head["compile"]["variants"] >= 1
    assert head["compile"]["compile_seconds_total"] > 0.0

    defrag = bn["defrag"]
    assert defrag["dominant"] in ("probe_solve", "plan_and_execute")
    assert defrag["costs_seconds"]["probe_solve"] > 0.0

    extras = result["extras"]
    assert extras["profile_samples"] >= 1
    assert extras["profile_out"] == str(out)
    text = out.read_text()
    assert text.strip(), "collapsed artifact is empty"
    for ln in text.strip().splitlines():
        assert ln.rsplit(" ", 1)[1].isdigit(), ln


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))
