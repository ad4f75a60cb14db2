"""Continuous profiling & device introspection: where the time and the
bytes go, answered by the process itself.

The metrics plane (obs/metrics.py) answers *what happened*, the tracing
plane (obs/tracing.py) *in what order*; this plane answers *where* — the
question the first chip session asks before anything else. Four pillars:

  * `SamplingProfiler` — a low-overhead daemon thread that walks
    `sys._current_frames()` on a fixed interval and folds each thread's
    stack into collapsed flamegraph lines (`thread;frame;...;leaf N`).
    Because the StagedPipeline names its stage threads
    (`ktpu-dispatch-stage`/`ktpu-settle-stage`/`ktpu-commit-stage`) and
    the fan-out shards name theirs, the per-thread attribution joins
    pipeline stages for free. An always-on ring keeps the recent window
    so `/debug/pprof/profile?seconds=N` serves the trailing N seconds
    without blocking the obs handler (lint R1: handlers never park).
  * `CompileRegistry` — per-jit-cache-entry compile accounting for the
    solver variant cache (scheduler/driver.py `_get_schedule_fn`):
    compile seconds from `jax.monitoring`'s backend-compile events with
    a first-call wall fallback, plus `Compiled.cost_analysis()` flops /
    bytes-accessed where the backend provides it (AOT lower+compile,
    gated — any failure falls back to the plain jit callable).
  * `DeviceMemoryMonitor` — `device.memory_stats()` high-water gauges
    with a graceful CPU-backend fallback (memory_stats() is None there)
    that accounts the StateDB's device blob buffers by dtype/shape, so
    the CPU harness still sees what WOULD sit in HBM.
  * `DeviceTraceCapture` — on-demand `jax.profiler.trace` windows
    (`/debug/profile/device?seconds=N` -> artifact dir) so the first
    chip session is a curl, not a code change.

Thread discipline: the sampler and capture threads never touch the
event loop (lint R1 tier-3) and pace themselves with Event.wait, never
time.sleep (tier-2).
"""

from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque

from kubernetes_tpu.obs import metrics as _metrics
from kubernetes_tpu.utils.clock import Clock, SYSTEM_CLOCK

# frames deeper than this fold into the cap (runaway recursion guard)
MAX_STACK_DEPTH = 64


def _fold_stack(frame, limit: int = MAX_STACK_DEPTH) -> str:
    """Leaf frame -> one interned root-first `file.py:fn;file.py:fn;...`
    string. Interning collapses the ring's storage to one copy per
    distinct stack, which is what makes an always-on ring affordable."""
    entries: list[str] = []
    f = frame
    while f is not None and len(entries) < limit:
        code = f.f_code
        entries.append(f"{os.path.basename(code.co_filename)}"
                       f":{code.co_name}")
        f = f.f_back
    entries.reverse()
    return sys.intern(";".join(entries))


class SamplingProfiler:
    """Walk `sys._current_frames()` on an interval; keep a ring of
    (timestamp, {thread_name: folded_stack}) samples.

    The walk itself runs under the GIL so it is a consistent snapshot;
    the sampler's own thread is excluded (its stack is always the walk).
    `clock` stamps ring entries — tests inject a ManualClock and call
    `sample_once(...)` directly for deterministic windows; the real
    thread paces with Event.wait so stop() is prompt and lint R1's
    time.sleep audit stays clean."""

    def __init__(self, interval_s: float = 0.01,
                 ring_s: float = 300.0,
                 registry: _metrics.Registry | None = None,
                 clock: Clock | None = None):
        self.interval_s = float(interval_s)
        self.ring_s = float(ring_s)
        self.clock = clock or SYSTEM_CLOCK
        maxlen = max(16, int(self.ring_s / max(self.interval_s, 1e-4)))
        self._ring: deque = deque(maxlen=maxlen)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        r = registry or _metrics.REGISTRY
        self._m_samples = r.counter(
            "profiling_samples_total",
            "Stack-walk samples folded into the profile ring.")
        self._m_walk = r.histogram(
            "profiling_sample_walk_seconds",
            "Cost of one sys._current_frames() walk+fold (the sampler's "
            "own overhead).",
            buckets=_metrics.exponential_buckets(1e-5, 4.0, 8))

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def start(self) -> None:
        if self.running:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._sample_loop, name="ktpu-profiler-sample",
            daemon=True)
        self._thread.start()

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout)
        self._thread = None

    def _sample_loop(self) -> None:
        # off-loop thread: paces on Event.wait (never time.sleep, never
        # the event loop) so stop() interrupts a pending interval
        while not self._stop.wait(self.interval_s):
            self.sample_once()

    def sample_once(self, now: float | None = None) -> dict[str, str]:
        """One walk: {thread_name: folded_stack}, appended to the ring
        stamped `now` (default: the injected clock)."""
        t0 = time.perf_counter()
        if now is None:
            now = self.clock.now()
        names = {t.ident: t.name for t in threading.enumerate()}
        me = threading.get_ident()
        stacks: dict[str, str] = {}
        for tid, frame in sys._current_frames().items():
            if tid == me:
                continue
            stacks[names.get(tid, f"tid-{tid}")] = _fold_stack(frame)
        with self._lock:
            self._ring.append((now, stacks))
        self._m_samples.inc()
        self._m_walk.observe(time.perf_counter() - t0)
        return stacks

    @property
    def sample_count(self) -> int:
        with self._lock:
            return len(self._ring)

    def collapsed(self, seconds: float | None = None,
                  now: float | None = None) -> str:
        """Collapsed flamegraph text (`thread;frame;...;leaf count`) for
        the trailing `seconds` window (None: the whole ring). Sorted for
        byte-stable output under a fixed sample set."""
        if now is None:
            now = self.clock.now()
        cutoff = None if seconds is None else now - float(seconds)
        with self._lock:
            ring = list(self._ring)
        counts: dict[str, int] = {}
        for ts, stacks in ring:
            if cutoff is not None and ts < cutoff:
                continue
            for tname, stack in stacks.items():
                key = f"{tname};{stack}" if stack else tname
                counts[key] = counts.get(key, 0) + 1
        lines = [f"{k} {v}" for k, v in sorted(counts.items())]
        return "\n".join(lines) + ("\n" if lines else "")


def _new_compile_record(variant: str) -> dict:
    return {"variant": variant, "calls": 0, "compile_seconds": 0.0,
            "compile_events": 0, "first_call_seconds": None,
            "flops": None, "bytes_accessed": None,
            "cost_analysis": False}


class CompileRegistry:
    """Per-variant compile accounting for jit cache entries.

    `instrument(variant, fn)` wraps a FRESH jit callable (a cache miss in
    the solver variant cache): the first call is timed wall-clock and
    attributed backend-compile seconds via a `jax.monitoring` duration
    listener (thread-local attribution — concurrent first calls on
    different variants don't cross-credit); subsequent calls are a
    counter bump and a dict hit. With `cost_analysis_enabled` the first
    call AOT-lowers and compiles so `Compiled.cost_analysis()` flops /
    bytes-accessed land in the record — any AOT or runtime mismatch
    falls back to the original jit callable permanently, so profiling
    can never take the solve path down."""

    def __init__(self, registry: _metrics.Registry | None = None):
        r = registry or _metrics.REGISTRY
        self._m_compile = r.histogram(
            "compile_seconds",
            "First-call compile cost per solver variant (BatchFlags).",
            labels=("variant",),
            buckets=_metrics.exponential_buckets(0.01, 4.0, 10))
        self._m_variants = r.gauge(
            "profiling_compile_variants",
            "Distinct jit variants seen by the compile registry.")
        self._lock = threading.Lock()
        self._variants: dict[str, dict] = {}
        self._local = threading.local()
        self._listener_on = False
        self.cost_analysis_enabled = False

    def _install_listener(self) -> None:
        if self._listener_on:
            return
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(
                self._on_event)
            self._listener_on = True
        except Exception:
            self._listener_on = True  # no jax: wall fallback only

    def _on_event(self, event: str, duration: float, **kw) -> None:
        # jax fires this for every timed event; only backend compiles of
        # the variant currently first-calling on THIS thread are ours
        variant = getattr(self._local, "variant", None)
        if variant is None or "backend_compile" not in event:
            return
        with self._lock:
            rec = self._variants.get(variant)
            if rec is not None:
                rec["compile_seconds"] += float(duration)
                rec["compile_events"] += 1

    def instrument(self, variant: str, fn):
        """Wrap `fn` (a fresh jit callable) with first-call compile
        accounting under `variant`."""
        self._install_listener()
        with self._lock:
            rec = self._variants.setdefault(
                variant, _new_compile_record(variant))
            self._m_variants.set(len(self._variants))
        state = {"fn": fn, "pending": True}
        gate = threading.Lock()

        def profiled_call(*args, **kwargs):
            if state["pending"]:
                with gate:
                    if state["pending"]:
                        return self._first_call(rec, state, variant,
                                                args, kwargs)
            rec["calls"] += 1
            return state["fn"](*args, **kwargs)

        # the jit surface callers inspect (HLO pins lower().as_text())
        # stays reachable through the wrapper
        lower = getattr(fn, "lower", None)
        if lower is not None:
            profiled_call.lower = lower
        return profiled_call

    def _first_call(self, rec, state, variant, args, kwargs):
        self._local.variant = variant
        t0 = time.perf_counter()
        try:
            if self.cost_analysis_enabled:
                aot = self._try_aot(rec, state["fn"], args, kwargs)
                if aot is not None:
                    state["fn"] = aot
            out = state["fn"](*args, **kwargs)
        finally:
            dt = time.perf_counter() - t0
            self._local.variant = None
            state["pending"] = False
            with self._lock:
                rec["calls"] += 1
                rec["first_call_seconds"] = dt
                if not rec["compile_events"]:
                    # no backend events (listener missing / cache hit
                    # from a prior process): first-call wall is the
                    # best available bound
                    rec["compile_seconds"] = dt
            self._m_compile.labels(variant).observe(dt)
        return out

    def _try_aot(self, rec, fn, args, kwargs):
        """AOT lower+compile so cost_analysis() is available. Returns a
        callable running the compiled executable (falling back to the
        jit original on any runtime mismatch), or None when AOT itself
        fails — profiling never changes solve-path behavior."""
        try:
            compiled = fn.lower(*args, **kwargs).compile()
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            rec["flops"] = float(cost.get("flops", 0.0))
            rec["bytes_accessed"] = float(cost.get("bytes accessed", 0.0))
            rec["cost_analysis"] = True
        except Exception:
            return None

        def run_compiled(*a, **k):
            try:
                return compiled(*a, **k)
            except Exception:
                # signature drift (e.g. a victims pytree appearing):
                # fall back to the retracing jit original
                return fn(*a, **k)

        return run_compiled

    def snapshot(self) -> dict[str, dict]:
        with self._lock:
            return {k: dict(v) for k, v in self._variants.items()}

    def totals(self) -> dict:
        with self._lock:
            recs = [dict(v) for v in self._variants.values()]
        return {
            "variants": len(recs),
            "compile_seconds_total": round(
                sum(r["compile_seconds"] for r in recs), 6),
            "flops_total": sum(r["flops"] or 0.0 for r in recs),
            "bytes_accessed_total": sum(
                r["bytes_accessed"] or 0.0 for r in recs),
        }


class DeviceMemoryMonitor:
    """`device.memory_stats()` gauges with a local high-water, plus the
    CPU fallback: the CPU backend returns None there, so the monitor
    accounts the StateDB's device blob buffers by dtype/shape instead —
    the exact bytes that WOULD occupy HBM on a real chip.

    `device_memory_bytes_limit` is only exported when the backend
    reports one; the DeviceMemoryHigh alert divides peak by limit, and
    a missing limit series makes that join an empty vector — the alert
    can never fire on the CPU fallback by construction."""

    def __init__(self, registry: _metrics.Registry | None = None):
        r = registry or _metrics.REGISTRY
        self._g_in_use = r.gauge(
            "device_memory_bytes_in_use",
            "Live device allocation per device (memory_stats).",
            labels=("device",))
        self._g_limit = r.gauge(
            "device_memory_bytes_limit",
            "Backend-reported allocatable bytes per device; absent on "
            "backends without memory_stats (CPU).",
            labels=("device",))
        self._g_peak = r.gauge(
            "device_memory_peak_bytes_in_use",
            "High-water device allocation per device (max of backend "
            "peak and every observed in_use).",
            labels=("device",))
        self._g_blob = r.gauge(
            "device_memory_statedb_bytes",
            "CPU-fallback accounting: StateDB device blob bytes by "
            "dtype (what would sit in HBM).",
            labels=("dtype",))
        self._peaks: dict[str, float] = {}
        self.backend_supported: bool | None = None

    def collect(self, statedbs=()) -> dict:
        """Refresh the gauges (called at scrape time) and return the
        snapshot: backend stats per device where supported, StateDB
        blob accounting always."""
        # devices come from the arrays this process already holds, never
        # from jax.devices(): a scrape must not open a backend (and with
        # it the chip) in a process that has not
        devices: dict = {}
        jax = sys.modules.get("jax")
        if jax is not None:
            for db in statedbs:
                for leaf in jax.tree_util.tree_leaves(
                        getattr(db, "_device", None)):
                    if isinstance(leaf, jax.Array):
                        devices.update(dict.fromkeys(leaf.devices()))
        supported = False
        per_device: dict[str, dict] = {}
        for dev in devices:
            try:
                stats = dev.memory_stats()
            except Exception:
                stats = None
            if not stats:
                continue
            supported = True
            label = f"{dev.platform}:{dev.id}"
            in_use = float(stats.get("bytes_in_use", 0.0))
            peak = max(self._peaks.get(label, 0.0),
                       float(stats.get("peak_bytes_in_use", 0.0)),
                       in_use)
            self._peaks[label] = peak
            self._g_in_use.labels(label).set(in_use)
            self._g_peak.labels(label).set(peak)
            if "bytes_limit" in stats:
                self._g_limit.labels(label).set(
                    float(stats["bytes_limit"]))
            per_device[label] = dict(stats)
        self.backend_supported = supported
        by_dtype: dict[str, int] = {}
        by_shape: dict[str, int] = {}
        if jax is not None:
            for db in statedbs:
                tree = getattr(db, "_device", None)
                if tree is None:
                    continue
                for leaf in jax.tree_util.tree_leaves(tree):
                    nbytes = int(getattr(leaf, "nbytes", 0) or 0)
                    if not nbytes:
                        continue
                    dt = str(getattr(leaf, "dtype", "unknown"))
                    shape = tuple(getattr(leaf, "shape", ()))
                    by_dtype[dt] = by_dtype.get(dt, 0) + nbytes
                    skey = f"{dt}[{','.join(str(d) for d in shape)}]"
                    by_shape[skey] = by_shape.get(skey, 0) + nbytes
        for dt, nbytes in by_dtype.items():
            self._g_blob.labels(dt).set(nbytes)
        return {"backend_supported": supported,
                "devices": per_device,
                "statedb_bytes_by_dtype": by_dtype,
                "statedb_bytes_by_shape": by_shape,
                "statedb_bytes_total": sum(by_dtype.values())}


class DeviceTraceCapture:
    """On-demand `jax.profiler.trace` windows. `capture(seconds)` spawns
    a capture thread and returns immediately (the obs handler must not
    park, lint R1); one window at a time — a second request while one is
    open reports busy."""

    def __init__(self, artifact_root: str | None = None):
        import tempfile
        self.artifact_root = (
            artifact_root
            or os.environ.get("KTPU_PROFILE_DIR")
            or os.path.join(tempfile.gettempdir(), "ktpu-device-traces"))
        self._lock = threading.Lock()
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        self._seq = 0
        self.captures: list[dict] = []

    def capture(self, seconds: float) -> dict:
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return {"status": "busy",
                        "artifact_dir": self.captures[-1]["artifact_dir"]
                        if self.captures else None}
            self._seq += 1
            outdir = os.path.join(self.artifact_root,
                                  f"capture-{self._seq:04d}")
            rec = {"status": "capturing", "artifact_dir": outdir,
                   "seconds": float(seconds)}
            self.captures.append(rec)
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._capture_window,
                args=(outdir, float(seconds), rec),
                name="ktpu-profiler-device", daemon=True)
            self._thread.start()
            return dict(rec)

    def _capture_window(self, outdir: str, seconds: float,
                        rec: dict) -> None:
        # off-loop thread: Event.wait pacing, no asyncio (lint R1)
        try:
            import jax
            os.makedirs(outdir, exist_ok=True)
            jax.profiler.start_trace(outdir)
            try:
                self._stop.wait(seconds)
            finally:
                jax.profiler.stop_trace()
            rec["status"] = "done"
        except Exception as exc:
            rec["status"] = f"error: {exc}"

    def join(self, timeout: float = 30.0) -> None:
        t = self._thread
        if t is not None:
            t.join(timeout)


# host<->device transfer accounting: the settle-stage readback side.
# (The upload side rides the statedb_flush_* seams in state/statedb.py.)
_M_READBACK = _metrics.REGISTRY.counter(
    "device_readback_bytes_total",
    "Bytes materialized device->host (settle-stage np.asarray reads).")


def record_readback(*arrays) -> int:
    """Count a device->host materialization; returns the bytes added."""
    total = 0
    for arr in arrays:
        nbytes = getattr(arr, "nbytes", None)
        if nbytes:
            total += int(nbytes)
    if total:
        _M_READBACK.inc(total)
    return total


# process-global compile registry: the driver's variant cache feeds it
# whether or not a plane is started (records are cheap; cost analysis
# stays off until a plane enables it)
COMPILES = CompileRegistry()


class ProfilingPlane:
    """The facade a component hands to the obs mux: owns the sampler,
    device-memory monitor and capture windows, and fronts the process
    CompileRegistry."""

    def __init__(self, registry: _metrics.Registry | None = None,
                 clock: Clock | None = None,
                 interval_s: float = 0.01):
        self.sampler = SamplingProfiler(
            interval_s=interval_s, registry=registry, clock=clock)
        self.memory = DeviceMemoryMonitor(registry=registry)
        self.capture = DeviceTraceCapture()
        self.compiles = COMPILES

    @property
    def running(self) -> bool:
        return self.sampler.running

    def start(self, cost_analysis: bool = True) -> None:
        if cost_analysis:
            self.compiles.cost_analysis_enabled = True
        self.sampler.start()

    def stop(self) -> None:
        self.sampler.stop()

    def profile_text(self, seconds: float | None = None) -> str:
        return self.sampler.collapsed(seconds=seconds)

    def capture_device(self, seconds: float) -> dict:
        return self.capture.capture(seconds)


# the process-global plane (obs.metrics.REGISTRY position): components
# route /debug/pprof/* here; bench --profile starts/stops it
PROFILER = ProfilingPlane()
