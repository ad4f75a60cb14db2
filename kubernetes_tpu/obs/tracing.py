"""Dapper-style distributed tracing for the scheduling pipeline.

One pod's life — client POST → APF queue → encode → dispatch → solve →
settle → bind → kubelet Running — crosses an HTTP hop, four pipeline
threads, and the kubelet sync loop.  contextvars do not survive the
``ktpu-dispatch/settle/commit`` thread boundaries (each stage is a plain
worker thread fed by a queue), so propagation here is *explicit*:

- over HTTP as a W3C ``traceparent`` header
  (``00-{trace_id:32x}-{span_id:16x}-{01|00}``),
- across pipeline stages as a ``Span`` carried on the queue item
  (``_BatchWork.span``),
- across the bind boundary as a pod annotation
  (``trace.ktpu.io/context``) that the kubelet joins on sync.

Sampling is head-based: the root span decides (``KTPU_TRACE_SAMPLE``,
default 1%) and children inherit, so the headline path pays only the
coin-flip.  Unsampled spans are real objects with ``sampled=False`` —
callers never branch — but they are never recorded.

Finished spans land in a bounded ring served at ``/debug/traces`` on the
obs mux and exportable as JSON-lines or Chrome trace-event JSON
(Perfetto-loadable; one row per pipeline stage/thread).

Span lifecycle discipline is lint-enforced (R6 ``span-discipline``):
``start_span`` must be used as a context manager or ended in a
``finally``; ``begin_span`` is the sanctioned escape hatch for explicit
cross-thread handoff and is tracked in the tracer's open-span table so
orphans are still observable (``Tracer.open_spans``).

Stage spans on the profiler's clock: ``stage(...)`` times one scheduler
stage boundary as a ``jax.profiler.TraceAnnotation`` (a TraceMe, on the
host clock the device trace uses, so a ``/debug/profile/device`` capture
shows the stage rows beside the device's ops) and feeds the counters
that stage already had: a ``scheduler_phase_duration_seconds`` phase, a
pipeline busy total, and this module's retroactive Dapper child.
``watch_gc`` adds one process-wide ``gc.callbacks`` hook that names
collector pauses the same way (``ktpu.gc.gen1`` / ``ktpu.gc.gen2``).
With no profiler capture open a TraceMe costs well under a microsecond.
"""

from __future__ import annotations

import contextvars
import gc
import json
import os
import threading
import time
import weakref
from collections import deque
from dataclasses import dataclass

TRACE_ANNOTATION = "trace.ktpu.io/context"

# Stage rows every stitched trace is expected to carry (also the Chrome
# export's thread names).  Order is the pipeline order.
STAGE_TIDS = ("client", "apiserver", "encode", "dispatch", "settle",
              "commit", "kubelet")


@dataclass(frozen=True)
class SpanContext:
    """Immutable (trace_id, span_id, sampled) triple — the wire identity."""

    trace_id: str   # 32 lowercase hex chars
    span_id: str    # 16 lowercase hex chars
    sampled: bool

    def to_traceparent(self) -> str:
        return f"00-{self.trace_id}-{self.span_id}-" \
               f"{'01' if self.sampled else '00'}"


def parse_traceparent(value: str | None) -> SpanContext | None:
    """Parse a W3C traceparent header; None on any malformation."""
    if not value:
        return None
    parts = value.strip().split("-")
    if len(parts) != 4:
        return None
    version, trace_id, span_id, flags = parts
    if len(version) != 2 or len(trace_id) != 32 or len(span_id) != 16 \
            or len(flags) != 2:
        return None
    try:
        int(trace_id, 16), int(span_id, 16), int(flags, 16)
    except ValueError:
        return None
    if version == "ff" or int(trace_id, 16) == 0 or int(span_id, 16) == 0:
        return None
    return SpanContext(trace_id.lower(), span_id.lower(),
                       sampled=bool(int(flags, 16) & 1))


class Span:
    """One timed operation on one thread row.

    Use ``with tracer.start_span(...)`` for scoped spans; ``end()`` is
    idempotent so explicit-handoff paths can double up on safety nets.
    """

    __slots__ = ("_tracer", "name", "context", "parent_id", "tid",
                 "start_wall", "_start_perf", "attrs", "_ended")

    def __init__(self, tracer: "Tracer", name: str, context: SpanContext,
                 parent_id: str | None, tid: str,
                 attrs: dict | None = None):
        self._tracer = tracer
        self.name = name
        self.context = context
        self.parent_id = parent_id
        self.tid = tid
        self.start_wall = time.time()
        self._start_perf = time.perf_counter()
        self.attrs = attrs
        self._ended = False

    @property
    def sampled(self) -> bool:
        return self.context.sampled

    def set_attr(self, key: str, value) -> None:
        if not self.context.sampled:
            return
        if self.attrs is None:
            self.attrs = {}
        self.attrs[key] = value

    def child(self, name: str, tid: str | None = None) -> "Span":
        """Begin a child span (explicit handoff — caller must end it)."""
        return self._tracer.begin_span(name, parent=self.context,
                                       tid=tid or self.tid)

    def end(self, status: str = "ok") -> None:
        if self._ended:
            return
        self._ended = True
        dur = time.perf_counter() - self._start_perf
        self._tracer._finish(self, dur, status)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.end("error" if exc_type is not None else "ok")


class Tracer:
    """Process-wide span factory + bounded finished-span ring.

    ``sample_rate`` None defers to ``KTPU_TRACE_SAMPLE`` (read per root
    span, so late env changes — e.g. bench setting it before the heavy
    imports — take effect); tests pin ``TRACER.sample_rate = 1.0``.
    """

    def __init__(self, sample_rate: float | None = None,
                 capacity: int = 512):
        self.sample_rate = sample_rate
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=capacity)
        self._open: dict[str, Span] = {}
        self.dropped_unfinished = 0

    # -- sampling ----------------------------------------------------------

    def _rate(self) -> float:
        if self.sample_rate is not None:
            return self.sample_rate
        try:
            return float(os.environ.get("KTPU_TRACE_SAMPLE", "0.01"))
        except ValueError:
            return 0.0

    def _sample_root(self) -> bool:
        rate = self._rate()
        if rate <= 0.0:
            return False
        if rate >= 1.0:
            return True
        # obs/ is outside the R4 determinism scope: tracing is diagnostic,
        # never part of the solve path, so ambient entropy is fine here.
        return os.urandom(2)[0] / 256.0 < rate

    @staticmethod
    def _gen_id(nbytes: int) -> str:
        return os.urandom(nbytes).hex()

    # -- span creation -----------------------------------------------------

    def start_span(self, name: str, parent: SpanContext | None = None,
                   tid: str = "main", attrs: dict | None = None) -> Span:
        """Scoped span: use as a context manager (R6-enforced)."""
        return self.begin_span(name, parent=parent, tid=tid, attrs=attrs)

    def begin_span(self, name: str, parent: SpanContext | None = None,
                   tid: str = "main", attrs: dict | None = None) -> Span:
        """Explicit-handoff span: the caller owns ``end()``.

        Sanctioned for queue items that cross thread boundaries; tracked
        in the open-span table so orphans stay visible.
        """
        if parent is not None:
            sampled = parent.sampled
            trace_id = parent.trace_id
            parent_id = parent.span_id
        else:
            sampled = self._sample_root()
            trace_id = self._gen_id(16)
            parent_id = None
        ctx = SpanContext(trace_id, self._gen_id(8), sampled)
        span = Span(self, name, ctx, parent_id, tid, attrs)
        if sampled:
            with self._lock:
                self._open[ctx.span_id] = span
        return span

    def record_span(self, name: str, parent: SpanContext | None,
                    start_wall: float, dur_s: float, tid: str = "main",
                    status: str = "ok", attrs: dict | None = None) -> None:
        """Record a retroactive span (already timed by the caller)."""
        if parent is None or not parent.sampled:
            return
        rec = {
            "trace_id": parent.trace_id,
            "span_id": self._gen_id(8),
            "parent_id": parent.span_id,
            "name": name,
            "tid": tid,
            "ts_us": int(start_wall * 1e6),
            "dur_us": max(int(dur_s * 1e6), 0),
            "status": status,
        }
        if attrs:
            rec["attrs"] = attrs
        with self._lock:
            self._ring.append(rec)

    def _finish(self, span: Span, dur_s: float, status: str) -> None:
        if not span.context.sampled:
            return
        rec = {
            "trace_id": span.context.trace_id,
            "span_id": span.context.span_id,
            "parent_id": span.parent_id,
            "name": span.name,
            "tid": span.tid,
            "ts_us": int(span.start_wall * 1e6),
            "dur_us": max(int(dur_s * 1e6), 0),
            "status": status,
        }
        if span.attrs:
            rec["attrs"] = span.attrs
        with self._lock:
            self._open.pop(span.context.span_id, None)
            self._ring.append(rec)

    # -- inspection / export -----------------------------------------------

    def open_spans(self) -> list[Span]:
        """Sampled spans begun but not yet ended (orphan detector)."""
        with self._lock:
            return list(self._open.values())

    def finished(self) -> list[dict]:
        with self._lock:
            return list(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.dropped_unfinished += len(self._open)
            self._open.clear()

    def debug_payload(self) -> dict:
        """The /debug/traces body: finished spans grouped by trace."""
        spans = self.finished()
        traces: dict[str, list] = {}
        for rec in spans:
            traces.setdefault(rec["trace_id"], []).append(rec)
        for recs in traces.values():
            recs.sort(key=lambda r: r["ts_us"])
        return {
            "num_traces": len(traces),
            "num_spans": len(spans),
            "open_spans": len(self.open_spans()),
            "traces": traces,
        }

    def to_jsonl(self) -> str:
        return "\n".join(json.dumps(rec, sort_keys=True)
                         for rec in self.finished())

    def to_chrome(self) -> str:
        """Chrome trace-event JSON: ph:"X" duration events, one row per
        stage thread (ph:"M" thread_name metadata), Perfetto-loadable."""
        events = []
        tids: dict[str, int] = {}

        def tid_row(name: str) -> int:
            if name not in tids:
                row = len(tids) + 1
                tids[name] = row
                events.append({
                    "ph": "M", "pid": 1, "tid": row,
                    "name": "thread_name", "args": {"name": name},
                })
            return tids[name]

        # seed the pipeline rows in pipeline order so the viewer lays
        # them out top-to-bottom regardless of which span finished first
        for stage in STAGE_TIDS:
            tid_row(stage)
        for rec in self.finished():
            events.append({
                "ph": "X", "pid": 1,
                "tid": tid_row(rec["tid"]),
                "name": rec["name"],
                "cat": rec.get("status", "ok"),
                "ts": rec["ts_us"],
                "dur": rec["dur_us"],
                "args": {
                    "trace_id": rec["trace_id"],
                    "span_id": rec["span_id"],
                    "parent_id": rec.get("parent_id"),
                    **(rec.get("attrs") or {}),
                },
            })
        return json.dumps({"traceEvents": events,
                           "displayTimeUnit": "ms"})


TRACER = Tracer()


_TRACE_ME = None


def _trace_me():
    """jax.profiler.TraceAnnotation, imported on first use (this module
    is imported by planes that never touch jax)."""
    global _TRACE_ME
    if _TRACE_ME is None:
        from jax.profiler import TraceAnnotation

        _TRACE_ME = TraceAnnotation
    return _TRACE_ME


# the metrics of the innermost open stage (per thread, and per asyncio
# task, so a stage held open across an await is not seen by other tasks)
_STAGE_METRICS: contextvars.ContextVar = contextvars.ContextVar(
    "ktpu_stage_metrics", default=None)


class stage:
    """One stage boundary: ``with stage(name, ...) as st: <work>``.

    - opens a TraceMe ``name`` (with ``batch=<id>`` metadata when given:
      the scheduler's per-batch sequence number, so every row of one batch
      carries one id);
    - on exit adds the elapsed time to ``metrics.add_phase(phase, ...)``,
      in thread CPU time where ``cpu`` (GIL waits on concurrent stage
      threads excluded), wall time otherwise; a stage given no ``metrics``
      reports to the innermost enclosing stage's in this context, and
      records no phase outside one (code below the scheduler, such as
      StateDB, names its sub-stages without holding a metrics handle);
    - adds the wall time to ``busy[0][busy[1]]`` (a pipeline busy total);
    - records a retroactive Dapper child named ``tid`` on that row when
      ``work_span`` is sampled (settable inside the block, with
      ``status`` and ``attrs``).

    ``st.elapsed`` holds the wall seconds once the block has exited.
    """

    __slots__ = ("name", "batch", "metrics", "phase", "cpu", "busy",
                 "work_span", "tid", "attrs", "status", "elapsed", "_tm",
                 "_t0", "_c0", "_token")

    def __init__(self, name: str, *, batch: int | None = None,
                 metrics=None, phase: str | None = None, cpu: bool = False,
                 busy: tuple[dict, str] | None = None, work_span=None,
                 tid: str | None = None, attrs: dict | None = None):
        self.name = name
        self.batch = batch
        self.metrics = metrics
        self.phase = phase
        self.cpu = cpu
        self.busy = busy
        self.work_span = work_span
        self.tid = tid
        self.attrs = attrs
        self.status = "ok"
        self.elapsed = 0.0

    def __enter__(self) -> "stage":
        if self.metrics is None:
            self.metrics = _STAGE_METRICS.get()
        self._token = _STAGE_METRICS.set(self.metrics)
        tm = _trace_me()
        self._tm = tm(self.name) if self.batch is None \
            else tm(self.name, batch=self.batch)
        self._tm.__enter__()
        if self.cpu:
            self._c0 = time.thread_time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        wall = time.perf_counter() - self._t0
        cpu = time.thread_time() - self._c0 if self.cpu else wall
        self._tm.__exit__(exc_type, exc, tb)
        _STAGE_METRICS.reset(self._token)
        self.elapsed = wall
        if self.phase is not None and self.metrics is not None:
            self.metrics.add_phase(self.phase, cpu)
        if self.busy is not None:
            totals, key = self.busy
            totals[key] += wall
        span = self.work_span
        if span is not None and span.sampled:
            TRACER.record_span(
                self.tid, span.context, time.time() - wall, wall,
                tid=self.tid,
                status="error" if exc_type is not None else self.status,
                attrs=self.attrs)


# ---- collector pauses -----------------------------------------------------

_GC_WATCHED: "weakref.WeakSet" = weakref.WeakSet()
_GC_STATE: dict = {"t0": 0.0, "tm": None, "counters": None}


def _gc_counters() -> tuple:
    from kubernetes_tpu.obs import metrics as _metrics

    fam = _metrics.REGISTRY.counter(
        "process_gc_pause_seconds_total",
        "Seconds the process spent in garbage collections, by generation.",
        ("generation",))
    return tuple(fam.labels(str(g)) for g in range(3))


def _on_gc(phase: str, info: dict) -> None:
    """gc.callbacks hook: every collection adds its pause to the
    process counter and to each watched scheduler's ``gc_pause`` phase
    (its metrics resolved now: the benchmark swaps them at a window's
    start); gen1 and gen2 collections also open a TraceMe."""
    gen = info["generation"]
    st = _GC_STATE
    if phase == "start":
        if gen:
            tm = _trace_me()(f"ktpu.gc.gen{gen}")
            tm.__enter__()
            st["tm"] = tm
        st["t0"] = time.perf_counter()
        return
    pause = time.perf_counter() - st["t0"]
    tm = st["tm"]
    if tm is not None:
        st["tm"] = None
        tm.__exit__(None, None, None)
    st["counters"][gen].inc(pause)
    for owner in _GC_WATCHED:
        owner.metrics.accrue("gc_pause", pause)


def watch_gc(owner) -> None:
    """Count collector pauses into ``owner.metrics.accrue("gc_pause",
    ...)`` (not the phase histogram: that stays one observation per
    batch phase). One hook per process, however many owners register."""
    if _GC_STATE["counters"] is None:
        _trace_me()  # never import inside a collection
        _GC_STATE["counters"] = _gc_counters()
        gc.callbacks.append(_on_gc)
    _GC_WATCHED.add(owner)


def pod_trace_context(pod) -> SpanContext | None:
    """Extract the trace context stamped on a pod, if any and sampled."""
    meta = getattr(pod, "metadata", None)
    ann = getattr(meta, "annotations", None) or {}
    ctx = parse_traceparent(ann.get(TRACE_ANNOTATION))
    if ctx is not None and ctx.sampled:
        return ctx
    return None
