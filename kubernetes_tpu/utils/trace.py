"""In-request step timing: over-threshold logging plus exportable spans.

The util/trace.Trace analog (reference apiserver/pkg/util/trace/trace.go:28-90;
the scheduler wraps Schedule with trace.Step(...) + LogIfLong(100ms),
core/generic_scheduler.go:89-126), extended into a span tracer: every
finished trace can feed a structured-JSON sink, while the log line stays
thresholded.

The sink is process-global: `set_trace_sink(callable | path | None)`, or
the KTPU_TRACE_FILE environment variable (one JSON object per line,
append mode) read at import. File sinks are serialized by a module lock
(multiple pipeline threads finish traces concurrently) and closed at
interpreter exit.

A StepTimer may additionally carry a distributed-tracing span
(obs/tracing.py): pass `trace_span=`, and `export()` folds the step
marks into that trace as retroactive child spans and ends the batch
span — so the legacy (non-staged) scheduling path produces the same
stitched trace shape as the staged pipeline.
"""

from __future__ import annotations

import atexit
import json
import logging
import os
import threading
import time
from typing import Callable

log = logging.getLogger("kubernetes_tpu.trace")

_sink: Callable[[dict], None] | None = None
_sink_lock = threading.Lock()
_sink_file = None


def _close_sink_file() -> None:
    global _sink_file
    with _sink_lock:
        if _sink_file is not None:
            try:
                _sink_file.close()
            except OSError:
                pass
            _sink_file = None


atexit.register(_close_sink_file)


def set_trace_sink(sink) -> None:
    """Install the structured trace sink: a callable(dict), a file path
    (JSON lines, appended), or None to disable. Replacing a file sink
    closes the previous handle."""
    global _sink, _sink_file
    if sink is None or callable(sink):
        _close_sink_file()
        _sink = sink
        return
    f = open(sink, "a", encoding="utf-8")

    def write(record: dict) -> None:
        # one lock around write+flush: records from concurrent pipeline
        # threads stay line-atomic, and writes cannot race the atexit
        # close
        with _sink_lock:
            if f.closed:
                return
            f.write(json.dumps(record) + "\n")
            f.flush()

    _close_sink_file()
    with _sink_lock:
        _sink_file = f
    _sink = write


def trace_sink() -> Callable[[dict], None] | None:
    return _sink


if os.environ.get("KTPU_TRACE_FILE"):
    set_trace_sink(os.environ["KTPU_TRACE_FILE"])


class StepTimer:
    """Named step spans off one start point (log_if_long is the finish
    point). `trace_span`, when given, is an obs/tracing.py Span owned by
    this timer: export() records each step as a child span and ends it."""

    def __init__(self, name: str, trace_span=None):
        self.name = name
        self.start = time.monotonic()
        self.start_wall = time.time()
        self.steps: list[tuple[str, float]] = []
        self.trace_span = trace_span

    def step(self, label: str) -> None:
        self.steps.append((label, time.monotonic()))

    def total(self) -> float:
        return time.monotonic() - self.start

    def spans(self) -> list[tuple[str, float]]:
        """-> [(step label, duration seconds)] between consecutive marks."""
        prev = self.start
        out = []
        for label, t in self.steps:
            out.append((label, t - prev))
            prev = t
        return out

    def export(self, total: float | None = None) -> None:
        """Feed the JSON sink and the distributed trace (no-ops when
        neither is configured)."""
        spans = None
        if _sink is not None:
            spans = self.spans()
            _sink({"ts": time.time(), "name": self.name,
                   "total_ms": round(1e3 * (total if total is not None
                                            else self.total()), 3),
                   "steps": [{"step": label, "ms": round(1e3 * dur, 3)}
                             for label, dur in spans]})
        if self.trace_span is not None:
            span = self.trace_span
            self.trace_span = None  # export finishes the trace exactly once
            if span.sampled:
                from kubernetes_tpu.obs.tracing import TRACER
                spans = spans if spans is not None else self.spans()
                wall = self.start_wall
                for label, dur in spans:
                    TRACER.record_span(label, span.context, wall, dur,
                                       tid="loop")
                    wall += dur
            span.end("ok")

    def log_if_long(self, threshold: float) -> bool:
        """Finish the trace: always export spans; log only when the total
        exceeds `threshold` (the reference's LogIfLong contract)."""
        total = self.total()
        self.export(total=total)
        if total < threshold:
            return False
        parts = [f"{label}: {1e3 * dur:.1f}ms"
                 for label, dur in self.spans()]
        log.warning("trace %s (total %.1fms): %s",
                    self.name, 1e3 * total, "; ".join(parts))
        return True
