"""Mean milliseconds from a pod's pop off the workqueue to its done():
encode and its batch's trip through the pipeline to the bind
(SchedulerMetrics.phase_us_per_pod["in_flight"], the workqueue's own
per-pod times)."""


def read(ctx):
    us = (ctx.get("phase_us_per_pod") or {}).get("in_flight")
    return None if us is None else us / 1e3
