"""Mean milliseconds a pod waited in the scheduler's workqueue, add to
pop (SchedulerMetrics.phase_us_per_pod["queue_wait"]: the workqueue's own
per-pod waits summed over the window, over the pods encoded)."""


def read(ctx):
    us = (ctx.get("phase_us_per_pod") or {}).get("queue_wait")
    return None if us is None else us / 1e3
