"""Host CPU microseconds per pod in the StateDB flush that opens each
dispatch (SchedulerMetrics.phase_us_per_pod["flush"]; thread CPU time)."""


def read(ctx):
    return (ctx.get("phase_us_per_pod") or {}).get("flush")
