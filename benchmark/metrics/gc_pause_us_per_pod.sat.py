"""Collector pause microseconds per pod scheduled over the window, every
generation (SchedulerMetrics.phase_us_per_pod["gc_pause"], from the
process's gc.callbacks hook). Reads in steps: a 3 s span holds no gen2
pause or one."""


def read(ctx):
    return (ctx.get("phase_us_per_pod") or {}).get("gc_pause")
