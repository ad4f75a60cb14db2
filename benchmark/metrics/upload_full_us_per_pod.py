"""Host CPU microseconds per pod in StateDB's whole-array uploads: the
ledger, affinity counts and membership columns a flush re-uploads whole,
each through an owned host copy (SchedulerMetrics.phase_us_per_pod
["upload_full"], the `ktpu.flush.upload_full` span; thread CPU time, a
part of "flush")."""


def read(ctx):
    return (ctx.get("phase_us_per_pod") or {}).get("upload_full")
