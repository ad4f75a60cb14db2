"""Share of the window a batch's apply closure waited for the event loop
to start it, from the commit thread's push to the closure's first line
(StagedPipeline.snapshot()["stage_busy_frac"]["apply_wait"])."""


def read(ctx):
    return (ctx.get("stage_busy_frac") or {}).get("apply_wait")
