"""Share of the window the dispatch stage waited at the ledger-dirty
barrier (StagedPipeline.snapshot()["stage_busy_frac"]["barrier_wait"]):
a dirty host ledger, with batches still in flight, holds the next
dispatch until they commit."""


def read(ctx):
    return (ctx.get("stage_busy_frac") or {}).get("barrier_wait")
