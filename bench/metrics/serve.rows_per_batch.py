"""Request rows per scoring call: ``SchedulerStats.scored_rows`` over
``batches``, counted over the window."""


def read(run):
    b = run.stats.get("batches", 0)
    return run.stats["scored_rows"] / b if b else None
