"""Mean wait of a request in the scheduler's queue, from ``submit()``
to its pop into a batch: ``SchedulerStats.queue_wait_ns`` over the
requests popped (scored, answered from the cache or deduped), counted
over the window."""


def read(run):
    wait = run.stats.get("queue_wait_ns")
    popped = sum(run.stats.get(k, 0) for k in
                 ("scored_rows", "answered_from_cache", "deduped_in_flight"))
    return wait / popped / 1e6 if wait is not None and popped else None
