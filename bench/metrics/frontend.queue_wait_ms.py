"""Mean wait of a dispatched row in the frontend's queue, from its
request's submit to the start of the dispatch that took it, in ms: the
program's ``FrontendStats.queue_wait_s`` over ``dispatched_rows``.

Warm-up takes the direct path, so the frontend dispatches nothing before
the window and its totals are the window's; where the totals and the
window's counter deltas disagree on the rows, or the program keeps no such
counter, there is nothing to read."""


def read(ctx):
    stats = getattr(getattr(ctx.server, "frontend", None), "stats", None)
    total_s = getattr(stats, "queue_wait_s", None)
    rows = ctx.frontend.get("dispatched_rows")
    if total_s is None or not rows or stats.dispatched_rows != rows:
        return None
    return 1e3 * total_s / rows
