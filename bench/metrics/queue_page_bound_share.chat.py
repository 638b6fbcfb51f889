"""Share of the time the backlog's head waited on KV pages (a slot was
free, but not its worst-case page reservation) among the time it
waited on pages or on slots, over the profiled span: the server's
``queue_blocked_pages_s`` and ``queue_blocked_slots_s`` counters."""


def read(ctx):
    c = ctx["run"].counters()
    pages = c.get("queue_blocked_pages_s")
    slots = c.get("queue_blocked_slots_s")
    if pages is None or slots is None or pages + slots <= 0:
        return None
    return 100.0 * pages / (pages + slots)
