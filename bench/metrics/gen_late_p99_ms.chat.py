"""How late the load generator sent the window's requests: 99th
percentile of send time minus due time, by the host clock."""
from bench import window


def read(ctx):
    late = [1e3 * (t.sent - t.due) for t in ctx["run"].window_requests()
            if t.req is not None]
    return window.percentile(late, 99)
