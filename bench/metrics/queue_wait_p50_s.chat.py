"""Median, over the window's requests, of the time each waited in the
server's queue: the server's admission stamp (``Request.t_admit``, set
when the request left the backlog for its first prefill) minus its
submission stamp (``t_submit``), both on the host clock; a request
never admitted waits forever.  None where the server stamps neither."""
import statistics


def read(ctx):
    reqs = [t.req for t in ctx["run"].window_requests() if t.req is not None]
    if not reqs or not hasattr(reqs[0], "t_admit"):
        return None
    return statistics.median(
        float("inf") if r.t_admit is None else r.t_admit - r.t_submit
        for r in reqs)
