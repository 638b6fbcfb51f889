"""Share of the paged-attention kernel's grid that lands on live pages,
over the profiled span: of the (slot, table column) steps the decode
blocks visit (the server's ``kv_pages_visited``), those on a page that
holds a position the step attends (``kv_pages_live``)."""


def read(ctx):
    c = ctx["run"].counters()
    visited, live = c.get("kv_pages_visited"), c.get("kv_pages_live")
    if not visited or live is None:
        return None
    return 100.0 * live / visited
