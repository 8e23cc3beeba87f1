"""Deferred requests per request in the window: the delta of the server's
``asa_serve_deferrals_total`` counter over the requests submitted."""


def read(ctx):
    c = ctx.get("counters")
    if c is None or not ctx.get("n_requests"):
        return None
    before, after = c
    key = "asa_serve_deferrals_total"
    return (after[key] - before[key]) / ctx["n_requests"]
