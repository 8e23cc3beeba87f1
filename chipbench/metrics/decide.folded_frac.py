"""Folded observations per request in the window: the delta of the
server's ``asa_serve_folded_total`` counter (observations applied behind
an earlier observation of their slot in the same batch) over the
requests submitted. None where the program has no such counter."""


def read(ctx):
    c = ctx.get("counters")
    key = "asa_serve_folded_total"
    if c is None or not ctx.get("n_requests") or key not in c[1]:
        return None
    before, after = c
    return (after[key] - before[key]) / ctx["n_requests"]
