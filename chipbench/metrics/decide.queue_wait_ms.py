"""Mean time from ``submit`` to the end of the batch formation that took
the request (ms), over the requests submitted in the traced window, from
the server's own spans (``ServeConfig.obs_spans``): each request's span
ends when its batch resolves, which names the batch, whose
``batch_form`` span ends when the batch was formed. Set-up's admissions,
all queued at once, are left out."""

import bisect

import numpy as np


def read(ctx):
    obs = ctx.get("serve_obs")
    span = ctx.get("trace_span")
    if obs is None or not obs.spans or span is None:
        return None
    forms, resolves, reqs = [], [], []
    for ph, name, _pid, _tid, t, dur, rid, _aux in list(obs.events):
        if name == "batch_form":
            forms.append(t + dur)
        elif name == "future_resolve":
            resolves.append(t)
        elif name == "request" and rid is not None and t >= span[0]:
            reqs.append((t, t + dur))
    if not forms or len(forms) != len(resolves) or not reqs:
        return None
    forms = np.asarray(forms)
    resolves = np.asarray(resolves)
    order = np.argsort(resolves)
    resolves, forms = resolves[order], forms[order]
    waits = []
    for t_enq, t_res in reqs:
        j = bisect.bisect_right(resolves, t_res) - 1
        if j >= 0 and forms[j] >= t_enq:
            waits.append(forms[j] - t_enq)
    return float(np.mean(waits)) * 1e3 if waits else None
