"""Share of lock-step lane-steps that did work: the sum over scenarios of
``final.steps`` over (lanes x the call's largest ``final.steps``), summed
over the window's calls. An exact count; 1 - it is the drain waste of
lanes that finished early and step as no-ops."""


def read(ctx):
    calls = ctx.get("sweep_calls")
    if not calls:
        return None
    work = sum(int(c["steps"].sum()) for c in calls)
    slots = sum(c["lanes"] * int(c["steps"].max()) for c in calls)
    return work / slots if slots else None
