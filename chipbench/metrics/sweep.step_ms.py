"""Device-busy milliseconds per lock-step of the traced calls: busy time
from the profiler trace over the sum of each call's largest
``final.steps``."""


def read(ctx):
    s = ctx.get("trace")
    calls = ctx.get("traced_calls")
    if s is None or not calls:
        return None
    steps = sum(int(c["steps"].max()) for c in calls)
    return s.busy_s * 1e3 / steps if steps else None
