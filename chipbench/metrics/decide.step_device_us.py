"""Device-busy microseconds per dispatched decision step in the traced
part of the window: busy time from the profiler trace over the batches
whose ``device_step`` span (the server's own) began inside it."""


def read(ctx):
    s = ctx.get("trace")
    obs = ctx.get("serve_obs")
    span = ctx.get("trace_span")
    if s is None or obs is None or not obs.spans or span is None:
        return None
    t0, t1 = span
    n = sum(1 for ev in list(obs.events)
            if ev[1] == "device_step" and t0 <= ev[4] <= t1)
    return s.busy_s * 1e6 / n if n else None
