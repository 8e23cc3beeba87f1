"""Share of device-busy time spent in XLA sort operations (the FCFS order
and the reservation's end-time order of ``schedule_pass``)."""


def read(ctx):
    s = ctx.get("trace")
    if s is None or "sweep_calls" not in ctx or s.busy_s <= 0:
        return None
    return s.sort_s / s.busy_s
