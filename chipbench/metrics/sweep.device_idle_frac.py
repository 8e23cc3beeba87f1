"""Share of the traced sweep window in which no operation ran on the chip
(mean over the chips used), from the profiler trace."""


def read(ctx):
    s = ctx.get("trace")
    return None if s is None or "sweep_calls" not in ctx else s.idle_frac
