"""Share of the traced serving window in which no operation ran on the
chip, from the profiler trace."""


def read(ctx):
    s = ctx.get("trace")
    return None if s is None or "serve_obs" not in ctx else s.idle_frac
