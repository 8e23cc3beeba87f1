"""Share of the traced window in which the serve loop was not idle: 1 -
the time of its ``asa.serve.idle`` annotations (blocked waiting for a
request with nothing queued) inside the window, over the window, on the
profiler's clock (``chipbench/scopes.py``)."""

from chipbench import scopes


def read(ctx):
    s = scopes.of_run(ctx, "serve_obs")
    if s is None or not s.serve or s.window_s <= 0:
        return None
    return 1.0 - scopes.covered_s(s.serve_in_window("idle")) / s.window_s
