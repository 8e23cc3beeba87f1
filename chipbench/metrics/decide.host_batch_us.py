"""Serve-thread microseconds of host work per dispatched batch: the time
of its ``asa.serve.batch_form``, ``pad``, ``device_step`` and
``future_resolve`` annotations inside the traced window (the scatter
read, which waits for the device, and idle time left out), over the
batches whose ``device_step`` starts inside it
(``chipbench/scopes.py``)."""

from chipbench import scopes

PHASES = ("batch_form", "pad", "device_step", "future_resolve")


def read(ctx):
    s = scopes.of_run(ctx, "serve_obs")
    if s is None or not s.batches():
        return None
    busy = sum(scopes.covered_s(s.serve_in_window(p)) for p in PHASES)
    return busy * 1e6 / s.batches()
