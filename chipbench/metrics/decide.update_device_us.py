"""Device microseconds of the table update per dispatched batch: self
time of the operations under the program's ``asa.update`` scope in the
traced window, over the batches whose ``asa.serve.device_step``
annotation starts inside it (``chipbench/scopes.py``)."""

from chipbench import scopes


def read(ctx):
    s = scopes.of_run(ctx, "serve_obs")
    if s is None or not s.scoped or not s.batches():
        return None
    return s.scope_s["asa.update"] * 1e6 / s.batches()
