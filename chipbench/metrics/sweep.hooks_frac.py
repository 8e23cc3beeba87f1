"""Share of the traced call's device-busy time spent in the ASA start
and chain hooks: self time of the operations under the program's
``xsim.hooks`` scope over the self time of every operation
(``chipbench/scopes.py``)."""

from chipbench import scopes


def read(ctx):
    s = scopes.of_run(ctx, "sweep_calls")
    return None if s is None else s.frac("xsim.hooks")
