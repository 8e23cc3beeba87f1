"""Share of the traced call's device-busy time spent in the event step's
own work: self time of the operations under the program's
``xsim.events`` scope (time advance, completions, releases, faults,
admissions; ``chipbench/scopes.py``) over the self time of every
operation."""

from chipbench import scopes


def read(ctx):
    s = scopes.of_run(ctx, "sweep_calls")
    return None if s is None else s.frac("xsim.events")
