"""Share of the timed calls' wall time in the sweep's instance build: the
program's span ``sweep.build`` (topologies, their repair, traffic),
``bench/scopes.py``.  The inside counterpart of ``sweep_host_pct``."""
from bench import scopes


def read(run):
    return scopes.host_pct(run, ("sweep.build",))
