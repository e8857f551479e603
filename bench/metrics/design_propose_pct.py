"""Share of the timed calls' wall time in the designer's move kernels: the
program's spans ``design.propose`` (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.host_pct(run, ("design.propose",))
