"""Share of the timed calls' wall time in the multigraph repair of the
instance build: the program's spans ``graphs.repair``
(``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.host_pct(run, ("graphs.repair",))
