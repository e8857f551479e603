"""Share of the timed calls' wall time in the designer's final
certification execute, its plan and its wait on the device included: the
program's spans ``design.certify`` (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.host_pct(run, ("design.certify",))
