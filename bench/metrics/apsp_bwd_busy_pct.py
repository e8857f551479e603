"""Share of the device's exclusive busy time, in the traced part of the
timed calls, spent in the APSP backward (the program's ``apsp_bwd`` op
scope: the shortest-path-DAG subgradient), over the chips the cell uses
(``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.scope_pct(run, "apsp_bwd")
