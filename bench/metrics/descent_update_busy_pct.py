"""Share of the device's exclusive busy time, in the traced part of the
timed calls, spent in the descent update (the program's
``descent_update`` op scope less the APSP inside it: Adam, the
Frank-Wolfe line search and blend, the bounds), over the chips the cell
uses (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.scope_pct(run, "descent_update")
