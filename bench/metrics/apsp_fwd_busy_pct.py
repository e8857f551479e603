"""Share of the device's exclusive busy time, in the traced part of the
timed calls, spent in the APSP forward (the program's ``apsp_fwd`` op
scope: the closure on whichever backend, with its ELL table packing),
over the chips the cell uses (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.scope_pct(run, "apsp_fwd")
