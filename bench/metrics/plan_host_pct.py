"""Share of the timed calls' wall time in the planner's host work: the
program's spans ``plan.build``, ``plan.pack``, ``plan.dispatch`` and
``plan.unpack`` (``bench/scopes.py``).  The host's wait on the device,
``plan.sync``, is not counted."""
from bench import scopes

SPANS = ("plan.build", "plan.pack", "plan.dispatch", "plan.unpack")


def read(run):
    return scopes.host_pct(run, SPANS)
