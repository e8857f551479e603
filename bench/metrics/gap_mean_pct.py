"""Mean relative bracket width (ub - lb) / ub, in percent, over every
bracket the window completed whose theta* > 0 (a fabric that cannot
route its demand has the exact answer lb = theta* = 0, and no width)."""
from bench import compare


def read(run):
    gaps = [(x["ub"] - x["lb"]) / x["ub"] for c in run.calls
            for x in c["lanes"] if compare.routable(x)]
    return 100.0 * sum(gaps) / len(gaps) if gaps else None
