"""Seconds of the program's ``plan.dispatch`` spans that closed before
the first timed call: each program's first dispatch in set-up, which
traces, lowers and compiles it or loads it from the compile cache
(``bench/scopes.py``)."""
from bench import scopes


def read(run):
    if not run.calls:
        return None
    spans = scopes.span_intervals(("plan.dispatch",),
                                  before=run.calls[0]["span"][0])
    if not spans:
        return None
    return sum(b - a for a, b in spans)
