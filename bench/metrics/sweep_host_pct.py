"""Share of the timed calls' wall time spent outside the engine's
``solve_batch`` (the sweep's host-side instance build and aggregation),
from the benchmark's own spans over the whole window."""


def read(run):
    calls = sum(b - a for a, b in run.call_spans())
    solve = sum(b - a for name, a, b in run.spans if name == "solve_batch")
    if calls <= 0 or solve <= 0:
        return None
    return 100.0 * (calls - solve) / calls
