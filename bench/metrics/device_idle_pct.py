"""Share of the timed calls in which no op ran on the device, averaged
over the chips the cell uses.

The trace covers only part of the window (a whole call holds millions of
op events), so the share is taken in two parts, each from its traced
time and weighted by its whole length on the host's clock: inside the
engine's ``solve_batch`` spans, and in the rest of the calls (the sweep's
host build).  A part that was not traced counts as idle: the entries
start device work only inside ``solve_batch``."""
from bench import trace


def read(run):
    traced = trace.clip(run.traced_calls, [run.traced_ns])
    solve = run.host_ns.get("solve_batch", [])
    solve_s = sum(b - a for name, a, b in run.spans if name == "solve_batch")
    calls_s = sum(b - a for a, b in run.call_spans())
    busy_s = 0.0
    for part, weight in ((trace.clip(traced, solve), solve_s),
                         (trace.gaps(solve, traced), calls_s - solve_s)):
        span = trace.length(part)
        busy = trace.busy_per_chip(run.trace, run.wl["chips"], part)
        if span > 0 and busy:
            busy_s += weight * sum(busy) / len(busy) / span
    if calls_s <= 0 or busy_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s / calls_s)
