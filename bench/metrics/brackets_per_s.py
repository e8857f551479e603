"""Certified brackets completed per second of the window's timed calls
(whole calls only; drawing the next call's instances is not timed)."""


def read(run):
    seconds = sum(b - a for a, b in run.call_spans())
    return sum(len(c["lanes"]) for c in run.calls) / seconds
