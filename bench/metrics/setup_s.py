"""Seconds from the start of ``bench/run.py`` to the first timed call:
imports, the compile cache, warm-up."""


def read(run):
    return run.setup_s
