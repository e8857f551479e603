"""Share of the lane-iterations the device ran in the designer's ranking
executes that a lane still needed: the counts ``lane_iters_used`` (each
lane's own iterations) over ``lane_iters_run`` (each lane at its chunk's
longest) of the program's ``design.rank`` spans inside the timed calls
(``bench/scopes.py``).  Early-stopped lanes that ride along with a
slower lane of their chunk lower it."""
from bench import scopes


def read(run):
    calls = run.call_spans()
    if not calls:
        return None
    recs = scopes.program_spans(kept_after=min(a for a, _ in calls))
    if recs is None:
        return None
    inside = [r for r in recs if r.name == "design.rank"
              and any(a <= r.start and r.end <= b for a, b in calls)]
    used = sum(r.counts.get("lane_iters_used", 0) for r in inside)
    ran = sum(r.counts.get("lane_iters_run", 0) for r in inside)
    return 100.0 * used / ran if ran > 0 else None
