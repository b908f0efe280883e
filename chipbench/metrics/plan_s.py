"""Host seconds in the planner: the session Tracer's top-level ``plan``
and ``lower`` spans during set-up."""

CATS = ("plan", "lower")


def read(run):
    ids = {s.id for s in run.program_spans if s.cat in CATS}
    top = [s for s in run.program_spans
           if s.cat in CATS and s.parent_id not in ids]
    if not top:
        return None
    return sum(s.dur_ns for s in top) / 1e9
