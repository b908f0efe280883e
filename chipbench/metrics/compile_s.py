"""Host seconds in the session Tracer's ``compile`` span: the warm-up
flush's trace, lowering and compilation (or load from the cache)."""


def read(run):
    spans = [s for s in run.program_spans if s.cat == "compile"]
    if not spans:
        return None
    return sum(s.dur_ns for s in spans) / 1e9
