"""The norm and GELU ops that run inside a conv kernel's epilogue, as a
share of all of them, in percent: the session Tracer's set-up spans,
each ``kernel`` span's ``epilogue`` parts over those parts plus each
``lower`` span's ``norm_gelu_outside`` count. None where the program
lowers no norm or GELU (or records no ``kernel`` span)."""
PARTS = ("norm", "gelu")


def read(run):
    inside = sum(p in PARTS for s in run.program_spans if s.cat == "kernel"
                 for p in s.attrs.get("epilogue", ()))
    outside = sum(s.attrs.get("norm_gelu_outside", 0)
                  for s in run.program_spans if s.cat == "lower")
    if inside + outside == 0:
        return None
    return 100.0 * inside / (inside + outside)
