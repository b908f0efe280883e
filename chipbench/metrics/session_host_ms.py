"""Host time inside the session's submit, flush and result calls per
batch served, from the benchmark's own spans around them."""


def read(run):
    if run.host_spans is None or not run.window.batches:
        return None
    t = sum(run.host_spans[n] for n in ("submit", "flush", "result"))
    return t / run.window.batches * 1e3
