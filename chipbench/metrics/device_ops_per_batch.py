"""Device operations that started in the traced window, per batch."""


def read(run):
    if run.trace is None or not run.window.batches:
        return None
    return run.trace["n_ops"] / run.window.batches
