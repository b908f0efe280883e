"""Process start to the end of warm-up: imports, chip start-up, weights,
frames, planning, compilation or the cache's load, warm-up flushes."""


def read(run):
    return run.setup_s
