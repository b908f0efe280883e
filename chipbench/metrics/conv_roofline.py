"""The chip's least time for the conv work of every batch traced
(``work.py``: the larger of operations over the peak rate and bytes
over HBM bandwidth, per conv node, at the session's batch) over the
device's busy time in the window, in percent."""
from chipbench import work


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    least = work.least_seconds_per_batch(run.nodes, run.max_batch,
                                         run.precision, run.peak)
    return 100.0 * least * run.window.batches / run.trace["busy_s"]
