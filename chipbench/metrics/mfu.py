"""The whole forward's share of the chip's peak: conv operations per
image times images finished in the window, over the window's seconds
and the peak rate (float32 against the bfloat16 peak), in percent."""
from chipbench import work


def read(run):
    if not run.window.done_in_window:
        return None
    flops = work.flops_per_image(run.nodes) * run.window.done_in_window
    return 100.0 * flops / run.seconds / work.peak_rate(run.peak,
                                                        run.precision)
