"""The whole step's share of the chip's peak: the least time the window's
steps need (``work.least_step_work``: the larger of bytes over peak
bandwidth and operations over peak FLOP/s) over the measured window."""
from bench import work


def read(run):
    if run.window_s <= 0 or run.steps <= 0:
        return None
    least = work.least_time(run.least_bytes, run.least_ops, run.peak)
    return 100.0 * least / run.window_s
