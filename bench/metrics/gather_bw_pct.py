"""Share of HBM bandwidth the XLA gathers over the ELL panels reach:
the bytes they move (from their own signatures in the trace) over their
device time, against the chip's peak bandwidth."""


def read(run):
    if run.trace is None or run.gather_seconds <= 0:
        return None
    rate = run.gather_bytes / run.gather_seconds
    return 100.0 * rate / run.peak["hbm_bytes_per_s"]
