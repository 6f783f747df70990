"""Share of HBM bandwidth the delivery gathers over the ELL panels reach:
the bytes they move (from their own signatures in the trace) over their
device time, against the chip's peak bandwidth.  The gathers are XLA's
(a ``kCustom`` fusion with a panel-sized result reading the panel's
``s32`` ids) and a delivery kernel's (a ``custom-call`` reading an ``s32``
operand of a panel's size); ``work.gather_traffic`` finds both."""


def read(run):
    if run.trace is None or run.gather_seconds <= 0:
        return None
    rate = run.gather_bytes / run.gather_seconds
    return 100.0 * rate / run.peak["hbm_bytes_per_s"]
