"""Real-time factor: wall seconds per simulated second over the whole
window (every chunk, monitor readout and checkpoint stall in it)."""


def read(run):
    return run.window_s / (run.steps * run.dt_ms * 1e-3)
