"""Set-up: process start to the window's start (imports, build, ELL,
device placement, compilation or cache load, warm-up chunk)."""


def read(run):
    return run.setup_s
