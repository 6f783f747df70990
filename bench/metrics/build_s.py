"""Host span around the ``Session`` constructor: procedural build, ELL
repacking and device placement."""


def read(run):
    return run.build_s
