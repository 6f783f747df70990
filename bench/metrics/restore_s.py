"""Time to resume: ``Session.restore`` of the snapshot saved at the
window's end until the restored state is on the device."""


def read(run):
    return run.restore_s
