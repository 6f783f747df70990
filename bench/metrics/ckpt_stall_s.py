"""Run-loop stall per checkpoint: all stall in the window over the saves
in it, as ``Session.last_ckpt_stalls`` records each."""


def read(run):
    if not run.ckpt_stalls:
        return None
    return sum(run.ckpt_stalls) / len(run.ckpt_stalls)
