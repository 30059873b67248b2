"""Batcher to ring: rows the device processed over the rows it had room
for (steps x width), in percent.  A partial plan pays a whole step."""


def read(run):
    steps = run.dispatcher("steps")
    if not steps:
        return None
    return 100.0 * run.dispatcher("processed") / (steps * run.width)
