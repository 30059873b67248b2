"""Durability and background work: periodic checkpoints that ended
inside the window (growth of the checkpointer's generation, sampled
every 100 ms).  One is expected at 30 s; another count changes what the
window's sheds, gaps and tail mean."""


def read(run):
    return len(run.checkpoints)
