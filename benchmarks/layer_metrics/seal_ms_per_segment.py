"""D2H and egress: mean milliseconds of one seal job (build, write and
publish one segment) - window delta of ``store.seal_job_s`` over its
observations, on whichever thread ran the job: a seal worker, or the
egress worker through the store's valve.  None where the program keeps
no such timer, or no job ran in the window."""


def read(run):
    seconds, count = run.timer("store.seal_job_s")
    return seconds / count * 1e3 if count else None
