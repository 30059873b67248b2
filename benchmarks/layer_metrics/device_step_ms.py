"""Device step: median device duration of one pipeline step, from the
profiler trace (runs of the step programs on the first chip, a chain
divided by its K)."""


def read(run):
    return (run.trace or {}).get("device_step_ms")
