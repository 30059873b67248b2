"""Process start to the first measured send: interpreter, JAX, compile
or cache load, instance start, fleet, calibration, traffic, priming."""


def read(run):
    return run.setup_s
