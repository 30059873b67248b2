"""D2H + egress: blocking device-to-host fetches per step in the window
(``host_syncs`` over ``steps``); a chain shares one fetch."""


def read(run):
    steps = run.dispatcher("steps")
    return run.dispatcher("host_syncs") / steps if steps else None
