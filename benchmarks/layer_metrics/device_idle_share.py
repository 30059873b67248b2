"""Device: 1 - (union of the intervals in which an operation ran on the
chip) / traced window, on the chip that was busy least, in percent."""


def read(run):
    idle = (run.trace or {}).get("idle_share_worst")
    return None if idle is None else 100.0 * idle
