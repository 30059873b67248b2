"""Protection: soft plus hard trips of the hung-step watchdog inside the
window (calibrated from the instance's own profile in set-up)."""


def _trips(marks) -> int:
    doc = marks["_dispatcher"]["device_fault"]["watchdog"]
    return doc["softTrips"] + doc["hardTrips"]


def read(run):
    return _trips(run.marks1) - _trips(run.marks0)
