"""Durability and background work, from outside: the longest interval
between two consecutive deliveries to the connector inside the window.
A checkpoint or a seal that holds the interpreter shows here."""

import numpy as np


def read(run):
    times = run.delivery_times()
    if len(times) < 2:
        return None
    return float(np.diff(times).max() * 1e3)
