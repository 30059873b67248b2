"""The plain reference of kind ``tenant-topics-presence``: what a deployment
of several tenants must have done with the sends, and which devices its
presence sweep must have reported.

Straight numpy over the generated inputs, independent of the program.
A *body* is one generated send without its stamp: equally long arrays
``dev`` (handles, each at most once), ``tenant`` (the dense id of the
tenant the send names, the same on every row: a payload comes off one
tenant's topic) and ``value`` (measurements only).  ``owner_of`` is an
array over handles: the dense id of the tenant that registered the
device, -1 for none.  A row whose send names another tenant than its
device's owner is refused whole: not stored, no alert, no state.
"""

from __future__ import annotations

import numpy as np

MEASUREMENT, ALERT, STATE_CHANGE = 0, 2, 5   # schema.EventType, checked at start

_COMPARE = {"GT": np.greater, "LT": np.less, "GTE": np.greater_equal,
            "LTE": np.less_equal}


def taken(body: dict, owner_of) -> np.ndarray:
    """Rows of ``body`` the system has to take: the named tenant owns
    the device."""
    return body["tenant"] == owner_of[body["dev"]]


def fires(rule: dict, value) -> np.ndarray:
    if rule["op"] not in _COMPARE:
        raise ValueError(f"the reference knows {sorted(_COMPARE)}, not "
                         f"{rule['op']!r}")
    return _COMPARE[rule["op"]](value, np.float32(rule["threshold"]))


def expected_counts(bodies: list, sent_bodies, owner_of, rules: dict) -> dict:
    """Totals over the accepted sends (one body index each).  ``rules``
    is {tenant id: its threshold rules}: a tenant's rows meet only that
    tenant's rules, and a row that several fire on is one alert.
    ``events`` and ``alerts`` are by tenant id."""
    out = {"rows": 0, "refused": 0,
           "events": {t: 0 for t in rules}, "alerts": {t: 0 for t in rules}}
    for bi in np.asarray(sent_bodies, np.int64).tolist():
        b = bodies[bi]
        ok = taken(b, owner_of)
        out["rows"] += len(ok)
        out["refused"] += int((~ok).sum())
        for t, own in rules.items():
            mine = ok & (b["tenant"] == t)
            if not mine.any():
                continue
            fired = np.zeros(len(ok), bool)
            for rule in own:
                fired |= fires(rule, b["value"])
            out["events"][t] += int(mine.sum())
            out["alerts"][t] += int((mine & fired).sum())
    return out


def newest_events(bodies: list, sends, ts_s_of, owner_of) -> dict:
    """{handle: (second, value)} of each device's newest taken event
    over the accepted ``sends`` [(seq, body index)]; newest is by the
    send's second, then its sequence number."""
    newest: dict = {}
    for seq, bi in sorted(sends, key=lambda s: (ts_s_of(s[0]), s[0])):
        b = bodies[bi]
        ok = taken(b, owner_of)
        ts_s = int(ts_s_of(seq))
        for dev, value in zip(b["dev"][ok].tolist(), b["value"][ok].tolist()):
            newest[dev] = (ts_s, value)
    return newest


def overdue(ts_s: int, now_s: int, missing_after_s: int) -> bool:
    """The program's integer rule, second for second."""
    return now_s - ts_s > missing_after_s


def reported_missing(newest: dict, missing_after_s: int, swept_s: int,
                     read_s: int) -> set:
    """Devices the presence sweep must have reported, once each, by the
    time the run is read: seen at least once, and the newest taken event
    overdue at ``swept_s``, the second of the last sweep known to have
    ended before the run was read.  A device that goes overdue between
    that sweep and ``read_s`` (the second the store is read, at the
    latest) may or may not have been reported by a later sweep: it has
    no one answer, and the traffic must not make one."""
    out = set()
    for dev, (ts_s, _) in newest.items():
        if overdue(ts_s, swept_s, missing_after_s):
            out.add(dev)
        elif overdue(ts_s, read_s, missing_after_s):
            raise ValueError(f"device {dev} goes overdue between the last "
                             f"sweep and the reading: the reference has "
                             f"no one answer")
    return out
