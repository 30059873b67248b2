"""The plain reference of kind ``tenants-presence``: what a deployment
of several tenants must have done with the sends, and which devices its
presence sweep must have reported.

Straight numpy over the generated inputs, independent of the program.
A *body* is one generated send without its stamp: equally long arrays
``dev`` (handles, each at most once), ``tenant`` (the dense id of the
tenant each row claims) and ``value`` (measurements only).  ``owner_of``
is an array over handles: the dense id of the tenant that registered
the device, -1 for none.  A row that claims another tenant than its
device's owner is refused whole: not stored, no alert, no state.
"""

from __future__ import annotations

import numpy as np

MEASUREMENT, ALERT, STATE_CHANGE = 0, 2, 5   # schema.EventType, checked at start

_COMPARE = {"GT": np.greater, "LT": np.less, "GTE": np.greater_equal,
            "LTE": np.less_equal}


def taken(body: dict, owner_of) -> np.ndarray:
    """Rows of ``body`` the system has to take: the claimed tenant owns
    the device."""
    return body["tenant"] == owner_of[body["dev"]]


def fires(rule: dict, value) -> np.ndarray:
    return _COMPARE[rule["op"]](value, np.float32(rule["threshold"]))


def expected_counts(bodies: list, sent_bodies, owner_of, rules: dict) -> dict:
    """Totals over the accepted sends (one body index each).  ``rules``
    is {tenant id: its threshold rule}: a tenant's rows meet only that
    tenant's rule.  ``events`` and ``alerts`` are by tenant id."""
    out = {"rows": 0, "refused": 0,
           "events": {t: 0 for t in rules}, "alerts": {t: 0 for t in rules}}
    for bi in np.asarray(sent_bodies, np.int64).tolist():
        b = bodies[bi]
        ok = taken(b, owner_of)
        out["rows"] += len(ok)
        out["refused"] += int((~ok).sum())
        for t, rule in rules.items():
            mine = ok & (b["tenant"] == t)
            out["events"][t] += int(mine.sum())
            out["alerts"][t] += int((mine & fires(rule, b["value"])).sum())
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


def reported_missing(newest: dict, missing_after_s: int, t_first_s: int,
                     t_last_s: int) -> set:
    """Devices a presence sweep must have reported, once each: seen at
    least once, and the newest event older than ``missing_after_s`` at
    every sweep between ``t_first_s`` and ``t_last_s``.  A device that
    is overdue at some of those sweeps and not at others has no one
    answer: the traffic must not make one."""
    out = set()
    for dev, (ts_s, _) in newest.items():
        if t_first_s - ts_s > missing_after_s:
            out.add(dev)
        elif t_last_s - ts_s > missing_after_s:
            raise ValueError(f"device {dev} goes overdue during the run: "
                             f"the reference has no one answer")
    return out
