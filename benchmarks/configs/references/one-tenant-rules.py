"""The plain reference of kind ``one-tenant-rules``: what the deployment
must have done with the sends.

Straight numpy over the generated inputs, independent of the program:
rule firings per body, and for a device its newest measurement and
location over every send the system accepted.  A *body* is one
generated send without its stamp — a dict of equally long arrays
``dev`` (handles), ``etype``, ``value``, ``lat``, ``lon`` and ``ts_ns``
(order inside one send) — and a send is (sequence number, body).
"""

from __future__ import annotations

import numpy as np

MEASUREMENT, LOCATION, ALERT = 0, 1, 2   # schema.EventType, checked at start


_COMPARE = {"GT": np.greater, "LT": np.less, "GTE": np.greater_equal,
            "LTE": np.less_equal}


def fires_threshold(rule: dict, etype, value):
    """Threshold rule ``{"op": "GT", "threshold": x}``: measurements only."""
    if rule["op"] not in _COMPARE:
        raise ValueError(f"the reference knows {sorted(_COMPARE)}, not "
                         f"{rule['op']!r}")
    return (etype == MEASUREMENT) & _COMPARE[rule["op"]](
        value, np.float32(rule["threshold"]))


def fires_zone(zone: dict, etype, lat, lon):
    """Geofence rectangle ``{"lat": [lo, hi], "lon": [lo, hi]}``: locations
    strictly inside."""
    (lat0, lat1), (lon0, lon1) = zone["lat"], zone["lon"]
    return ((etype == LOCATION) & (lat > lat0) & (lat < lat1)
            & (lon > lon0) & (lon < lon1))


def body_counts(body: dict, rules: dict) -> tuple:
    """(events, threshold alerts, zone alerts) of one body under
    ``rules`` = ``{"thresholds": [rule, ...], "zones": [zone, ...]}``.
    A row raises one alert of a family however many of its rules fire
    (the first that does names the alert)."""
    n = len(body["dev"])
    hot, inside = np.zeros(n, bool), np.zeros(n, bool)
    for rule in rules["thresholds"]:
        hot |= fires_threshold(rule, body["etype"], body["value"])
    for zone in rules["zones"]:
        inside |= fires_zone(zone, body["etype"], body["lat"], body["lon"])
    return n, int(hot.sum()), int(inside.sum())


def expected_counts(bodies: list, sent_bodies, rules: dict) -> dict:
    """Totals over the accepted sends (``sent_bodies``: one body index
    per accepted send)."""
    per_body = np.asarray([body_counts(b, rules) for b in bodies],
                          np.int64).reshape(len(bodies), 3)
    n, thr, zon = per_body[np.asarray(sent_bodies, np.int64)].sum(axis=0) \
        if len(sent_bodies) else (0, 0, 0)
    return {"events": int(n), "threshold_alerts": int(thr),
            "zone_alerts": int(zon), "derived_alerts": int(thr + zon)}


def newest_state(bodies: list, sends, ts_s_of, picked) -> dict:
    """For each handle in ``picked``: what its state row must hold after
    the accepted ``sends`` [(seq, body index)] — the newest event's
    second and type, the newest measurement's value, the newest
    location.  Newest is by (stamp of the send, ``ts_ns`` inside it);
    ``ts_s_of(seq)`` is the second the stamp falls in."""
    picked = np.asarray(picked)
    rows_of = [np.nonzero(np.isin(b["dev"], picked))[0] for b in bodies]
    cols = {k: [] for k in ("dev", "key", "ts_s", "etype", "value", "lat",
                            "lon")}
    for seq, bi in sends:
        rows, b = rows_of[bi], bodies[bi]
        if not len(rows):
            continue
        cols["dev"].append(b["dev"][rows])
        cols["key"].append((np.int64(seq) << 32) + b["ts_ns"][rows])
        cols["ts_s"].append(np.full(len(rows), ts_s_of(seq), np.int64))
        for k in ("etype", "value", "lat", "lon"):
            cols[k].append(b[k][rows])
    if not cols["dev"]:
        return {}
    c = {k: np.concatenate(v) for k, v in cols.items()}
    want = {}
    for dev in picked.tolist():
        rows = np.nonzero(c["dev"] == dev)[0]
        if not len(rows):
            continue
        newest = rows[np.argmax(c["key"][rows])]
        doc = {"last_event_ts_s": int(c["ts_s"][newest]),
               "last_event_type": int(c["etype"][newest])}
        meas = c["etype"][rows] == MEASUREMENT
        mrows, lrows = rows[meas], rows[~meas]
        if len(mrows):
            m = mrows[np.argmax(c["key"][mrows])]
            doc["value"] = float(c["value"][m])
            doc["value_ts_s"] = int(c["ts_s"][m])
        if len(lrows):
            at = lrows[np.argmax(c["key"][lrows])]
            doc["lat"] = float(c["lat"][at])
            doc["lon"] = float(c["lon"][at])
            doc["loc_ts_s"] = int(c["ts_s"][at])
        want[dev] = doc
    return want
