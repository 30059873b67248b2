"""Kind ``one-tenant-rules``: one tenant's fleet under threshold and
geofence rules.

Every device is the default tenant's, in one area; every rule and every
zone holds for the whole fleet; sends carry measurements and locations,
and the system derives alerts from them and nothing else.  What the
harness asks of a kind (``cells.py``) is here: the fleet and its rules
(``populate``), the plain reference (``reference``), which delivered
rows are a send's own (``own_rows``), the comparisons with the
reference (``compare``, ``compare_intake``) and the controls
(``FAULTS``).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import cells
from benchmarks.harness import OK, PARTIAL

reference = cells.reference_of(__file__)


def populate(dep) -> None:
    """Fleet, then rules: leaves ``dep.tokens`` and ``dep.handles``."""
    from sitewhere_tpu.schema import AlertLevel, ComparisonOp, EventType

    if (reference.MEASUREMENT, reference.LOCATION, reference.ALERT) != (
            int(EventType.MEASUREMENT), int(EventType.LOCATION),
            int(EventType.ALERT)):
        raise RuntimeError("the reference's event-type constants are stale")
    inst, config = dep.inst, dep.config
    t0 = time.perf_counter()
    dep.tokens, dep.handles = _register_fleet(
        dep, int(config["fleet"]["devices"]))
    dt = time.perf_counter() - t0
    dep.log(f"[deploy] registered {len(dep.tokens)} devices in "
            f"{dt:.1f}s ({len(dep.tokens) / dt:.0f}/s)")
    for i, rule in enumerate(config["rules"]["thresholds"]):
        inst.rules.create_rule(
            mtype=None, op=ComparisonOp[rule["op"]],
            threshold=float(rule["threshold"]), alert_type=f"t{i}",
            alert_level=AlertLevel.WARNING)
    for i, zone in enumerate(config["rules"]["zones"]):
        (lat0, lat1), (lon0, lon1) = zone["lat"], zone["lon"]
        inst.device_management.create_zone(
            token=f"z{i}", name=f"Z{i}", area="hq",
            alert_type=f"inside{i}",
            bounds=[(lat0, lon0), (lat0, lon1), (lat1, lon1),
                    (lat1, lon0)])


def _register_fleet(dep, n_devices: int):
    """Devices with assignments through the management API, a
    ``1/n_shards`` of them on each shard: a registry block belongs
    to shard ``handle // rows_per_shard`` and handles are minted
    densely, so the handles in between are reserved."""
    inst = dep.inst
    dm = inst.device_management
    dm.create_device_type(token="sensor", name="Sensor")
    dm.create_area_type(token="bldg", name="Building")
    dm.create_area(token="hq", name="HQ", area_type="bldg")
    per_shard = n_devices // dep.n_shards
    rows_per_shard = dep.capacity // dep.n_shards
    tokens = []
    for s in range(dep.n_shards):
        for i in range(len(inst.identity.device), s * rows_per_shard):
            inst.identity.device.mint(f"reserved-{i}")
        for i in range(per_shard):
            token = f"d-{s}-{i}"
            dm.create_device(token=token, device_type="sensor")
            dm.create_device_assignment(device=token, area="hq")
            tokens.append(token)
    handles = np.asarray(inst.identity.device.lookup_many(tokens),
                         np.int32)
    return tokens, handles


def own_rows(cols) -> np.ndarray:
    """A delivered row is a send's own unless the system derived it."""
    return np.asarray(cols["event_type"]) != reference.ALERT


def compare(checks, dep, traffic, run) -> None:
    """The run against the plain reference: counts, the store, what the
    connector saw, the sampled devices' state."""
    sends, inst, config = run.sends, dep.inst, dep.config
    accepted = np.nonzero(sends.status == OK)[0]
    want = reference.expected_counts(
        traffic.bodies, sends.body[accepted], config["rules"])
    snap = dep.d.metrics_snapshot()
    n, derived = want["events"], want["derived_alerts"]
    checks.equal("processed", snap["processed"], n + derived)
    checks.equal("accepted", snap["accepted"], n + derived)
    for key in ("threshold_alerts", "zone_alerts", "derived_alerts"):
        checks.equal(key, snap[key], want[key])
    checks.equal("unregistered + unassigned",
                 snap["unregistered"] + snap["unassigned"], 0)
    store = inst.event_store
    checks.equal("store total = source events + derived alerts",
                 store.total_events, n + derived)
    checks.equal("stored ALERT events = derived alerts",
                 store.query(event_type=reference.ALERT).total, derived)
    checks.equal("rows the connector saw of accepted sends",
                 int(run.delivery.delivered[accepted].sum()), n)
    checks.equal("rows the connector could not place", run.delivery.stray, 0)
    checks.equal("sends partly admitted",
                 int((sends.status == PARTIAL).sum()), 0)

    rng = np.random.default_rng(0)
    named = np.unique(np.concatenate([b["dev"] for b in traffic.bodies]))
    picked = rng.choice(named, min(int(config.get("sample_devices", 128)),
                                   len(named)), replace=False)
    expect = reference.newest_state(
        traffic.bodies, [(int(s), int(sends.body[s])) for s in accepted],
        traffic.ts_s_of, picked)
    bad = []
    for dev, doc in expect.items():
        row = dep.state_row(dev)
        got = {k: row[k] for k in doc}
        if got != doc:
            bad.append((dev, got, doc))
    checks.check(f"state of {len(expect)} sampled devices = their newest "
                 f"events", expect and not bad,
                 f"{len(bad)} differ, first: {bad[:1]}")


def compare_intake(checks, dep, traffic, run) -> None:
    """Every payload of this kind's mixes is one line kind, so the wire
    intake never leaves the native fill-direct scanner."""
    checks.equal("pipeline.bytes_copied.decode (native fill-direct decode)",
                 int(dep.inst.metrics.counter(
                     "pipeline.bytes_copied.decode").value), 0)


def _half_of_every_batch(dep):
    """The send acknowledged in full, half of its rows handed on: an
    acknowledged event is not stored (durability)."""
    whole = dep.d.ingest_arrays

    def half(**cols):
        n = len(cols["device_id"]) // 2
        return whole(**{k: v[:n] for k, v in cols.items()})
    dep.d.ingest_arrays = half


def _a_state_answer_altered(dep):
    """A device's last value off by one where it is read back
    (last-known state is exact)."""
    state = dep.inst.device_state
    true = state.get_device_state_by_id

    def altered(handle):
        row = true(handle)
        row["last_values"] = [v + 1.0 for v in row["last_values"]]
        return row
    state.get_device_state_by_id = altered


def _another_threshold(dep):
    """The rules run against another threshold than the configuration
    states (alerts are exact): the control proper."""
    for rule in dep.config["rules"]["thresholds"]:
        rule["threshold"] = float(rule["threshold"]) - 5.0


# fault -> (planted before or after the deployment is populated, how,
#           the start of the name of a comparison that has to fail)
FAULTS = {
    "half-of-every-batch": ("after", _half_of_every_batch, "processed"),
    "state-answer-altered": ("after", _a_state_answer_altered, "state of "),
    "another-threshold": ("before", _another_threshold, "threshold_alerts"),
}
