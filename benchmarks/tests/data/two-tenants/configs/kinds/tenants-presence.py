"""Kind ``tenants-presence``: several tenants on one instance, each with
its own devices and its own threshold rule, under the presence sweep.

Every tenant is created through ``inst.tenants`` and registers its
devices through its own engine's device management; its rule is scoped
to it.  Sends carry measurements with the claimed tenant's id on every
row; the system derives alerts from them and, from its own presence
sweep, one STATE_CHANGE event for a device gone silent.  Leaves for the
traffic kinds, beside ``dep.tokens`` and ``dep.handles``: ``dep.owner``
(the dense tenant id of each device, aligned with ``handles``),
``dep.owner_of`` (the same over every handle, -1 for none) and
``dep.silent`` (aligned: the devices whose only event the traffic ages).
"""

from __future__ import annotations

import json
import time

import numpy as np

from benchmarks import cells
from benchmarks.harness import OK, PARTIAL

reference = cells.reference_of(__file__)


def populate(dep) -> None:
    from sitewhere_tpu.schema import AlertLevel, ComparisonOp, EventType

    if (reference.MEASUREMENT, reference.ALERT, reference.STATE_CHANGE) != (
            int(EventType.MEASUREMENT), int(EventType.ALERT),
            int(EventType.STATE_CHANGE)):
        raise RuntimeError("the reference's event-type constants are stale")
    inst, config = dep.inst, dep.config
    if dep.n_shards != 1:
        raise ValueError("kind tenants-presence lays its fleet out for one "
                         "shard")
    stated = int(config["presence"]["missing_after_s"])
    if int(inst.presence.missing_after_s) != stated:
        raise RuntimeError(f"the instance sweeps with missing_after_s "
                           f"{inst.presence.missing_after_s}, the file "
                           f"states {stated}")
    n_silent = int(config["presence"]["silent_devices_per_tenant"])
    t0 = time.perf_counter()
    dep.tokens, dep.tenant_ids, owner, silent = [], {}, [], []
    for tenant in config["tenants"]:
        name = tenant["token"]
        inst.tenants.create_tenant(token=name, name=name.title(),
                                   auth_token=f"{name}-auth-token-123")
        engine = inst.engines.get_engine(name)
        dep.tenant_ids[name] = int(engine.tenant_id)
        dm = engine.device_management
        dm.create_device_type(token="sensor", name="Sensor")
        for i in range(int(tenant["devices"])):
            token = f"{name}-d{i}"
            dm.create_device(token=token, device_type="sensor")
            dm.create_device_assignment(device=token)
            dep.tokens.append(token)
            owner.append(engine.tenant_id)
            silent.append(i >= int(tenant["devices"]) - n_silent)
        rule = tenant["threshold"]
        inst.rules.create_rule(
            mtype=None, op=ComparisonOp[rule["op"]],
            threshold=float(rule["threshold"]), alert_type=f"hot-{name}",
            alert_level=AlertLevel.WARNING, tenant=name)
    dep.handles = np.asarray(inst.identity.device.lookup_many(dep.tokens),
                             np.int32)
    dep.owner = np.asarray(owner, np.int32)
    dep.silent = np.asarray(silent, bool)
    dep.owner_of = np.full(dep.capacity, -1, np.int32)
    dep.owner_of[dep.handles] = dep.owner
    dt = time.perf_counter() - t0
    dep.log(f"[deploy] registered {len(dep.tokens)} devices of "
            f"{len(dep.tenant_ids)} tenants in {dt:.1f}s")


def own_rows(cols) -> np.ndarray:
    """A delivered row is a send's own unless the system derived it: an
    alert, or the sweep's report of a silent device."""
    etype = np.asarray(cols["event_type"])
    return (etype != reference.ALERT) & (etype != reference.STATE_CHANGE)


def compare(checks, dep, traffic, run) -> int:
    """The run against the plain reference, by tenant where the program
    can be read by tenant.  Returns the dead letters the reference
    accounts for: those of the rows it expects refused."""
    sends, inst, config = run.sends, dep.inst, dep.config
    accepted = np.nonzero(sends.status == OK)[0]
    rules = {dep.tenant_ids[t["token"]]: t["threshold"]
             for t in config["tenants"]}
    want = reference.expected_counts(
        traffic.bodies, sends.body[accepted], dep.owner_of, rules)
    newest = reference.newest_events(
        traffic.bodies, [(int(s), int(sends.body[s])) for s in accepted],
        traffic.ts_s_of, dep.owner_of)
    missing = reference.reported_missing(
        newest, int(config["presence"]["missing_after_s"]),
        int(traffic.t_first_s), int(time.time()) + 1)
    events, alerts = sum(want["events"].values()), sum(want["alerts"].values())
    reports = len(missing)
    snap = dep.d.metrics_snapshot()
    checks.equal("processed", snap["processed"],
                 want["rows"] + alerts + reports)
    checks.equal("accepted", snap["accepted"], events + alerts + reports)
    checks.equal("unregistered (rows claiming a tenant that does not own "
                 "the device)", snap["unregistered"], want["refused"])
    checks.equal("unassigned", snap["unassigned"], 0)
    checks.equal("threshold_alerts", snap["threshold_alerts"], alerts)
    checks.equal("derived_alerts", snap["derived_alerts"], alerts)
    store = inst.event_store
    checks.equal("store total = taken events + alerts + reports",
                 store.total_events, events + alerts + reports)
    for name, tid in dep.tenant_ids.items():
        checks.equal(f"stored MEASUREMENT events of tenant {name}",
                     store.query(tenant_id=tid,
                                 event_type=reference.MEASUREMENT).total,
                     want["events"][tid])
        checks.equal(f"stored ALERT events of tenant {name}",
                     store.query(tenant_id=tid,
                                 event_type=reference.ALERT).total,
                     want["alerts"][tid])
    checks.equal("stored STATE_CHANGE events = devices gone silent",
                 store.query(event_type=reference.STATE_CHANGE).total,
                 reports)
    per_device = [store.query(event_type=reference.STATE_CHANGE,
                              device_id=int(dev)).total for dev in missing]
    checks.equal("silent devices reported once each",
                 int(sum(n == 1 for n in per_device)), reports)
    checks.equal("rows the connector saw of accepted sends",
                 int(run.delivery.delivered[accepted].sum()), events)
    checks.equal("rows the connector could not place", run.delivery.stray, 0)
    checks.equal("sends partly admitted",
                 int((sends.status == PARTIAL).sum()), 0)
    letters = [json.loads(doc) for _, doc in inst.dead_letters.scan(0)]
    refused = [d for d in letters if d.get("kind") == "unregistered"]
    checks.equal("rows dead-lettered as unregistered",
                 int(sum(d["count"] for d in refused)), want["refused"])

    rng = np.random.default_rng(0)
    picked = rng.choice(dep.handles, min(int(config["sample_devices"]),
                                         len(dep.handles)), replace=False)
    picked = np.union1d(picked, np.asarray(sorted(missing), np.int32)[:4])
    bad = []
    for dev in picked.tolist():
        row = inst.device_state.get_device_state_by_id(dev)
        got = {"presence_missing": row["presence_missing"]}
        doc = {"presence_missing": dev in missing}
        if dev in newest:
            doc["last_event_ts_s"], doc["value"] = newest[dev]
            got["last_event_ts_s"] = row["last_event_ts_s"]
            got["value"] = row["last_values"][dep.slot]
        if got != doc:
            bad.append((dev, got, doc))
    checks.check(f"state of {len(picked)} sampled devices = their newest "
                 f"taken events and whether they went silent", not bad,
                 f"{len(bad)} differ, first: {bad[:1]}")
    return len(refused)


def compare_intake(checks, dep, traffic, run) -> None:
    """Columns come decoded: nothing passes the wire decoder."""
    checks.equal("pipeline.bytes_copied.decode",
                 int(dep.inst.metrics.counter(
                     "pipeline.bytes_copied.decode").value), 0)


def _one_tenants_rows_as_the_others(dep):
    """Every row that claims the first tenant handed on under the
    second's id (a tenant's rows are that tenant's)."""
    first, second = list(dep.tenant_ids.values())[:2]
    whole = dep.d.ingest_arrays

    def swapped(**cols):
        tenant = np.asarray(cols["tenant_id"])
        cols["tenant_id"] = np.where(tenant == first, second,
                                     tenant).astype(np.int32)
        return whole(**cols)
    dep.d.ingest_arrays = swapped


def _another_missing_after(dep):
    """The sweep run with another ``missing_after_s`` than the file
    states (a silent device is reported): the control proper."""
    dep.inst.presence.missing_after_s = int(
        dep.config["presence"]["missing_after_s"]) + 2 * 3600


# fault -> (planted before or after the deployment is populated, how,
#           the start of the name of a comparison that has to fail)
FAULTS = {
    "one-tenants-rows-as-the-others": (
        "after", _one_tenants_rows_as_the_others, "unregistered"),
    "another-missing-after": (
        "after", _another_missing_after, "stored STATE_CHANGE events"),
}
