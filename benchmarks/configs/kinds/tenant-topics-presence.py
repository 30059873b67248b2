"""Kind ``tenant-topics-presence``: several tenants on one instance,
each with its own engine, devices and threshold rules, under the
presence sweep.  (Grown from the rehearsal's kind ``tenants-presence``
in ``benchmarks/tests/data/two-tenants/``, which keeps that name:
``test_second_kind.py`` lays its files over a copy of the benchmark and
holds that none of them was there.)

Every tenant of the file's ``tenants`` is created through
``inst.tenants`` and registers its devices through its own engine's
device management; ``rules.thresholds`` holds the rules, each scoped to
the tenant it names.  A send is one tenant's (the wire intake's
``tenant``, as upstream's is the topic's); the system derives alerts
from it and, from its own presence sweep, one STATE_CHANGE event for a
device gone silent.

The fleet: a tenant's ``devices`` are its live devices, and
``fleet.devices`` is their sum; a smaller number there (a test's cut)
cuts every tenant in proportion.  On top come
``presence.silent_cohorts`` cohorts of ``presence.silent_cohort_devices``
devices, registered to the tenants in the proportion of the file's
``devices``: the traffic gives each one event and never names it again.
Leaves for the traffic kinds, beside ``dep.tokens`` and ``dep.handles``
(every device, cohorts too): ``dep.tenant_ids`` {token: dense id},
``dep.owner`` (the dense tenant id of each device, aligned with
``handles``), ``dep.owner_of`` (the same over every handle, -1 for
none) and ``dep.cohort`` (aligned: the silent cohort a device belongs
to, -1 for a live device).
"""

from __future__ import annotations

import json
import time

import numpy as np

from benchmarks import cells
from benchmarks.harness import OK, PARTIAL

reference = cells.reference_of(__file__)


def _missing_after_s(config: dict) -> int:
    return int(config["config"]["presence"]["missing_after_s"])


def populate(dep) -> None:
    from sitewhere_tpu.schema import AlertLevel, ComparisonOp, EventType

    if (reference.MEASUREMENT, reference.ALERT, reference.STATE_CHANGE) != (
            int(EventType.MEASUREMENT), int(EventType.ALERT),
            int(EventType.STATE_CHANGE)):
        raise RuntimeError("the reference's event-type constants are stale")
    inst, config = dep.inst, dep.config
    if dep.n_shards != 1:
        raise ValueError("kind tenant-topics-presence lays its fleet out for one "
                         "shard")
    stated = _missing_after_s(config)
    if int(inst.presence.missing_after_s) != stated:
        raise RuntimeError(f"the instance sweeps with missing_after_s "
                           f"{inst.presence.missing_after_s}, the file "
                           f"states {stated}")
    tenants = config["tenants"]
    stated_fleet = sum(int(t["devices"]) for t in tenants)
    fleet = int(config["fleet"]["devices"])
    cohorts = int(config["presence"]["silent_cohorts"])
    per_cohort = int(config["presence"]["silent_cohort_devices"])
    t0 = time.perf_counter()
    dep.tokens, dep.tenant_ids, owner, cohort = [], {}, [], []
    for tenant in tenants:
        name = tenant["token"]
        inst.tenants.create_tenant(token=name, name=name.title(),
                                   auth_token=f"{name}-auth-token-123")
        engine = inst.engines.get_engine(name)
        dep.tenant_ids[name] = int(engine.tenant_id)
        dm = engine.device_management
        dm.create_device_type(token="sensor", name="Sensor")
        live = int(tenant["devices"]) * fleet // stated_fleet
        silent = int(tenant["devices"]) * per_cohort // stated_fleet
        for i in range(live + cohorts * silent):
            token = f"{name}-d{i}"
            dm.create_device(token=token, device_type="sensor")
            dm.create_device_assignment(device=token)
            dep.tokens.append(token)
            owner.append(engine.tenant_id)
            cohort.append(-1 if i < live else (i - live) // silent)
    for rule in config["rules"]["thresholds"]:
        inst.rules.create_rule(
            mtype=None, op=ComparisonOp[rule["op"]],
            threshold=float(rule["threshold"]),
            alert_type=f"hot-{rule['tenant']}",
            alert_level=AlertLevel.WARNING, tenant=rule["tenant"])
    dep.handles = np.asarray(inst.identity.device.lookup_many(dep.tokens),
                             np.int32)
    dep.owner = np.asarray(owner, np.int32)
    dep.cohort = np.asarray(cohort, np.int32)
    dep.owner_of = np.full(dep.capacity, -1, np.int32)
    dep.owner_of[dep.handles] = dep.owner
    dt = time.perf_counter() - t0
    dep.log(f"[deploy] registered {len(dep.tokens)} devices of "
            f"{len(dep.tenant_ids)} tenants in {dt:.1f}s "
            f"({len(dep.tokens) / dt:.0f}/s), "
            f"{int((dep.cohort >= 0).sum())} of them in {cohorts} silent "
            f"cohorts")


def own_rows(cols) -> np.ndarray:
    """A delivered row is a send's own unless the system derived it: an
    alert, or the sweep's report of a silent device."""
    etype = np.asarray(cols["event_type"])
    return (etype != reference.ALERT) & (etype != reference.STATE_CHANGE)


def _rules_by_tenant(dep) -> dict:
    rules = {tid: [] for tid in dep.tenant_ids.values()}
    for rule in dep.config["rules"]["thresholds"]:
        rules[dep.tenant_ids[rule["tenant"]]].append(rule)
    return rules


def compare(checks, dep, traffic, run) -> int:
    """The run against the plain reference, by tenant where the program
    can be read by tenant.  Returns the dead letters the reference
    accounts for: those of the rows it expects refused."""
    from sitewhere_tpu.services.common import SearchCriteria

    sends, inst, config = run.sends, dep.inst, dep.config
    read_s = int(time.time()) + 1
    accepted = np.nonzero(sends.status == OK)[0]
    want = reference.expected_counts(
        traffic.bodies, sends.body[accepted], dep.owner_of,
        _rules_by_tenant(dep))
    newest = reference.newest_events(
        traffic.bodies, [(int(s), int(sends.body[s])) for s in accepted],
        traffic.ts_s_of, dep.owner_of)
    missing = reference.reported_missing(
        newest, _missing_after_s(config), int(traffic.swept_s), read_s)
    events, alerts = sum(want["events"].values()), sum(want["alerts"].values())
    reports = len(missing)
    snap = dep.d.metrics_snapshot()
    checks.equal("processed", snap["processed"],
                 want["rows"] + alerts + reports)
    checks.equal("accepted", snap["accepted"], events + alerts + reports)
    checks.equal("unregistered (rows of sends naming a tenant that does "
                 "not own the device)", snap["unregistered"], want["refused"])
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
    stored = store.query(SearchCriteria(page_size=0),
                         event_type=reference.STATE_CHANGE).results
    checks.equal("stored STATE_CHANGE events = devices gone silent",
                 len(stored), reports)
    reported = np.asarray([r.device_id for r in stored], np.int64)
    checks.equal("silent devices reported once each, and no other device",
                 int(len(set(reported.tolist())) == len(reported)
                     and set(reported.tolist()) == missing), 1)
    checks.equal("reports stored under another tenant than the device's",
                 int(sum(r.tenant_id != dep.owner_of[r.device_id]
                         for r in stored)), 0)
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
    live = dep.handles[dep.cohort < 0]
    picked = rng.choice(live, min(int(config["sample_devices"]), len(live)),
                        replace=False)
    picked = np.union1d(picked, np.asarray(sorted(missing), np.int32)[::97])
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
    _compare_replay(checks, dep, traffic)
    return len(refused)


def _compare_replay(checks, dep, traffic) -> None:
    """One journaled payload of a tenant replayed in place (the record
    of an unmeasured priming send, so no latency reads it): its rows
    have to land in the tenant the send named, where its devices are
    owned and none is refused.  Made last: the counts above are read."""
    offset, body = traffic.replay_record
    before = dep.d.metrics_snapshot()
    dep.d.replay_journal(from_offset=offset, upto=offset + 1)
    dep.drain()
    after = dep.d.metrics_snapshot()
    checks.equal("replayed rows of a tenant's journaled payload",
                 after["processed"] - before["processed"]
                 - (after["derived_alerts"] - before["derived_alerts"]),
                 len(traffic.bodies[body]["dev"]))
    checks.equal("replayed rows refused (a replay lands a row in the "
                 "tenant it was accepted under)",
                 after["unregistered"] - before["unregistered"], 0)


def compare_intake(checks, dep, traffic, run) -> None:
    """Every payload is one line kind and names a tenant through its
    source, not on its lines, so the wire intake never leaves the native
    fill-direct scanner: a tenant must not cost the fast path."""
    checks.equal("pipeline.bytes_copied.decode (native fill-direct decode)",
                 int(dep.inst.metrics.counter(
                     "pipeline.bytes_copied.decode").value), 0)


def _one_tenants_rows_as_the_others(dep):
    """Every payload that names the first tenant handed on under the
    second's name (a tenant's rows are that tenant's)."""
    first, second = list(dep.tenant_ids)[:2]
    whole = dep.d.ingest_wire_lines

    def swapped(payload, tenant="default", **kw):
        return whole(payload, tenant=second if tenant == first else tenant,
                     **kw)
    dep.d.ingest_wire_lines = swapped


def _another_missing_after(dep):
    """The sweep run with another ``missing_after_s`` than the file
    states (a silent device is reported): the control proper."""
    dep.inst.presence.missing_after_s = _missing_after_s(dep.config) + 2 * 3600


def _replay_forgets_the_tenant(dep):
    """The journal record written without the payload's tenant, as it
    was before a record carried one (a replay lands a row in the tenant
    it was accepted under)."""
    journal = dep.inst.ingest_journal
    whole = journal.append
    journal.append = lambda payload, tenant="default": whole(payload)


# fault -> (planted before or after the deployment is populated, how,
#           the start of the name of a comparison that has to fail)
FAULTS = {
    "one-tenants-rows-as-the-others": (
        "after", _one_tenants_rows_as_the_others, "unregistered"),
    "another-missing-after": (
        "after", _another_missing_after, "stored STATE_CHANGE events"),
    "replay-forgets-the-tenant": (
        "after", _replay_forgets_the_tenant, "replayed rows refused"),
}
