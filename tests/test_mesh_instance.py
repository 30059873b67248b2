"""The served path on a four-shard mesh, from ``fleet-4m-mesh4``'s file.

An ``Instance`` with ``pipeline.n_shards`` 4 at toy size (four of the
virtual CPU devices), built and populated as the benchmark's deployment
does it, fed column batches in shard-block order (one full-width fill
plan each) and the same batches shuffled (the per-shard gather lane, a
plan when the fullest segment fills).  The outcome is held to the plain
reference (``benchmarks/reference.py``, which knows nothing of shards)
and to a one-shard instance given the same sends.  Then what the mesh
adds to the program: the calibration probes one shard's share, a packed
plan's placement is timed, the sharded batcher counts rows per shard.
"""

import copy
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks import cells, reference  # noqa: E402
from benchmarks.deployment import Deployment  # noqa: E402

WIDTH, CAPACITY, DEVICES, SHARDS = 1024, 4096, 2048, 4
BLOCK_SENDS, SHUFFLED_SENDS = 6, 6
PLACE = "pipeline.stage_place_s"
SHARD_ROWS = "ingest.shard_rows_emitted."


def _config(n_shards: int) -> dict:
    config = copy.deepcopy(cells.load_json(os.path.join(
        REPO, "benchmarks", "configs", "fleet-4m-mesh4.json")))
    config["config"]["pipeline"].update(
        width=WIDTH, registry_capacity=CAPACITY, n_shards=n_shards,
        ring_depth=2)
    config["fleet"]["devices"] = DEVICES
    return config


def _bodies(dep) -> list:
    """The mix's own bodies (shard-block order), then each of them with
    its rows shuffled: full-width sends that only the gather lane can
    route."""
    params = cells.load_json(os.path.join(
        REPO, "benchmarks", "traffic", "columns-saturate.json"))
    kind = cells.load_module(os.path.join(
        REPO, "benchmarks", "traffic", "kinds", params["kind"] + ".py"))
    rng = np.random.default_rng(7)
    block = kind.build(dict(params, pool_batches=BLOCK_SENDS), dep,
                       rng).bodies
    shuffled = []
    for body in block[:SHUFFLED_SENDS]:
        order = rng.permutation(WIDTH)
        shuffled.append({k: v[order] for k, v in body.items()})
    return block + shuffled


class Served:
    """One deployment, populated as the benchmark does it (fleet, the
    configuration's rules, calibration), and what the tests read."""

    def __init__(self, n_shards: int) -> None:
        self.rows_seen = 0
        self.dep = Deployment(_config(n_shards), self._connector,
                              log=lambda line: None)
        self.dep.populate()
        self.base_s = 1_753_800_000
        self.bodies: list = []

    def _connector(self, cols, mask) -> None:
        self.rows_seen += int((np.asarray(mask) & (np.asarray(
            cols["event_type"]) != reference.ALERT)).sum())

    def serve(self, bodies: list, handle_of=None) -> None:
        """Send ``bodies`` (``handle_of`` translates their devices to
        this instance's handles) and drain."""
        self.bodies = bodies
        self.place_before_sends = self.timer_count(PLACE)
        for seq, body in enumerate(bodies):
            self.send(seq, body, None if handle_of is None
                      else handle_of(body["dev"]))
        self.dep.drain()
        self.snap = self.dep.d.metrics_snapshot()

    def send(self, seq: int, body: dict, dev=None) -> None:
        dep, n = self.dep, len(body["dev"])
        dep.d.ingest_arrays(
            device_id=body["dev"] if dev is None else dev,
            event_type=body["etype"],
            ts_s=np.full(n, self.base_s + seq, np.int32),
            ts_ns=body["ts_ns"].astype(np.int32),
            mtype_id=np.full(n, dep.mtype, np.int32),
            value=body["value"], lat=body["lat"], lon=body["lon"])

    def counter(self, name: str) -> int:
        return int(self.dep.inst.metrics.snapshot()["counters"]
                   .get(name, 0))

    def timer_count(self, name: str) -> int:
        timers = self.dep.inst.metrics.snapshot()["timers"]
        return int(timers[name]["count"]) if name in timers else 0

    def shard_rows(self) -> list:
        return [self.counter(f"{SHARD_ROWS}{s}") for s in range(SHARDS)]


@pytest.fixture(scope="module")
def mesh(devices):
    served = Served(SHARDS)
    served.serve(_bodies(served.dep))
    yield served
    served.dep.close()


@pytest.fixture(scope="module")
def single(mesh):
    """The same sends through one shard: the k-th device of the fleet is
    the k-th of ``handles`` on either instance."""
    one = Served(1)
    one.serve(mesh.bodies, lambda dev: one.dep.handles[
        np.searchsorted(mesh.dep.handles, dev)])
    yield one
    one.dep.close()


def _state_rows(dep, handles) -> dict:
    """``dep.state_row`` of every handle, from one device-to-host copy
    of the state (a row at a time costs a gather per field per row)."""
    import jax

    s = jax.device_get(dep.inst.device_state.current)
    h = np.asarray(handles)
    cols = {"last_event_ts_s": s.last_event_ts_s[h],
            "last_event_type": s.last_event_type[h],
            "value": s.last_values[h, dep.slot],
            "value_ts_s": s.last_value_ts_s[h, dep.slot],
            "lat": s.last_lat[h], "lon": s.last_lon[h],
            "loc_ts_s": s.last_location_ts_s[h]}
    return {int(dev): {k: v[i].item() for k, v in cols.items()}
            for i, dev in enumerate(h)}


def _expected(mesh) -> dict:
    return reference.expected_counts(
        mesh.bodies, list(range(len(mesh.bodies))),
        mesh.dep.config["rules"])


def test_the_mesh_instance_shards_its_state_and_its_fleet(mesh):
    dep = mesh.dep
    assert dep.n_shards == SHARDS and dep.d.mesh is not None
    state = dep.inst.device_state.current
    assert len(state.last_event_ts_s.sharding.device_set) == SHARDS
    rows_per_shard = CAPACITY // SHARDS
    assert np.bincount(dep.handles // rows_per_shard).tolist() \
        == [DEVICES // SHARDS] * SHARDS
    # handles are minted densely: the blocks between were reserved
    assert len(dep.inst.identity.device) \
        == (SHARDS - 1) * rows_per_shard + DEVICES // SHARDS


@pytest.mark.parametrize("key, want", [
    ("processed", lambda w: w["events"] + w["derived_alerts"]),
    ("accepted", lambda w: w["events"] + w["derived_alerts"]),
    ("threshold_alerts", lambda w: w["threshold_alerts"]),
    ("zone_alerts", lambda w: w["zone_alerts"]),
    ("derived_alerts", lambda w: w["derived_alerts"]),
    ("unregistered", lambda w: 0),
    ("unassigned", lambda w: 0),
])
def test_mesh_counts_equal_the_reference(mesh, key, want):
    expected = _expected(mesh)
    assert expected["threshold_alerts"] > 0 and expected["zone_alerts"] > 0
    assert mesh.snap[key] == want(expected)


def test_mesh_stores_every_source_row_and_every_alert(mesh):
    from sitewhere_tpu.schema import EventType

    expected = _expected(mesh)
    store = mesh.dep.inst.event_store
    assert store.total_events \
        == expected["events"] + expected["derived_alerts"]
    assert store.query(event_type=int(EventType.ALERT)).total \
        == expected["derived_alerts"]
    assert mesh.rows_seen == expected["events"]


def test_mesh_state_of_every_device_is_its_newest_events(mesh):
    dep = mesh.dep
    sends = [(seq, seq) for seq in range(len(mesh.bodies))]
    expect = reference.newest_state(
        mesh.bodies, sends, lambda seq: mesh.base_s + seq, dep.handles)
    assert len(expect) > DEVICES // 2     # most devices were named
    rows = _state_rows(dep, dep.handles)
    bad = [(dev, rows[dev], doc) for dev, doc in expect.items()
           if {k: rows[dev][k] for k in doc} != doc]
    assert not bad, bad[:2]
    # the bulk read is the public row
    for dev in dep.handles[[0, DEVICES // 2, DEVICES - 1]]:
        assert dep.state_row(dev) == rows[int(dev)]


def test_both_lanes_of_the_sharded_batcher_ran(mesh):
    # shard-block sends fill every segment at once (one plan a send,
    # the mesh ring chains them); shuffled sends leave segments unequal
    assert mesh.snap["ring_chains"] >= 1
    assert mesh.snap["steps"] > len(mesh.bodies)
    fault = mesh.snap["device_fault"]
    assert fault["breaker"]["levelName"] == "chained"
    assert fault["breaker"]["trips"] == 0


def test_one_shard_given_the_same_sends_agrees(mesh, single):
    for key in ("processed", "accepted", "threshold_alerts", "zone_alerts",
                "derived_alerts", "unregistered", "unassigned"):
        assert single.snap[key] == mesh.snap[key], key
    assert single.dep.inst.event_store.total_events \
        == mesh.dep.inst.event_store.total_events
    rows4 = _state_rows(mesh.dep, mesh.dep.handles)
    rows1 = _state_rows(single.dep, single.dep.handles)
    bad = [(int(h4), int(h1)) for h4, h1 in zip(mesh.dep.handles,
                                                single.dep.handles)
           if rows4[int(h4)] != rows1[int(h1)]]
    assert not bad, bad[:2]


class _Probe:
    """Stands in for ``profile_device_stages``: notes its arguments."""

    def __init__(self) -> None:
        self.calls = []

    def __call__(self, **kw):
        self.calls.append(kw)
        return {"full_ms": 40.0, "width": kw["width"]}


@pytest.mark.parametrize("n_shards", [SHARDS, 1])
def test_the_calibration_probes_one_shards_share(
        mesh, single, monkeypatch, n_shards):
    from sitewhere_tpu.pipeline import telemetry

    served = mesh if n_shards > 1 else single
    inst = served.dep.inst
    probe = _Probe()
    monkeypatch.setattr(telemetry, "profile_device_stages", probe)
    result = inst.run_device_profile(iters=2, repeats=3)
    assert result["full_ms"] == 40.0
    (kw,) = probe.calls
    # one shard's rows against one shard's slots; with one shard the
    # whole, which is what it passed before there was a mesh
    assert kw == dict(
        width=WIDTH // n_shards, capacity=CAPACITY // n_shards,
        rules_capacity=int(inst.rules.publish().threshold.shape[0]),
        zones_capacity=int(inst.mirror.publish_zones().nvert.shape[0]),
        iters=2, repeats=3, metrics=inst.metrics)
    wd = inst.dispatcher.watchdog
    assert (wd.soft_s, wd.hard_s) == (2.0, 16.0)   # 50 x and 400 x 40 ms


def test_the_calibration_ran_on_the_mesh_instance(mesh):
    """``populate()`` made the real probes, at the configuration's own
    ``calibration``, before any send."""
    profile, inst = mesh.dep.profile, mesh.dep.inst
    assert profile["width"] == WIDTH // SHARDS
    assert profile["full_ms"] > 0 and profile["state_ms"] > 0
    hist = inst.metrics.snapshot()["histograms"]
    assert hist["device.stage_ms.full"]["count"] >= 1


def test_a_packed_plans_placement_is_timed_on_the_mesh_only(mesh, single):
    # the boot warm-up places a batch too and is no plan
    assert mesh.place_before_sends == 0
    assert mesh.timer_count(PLACE) == mesh.counter("ingest.batches_emitted")
    assert mesh.timer_count(PLACE) > len(mesh.bodies)
    timers = single.dep.inst.metrics.snapshot()["timers"]
    assert PLACE not in timers
    assert single.snap["steps"] > 0


def test_shard_rows_add_up_and_follow_a_skewed_batch(mesh, single):
    assert sum(mesh.shard_rows()) == mesh.counter("ingest.rows_emitted")
    assert min(mesh.shard_rows()) > 0
    counters = single.dep.inst.metrics.snapshot()["counters"]
    assert not [k for k in counters if k.startswith(SHARD_ROWS)]

    # 200 rows, every one a device of shard 2: its alerts re-enter
    # through the gather lane and land on shard 2 as well
    dep, before = mesh.dep, mesh.shard_rows()
    emitted = mesh.counter("ingest.rows_emitted")
    per_shard = DEVICES // SHARDS
    body = {k: v[:200] for k, v in mesh.bodies[0].items()}
    mesh.send(len(mesh.bodies), body,
              dev=dep.handles[2 * per_shard:2 * per_shard + 200])
    dep.drain()
    grown = [b - a for a, b in zip(before, mesh.shard_rows())]
    assert grown[2] >= 200 and grown[0] == grown[1] == grown[3] == 0
    assert sum(grown) == mesh.counter("ingest.rows_emitted") - emitted
