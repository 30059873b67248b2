"""Device management service: CRUD, validation, registry epochs.

Covers the `IDeviceManagement` surface (reference:
service-device-management/.../MongoDeviceManagement.java) and the mirror →
Registry epoch publication the pipeline gathers against.
"""

import numpy as np
import pytest

from sitewhere_tpu.ids import NULL_ID, IdentityMap
from sitewhere_tpu.schema import AssignmentStatus
from sitewhere_tpu.services.common import (
    DuplicateToken,
    EntityNotFound,
    InvalidReference,
    SearchCriteria,
    ValidationError,
)
from sitewhere_tpu.services.device_management import (
    DeviceGroupElement,
    DeviceManagement,
    RegistryMirror,
)


@pytest.fixture()
def dm():
    identity = IdentityMap(capacity=4096)
    mirror = RegistryMirror(capacity=4096, max_zones=32, max_verts=8)
    svc = DeviceManagement("default", identity, mirror)
    svc.create_device_type(token="thermo", name="Thermostat")
    return svc


def test_device_type_crud(dm):
    dt = dm.get_device_type("thermo")
    assert dt.name == "Thermostat"
    dm.update_device_type("thermo", description="updated")
    assert dm.get_device_type("thermo").description == "updated"
    with pytest.raises(DuplicateToken):
        dm.create_device_type(token="thermo", name="again")
    with pytest.raises(ValidationError):
        dm.create_device_type(token="noname", name="")
    assert dm.list_device_types().total == 1


def test_device_commands_and_statuses(dm):
    cmd = dm.create_device_command(
        "thermo",
        token="set-point",
        name="setPoint",
        namespace="http://acme/thermo",
        parameters=[("target", "double", True), ("mode", "string", False)],
    )
    assert dm.get_device_command("thermo", "set-point").name == "setPoint"
    assert len(dm.list_device_commands("thermo")) == 1
    dm.create_device_status("thermo", token="ok", code="ok", name="OK")
    assert dm.list_device_statuses("thermo")[0].code == "ok"
    dm.delete_device_command("thermo", "set-point")
    assert dm.list_device_commands("thermo") == []


def test_device_crud_updates_registry(dm):
    dev = dm.create_device(token="d-1", device_type="thermo")
    did = dm.identity.device.lookup("d-1")
    assert did != NULL_ID
    assert dm.mirror.active[did]
    assert dm.mirror.assignment_status[did] == AssignmentStatus.NONE

    with pytest.raises(InvalidReference):
        dm.create_device(token="d-2", device_type="missing")
    with pytest.raises(DuplicateToken):
        dm.create_device(token="d-1", device_type="thermo")

    dm.delete_device("d-1")
    assert not dm.mirror.active[did]
    with pytest.raises(EntityNotFound):
        dm.get_device("d-1")


def test_assignment_lifecycle_and_registry_sync(dm):
    dm.create_area_type(token="building", name="Building")
    dm.create_area(token="hq", area_type="building", name="HQ")
    dm.create_customer_type(token="org", name="Org")
    dm.create_customer(token="acme", customer_type="org", name="Acme")
    dm.create_device(token="d-1", device_type="thermo")

    a = dm.create_device_assignment(
        token="a-1", device="d-1", customer="acme", area="hq", asset="asset-9"
    )
    did = dm.identity.device.lookup("d-1")
    assert dm.mirror.assignment_status[did] == AssignmentStatus.ACTIVE
    assert dm.mirror.area_id[did] == dm.identity.area.lookup("default:hq")
    assert dm.mirror.customer_id[did] == dm.identity.customer.lookup("default:acme")

    # Only one active assignment per device (reference invariant).
    with pytest.raises(ValidationError):
        dm.create_device_assignment(device="d-1")
    # Device with active assignment cannot be deleted.
    with pytest.raises(ValidationError):
        dm.delete_device("d-1")

    dm.mark_missing("a-1")
    assert dm.mirror.assignment_status[did] == AssignmentStatus.MISSING

    # After release the device has no live assignment — the registry row
    # returns to NONE (the pipeline dead-letters its events as unassigned,
    # same as the reference's null-assignment path).
    dm.release_device_assignment("a-1")
    assert a.released_date_s is not None
    assert dm.mirror.assignment_status[did] == AssignmentStatus.NONE
    assert dm.mirror.assignment_id[did] == NULL_ID

    # After release a new assignment is allowed.
    dm.create_device_assignment(token="a-2", device="d-1")
    assert dm.mirror.assignment_status[did] == AssignmentStatus.ACTIVE
    res = dm.list_device_assignments(device="d-1", status="Released")
    assert [x.token for x in res] == ["a-1"]


def test_registry_epoch_publication(dm):
    mirror = dm.mirror
    e0 = mirror.epoch
    reg = mirror.publish_registry()
    assert int(reg.epoch) == e0 + 1
    assert not mirror._dirty
    dm.create_device(token="d-9", device_type="thermo")
    assert mirror.dirty
    reg2 = mirror.publish_registry()
    did = dm.identity.device.lookup("d-9")
    assert bool(reg2.active[did])


def test_area_and_customer_hierarchy(dm):
    dm.create_area_type(token="site", name="Site")
    dm.create_area(token="root", area_type="site", name="Root")
    dm.create_area(token="child", area_type="site", name="Child", parent_area="root")
    tree = dm.area_tree()
    assert tree[0]["token"] == "root"
    assert tree[0]["children"][0]["token"] == "child"
    with pytest.raises(ValidationError):
        dm.delete_area("root")  # has children
    assert dm.list_areas(parent="root").total == 1
    assert dm.list_areas(root_only=True).total == 1

    dm.create_customer_type(token="org", name="Org")
    dm.create_customer(token="parent", customer_type="org", name="P")
    dm.create_customer(token="kid", customer_type="org", name="K", parent_customer="parent")
    with pytest.raises(ValidationError):
        dm.delete_customer("parent")
    assert dm.list_customers(parent="parent").total == 1


def test_zone_rows_flow_to_zone_table(dm):
    dm.create_area_type(token="site", name="Site")
    dm.create_area(token="hq", area_type="site", name="HQ")
    z = dm.create_zone(
        token="z-1",
        area="hq",
        name="fence",
        bounds=[(0.0, 0.0), (0.0, 10.0), (10.0, 10.0), (10.0, 0.0)],
        condition="outside",
    )
    zid = dm.identity.zone.lookup("default:z-1")
    table = dm.mirror.publish_zones()
    assert bool(table.active[zid])
    assert int(table.nvert[zid]) == 4
    assert int(table.condition[zid]) == 1
    # verts stored as (lon, lat)
    np.testing.assert_allclose(np.asarray(table.verts[zid][1]), [10.0, 0.0])

    with pytest.raises(ValidationError):
        dm.create_zone(token="bad", area="hq", bounds=[(0, 0), (1, 1)])

    dm.delete_zone("z-1")
    assert not dm.mirror.z_active[zid]


def test_device_groups_flatten_nested(dm):
    for i in range(3):
        dm.create_device(token=f"d-{i}", device_type="thermo")
    inner = dm.create_device_group(token="inner", name="Inner", roles=["fleet"])
    dm.add_device_group_elements(
        "inner", [DeviceGroupElement(device="d-0"), DeviceGroupElement(device="d-1")]
    )
    dm.create_device_group(token="outer", name="Outer")
    dm.add_device_group_elements(
        "outer", [DeviceGroupElement(nested_group="inner"), DeviceGroupElement(device="d-2")]
    )
    tokens = sorted(d.token for d in dm.group_devices("outer"))
    assert tokens == ["d-0", "d-1", "d-2"]
    assert dm.list_devices(group="outer").total == 3
    assert dm.list_device_groups(role="fleet").total == 1
    with pytest.raises(ValidationError):
        dm.add_device_group_elements("outer", [DeviceGroupElement(nested_group="outer")])
    dm.remove_device_group_elements("outer", [DeviceGroupElement(device="d-2")])
    assert len(dm.get_device_group("outer").elements) == 1


def test_alarms(dm):
    dm.create_device(token="d-1", device_type="thermo")
    al = dm.create_device_alarm(token="al-1", device="d-1", message="overheating")
    assert al.state == "Triggered"
    dm.acknowledge_alarm("al-1")
    assert dm.get_device_alarm("al-1").state == "Acknowledged"
    dm.resolve_alarm("al-1")
    assert dm.get_device_alarm("al-1").state == "Resolved"
    assert dm.list_device_alarms(device="d-1", state="Resolved").total == 1
    dm.delete_device_alarm("al-1")
    with pytest.raises(EntityNotFound):
        dm.get_device_alarm("al-1")


def test_paging(dm):
    for i in range(25):
        dm.create_device(token=f"d-{i:03d}", device_type="thermo")
    page2 = dm.list_devices(SearchCriteria(page=2, page_size=10))
    assert page2.total == 25
    assert len(page2.results) == 10
    assert page2.results[0].token == "d-010"
    assert dm.list_devices(excluding_assigned=True).total == 25


def test_listeners_fire_on_mutation(dm):
    seen = []
    dm.add_listener(lambda kind, e: seen.append(kind))
    dm.create_device(token="d-1", device_type="thermo")
    dm.create_device_assignment(token="a-1", device="d-1")
    dm.release_device_assignment("a-1")
    assert "device.created" in seen
    assert "assignment.created" in seen
    assert "assignment.released" in seen


def test_cross_tenant_device_token_collision_rejected():
    """A second tenant reusing a device token must not hijack the registry row."""
    identity = IdentityMap(capacity=4096)
    mirror = RegistryMirror(capacity=4096)
    t1 = DeviceManagement("t1", identity, mirror)
    t2 = DeviceManagement("t2", identity, mirror)
    t1.create_device_type(token="thermo", name="A")
    t2.create_device_type(token="thermo", name="B")
    t1.create_device(token="d-1", device_type="thermo")
    with pytest.raises(DuplicateToken):
        t2.create_device(token="d-1", device_type="thermo")
    did = identity.device.lookup("d-1")
    assert mirror.tenant_id[did] == t1.tenant_id


def test_assignment_cannot_move_devices(dm):
    dm.create_device(token="d-a", device_type="thermo")
    dm.create_device(token="d-b", device_type="thermo")
    dm.create_device_assignment(token="a-1", device="d-a")
    with pytest.raises(ValidationError):
        dm.update_device_assignment("a-1", device="d-b")
    with pytest.raises(InvalidReference):
        dm.update_device_assignment("a-1", customer="nope")


def test_bad_zone_update_leaves_store_consistent(dm):
    dm.create_area_type(token="site", name="Site")
    dm.create_area(token="hq", area_type="site", name="HQ")
    dm.create_zone(token="z-1", area="hq", bounds=[(0, 0), (0, 5), (5, 5)])
    with pytest.raises(ValidationError):
        dm.update_zone("z-1", bounds=[(0, 0), (1, 1)])
    with pytest.raises(InvalidReference):
        dm.update_zone("z-1", area="nope")
    assert len(dm.get_zone("z-1").bounds) == 3  # unchanged
    # Too many vertices for the mirror is a clean ValidationError at create.
    many = [(0.0, float(i)) for i in range(dm.mirror.max_verts + 1)]
    with pytest.raises(ValidationError):
        dm.create_zone(token="z-big", area="hq", bounds=many)
    assert "z-big" not in dm.zones
    zid = dm.identity.zone.lookup("default:z-big")
    assert zid == NULL_ID


def test_rejected_update_leaves_entity_untouched(dm):
    dm.create_device(token="d-1", device_type="thermo")
    dm.create_device_assignment(token="a-1", device="d-1")
    with pytest.raises(ValidationError):
        dm.update_device_assignment("a-1", status="Bogus")
    a = dm.get_device_assignment("a-1")
    assert a.status == "Active"  # rejected update did not half-apply
    did = dm.identity.device.lookup("d-1")
    assert dm.mirror.assignment_status[did] == AssignmentStatus.ACTIVE
    with pytest.raises(ValidationError):
        dm.update_device("d-1", comments="x", not_a_field=1)
    assert dm.get_device("d-1").comments == ""


def test_deleted_device_token_reuse_keeps_handle(dm):
    dm.create_device(token="d-1", device_type="thermo")
    did = dm.identity.device.lookup("d-1")
    dm.delete_device("d-1")
    # Handle is tombstoned, not freed: a new unrelated device gets a fresh
    # handle; recreating the same token reuses the old one.
    dm.create_device(token="d-2", device_type="thermo")
    assert dm.identity.device.lookup("d-2") != did
    dm.create_device(token="d-1", device_type="thermo")
    assert dm.identity.device.lookup("d-1") == did
    assert dm.mirror.active[did]


def test_tenant_isolation_between_services():
    identity = IdentityMap(capacity=4096)
    mirror = RegistryMirror(capacity=4096)
    t1 = DeviceManagement("t1", identity, mirror)
    t2 = DeviceManagement("t2", identity, mirror)
    t1.create_device_type(token="thermo", name="A")
    t2.create_device_type(token="thermo", name="B")  # same token, different tenant
    t1.create_device(token="d-t1", device_type="thermo")
    t2.create_device(token="d-t2", device_type="thermo")
    d1 = identity.device.lookup("d-t1")
    d2 = identity.device.lookup("d-t2")
    assert mirror.tenant_id[d1] == t1.tenant_id
    assert mirror.tenant_id[d2] == t2.tenant_id
    assert t1.tenant_id != t2.tenant_id


def test_active_assignment_lookup_survives_delete_and_restore(dm):
    """The per-device assignment index answers exactly what a scan of
    ``dm.assignments`` would, across every way the dict changes:
    create, delete, and a checkpoint restore refilling it in place."""
    from sitewhere_tpu.runtime.checkpoint import merge_store

    def scan(device):
        for a in dm.assignments.values():
            if a.device == device and a.status in ("Active", "Missing"):
                return a
        return None

    for i in range(3):
        dm.create_device(token=f"d-{i}", device_type="thermo")
    dm.create_device_assignment(token="a-0", device="d-0")
    dm.release_device_assignment("a-0")
    dm.create_device_assignment(token="a-0b", device="d-0")
    dm.create_device_assignment(token="a-1", device="d-1")
    assert dm.get_active_assignment("d-0").token == "a-0b"
    assert dm.get_active_assignment("d-2") is None

    dm.delete_device_assignment("a-0b")
    assert dm.get_active_assignment("d-0") is scan("d-0") is None
    dm.create_device_assignment(token="a-0c", device="d-0")
    assert dm.get_active_assignment("d-0").token == "a-0c"

    # restore: the same dict object, other contents
    import copy

    snap = copy.deepcopy(dm.assignments)
    del snap["a-1"]
    merge_store(dm, {"assignments": snap})
    for device in ("d-0", "d-1", "d-2"):
        assert dm.get_active_assignment(device) is scan(device)
    assert dm.get_active_assignment("d-1") is None
    dm.create_device_assignment(token="a-1b", device="d-1")   # allowed again


def test_registering_a_fleet_is_not_quadratic(dm):
    """create_device_assignment used to scan every assignment (twice):
    registering n devices cost n^2 and 100k took minutes.  Compare the
    work per device early and late in a fleet instead of a wall clock."""
    calls = []
    real = dict.values

    class Counting(dict):
        def values(self):
            calls.append(len(self))
            return real(self)

    dm.assignments = Counting()
    for i in range(300):
        dm.create_device(token=f"f-{i}", device_type="thermo")
        dm.create_device_assignment(device=f"f-{i}")
    # the index is built from the dict once; nothing walks it per device
    assert len(calls) <= 1
