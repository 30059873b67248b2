"""The plain reference of kind ``one-tenant-rules`` under the name that
tier-1's ``tests/test_mesh_instance.py`` imports.  A kind's reference is
``configs/references/<kind>.py``; nothing of the benchmark imports this
module."""

from benchmarks import cells as _cells

_kind = _cells.load_module(_cells.reference_file("one-tenant-rules"))

MEASUREMENT, LOCATION, ALERT = _kind.MEASUREMENT, _kind.LOCATION, _kind.ALERT
fires_threshold = _kind.fires_threshold
fires_zone = _kind.fires_zone
body_counts = _kind.body_counts
expected_counts = _kind.expected_counts
newest_state = _kind.newest_state
