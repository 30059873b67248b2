"""Dispatcher intake: share of the rows taken through the wire intake in
the window that came in under a tenant other than ``default``
(``ingest.wire_rows_tenant`` over ``ingest.wire_rows``), in percent: 100
in a cell whose every send names its tenant, or the demux is bypassed.
None where the program keeps no such counters or no row came in."""

TENANT, ALL = "ingest.wire_rows_tenant", "ingest.wire_rows"


def read(run):
    if TENANT not in run.marks1 or not run.counter(ALL):
        return None
    return 100.0 * run.counter(TENANT) / run.counter(ALL)
