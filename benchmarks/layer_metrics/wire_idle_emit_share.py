"""Batcher to ring: share of the rows taken through the wire intake in
the window that left in an idle emission (``ingest.rows_emitted_idle``
over ``ingest.wire_rows``), in percent: a payload that found nothing
pending and no plan outstanding, emitted in its own intake call instead
of waiting for the loop's deadline poll.  None where the program keeps
no such counter or no row came in."""

IDLE, ALL = "ingest.rows_emitted_idle", "ingest.wire_rows"


def read(run):
    if IDLE not in run.marks1 or not run.counter(ALL):
        return None
    return 100.0 * run.counter(IDLE) / run.counter(ALL)
