"""Dispatcher intake: mean milliseconds of ``Journal.append`` per wire
payload (lock wait and the fsync of every 256th included) - window
delta of ``ingest.journal_append_s``: durability's cost on the intake
path."""


def read(run):
    seconds, count = run.timer("ingest.journal_append_s")
    return seconds / count * 1e3 if count else None
