"""D2H and egress: share of the window's committed seals that the
native writer made (``store.seals_native`` over ``store.seals``), in
percent: zone maps, Blooms and the segment file written in one call
with the GIL released, where the rest took ``np.savez``.  None where
the program keeps no such counter, or nothing was sealed in the
window."""

NATIVE = "store.seals_native"


def read(run):
    if NATIVE not in run.marks1:
        return None
    seals = run.counter("store.seals")
    return 100.0 * run.counter(NATIVE) / seals if seals else None
