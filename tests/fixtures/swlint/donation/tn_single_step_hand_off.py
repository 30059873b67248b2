"""TN: the single step is handed the epoch under the manager's lock
(``step_packed`` reads, dispatches and commits there), so no caller
holds the donated carry; a retry goes through the hand-off again."""
from sitewhere_tpu.pipeline.packed import build_packed_step


def dispatch(manager, tables, bi, bf):
    step = build_packed_step(donate=True)
    try:
        out = manager.step_packed(step, tables, bi, bf)
    except RuntimeError:
        out = manager.step_packed(step, tables, bi, bf)
    return out[1:]
