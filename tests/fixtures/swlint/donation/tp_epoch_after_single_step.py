"""DN001: the single step donates the epoch it is handed; merging the
step's output against that epoch afterwards reads deleted buffers (the
reason the step goes through ``DeviceStateManager.step_packed``)."""
from sitewhere_tpu.pipeline.packed import build_packed_step


def dispatch(manager, tables, bi, bf):
    step = build_packed_step(donate=True)
    epoch = manager.current_packed
    out = step(tables, epoch, bi, bf)
    manager.commit_packed(out[0], present_now=out[3], read_epoch=epoch)
    return out
