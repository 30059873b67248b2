"""Kernels: the least time the chip could take for one step of this
width under this configuration's rules (``roofline.step_floor``, from
shapes and ``peaks.json``) over the measured ``device_step_ms``, in
percent.  Whole step only."""

from benchmarks import roofline


def read(run):
    step_ms = (run.trace or {}).get("device_step_ms")
    if not step_ms:
        return None
    floor = roofline.step_floor(
        run.device["kind"], run.width // run.n_shards,
        **roofline.rule_shape(run.config["rules"]))
    return 100.0 * floor["seconds"] * 1e3 / step_ms
