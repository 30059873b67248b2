"""Multi-host scaffolding: shard ownership + global-batch assembly.

A 1-process cluster is a degenerate but real configuration: all shards
are process-local and make_array_from_process_local_data must accept the
full batch.  True DCN runs need multi-process hardware (documented in
parallel/multihost.py).
"""

import os

import numpy as np
import pytest

from sitewhere_tpu.parallel import mesh as meshmod
from sitewhere_tpu.parallel.multihost import (
    initialize_from_env,
    make_global_batch,
    owned_device_range,
    process_local_shards,
)


def test_initialize_noop_without_env(monkeypatch):
    monkeypatch.delenv("SW_COORDINATOR", raising=False)
    assert initialize_from_env() is False


def test_all_shards_local_in_single_process(mesh8):
    assert process_local_shards(mesh8) == list(range(8))


def test_owned_device_range_matches_router():
    for shard in range(8):
        lo, hi = owned_device_range(shard, 1024, 8)
        assert meshmod.shard_for_device(lo, 1024, 8) == shard
        assert meshmod.shard_for_device(hi - 1, 1024, 8) == shard
    with pytest.raises(ValueError):
        owned_device_range(0, 1001, 8)


def test_make_global_batch_round_trips(mesh8):
    width = 64
    cols = {
        "device_id": np.arange(width, dtype=np.int32),
        "value": np.linspace(0, 1, width, dtype=np.float32),
    }
    out = make_global_batch(mesh8, cols, global_width=width)
    assert out["device_id"].shape == (width,)
    assert len(out["device_id"].sharding.device_set) == 8
    np.testing.assert_array_equal(np.asarray(out["device_id"]),
                                  cols["device_id"])
    np.testing.assert_allclose(np.asarray(out["value"]), cols["value"])


@pytest.mark.slow
def test_two_process_sharded_step(tmp_path):
    """REAL multi-process validation: two OS processes form a
    jax.distributed cluster (loopback coordinator, Gloo collectives —
    the CPU stand-in for DCN), each holding 2 of 4 mesh shards, each
    contributing only its own registry/state rows and batch segment;
    ONE shard_map pipeline step runs across both and the psum'd metrics
    agree everywhere.  See tests/multihost_worker.py."""
    import socket
    import subprocess
    import sys

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    worker = os.path.join(os.path.dirname(__file__), "multihost_worker.py")
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update({
            "SW_COORDINATOR": f"127.0.0.1:{port}",
            "SW_NUM_PROCESSES": "2",
            "SW_PROCESS_ID": str(pid),
            "JAX_PLATFORMS": "cpu",   # one process per chip
            "PYTHONPATH": os.path.dirname(os.path.dirname(worker))
                          + os.pathsep + env.get("PYTHONPATH", ""),
        })
        # fresh XLA_FLAGS: the worker sets its own device count and the
        # conftest's 8-device flag would skew the per-process mesh
        env["XLA_FLAGS"] = ""
        procs.append(subprocess.Popen(
            [sys.executable, worker], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out}"
        assert f"[p{pid}] MULTIPROC OK" in out, out
        assert "processed=64 accepted=64 unregistered=0" in out, out
