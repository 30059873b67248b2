"""The event store's native seal (swwire.c ``seal_segment``) against the
Python writer it stands in for.

A seal is one native call with the GIL released: zone maps, Blooms and
the whole ``.npz``.  It must make the file ``np.savez`` makes — the same
members, byte for byte, so ``np.load``, ``open_segment`` and every
reader stay as they are — and the same in-memory segment.
``SW_NATIVE=0`` keeps the whole Python path.  (A store sealed through
it runs the store's own suites in ``test_native_seal_store.py``.)
"""

import json
import os
import subprocess
import sys
import threading
import zipfile

import numpy as np
import pytest

from sitewhere_tpu import native
from sitewhere_tpu.store.segment import (
    BLOOM_COLUMNS,
    COLUMN_NAMES,
    FLOAT_COLUMNS,
    INT_COLUMNS,
    ColumnCache,
    Segment,
    open_segment,
    seal_segment_file,
    unpack_cols,
    write_segment_file,
)

SW = native.load_swwire()
needs_native = pytest.mark.skipif(
    SW is None, reason="native toolchain unavailable")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ALLOC = 12_000  # the shard buffer the rows are views of
REPLACES = ((3, 0, 5), (4, 5, 7), (11, 12, 1))


def shard_buffer(seed=7):
    """A packed pair wider than any segment here: full-range ints (so
    the Blooms hash negative ids), a realistic ts_s row, normal floats."""
    rng = np.random.default_rng(seed)
    ints = rng.integers(-2**31, 2**31, size=(len(INT_COLUMNS), ALLOC),
                        dtype=np.int64).astype(np.int32)
    ints[INT_COLUMNS.index("ts_s")] = 1_753_900_000 + rng.integers(
        -5000, 5000, ALLOC)
    flts = rng.standard_normal((len(FLOAT_COLUMNS), ALLOC)).astype(
        np.float32)
    return ints, flts


def seal_with(module, monkeypatch, path, ints, flts, replaces):
    """Seal one segment with the native module loaded (or not)."""
    with monkeypatch.context() as m:
        m.setattr(native, "_swwire", module)
        return seal_segment_file(path, 41, ints, flts, shard=2,
                                 shard_count=4, replaces=replaces,
                                 sync=True)


CASES = [pytest.param(n, rep, id=f"n{n}-{'replaces' if rep else 'plain'}")
         for n in (0, 1, 257, 10_000) for rep in (None, REPLACES)]


@needs_native
@pytest.mark.parametrize("n,replaces", CASES)
def test_native_and_python_files_hold_the_same_members(tmp_path,
                                                       monkeypatch, n,
                                                       replaces):
    ints, flts = shard_buffer()
    rows = (ints[:, 3:3 + n], flts[:, 3:3 + n])  # strided row views
    a, used_a = seal_with(SW, monkeypatch, str(tmp_path / "a.npz"),
                          *rows, replaces)
    b, used_b = seal_with(None, monkeypatch, str(tmp_path / "b.npz"),
                          *rows, replaces)
    assert (used_a, used_b) == (True, False)
    assert not list(tmp_path.glob("*.tmp.*"))
    with zipfile.ZipFile(tmp_path / "a.npz") as za, \
            zipfile.ZipFile(tmp_path / "b.npz") as zb:
        assert za.testzip() is None   # every member's CRC-32 holds
        assert sorted(za.namelist()) == sorted(zb.namelist())
        for name in za.namelist():    # headers and data, byte for byte
            assert za.read(name) == zb.read(name), name
    with np.load(tmp_path / "a.npz") as la, np.load(tmp_path / "b.npz") as lb:
        assert sorted(la.files) == sorted(lb.files)
        assert ("_meta_replaces" in la.files) == bool(replaces)
        for name in la.files:
            assert la[name].dtype == lb[name].dtype, name
            assert la[name].tobytes() == lb[name].tobytes(), name
        for i, name in enumerate(INT_COLUMNS):
            assert np.array_equal(la[name], rows[0][i])


@needs_native
@pytest.mark.parametrize("n,replaces", CASES)
def test_open_segment_reads_the_same_metadata_from_both(tmp_path,
                                                        monkeypatch, n,
                                                        replaces):
    ints, flts = shard_buffer(seed=n)
    rows = (ints[:, :n], flts[:, :n])
    made = [seal_with(mod, monkeypatch, str(tmp_path / f"{tag}.npz"),
                      *rows, replaces)[0]
            for tag, mod in (("a", SW), ("b", None))]
    cache = ColumnCache(1 << 20)
    opened = [open_segment(41, str(tmp_path / f"{tag}.npz"), cache)
              for tag in "ab"]
    reference = Segment(41, unpack_cols(*rows), shard=2, shard_count=4)
    for seg in made + opened:
        assert (seg.n, seg.min_ts, seg.max_ts, seg.bounds, seg.shard,
                seg.shard_count, seg.replaces) == (
            reference.n, reference.min_ts, reference.max_ts,
            reference.bounds, 2, 4, replaces)
        assert sorted(seg.blooms) == sorted(BLOOM_COLUMNS)
        for name in BLOOM_COLUMNS:
            assert np.array_equal(seg.blooms[name], reference.blooms[name])
    # the sealed segment keeps its rows resident until detach
    assert np.array_equal(made[0].col("device_id"),
                          rows[0][INT_COLUMNS.index("device_id")])
    assert made[0].order_key == made[1].order_key == 41


@needs_native
def test_write_segment_file_takes_the_native_writer_for_a_column_dict(
        tmp_path, monkeypatch):
    ints, flts = shard_buffer(seed=3)
    cols = unpack_cols(ints[:, :257], flts[:, :257])
    cols = {name: cols[name] for name in COLUMN_NAMES}   # schema order
    seg = Segment(9, cols, shard=1, shard_count=4)
    seg.replaces = REPLACES
    for tag, module in (("a", SW), ("b", None)):
        with monkeypatch.context() as m:
            m.setattr(native, "_swwire", module)
            write_segment_file(str(tmp_path / f"{tag}.npz"), cols, seg)
    with zipfile.ZipFile(tmp_path / "a.npz") as za, \
            zipfile.ZipFile(tmp_path / "b.npz") as zb:
        assert sorted(za.namelist()) == sorted(zb.namelist())
        for name in za.namelist():
            assert za.read(name) == zb.read(name), name


@needs_native
def test_an_io_failure_raises_oserror_naming_the_file(tmp_path,
                                                      monkeypatch):
    ints, flts = shard_buffer()
    path = str(tmp_path / "gone" / "a.npz")
    with pytest.raises(FileNotFoundError) as info:
        seal_with(SW, monkeypatch, path, ints[:, :8], flts[:, :8], None)
    assert str(tmp_path / "gone") in info.value.filename


@needs_native
def test_concurrent_native_seals_each_make_the_python_file(tmp_path,
                                                           monkeypatch):
    """Seals run with the GIL released, so they overlap: more threads
    than cores, a short switch interval, and every file still the one
    np.savez makes of the same rows."""
    ints, flts = shard_buffer(seed=11)
    sizes = [(7 * i) % 300 for i in range(48)]
    with monkeypatch.context() as m:
        m.setattr(native, "_swwire", None)
        for i, n in enumerate(sizes):
            seal_segment_file(str(tmp_path / f"py{i}.npz"), i,
                              ints[:, i:i + n], flts[:, i:i + n])
    errors = []

    def seal(lo, hi):
        try:
            for i in range(lo, hi):
                n = sizes[i]
                _, used = seal_segment_file(
                    str(tmp_path / f"c{i}.npz"), i, ints[:, i:i + n],
                    flts[:, i:i + n], sync=False)
                assert used
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    threads = [threading.Thread(target=seal, args=(k, k + 4))
               for k in range(0, len(sizes), 4)]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads) and not errors
    for i in range(len(sizes)):
        with zipfile.ZipFile(tmp_path / f"c{i}.npz") as za, \
                zipfile.ZipFile(tmp_path / f"py{i}.npz") as zb:
            assert {n: za.read(n) for n in za.namelist()} \
                == {n: zb.read(n) for n in zb.namelist()}


def test_sw_native_0_keeps_the_python_writer(tmp_path):
    """With the native tier switched off the store seals through
    np.savez, and the native share reads 0."""
    script = (
        "import json, sys\n"
        "from sitewhere_tpu import native\n"
        "from sitewhere_tpu.runtime.metrics import MetricsRegistry\n"
        "from sitewhere_tpu.store.segmented import SegmentStore\n"
        "sys.path.insert(0, 'tests')\n"
        "from test_segment_store import make_cols\n"
        "assert native.load_swwire() is None\n"
        "reg = MetricsRegistry()\n"
        "store = SegmentStore(sys.argv[1], flush_rows=64, n_shards=2,\n"
        "                     seal_workers=1, metrics=reg)\n"
        "store.start()\n"
        "store.append_columns(make_cols(500))\n"
        "store.flush(sync=True)\n"
        "store.stop()\n"
        "c = reg.snapshot()['counters']\n"
        "print(json.dumps([c['store.seals'], c['store.seals_native'],\n"
        "                  store.total_events]))\n")
    env = dict(os.environ, SW_NATIVE="0", JAX_PLATFORMS="cpu")
    env.pop("SW_NATIVE_LIB", None)
    out = subprocess.run([sys.executable, "-c", script, str(tmp_path)],
                         cwd=_REPO, env=env, capture_output=True,
                         text=True, timeout=100)
    assert out.returncode == 0, out.stderr[-2000:]
    seals, seals_native, total = json.loads(out.stdout.strip().splitlines()[-1])
    assert seals >= 8 and seals_native == 0 and total == 500
