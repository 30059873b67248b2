"""Checkpoint/resume: periodic durable snapshots of all memory-resident state.

The reference never snapshots because nothing lives in memory: the whole
model is durable in MongoDB (``service-device-management/.../mongodb/
MongoDeviceManagement.java``) and stream position lives in Kafka committed
offsets (``MicroserviceKafkaConsumer.java:94``).  Here the model lives in
host dicts + device tensors for speed, so durability is explicit:

- a :class:`Checkpointer` snapshots the identity map, registry-mirror
  columns, DeviceState tensors, every management store, and every
  registered per-component :class:`StateProvider` (live analytics/CEP
  operator state, ingest dedup tables, forward-spool cursors) into
  ``data_dir/checkpoint/`` on an interval and at shutdown;
- stream position is the ingest :class:`~sitewhere_tpu.ingest.journal.
  JournalReader` committed offset (commit-after-egress, owned by the
  dispatcher);
- restart = restore the newest complete snapshot, then replay journal
  records past each component's as-of offset (at-least-once, exactly the
  reference's crash contract: "events stack up in Kafka… resume where it
  left off").

Per-component offsets: every snapshot section records the journal offset
it is consistent as-of — the committed offset captured at save START for
the pipeline-fed sections (conservative: committed only grows, and the
commit gate guarantees all effects below it have landed), and the exact
applied offset for sections that track their own position (the analytics
runner).  Restore replays from the MINIMUM of the restored offsets, so a
snapshot taken mid-stream still converges: each component re-derives
exactly what it is missing (H-STREAM's durable-operator-state
requirement, arXiv:2108.03485; the offset-consistent recovery semantics
of arXiv:1807.07724).

Atomicity + torn-snapshot tolerance: every file is written ``tmp → fsync
→ os.replace`` and a ``MANIFEST.json`` naming the snapshot generation is
replaced LAST — a crash mid-save leaves the previous manifest pointing at
the previous complete file set.  Beyond that, snapshot sections are
CRC-framed, versioned records (:func:`write_framed`): a torn, truncated,
or bit-rotted section is DETECTED at restore and the whole generation is
abandoned in favor of the previous complete one (retained on disk for
exactly this purpose; the manifest anchor ``manifest-<gen>.json`` of the
previous generation survives the MANIFEST swap).  A section whose schema
version is not supported is skipped with a log line — never a mid-boot
crash.  Only when every retained generation fails does restore report a
fresh boot.

Consistency: each component is snapshotted under its own lock, not one
global freeze, so a write racing the save can land in one component's
snapshot and not another's.  The skew is harmless under the at-least-once
contract: journal replay re-derives pipeline effects, and the snapshot
order (stores → tensors → identity LAST) ensures a token minted mid-save
resolves to a handle whose registry row is simply still inactive —
reported unregistered and replayed, never silently dropped.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import json
import logging
import os
import pickle
import struct
import threading
import time
import zlib
from dataclasses import fields as dataclass_fields
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from sitewhere_tpu.runtime import faults
from sitewhere_tpu.runtime.lifecycle import LifecycleComponent
from sitewhere_tpu.runtime.metrics import MetricsRegistry
from sitewhere_tpu.runtime.process import name_os_thread

logger = logging.getLogger("sitewhere_tpu.checkpoint")

# Host-dict state per Instance attribute: (attr name on Instance, attrs to
# snapshot).  Entities are plain dataclasses — pickled by value.
_STORE_ATTRS = {
    "device_management": (
        "device_types", "devices", "assignments", "area_types", "areas",
        "customer_types", "customers", "zones", "device_groups", "alarms",
    ),
    "users": ("_users", "_authorities"),
    "tenants": ("_tenants", "_templates", "_datasets"),
    "assets": ("_types", "_assets"),
    "schedules": ("schedules", "jobs", "_fires"),
    "batch_ops": ("operations",),
    "rules": ("_rules", "_slots", "_free"),
}

_MIRROR_ARRAYS = (
    "active", "tenant_id", "device_type_id", "assignment_id",
    "assignment_status", "area_id", "customer_id", "asset_id",
    "z_active", "z_tenant", "z_area", "z_verts", "z_nvert",
    "z_condition", "z_alert_code", "z_alert_level",
)

# framed snapshot-section format (see write_framed)
SNAP_MAGIC = b"SWSNAP1\n"
_FRAME = struct.Struct("<II")  # (length, crc32) — the journal's framing
MANIFEST_VERSION = 2
# 1: one pickle of {store: {attr: container}}; 2: each store's containers
# pickled on their own under the store's lock ({store: bytes})
STORES_VERSION = 2
_SUPPORTED_STORES_VERSIONS = {1, 2}
# section names owned by the checkpointer itself — providers may not
# register under them
_RESERVED_SECTIONS = frozenset({"stores", "mirror", "state", "identity"})
#: the numbered blocks of ``Checkpointer.save``, in order
SAVE_PHASES = ("stores", "mirror", "state", "identity", "providers",
               "manifest")


class SnapshotCorrupt(Exception):
    """A snapshot section failed its CRC/framing/decode check — the
    generation is torn; restore falls back to the previous one."""


def _freeze_store(obj, keys) -> bytes:
    """One store's containers pickled UNDER its lock: entities are
    mutated IN PLACE (``update_fields``) and carry mutable sub-containers
    (metadata, authority lists), so the walk over live objects has to
    happen while no writer can run.  The C pickler is that walk; not a
    ``copy.deepcopy`` pickled after the lock, which is pure Python and
    holds the lock (and, in 5 ms turns, the GIL against the egress
    worker) eight times as long at 131k devices."""
    lock = getattr(obj, "_lock", None)
    with lock if lock is not None else contextlib.nullcontext():
        return pickle.dumps({k: getattr(obj, k) for k in keys}, protocol=4)


def _thaw_stores(stores: dict, unpickle) -> dict:
    """A version-2 stores section back to ``{store: {attr: container}}``
    (a version-1 section already is)."""
    def thaw(v):
        return unpickle(v) if isinstance(v, bytes) else v

    for attr, values in stores.items():
        if attr == "__engines__":
            for facades in values.values():
                for name in facades:
                    facades[name] = thaw(facades[name])
        else:
            stores[attr] = thaw(values)
    return stores


def merge_store(obj, values: Dict[str, object]) -> None:
    """Restore snapshotted attributes into a live store IN PLACE where
    possible (dict containers are cleared+updated so components holding
    references keep seeing the store).  A store that derives anything
    from those attributes drops it in ``_on_restored``."""
    for k, v in values.items():
        current = getattr(obj, k)
        if isinstance(current, dict) and isinstance(v, dict):
            current.clear()
            current.update(v)
        else:
            setattr(obj, k, v)
    on_restored = getattr(obj, "_on_restored", None)
    if on_restored is not None:
        on_restored()


def _atomic_write(path: str, write_fn) -> None:
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        write_fn(f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def write_framed(path: str, header: Dict[str, object],
                 payload: bytes) -> None:
    """Write one CRC-framed, versioned snapshot section: magic, then a
    JSON header record and the payload record, each ``[len][crc32]``
    prefixed (the journal's record framing) — a torn or corrupted write
    is detectable at restore instead of surfacing as an unpickling crash
    mid-boot.  tmp → fsync → replace, like every snapshot file."""
    head = json.dumps(header, separators=(",", ":")).encode()

    def _write(f):
        f.write(SNAP_MAGIC)
        for blob in (head, payload):
            f.write(_FRAME.pack(len(blob), zlib.crc32(blob)))
            f.write(blob)

    _atomic_write(path, _write)


def read_framed(path: str,
                component: Optional[str] = None
                ) -> Tuple[Dict[str, object], bytes]:
    """Read + verify one framed section; raises :class:`SnapshotCorrupt`
    on any framing/CRC/decode violation (never a decoder-specific
    exception — the restore fallback catches ONE type)."""
    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError as e:
        raise SnapshotCorrupt(f"{path}: {e}") from e
    if not data.startswith(SNAP_MAGIC):
        raise SnapshotCorrupt(f"{path}: bad magic")
    pos = len(SNAP_MAGIC)
    blobs: List[bytes] = []
    for _ in range(2):
        if pos + _FRAME.size > len(data):
            raise SnapshotCorrupt(f"{path}: truncated frame header")
        length, crc = _FRAME.unpack_from(data, pos)
        pos += _FRAME.size
        blob = data[pos:pos + length]
        pos += length
        if len(blob) < length:
            raise SnapshotCorrupt(f"{path}: truncated payload")
        if zlib.crc32(blob) != crc:
            raise SnapshotCorrupt(f"{path}: CRC mismatch")
        blobs.append(blob)
    try:
        header = json.loads(blobs[0])
    except ValueError as e:
        raise SnapshotCorrupt(f"{path}: unreadable header") from e
    if component is not None and header.get("component") != component:
        raise SnapshotCorrupt(
            f"{path}: component tag {header.get('component')!r} != "
            f"{component!r}")
    return header, blobs[1]


@dataclasses.dataclass
class StateProvider:
    """One pluggable snapshot section (analytics state, dedup tables…).

    ``snapshot_fn() -> (payload_bytes, extra_header)`` — ``extra_header``
    may carry ``as_of`` (the journal offset the payload is consistent
    as-of; None/absent = the checkpointer's conservative committed
    offset).  ``restore_fn(header, payload)`` re-hydrates the component;
    it runs only after the payload passed CRC and version checks."""

    name: str
    snapshot_fn: Callable[[], Tuple[bytes, Optional[Dict[str, object]]]]
    restore_fn: Callable[[Dict[str, object], bytes], None]
    version: int = 1
    supported_versions: Optional[frozenset] = None

    def accepts(self, version) -> bool:
        if self.supported_versions is not None:
            return version in self.supported_versions
        return version == self.version


class Checkpointer(LifecycleComponent):
    """Periodic + shutdown snapshots of one :class:`Instance`'s state."""

    def __init__(self, instance, interval_s: float = 30.0,
                 prune_journal: bool = False):
        super().__init__(name="checkpointer")
        self.instance = instance
        self.interval_s = float(interval_s)
        self.prune_journal = bool(prune_journal)
        self.dir = os.path.join(instance.data_dir, "checkpoint")
        os.makedirs(self.dir, exist_ok=True)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._save_lock = threading.Lock()
        self._providers: Dict[str, StateProvider] = {}
        self.last_saved_at: Optional[float] = None
        # crash-recovery surface (filled by restore()):
        self.restored_generation: Optional[int] = None
        self.restored_offsets: Dict[str, int] = {}
        #: minimum restored as-of offset — Instance.start replays the
        #: journal from here so every component re-derives what its
        #: snapshot is missing (None = no offsets restored: replay from
        #: the committed offset, the pre-offset-contract behavior)
        self.replay_floor: Optional[int] = None
        self.restore_s: float = 0.0
        # one periodic checkpoint, start to manifest swap (inside
        # _save_lock), and its six phases — each a timer and, in a
        # profiler capture, a span of the same name
        metrics = getattr(instance, "metrics", None) or MetricsRegistry()
        self._m_save = metrics.timer("checkpoint.save_s")
        self._m_phase = {
            p: metrics.timer(f"checkpoint.phase_{p}_s") for p in SAVE_PHASES
        }
        candidates = self._manifest_candidates()
        self.generation = candidates[0][0] if candidates else -1

    def register_provider(self, provider: StateProvider) -> None:
        """Register a per-component snapshot section.  Must happen before
        :meth:`restore` (Instance wires providers, then restores)."""
        if provider.name in _RESERVED_SECTIONS:
            raise ValueError(f"section name {provider.name!r} is reserved")
        self._providers[provider.name] = provider

    def saving_within(self, seconds: float) -> bool:
        """Did a save run at any time in the last ``seconds``: one
        holds the save lock now, or the last one ended inside them (the
        stall witness's ``save_probe``)."""
        return self._save_lock.locked() or (
            self.last_saved_at is not None
            and time.time() - self.last_saved_at <= seconds)

    # -- manifest -----------------------------------------------------------

    @property
    def _manifest_path(self) -> str:
        return os.path.join(self.dir, "MANIFEST.json")

    def _manifest(self) -> dict:
        try:
            with open(self._manifest_path) as f:
                return json.load(f)
        except (FileNotFoundError, ValueError):
            return {}

    def _manifest_candidates(self) -> List[Tuple[int, dict]]:
        """Usable manifests, newest generation first: the MANIFEST swap
        target plus the per-generation anchors retained for torn-snapshot
        fallback.  A manifest that doesn't parse is simply not a
        candidate."""
        seen: Dict[int, dict] = {}
        current = self._manifest()
        if isinstance(current.get("generation"), int):
            seen[current["generation"]] = current
        for path in glob.glob(os.path.join(self.dir, "manifest-*.json")):
            try:
                with open(path) as f:
                    doc = json.load(f)
            except (OSError, ValueError):
                continue
            gen = doc.get("generation")
            if isinstance(gen, int):
                seen.setdefault(gen, doc)
        return sorted(seen.items(), key=lambda kv: -kv[0])

    # -- save ---------------------------------------------------------------

    def save(self) -> Optional[str]:
        """Write one snapshot generation; returns the manifest path."""
        with self._save_lock:
            inst = self.instance
            # As-of capture FIRST (shutdown-ordering audit): the committed
            # offset is read BEFORE any component snapshot, so a claimed
            # offset can never lead the data — commits only grow, and
            # every effect below the captured value has already landed in
            # the components read after it.  Instance.stop runs this save
            # after the dispatcher flush committed the final offset, so a
            # clean shutdown's snapshot covers the whole sealed journal.
            reader = getattr(getattr(inst, "dispatcher", None),
                             "journal_reader", None)
            committed = int(reader.committed) if reader is not None else 0
            journal = getattr(inst, "ingest_journal", None)
            journal_end = int(journal.end_offset) if journal is not None \
                else 0
            with self._m_save.time():
                self._write_generation(inst, committed, journal_end)
            # 7. journal retention (opt-in): everything below the
            # pipeline's durably committed offset is re-derivable from
            # this snapshot + the event store, so whole segments under
            # it reclaim.  payload_ref resolution for rows older than
            # the snapshot becomes unresolvable — every downstream
            # handler already tolerates a missing ref.
            if self.prune_journal:
                if reader is not None:
                    pruned = inst.ingest_journal.prune(reader.committed)
                    if pruned:
                        logger.info(
                            "pruned %d ingest-journal segment(s) below "
                            "committed offset %d", pruned, reader.committed)
            # 8. dead-letter retention: keep the newest N records (the
            # Kafka-retention analog for the dead-letter topics); pruned
            # records stop being listable/requeueable, which is what
            # retention means.  0 disables.
            keep = int(inst.config.get("dead_letters.retain_records",
                                       10_000) or 0)
            if keep > 0:
                cut = inst.dead_letters.end_offset - keep
                if cut > 0 and inst.dead_letters.prune(cut):
                    logger.info("pruned dead-letter segments below %d", cut)
            logger.info("checkpoint generation %d saved (committed=%d)",
                        self.generation, committed)
            return self._manifest_path

    def _write_generation(self, inst, committed: int,
                          journal_end: int) -> None:
        """Write generation ``self.generation + 1`` and commit it with
        the MANIFEST swap — one ``checkpoint.save_s`` span, each numbered
        phase a ``checkpoint.phase_<name>_s`` span inside it (in a
        profiler capture: which phase covers a delivery gap)."""
        phase = self._m_phase
        gen = self.generation + 1
        names: Dict[str, str] = {}
        offsets: Dict[str, int] = {}

        with phase["stores"].time():
            # 1. management stores — each store's containers are pickled
            # under its own lock, so no pickle races a concurrent mutation
            # A gateway instance serves some domains through RemoteDomain
            # facades (rpc/domains.py) — the OWNER checkpoints those
            # stores; snapshotting a facade would capture nothing.
            stores: Dict[str, object] = {
                attr: _freeze_store(getattr(inst, attr), keys)
                for attr, keys in _STORE_ATTRS.items()
                if not getattr(getattr(inst, attr), "_remote_facade_", False)
            }
            # non-default tenant engines' service façades (the default
            # tenant's ARE the instance-level stores above)
            engines = getattr(inst, "engines", None)
            if engines is not None:
                stores["__engines__"] = {
                    eng.tenant.token: {
                        "device_management": _freeze_store(
                            eng.device_management,
                            _STORE_ATTRS["device_management"]),
                        "assets": _freeze_store(
                            eng.asset_management, _STORE_ATTRS["assets"]),
                    }
                    for eng in engines.list_engines()
                    if eng.tenant.token != "default"
                }
            names["stores"] = f"stores-{gen:08d}.swsnap"
            write_framed(
                os.path.join(self.dir, names["stores"]),
                {"component": "stores", "version": STORES_VERSION,
                 "as_of": committed},
                pickle.dumps(stores, protocol=4))
            offsets["stores"] = committed
            # chaos kill point: a death here leaves gen's stores file on
            # disk with no manifest — the previous generation must restore
            faults.crosspoint("crash.mid_checkpoint")

        with phase["mirror"].time():
            # 2. registry mirror columns (+ zone tables + epoch)
            mirror = inst.mirror
            with mirror._lock:
                mirror_arrays = {
                    k: np.array(getattr(mirror, k)) for k in _MIRROR_ARRAYS
                }
                mirror_arrays["epoch"] = np.asarray(mirror.epoch)
                # z_hi drives the published ZoneTable's pow2 trim — a
                # restore without it would trim restored zones away
                mirror_arrays["z_hi"] = np.asarray(mirror.z_hi)
            names["mirror"] = f"mirror-{gen:08d}.npz"
            _atomic_write(
                os.path.join(self.dir, names["mirror"]),
                lambda f: np.savez(f, **mirror_arrays),
            )
            offsets["mirror"] = committed

        with phase["state"].time():
            # 3. device-state tensors (one device→host copy per field);
            # a remoted device_state belongs to the owning host's
            # checkpoints, like any other facade-backed domain
            if not getattr(inst.device_state, "_remote_facade_", False):
                state = inst.device_state.current
                state_arrays = {
                    fld.name: np.asarray(getattr(state, fld.name))
                    for fld in dataclass_fields(state)
                }
                names["state"] = f"state-{gen:08d}.npz"
                _atomic_write(
                    os.path.join(self.dir, names["state"]),
                    lambda f: np.savez(f, **state_arrays),
                )
                offsets["state"] = committed

        with phase["identity"].time():
            # 4. identity map LAST (see module docstring: a token minted
            # mid-save must never be dangling in the restored identity)
            names["identity"] = f"identity-{gen:08d}.json"
            inst.identity.save(os.path.join(self.dir, names["identity"]))

        with phase["providers"].time():
            # 5. registered component providers (analytics/CEP operator
            # state with its exact applied offset, dedup tables, spool
            # cursors…) — a provider crash skips ITS section, never the
            # snapshot: the component then re-derives from the journal
            # like a component that never snapshotted
            for provider in self._providers.values():
                try:
                    payload, extra = provider.snapshot_fn()
                except Exception:
                    logger.exception("state provider %s snapshot failed; "
                                     "section skipped", provider.name)
                    continue
                header = {"component": provider.name,
                          "version": provider.version}
                header.update(extra or {})
                as_of = header.get("as_of")
                header["as_of"] = committed if as_of is None else int(as_of)
                names[provider.name] = f"{provider.name}-{gen:08d}.swsnap"
                write_framed(os.path.join(self.dir, names[provider.name]),
                             header, payload)
                offsets[provider.name] = int(header["as_of"])

        with phase["manifest"].time():
            # 6. manifest: the per-generation anchor first (it is what
            # torn-snapshot fallback finds when a LATER save dies before
            # its swap), then the MANIFEST swap commits the generation
            manifest = {"generation": gen, "files": names,
                        "saved_at": time.time(),
                        "version": MANIFEST_VERSION,
                        "offsets": offsets,
                        "committed": committed,
                        "journal_end": journal_end}
            blob = json.dumps(manifest).encode()
            _atomic_write(
                os.path.join(self.dir, f"manifest-{gen:08d}.json"),
                lambda f: f.write(blob))
            # chaos kill point: gen is fully on disk but not committed —
            # restore must come up on the previous manifest
            faults.crosspoint("crash.pre_manifest")
            _atomic_write(self._manifest_path, lambda f: f.write(blob))
            self.generation = gen
            self.last_saved_at = time.time()
            # keep gen-1 too: torn-generation fallback needs ONE previous
            # complete file set on disk (gc'd once gen+1 commits)
            self._gc(keep=gen - 1)

    def _gc(self, keep: int) -> None:
        for path in glob.glob(os.path.join(self.dir, "*-*.np[zy]")) + \
                glob.glob(os.path.join(self.dir, "*-*.pkl")) + \
                glob.glob(os.path.join(self.dir, "*-*.swsnap")) + \
                glob.glob(os.path.join(self.dir, "*-*.json")):
            base = os.path.basename(path)
            try:
                gen = int(base.rsplit("-", 1)[1].split(".")[0])
            except (IndexError, ValueError):
                continue
            if gen < keep:
                with contextlib.suppress(OSError):
                    os.remove(path)

    # -- restore ------------------------------------------------------------

    def restore(self) -> bool:
        """Restore the newest COMPLETE snapshot into the live components.

        Called from ``Instance.__init__`` after provider registration,
        before start.  Generations are tried newest-first: every section
        is read and validated (CRC frames, schema versions, parseable
        payloads) BEFORE anything is applied, so a torn generation falls
        back to the previous complete one without leaving components
        half-hydrated.  Returns True if a snapshot was restored; False —
        never an exception — when no usable generation exists (fresh
        boot)."""
        t0 = time.perf_counter()
        for gen, manifest in self._manifest_candidates():
            names = manifest.get("files")
            if not names:
                continue
            try:
                sections = self._load_generation(manifest)
            except Exception as e:  # noqa: BLE001 — one torn file must
                # not take boot down; fall back to the older generation
                logger.warning(
                    "checkpoint generation %s unusable (%s: %s); trying "
                    "the previous generation", gen,
                    type(e).__name__, e)
                continue
            self.restored_offsets = {
                k: int(v)
                for k, v in (manifest.get("offsets") or {}).items()
                if k in sections
            }
            self._apply_generation(manifest, sections)
            self.restored_generation = int(gen)
            if self.restored_offsets:
                self.replay_floor = min(self.restored_offsets.values())
            self.restore_s = time.perf_counter() - t0
            metrics = getattr(self.instance, "metrics", None)
            if metrics is not None:
                metrics.gauge("recovery.restore_s").set(self.restore_s)
            logger.info(
                "restored checkpoint generation %s in %.3fs "
                "(replay floor %s; %d devices, %d users)",
                gen, self.restore_s, self.replay_floor,
                len(self.instance.identity.device),
                len(self.instance.users.list_users()))
            return True
        return False

    def _load_generation(self, manifest: dict) -> Dict[str, object]:
        """Read + validate every section of one generation into host
        memory WITHOUT touching live components.  Raises on corruption
        (the caller falls back); version-unsupported sections are logged
        and omitted from the result."""
        names = manifest["files"]
        sections: Dict[str, object] = {}

        # identity: parse up front so a torn file fails the generation
        # here, not inside load_into after other sections applied
        with open(os.path.join(self.dir, names["identity"])) as f:
            json.load(f)

        # management stores: framed current format, raw pickle legacy
        stores_path = os.path.join(self.dir, names["stores"])
        if names["stores"].endswith(".swsnap"):
            header, payload = read_framed(stores_path, component="stores")
            if header.get("version") not in _SUPPORTED_STORES_VERSIONS:
                logger.warning(
                    "stores section version %s unsupported; skipping "
                    "store restore", header.get("version"))
            else:
                sections["stores"] = _thaw_stores(
                    self._unpickle(payload, stores_path),
                    lambda blob: self._unpickle(blob, stores_path))
        else:
            with open(stores_path, "rb") as f:
                sections["stores"] = self._unpickle(f.read(), stores_path)

        # registry mirror / device state: npz (zip CRC verifies members)
        try:
            with np.load(os.path.join(self.dir, names["mirror"])) as z:
                sections["mirror"] = {k: np.array(z[k]) for k in z.files}
            if "state" in names:
                with np.load(os.path.join(self.dir, names["state"])) as z:
                    sections["state"] = {k: np.array(z[k])
                                         for k in z.files}
        except Exception as e:
            raise SnapshotCorrupt(f"tensor section unreadable: {e}") from e

        # provider sections
        for name, fname in names.items():
            if name in _RESERVED_SECTIONS:
                continue
            provider = self._providers.get(name)
            if provider is None:
                logger.warning("snapshot section %s has no registered "
                               "provider; ignored", name)
                continue
            header, payload = read_framed(
                os.path.join(self.dir, fname), component=name)
            if not provider.accepts(header.get("version")):
                logger.warning(
                    "snapshot section %s version %s unsupported "
                    "(provider speaks %s); section skipped — state "
                    "re-derives from the journal", name,
                    header.get("version"), provider.version)
                continue
            sections[name] = (provider, header, payload)
        return sections

    @staticmethod
    def _unpickle(payload: bytes, path: str):
        try:
            return pickle.loads(payload)
        except Exception as e:  # noqa: BLE001 — unpickling raises anything
            raise SnapshotCorrupt(f"{path}: unpicklable ({e})") from e

    def _apply_generation(self, manifest: dict,
                          sections: Dict[str, object]) -> None:
        """Hydrate live components from pre-validated sections."""
        import jax.numpy as jnp

        inst = self.instance
        names = manifest["files"]

        # identity — strictly in place: the batcher captured bound
        # lookup/mint methods of the existing HandleSpace objects
        inst.identity.load_into(os.path.join(self.dir, names["identity"]))

        # management stores
        stores = sections.get("stores")
        if stores is not None:
            # non-default engine stores hydrate lazily when the engine
            # manager (re)creates each engine (_make_tenant_engine)
            inst._engine_snapshots = stores.pop("__engines__", {})
            for attr, values in stores.items():
                obj = getattr(inst, attr)
                if getattr(obj, "_remote_facade_", False):
                    continue  # domain remoted since the snapshot
                merge_store(obj, values)
            # restored rules must rebuild their device table
            if hasattr(inst.rules, "_dirty"):
                inst.rules._dirty = True

        # registry mirror
        z = sections["mirror"]
        with inst.mirror._lock:
            for k in _MIRROR_ARRAYS:
                getattr(inst.mirror, k)[:] = z[k]
            inst.mirror.epoch = int(z["epoch"])
            # pre-z_hi snapshots: fall back to the conservative full
            # capacity (correct, just untrimmed until zones change)
            inst.mirror.z_hi = (int(z["z_hi"]) if "z_hi" in z
                                else inst.mirror.max_zones)
            inst.mirror._dirty = True
            inst.mirror._zones_dirty = True

        # device state — tolerant of fields added since the snapshot was
        # taken (e.g. ewma_values) AND of shape changes (e.g. a different
        # EWMA scale count): mismatched fields keep their empty init
        # rather than crashing every subsequent pipeline step
        z = sections.get("state")
        if z is not None and not getattr(
                inst.device_state, "_remote_facade_", False):
            current = inst.device_state.current
            known = {
                fld.name: getattr(current, fld.name).shape
                for fld in dataclass_fields(current)
            }
            updates = {}
            skipped = set()
            for k, arr in z.items():
                if k not in known:
                    continue
                if arr.shape != known[k]:
                    logger.warning(
                        "checkpoint field %s shape %s != current %s; "
                        "keeping empty init", k, arr.shape, known[k])
                    skipped.add(k)
                    continue
                updates[k] = jnp.asarray(arr)
            if "ewma_values" in skipped or "ewma_values" not in z:
                # fold_ewma_arrays seeds on last_value_ts_s > 0 — restoring
                # the timestamps without the EWMAs would treat zeroed averages
                # as seeded and drag windowed rules toward 0; drop the
                # measurement stats together so seeding re-occurs
                for k in ("last_value_ts_s", "last_value_ts_ns",
                          "last_values"):
                    updates.pop(k, None)
            inst.device_state.commit(current.replace(**updates))

        # provider sections — a restore_fn crash degrades to "this
        # component never snapshotted", never a failed boot
        for name, entry in sections.items():
            if name in ("stores", "mirror", "state"):
                continue
            provider, header, payload = entry
            try:
                provider.restore_fn(header, payload)
            except Exception:
                logger.exception(
                    "state provider %s restore failed; its state "
                    "re-derives from the journal", name)
                self.restored_offsets.pop(name, None)

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> None:
        super().start()
        self._stop.clear()
        if self.interval_s > 0:
            self._thread = threading.Thread(
                target=self._loop, name="checkpointer-loop", daemon=True
            )
            self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        super().stop()

    def _loop(self) -> None:
        name_os_thread("sw-checkpoint")
        while not self._stop.wait(self.interval_s):
            try:
                self.save()
            except Exception:
                logger.exception("periodic checkpoint failed")
