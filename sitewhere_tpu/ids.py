"""Host-edge identity: string tokens → dense int32 handles.

The reference addresses everything by string tokens/UUIDs (device tokens key
Kafka partitioning — ``MicroserviceKafkaProducer.java:106``,
``EventSourcesManager.java:166`` — and every gRPC lookup is by token).
Strings are hostile to TPU execution, so *all* identity is resolved at the
host edge (SURVEY.md §7 "String/ID handling on TPU"): each namespace gets a
:class:`HandleSpace` minting dense, stable ``int32`` handles that index
registry/state tensors directly.  Handles are never reused within a space's
lifetime unless explicitly freed, and the mapping is serializable so
checkpoints can restore it (reference analog: Mongo `_id` ↔ token indexes).
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
from typing import Dict, Iterable, List, Optional

NULL_ID = -1


def _tt_set(table, token: str, hid: int) -> None:
    """Mirror one mapping into a C TokenTable, skipping tokens that are
    not UTF-8-encodable (lone surrogates).  Such tokens can never match
    on the resolved wire path anyway — the C scanner only accepts strict
    UTF-8 payload bytes and bails on escape sequences — so omitting them
    just routes their (impossible) lines through the Python fallback."""
    try:
        table.set(token, hid)
    except UnicodeEncodeError:
        pass


def _tt_discard(table, token: str) -> None:
    try:
        table.discard(token)
    except UnicodeEncodeError:
        pass


def stable_hash64(token: str) -> int:
    """Collision-safe 64-bit content hash of a token.

    Used for cross-process-stable identity (e.g. alternate-id event
    deduplication, reference ``AlternateIdDeduplicator.java``) — NOT for
    registry indexing, which uses dense minted handles.
    """
    digest = hashlib.blake2b(token.encode("utf-8"), digest_size=8).digest()
    return int.from_bytes(digest, "little", signed=True)


class HandleSpace:
    """Mints dense int32 handles for one namespace of string tokens.

    Thread-safe; the ingest frontends resolve tokens concurrently while the
    management services mint new handles (reference analog: the near-cache in
    ``CachedDeviceManagementApiChannel.java`` in front of Mongo lookups —
    here the "cache" IS the authoritative map and lookup is O(1) exact).
    """

    def __init__(self, name: str, capacity: int = 1 << 22):
        self.name = name
        self.capacity = capacity
        self._lock = threading.Lock()
        self._token_to_id: Dict[str, int] = {}
        self._id_to_token: List[Optional[str]] = []
        self._free: List[int] = []
        # C-side mirror for the resolved wire scanner (built lazily by
        # native_table(); every mutator keeps it in sync under _lock).
        self._native = None

    def __len__(self) -> int:
        return len(self._token_to_id)

    def __contains__(self, token: str) -> bool:
        return token in self._token_to_id

    def lookup(self, token: str) -> int:
        """Return the handle for ``token`` or NULL_ID if unknown."""
        return self._token_to_id.get(token, NULL_ID)

    def lookup_many(self, tokens: Iterable[str]) -> List[int]:
        get = self._token_to_id.get
        return [get(t, NULL_ID) for t in tokens]

    def mint(self, token: str) -> int:
        """Return the handle for ``token``, minting a new one if needed."""
        hid = self._token_to_id.get(token, NULL_ID)
        if hid != NULL_ID:
            return hid
        with self._lock:
            hid = self._token_to_id.get(token, NULL_ID)
            if hid != NULL_ID:
                return hid
            return self._mint_locked(token)

    def _mint_locked(self, token: str) -> int:
        if self._free:
            hid = self._free.pop()
            self._id_to_token[hid] = token
        else:
            hid = len(self._id_to_token)
            if hid >= self.capacity:
                raise RuntimeError(
                    f"HandleSpace '{self.name}' exhausted at {self.capacity}"
                )
            self._id_to_token.append(token)
        self._token_to_id[token] = hid
        if self._native is not None:
            _tt_set(self._native, token, hid)
        return hid

    def free(self, token: str) -> None:
        """Release a handle for reuse (e.g. device deleted)."""
        with self._lock:
            hid = self._token_to_id.pop(token, NULL_ID)
            if hid != NULL_ID:
                self._id_to_token[hid] = None
                self._free.append(hid)
                if self._native is not None:
                    _tt_discard(self._native, token)

    def native_table(self):
        """C-side byte->id mirror for the resolved wire scanner, or None.

        Built lazily on first use (the device space is the only one the
        wire path resolves at rate); after that every mint/free keeps it
        in sync, so the scanner's lookups match ``lookup`` exactly.  The
        scanner resolves GIL-held and mutators run GIL-held too, so no
        extra synchronization is needed on the C side.
        """
        if self._native is not None:
            return self._native
        from sitewhere_tpu.native import load_swwire

        mod = load_swwire()
        if mod is None or not hasattr(mod, "TokenTable"):
            return None
        with self._lock:
            if self._native is None:
                table = mod.TokenTable()
                for token, hid in self._token_to_id.items():
                    _tt_set(table, token, hid)
                self._native = table
        return self._native

    def token_of(self, hid: int) -> Optional[str]:
        """Reverse lookup (host-side only, e.g. for REST responses)."""
        if 0 <= hid < len(self._id_to_token):
            return self._id_to_token[hid]
        return None

    def tokens(self) -> List[str]:
        return list(self._token_to_id)

    # --- serialization (checkpoint/resume; SURVEY.md §5 checkpointing) ---

    def to_dict(self) -> dict:
        with self._lock:
            return {
                "name": self.name,
                "capacity": self.capacity,
                "id_to_token": list(self._id_to_token),
            }

    @classmethod
    def from_dict(cls, data: dict) -> "HandleSpace":
        space = cls(data["name"], data["capacity"])
        space.load_state(data["id_to_token"])
        return space

    def load_state(self, id_to_token) -> None:
        """Restore IN PLACE — components capture bound ``lookup``/``mint``
        methods at construction (e.g. the batcher's resolvers), so resume
        must mutate the existing space, never swap the object."""
        with self._lock:
            self._id_to_token = list(id_to_token)
            self._token_to_id = {
                t: hid for hid, t in enumerate(self._id_to_token)
                if t is not None
            }
            self._free = [hid for hid, t in enumerate(self._id_to_token)
                          if t is None]
            if self._native is not None:
                # Build a fully-populated replacement and SWAP — readers
                # (the dispatcher re-fetches per payload) see a complete
                # old or complete new table, matching the atomicity of
                # the _token_to_id dict assignment above.  An in-place
                # clear()+set() rebuild would expose an empty/partial
                # table to a concurrent resolved decode.
                from sitewhere_tpu.native import load_swwire

                mod = load_swwire()
                table = mod.TokenTable() if mod is not None else None
                if table is not None:
                    for token, hid in self._token_to_id.items():
                        _tt_set(table, token, hid)
                self._native = table


class IdentityMap:
    """The full set of handle namespaces used by the framework.

    One per id column in :mod:`sitewhere_tpu.schema`.  Mirrors the entity
    kinds of the reference model (devices, assignments, device types, areas,
    customers, assets, tenants, measurement names, alert types, commands).
    """

    SPACES = (
        "device",
        "assignment",
        "device_type",
        "area",
        "customer",
        "asset",
        "tenant",
        "mtype",
        "alert_type",
        "command",
        "invocation",
        "zone",
        "user",
        "area_type",
        "customer_type",
        "device_group",
        "schedule",
        "batch_operation",
    )

    def __init__(self, capacity: int = 1 << 22):
        self.spaces: Dict[str, HandleSpace] = {
            name: HandleSpace(name, capacity) for name in self.SPACES
        }

    def __getattr__(self, name: str) -> HandleSpace:
        try:
            return self.__dict__["spaces"][name]
        except KeyError:
            raise AttributeError(name) from None

    def save(self, path: str) -> None:
        payload = {name: space.to_dict() for name, space in self.spaces.items()}
        tmp = f"{path}.tmp.{os.getpid()}"
        # dumps + one binary write, not dump() into a text file: one pass
        # of the C encoder (ASCII out) and a write that lets the GIL go.
        # dump() walks the tree in Python and the text layer re-buffers
        # every chunk, which holds the GIL in turns three times as long
        # over a mesh registry's millions of tokens (same bytes either way)
        blob = json.dumps(payload).encode("ascii")
        with open(tmp, "wb") as f:
            f.write(blob)
            f.flush()
            os.fsync(f.fileno())  # durable before the rename commits it —
            # a checkpoint manifest fsynced later must never point at
            # identity data still sitting in the page cache
        os.replace(tmp, path)  # atomic: a crash mid-dump can't corrupt the map

    @classmethod
    def load(cls, path: str) -> "IdentityMap":
        with open(path) as f:
            payload = json.load(f)
        im = cls()
        for name, data in payload.items():
            im.spaces[name] = HandleSpace.from_dict(data)
        return im

    def load_into(self, path: str) -> None:
        """Restore every space IN PLACE (see ``HandleSpace.load_state``)."""
        with open(path) as f:
            payload = json.load(f)
        for name, data in payload.items():
            space = self.spaces.get(name)
            if space is None:
                self.spaces[name] = HandleSpace.from_dict(data)
            else:
                space.capacity = data["capacity"]
                space.load_state(data["id_to_token"])
