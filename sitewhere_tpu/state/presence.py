"""Presence detection: vectorized missing-device sweep.

Reference: ``service-device-state/.../presence/DevicePresenceManager.java``
— a background thread (default check every 10m) queries assignments whose
last interaction predates the missing interval (default 8h) and fires
StateChange events via ``PresenceNotificationStrategies.
SendOnceNotificationStrategy`` (notify once per missing episode).

Here the scan is one jitted pass over the ``DeviceState`` columns: a
device is *newly missing* when it has seen at least one event, is not
already flagged, and its last event is older than the missing interval.
Send-once falls out of the ``presence_missing`` flag itself (the pipeline
step clears it on any accepted event, re-arming notification).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Dict, Optional, Tuple

import jax
import numpy as np

from sitewhere_tpu.ids import NULL_ID
from sitewhere_tpu.runtime.lifecycle import LifecycleComponent
from sitewhere_tpu.runtime.process import name_os_thread
from sitewhere_tpu.schema import DeviceState, EventType

logger = logging.getLogger("sitewhere_tpu.state.presence")

# StateChange codes carried in the alert_code column of STATE_CHANGE events
# (reference: IDeviceStateChangeCreateRequest category/type strings
# "presence"/"missing").
STATE_CHANGE_PRESENCE_MISSING = 1
# Device crossed the numeric-integrity quarantine threshold (cumulative
# NaN/Inf rows — runtime/dispatcher.py _scan_quarantine); rides the same
# STATE_CHANGE egress as presence transitions.
STATE_CHANGE_QUARANTINED = 2


def newly_missing(last_event_type, last_event_ts_s, presence_missing,
                  now_s, missing_after_s) -> jax.Array:
    """``bool[D]``: devices a sweep at ``now_s`` flags — seen at least
    once, not flagged yet, last event older than the missing interval."""
    has_events = last_event_type != NULL_ID
    overdue = (now_s - last_event_ts_s) > missing_after_s
    return has_events & overdue & ~presence_missing


@jax.jit
def presence_sweep(
    state: DeviceState, now_s: jax.Array, missing_after_s: jax.Array
) -> Tuple[DeviceState, jax.Array]:
    """One vectorized presence pass.

    Returns ``(new_state, newly_missing)`` where ``newly_missing`` is a
    ``bool[D]`` mask of devices flagged by THIS sweep (the send-once set).
    """
    newly = newly_missing(
        state.last_event_type, state.last_event_ts_s, state.presence_missing,
        now_s, missing_after_s)
    return state.replace(presence_missing=state.presence_missing | newly), newly


def state_change_columns(
    device_ids: np.ndarray, tenant_ids: np.ndarray, now_s: int,
    code: int = STATE_CHANGE_PRESENCE_MISSING,
) -> Dict[str, np.ndarray]:
    """The STATE_CHANGE rows of the given devices as host columns for
    the ``ingest_arrays`` edge — re-injected through the normal ingest
    path like the reference's presence StateChange events flow back
    through event management.  ``tenant_ids`` aligns with ``device_ids``
    row for row.  Plain numpy, whatever the count: a program built at
    the count's length would compile anew for every new count.
    """
    n = int(np.size(device_ids))
    return {
        "device_id": np.asarray(device_ids, np.int32),
        "tenant_id": np.asarray(tenant_ids, np.int32),
        "event_type": np.full(n, int(EventType.STATE_CHANGE), np.int32),
        "ts_s": np.full(n, int(now_s), np.int32),
        "alert_code": np.full(n, int(code), np.int32),
        # System-generated: must not mark the device present or bump its
        # last-event time (reference isUpdateState() semantics).
        "update_state": np.zeros(n, bool),
    }


class PresenceManager(LifecycleComponent):
    """Background presence checker over a :class:`DeviceStateManager`.

    ``on_state_changes`` receives the STATE_CHANGE rows (host columns,
    :func:`state_change_columns`) of each sweep that found newly-missing
    devices (the notification-strategy hook); wire it to the ingest path
    (``ingest_arrays``) for re-injection.
    """

    def __init__(
        self,
        state_manager,  # DeviceStateManager
        check_interval_s: float = 600.0,  # reference default "10m"
        missing_after_s: int = 8 * 3600,  # reference default "8h"
        on_state_changes: Optional[Callable[[Dict[str, np.ndarray]], None]] = None,
        clock: Optional[Callable[[], float]] = None,
    ):
        super().__init__(name="presence-manager")
        self.state_manager = state_manager
        self.check_interval_s = check_interval_s
        self.missing_after_s = missing_after_s
        self.on_state_changes = on_state_changes
        self._clock = clock or time.time
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.sweeps = 0
        self.total_marked_missing = 0
        # the second the newest COMPLETED sweep judged overdue against
        # (None until one ended): a device overdue by then is reported
        self.last_sweep_s: Optional[int] = None

    def sweep_once(self, now_s: Optional[int] = None) -> int:
        """Run one sweep; returns how many devices were newly marked.

        Reference: one iteration of the ``PresenceChecker`` loop.
        """
        now = int(self._clock()) if now_s is None else now_s
        marked = self.state_manager.apply_presence_sweep(now, self.missing_after_s)
        count = 0
        if marked is not None:
            count = len(marked["device_id"])
            self.total_marked_missing += count
            if self.on_state_changes is not None:
                self.on_state_changes(marked)
        self.sweeps += 1
        self.last_sweep_s = now
        return count

    def _loop(self) -> None:
        name_os_thread("sw-presence")
        while not self._stop.wait(self.check_interval_s):
            try:
                self.sweep_once()
            except Exception:
                logger.exception("presence sweep failed")

    def start(self) -> None:
        super().start()
        self._stop.clear()
        # what a served sweep runs is compiled (or loaded) here, not by
        # the first sweep in service; best-effort, like the dispatcher's
        # warm-up: a failure only defers the compile
        try:
            self.state_manager.warm_presence_programs()
        except Exception:
            logger.warning("presence warm-up failed (compile deferred to "
                           "the first sweep)", exc_info=True)
        self._thread = threading.Thread(
            target=self._loop, name="presence-checker", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        super().stop()
