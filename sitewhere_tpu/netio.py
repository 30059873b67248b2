"""Socket helpers shared by the ingest receivers, the MQTT client and the
WebSocket endpoints (standard library only, so every layer can import it).
"""

from __future__ import annotations

import socket
from typing import Optional


def close_socket(sock: Optional[socket.socket]) -> None:
    """Shut ``sock`` down, then close it.  ``close()`` alone does not wake
    a thread blocked on the socket in ``accept()``/``recv()`` on Linux: its
    loop would never see the stop, and the stop would wait out a join's
    timeout for it."""
    if sock is None:
        return
    try:
        sock.shutdown(socket.SHUT_RDWR)
    except OSError:
        pass   # never connected (a UDP socket still wakes), or closed
    try:
        sock.close()
    except OSError:
        pass
