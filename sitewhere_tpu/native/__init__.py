"""Native runtime tier: lazily-built C accelerators with Python fallback.

The reference's performance tier is JVM infrastructure (Netty, Kafka
clients); here the compute tier is XLA/Pallas and the HOST tier gets C
where CPython is the ceiling — first the NDJSON wire decoder
(SURVEY.md §0: a "C++ host-side ingest shim … justified by capability").

Build model: no pip, no wheels — the extension compiles ON FIRST USE
with the toolchain baked into the image (cc + CPython headers via
sysconfig), cached next to the source keyed by the source hash and
Python ABI.  Any failure (no compiler, sandboxed fs, bad flags) just
leaves the pure-Python path in charge; correctness never depends on the
native tier being present.
"""

from __future__ import annotations

import hashlib
import logging
import os
import subprocess
import sys
import sysconfig
from typing import Optional

logger = logging.getLogger("sitewhere_tpu.native")

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "swwire.c")

_swwire = None
_tried = False
_load_lock = __import__("threading").Lock()

# Decodes that arrived while the first-use build was in flight and took
# the Python path instead (load_swwire's non-blocking lock).  Surfaced
# as the ``native.build_fallbacks`` gauge so a seconds-long compile
# silently degrading the intake tier is visible, not inferred.
build_fallbacks = 0


def _build_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.blake2b(f.read(), digest_size=8).hexdigest()
    abi = sysconfig.get_config_var("SOABI") or "abi"
    return os.path.join(_DIR, f"_swwire-{digest}-{abi}.so")


def _compile(out: str) -> bool:
    cc = os.environ.get("CC", "cc")
    include = sysconfig.get_paths()["include"]
    tmp = f"{out}.tmp.{os.getpid()}.so"
    # -lm for llrint (the fill-direct epoch split), -pthread for the
    # TokenTable rwlock the GIL-free resolved scan reads under
    cmd = [cc, "-O2", "-shared", "-fPIC", "-pthread", f"-I{include}",
           _SRC, "-o", tmp, "-lm"]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        logger.info("native build unavailable (%s); using Python path", e)
        return False
    if proc.returncode != 0:
        logger.warning("native build failed; using Python path:\n%s",
                       proc.stderr[-1000:])
        return False
    os.replace(tmp, out)
    return True


def load_swwire():
    """The _swwire module, building it on first use; None if unavailable.

    Disable explicitly with SW_NATIVE=0 (e.g. for A/B benchmarks)."""
    global _swwire, _tried
    if _swwire is not None or _tried:
        return _swwire
    # Non-blocking: while the (possibly seconds-long) first-use build is
    # in flight on the warmup thread, decode callers get None and take
    # the Python path instead of parking on the lock.  Each such miss is
    # counted — Instance.start() kicks the build on the warmup thread
    # precisely so this stays near zero in production.
    if not _load_lock.acquire(blocking=False):
        global build_fallbacks
        build_fallbacks += 1
        return None
    try:
        if _swwire is not None or _tried:
            return _swwire
        return _load_locked()
    finally:
        _load_lock.release()


def loaded_swwire():
    """The _swwire module if a load has already finished, else None: for
    a caller off the decode path (the event store's seal) that must
    neither build the module, nor wait for a build in flight, nor count
    a decode fallback.  ``Instance.start``'s warm-up and the decode tier
    load it; until then such a caller takes its Python path."""
    return _swwire


def build_swwire():
    """Compile ``swwire.c`` NOW on the calling thread and load it — for
    a caller that must know the native tier is in place before its first
    decode (``chip_smoke.py``).  Always compiles: a ``.so`` left in the
    tree by an earlier run is never trusted.  Raises where
    :func:`load_swwire` would quietly leave the Python path in charge."""
    with _load_lock:
        if not _compile(_build_path()):
            raise RuntimeError("native wire decoder did not build "
                               "(no C compiler, or swwire.c failed)")
        if _swwire is None:
            _load_locked()
        if _swwire is None:
            raise RuntimeError("native wire decoder built but did not load")
        return _swwire


def _load_locked():
    global _swwire, _tried
    _tried = True
    if os.environ.get("SW_NATIVE", "1") == "0":
        return None
    try:
        # SW_NATIVE_LIB: load a PREBUILT extension instead of the
        # hash-keyed first-use build — how tools/native_sanitize.sh
        # injects its ASan/UBSan-instrumented build under the normal
        # test suite (the sanitizer runtime must be LD_PRELOADed by the
        # harness; this loader only swaps the .so path).
        override = os.environ.get("SW_NATIVE_LIB")
        if override:
            path = override
            if not os.path.exists(path):
                logger.warning("SW_NATIVE_LIB=%s missing; Python path",
                               path)
                return None
        else:
            path = _build_path()
            if not os.path.exists(path) and not _compile(path):
                return None
        import importlib.util

        spec = importlib.util.spec_from_file_location("_swwire", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)  # type: ignore[union-attr]
        _swwire = mod
        logger.info("native wire decoder loaded (%s)",
                    os.path.basename(path))
    except Exception:
        logger.exception("native wire decoder unavailable; Python path")
        _swwire = None
    return _swwire
