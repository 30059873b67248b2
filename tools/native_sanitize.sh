#!/usr/bin/env bash
# ASan/UBSan build of the C wire scanner + the fill-direct test suite
# run under the instrumented build.
#
#   tools/native_sanitize.sh           # build + run tests/test_native_fill.py
#   tools/native_sanitize.sh --build   # build only, print the .so path
#
# The production build (native/__init__.py) compiles swwire.c with -O2 on
# first use; memory bugs in the scanner — the code that parses HOSTILE
# wire bytes straight into the batcher's buffers — would corrupt the
# packed columns silently.  This target rebuilds it with
# AddressSanitizer + UndefinedBehaviorSanitizer (no recover: any finding
# aborts the test run) and executes the full fill-direct suite against
# it via SW_NATIVE_LIB, with the sanitizer runtime LD_PRELOADed into the
# (uninstrumented) CPython host.
#
# Wired into the verify flow as the slow-marked tests/test_native_sanitize.py
# (pytest -m slow) and runnable standalone from any checkout.
set -euo pipefail

REPO="$(cd "$(dirname "$0")/.." && pwd)"
SRC="$REPO/sitewhere_tpu/native/swwire.c"
OUT_DIR="${TMPDIR:-/tmp}/sw_native_sanitize"
OUT="$OUT_DIR/_swwire_sanitized.so"
CC="${CC:-cc}"

command -v "$CC" >/dev/null || { echo "native_sanitize: no C compiler" >&2; exit 3; }

INCLUDE="$(python -c 'import sysconfig; print(sysconfig.get_paths()["include"])')"
LIBASAN="$("$CC" -print-file-name=libasan.so)"
if [ ! -e "$LIBASAN" ]; then
    echo "native_sanitize: libasan runtime not found ($LIBASAN)" >&2
    exit 3
fi

mkdir -p "$OUT_DIR"
echo "native_sanitize: building $OUT"
"$CC" -O1 -g -fno-omit-frame-pointer \
    -fsanitize=address,undefined -fno-sanitize-recover=all \
    -shared -fPIC -pthread -I"$INCLUDE" "$SRC" -o "$OUT" -lm

if [ "${1:-}" = "--build" ]; then
    echo "$OUT"
    exit 0
fi

# detect_leaks=0: CPython itself "leaks" interned objects at exit — leak
# checking an embedded interpreter is all noise; ASan's real value here
# is overflow/UAF/UB detection during the scan.
# verify_asan_link_order=0: the host python is uninstrumented, the
# runtime arrives via LD_PRELOAD — that inversion is exactly what the
# check would (falsely) reject.
cd "$REPO"
# Preflight: the instrumented .so must actually LOAD in the child
# environment — native/__init__.py swallows import failures into a
# Python-path fallback, and the native test suites skip wholesale when
# the module is absent, so a dlopen failure (ASan runtime mismatch,
# stripped LD_PRELOAD) would otherwise read as a vacuously green gate.
echo "native_sanitize: preflighting instrumented load"
env LD_PRELOAD="$LIBASAN" \
    ASAN_OPTIONS="detect_leaks=0,verify_asan_link_order=0" \
    SW_NATIVE_LIB="$OUT" SW_SANITIZED_SO="$OUT" JAX_PLATFORMS=cpu \
    python -c 'import os, sys
from sitewhere_tpu import native
mod = native.load_swwire()
want = os.environ["SW_SANITIZED_SO"]
origin = getattr(getattr(mod, "__spec__", None), "origin", None)
if origin != want:
    print("native_sanitize: instrumented .so did not load "
          "(got %r, wanted %r)" % (origin, want), file=sys.stderr)
    sys.exit(1)'

echo "native_sanitize: running the native suites under ASan/UBSan"
env LD_PRELOAD="$LIBASAN" \
    ASAN_OPTIONS="detect_leaks=0,abort_on_error=1,verify_asan_link_order=0" \
    UBSAN_OPTIONS="print_stacktrace=1,halt_on_error=1" \
    SW_NATIVE_LIB="$OUT" \
    JAX_PLATFORMS=cpu \
    python -m pytest tests/test_native_fill.py tests/test_native_wire.py \
        tests/test_native_resolved.py tests/test_native_seal.py -q \
        -p no:cacheprovider "$@"
echo "native_sanitize: OK (ASan/UBSan clean)"
