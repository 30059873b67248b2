"""The segment store's own suites with every seal through the native
writer (swwire.c ``seal_segment``): a store sealed natively answers the
query, scan, compaction, tiering and fail-closed tests as one sealed by
``np.savez`` does, and its counters say every seal was native.  The
file-level comparison of the two writers is ``test_native_seal.py``.
"""

import pytest

from sitewhere_tpu import native
from sitewhere_tpu.runtime.metrics import global_registry
from tests import test_segment_store as store_suite

SW = native.load_swwire()


class NativeSeals:
    """Every seal of the test takes the native writer, and the store's
    counters say so."""

    @pytest.fixture(autouse=True)
    def _native_writer(self, monkeypatch):
        if SW is None:
            pytest.skip("native toolchain unavailable")
        monkeypatch.setattr(native, "_swwire", SW)
        counters = global_registry().snapshot()["counters"]
        seals0 = counters.get("store.seals", 0)
        native0 = counters.get("store.seals_native", 0)
        yield
        counters = global_registry().snapshot()["counters"]
        assert (counters.get("store.seals_native", 0) - native0
                == counters.get("store.seals", 0) - seals0)


class TestNativeBackgroundSeal(NativeSeals, store_suite.TestBackgroundSeal):
    pass


class TestNativeLiveRetroEquivalence(
        NativeSeals, store_suite.TestLiveRetroEquivalence):
    pass


class TestNativeCatalogPruning(NativeSeals, store_suite.TestCatalogPruning):
    pass


class TestNativeCompaction(NativeSeals, store_suite.TestCompaction):
    pass


class TestNativeTiering(NativeSeals, store_suite.TestTiering):
    pass


class TestNativeSealFailClosed(NativeSeals, store_suite.TestSealFailClosed):
    pass
