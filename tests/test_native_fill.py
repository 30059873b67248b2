"""Zero-copy fill-direct ingest: golden native≡python equivalence,
bail/no-torn-rows contract, reserve/commit semantics, truncation fuzz.

The fill-direct tier (swwire.c ``decode_measurement_lines_resolved_into``
+ ``Batcher.reserve``/commit) is PURELY an accelerator: for any payload
it accepts, the committed batch columns must be bit-identical to what
the pure-Python decoder + ``resolve_columns`` + ``add_arrays`` would
have produced; anything else must bail with NOTHING committed (the
reservation is private until commit, so a mid-payload bail can never
leave torn rows).
"""

import json

import numpy as np
import pytest

from sitewhere_tpu.ids import NULL_ID, HandleSpace
from sitewhere_tpu.ingest import columnar
from sitewhere_tpu.ingest.batcher import Batcher, Reservation
from sitewhere_tpu.ingest.decoders import DecodeError
from sitewhere_tpu.native import load_swwire

pytestmark = pytest.mark.skipif(
    load_swwire() is None, reason="native toolchain unavailable")

WIDTH = 32
CAPACITY = 256


def _line(token, value, ts=1_753_800_000, name="temp", extra=None,
          raw=None):
    if raw is not None:
        return raw
    req = {"name": name, "value": value, "eventDate": ts}
    req.update(extra or {})
    return json.dumps({"deviceToken": token, "type": "Measurement",
                       "request": req}, separators=(",", ":"))


def _spaces(n_devices=16):
    dev = HandleSpace("device", CAPACITY)
    mt = HandleSpace("mtype", 64)
    al = HandleSpace("alert_type", 64)
    for i in range(n_devices):
        dev.mint(f"dev-{i}")
    return dev, mt, al


def _batcher(dev, mt, al, width=WIDTH, n_shards=1, deadline_ms=1e9):
    return Batcher(width=width, n_shards=n_shards,
                   registry_capacity=CAPACITY,
                   resolve_device=dev.lookup, resolve_mtype=mt.mint,
                   resolve_alert=al.mint, deadline_ms=deadline_ms)


def _fill(payload, dev, batcher, mt):
    """Run the fill-direct decode; returns (n, reservation) or None."""
    res = batcher.reserve(payload.count(b"\n") + 1)
    if res is None:
        return None
    n = columnar.decode_fill_direct(payload, dev, res, mt.mint)
    if n is None:
        return None
    return n, res


def _python_columns(payload, dev, mt, al):
    """The golden reference: pure-Python decode + resolution (no native
    involvement at all)."""
    cols, host = columnar._decode_lines_inner(
        columnar.parse_envelopes(payload))
    assert host == []
    return columnar.resolve_columns(cols, dev.lookup, mt.mint, al.mint)


def _assert_rows_equal(res, n, ref):
    """Committed reservation rows [0:n] vs the reference columns."""
    assert n == len(ref["device_id"])
    np.testing.assert_array_equal(res.device_id[:n], ref["device_id"])
    np.testing.assert_array_equal(res.mtype_id[:n], ref["mtype_id"])
    np.testing.assert_array_equal(res.ts_s[:n], ref["ts_s"])
    np.testing.assert_array_equal(res.ts_ns[:n], ref["ts_ns"])
    # bit-identical float32: compare raw bytes, not approx
    assert res.value[:n].tobytes() == \
        np.asarray(ref["value"], np.float32).tobytes()
    np.testing.assert_array_equal(
        res.update_state[:n].astype(bool),
        np.asarray(ref["update_state"], bool))


# ---------------------------------------------------------------------------
# golden equivalence
# ---------------------------------------------------------------------------

class TestFillEquivalence:
    def test_bit_identical_to_python_decoder(self):
        dev, mt, al = _spaces()
        # pre-mint the names so resolution order cannot differ between
        # paths (production shares ONE HandleSpace the same way)
        for nm in ("temp", "rh"):
            mt.mint(nm)
        lines = [
            _line(f"dev-{i % 16}", v, ts=ts, name=nm, extra=extra)
            for i, (v, ts, nm, extra) in enumerate([
                (20.5, 1_753_800_000, "temp", None),
                (-3, 1_753_800_001.25, "rh", None),
                (0, 0, "temp", None),                      # ts -> 0
                (1e-8, 1_753_800_000_000, "temp", None),   # epoch millis
                (7.25, 1_753_800_003, "temp", {"updateState": False}),
                (123456789.5, 1_753_800_004, "rh",
                 {"updateState": True}),
                (2.0, 1_753_800_005.999, "temp", None),
                (-0.0, 1, "rh", None),
            ])
        ]
        lines.append(_line("ghost-device", 9.75))  # unknown -> NULL_ID
        payload = ("\n".join(lines) + "\n\n").encode()  # trailing blanks
        batcher = _batcher(dev, mt, al)
        out = _fill(payload, dev, batcher, mt)
        assert out is not None
        n, res = out
        ref = _python_columns(payload, dev, mt, al)
        _assert_rows_equal(res, n, ref)
        assert res.device_id[n - 1] == NULL_ID  # the ghost

    def test_per_line_key_orders_hit_the_parser_fallback(self):
        """Lines whose key order differs from line 1 miss the template
        and take the full per-line parser — results must be identical."""
        dev, mt, al = _spaces()
        mt.mint("temp")
        lines = [
            _line("dev-1", 1.5),
            json.dumps({"type": "Measurement", "deviceToken": "dev-2",
                        "request": {"value": 2.5, "name": "temp",
                                    "eventDate": 1_753_800_001}}),
            json.dumps({"request": {"eventDate": 1_753_800_002,
                                    "name": "temp", "value": 3.5},
                        "deviceToken": "dev-3", "type": "Measurements"}),
            # timestamp alias instead of eventDate
            json.dumps({"deviceToken": "dev-4", "type": "Measurement",
                        "request": {"name": "temp", "value": 4.5,
                                    "timestamp": 1_753_800_003}}),
            # hardwareId alias (template-ineligible, parser accepts)
            json.dumps({"hardwareId": "dev-5", "type": "Measurement",
                        "request": {"name": "temp", "value": 5.5,
                                    "eventDate": 1_753_800_004}}),
        ]
        payload = "\n".join(lines).encode()
        batcher = _batcher(dev, mt, al)
        out = _fill(payload, dev, batcher, mt)
        assert out is not None
        n, res = out
        _assert_rows_equal(res, n, _python_columns(payload, dev, mt, al))

    def test_number_forms_bit_exact(self):
        """The template fast-path number parse must be bit-identical to
        strtod across integer/decimal/exponent/long-mantissa forms."""
        dev, mt, al = _spaces()
        mt.mint("x")
        # (not "-0": json.loads parses it to int 0 while every native
        # tier — old and new alike — follows strtod to -0.0; the sign
        # of zero is the one numerically-invisible divergence)
        values = ["0", "-0.0", "0.5", "-12345", "20.1", "1e3", "-2.5e-3",
                  "9007199254740993", "3.141592653589793238",
                  "0.1", "1234567890123456.75", "1e22"]
        lines = [
            '{"deviceToken":"dev-1","type":"Measurement","request":'
            '{"name":"x","value":%s,"eventDate":%s}}' % (v, t)
            for v in values for t in ("1753800000", "1753800000.5",
                                      "1753800000123.25")
        ]
        payload = "\n".join(lines).encode()
        batcher = _batcher(dev, mt, al, width=256)
        out = _fill(payload, dev, batcher, mt)
        assert out is not None
        n, res = out
        _assert_rows_equal(res, n, _python_columns(payload, dev, mt, al))

    def test_event_family_fill_matches_python(self):
        """The generic event-family fill variant
        (decode_event_lines_into) must match the pure decoder over a
        mixed measurement/location/alert payload."""
        mod = load_swwire()
        if not hasattr(mod, "decode_event_lines_into"):
            pytest.skip("fill-direct event scanner unavailable")
        lines = [
            json.dumps({"deviceToken": "a", "type": "Measurement",
                        "request": {"name": "t", "value": 1.5,
                                    "eventDate": 100}}),
            json.dumps({"deviceToken": "b", "type": "Location",
                        "request": {"latitude": 1.25, "longitude": -2.5,
                                    "elevation": 10.0,
                                    "eventDate": 200.5}}),
            json.dumps({"deviceToken": "c", "type": "Alert",
                        "request": {"type": "hot", "level": "warning",
                                    "eventDate": 300,
                                    "latitude": 3.0, "longitude": 4.0}}),
        ]
        payload = "\n".join(lines).encode()
        filled = columnar._native_decode_events_into(mod, payload)
        assert filled is not None
        cols, host = filled
        ref, ref_host = columnar._decode_lines_inner(
            columnar.parse_envelopes(payload))
        assert host == ref_host == []
        assert list(cols["device_token"]) == list(ref["device_token"])
        assert list(cols["mtype"]) == list(ref["mtype"])
        assert list(cols["alert_type"]) == list(ref["alert_type"])
        for key in ("event_type", "ts_s", "ts_ns", "alert_level"):
            np.testing.assert_array_equal(cols[key], ref[key])
        for key in ("value", "lat", "lon", "elevation"):
            assert np.asarray(cols[key], np.float32).tobytes() == \
                np.asarray(ref[key], np.float32).tobytes()
        np.testing.assert_array_equal(
            np.asarray(cols["update_state"], bool),
            np.asarray(ref["update_state"], bool))


# ---------------------------------------------------------------------------
# bail contract: nothing committed, ever
# ---------------------------------------------------------------------------

class TestFillBail:
    @pytest.mark.parametrize("bad_line", [
        '{"deviceToken":"dev-1","type":"Location","request":'
        '{"latitude":1,"longitude":2}}',           # non-measurement kind
        '{"deviceToken":"dev-1","type":"Measurement","request":'
        '{"name":"t","value":}}',                  # malformed JSON
        '{"deviceToken":"dev-1","type":"Measurement","request":'
        '{"name":"t"}}',                           # missing value
        '{"deviceToken":"","type":"Measurement","request":'
        '{"name":"t","value":1}}',                 # empty token
        '{"deviceToken":"dev-1","type":"Measurement","request":'
        '{"name":"t","value":1,"metadata":{}}}',   # unknown request key
        'garbage not json',
    ])
    def test_mid_payload_bad_line_bails_with_no_torn_rows(self, bad_line):
        dev, mt, al = _spaces()
        batcher = _batcher(dev, mt, al)
        good = [_line(f"dev-{i}", 1.0 + i) for i in range(5)]
        payload = "\n".join(good + [bad_line] + good).encode()
        assert _fill(payload, dev, batcher, mt) is None
        assert batcher.pending == 0          # nothing committed
        assert batcher.emitted_batches == 0  # nothing emitted

    def test_empty_and_blank_payloads_bail(self):
        dev, mt, al = _spaces()
        batcher = _batcher(dev, mt, al)
        assert _fill(b"", dev, batcher, mt) is None
        assert _fill(b"\n \n\t\n", dev, batcher, mt) is None
        assert batcher.pending == 0

    def test_out_of_range_timestamp_bails_where_python_raises(self):
        """A finite eventDate past the int32 epoch range: the fill path
        bails; the fallback surfaces the same DecodeError the pure path
        raises — one observable behavior, two tiers."""
        dev, mt, al = _spaces()
        batcher = _batcher(dev, mt, al)
        payload = _line("dev-1", 1.0, ts=4e18).encode()
        assert _fill(payload, dev, batcher, mt) is None
        assert batcher.pending == 0
        with pytest.raises(DecodeError):
            columnar.decode_json_lines(payload, device_space=dev)
        with pytest.raises(DecodeError):
            columnar._decode_lines_inner(columnar.parse_envelopes(payload))

    def test_payload_wider_than_reservation_bails(self):
        dev, mt, al = _spaces()
        batcher = _batcher(dev, mt, al)
        payload = "\n".join(
            _line(f"dev-{i % 16}", float(i)) for i in range(WIDTH + 8)
        ).encode()
        # reserve() refuses payloads wider than one batch outright
        assert batcher.reserve(payload.count(b"\n") + 1) is None

    def test_fuzz_truncations_never_diverge(self):
        """Every truncation of a valid payload: if the fill path accepts
        it, the pure-Python decoder must produce identical rows; if it
        bails, nothing may have been committed."""
        dev, mt, al = _spaces()
        mt.mint("temp")
        mt.mint("rh")
        base = "\n".join(
            _line(f"dev-{i % 16}", 1.5 * i,
                  ts=1_753_800_000 + i,
                  name=("temp" if i % 2 else "rh"))
            for i in range(8)
        ).encode()
        for cut in range(0, len(base), 7):
            payload = base[:cut]
            batcher = _batcher(dev, mt, al)
            out = _fill(payload, dev, batcher, mt)
            if out is None:
                assert batcher.pending == 0
                continue
            n, res = out
            ref = _python_columns(payload, dev, mt, al)
            _assert_rows_equal(res, n, ref)

    def test_fuzz_overlong_and_wild_names_bail(self):
        dev, mt, al = _spaces()
        batcher = _batcher(dev, mt, al, width=512)
        # >256 distinct names: past the scanner's uniq memo — must bail
        payload = "\n".join(
            _line("dev-1", 1.0, name=f"name-{i}") for i in range(300)
        ).encode()
        assert _fill(payload, dev, batcher, mt) is None
        assert batcher.pending == 0
        # one enormous (but valid) line still decodes equivalently
        big = _line("dev-1", 2.0, name="n" * 4096)
        out = _fill(big.encode(), dev, batcher, mt)
        assert out is not None
        n, res = out
        _assert_rows_equal(res, n,
                           _python_columns(big.encode(), dev, mt, al))

    def test_invalid_utf8_token_bails_like_json_loads(self):
        dev, mt, al = _spaces()
        batcher = _batcher(dev, mt, al)
        good = _line("dev-1", 1.0).encode()
        bad = good.replace(b"dev-1", b"dev-\xff")
        payload = good + b"\n" + bad
        assert _fill(payload, dev, batcher, mt) is None
        with pytest.raises(DecodeError):
            columnar.parse_envelopes(payload)


# ---------------------------------------------------------------------------
# reserve/commit semantics
# ---------------------------------------------------------------------------

class TestReserveCommit:
    def test_reserve_refuses_oversize_only(self):
        """Sharded batchers reserve too (commit routes the resolved ids
        by shard); only cap-out-of-range payloads are refused."""
        dev, mt, al = _spaces()
        sharded = Batcher(width=WIDTH, n_shards=2,
                          registry_capacity=CAPACITY,
                          resolve_device=dev.lookup,
                          resolve_mtype=mt.mint, resolve_alert=al.mint)
        assert isinstance(sharded.reserve(4), Reservation)
        assert sharded.reserve(WIDTH + 1) is None
        batcher = _batcher(dev, mt, al)
        assert batcher.reserve(WIDTH + 1) is None
        assert batcher.reserve(0) is None
        assert isinstance(batcher.reserve(WIDTH), Reservation)

    def test_adopted_full_width_plan_matches_add_arrays(self):
        """A committed full-width reservation is ADOPTED (zero-copy);
        its packed buffers must equal the copy path's emission for the
        same rows, padding and bool rows included."""
        dev, mt, al = _spaces()
        mt.mint("temp")
        payload = "\n".join(
            _line(f"dev-{i % 16}", 0.5 * i, ts=1_753_800_000 + i)
            for i in range(WIDTH)
        ).encode()
        fill_b = _batcher(dev, mt, al)
        n, res = _fill(payload, dev, fill_b, mt)
        res.set_const(tenant_id=3, payload_ref=42)
        before = fill_b.copied_bytes
        plans = res.commit()
        assert len(plans) == 1 and plans[0].n_events == WIDTH
        assert fill_b.copied_bytes == before  # adoption: zero copies

        ref_b = _batcher(dev, mt, al)
        cols = _python_columns(payload, dev, mt, al)
        cols["tenant_id"] = np.full(WIDTH, 3, np.int32)
        cols["payload_ref"] = np.full(WIDTH, 42, np.int32)
        ref_plans = ref_b.add_arrays(**cols)
        assert len(ref_plans) == 1
        assert plans[0].packed_i.tobytes() == \
            ref_plans[0].packed_i.tobytes()
        assert plans[0].packed_f.tobytes() == \
            ref_plans[0].packed_f.tobytes()

    def test_partial_reservation_adopts_on_deadline_with_clean_padding(self):
        dev, mt, al = _spaces()
        mt.mint("temp")
        k = 5
        payload = "\n".join(
            _line(f"dev-{i}", 1.0 + i) for i in range(k)).encode()
        batcher = _batcher(dev, mt, al, deadline_ms=0.0)
        n, res = _fill(payload, dev, batcher, mt)
        res.set_const(tenant_id=0, payload_ref=7)
        assert res.commit() == []        # k < width: nothing emitted yet
        assert batcher.pending == k
        plan = batcher.poll()            # deadline emit adopts the chunk
        assert plan is not None and plan.n_events == k
        from sitewhere_tpu.pipeline.packed import BATCH_I
        valid = plan.packed_i[BATCH_I.index("valid")]
        assert valid[:k].all() and not valid[k:].any()
        dev_row = plan.packed_i[BATCH_I.index("device_id")]
        assert (dev_row[k:] == NULL_ID).all()
        assert (plan.packed_i[BATCH_I.index("payload_ref")][:k] == 7).all()
        assert (plan.packed_i[BATCH_I.index("payload_ref")][k:]
                == NULL_ID).all()
        assert batcher.pending == 0

    def test_adoption_skipped_when_other_chunks_queued(self):
        """A reserved chunk behind earlier rows takes the copy path —
        same batch content, just not adopted."""
        dev, mt, al = _spaces()
        mt.mint("temp")
        batcher = _batcher(dev, mt, al)
        batcher.add_arrays(device_id=np.asarray([0, 1], np.int32),
                           value=np.asarray([9.0, 8.0], np.float32))
        payload = "\n".join(
            _line(f"dev-{i % 16}", float(i)) for i in range(WIDTH)
        ).encode()
        n, res = _fill(payload, dev, batcher, mt)
        res.set_const(tenant_id=0, payload_ref=1)
        plans = res.commit()
        assert len(plans) == 1
        plan = plans[0]
        assert plan.packed_i is not res.ibuf  # copied, not adopted
        host = plan.host_cols
        assert host["value"][0] == 9.0        # earlier rows lead
        assert host["value"][2] == 0.0        # then the payload's rows
        assert batcher.pending == 2           # carry-over preserved

    def test_commit_twice_and_after_abort_raise(self):
        dev, mt, al = _spaces()
        batcher = _batcher(dev, mt, al)
        payload = _line("dev-1", 1.0).encode()
        n, res = _fill(payload, dev, batcher, mt)
        res.set_const(tenant_id=0, payload_ref=NULL_ID)
        res.commit()
        with pytest.raises(RuntimeError):
            res.commit()
        n2, res2 = _fill(payload, dev, batcher, mt)
        res2.abort()
        with pytest.raises(RuntimeError):
            res2.commit()

    def test_out_of_capacity_id_rewritten_in_place(self):
        # a handle space ROOMIER than the registry: minted handles can
        # land past the batcher's capacity and must rewrite to NULL_ID
        dev = HandleSpace("device", CAPACITY * 2)
        mt = HandleSpace("mtype", 64)
        al = HandleSpace("alert_type", 64)
        for i in range(CAPACITY + 2):
            dev.mint(f"extra-{i}")
        batcher = _batcher(dev, mt, al)
        payload = _line(f"extra-{CAPACITY + 1}", 5.0).encode()
        n, res = _fill(payload, dev, batcher, mt)
        assert dev.lookup(f"extra-{CAPACITY + 1}") >= CAPACITY
        res.set_const(tenant_id=0, payload_ref=NULL_ID)
        res.commit()
        assert res.device_id[0] == NULL_ID
