"""The Electra cell's per-layer readers on synthetic contexts whose answers
are known: the key-sum work is the same whatever the row width, the key-sum
readers take stages ``s1_key_sum`` and ``s1_set_sum``, and the key fill is
read from flight records; each is None where there is nothing to read."""

import pytest

from benchmark import trace
from benchmark.run import metric_reader
from benchmark.work import bls_verify, fp, key_sum

PEAK = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def test_key_sum_work_by_hand():
    # one 26,560-key set and a 1-key set: 26,559 additions of 16 m each
    assert key_sum.ops(2, 26561) == 26559 * 16 * 9312
    assert key_sum.nbytes(2, 26561) == 26561 * 96
    # the aggregation term of the whole verification, nothing more
    assert key_sum.ops(11, 62978) == (
        bls_verify.fp_mults(11, 62978) - bls_verify.fp_mults(11, 11)) * fp.FP_MUL_INT8_OPS


def _ctx(shape, stage_of, monkeypatch, work):
    """One ``_device_verify`` execution of 700 ns on one chip: 300 ns of
    key rows' sums, 100 ns of set sums, 300 ns of pairing."""
    from lighthouse_tpu import device_telemetry

    monkeypatch.setattr(device_telemetry, "program_stages",
                        lambda op, s: stage_of if op == "bls_verify" else None)
    modules = {"/device:TPU:0": [["jit__device_verify(3)", 1000, 1700]]}
    ops = {"/device:TPU:0": [["k.1", 1000, 1300], ["r.1", 1300, 1400],
                             ["p.1", 1400, 1700]]}
    return {"device": trace.DeviceTrace(modules, ops, 0, 10_000),
            "flight": [{"op": "bls_verify", "shape": shape,
                        "host_fallback": False}],
            "device_kind": "TPU v5 lite", "work": work}


STAGE_OF = {"k.1": "s1_key_sum", "r.1": "s1_set_sum", "p.1": "miller_loop"}


@pytest.mark.parametrize("shape", ["16x2048x64x16", "16x512x256x64"])
def test_key_sum_readers_at_any_row_width(monkeypatch, shape):
    """The same block at two row widths reads the same work: the share is
    the live sets' and keys' least time over the two stages' 400 ns."""
    work = [{"sets": 11, "keys": 62978}]
    ctx = _ctx(shape, STAGE_OF, monkeypatch, work)
    assert metric_reader("key_sum_device_ms.electra")(ctx) == pytest.approx(4e-4)
    want = key_sum.roofline_pct(11, 62978, 400e-9, PEAK)[0]
    assert metric_reader("key_sum_roofline.electra")(ctx) == pytest.approx(want)


def test_key_sum_readers_find_nothing(monkeypatch):
    """No stage of the key sum (a program without the map, or the parent's
    host-routed batches): None, never 0."""
    ctx = _ctx("16x2048x64x16", {"p.1": "miller_loop"}, monkeypatch,
               [{"sets": 11, "keys": 62978}])
    assert metric_reader("key_sum_device_ms.electra")(ctx) is None
    assert metric_reader("key_sum_roofline.electra")(ctx) is None
    ctx["flight"][0]["host_fallback"] = True
    assert metric_reader("key_sum_roofline.electra")(ctx) is None
    assert metric_reader("key_sum_roofline.electra")({"flight": []}) is None


def test_key_fill_reads_the_flight_records():
    read = metric_reader("key_fill_pct.electra")
    rec = {"op": "bls_verify", "host_fallback": False, "shape": "16x2048x64x16",
           "live_keys": 62978, "key_rows": 64, "key_slots": 131072}
    other = {"op": "kzg_batch", "host_fallback": False, "shape": "8"}
    assert read({"flight": [rec, dict(rec), other]}) == pytest.approx(
        100 * 62978 / 131072)
    half = dict(rec, live_keys=65536)
    assert read({"flight": [rec, half]}) == pytest.approx(
        100 * (62978 + 65536) / (2 * 131072))
    # the parent's records: routed to the host, or without key slots
    assert read({"flight": [dict(rec, host_fallback=True)]}) is None
    assert read({"flight": [{"op": "bls_verify", "host_fallback": False,
                             "shape": "256x512", "live_keys": 62978}]}) is None
    assert read({}) is None
