"""The readers of the funnel's inner spans and of the device stages, on
synthetic contexts whose answers are known: each gives the median per
request, and None where its span or stage is missing."""

import pytest

from benchmark import trace
from benchmark.metrics import _stages
from benchmark.run import metric_reader

SPAN_READERS = {
    "subgroup_ms.block": "marshal_subgroup",
    "hash_to_g2_ms.block": "marshal_hash_to_g2",
    "key_pack_ms.block": "marshal_pack",
    "upload_ms.block": "marshal_upload",
    "kzg_host_ms.blob_da": "kzg_host_prep",
    "kzg_eval_ms.blob_da": "kzg_evaluate",
}


@pytest.mark.parametrize("metric", sorted(SPAN_READERS))
def test_span_reader_takes_the_median(metric):
    spans = {SPAN_READERS[metric]: [{"duration": d, "fields": {}}
                                    for d in (0.030, 0.010, 0.020)],
             "other": [{"duration": 9.0, "fields": {}}]}
    assert metric_reader(metric)({"spans": spans}) == pytest.approx(20.0)
    assert metric_reader(metric)({"spans": {"other": spans["other"]}}) is None


def _device_ctx(op, module, flight_ok=True):
    """Two executions of one program on one chip.  The first: a stage-one
    op of 100 ns with another nested in it, then a pairing op of 200 ns
    that a later op of no stage overlaps by 50 ns.  The second: 300 ns of
    stage one, 100 ns of pairing."""
    modules = {"/device:TPU:0": [[f"jit_{module}(7)", 1000, 1500],
                                 [f"jit_{module}(7)", 2000, 2500],
                                 ["jit_other(1)", 3000, 3100]]}
    ops = {"/device:TPU:0": [["a.1", 1000, 1100], ["a.2", 1010, 1090],
                             ["p.1", 1100, 1300], ["x.1", 1250, 1400],
                             ["a.1", 2000, 2300], ["p.2", 2300, 2400],
                             ["a.1", 3000, 3100]]}
    flight = [{"op": op, "shape": "8x4" if op == "bls_verify" else "8",
               "host_fallback": not flight_ok}]
    return {"device": trace.DeviceTrace(modules, ops, 0, 10_000),
            "flight": flight}


#: metric -> (op, program, median over the two executions, ms)
STAGE_READERS = {
    "msm_device_ms.block": ("bls_verify", "_device_verify", 2e-4),
    "pairing_device_ms.block": ("bls_verify", "_device_verify", 1.5e-4),
    "msm_device_ms.blob_da": ("kzg_batch", "_device_kzg_batch", 2e-4),
    "pairing_device_ms.blob_da": ("kzg_batch", "_device_kzg_batch", 1.5e-4),
}


def _stage_map(stage_one, pairing):
    return {"a.1": stage_one, "a.2": stage_one, "p.1": pairing, "p.2": pairing}


@pytest.mark.parametrize("metric", sorted(STAGE_READERS))
def test_stage_reader_takes_the_median_per_execution(metric, monkeypatch):
    from lighthouse_tpu import device_telemetry

    op, module, want = STAGE_READERS[metric]
    pairing = "miller_loop" if op == "bls_verify" else "final_exp"
    one = "s2_g1_ladder" if op == "bls_verify" else "k1_g1_ladders"
    asked = []

    def fake(o, shape):
        asked.append((o, shape))
        return _stage_map(one, pairing) if o == op else None

    monkeypatch.setattr(device_telemetry, "program_stages", fake)
    read = metric_reader(metric)
    assert read(_device_ctx(op, module)) == pytest.approx(want)
    assert asked[0] == (op, (8, 4) if op == "bls_verify" else (8,))
    # no flight record of the op on the device: no map, no metric
    assert read(_device_ctx(op, module, flight_ok=False)) is None
    # a program whose ops name none of the stages
    monkeypatch.setattr(device_telemetry, "program_stages",
                        lambda o, shape: {"a.1": "elsewhere"})
    assert read(_device_ctx(op, module)) is None


def test_stage_reader_on_a_program_without_the_map(monkeypatch):
    """The parent program has no ``program_stages``: the reader finds
    nothing to read and does not raise."""
    from lighthouse_tpu import device_telemetry

    monkeypatch.delattr(device_telemetry, "program_stages")
    ctx = _device_ctx("bls_verify", "_device_verify")
    assert metric_reader("msm_device_ms.block")(ctx) is None
    assert metric_reader("pairing_device_ms.block")({"flight": []}) is None


def test_stage_seconds_counts_nested_and_overlapping_ops_once():
    ctx = _device_ctx("bls_verify", "_device_verify")
    got = _stages.stage_seconds(ctx["device"], "_device_verify",
                                _stage_map("s1", "s4"))
    assert got == [pytest.approx({"s1": 100e-9, "s4": 200e-9, None: 100e-9}),
                   pytest.approx({"s1": 300e-9, "s4": 100e-9})]


def test_recorded_chip_trace_by_stage():
    """A trace recorded on a TPU v5e of a program with two named scopes,
    run three times inside the host annotation, and the stage map
    ``device_telemetry.hlo_stage_map`` made of its compiled HLO there.  On the chip a loop is one op with its body's ops nested inside
    it: the reduction counts the loop once, in its scope, and the stages
    add up to the program's time."""
    import json
    import os

    from jax.profiler import ProfileData

    data = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
    with open(os.path.join(data, "tpu_stages.json")) as f:
        stage_of = json.load(f)["stages"]
    pd = ProfileData.from_file(os.path.join(data, "tpu_stages.xplane.pb"))
    t = trace.DeviceTrace.from_profile(pd, "bench_window")
    runs = t.module_seconds("two_scopes")
    assert runs == pytest.approx([74296e-9, 74419e-9, 74289e-9])
    per = _stages.stage_seconds(t, "two_scopes", stage_of)
    assert len(per) == 3
    for run, ex in zip(runs, per):
        assert ex["st_first"] == pytest.approx(70.69e-6, rel=1e-3)
        assert ex["st_second"] == pytest.approx(0.446e-6, rel=1e-2)
        # the input's copy and conversion lie outside both scopes
        assert ex[None] < 0.05 * run
        assert sum(ex.values()) == pytest.approx(run, rel=1e-3)
    names = {n for evs in t.ops.values() for n, _, _ in evs}
    assert names - set(stage_of) == {"convert.1", "copy-start", "copy-done"}
