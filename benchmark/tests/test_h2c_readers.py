"""The reader of the device's cofactor-clearing stage on synthetic contexts
whose answers are known: it takes stage ``h2c_clear`` alone, the stages of
``msm_device_ms.block`` and ``pairing_device_ms.block`` leave it out, and it
is None where a program has no such stage (the parent's)."""

import pytest

from benchmark import trace
from benchmark.run import metric_reader


def _ctx(stage_of, monkeypatch):
    """Two ``_device_verify`` executions on one chip: 100 ns of s3, then
    200 / 400 ns of the clear, then 300 ns of pairing."""
    from lighthouse_tpu import device_telemetry

    monkeypatch.setattr(device_telemetry, "program_stages",
                        lambda op, s: stage_of if op == "bls_verify" else None)
    modules = {"/device:TPU:0": [["jit__device_verify(3)", 1000, 1600],
                                 ["jit__device_verify(3)", 2000, 2800]]}
    ops = {"/device:TPU:0": [["m.1", 1000, 1100], ["h.1", 1100, 1300],
                             ["p.1", 1300, 1600],
                             ["m.1", 2000, 2100], ["h.1", 2100, 2500],
                             ["p.1", 2500, 2800]]}
    return {"device": trace.DeviceTrace(modules, ops, 0, 10_000),
            "flight": [{"op": "bls_verify", "shape": "256x512",
                        "host_fallback": False}]}


def test_h2c_clear_reader_takes_its_stage_alone(monkeypatch):
    stage_of = {"m.1": "s3_g2_msm", "h.1": "h2c_clear", "p.1": "miller_loop"}
    ctx = _ctx(stage_of, monkeypatch)
    assert metric_reader("h2c_clear_device_ms.block")(ctx) == pytest.approx(3e-4)
    assert metric_reader("msm_device_ms.block")(ctx) == pytest.approx(1e-4)
    assert metric_reader("pairing_device_ms.block")(ctx) == pytest.approx(3e-4)


def test_h2c_clear_reader_finds_nothing_without_the_stage(monkeypatch):
    ctx = _ctx({"m.1": "s3_g2_msm", "p.1": "miller_loop"}, monkeypatch)
    assert metric_reader("h2c_clear_device_ms.block")(ctx) is None
    ctx["flight"][0]["host_fallback"] = True
    assert metric_reader("h2c_clear_device_ms.block")(ctx) is None
    assert metric_reader("h2c_clear_device_ms.block")({"flight": []}) is None
