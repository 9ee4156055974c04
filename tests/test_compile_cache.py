"""Shared persistent-compile-cache config + AOT bucket warmup
(ops/compile_cache.py): directory resolution precedence, telemetry flowing
into the device_program_compiles machinery, and the mirror pre-seed that
keeps a warmed bucket's first production dispatch out of the compile count.
"""

import os

import jax
import pytest

from lighthouse_tpu import device_telemetry, metrics
from lighthouse_tpu.ops import compile_cache as cc


@pytest.fixture(autouse=True)
def _restore_cache_dir():
    """Tests point the jax cache at tmp dirs; the suite's shared cache must
    be back in force afterwards or every later compile goes cold."""
    yield
    cc.configure_persistent_cache()


def test_cache_dir_resolution_precedence(monkeypatch, tmp_path):
    # JAX_COMPILATION_CACHE_DIR, when set, is the only cache directory
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jaxdir"))
    assert cc.default_cache_dir() == str(tmp_path / "jaxdir")
    assert cc.configure_persistent_cache() == str(tmp_path / "jaxdir")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "jaxdir")
    # unset: <repo>/.jax_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.default_cache_dir() == os.path.join(repo, ".jax_cache")
    assert cc.configure_persistent_cache() == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        repo, ".jax_cache")


def test_env_bucket_list_parsing(monkeypatch):
    monkeypatch.setenv(cc.AOT_BUCKETS_ENV, "128x32, 4096x32")
    assert cc._env_buckets() == [(128, 32), (4096, 32)]
    monkeypatch.setenv(cc.AOT_BUCKETS_ENV, "")
    assert cc._env_buckets() is None


def test_warmup_compiles_bucket_and_feeds_telemetry():
    """AOT warmup of the smallest bucket: lowers+compiles from abstract
    shapes (no example batch), classifies hit/miss, pre-seeds the compile
    mirror so the shape's later first dispatch is not counted as a compile.
    """
    device_telemetry.reset_for_tests()
    warm_before = metrics.DEVICE_AOT_WARMUP.get(
        op="bls_verify", shape="1x1", outcome="hit"
    ) + metrics.DEVICE_AOT_WARMUP.get(
        op="bls_verify", shape="1x1", outcome="miss"
    )
    results = cc.warmup_standard_buckets([(1, 1)])
    assert len(results) == 1
    rec = results[0]
    assert rec["op"] == "bls_verify" and rec["shape"] == "1x1"
    assert rec["outcome"] in ("hit", "miss")
    assert device_telemetry.COMPILE_CACHE.seen("bls_verify", (1, 1))
    entry = next(
        e for e in device_telemetry.COMPILE_CACHE.inventory()
        if e["shape"] == "1x1"
    )
    assert entry["source"] == "warmup"
    assert entry["invocations"] == 0  # no production dispatch yet
    warm_after = metrics.DEVICE_AOT_WARMUP.get(
        op="bls_verify", shape="1x1", outcome="hit"
    ) + metrics.DEVICE_AOT_WARMUP.get(
        op="bls_verify", shape="1x1", outcome="miss"
    )
    assert warm_after == warm_before + 1
    # a dispatch AFTER the warmup is an invocation, not a compile
    compiles = metrics.DEVICE_PROGRAM_COMPILES.get(op="bls_verify", shape="1x1")
    assert device_telemetry.note_dispatch("bls_verify", (1, 1), 0.001) is False
    assert metrics.DEVICE_PROGRAM_COMPILES.get(op="bls_verify", shape="1x1") == compiles


def test_maybe_warmup_from_env_disabled_by_default(monkeypatch):
    monkeypatch.delenv(cc.AOT_WARMUP_ENV, raising=False)
    assert cc.maybe_warmup_from_env() is None


def test_maybe_warmup_from_env_background(monkeypatch):
    monkeypatch.setenv(cc.AOT_WARMUP_ENV, "1")
    monkeypatch.setenv(cc.AOT_BUCKETS_ENV, "1x1")
    thread = cc.maybe_warmup_from_env()
    assert thread is not None
    thread.join(timeout=300)
    assert not thread.is_alive()
    assert device_telemetry.COMPILE_CACHE.seen("bls_verify", (1, 1))
