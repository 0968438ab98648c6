"""chip_smoke.py off the chip: it refuses to run without a TPU, its
phases pass at 8 MiB on the CPU (where `JaxBackend` picks `xla`), and
the device discovery it rests on (utils/devices.py) places the compile
cache and resolves `auto` the way the smoke relies on.
"""

import json
import os
import pathlib
import subprocess
import sys
import weakref

import jax
import pytest

import chip_smoke
from seaweedfs_tpu.ec import backend as B
from seaweedfs_tpu.ec import encoder
from seaweedfs_tpu.utils import devices, trace

REPO = pathlib.Path(__file__).resolve().parent.parent
MIB = 1 << 20

SMALL_SHAPES = [
    ("10+4 encode @64KiB", 10, 4, 64 << 10, "encode"),
    ("10+4 rebuild2 @64KiB", 10, 4, 64 << 10, "rebuild"),
    ("4+2 encode @16KiB", 4, 2, 16 << 10, "encode"),
]


def test_smoke_script_fails_without_a_tpu():
    out = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120, cwd=str(REPO),
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


@pytest.fixture
def smoke_process(monkeypatch):
    """What the smoke assumes of its own process: no FallbackBackend of
    another test alive, `auto` cache empty, tracer put back after."""
    monkeypatch.setattr(B, "_FALLBACKS", weakref.WeakSet())
    B.get_backend.cache_clear()
    was_armed = trace.armed
    yield chip_smoke.CompileMeter()
    trace.configure(enabled=was_armed)
    trace.reset()
    B.get_backend.cache_clear()


def test_one_chip_phases_pass_at_8mib_on_cpu(smoke_process, monkeypatch, capsys):
    # the last phase asks what `auto` means in a process that holds a
    # TPU: report one; JaxBackend still sees the CPU and picks xla
    monkeypatch.setattr(devices, "tpu_attached", lambda: True)
    chip_smoke.run_one_chip(
        seed=3, meter=smoke_process, volume_bytes=8 * MIB,
        staged_shapes=SMALL_SHAPES,
    )
    lines = capsys.readouterr().out.splitlines()
    phases = [json.loads(line)["phase"] for line in lines]
    assert phases == [
        "cluster", "load", "encode", "degraded_read", "rebuild",
        "staged_shapes", "auto",
    ]


def test_cross_chip_phases_pass_on_virtual_devices(
    smoke_process, monkeypatch, capsys
):
    # 8 MiB is "wide" here, so the lone stream keeps the column mesh
    monkeypatch.setattr(encoder, "WIDE_STREAM_BYTES", 8 * MIB)
    chip_smoke.run_four_chips(
        seed=3, meter=smoke_process, wide_bytes=8 * MIB, volume_bytes=MIB
    )
    out = capsys.readouterr().out
    assert '"placement": ["mesh"]' in out
    assert "concurrent_streams_chips" in out


def test_compile_cache_placement(monkeypatch):
    dir_was = jax.config.jax_compilation_cache_dir
    floor_was = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        # placed from outside: JAX reads the variable, the module sets none
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        devices.place_compile_cache()
        assert jax.config.jax_compilation_cache_dir == dir_was
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        # not placed: a fixed directory in the checkout
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        devices.place_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", dir_was)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", floor_was
        )


def test_local_devices_is_in_process_and_cached():
    info = devices.local_devices()
    assert info == ("cpu", jax.devices()[0].device_kind, len(jax.devices()))
    assert devices.local_devices() is info
    # the cache is a TPU matter: a CPU process keeps JAX's own setting
    assert jax.config.jax_compilation_cache_dir == os.environ.get(
        "JAX_COMPILATION_CACHE_DIR"
    )


def test_auto_raises_when_a_tpu_backend_cannot_be_built(monkeypatch):
    def broken(ctx):
        raise RuntimeError("Mosaic refused the kernel")

    monkeypatch.setattr(devices, "tpu_attached", lambda: True)
    monkeypatch.setattr(B, "JaxBackend", broken)
    B.get_backend.cache_clear()
    try:
        with pytest.raises(RuntimeError, match="Mosaic refused"):
            B.get_backend("auto", 6, 3)
    finally:
        B.get_backend.cache_clear()


def test_auto_is_the_cpu_when_no_tpu_is_reported(monkeypatch):
    monkeypatch.setattr(devices, "tpu_attached", lambda: False)
    B.get_backend.cache_clear()
    try:
        assert isinstance(B.get_backend("auto", 6, 3), B.CpuBackend)
    finally:
        B.get_backend.cache_clear()
