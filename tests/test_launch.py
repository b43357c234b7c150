"""Launcher plumbing: the compile-cache location, the chip preset, the
platform-decided interpret mode and the one-host training mesh."""
from __future__ import annotations

import os

import jax
import pytest

from repro.configs import CHIP_LAYERS, get_config, preset_config
from repro.kernels import interpret_mode
from repro.launch import compile_cache

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory after a test moves it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_var_wins(monkeypatch, cache_config, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the function sets no directory
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.use_compile_cache()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_chip_preset_keeps_published_widths():
    full = get_config("olmo-1b")
    chip = preset_config(full, "chip")
    assert chip.num_layers == CHIP_LAYERS < full.num_layers
    for f in ("d_model", "d_ff", "vocab_size", "attn"):
        assert getattr(chip, f) == getattr(full, f)
    assert preset_config(full, "pod") == full
    assert preset_config(full, "cpu").d_model < full.d_model
    with pytest.raises(ValueError, match="preset"):
        preset_config(full, "tiny")


def test_interpret_mode_follows_the_platform():
    assert jax.default_backend() == "cpu"
    assert interpret_mode() is True
    assert interpret_mode(False) is False  # compiling for a described TPU


@pytest.mark.multidevice
def test_host_mesh_spans_four_host_devices(multidevice):
    rec = multidevice("""
import json
import jax
from jax.sharding import AxisType
from repro.launch import mesh as mesh_mod
mesh = mesh_mod.make_host_mesh()
print(json.dumps({"shape": dict(mesh.shape),
                  "agents": mesh_mod.num_agents(mesh),
                  "auto": all(t == AxisType.Auto for t in mesh.axis_types),
                  "devices": mesh.devices.size}))
""", devices=4)
    assert rec == {"shape": {"pod": 1, "agent": 4, "fsdp": 1, "model": 1},
                   "agents": 4, "auto": True, "devices": 4}
