"""Runtime: mesh construction, seeding, dist autodetect parsing."""

import jax
import numpy as np
import pytest

from distribuuuu_tpu.runtime import create_mesh, data_mesh, setup_seed
from distribuuuu_tpu.runtime.dist import _first_slurm_hostname


def test_data_mesh_all_devices():
    mesh = data_mesh(-1)
    assert mesh.axis_names == ("data",)
    assert mesh.devices.size == 8


def test_create_mesh_wildcard_inference():
    mesh = create_mesh({"data": -1, "model": 2})
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {"data": 4, "model": 2}


def test_create_mesh_errors():
    with pytest.raises(ValueError):
        create_mesh({"data": 3})  # 8 % 3 != 0 → mismatch
    with pytest.raises(ValueError):
        create_mesh({"a": -1, "b": -1})


def test_setup_seed_deterministic():
    k1 = setup_seed(123, 0)
    k2 = setup_seed(123, 0)
    assert jax.random.randint(k1, (), 0, 1 << 30) == jax.random.randint(k2, (), 0, 1 << 30)
    # numpy stream is also seeded per-host
    np.random.seed  # (smoke: call path exercised inside setup_seed)


def test_setup_seed_none_gives_entropy():
    k1 = setup_seed(None, 0)
    k2 = setup_seed(None, 0)
    assert int(jax.random.randint(k1, (), 0, 1 << 30)) != int(
        jax.random.randint(k2, (), 0, 1 << 30)
    )


def test_slurm_nodelist_fallback_parse():
    # scontrol is absent in this environment → exercises the regex fallback
    assert _first_slurm_hostname("tpu-host-[3-7,9]") == "tpu-host-3"
    assert _first_slurm_hostname("single-node") == "single-node"
    assert _first_slurm_hostname("n[12,15]") == "n12"


def _record_config_updates(monkeypatch):
    """Capture `jax.config.update` calls instead of performing them: these
    tests must not move the cache of the process they run in."""
    calls = {}
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.__setitem__(k, v))
    return calls


def test_compile_cache_is_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins: no directory is set in code (jax reads
    the variable itself) and cfg.TRAIN.COMPILE_CACHE_DIR does not override."""
    from distribuuuu_tpu.runtime.compile_cache import enable_persistent_cache

    calls = _record_config_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/by/the/launcher")
    assert enable_persistent_cache("/from/cfg") == "/placed/by/the/launcher"
    assert set(calls) == {"jax_persistent_cache_min_compile_time_secs"}


def test_compile_cache_default_is_the_fixed_checkout_path(monkeypatch):
    """Unset, the cache sits at cfg's directory if given, else at
    <checkout>/.cache/jax_compile — a path that never moves (it is part of
    the cache key), not a tempdir or a pid."""
    import os

    from distribuuuu_tpu.runtime.compile_cache import enable_persistent_cache

    calls = _record_config_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fixed = os.path.join(repo, ".cache", "jax_compile")
    assert enable_persistent_cache() == enable_persistent_cache("") == fixed
    assert calls["jax_compilation_cache_dir"] == fixed
    assert enable_persistent_cache("/from/cfg") == "/from/cfg"
    assert calls["jax_compilation_cache_dir"] == "/from/cfg"


def test_create_mesh_flat_list_is_for_non_tpu_devices_only(monkeypatch):
    """`create_device_mesh` refusing a layout is flattened on CPU devices and
    propagates on TPU ones (a wrong topology must not be hidden)."""
    from jax.experimental import mesh_utils

    def refuse(*a, **k):
        raise NotImplementedError("no such topology")

    monkeypatch.setattr(mesh_utils, "create_device_mesh", refuse)
    assert create_mesh({"data": 8}).devices.shape == (8,)

    class FakeTpu:
        platform = "tpu"

    with pytest.raises(NotImplementedError, match="no such topology"):
        create_mesh({"data": 2}, devices=[FakeTpu(), FakeTpu()])
