"""utils/jaxenv.py: the daemon's boot device check and the one place
the compile cache directory is decided."""

import os
from types import SimpleNamespace

import jax
import pytest

from opentsdb_tpu.utils import jaxenv


def _devices(platform, kind, n=1):
    return lambda *a, **k: [SimpleNamespace(platform=platform,
                                            device_kind=kind, id=i)
                            for i in range(n)]


def test_tpu_passes(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(jax, "devices", _devices("tpu", "TPU v5 lite"))
    assert jaxenv.require_serving_device("tpu") == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def test_silent_cpu_exits_nonzero(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(jax, "devices", _devices("cpu", "cpu"))
    with pytest.raises(SystemExit) as e:
        jaxenv.require_serving_device("tpu")
    assert e.value.code not in (0, None)


def test_cpu_asked_for_by_name_passes(monkeypatch):
    monkeypatch.setattr(jax, "devices", _devices("cpu", "cpu", 8))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert jaxenv.require_serving_device("tpu")["count"] == 8
    monkeypatch.delenv("JAX_PLATFORMS")
    assert jaxenv.require_serving_device("cpu")["platform"] == "cpu"


def test_tsd_refuses_to_boot_on_a_silent_cpu(monkeypatch, tmp_path):
    """`tsdb tsd` with the default --backend tpu and no TPU exits
    non-zero at boot, before the store is opened."""
    from opentsdb_tpu.tools import cli

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(jaxenv, "setup_compile_cache", lambda: "unused")
    with pytest.raises(SystemExit) as e:
        cli.main(["tsd", "--port", "0", "--bind", "127.0.0.1",
                  "--wal", str(tmp_path / "wal")])
    assert e.value.code not in (0, None)
    assert not os.path.exists(tmp_path / "wal")


def test_compile_cache_dir_comes_from_outside(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    jaxenv.setup_compile_cache()
    assert all("dir" not in k for k, _ in calls), calls
    calls.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    jaxenv.setup_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert (jaxenv._CACHE_OPTION,
            os.path.join(repo, ".jax_cache")) in calls
