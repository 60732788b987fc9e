"""Config layer tests (reference read_conf_file parity: server.c:61-90)."""

import jax.numpy as jnp
import pytest

from dsort_tpu.config import (
    ConfigError,
    JobConfig,
    MeshConfig,
    SortConfig,
    load_conf_file,
)


def test_load_conf_file_reference_format(tmp_path):
    # server.conf:1 / client.conf:1-2 exact format
    p = tmp_path / "server.conf"
    p.write_text("SERVER_PORT=9008\n")
    assert load_conf_file(p) == {"SERVER_PORT": "9008"}
    p2 = tmp_path / "client.conf"
    p2.write_text("SERVER_IP=128.226.114.205\nSERVER_PORT=9008\n")
    assert load_conf_file(p2) == {
        "SERVER_IP": "128.226.114.205",
        "SERVER_PORT": "9008",
    }


def test_load_conf_file_comments_and_blank(tmp_path):
    p = tmp_path / "c.conf"
    p.write_text("# comment\n\nKEY = spaced value \n")
    assert load_conf_file(p) == {"KEY": "spaced value"}


def test_load_conf_file_missing_raises():
    with pytest.raises(ConfigError, match="not found"):
        load_conf_file("/nonexistent/x.conf")


def test_load_conf_file_malformed_raises(tmp_path):
    p = tmp_path / "bad.conf"
    p.write_text("NOEQUALS\n")
    with pytest.raises(ConfigError, match="KEY=value"):
        load_conf_file(p)


def test_sort_config_from_mapping():
    cfg = SortConfig.from_mapping(
        {
            "SERVER_IP": "10.0.0.1",
            "SERVER_PORT": "9999",
            "NUM_WORKERS": "8",
            "KEY_DTYPE": "int64",
            "CAPACITY_FACTOR": "3.5",
        }
    )
    assert cfg.server_ip == "10.0.0.1"
    assert cfg.server_port == 9999
    assert cfg.mesh.num_workers == 8
    assert cfg.job.key_dtype == jnp.int64
    assert cfg.job.capacity_factor == 3.5


def test_sort_config_defaults_match_reference():
    cfg = SortConfig()
    assert cfg.server_port == 9008  # server.conf:1
    assert cfg.output_path == "output.txt"  # server.c:517


def test_validation_errors():
    with pytest.raises(ConfigError):
        MeshConfig(num_workers=0)
    with pytest.raises(ConfigError):
        JobConfig(capacity_factor=0.5)
    with pytest.raises(ConfigError):
        JobConfig(oversample=0)


def test_from_mapping_rejects_zero_values():
    # Regression: explicit 0 must hit validation, not be silently defaulted.
    with pytest.raises(ConfigError):
        SortConfig.from_mapping({"OVERSAMPLE": "0"})
    with pytest.raises(ConfigError):
        SortConfig.from_mapping({"DP": "0"})


@pytest.mark.parametrize("env_dir", [None, "from_env"])
def test_compile_cache_placement(env_dir, tmp_path, monkeypatch):
    """`use_compile_cache` (called by `dsort` and chip_smoke.py):
    a set ``JAX_COMPILATION_CACHE_DIR`` is JAX's to read and nothing is set
    in code; unset, the cache is the checkout's fixed ``.jax_cache``."""
    import os

    import jax

    from dsort_tpu.utils.backend import CACHE_DIR, use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    calls = []
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        assert calls == []
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert use_compile_cache() == CACHE_DIR
        assert calls == [("jax_compilation_cache_dir", CACHE_DIR)]
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert CACHE_DIR == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == before
