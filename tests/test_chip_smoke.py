"""``chip_smoke.py`` at a tiny size on the CPU.

The script is the quickest proof that the system still starts on the
chip; these tests keep its phases (served path with WAL recovery, Pallas
against XLA, sharded SDE and Federation) working between chip runs, and
pin that it refuses to report a result without a TPU.
"""
import importlib.util
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("chip_smoke", mod)
    spec.loader.exec_module(mod)
    return sys.modules["chip_smoke"]


def _tiny(smoke):
    return smoke.Deployment(n_streams=2048, window=8192, n_windows=4,
                            n_clients=4, n_continuous=32, n_queried_rows=256,
                            md_records=512, pallas_rows=64,
                            pallas_tuples=1024)


def test_served_path_recovers_byte_equal(smoke, tmp_path, capsys):
    smoke.run_served(_tiny(smoke), 0, tmp_path / "run")
    out = capsys.readouterr().out
    assert '"phase": "recover"' in out
    assert not (tmp_path / "run").exists()      # WAL + checkpoints removed


def test_pallas_phase_matches_xla(smoke, capsys):
    smoke.run_pallas(_tiny(smoke), 0)
    out = capsys.readouterr().out
    assert out.count('"byte_equal": true') == len(smoke.PALLAS_KINDS)


def test_refuses_to_report_without_a_tpu(smoke, capsys):
    import jax
    was = jax.config.jax_compilation_cache_dir     # main() sets it
    try:
        assert smoke.main([]) != 0
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert '"ok": true' not in capsys.readouterr().out


def test_compile_cache_dir_is_env_or_checkout(monkeypatch, tmp_path):
    import jax
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compilation_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable_compilation_cache() == \
            str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_check_catches_a_wrong_answer(smoke):
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_cm(9.0, 10.0, 10.0, 0.01, "under-count")
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_hll(120.0, 100.0, 0.0325, "too far")


_FOUR_DEVICE_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, sys.argv[1])
    import chip_smoke as cs
    dep = cs.Deployment(n_streams=1024, window=4096, n_windows=2,
                        n_clients=4, n_continuous=16, n_queried_rows=128,
                        md_records=256)
    cs.run_sharded(dep, 0, 4)
    cs.run_federation(dep, 0, 4)
    print("OK")
""")


def test_mesh_and_federation_phases_on_4_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    out = subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICE_SCRIPT, str(ROOT)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert '"phase": "mesh", "devices": 4, "byte_equal": true' in out.stdout
    assert "OK" in out.stdout
