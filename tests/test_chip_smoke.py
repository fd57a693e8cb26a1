"""chip_smoke.py without a chip: its phases on CPU at a tiny size, its
refusal to run off the chip, and the runtime helpers it relies on.

On CPU the kernels interpret; the phases still check every batched run
against the scalar cluster, so the script's control flow is guarded here
and only the chip run measures anything.
"""

from __future__ import annotations

import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

from repro import runtime

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_match_scalar_on_cpu(chip_smoke):
    rows = chip_smoke.one_chip_phases(n_keys=256, ticks=60, jnp_ticks=30)
    assert [r["phase"] for r in rows] == [
        "kernel_cp", "kernel_all_aboard", "jnp_cp"]
    for r in rows:
        assert r["identical_to_scalar"] and r["completions"] > 0
        # the plane was sized to the universe before the first op
        assert r["kv_plane"] == [18, chip_smoke.N_MACHINES, 256]
        assert r["fused_calls"] > 0 and r["h2d_bytes"] > 0
    assert [r["use_kernel"] for r in rows] == [True, True, False]
    assert rows[1]["paths"]["all_aboard_fast"] > 0
    assert rows[0]["paths"]["cp_slow"] > 0


class _CpuDevice:
    platform = "cpu"
    device_kind = "cpu"


def test_main_refuses_without_tpu(chip_smoke, capsys, monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda: [_CpuDevice()])
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no TPU" in captured.err


def test_script_alone_exits_nonzero(tmp_path):
    """Copied out of the repo, the script finds no chip here (and no
    package there) and prints no result line."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_kernels_interpret_off_the_chip():
    assert runtime.kernel_interpret() == (jax.default_backend() != "tpu")


def test_compile_cache_dir_from_env_or_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert runtime.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert runtime.use_compile_cache() == str(runtime.REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(
            ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
