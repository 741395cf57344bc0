"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
phases run end to end on the CPU at smoke width (interpret-mode kernels),
so a wrong path, argument or assertion shows up before a chip call. Also
the compile-cache helper the launchers share."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def _small(c):
    return dict(c, requests=4, prompt_len=24, max_new=4, max_batch=4,
                forced_steps=6)


def test_refuses_to_run_without_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_one_chip_phase_at_smoke_width(monkeypatch, capsys):
    # interpret mode leaves no compiled kernel call in the HLO to look for
    monkeypatch.setattr(chip_smoke, "KERNEL_CALL", "")
    res = chip_smoke.one_chip(_small(chip_smoke.ONE_CHIP), smoke=True,
                              compute_dtype=jnp.float32)
    # the interpret-mode fused path is bit-exact with the jnp path
    assert res == {"max_abs": 0.0, "max_rel": 0.0, "argmax_agreement": 1.0}
    out = capsys.readouterr().out
    assert "batched-greedy-fused: served 4 requests" in out


def test_four_chip_phase_at_smoke_width(tmp_path):
    """The mesh phase on four virtual CPU devices, in a fresh process: the
    device count has to be fixed before JAX starts."""
    code = ("import json, jax, jax.numpy as jnp, chip_smoke\n"
            "from test_chip_smoke import _small\n"
            "res = chip_smoke.four_chip(jax.devices(), _small(chip_smoke.FOUR_CHIP),"
            " smoke=True, compute_dtype=jnp.float32)\n"
            "print(json.dumps(res))\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(REPO / "src"), str(REPO),
                                          str(REPO / "tests")]),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = proc.stdout
    assert "cascade: decode step partial-sum all-reduces 0" in out
    assert "megatron: decode step partial-sum all-reduces 0" not in out
    res = json.loads(out.strip().splitlines()[-1])
    assert res["max_rel"] <= chip_smoke.MESH_REL_TOL


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert enable_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_lands_in_env_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets nothing, and the
    entries of a compile land in that directory (CPU, fresh process)."""
    code = ("import jax, jax.numpy as jnp, json\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "path = enable_compile_cache()\n"
            "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64)))\n"
            "print(json.dumps([path, jax.config.jax_compilation_cache_dir]))\n")
    env = {**os.environ, "PYTHONPATH": str(REPO / "src"), "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"}
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-800:]
    path, used = json.loads(proc.stdout.strip().splitlines()[-1])
    assert path == used == str(tmp_path)
    assert any(tmp_path.iterdir()), "no cache entry was written"
