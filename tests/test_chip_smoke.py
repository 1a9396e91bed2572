"""chip_smoke.py off the chip, and the two rules it rests on: a parent
that imports the program holds no chip, and the compile cache is placed
from outside."""

import json
import os
import subprocess
import sys

import jax
import pytest
from conftest import REPO, device_env

from mlx_cuda_distributed_pretraining_tpu.utils import compile_cache

SMOKE = os.path.join(REPO, "chip_smoke.py")


def _smoke(*argv, timeout=300):
    return subprocess.run([sys.executable, SMOKE, *argv], env=device_env(1),
                          capture_output=True, text=True, timeout=timeout)


def test_chip_smoke_rehearsal_runs_every_phase():
    """``--rehearse`` on CPU reaches the last line through every phase, at
    tiny widths — and that line never claims a chip."""
    proc = _smoke("--rehearse")
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    for phase in ("device", "kernels", "train", "serve"):
        assert f"== {phase}: ok" in proc.stdout, proc.stdout[-2000:]
    assert "token-identical" in proc.stdout
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is not True and last["rehearsal"] == "passed"
    assert last["device"]["platform"] == "cpu"


def test_chip_smoke_without_a_chip_fails_and_prints_no_result():
    proc = _smoke()
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "JAX found no TPU" in proc.stderr


def test_importing_entry_points_initializes_no_backend():
    """Supervisors, routers and launchers import these and must stay off
    the chip so that their children can have it."""
    modules = [f"mlx_cuda_distributed_pretraining_tpu.{m}" for m in (
        "train.supervisor", "serve.fleet", "serve.router", "parallel.launch",
        "train.trainer", "infer.server", "serve.engine", "utils.compile_cache",
    )] + ["chip_smoke"]
    code = (
        "import importlib, sys\n"
        "from jax._src import xla_bridge\n"
        f"for m in {modules!r}:\n"
        "    importlib.import_module(m)\n"
        "    assert not xla_bridge.backends_are_initialized(), m\n"
        "print('no backend')\n")
    proc = subprocess.run([sys.executable, "-c", code], env=device_env(1),
                          capture_output=True, text=True, timeout=120, cwd=REPO)
    assert proc.returncode == 0 and "no backend" in proc.stdout, proc.stderr[-2000:]


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir_is_the_environments_or_the_checkouts(
        from_env, monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set JAX reads it itself and the helper
    sets no directory; unset, the one fixed path inside the checkout."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    # The suite runs with the cache off (conftest); the helper says so.
    assert compile_cache.enable_compilation_cache().startswith(
        "compilation cache: off")
    updates = []
    if from_env:
        monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path / "xla"))
    else:
        monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    monkeypatch.setattr(cc, "reset_cache", lambda: None)
    jax.config.update("jax_enable_compilation_cache", True)
    try:
        # Record, don't apply: the suite must not gain a shared cache.
        monkeypatch.setattr(jax.config, "update",
                            lambda name, value: updates.append((name, value)))
        line = compile_cache.enable_compilation_cache()
    finally:
        monkeypatch.undo()
        jax.config.update("jax_enable_compilation_cache", False)
    dirs = [v for n, v in updates if n == "jax_compilation_cache_dir"]
    if from_env:
        assert dirs == [] and str(tmp_path / "xla") in line
        assert compile_cache.cache_dir() != str(tmp_path / "xla")  # env undone
    else:
        assert dirs == [os.path.join(REPO, ".jax_cache")]
        assert os.path.join(REPO, ".jax_cache") in line
    assert "cold" in line or "warm" in line
