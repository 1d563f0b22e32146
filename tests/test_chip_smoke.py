"""chip_smoke.py refuses to run without a TPU, and the compile cache is
placed from outside (``launch/cache.py``)."""
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _run(args, cwd, env_update=None, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_update or {})
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=120,
    )


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_tpu(where, tmp_path):
    """On the CPU, and in a directory holding only the script, it exits
    non-zero and prints no result line."""
    if where == "checkout":
        script, cwd = ROOT / "chip_smoke.py", ROOT
    else:
        script = tmp_path / "chip_smoke.py"
        shutil.copy(ROOT / "chip_smoke.py", script)
        cwd = tmp_path
    proc = _run(
        [str(script)], cwd,
        env_update={"JAX_PLATFORMS": "cpu",
                    "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")},
        drop=("PYTHONPATH",),
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    if where == "checkout":
        assert "platform 'cpu'" in proc.stderr, proc.stderr


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "default"])
def test_compile_cache_placement(from_env, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins and is left to JAX; otherwise the
    cache goes to the fixed ``<checkout>/.jax_cache``."""
    code = (
        "import jax; from repro.launch.cache import init_compile_cache; "
        "print(init_compile_cache()); "
        "print(jax.config.jax_compilation_cache_dir)"
    )
    env_dir = str(tmp_path / "cache")
    proc = _run(
        ["-c", code], ROOT,
        env_update={"JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT / "src"),
                    **({"JAX_COMPILATION_CACHE_DIR": env_dir}
                       if from_env else {})},
        drop=() if from_env else ("JAX_COMPILATION_CACHE_DIR",),
    )
    assert proc.returncode == 0, proc.stderr
    returned, configured = proc.stdout.split()
    want = env_dir if from_env else str(ROOT / ".jax_cache")
    assert returned == configured == want
