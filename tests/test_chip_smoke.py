"""The chip smoke's guards, checked on the CPU: chip_smoke.py refuses to
run (and reports nothing) without a TPU, and the entry points keep JAX's
compilation cache where compile_cache says."""
import importlib.util
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    smoke = _load_chip_smoke()
    with pytest.raises(SystemExit, match="no TPU found"):
        smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def _child(code: str, **env):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": os.path.join(ROOT, "src"), **env}
    env = {k: v for k, v in env.items() if v is not None}
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=300,
        cwd=ROOT,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    return res.stdout.strip().splitlines()[-1]


_COMPILE = """
from repro.compile_cache import enable_compile_cache
path = enable_compile_cache()
import jax, jax.numpy as jnp
jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)))
print(path, jax.config.jax_compilation_cache_dir)
"""


def test_compile_cache_honours_env_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there."""
    out = _child(
        _COMPILE,
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
    )
    assert out == f"{tmp_path} {tmp_path}"
    assert any(p.name.startswith("jit_") for p in tmp_path.iterdir())


def test_compile_cache_defaults_to_checkout_dir():
    """Without the variable the cache sits at <checkout>/.jax-cache: a fixed
    path (never a temporary or per-run one), already in .gitignore."""
    out = _child(
        "from repro.compile_cache import enable_compile_cache\n"
        "import jax\n"
        "print(enable_compile_cache(), jax.config.jax_compilation_cache_dir)\n",
        JAX_COMPILATION_CACHE_DIR=None,
    )
    want = os.path.join(ROOT, ".jax-cache")
    assert out == f"{want} {want}"
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax-cache/" in f.read().split()
