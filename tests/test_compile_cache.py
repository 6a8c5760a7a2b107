"""The persistent compilation cache goes where JAX_COMPILATION_CACHE_DIR
says, or else to ``.jax_cache/`` at the root of the checkout.  Each case
runs in a child process: the cache setting is process-global."""

import os
import subprocess
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parent.parent / "src"


def _run(code: str, **env) -> str:
    base = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    full = {**base, "PYTHONPATH": str(SRC), "JAX_PLATFORMS": "cpu", **env}
    return subprocess.run([sys.executable, "-c", code], env=full, check=True,
                          capture_output=True, text=True).stdout.strip()


def test_entries_land_in_the_directory_from_the_environment(tmp_path):
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.compile_cache import use_compile_cache\n"
        "print(use_compile_cache())\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)).block_until_ready()\n"
    )
    out = _run(code, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert out == str(tmp_path)
    assert any(p.name.startswith("jit__lambda") for p in tmp_path.iterdir())


def test_default_is_the_checkout_cache_dir():
    code = (
        "import jax\n"
        "from repro.compile_cache import use_compile_cache\n"
        "use_compile_cache()\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    assert _run(code) == str(SRC.parent / ".jax_cache")
