"""Process start-up: importing the package touches no backend, and the
entry points' compile cache lands where ``JAX_COMPILATION_CACHE_DIR`` says,
else at ``<checkout>/.jax_cache``."""
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro import runtime

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


def test_import_queries_no_backend():
    code = ("import repro.api, repro.storage, repro.kernels.ops\n"
            "from jax._src import xla_bridge as xb\n"
            "raise SystemExit(1 if xb._backends else 0)\n")
    env = dict(os.environ, PYTHONPATH=str(SRC))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]


@pytest.mark.parametrize("env_dir", [None, "custom"])
def test_compile_cache_dir(env_dir, monkeypatch, tmp_path):
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(runtime.CHECKOUT / ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert runtime.init_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
