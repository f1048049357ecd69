"""The chip smoke's own logic at a tiny size on the CPU: its numpy
reference agrees with both stores, and the script refuses to report a
result without a TPU."""
import importlib.util
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_checks_pass_on_small_graph(smoke):
    rec = smoke.run(log2_v=8, edgefactor=4, chunk=1 << 9)
    assert rec["impl"]["append"] in ("ref", "pallas")
    assert rec["defrags"] >= 1        # the forced rebuild ran and was checked
    assert set(rec["phase_host_wall_s"]) >= {"ingest_s", "reads_s",
                                             "bfs_s", "pagerank_s"}


def test_smoke_sharded_path_on_four_devices():
    """The ``--chips 4`` phase on four host devices: the sharded store's
    answers match the reference and every state leaf spans all four."""
    code = ("import sys; sys.path.insert(0, sys.argv[1]); import chip_smoke; "
            "chip_smoke.run(log2_v=8, edgefactor=4, n_shards=4, "
            "chunk=1 << 9)")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code, str(ROOT)], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=str(ROOT))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "every state leaf has shards on all 4 devices" in proc.stdout


def test_smoke_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300, cwd=str(ROOT))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
