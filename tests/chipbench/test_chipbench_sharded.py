"""The u21x4 configuration's sharded path (``ShardedStore``, four shards,
owner-routed exchange) through the harness on four virtual CPU devices,
at a tiny size: the run comes out correct, so every op landed on its
owner. Runs in a child process, because the device count is fixed when
JAX starts."""
import json
import os
import subprocess
import sys

from conftest import BENCH, ROOT

CHILD = r"""
import json, sys, time
sys.path.insert(0, sys.argv[1])
import jax
assert len(jax.devices()) == 4, jax.devices()
import harness
from conftest import shrink
from traffic import load_mix
cfg = json.load(open(harness.CHECKOUT / harness.BENCH_REL / "configs/u21x4.json"))
mix = load_mix(harness.CHECKOUT / harness.BENCH_REL / "traffic/insert.json")
e2e = [{"name": "ingest_ops_per_s", "unit": "ops/s"},
       {"name": "setup_s", "unit": "s"}]
cell = shrink(harness.build_cell("u21x4.insert", cfg, mix, 4, e2e, []), scale=9)
cell.config["edges"] = 512 << 9
cell.mix["check"]["vertices"] = 64
out = harness.run_cell(cell, 2**31 + 3, 1.0, False, time.perf_counter(),
                       {"hbm_bytes_per_s": 819e9}, log=lambda *a: None)
print(json.dumps(out))
"""


def test_u21x4_sharded_path_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"),
                                           str(ROOT / "tests" / "chipbench")]))
    p = subprocess.run([sys.executable, "-c", CHILD, str(BENCH)], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    assert out["window"]["write_ops"] > 0
    assert out["device"]["count"] == 4
