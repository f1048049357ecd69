"""BENCHMARK.json and the files it names: every cell resolves its
configuration, traffic and metric readers by name, a new cell needs only
new files and entries, and the run command refuses to run off the chip."""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_spec_keeps_to_the_contract():
    spec = _spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"][1] == "benchmarks/chip/run.py"
    for p in spec["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert 1 <= spec["run_seconds"] <= 51
    names = [c["name"] for c in spec["configs"]]
    cells = [w["name"] for w in spec["workloads"]]
    metrics = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    for n in names + cells + metrics:
        assert NAME.match(n), n
    assert len(set(names)) == len(names) and len(set(cells)) == len(cells)
    assert len(set(metrics)) == len(metrics)
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for c in spec["configs"]:
        assert any(w["config"] == c["name"] for w in spec["workloads"])
        assert c["file"].startswith("benchmarks/chip/")
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e["setup_s"]
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", cells)
    for w in spec["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    layers = (ROOT / "PERF.md").read_text()
    for m in spec["per_layer"]:
        assert m["layer"] in layers, m["layer"]


@pytest.mark.parametrize("cell", [w["name"] for w in _spec()["workloads"]])
def test_every_cell_resolves_by_name(cell):
    import harness
    c = harness.resolve_cell(cell)
    assert c.config["chips"] == c.chips
    got = {m["name"] for m, _ in c.end_to_end}
    assert "setup_s" in got and len(got) >= 2
    assert c.per_layer and all(callable(r.read) for _, r in c.per_layer)
    assert all(callable(r.read) for _, r in c.end_to_end)
    assert callable(c.generator.generate)
    assert callable(c.backend.store_kwargs) and callable(c.loop.window)


def test_configs_cut_only_scale():
    """Each configuration keeps its source's inserts per vertex: vertices
    and edges are the source's cut by one power of two."""
    for c in _spec()["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        src = cfg["source_counts"]
        cut = src["vertices"] / cfg["vertices"]
        assert cut == pytest.approx(2 ** round(np.log2(cut)), rel=1e-5)
        assert src["edges"] / cfg["edges"] == pytest.approx(cut, rel=1e-5)
        assert set(c["reduced"]) >= {"vertices", "edges"}


NEW_FILES = {
    "configs/u18r.json": None,            # written in the test
    "traffic/hot_reads.json": None,
    "generators/ring.py": (
        "import numpy as np\n"
        "def generate(cfg, V, E, rng):\n"
        "    ids = rng.choice(1 << cfg['id_bits'], V, replace=False)"
        ".astype(np.uint64)\n"
        "    su = np.arange(E) % V\n"
        "    return dict(ids=ids, su=su, sv=(su + 1) % V,\n"
        "                w=np.ones(E, np.float32))\n"),
    "backends/local_half.py": (
        "from ustream import capacity\n"
        "def store_kwargs(cfg):\n"
        "    return dict(capacity(cfg, 1)['common'], n_max=cfg['vertices'])\n"
        "def make_store(api, jax, cfg, chips, kw):\n"
        "    return api.make_store('local', **kw)\n"),
    "loops/paced.py": (
        "def warmup(drive):\n    pass\n"
        "def window(drive, win, seconds):\n    win.window_s = seconds\n"),
    "e2e/reads_per_s.py": (
        "def read(win):\n    return win.n_reads / win.window_s\n"),
    "layers/reads_per_round.py": (
        "def read(win):\n    return win.n_reads / win.rounds\n"),
}


def test_a_new_cell_needs_only_new_files_and_entries(tmp_path):
    """A temporary configuration with its own generator and backend, a
    mix with its own loop, an end-to-end and a per-layer metric are found
    by name: the existing files are copied unchanged, and only new files
    and new BENCHMARK.json entries are added."""
    import harness
    from ustream import make_graph
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench)
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    spec = _spec()
    cfg = json.loads((BENCH / "configs" / "u19.json").read_text())
    cfg.update(name="u18r", vertices=64, edges=256, generator="ring",
               backend="local_half")
    mix = json.loads((BENCH / "traffic" / "serve.json").read_text())
    mix.update(loop="paced", read=dict(mix["read"], ids=16))
    for rel, text in NEW_FILES.items():
        assert not (bench / rel).exists(), rel
        (bench / rel).write_text(text or "")
    (bench / "configs/u18r.json").write_text(json.dumps(cfg))
    (bench / "traffic/hot_reads.json").write_text(json.dumps(mix))
    spec["configs"].append(dict(spec["configs"][0], name="u18r",
                                file="benchmarks/chip/configs/u18r.json"))
    spec["workloads"].append(dict(name="u18r.hot_reads", config="u18r",
                                  traffic="hot_reads", chips=1, why="test"))
    spec["end_to_end"].append(dict(name="reads_per_s", unit="1/s",
                                   better="higher", bound=0.01,
                                   source="host_clock",
                                   workloads=["u18r.hot_reads"]))
    spec["per_layer"].append(dict(name="reads_per_round.hot", unit="1",
                                  better="higher", source="program_counter",
                                  layer="read path", moves="reads_per_s",
                                  workloads=["u18r.hot_reads"]))
    spec["per_layer"].append(dict(name="device_idle_share.hot", unit="%",
                                  better="lower", source="device_trace",
                                  layer="device", moves="reads_per_s",
                                  workloads=["u18r.hot_reads"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    c = harness.resolve_cell("u18r.hot_reads", root=tmp_path)
    for p, b in before.items():             # nothing that was there moved
        assert p.read_bytes() == b, p
    assert c.config["vertices"] == 64 and c.mix["read"]["ids"] == 16
    assert c.loop.__file__.endswith("loops/paced.py")
    g = make_graph(c.config, 2**31 + 5, c.generator)
    assert np.array_equal(g["sv"], (g["su"] + 1) % 64) and g["E"] == 256
    kw = c.backend.store_kwargs(c.config)
    assert kw["n_max"] == 64 and "batch" not in kw
    e2e = {m["name"]: r for m, r in c.end_to_end}
    assert set(e2e) == {"reads_per_s", "setup_s"}
    readers = {m["name"]: r for m, r in c.per_layer}
    # a new suffix of an existing quantity needs no new reader
    assert set(readers) == {"reads_per_round.hot", "device_idle_share.hot"}
    win = harness.Window(rounds=4, read_s=[0.1, 0.2], window_s=2.0)
    assert e2e["reads_per_s"].read(win) == 1.0
    assert readers["reads_per_round.hot"].read(win) == 0.5
    assert readers["device_idle_share.hot"].read(win) is None   # no trace


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "u19.insert", "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    p = _run(ROOT, {})
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's paths
    has no program: the run fails and prints no result."""
    for p in _spec()["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
