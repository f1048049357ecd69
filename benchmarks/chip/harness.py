"""The benchmark harness: resolve a cell by name, set it up, run its
measured window, check what the timed path produced, and build the result
line.

Everything that belongs to one cell is found by name (``named.py``):

* ``BENCHMARK.json`` (at the checkout's root) names the cell's
  configuration, traffic mix and metrics;
* ``configs/<config>.json``: the deployment, whose ``generator`` and
  ``backend`` name ``generators/<generator>.py`` and
  ``backends/<backend>.py``;
* ``traffic/<mix>.json``: the traffic (``traffic.py`` reads it), whose
  ``loop`` names ``loops/<loop>.py``;
* ``e2e/<metric>.py`` and ``layers/<metric>.py`` (or
  ``layers/<quantity>.py`` for ``<quantity>.<suffix>``): each metric's
  reader, a function ``read(win)`` of the ``Window`` that returns a
  number, or None when what it reads is not in the run.

The store is driven only through ``repro.api``.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

import compare
import named
import tracereduce
from traffic import Traffic, load_mix
from ustream import make_graph, rng_for

BENCH_REL = pathlib.Path("benchmarks/chip")
CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    end_to_end: list          # (metric entry of BENCHMARK.json, reader)
    per_layer: list           # (metric entry of BENCHMARK.json, reader)
    generator: object         # generators/<generator>.py
    backend: object           # backends/<backend>.py
    loop: object              # loops/<loop>.py


def build_cell(name: str, cfg: dict, mix: dict, chips: int, e2e: list,
               layer: list, bench: pathlib.Path = named.HERE) -> Cell:
    """A cell from its configuration, mix and metric entries, with every
    part they name loaded from ``bench``."""
    if cfg["chips"] != chips:
        raise ValueError(f"{name}: the cell asks {chips} chips, its "
                         f"configuration {cfg['chips']}")
    return Cell(
        name, cfg, mix, chips,
        [(m, named.load("e2e", m["name"], bench)) for m in e2e],
        [(m, named.load("layers", m["name"], bench, suffix_fallback=True))
         for m in layer],
        named.load("generators", cfg["generator"], bench),
        named.load("backends", cfg["backend"], bench),
        named.load("loops", mix["loop"], bench))


def resolve_cell(name: str, root: pathlib.Path = CHECKOUT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    wl = cells[name]
    cfgs = {c["name"]: c for c in spec["configs"]}
    cfg = json.loads((root / cfgs[wl["config"]]["file"]).read_text())
    bench = root / BENCH_REL
    mix = load_mix(bench / "traffic" / f"{wl['traffic']}.json")
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in moved
                              else [])]
    return build_cell(name, cfg, mix, wl["chips"], e2e, layer, bench)


@dataclasses.dataclass
class Window:
    """What the measured window did; metric readers get it as ``win``."""
    setup_s: float = 0.0
    window_s: float = 0.0
    rounds: int = 0
    write_ops: int = 0
    apply_s: list = dataclasses.field(default_factory=list)
    read_s: list = dataclasses.field(default_factory=list)
    analytics_s: list = dataclasses.field(default_factory=list)
    compiles: int = 0         # programs compiled or loaded in the window
    counters: dict = dataclasses.field(default_factory=dict)
    memory_peak_bytes: int = 0
    store_kwargs: dict = dataclasses.field(default_factory=dict)
    peaks: dict = dataclasses.field(default_factory=dict)
    trace: object = None      # tracereduce.TraceSummary

    @property
    def n_reads(self):
        return len(self.read_s)

    @property
    def n_analytics(self):
        return len(self.analytics_s)

    def record(self) -> dict:
        """What the window did, for the result line: counts, quartiles of
        the host-clock time of each call kind (ms), the store's counters."""
        def q(xs):
            if len(xs) < 2:
                return [1e3 * x for x in xs]
            return [1e3 * x for x in statistics.quantiles(xs, n=4)]
        return {"seconds": self.window_s, "rounds": self.rounds,
                "write_ops": self.write_ops, "reads": self.n_reads,
                "analytics": self.n_analytics,
                "programs_compiled_or_loaded": self.compiles,
                "apply_ms_quartiles": q(self.apply_s),
                "read_ms_quartiles": q(self.read_s),
                "analytics_ms_quartiles": q(self.analytics_s),
                "store_counters": self.counters}


@dataclasses.dataclass
class Drive:
    """What a loop (``loops/<loop>.py``) drives: the store, the traffic,
    the op log and answers the check reads, and the span annotation."""
    store: object
    traffic: Traffic
    run: compare.Run
    api: object
    span: object


class ProgramAnswers:
    """What the timed path answered: the final state through the store's
    own reads, the window's recorded read answers, its last PageRank."""

    def __init__(self, store, run: compare.Run):
        self.store = store
        self.run = run

    def final(self, ref, xs):
        from repro.api import ReadOp
        ids = self.run.graph["ids"][xs]
        return (self.store.read(ReadOp("num_edges")),
                np.asarray(self.store.read(ReadOp("degree", ids=ids))),
                self.store.read(ReadOp("neighbors", ids=ids)))

    def read(self, k: int):
        return self.run.reads[k][2]

    def pagerank(self, iters: int) -> np.ndarray:
        ids = self.run.graph["ids"]
        d = self.run.analytics[1]
        out = np.full(len(ids), np.nan)
        if d is None:
            return out
        keys = np.fromiter(d.keys(), np.uint64, len(d))
        vals = np.fromiter(d.values(), np.float64, len(d))
        order = np.argsort(ids)
        pos = np.clip(np.searchsorted(ids[order], keys), 0, len(ids) - 1)
        ok = ids[order][pos] == keys
        if not ok.all():        # a vertex the graph never had
            return out
        out[order[pos]] = vals
        return out


class _CompileCounter:
    """Counts XLA compiles and persistent-cache loads while active."""

    def __init__(self):
        self.n = 0

    def __call__(self, event, duration, **_):
        if event in ("/jax/core/compile/backend_compile_duration",
                     "/jax/compilation_cache/cache_retrieval_time_sec"):
            self.n += 1


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, peaks: dict, with_control: bool = False,
             log=None) -> dict:
    """Set up, measure, check. Returns the result line as a dict."""
    import jax
    from repro import api
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    cfg, mix = cell.config, cell.mix

    # ---- set-up: data from the seed, state, programs, warm-up ----
    graph = make_graph(cfg, seed, cell.generator)
    kw = cell.backend.store_kwargs(cfg)
    store = cell.backend.make_store(api, jax, cfg, cell.chips, kw)
    tr = Traffic(mix, graph, cell.chips, seed)
    run = compare.Run(graph, tr, cfg["undirected"])
    drive = Drive(store, tr, run, api, jax.profiler.TraceAnnotation)
    ids = graph["ids"]
    if mix["preload_ops"]:
        u, v, w = tr.preload()
        run.failed += store.apply(api.OpBatch.edges(ids[u], ids[v], w)).dropped
    cell.loop.warmup(drive)
    win = Window(store_kwargs=kw, peaks=peaks)
    win.setup_s = time.perf_counter() - t_start
    log(f"set-up: {win.setup_s:.3f} s, {tr.n_logged} ops applied")

    # ---- the measured window ----
    counter = _CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    stats0 = dict(store.stats)
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1      # the benchmark's spans, not the runtime's
        jax.profiler.start_trace(tdir, profiler_options=opts)
    with drive.span(tracereduce.WINDOW_SPAN):
        cell.loop.window(drive, win, seconds)
    if trace:
        jax.profiler.stop_trace()
    jax.monitoring.unregister_event_duration_listener(counter)
    win.compiles = counter.n
    win.counters = {k: v - stats0.get(k, 0) for k, v in store.stats.items()
                    if isinstance(v, (int, float))}
    devs = jax.devices()[:cell.chips]
    win.memory_peak_bytes = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    if trace:
        win.trace = tracereduce.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
    record = win.record()
    log("window: " + json.dumps(record))

    # ---- the check, after the window ----
    chk = mix["check"]
    sample = rng_for(seed, 3).choice(graph["V"], min(chk["vertices"],
                                                     graph["V"]),
                                     replace=False)
    iters = (mix["analytics"]["params"].get("iters")
             if mix["analytics"] else None)
    checks = compare.compare(run, ProgramAnswers(store, run), sample,
                             chk["reads"], rng_for(seed, 4), iters)
    control = None
    if with_control:
        control = compare.compare(run, compare.ControlAnswers(run), sample,
                                  chk["reads"], rng_for(seed, 4), iters)

    # ---- the result line ----
    metrics = {}
    for m, reader in (cell.per_layer if trace else cell.end_to_end):
        v = reader.read(win)
        if v is None and not trace:
            raise RuntimeError(f"{cell.name}: the window gave no "
                               f"{m['name']}")
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": int(win.memory_peak_bytes)}
    out = {"correct": compare.passed(checks),
           "attempted": win.write_ops + win.n_reads + win.n_analytics,
           "failed": int(run.failed), "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = win.trace.busy_s
        device["window_s"] = win.trace.window_s
        out["breakdown"] = {"device_ops": win.trace.top_ops(10),
                            "idle_gaps": win.trace.idle_gaps(10)}
    out["window"] = record
    if control is not None:
        out["control_checks"] = control
        out["control_correct"] = compare.passed(control)
    out["checks"] = checks
    return out
