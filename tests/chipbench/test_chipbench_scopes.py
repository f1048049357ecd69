"""The store's named scopes and host spans in the trace reduction
(``scopetrace.py``), on small traces recorded on one TPU v5e by
``record_trace.py`` (``run.py --trace 1 --seconds 0``: set-up, then a
traced window of one round of the cell), cut to the device plane and the
``bench.*`` and ``graph.*`` host spans:

* ``u19_scoped_insert_one_apply``: ``u19.insert``, one 16,384-op apply (8
  executions of the edge-update step);
* ``u19_scoped_serve_one_round``: ``u19.serve``'s first window round, one
  2,048-op apply, capture and a 64-ID ``neighbors`` read, no PageRank;

and on ``u19_insert_one_apply``, recorded before the program had scopes
or spans. Their numbers are pinned, so a change that moves the yardstick
shows here."""
import time

import pytest

import harness
import kernel_bytes
import scopetrace
import tracereduce
from conftest import BENCH
from ustream import store_kwargs

FIX = BENCH / "fixtures"
STEP = "jit_step_update_edges"
READ = "jit_step_neighbors"
STEP_SCOPES = ("vertex_index", "group", "compact", "probe", "append")
READ_SCOPES = ("vertex_index", "gather", "compact")
NEW_INSERT = ("probe_device_ms.insert", "compact_device_ms.insert",
              "stage_idle_ms.insert")
# device seconds of the 8 steps in the insert fixture, by scope
PINNED_STEP_SCOPES = {"vertex_index": 0.016962862, "group": 0.002425961,
                      "compact": 0.265670828, "probe": 0.6506118,
                      "append": 0.018127827}
PINNED_STEP_OPS = 0.997742015            # every non-container op
PINNED_STAGE_IDLE_MS = 21.529297         # one flush
PINNED_FETCH_S = 0.046404429             # the read's graph.read.fetch span
PINNED_FETCH_IDLE_S = 0.046404429        # all of it idle on the device


@pytest.fixture(scope="module")
def first_cut():
    return scopetrace.load(FIX / "u19_insert_one_apply.xplane.pb.gz")


@pytest.fixture(scope="module")
def insert():
    return scopetrace.load(FIX / "u19_scoped_insert_one_apply.xplane.pb.gz")


@pytest.fixture(scope="module")
def serve():
    return scopetrace.load(FIX / "u19_scoped_serve_one_round.xplane.pb.gz")


def _window(cell_name, trace, **counters):
    cell = harness.resolve_cell(cell_name)
    win = harness.Window(trace=trace, store_kwargs=store_kwargs(cell.config),
                         peaks=kernel_bytes.peaks("TPU v5 lite"),
                         counters=counters, memory_peak_bytes=1)
    return {m["name"]: r.read(win) for m, r in cell.per_layer}, win


def test_loading_returns_a_scoped_trace():
    assert tracereduce.load is scopetrace.load


def test_decoder_reads_tf_op_on_the_first_cut(first_cut):
    ops = first_cut.tf_op
    probe = [k for k in ops if k.startswith("%fusion.262 = s32[8192]")]
    assert len(probe) == 1
    assert ops[probe[0]] == "jit(step_update_edges)/gather:"
    assert ops[next(k for k in ops if k.startswith("%compact_rows_pallas."))
               ].startswith("jit(step_update_edges)/cond/branch_0_fun/")
    assert first_cut.program_spans == []


def test_the_first_cut_keeps_every_number(first_cut):
    from jax.profiler import ProfileData
    plain = tracereduce.TraceSummary(ProfileData.from_serialized_xspace(
        scopetrace.raw_bytes(FIX / "u19_insert_one_apply.xplane.pb.gz")))
    k = r"^%compact_rows_pallas\.\d+ = "
    for t in (plain, first_cut):
        assert t.window_s == pytest.approx(0.74802545, rel=1e-6)
        assert t.busy_s == pytest.approx(0.722981486, rel=1e-6)
    assert first_cut.module_seconds(STEP) == plain.module_seconds(STEP)
    assert first_cut.op_seconds(k, STEP) == plain.op_seconds(k, STEP)
    assert first_cut.top_ops(10) == plain.top_ops(10)
    assert first_cut.idle_gaps(10) == plain.idle_gaps(10)


def test_new_readers_find_nothing_on_the_first_cut(first_cut):
    got, _ = _window("u19.insert", first_cut, flushes=1, host_stage_ms=1.0,
                     super_batches=1)
    assert all(got[m] is None for m in NEW_INSERT)
    assert first_cut.scope_seconds("probe", STEP) == 0
    got, _ = _window("u19.serve", first_cut)
    assert got["read_fetch_ms.serve"] is None


def test_step_scopes_are_pinned(insert):
    assert insert.module_count(STEP) == 8
    got = {s: insert.scope_seconds(s, STEP) for s in STEP_SCOPES}
    assert got == pytest.approx(PINNED_STEP_SCOPES, rel=1e-6)
    assert insert.scope_seconds(None, STEP) == pytest.approx(
        PINNED_STEP_OPS, rel=1e-6)


def test_step_scopes_cover_the_step_once(insert):
    total = insert.scope_seconds(None, STEP)
    scoped = sum(insert.scope_seconds(s, STEP) for s in STEP_SCOPES)
    assert 0.9 * total <= scoped <= total * (1 + 1e-9)
    # the defrag branch is named inside compact; no defrag ran here
    assert insert.scope_seconds("defrag", STEP) == 0


def test_insert_readers_on_the_scoped_trace(insert):
    got, _ = _window("u19.insert", insert, flushes=1, host_stage_ms=1.0,
                     super_batches=1)
    assert got["probe_device_ms.insert"] == pytest.approx(
        1e3 * PINNED_STEP_SCOPES["probe"] / 8, rel=1e-6)
    assert got["compact_device_ms.insert"] == pytest.approx(
        1e3 * PINNED_STEP_SCOPES["compact"] / 8, rel=1e-6)
    assert got["stage_idle_ms.insert"] == pytest.approx(PINNED_STAGE_IDLE_MS,
                                                        rel=1e-6)
    assert 50 < got["step_device_ms.insert"] < 200


def test_idle_is_labelled_by_the_program_spans(insert):
    names = {s[2] for s in insert.program_spans}
    assert {"graph.apply", "graph.apply.stage", "graph.apply.dispatch",
            "graph.apply.wait"} <= names
    idle = insert.window_s - insert.busy_s
    gaps = insert.idle_gaps(20)
    assert abs(sum(s for _, s in gaps) - idle) < 1e-6
    program = sum(s for name, s in gaps if name.startswith("graph."))
    assert program >= 0.9 * insert.idle_seconds_in({"bench.apply"})
    staged = insert.idle_seconds_in({"graph.apply.stage",
                                     "graph.apply.dispatch"})
    assert 0 < staged <= insert.idle_seconds_in({"graph.apply"}) <= idle


def test_read_fetch_spans(serve):
    fetch = [s for s in serve.program_spans if s[2] == "graph.read.fetch"]
    reads = [s for s in serve.program_spans if s[2] == "graph.read"]
    assert len(fetch) == len(reads) == 1
    assert reads[0][0] <= fetch[0][0] and fetch[0][1] <= reads[0][1]
    assert 1e-9 * (fetch[0][1] - fetch[0][0]) == pytest.approx(
        PINNED_FETCH_S, rel=1e-6)
    assert serve.idle_seconds_in({"graph.read.fetch"}) == pytest.approx(
        PINNED_FETCH_IDLE_S, rel=1e-6)
    gaps = serve.idle_gaps(20)
    program = sum(s for name, s in gaps if name.startswith("graph."))
    assert program >= 0.9 * serve.idle_seconds_in({"bench.apply",
                                                   "bench.read"})


def test_read_scopes_cover_the_read(serve):
    assert serve.module_count(READ) == 1
    total = serve.scope_seconds(None, READ)
    scoped = sum(serve.scope_seconds(s, READ) for s in READ_SCOPES)
    assert 0.9 * total <= scoped <= total * (1 + 1e-9)
    assert serve.scope_seconds("gather", READ) > \
        serve.scope_seconds("compact", READ)


def test_read_fetch_reader_takes_the_counter(serve):
    got, win = _window("u19.serve", serve, read_fetch_ms=300.0)
    assert got["read_fetch_ms.serve"] is None          # no reads counted
    win.read_s = [1.5, 1.6]
    cell = harness.resolve_cell("u19.serve")
    reader = dict((m["name"], r) for m, r in cell.per_layer)[
        "read_fetch_ms.serve"]
    assert reader.read(win) == 150.0


@pytest.mark.parametrize("name", ["u19.insert", "u19.serve"])
def test_a_traced_cpu_run_keeps_to_its_readers(tiny, name):
    """On the CPU the trace has no chip plane: the device-trace readers
    find nothing, the counter reader reads the program's counter."""
    out = harness.run_cell(tiny(name), 2**31 + 13, 0.5, True,
                           time.perf_counter(), {"hbm_bytes_per_s": 819e9},
                           log=lambda *a: None)
    assert out["correct"], out["checks"]
    got = out["metrics"]
    assert not any(m in got for m in NEW_INSERT)
    if name == "u19.serve":
        assert got["read_fetch_ms.serve"]["value"] > 0
        assert out["window"]["store_counters"]["read_fetch_bytes"] > 0
