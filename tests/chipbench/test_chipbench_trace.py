"""The trace reduction on a small trace recorded on one TPU v5e under
``--trace 1`` of ``u19.insert``: the window cut to its first 16,384-op
apply (8 executions of the edge-update step), keeping the device plane's
events and the benchmark's host spans. The reduction's numbers on it are
pinned, so a change that moves the yardstick shows here."""
import pytest

import harness
import kernel_bytes
import tracereduce
from conftest import BENCH
from ustream import store_kwargs

FIXTURE = BENCH / "fixtures" / "u19_insert_one_apply.xplane.pb.gz"
STEP = "jit_step_update_edges"


@pytest.fixture(scope="module")
def summary():
    return tracereduce.load(FIXTURE)


def test_pinned_numbers(summary):
    k = r"^%compact_rows_pallas\.\d+ = "
    assert summary.window_s == pytest.approx(0.74802545, rel=1e-6)
    assert summary.busy_s == pytest.approx(0.722981486, rel=1e-6)
    assert summary.module_seconds(STEP) == pytest.approx(0.723159199,
                                                         rel=1e-6)
    assert summary.op_seconds(k, module=STEP) == pytest.approx(
        0.000260588, rel=1e-6)


def test_window_busy_and_programs(summary):
    assert summary.n_devices == 1
    assert 0 < summary.busy_s < summary.window_s
    assert summary.module_count(STEP) == 8
    per_batch = summary.module_seconds(STEP) / 8
    assert 0.05 < per_batch < 0.2
    # the step's ops account for its device time, not more
    assert summary.busy_s >= summary.module_seconds(STEP) * 0.99


def test_kernel_time_sits_inside_the_step(summary):
    k = r"^%compact_rows_pallas\.\d+ = "
    calls = [o for o in summary.devices[0]["ops"]
             if o[2].startswith("%compact_rows_pallas.")]
    assert len(calls) == 16                             # two tiers a batch
    assert 0 < summary.op_seconds(k, module=STEP) < \
        summary.module_seconds(STEP)
    assert summary.op_seconds(k, module="jit_step_neighbors") == 0


def test_breakdown_is_bounded_and_labelled(summary):
    ops = summary.top_ops(10)
    assert 1 <= len(ops) <= 10
    assert all(name.startswith(STEP + "/%") for name, _ in ops)
    assert ops == sorted(ops, key=lambda x: -x[1])
    gaps = summary.idle_gaps(10)
    assert gaps and len(gaps) <= 10
    idle = summary.window_s - summary.busy_s
    assert abs(sum(s for _, s in gaps) - idle) < 1e-6
    assert any(name.startswith("bench.apply x") for name, _ in gaps)


def test_insert_readers_on_the_recorded_trace(summary):
    cell = harness.resolve_cell("u19.insert")
    win = harness.Window(trace=summary, peaks=kernel_bytes.peaks(
        "TPU v5 lite"), store_kwargs=store_kwargs(cell.config),
        counters={"host_stage_ms": 40.0, "super_batches": 1},
        memory_peak_bytes=1)
    got = {m["name"]: r.read(win) for m, r in cell.per_layer}
    assert 50 < got["step_device_ms.insert"] < 200
    assert 0 < got["compact_rows_roofline.insert"] < 100
    assert 0 < got["device_idle_share.insert"] < 100
    assert got["host_stage_ms.insert"] == 40.0


def test_an_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        kernel_bytes.peaks("TPU v9 imaginary")
