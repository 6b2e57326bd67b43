"""The flush-span readers, and the accepted readers pinned.

``mnist_flush_spans.xplane.pb`` is a trace recorded on a TPU v5e by
``chipbench/testdata/record_flush_spans.py``: three 8,192-row flushes of
the mnist-ovr10 engine step inside a ``chipbench.window`` span, with the
program's stage spans on. The expected values below were worked out by
hand from its events (listed with each). ``mnist_two_flushes.xplane.pb``
predates the spans: every reader of them reads nothing there, and the
accepted readers and the breakdown read exactly what they always did.
"""

import json
import os

import pytest

from chipbench import flush, harness, spec, trace
from chipbench.tests import tiny

OLD = os.path.join(spec.BENCH_DIR, "testdata", "mnist_two_flushes.xplane.pb")
CONFIG = {"family": "maclaurin", "heads": 10, "d": 780}
NEW_READERS = ("flush_host_ms.bulk", "flush_prep_ms.bulk", "h2d_ms.bulk",
               "result_sync_ms.bulk", "idle_under_flush.bulk")


def _run(summary, rows, trace_path=None):
    cell = spec.Cell("mnist-bulk", "mnist-ovr10", "bulk-closed", 1, CONFIG, {}, {}, [], [])
    counters = {"served_rows": rows, "rows": rows, "flushes": rows // 8192}
    return harness.Run(cell, summary, counters, harness.load_peaks("TPU v5 lite"),
                       trace_path=trace_path)


def _read(name, run):
    return spec.load_metric(name).read(run)


# -------------------------------------------- the accepted readers, pinned

OLD_VALUES = {
    "device_idle.bulk": 96.57234668904171,
    "quadform_roofline.bulk": 69.75833758902588,
    "step_mfu.bulk": 2.158847848005415,
}
OLD_DEVICE_OPS = [
    ["jit__step/_step.1 tpu_custom_call", 0.0014544170000000002],
    ["jit__step/copy.1 copy", 7.370900000000001e-05],
    ["jit__step/bitcast_reduce_fusion fusion", 4.2262e-05],
    ["jit__step/compare_reduce_fusion fusion", 2.1975e-05],
    ["jit__step/pad.0 pad", 1.2431e-05],
    ["jit__step/iota_reduce_fusion fusion", 1.9070000000000001e-06],
    ["jit__step/copy-start.1 copy-start", 1.3e-08],
    ["jit__step/copy-start.2 copy-start", 1.3e-08],
    ["jit__step/copy-done.1 copy-done", 6.000000000000001e-09],
    ["jit__step/copy-done.2 copy-done", 6.000000000000001e-09],
]
OLD_IDLE_GAPS = [["np.asarray(jax.Array)", 0.04526928799999999]]


@pytest.fixture(scope="module")
def old():
    return trace.summarize(OLD, [0])


@pytest.mark.parametrize("name", sorted(OLD_VALUES))
def test_accepted_readers_read_their_pinned_values(old, name):
    assert spec.load_metric(name).read(_run(old, 2 * 8192)) == OLD_VALUES[name]


def test_breakdown_reads_its_pinned_entries(old):
    assert old.window_s == 0.046876036
    assert old.busy_s == {0: 0.001606748}
    assert len(old.ops) == 24
    assert old.device_ops() == OLD_DEVICE_OPS
    assert old.idle_gaps == OLD_IDLE_GAPS


@pytest.mark.parametrize("name", NEW_READERS)
def test_flush_readers_read_nothing_from_a_trace_without_spans(old, name):
    assert _read(name, _run(old, 2 * 8192, OLD)) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_flush_readers_read_nothing_without_a_trace(name):
    run = harness.Run(spec.Cell("c", "x", "y", 1, CONFIG, {}, {}, [], []), None,
                      {"served_rows": 0, "rows": 0, "flushes": 0}, {}, trace_path=OLD)
    assert _read(name, run) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_flush_readers_raise_when_a_traced_run_has_no_trace_dir(old, name):
    """A traced ``Run`` without the path of its trace raises: the metrics
    must not vanish unseen."""
    with pytest.raises(RuntimeError, match="without its trace path"):
        _read(name, _run(old, 2 * 8192))


# ------------------------------------------------ the spans, by hand

NEW = os.path.join(spec.BENCH_DIR, "testdata", "mnist_flush_spans.xplane.pb")

# The trace's events (ns). Window: chipbench.window 44,518,029 - 226,799,507
# (182,281,478). Three runtime.flush spans on one thread line, all inside:
#   flush      68,935,849   64,188,609   36,141,220   (sum 169,265,678)
#   assemble    2,565,190    2,514,540    2,974,040
#   pad        64,710,679   60,387,829   31,606,590
#   put           666,920      541,900      598,140
#   put starts  113,184,198  177,701,487  213,628,647
# The three steps start on the device (XLA Modules) at 122,761,127,
# 187,280,590 and 221,911,632, one after each step enqueue.
# svm_engine.sync on two client lines, all inside: 14,955,640,
# 10,984,630, 13,823,270. Device ops: three steps of 802,813, 802,876 and
# 802,869 ns busy (disjoint ops, 2,408,558 in all); the first two lie
# inside flushes 2 and 3, the third after the last flush.
NEW_VALUES = {
    # 169,265,678 / 3
    "flush_host_ms.bulk": 56.421892666666665,
    # (8,053,770 + 156,705,098) / 3
    "flush_prep_ms.bulk": 54.91962266666666,
    # (9,576,929 + 9,579,103 + 8,282,985) / 3: put start to device start
    "h2d_ms.bulk": 9.146339,
    # 39,763,540 / 3
    "result_sync_ms.bulk": 13.254513333333334,
    # (169,265,678 - 802,813 - 802,876) / (182,281,478 - 2,408,558)
    "idle_under_flush.bulk": 93.21024476613823,
}


@pytest.fixture(scope="module")
def new():
    return trace.summarize(NEW, [0])


@pytest.mark.parametrize("name", NEW_READERS)
def test_flush_readers_read_the_values_worked_out_by_hand(new, name):
    run = _run(new, 3 * 8192, NEW)
    assert _read(name, run) == pytest.approx(NEW_VALUES[name], rel=1e-12)


# Every reader's value on both committed traces, as the readers read them
# while they found the trace by its caller's ``trace_dir`` (recorded
# before the harness handed them ``Run.trace_path``).
PINNED = {
    (OLD, "device_idle.bulk"): 96.57234668904171,
    (OLD, "quadform_roofline.bulk"): 69.75833758902588,
    (OLD, "step_mfu.bulk"): 2.158847848005415,
    (OLD, "flush_host_ms.bulk"): None,
    (OLD, "flush_prep_ms.bulk"): None,
    (OLD, "h2d_ms.bulk"): None,
    (OLD, "result_sync_ms.bulk"): None,
    (OLD, "idle_under_flush.bulk"): None,
    (NEW, "device_idle.bulk"): 98.67866004465907,
    (NEW, "quadform_roofline.bulk"): 69.77213770156501,
    (NEW, "step_mfu.bulk"): 0.8327634043127329,
    (NEW, "flush_host_ms.bulk"): 56.421892666666665,
    (NEW, "flush_prep_ms.bulk"): 54.91962266666666,
    (NEW, "h2d_ms.bulk"): 9.146339000000001,
    (NEW, "result_sync_ms.bulk"): 13.254513333333334,
    (NEW, "idle_under_flush.bulk"): 93.21024476613823,
}


@pytest.mark.parametrize("path,name", sorted(PINNED), ids=lambda v: os.path.basename(v))
def test_every_reader_reads_its_pinned_value_from_the_trace_path(old, new, path, name):
    summary, rows = (old, 2 * 8192) if path == OLD else (new, 3 * 8192)
    assert _read(name, _run(summary, rows, path)) == PINNED[(path, name)]


def test_flush_stages_cover_the_flush(new):
    f = flush.load(NEW, new)
    assert (f.count, f.syncs, f.h2d_count) == (3, 3, 3)
    assert f.ms("put") == pytest.approx(1806960 / 3 / 1e6, rel=1e-12)
    assert 0.99 < sum(f.stage_s.values()) / f.mean_s < 1.0
    assert 100.0 * new.idle_share() == pytest.approx(100.0 * (1 - 2408558 / 182281478))


# ------------------------------------------- a traced run of the harness


def test_a_traced_harness_run_reports_the_flush_metrics(tmp_path):
    """The whole harness on the CPU at a tiny size with ``--trace 1``: the
    readers find the run's own trace and every flush metric is there.
    (CPU numbers: plumbing only, never a device reading.)"""
    root = tiny.make_root(tmp_path / "root")
    bench_path = os.path.join(root, "BENCHMARK.json")
    with open(bench_path) as f:
        bench = json.load(f)
    bench["per_layer"] += [
        {"name": n, "unit": "ms", "better": "lower", "source": "program_span",
         "layer": "runtime scheduler", "moves": "rows_per_s", "workloads": ["tiny-bulk"]}
        for n in NEW_READERS
    ]
    with open(bench_path, "w") as f:
        json.dump(bench, f)
    peaks_path = os.path.join(root, "chipbench", "peaks.json")
    with open(peaks_path) as f:
        peaks = json.load(f)
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]    # the test's own table
    with open(peaks_path, "w") as f:
        json.dump(peaks, f)
    args = harness.parse(["--workload", "tiny-bulk", "--seed", str(2**31 + 77),
                          "--seconds", "0.5", "--trace", "1"])
    tdir = tmp_path / "trace"
    tdir.mkdir()
    res = harness.execute(args, 0.0, root=root, chips_check=tiny.cpu_devices,
                          cache=False, trace_dir=str(tdir))
    assert res["correct"] is True
    got = res["metrics"]
    assert set(NEW_READERS) - {"h2d_ms.bulk"} <= set(got), got
    host = got["flush_host_ms.bulk"]["value"]
    assert 0 < got["flush_prep_ms.bulk"]["value"] < host
    # the CPU trace has no device plane: a step has no start on a device
    assert "h2d_ms.bulk" not in got
    assert got["result_sync_ms.bulk"]["value"] > 0
    assert 0 < got["idle_under_flush.bulk"]["value"] <= 100


def test_stage_intervals_intersect_and_measure():
    a = [(0, 10), (20, 30)]
    b = [(5, 25), (28, 40)]
    assert flush._intersect(a, b) == [(5, 10), (20, 25), (28, 30)]
    assert flush._measure(flush._intersect(a, b)) == 12


def test_step_enqueues_pair_with_device_programs_in_order():
    lines = {"flush": [(10, 12, "svm_engine.step/maclaurin/b8192"),
                       (30, 31, "svm_engine.step_exact/b8192"),
                       (50, 52, "svm_engine.step/maclaurin/b8192")],
             "other": [(11, 40, "svm_engine.sync")]}
    # a program before the first enqueue is none of theirs; the last
    # enqueue's program never started in the trace
    assert flush._device_starts(lines, [5, 20, 25, 33]) == {10: 20, 30: 33}
