"""The trace reduction and the work counts, on a small trace recorded on
a TPU v5e: two 8,192-row flushes of the mnist-ovr10 engine step."""

import os

import pytest

from chipbench import harness, spec, trace, work

TRACE = os.path.join(spec.BENCH_DIR, "testdata", "mnist_two_flushes.xplane.pb")
ROWS = 2 * 8192
CONFIG = {"family": "maclaurin", "heads": 10, "d": 780}


@pytest.fixture(scope="module")
def summary():
    return trace.summarize(TRACE, [0])


def _run(summary):
    cell = spec.Cell("mnist-bulk", "mnist-ovr10", "bulk-closed", 1, CONFIG, {}, {}, [], [])
    counters = {"served_rows": ROWS, "rows": ROWS, "flushes": 2}
    return harness.Run(cell, summary, counters, harness.load_peaks("TPU v5 lite"))


def test_busy_is_a_union_inside_the_window(summary):
    assert 0 < summary.busy_s[0] <= summary.window_s
    assert 0.0 <= summary.idle_share() < 1.0
    total = sum(o.dur_ns for o in summary.ops) * 1e-9
    assert summary.busy_s[0] <= total + 1e-12


def test_kernel_is_found_by_what_the_trace_shows(summary):
    calls, seconds = summary.select("jit__step", "tpu_custom_call")
    assert calls == 2
    assert 1e-4 < seconds / calls < 5e-3
    labels = [name for name, _ in summary.device_ops()]
    assert labels[0].startswith("jit__step/") and labels[0].endswith("tpu_custom_call")


def test_shares_stay_at_or_under_100_percent(summary):
    run = _run(summary)
    for name in ("quadform_roofline.bulk", "step_mfu.bulk", "device_idle.bulk"):
        value = spec.load_metric(name).read(run)
        assert value is not None and 0.0 < value <= 100.0, (name, value)
    assert any("compute-bound" in n for n in run.notes)


def test_readers_return_nothing_without_a_trace():
    run = harness.Run(spec.Cell("c", "x", "y", 1, CONFIG, {}, {}, [], []), None,
                      {"served_rows": 0, "rows": 0, "flushes": 0}, {})
    for name in ("quadform_roofline.bulk", "step_mfu.bulk", "device_idle.bulk"):
        assert spec.load_metric(name).read(run) is None


def test_breakdown_is_bounded(summary):
    assert len(summary.device_ops()) <= trace.TOP
    assert len(summary.idle_gaps) <= trace.TOP
    assert all(seconds > 0 for _, seconds in summary.idle_gaps)


def test_work_counts_follow_shapes_not_tiles():
    assert work.quadform_flops(8192, 10, 780) == pytest.approx(8192 * 10 * (2 * 780**2 + 4 * 780))
    one = work.quadform_bytes(8192, 1, 10, 780)
    assert one == 10 * (780 * 780 + 780) * 4 + 8192 * (780 + 20) * 4
    t, bound = work.roofline_seconds(work.quadform_flops(8192, 10, 780), one, 197e12, 819e9)
    assert bound == "compute" and t == pytest.approx(8192 * 10 * (2 * 780**2 + 4 * 780) / 197e12)
    assert work.roofline_seconds(1.0, 1e9, 197e12, 819e9)[1] == "memory"


@pytest.mark.parametrize("rows,calls,heads,n_sv,d,flops,nbytes", [
    # epsilon: per row 2*36,988*2,000 + 2*2,000 + 2*36,988 = 148,029,976;
    # one call reads (73,976,000 + 36,988) floats, each row 2,001
    (8192, 1, 1, 36988, 2000, 1_212_661_563_392, 296_051_952 + 65_568_768),
    # mnist-ovr10: per row 3,391,440 + 1,560 + 43,480 = 3,436,480; two
    # calls of (1,695,720 + 21,740) floats, each row 790
    (8192, 2, 10, 2174, 780, 28_151_644_160, 13_739_680 + 25_886_720),
])
def test_exact_work_counts_by_hand(rows, calls, heads, n_sv, d, flops, nbytes):
    assert work.exact_flops(rows, heads, n_sv, d) == flops
    assert work.exact_bytes(rows, calls, heads, n_sv, d) == nbytes
