"""The exact path's readers: the fallback spans and ``rbf_pred``'s
roofline share.

``epsilon_fallback_spans.xplane.pb`` is a trace recorded on the CPU by
``chipbench/testdata/record_fallback_spans.py``: three 64-row requests of
an epsilon-shaped deployment at a test size, every row outside Eq 3.11,
each result's fallback on the one client thread, inside a
``chipbench.window`` span. The expected values below were worked out by
hand from its events (listed with each). The trace has no device plane,
so ``rbf_pred_roofline.bulk`` reads nothing there; its value is worked
out by hand on a constructed trace summary instead.
"""

import os

import pytest

from chipbench import fallback, flush, harness, spec, trace, work

FALLBACK_TRACE = os.path.join(spec.BENCH_DIR, "testdata", "epsilon_fallback_spans.xplane.pb")
MNIST_TRACES = [os.path.join(spec.BENCH_DIR, "testdata", name)
                for name in ("mnist_two_flushes.xplane.pb", "mnist_flush_spans.xplane.pb")]
EPSILON = {"family": "maclaurin", "heads": 1, "n_sv": 36988, "d": 2000}
READERS = ("rbf_pred_roofline.bulk", "fallback_ms.bulk", "fallback_prep_ms.bulk")


def _run(summary, counters, config=EPSILON, trace_path=None):
    cell = spec.Cell("epsilon-bulk", "epsilon-bin", "bulk-closed", 1, config, {}, {}, [], [])
    return harness.Run(cell, summary, counters, harness.load_peaks("TPU v5 lite"),
                       trace_path=trace_path)


def _read(name, run):
    return spec.load_metric(name).read(run)


# ------------------------------------------------- the spans, by hand

# The trace's events (ns), all on one thread line. Window: chipbench.window
# 184,590 - 6,765,905. Three svm_engine.fallback spans, all inside:
#   fallback   1,024,217    823,722  1,045,914   (sum 2,893,853)
#   gather        24,318     21,103     25,673   (sum 71,094)
#   put          266,536    269,358    235,015   (sum 770,909)
#   step          97,568     85,278     52,548
#   sync         585,753    419,529    684,174
# each stage inside its fallback and in this order.
FALLBACK_VALUES = {
    # 2,893,853 / 3
    "fallback_ms.bulk": 0.9646176666666667,
    # (71,094 + 770,909) / 3
    "fallback_prep_ms.bulk": 0.28066766666666667,
    # no device plane: no rbf_pred call in the trace
    "rbf_pred_roofline.bulk": None,
}


@pytest.fixture(scope="module")
def recorded():
    return trace.summarize(FALLBACK_TRACE, [0])


@pytest.mark.parametrize("name", READERS)
def test_readers_read_the_values_worked_out_by_hand(recorded, name):
    run = _run(recorded, {"fallback_rows": 192, "served_rows": 192},
               config=dict(EPSILON, n_sv=1024, d=64), trace_path=FALLBACK_TRACE)
    got = _read(name, run)
    if FALLBACK_VALUES[name] is None:
        assert got is None
    else:
        assert got == pytest.approx(FALLBACK_VALUES[name], rel=1e-12)


def test_the_stages_lie_in_order_inside_each_fallback(recorded):
    f = fallback.load(FALLBACK_TRACE, recorded)
    assert (f.count, f.staged) == (3, 3)
    assert f.ms("step") == pytest.approx((97568 + 85278 + 52548) / 3 / 1e6, rel=1e-12)
    assert f.ms("sync") == pytest.approx((585753 + 419529 + 684174) / 3 / 1e6, rel=1e-12)
    assert 0.95 < sum(f.stage_s.values()) / f.mean_s < 1.0
    _, lines, _ = flush._events(FALLBACK_TRACE, recorded.devices)
    names = [name for events in lines.values() for _, _, name in events
             if name.startswith(fallback.FALLBACK)]
    one = [fallback.FALLBACK, *fallback.STAGES.values()]
    assert [n.split("/")[0] for n in names] == [n.rstrip("/") for n in one] * 3


@pytest.mark.parametrize("path", MNIST_TRACES, ids=os.path.basename)
@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_from_a_trace_without_fallbacks(path, name):
    run = _run(trace.summarize(path, [0]), {"fallback_rows": 0, "served_rows": 16384},
               trace_path=path)
    assert _read(name, run) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_a_trace(name):
    assert _read(name, _run(None, {"fallback_rows": 0}, trace_path=FALLBACK_TRACE)) is None


@pytest.mark.parametrize("name", ("fallback_ms.bulk", "fallback_prep_ms.bulk"))
def test_span_readers_raise_when_a_traced_run_has_no_trace_path(recorded, name):
    with pytest.raises(RuntimeError, match="without its trace path"):
        _read(name, _run(recorded, {"fallback_rows": 192}))


def test_only_whole_fallbacks_inside_the_window_count(monkeypatch):
    """A fallback that crosses the window's end is left out, and a
    fallback with no stage spans (a program that records only the whole
    span) counts in the mean but gives no stage time."""
    lines = {
        "client-0": [(10, 100, fallback.FALLBACK),
                     (12, 20, "svm_engine.fallback.gather/b32"),
                     (20, 25, "svm_engine.fallback.put/b32"),
                     (25, 30, "svm_engine.fallback.step/b32"),
                     (30, 99, "svm_engine.fallback.sync"),
                     (120, 130, "svm_engine.fallback.gather/b32")],      # outside any
        "client-1": [(200, 300, fallback.FALLBACK),
                     (900, 1100, fallback.FALLBACK),                     # crosses the end
                     (910, 920, "svm_engine.fallback.gather/b32")],
    }
    monkeypatch.setattr(flush, "_events", lambda path, devices: ((0, 1000), lines, []))
    summary = trace.Summary(1e-6, {0: 0.0}, [], [], [0])
    f = fallback.load("unused", summary)
    assert (f.count, f.staged) == (2, 1)
    assert f.mean_s == pytest.approx((90 + 100) / 2 * 1e-9)
    assert f.stage_s == pytest.approx({"gather": 4e-9, "put": 2.5e-9, "step": 2.5e-9,
                                       "sync": 34.5e-9})
    run = _run(summary, {"fallback_rows": 64}, trace_path="unused")
    assert _read("fallback_ms.bulk", run) == pytest.approx(95e-6)
    assert _read("fallback_prep_ms.bulk", run) == pytest.approx(6.5e-6)
    lines["client-0"] = lines["client-0"][:1]
    run = _run(summary, {"fallback_rows": 64}, trace_path="unused")
    assert _read("fallback_ms.bulk", run) == pytest.approx(95e-6)
    assert _read("fallback_prep_ms.bulk", run) is None


# ----------------------------------------------- rbf_pred, by hand

RBF_TEXT = ('%rbf_pred.1 = f32[8192,8]{1,0} custom-call(f32[1]{0} %p0, f32[37120,2048]{1,0} '
            '%pad.8), custom_call_target="tpu_custom_call"')


def _summary(ops):
    return trace.Summary(window_s=10.0, busy_s={0: 1.0}, ops=ops, idle_gaps=[], devices=[0])


def test_rbf_pred_roofline_by_hand():
    """Two exact calls of 8,192 rows at epsilon's shapes, 41.3 ms each on
    the device. Per row 2 x 36,988 x 2,000 + 2 x 2,000 + 2 x 36,988 =
    148,029,976 flop, so 2,425,323,126,784 flop over 197e12 flop/s =
    12.3113 ms; bytes 2 x 296,051,952 + 16,384 x 2,001 x 4 = 723,241,440
    over 819e9 B/s = 0.8831 ms: compute-bound. 12.3113 / 82.6 = 14.905%.
    The pad of X and the quadform call do not count."""
    ops = [trace.Op(0, "jit_exact_scores", RBF_TEXT, 0.0, 41.3e6),
           trace.Op(0, "jit_exact_scores", RBF_TEXT.replace("rbf_pred.1", "rbf_pred"),
                    50e6, 41.3e6),
           trace.Op(0, "jit_exact_scores", "%pad.8 = f32[37120,2048]{1,0} pad(f32[36988,2000]"
                    "{1,0} %p1, f32[] %c), padding=0_132x0_48", 100e6, 0.7e6),
           trace.Op(0, "jit__step", '%quadform.1 = f32[8192,8]{1,0} custom-call(), '
                    'custom_call_target="tpu_custom_call"', 110e6, 0.4e6)]
    run = _run(_summary(ops), {"fallback_rows": 16384, "served_rows": 16384})
    flops = work.exact_flops(16384, 1, 36988, 2000)
    assert flops == 2_425_323_126_784
    assert work.exact_bytes(16384, 2, 1, 36988, 2000) == 723_241_440
    expected = 100.0 * (2_425_323_126_784 / 197e12) / (2 * 41.3e-3)
    assert _read("rbf_pred_roofline.bulk", run) == pytest.approx(expected, rel=1e-9)
    assert _read("rbf_pred_roofline.bulk", run) == pytest.approx(14.905, abs=1e-3)
    assert any("2 calls" in n and "compute-bound" in n for n in run.notes)


@pytest.mark.parametrize("ops,rows", [
    ([], 16384),                                                   # no exact call
    ([trace.Op(0, "jit_exact_scores", RBF_TEXT, 0.0, 41.3e6)], 0),  # no row re-scored
], ids=["no-call", "no-rows"])
def test_rbf_pred_roofline_reads_nothing_where_no_exact_call_ran(ops, rows):
    assert _read("rbf_pred_roofline.bulk", _run(_summary(ops), {"fallback_rows": rows})) is None
