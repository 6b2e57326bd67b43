"""The ``epsilon-bin`` configuration at a test size on the CPU: served
through ``Runtime.submit`` and compared with the plain reference
(``chipbench/reference.py``) under the configuration's own limits, and a
tiny epsilon-shaped cell through the whole harness."""

import json
import os

import numpy as np
import pytest

from chipbench import check, deploy, harness, reference, spec
from chipbench.tests import tiny

SEED = 2**32 + 1616                      # a seed past 32 bits
CONFIG = spec.load("configs", "epsilon-bin")
ROWS = 64


def _small(**kw):
    """The configuration at a test size: d 64 and 1,024 support vectors."""
    return dict(CONFIG, d=64, n_sv=1024, **kw)


def _serve(cfg, n_requests=4):
    """Scores, labels and verdicts of ``n_requests`` requests of 64 rows
    sent through ``Runtime.submit``, with the model and the rows sent."""
    from repro.serve import Runtime

    model = deploy.make_model(cfg, SEED)
    pool = deploy.make_pool(cfg, SEED, n_requests * ROWS)
    with Runtime(engine_opts={"min_bucket": 32, "max_batch": ROWS}) as runtime:
        deploy.publish(runtime, cfg, model, {"warm_buckets": [ROWS]}, "eps")
        futures = [runtime.submit("eps", pool[i * ROWS:(i + 1) * ROWS])
                   for i in range(n_requests)]
        results = [f.result(timeout=60) for f in futures]
        scores = np.concatenate([np.asarray(r.values, np.float32).reshape(ROWS, -1)
                                 for r in results])
        labels = np.concatenate([np.asarray(r.labels) for r in results])
        valid = np.concatenate([np.asarray(r.valid) for r in results])
    return model, pool, scores, labels, valid


@pytest.mark.parametrize("ratio,heads", [(1.4, 1), (1.4, 3), (0.1, 1)],
                         ids=["epsilon-ratio-K1", "epsilon-ratio-K3", "inside-envelope"])
def test_served_scores_meet_the_configurations_limits(ratio, heads):
    """At the paper's gamma / gamma_max (1.4) every row lies outside
    Eq 3.11 and is scored by the exact expansion; at 0.1 none is, and the
    Maclaurin form scores them all."""
    cfg = _small(heads=heads, gamma_ratio=ratio)
    model, pool, scores, labels, valid = _serve(cfg)
    ref = reference.exact_scores(model.X, model.alpha, model.b, model.gamma, pool)
    ref_valid = reference.envelope_valid(model.X, model.gamma, pool)
    numbers = check.compare(scores, labels, valid, ref, ref_valid, multiclass=heads > 1)
    numbers["unanswered"] = 0
    assert check.verdict(numbers, CONFIG["limits"]), numbers
    assert valid.all() if ratio < 1 else not valid.any()


class _Counted(harness.Window):
    """The harness's window, with the engines' exact calls counted. Goes
    once ``harness.Window.counters`` counts ``fallback_calls`` itself."""

    seen: list = []

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.seen.append(self)

    def counters(self) -> dict:
        out = super().counters()
        out["fallback_calls"] = sum(e.stats.fallback_batches for e in self.engines)
        return out


def test_a_tiny_epsilon_cell_runs_through_the_harness(tmp_path, monkeypatch):
    cfg = dict(_small(), n_sv=256)
    root = tiny.make_root(tmp_path / "root", config=cfg)
    bench_path = os.path.join(root, "BENCHMARK.json")
    with open(bench_path) as f:
        bench = json.load(f)
    real = spec.load_benchmark()
    bench["per_layer"] += [dict(m, workloads=["tiny-bulk"]) for m in real["per_layer"]
                           if "epsilon-bulk" in m.get("workloads", [])]
    with open(bench_path, "w") as f:
        json.dump(bench, f)
    peaks_path = os.path.join(root, "chipbench", "peaks.json")
    with open(peaks_path) as f:
        peaks = json.load(f)
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]    # the test's own table
    with open(peaks_path, "w") as f:
        json.dump(peaks, f)
    windows = []
    monkeypatch.setattr(_Counted, "seen", windows)
    monkeypatch.setattr(harness, "Window", _Counted)
    args = harness.parse(["--workload", "tiny-bulk", "--seed", str(SEED), "--seconds", "0.5",
                          "--trace", "1"])
    tdir = tmp_path / "trace"
    tdir.mkdir()
    res = harness.execute(args, 0.0, root=root, chips_check=tiny.cpu_devices, cache=False,
                          trace_dir=str(tdir))
    (window,) = windows
    counters = window.delta
    assert res["correct"] is True and res["failed"] == 0
    assert window.compiles == 0
    assert counters["fallback_rows"] == counters["served_rows"] > 0
    assert 0 < counters["fallback_calls"] <= counters["flushes"]
    got = res["metrics"]
    # CPU numbers: plumbing only; the CPU trace has no device plane
    assert "rbf_pred_roofline.bulk" not in got
    assert 0 < got["fallback_prep_ms.bulk"]["value"] < got["fallback_ms.bulk"]["value"]
