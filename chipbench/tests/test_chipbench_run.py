"""A whole run on the CPU at a tiny size, past the harness's look for a
chip: the result line's schema, the comparison, the control, and the
timed path broken underneath. The command itself refuses a CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import check, harness, spec
from chipbench.tests import tiny
from repro.serve import svm_engine

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def _run(root, cell, *extra, **kw):
    args = harness.parse(["--workload", cell, "--seed", str(2**31 + 12345),
                          "--seconds", "0.5", *extra])
    return harness.execute(args, 0.0, root=root, chips_check=tiny.cpu_devices, cache=False, **kw)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("heads", [3, 1])
def test_result_line_schema(tmp_path, heads):
    root = tiny.make_root(tmp_path, config=dict(tiny.TINY_CONFIG, heads=heads))
    res = _run(root, "tiny-bulk")
    assert list(res) == KEYS
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"setup_s", "rows_per_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert set(res["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert list(res["checks"]) == list(check.ORDER)
    json.dumps(res, allow_nan=False)


def test_control_comes_out_not_correct(tmp_path):
    cfg = dict(tiny.TINY_CONFIG, n_sv=256)
    base = tiny.make_root(tmp_path, config=cfg)
    cell = spec.load_cell("tiny-bulk", base)
    from chipbench import deploy, drive

    model = deploy.make_model(cell.config, 7)
    pool = deploy.make_pool(cell.config, 7, 256)
    from chipbench import reference

    exact = reference.exact_scores(model.X, model.alpha, model.b, model.gamma, pool)
    answers = [drive.Answer(np.arange(256), exact, np.argmax(exact, axis=1),
                            reference.envelope_valid(model.X, model.gamma, pool))]
    driven = drive.Driven(1, 0, 0, 1.0, 256, answers)
    numbers, ctl, n = harness.judge(cell, model, pool, driven, control=True)
    assert n == 256 and check.verdict(numbers, cell.config["limits"])
    assert not check.verdict(ctl, cell.config["limits"])


def _break_the_engine(monkeypatch, fault):
    """Break the timed path where the engine produces its answers."""
    real = svm_engine.SVMEngine._finalize

    def finalize(self, Z, chunks):
        values, valid, labels = real(self, Z, chunks)
        values, labels = np.array(values), np.array(labels)
        if fault == "score":
            values[0] = values[0] + 0.05 * (1.0 + np.abs(values[0]))
        elif fault == "label":
            labels[0] = (labels[0] + 1) % self.num_heads
        elif fault == "half":               # half the rows left unscored
            half = len(values) // 2
            values[half:], labels[half:] = values[:len(values) - half], labels[:len(values) - half]
        elif fault == "raise":
            raise RuntimeError("the step failed")
        return values, valid, labels

    monkeypatch.setattr(svm_engine.SVMEngine, "_finalize", finalize)


@pytest.mark.parametrize("fault,caught_by", [
    ("score", {"mean_err_rel", "max_err_rel"}),
    ("label", {"label_errors"}),
    ("half", {"mean_err_rel", "max_err_rel"}),
    ("raise", {"unanswered"}),
])
def test_a_broken_path_is_not_correct(root, monkeypatch, fault, caught_by):
    _break_the_engine(monkeypatch, fault)
    res = _run(root, "tiny-bulk")
    assert res["correct"] is False
    failing = {k for k, v in res["checks"].items() if v["value"] > v["limit"]}
    assert failing & caught_by


def test_the_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"), "--workload", "mnist-bulk",
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    out = subprocess.run(cmd, cwd=spec.ROOT, env=env, capture_output=True, timeout=240)
    assert out.returncode != 0
    assert out.stdout.strip() == b""
    assert b"no TPU" in out.stderr
