"""The feature kinds a configuration can name, each with the property of
the Table 1 data sets it stands for, and a tiny configuration of each
kind through the whole harness on the CPU."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import deploy, harness
from chipbench.tests import tiny

KINDS = ("binary", "pixels", "dense", "standardized")
SEED = 2**33 + 2024                      # a seed past 32 bits


def _d(kind):
    return 123 if kind == "binary" else 64           # binary is a9a's 123 columns


def _draw(kind, n=512, d=None, seed=SEED):
    d = _d(kind) if d is None else d
    return np.asarray(deploy._draw_pool(deploy.key_for(seed, 1), n=n, d=d, kind=kind))


@functools.partial(jax.jit, static_argnames=("n", "d"))
def _pixels_as_first_drawn(key, *, n, d):
    """The ``pixels`` draw as the benchmark made it when it had no other
    kind: mnist-bulk's rows and support vectors must not move."""
    k1, k2 = jax.random.split(key)
    keep = jax.random.uniform(k1, (n, d)) < 0.19
    return jnp.where(keep, jax.random.uniform(k2, (n, d)), 0.0)


@pytest.mark.parametrize("kind", KINDS)
def test_each_kind_has_its_data_sets_property(kind):
    x = _draw(kind)
    assert x.shape == (512, _d(kind)) and x.dtype == np.float32
    if kind == "binary":                                  # a9a
        assert set(np.unique(x)) <= {0.0, 1.0}
        # one 1 in each of the 14 attributes' groups of columns, so the
        # share of ones is 14 / 123 and every row's ||x||^2 is 14
        ends = np.cumsum(deploy.A9A_GROUPS)
        per_group = np.add.reduceat(x, np.r_[0, ends[:-1]], axis=1)
        np.testing.assert_array_equal(per_group, 1.0)
        assert np.mean(x) == pytest.approx(14 / 123)
        # every category of every attribute is drawn somewhere
        assert x.sum(axis=0).min() > 0
    elif kind == "dense":                                 # ijcnn1, sensit
        assert -0.8 <= x.min() < -0.7 and 0.7 < x.max() <= 0.8
    elif kind == "standardized":                          # epsilon
        np.testing.assert_allclose(np.linalg.norm(x.astype(np.float64), axis=1), 1.0,
                                   rtol=0, atol=1e-5)
    else:                                                 # mnist
        key = deploy.key_for(SEED, 1)
        np.testing.assert_array_equal(x, np.asarray(_pixels_as_first_drawn(key, n=512, d=64)))
        assert 0.0 <= x.min() and x.max() <= 1.0
        assert abs(np.mean(x > 0) - 0.19) < 0.01


def test_pixels_model_and_pool_are_the_first_draws():
    cfg = dict(tiny.TINY_CONFIG, n_sv=96, d=40)
    model = deploy.make_model(cfg, SEED)
    kx = jax.random.split(deploy.key_for(SEED, 0), 4)[0]
    np.testing.assert_array_equal(np.asarray(model.X),
                                  np.asarray(_pixels_as_first_drawn(kx, n=96, d=40)))
    np.testing.assert_array_equal(
        deploy.make_pool(cfg, SEED, 200),
        np.asarray(_pixels_as_first_drawn(deploy.key_for(SEED, 1), n=200, d=40)))


def test_the_same_seed_draws_the_same_rows():
    for kind in KINDS:
        np.testing.assert_array_equal(_draw(kind, seed=7), _draw(kind, seed=7))
        assert not np.array_equal(_draw(kind, seed=7), _draw(kind, seed=8))


def test_an_unknown_kind_is_an_error():
    with pytest.raises(ValueError, match="unknown feature kind"):
        _draw("sparse")
    with pytest.raises(ValueError, match="a9a's 123 columns"):
        _draw("binary", d=64)


# ------------------------------------------- a tiny cell of each kind


class _Recorded(harness.Window):
    """The harness's window, kept for the test to read its counters."""

    seen: list = []

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.seen.append(self)


def _execute(root, monkeypatch, *extra, **kw):
    windows, judged = [], []
    monkeypatch.setattr(_Recorded, "seen", windows)
    monkeypatch.setattr(harness, "Window", _Recorded)
    real_judge = harness.judge

    def judge(cell, model, pool, driven, control=False):
        judged.append(driven)
        return real_judge(cell, model, pool, driven, control)

    monkeypatch.setattr(harness, "judge", judge)
    args = harness.parse(["--workload", "tiny-bulk", "--seed", str(SEED),
                          "--seconds", "0.5", *extra])
    res = harness.execute(args, 0.0, root=root, chips_check=tiny.cpu_devices, cache=False, **kw)
    (window,), (driven,) = windows, judged
    return res, window, np.concatenate([a.valid for a in driven.answers])


def _config(kind):
    if kind == "standardized":              # epsilon's gamma / gamma_max
        return dict(tiny.TINY_CONFIG, features=kind, heads=1, gamma_ratio=1.4)
    if kind == "binary":                    # a9a: 123 columns, gamma / gamma_max 1.111
        return dict(tiny.TINY_CONFIG, features=kind, d=123, heads=1, gamma_ratio=1.111)
    return dict(tiny.TINY_CONFIG, features=kind)


@pytest.mark.parametrize("kind", KINDS)
def test_a_tiny_cell_of_each_kind_is_correct(tmp_path, monkeypatch, kind):
    root = tiny.make_root(tmp_path, config=_config(kind))
    res, window, valid = _execute(root, monkeypatch)
    counters = window.delta
    assert res["correct"] is True and res["failed"] == 0
    assert window.compiles == 0          # the exact path too compiled in set-up
    assert res["checks"]["validity_errors"]["value"] == 0
    assert counters["served_rows"] > 0 and len(valid) > 0
    if kind in ("standardized", "binary"):
        # rows of one norm, that of the largest support vector, at
        # gamma/gamma_max above 1 (epsilon's 1.4, a9a's 1.111) lie outside
        # Eq 3.11: the exact path scores every one
        assert not valid.any()
        assert counters["fallback_rows"] == counters["served_rows"]
    else:
        assert valid.all() and counters["fallback_rows"] == 0


def test_a_traced_run_hands_readers_fallback_rows_and_the_trace_path(tmp_path, monkeypatch):
    root = tiny.make_root(tmp_path / "root", config=_config("standardized"))
    bench_path = os.path.join(root, "BENCHMARK.json")
    with open(bench_path) as f:
        bench = json.load(f)
    readers = {
        "fallback_rows.test": "run.counters['fallback_rows']",
        "served_rows.test": "run.counters['served_rows']",
        "trace_found.test": "float(os.path.isfile(run.trace_path))",
    }
    for name, expr in readers.items():
        with open(os.path.join(root, "chipbench", "metrics", name + ".py"), "w") as f:
            f.write(f"import os\n\n\ndef read(run):\n    return {expr}\n")
        bench["per_layer"].append({"name": name, "unit": "rows", "better": "lower",
                                   "source": "program_counter", "layer": "model step",
                                   "moves": "rows_per_s", "workloads": ["tiny-bulk"]})
    with open(bench_path, "w") as f:
        json.dump(bench, f)
    peaks_path = os.path.join(root, "chipbench", "peaks.json")
    with open(peaks_path) as f:
        peaks = json.load(f)
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]    # the test's own table
    with open(peaks_path, "w") as f:
        json.dump(peaks, f)
    tdir = tmp_path / "trace"
    tdir.mkdir()
    res, window, valid = _execute(root, monkeypatch, "--trace", "1", trace_dir=str(tdir))
    counters = window.delta
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert res["correct"] is True and not valid.any()
    assert got["fallback_rows.test"] == got["served_rows.test"] == counters["served_rows"] > 0
    assert got["trace_found.test"] == 1.0
