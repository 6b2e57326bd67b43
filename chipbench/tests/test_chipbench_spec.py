"""The harness finds configurations, mixes, cells and per-layer metrics
by file name, and knows no device it has no peaks for."""

import json
import os

import pytest

from chipbench import harness, spec
from chipbench.tests import tiny


def test_every_benchmark_name_has_its_files():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["family"] == "maclaurin" and cell.config["dtype"] == "float32"
        assert cell.traffic["entry"] == "submit"
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer
    for m in bench["per_layer"]:
        assert callable(spec.load_metric(m["name"]).read)
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(spec.ROOT, c["file"]))


def test_new_files_are_found_without_editing_one(tmp_path):
    root = tiny.make_root(tmp_path)
    before = {p: open(p).read() for p in (os.path.join(root, "BENCHMARK.json"),)}
    cfg = dict(tiny.TINY_CONFIG, d=24)
    with open(spec.path_of("configs", "wider", root), "w") as f:
        json.dump(cfg, f)
    with open(spec.path_of("traffic", "small-requests", root), "w") as f:
        json.dump(dict(tiny.TINY_BULK, rows={"dist": "fixed", "value": 16}), f)
    with open(spec.path_of("metrics", "flushes.bulk", root), "w") as f:
        f.write("def read(run):\n    return run.counters.get('flushes')\n")
    assert spec.load("configs", "wider", root)["d"] == 24
    assert spec.load("traffic", "small-requests", root)["rows"]["value"] == 16
    assert spec.load_metric("flushes.bulk", root).read(
        harness.Run(spec.Cell("c", "x", "y", 1, cfg, {}, {}, [], []), None, {"flushes": 7}, {})) == 7
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["workloads"].append({"name": "wider-small", "config": "wider",
                               "traffic": "small-requests", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "flushes.bulk", "unit": "flushes", "better": "lower",
                               "source": "program_counter", "layer": "runtime scheduler",
                               "moves": "rows_per_s", "workloads": ["wider-small"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cell = spec.load_cell("wider-small", root)
    assert cell.config["d"] == 24 and cell.options == {}
    assert cell.traffic["rows"]["value"] == 16
    assert [m["name"] for m in cell.per_layer] == ["flushes.bulk"]
    assert before                                   # the existing files were only read


def test_unknown_names_are_errors(tmp_path):
    root = tiny.make_root(tmp_path)
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell", root)
    with pytest.raises(spec.SpecError):
        spec.load("configs", "no-such-config", root)
    with pytest.raises(spec.SpecError):
        spec.load_metric("no_such.metric", root)


def test_peaks_know_the_v5e_and_refuse_other_kinds():
    v5e = harness.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["int8_ops"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v9 imaginary")
    with open(os.path.join(spec.BENCH_DIR, "peaks.json")) as f:
        assert "TPU v5e" in json.load(f)["source"]
