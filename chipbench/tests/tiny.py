"""A tiny benchmark tree for the CPU tests: the real harness, readers and
peak table, with configurations and mixes small enough for a test run."""

from __future__ import annotations

import json
import os
import shutil

from chipbench import spec

TINY_CONFIG = {
    "source": "tiny stand-in for the CPU tests", "dataset": "tiny",
    "d": 16, "heads": 3, "n_sv": 64, "features": "pixels",
    "paper_gamma": 1e-4, "paper_gamma_max": 1e-3, "gamma_ratio": 0.1,
    "family": "maclaurin", "dtype": "float32", "reduced": [],
    "limits": {"mean_err_rel": 0.002, "max_err_rel": 0.004, "label_errors": 0,
               "validity_errors": 0, "unanswered": 0},
}
TINY_BULK = {"entry": "submit", "arrivals": "closed", "rows": {"dist": "fixed", "value": 64},
             "in_flight_per_chip": 2, "pool_rows": 256, "drain_s": 30}
ENGINE = {"min_bucket": 8, "max_batch": 64}


def _write(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def make_root(tmp_path, config: dict | None = None) -> str:
    """A checkout-shaped directory with the cell ``tiny-bulk``; the
    harness's own files are the real ones."""
    root = str(tmp_path)
    real = spec.BENCH_DIR
    bulk = ["tiny-bulk"]

    def metric(name, unit, cells, **kw):
        return {"name": name, "unit": unit, "better": "lower", "source": "host_clock",
                "workloads": cells, **kw}

    bench = {
        "command": ["python3", "chipbench/run.py"], "paths": ["chipbench"], "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "test", "reduced": [], "why": "test",
                     "file": "chipbench/configs/tiny.json"}],
        "workloads": [
            {"name": "tiny-bulk", "config": "tiny", "traffic": "tiny-bulk", "chips": 1, "why": "t"},
        ],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
             "source": "host_clock"},
            metric("rows_per_s", "rows/s", bulk, bound=0.05),
        ],
        "per_layer": [
            metric("device_idle.bulk", "%", bulk, layer="device", moves="rows_per_s"),
            metric("step_mfu.bulk", "%", bulk, layer="model step", moves="rows_per_s"),
        ],
    }
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    _write(spec.path_of("configs", "tiny", root), config or TINY_CONFIG)
    _write(spec.path_of("traffic", "tiny-bulk", root), TINY_BULK)
    _write(spec.path_of("cells", "tiny-bulk", root),
           {"warm_buckets": [64], "runtime": {"engine_opts": ENGINE}})
    shutil.copytree(os.path.join(real, "metrics"), os.path.join(root, "chipbench", "metrics"))
    shutil.copy(os.path.join(real, "peaks.json"), os.path.join(root, "chipbench", "peaks.json"))
    return root


def cpu_devices(chips: int):
    import jax

    return jax.devices()[:chips]
