"""Finding a cell's pieces by name.

Nothing here lists configurations, mixes or metrics: each is a file
named after the name ``BENCHMARK.json`` gives it, so a later change adds
a cell by adding files and entries, never by editing one.

    chipbench/configs/<config>.json   one deployment (shapes, seed rules,
                                      the limits its comparison uses)
    chipbench/traffic/<mix>.json      one traffic mix, read by drive.py
    chipbench/metrics/<metric>.py     one per-layer metric: read(run)
    chipbench/cells/<cell>.json       optional: the cell's own settings
                                      (warmed buckets, Runtime settings)
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
KINDS = {"configs": ".json", "traffic": ".json", "metrics": ".py", "cells": ".json"}


class SpecError(ValueError):
    """A name that ``BENCHMARK.json`` or the files under chipbench lack."""


def path_of(kind: str, name: str, root: str = ROOT) -> str:
    if kind not in KINDS:
        raise SpecError(f"unknown kind {kind!r}")
    return os.path.join(root, "chipbench", kind, name + KINDS[kind])


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise SpecError(f"no BENCHMARK.json under {root}")
    return _json(path)


def load(kind: str, name: str, root: str = ROOT) -> dict:
    path = path_of(kind, name, root)
    if not os.path.exists(path):
        raise SpecError(f"no {kind} file for {name!r} (looked for {path})")
    return _json(path)


def load_metric(name: str, root: str = ROOT):
    """The module of one per-layer metric; it defines ``read(run)``."""
    path = path_of("metrics", name, root)
    if not os.path.exists(path):
        raise SpecError(f"no reader for per-layer metric {name!r} ({path})")
    mod_spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    if not callable(getattr(module, "read", None)):
        raise SpecError(f"{path} defines no read(run)")
    return module


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    options: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise SpecError(f"BENCHMARK.json has no workload {name!r}")
    w = entries[0]
    options_path = path_of("cells", name, root)
    options = _json(options_path) if os.path.exists(options_path) else {}
    return Cell(
        name=name,
        config_name=w["config"],
        traffic_name=w["traffic"],
        chips=int(w["chips"]),
        config=load("configs", w["config"], root),
        traffic=load("traffic", w["traffic"], root),
        options=options,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )
