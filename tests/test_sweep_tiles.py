"""``tools/sweep_tiles.py``, the tile sweep behind the checked-in tuning
table, run at a tiny shape into a temporary table."""

import importlib.util
import os

import pytest

from repro.kernels.common import TileConfig, tuning

TOOL = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools",
    "sweep_tiles.py",
)


@pytest.fixture
def sweep_tiles():
    spec = importlib.util.spec_from_file_location("sweep_tiles", TOOL)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    yield module
    tuning.clear_overrides()  # the sweep records its picks in-process too


def test_the_sweep_measures_the_default_and_records_no_slower_pick(
    sweep_tiles, tmp_path
):
    with open(tuning.TABLE_PATH, "rb") as f:
        checked_in = f.read()
    out = str(tmp_path / "table.json")
    recorded = sweep_tiles.sweep(
        d=8, n_sv=16, buckets=(8, 32), batch=8, num_features=16, out=out
    )

    assert recorded == [
        ("quadform", "d8_k1_n8"),
        ("quadform", "d8_k1_n32"),
        ("rbf_pred", "d8_m16_n8"),
        ("fwht", "d8_f16_n8"),
    ]
    table = tuning.load_table(out)["entries"][tuning.platform()]
    for kernel, key in recorded:
        entry = table[kernel][key]
        # default_ms is the default config's own measurement
        assert entry["measured_ms"] <= entry["default_ms"]
        assert entry["source"] == "tools/sweep_tiles.py"
        assert tuning.lookup(kernel, key) == TileConfig.from_json(entry["config"])
    with open(tuning.TABLE_PATH, "rb") as f:
        assert f.read() == checked_in
