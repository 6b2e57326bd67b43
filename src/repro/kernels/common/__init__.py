"""Shared tiled-kernel infrastructure for ``repro.kernels.*``.

One layer, used by the kernel families (quadform, rbf_pred, rff_score,
fwht, maclaurin_attn):

  * ``tiles``    — lane/block padding arithmetic (the ``-(-n//b)*b`` that
    used to be hand-rolled per kernel);
  * ``config``   — the frozen, hashable ``TileConfig`` every pallas_call
    receives (jit-static);
  * ``tuning``   — measured-or-default ``TileConfig`` resolution per
    (kernel, platform, shape bucket), backed by the checked-in
    ``tuning_table.json``;
  * ``autotune`` — the sweep harness that produces those measurements
    (driven by ``tools/sweep_tiles.py``);
  * ``cost``     — the analytic cost prior that ranks tile and family
    candidates before anything is measured.

Typical kernel-side use::

    from repro.kernels.common import TileConfig, tiles, tuning

    def my_kernel_wrapper(x, *, config: TileConfig | None = None, interpret=False):
        config = config or tuning.lookup("my_kernel")
        n_pad = tiles.round_up(x.shape[0], config.block_n)
        ...
"""

from repro.kernels.common.config import TileConfig
from repro.kernels.common import autotune, cost, tiles, tuning

__all__ = ["TileConfig", "autotune", "cost", "tiles", "tuning"]
