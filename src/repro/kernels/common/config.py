"""``TileConfig`` — the one value that travels from the tuning registry
through ``repro.core.backend`` down into a ``pallas_call``.

A single frozen (hashable — it is a jit static argument) dataclass covers
all three kernel families; fields a family does not use are simply
ignored by it:

  ==============  ==========================================================
  field           used by
  ==============  ==========================================================
  ``block_n``     quadform (Z rows/tile), rbf_pred (Z rows/tile)
  ``block_m``     rbf_pred (SV rows per double-buffered stream tile)
  ``block_k``     quadform (heads per stacked-Hessian grid block;
                  ``None`` = as many as ``vmem_limit_mb`` allows)
  ``chunk``       maclaurin_attn (sequence positions per grid step)
  ``vmem_limit_mb``  quadform's scoped-VMEM limit: the budget ``block_k``
                  auto-resolution fills, and the limit the kernel is
                  compiled with (16 MiB is the TPU v5e default)
  ==============  ==========================================================

Instances come from ``repro.kernels.common.tuning`` (measured table or
per-kernel default) — construct one directly only in tests/benchmarks.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class TileConfig:
    block_n: int = 512
    block_m: int = 256
    block_k: int | None = None
    chunk: int = 128
    vmem_limit_mb: int = 16

    def __post_init__(self):
        for name in ("block_n", "block_m", "chunk", "vmem_limit_mb"):
            v = getattr(self, name)
            if not (isinstance(v, int) and v > 0):
                raise ValueError(f"TileConfig.{name} must be a positive int, got {v!r}")
        if self.block_k is not None and not (
            isinstance(self.block_k, int) and self.block_k > 0
        ):
            raise ValueError(f"TileConfig.block_k must be None or a positive int")

    def with_(self, **updates) -> "TileConfig":
        """Functional update (``dataclasses.replace`` spelled tersely)."""
        return dataclasses.replace(self, **updates)

    def clamp_block_n(self, n: int) -> "TileConfig":
        """Shrink block_n to the (padded) batch so tiny buckets do not pad
        up to a full default tile."""
        from repro.kernels.common.tiles import SUBLANE, round_up

        target = min(self.block_n, max(SUBLANE, round_up(n, SUBLANE)))
        return self if target == self.block_n else self.with_(block_n=target)

    def quadform_vmem_bytes(
        self, block_k: int, d_pad: int, *, m_itemsize: int = 4,
        per_head_rows: int = 1,
    ) -> int:
        """Scoped VMEM one quadform grid step takes at ``block_k`` heads.

        The pipeline double-buffers every block — the Hessian block, the
        Z tile, the per-head rows (V, plus the column scales for int8),
        the scalar rows and both output tiles — each padded to the
        (8, 128) tiling; on top come the f32 temporaries of one head's
        dot (Z @ M_h and its product with Z), the two (BN, BK) head
        accumulators and, for an int8 Hessian, the head's upcast (d, d)
        slice.
        """
        from repro.kernels.common.tiles import LANE, SUBLANE, round_up

        f32 = 4
        rows, lanes = round_up(block_k, SUBLANE), round_up(block_k, LANE)
        blocks = (
            block_k * d_pad * d_pad * m_itemsize
            + self.block_n * d_pad * f32
            + per_head_rows * rows * d_pad * f32
            + SUBLANE * lanes * f32
            + 2 * self.block_n * lanes * f32
        )
        temps = 2 * self.block_n * (d_pad + lanes) * f32
        if m_itemsize != f32:
            temps += d_pad * d_pad * f32
        return 2 * blocks + temps

    def resolve_block_k(
        self, k: int, d_pad: int, *, m_itemsize: int = 4, per_head_rows: int = 1
    ) -> int:
        """Heads per quadform grid block.

        Explicit ``block_k`` wins (capped at k). Otherwise the most heads
        whose grid step (``quadform_vmem_bytes``) fits ``vmem_limit_mb``,
        floored at one head, then evened out over the same number of
        blocks so the last block pads as few heads as possible. The
        kernel puts the head-block index on a leading axis of every
        per-head array, so any count is legal on the (8, 128) tiling.
        """
        if self.block_k is not None:
            return max(1, min(self.block_k, k))
        budget = self.vmem_limit_mb << 20
        fit = 1
        while fit < k and self.quadform_vmem_bytes(
            fit + 1, d_pad, m_itemsize=m_itemsize, per_head_rows=per_head_rows
        ) <= budget:
            fit += 1
        blocks = -(-k // fit)
        return -(-k // blocks)

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "TileConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})
