"""Per-(kernel, platform, shape-bucket) tile tuning registry.

The registry answers one question on the serving hot path: *which
``TileConfig`` should this kernel use for this shape on this hardware?*
Resolution order:

  1. in-process overrides (``record(...)`` — what the autotuner and tests
     write);
  2. the checked-in measured table ``tuning_table.json`` next to this
     module (written back by ``tools/sweep_tiles.py``, keyed by platform
     so CPU numbers never leak onto TPU);
  3. the per-kernel default (the pre-tuning fixed block sizes).

Keys are canonical strings from ``shape_key(d=.., k=.., n=..)`` —
dimension names sorted, so every caller produces the same key for the
same bucket. Lookup never fails: an unknown kernel/key quietly falls back
to ``DEFAULTS``; ``lookup(..., strict=True)`` raises instead (tests).

To add a measured entry by hand, append under
``entries.<platform>.<kernel>.<key>`` in the JSON (see
``tools/sweep_tiles.py`` for the canonical writer) — or call
``record(...)`` + ``save_table()``.
"""

from __future__ import annotations

import json
import os
import re
import threading
import warnings

import jax

from repro.kernels.common.config import TileConfig

TABLE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tuning_table.json")

DEFAULTS: dict[str, TileConfig] = {
    "quadform": TileConfig(block_n=512),
    "rbf_pred": TileConfig(block_n=256, block_m=256),
    "rff_score": TileConfig(block_n=256),
    "maclaurin_attn": TileConfig(chunk=128),
    # int8-weight variants are separate tuning families: the quantized
    # operand streams at a quarter of the f32 HBM bandwidth, so the
    # optimal tilings diverge from the f32 kernels' on real hardware.
    "quadform_q8": TileConfig(block_n=512),
    "rff_score_q8": TileConfig(block_n=256),
    # Structured (Fastfood) scoring: VPU butterfly stages dominate, so the
    # Z-tile block is the only knob; the readout GEMM is thin. Separate
    # family for the int8-operator variant (same rationale as above).
    "fwht": TileConfig(block_n=256),
    "fwht_q8": TileConfig(block_n=256),
}

# Canonical shape_key grammar: underscore-joined <dims><int> groups, e.g.
# "d64_k10_n1024" (whatever shape_key() can emit).
_KEY_RE = re.compile(r"^[a-z]+\d+(?:_[a-z]+\d+)*$")

_lock = threading.Lock()
_overrides: dict[tuple[str, str, str], dict] = {}
_table_cache: dict | None = None


def platform() -> str:
    """Hardware key the registry partitions on (cpu / tpu / gpu)."""
    return jax.default_backend()


def shape_key(**dims) -> str:
    """Canonical bucket key: ``shape_key(d=64, k=10, n=1024) -> 'd64_k10_n1024'``.

    Dimension names are sorted so call-site order never matters. Batch-like
    dimensions should be passed through ``bucket()`` first so every caller
    lands on the keys the tile sweep records.
    """
    return "_".join(f"{name}{int(dims[name])}" for name in sorted(dims))


def bucket(n: int, lo: int = 32, hi: int = 8192) -> int:
    """Canonical batch bucket: next power of two, floored at lo, capped at hi.

    THE bucketing policy — the serving engine's shape buckets, the sweep's
    recorded keys and the dispatch-level lookups all share it, so a batch
    of 1000 resolves the entry measured for the 1024 bucket instead of
    missing the table on a raw-n key.
    """
    if n <= lo:
        return lo
    return min(hi, 1 << (int(n) - 1).bit_length())


def _read_table(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return {"version": 1, "entries": {}}


def validate_table(table: dict, *, origin: str = "tuning table") -> dict:
    """Drop malformed entries, warning once per problem instead of letting a
    corrupt checked-in table surface later as a KeyError / TypeError deep in
    a trace. Checks, per ``entries.<platform>.<kernel>.<key>``:

      * the kernel is a known family (has a ``DEFAULTS`` entry);
      * the key matches the ``shape_key`` grammar;
      * the entry carries a ``config`` dict that ``TileConfig`` accepts.

    Returns a NEW table containing only the surviving entries (input is
    not mutated); table-level shape problems reset to an empty table.
    """
    if not isinstance(table, dict) or not isinstance(table.get("entries", {}), dict):
        warnings.warn(f"{origin}: top-level structure malformed; ignoring table")
        return {"version": 1, "entries": {}}
    clean: dict = {"version": table.get("version", 1), "entries": {}}
    for plat, kernels in table.get("entries", {}).items():
        if not isinstance(kernels, dict):
            warnings.warn(f"{origin}: platform {plat!r} entries malformed; dropped")
            continue
        for kernel, keys in kernels.items():
            if kernel not in DEFAULTS:
                warnings.warn(
                    f"{origin}: unknown kernel {kernel!r} under {plat!r} "
                    f"(known: {sorted(DEFAULTS)}); dropped"
                )
                continue
            if not isinstance(keys, dict):
                warnings.warn(f"{origin}: {plat}/{kernel} entries malformed; dropped")
                continue
            for key, entry in keys.items():
                if not _KEY_RE.match(key):
                    warnings.warn(
                        f"{origin}: malformed shape_key {key!r} under "
                        f"{plat}/{kernel}; dropped"
                    )
                    continue
                cfg = entry.get("config") if isinstance(entry, dict) else None
                if not isinstance(cfg, dict):
                    warnings.warn(
                        f"{origin}: entry {plat}/{kernel}/{key} has no "
                        f"config dict; dropped"
                    )
                    continue
                try:
                    TileConfig.from_json(cfg)
                except (TypeError, ValueError) as e:
                    warnings.warn(
                        f"{origin}: bad config for {plat}/{kernel}/{key} "
                        f"({e}); dropped"
                    )
                    continue
                clean["entries"].setdefault(plat, {}).setdefault(kernel, {})[key] = entry
    return clean


def load_table(path: str = TABLE_PATH) -> dict:
    """Read + validate a tuning table file (malformed entries are dropped
    with a warning; a missing/unreadable file is an empty table)."""
    return validate_table(_read_table(path), origin=path)


def _load_table() -> dict:
    """The checked-in default table, read once per process (lookup tier 2)."""
    global _table_cache
    if _table_cache is None:
        _table_cache = load_table(TABLE_PATH)
    return _table_cache


def lookup(
    kernel: str,
    key: str | None = None,
    *,
    platform_name: str | None = None,
    strict: bool = False,
) -> TileConfig:
    """Resolve the ``TileConfig`` for one (kernel, platform, bucket).

    ``key=None`` skips the measured tiers and returns the kernel default
    (what a caller with no shape information gets).
    """
    plat = platform_name or platform()
    if key is not None:
        with _lock:
            hit = _overrides.get((plat, kernel, key))
        if hit is not None:
            return TileConfig.from_json(hit)
        entry = _load_table().get("entries", {}).get(plat, {}).get(kernel, {}).get(key)
        if entry is not None:
            return TileConfig.from_json(entry["config"])
    if strict:
        raise KeyError(f"no measured tuning for ({plat}, {kernel}, {key})")
    if kernel not in DEFAULTS:
        raise KeyError(f"unknown kernel family {kernel!r}; known: {sorted(DEFAULTS)}")
    return DEFAULTS[kernel]


def record(
    kernel: str,
    key: str,
    config: TileConfig,
    *,
    platform_name: str | None = None,
    measured_ms: float | None = None,
    default_ms: float | None = None,
    source: str | None = None,
) -> None:
    """Write one measured entry into the in-process override tier."""
    entry = {**config.to_json()}
    meta = {
        k: v
        for k, v in (
            ("measured_ms", measured_ms),
            ("default_ms", default_ms),
            ("source", source),
        )
        if v is not None
    }
    with _lock:
        _overrides[(platform_name or platform(), kernel, key)] = entry
        _overrides_meta[(platform_name or platform(), kernel, key)] = meta


_overrides_meta: dict[tuple[str, str, str], dict] = {}


def clear_overrides() -> None:
    """Drop every in-process override (test isolation)."""
    with _lock:
        _overrides.clear()
        _overrides_meta.clear()


def save_table(path: str = TABLE_PATH) -> str:
    """Merge the in-process overrides into the table at ``path`` and write it.

    The tile sweep calls this after recording its winners, producing
    the checked-in ``tuning_table.json`` the next process reads back. The
    TARGET file is re-read and merged (never the in-process cache, which
    may belong to a different path); the cached default table is refreshed
    only when writing to the default location.
    """
    global _table_cache
    table = _read_table(path)
    entries = table.setdefault("entries", {})
    with _lock:
        for (plat, kernel, key), cfg in _overrides.items():
            slot = entries.setdefault(plat, {}).setdefault(kernel, {})
            slot[key] = {"config": cfg, **_overrides_meta.get((plat, kernel, key), {})}
    table["version"] = 1
    with open(path, "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
        f.write("\n")
    if path == TABLE_PATH:
        _table_cache = table
    return path


def reload_table() -> None:
    """Forget the cached table so the next lookup re-reads the file."""
    global _table_cache
    _table_cache = None
