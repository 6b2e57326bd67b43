"""How ``correct`` is decided: every served answer against the plain
reference, each number beside its limit.

Numbers compared (limits in the configuration file, readings they were
set from in PERF.md):

  mean_err_rel     mean |served - exact| over every served score, over
                   the mean |exact|
  max_err_rel      max |served - exact| over the max |exact|
  label_errors     labels that are not the argmax (binary: the sign) of
                   the row's served scores, plus labels that differ from
                   the exact argmax by more than a near tie (the exact
                   gap between the two classes within twice the row's
                   own served error); an exact comparison, limit 0
  validity_errors  rows whose served Eq 3.11 verdict differs from the
                   reference's; an exact comparison, limit 0
  unanswered       requests with no answer by the drain deadline, or
                   answered with an error or with too few rows; limit 0
"""

from __future__ import annotations

import numpy as np

ORDER = ("mean_err_rel", "max_err_rel", "label_errors", "validity_errors", "unanswered")


def _labels_of(scores: np.ndarray, multiclass: bool) -> np.ndarray:
    if multiclass:
        return np.argmax(scores, axis=1)
    return np.where(scores[:, 0] >= 0, 1, -1)


def compare(scores, labels, valid, ref, ref_valid, *, multiclass: bool) -> dict:
    """The numbers for one set of served rows (scores (n, K))."""
    scores = np.asarray(scores, np.float64)
    ref = np.asarray(ref, np.float64)
    n = scores.shape[0]
    if n == 0:
        return {"mean_err_rel": 0.0, "max_err_rel": 0.0, "label_errors": 0,
                "validity_errors": 0}
    err = np.abs(scores - ref)
    row_err = np.max(err, axis=1)
    own = np.asarray(labels) != _labels_of(scores, multiclass)
    exact = _labels_of(ref, multiclass)
    if multiclass:
        rows = np.arange(n)
        gap = ref[rows, exact] - ref[rows, np.asarray(labels)]
    else:
        gap = np.abs(ref[:, 0])
    beyond_tie = (np.asarray(labels) != exact) & (gap > 2.0 * row_err)
    return {
        "mean_err_rel": float(np.mean(err) / max(np.mean(np.abs(ref)), 1e-30)),
        "max_err_rel": float(np.max(err) / max(np.max(np.abs(ref)), 1e-30)),
        "label_errors": int(np.sum(own | beyond_tie)),
        "validity_errors": int(np.sum(np.asarray(valid, bool) != np.asarray(ref_valid, bool))),
    }


def verdict(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limits[k] for k in ORDER)


def as_checks(numbers: dict, limits: dict) -> dict:
    return {k: {"value": numbers[k], "limit": limits[k]} for k in ORDER}


def lines(numbers: dict, limits: dict, prefix: str = "check") -> list:
    return [f"{prefix} {k} {numbers[k]!r} limit {limits[k]!r}" for k in ORDER]
