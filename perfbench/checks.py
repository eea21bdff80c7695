"""The comparison that decides ``correct``.

A sample of the answers served in the window, drawn from the seed, is
held against the exact answers of the plain reference:

* ``unanswered``: requests due in the window that never resolved, or
  resolved with an error (every request, not only the sample);
* ``bad_ids``: sampled answers with an id outside the corpus, an id
  twice, a wrong length or a score that is not finite;
* ``score_err_ulp``: the largest gap between a served score and the
  exact score of the row it names;
* ``rank_gap_ulp``: the largest amount by which the row served at rank
  ``r`` scores below the exact ``r``-th best row (a wrong or missed row
  shows here, a near-tie swapped by rounding barely does).

Score gaps are in f32 ULPs at the row's scale, the largest exact score
magnitude among its best ``k``.  The limits sit in the configuration
file, each between the largest reading of sound runs and the smallest
reading of the control (see ``PERF.md``).
"""

from __future__ import annotations

import numpy as np


def ulp32(scale: np.ndarray) -> np.ndarray:
    scale = np.maximum(np.abs(scale), np.finfo(np.float32).tiny)
    return 2.0 ** (np.floor(np.log2(scale)) - 23)


def compare(served_scores, served_ids, exact_top, exact_served,
            n_rows: int) -> dict:
    """``served_*`` [S, k] as served (the caller marks a row of another
    length bad before it gets here); ``exact_top`` [S, k] the exact best
    scores, descending; ``exact_served`` [S, k] the exact score of each
    served id (any value where the id is out of range)."""
    s = np.asarray(served_scores, np.float64)
    ids = np.asarray(served_ids, np.int64)
    k = exact_top.shape[1]
    bad = ((ids < 0) | (ids >= n_rows)).any(axis=1)
    bad |= ~np.isfinite(s).all(axis=1)
    bad |= np.array([len(set(row)) != k for row in ids.tolist()])
    ok = ~bad
    unit = ulp32(np.abs(exact_top).max(axis=1, keepdims=True))
    err = np.abs(s - exact_served) / unit
    gap = np.maximum(exact_top - exact_served, 0.0) / unit
    return {
        "bad_ids": int(bad.sum()),
        "score_err_ulp": float(err[ok].max()) if ok.any() else float("inf"),
        "rank_gap_ulp": float(gap[ok].max()) if ok.any() else float("inf"),
    }


def verdict(numbers: dict, limits: dict):
    """(correct, [(name, value, limit)]): every number at or under its
    limit."""
    rows = [(name, numbers[name], limits[name]) for name in limits]
    return all(v <= lim for _, v, lim in rows), rows
