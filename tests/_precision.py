"""The precision contract, as shared test helpers.

The rule — *same ids under a planted margin, scores within a stated
bound*, with its f32 and bf16 bounds and why they are what they are — is
stated once, in ``src/repro/core/precision.py``, which the serving
example and ``chip_smoke.py`` check against too.  This module turns its
violation lists into assertions and builds the margin-planted data.

Bitwise equality (:func:`assert_topk_bitwise`) is kept only where one
compiled program is compared with itself: a cached result vs its
producer, a served row vs the offline batched run of the same pipeline,
a property that holds exactly by construction (integer-valued data).

Recall@k == 1.0 needs the oracle's top-k to be separated from rank k+1
by more than the bf16 perturbation; :func:`planted_margin_corpus` builds
corpora where that margin is guaranteed by construction, so the recall
assertion is a real invariant rather than a seed lottery.
"""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

from repro.core.precision import (BF16_MAX_ULP, F32_MAX_ULP,  # noqa: F401
                                  bf16_contract_violations,
                                  topk_close_violations)

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))  # for the
# canonical margin-planted constructions in benchmarks/common.py


def assert_topk_bitwise(want, got, ctx=""):
    """One program against itself: scores AND indices are bit-identical."""
    np.testing.assert_array_equal(np.asarray(want.scores),
                                  np.asarray(got.scores), err_msg=str(ctx))
    np.testing.assert_array_equal(np.asarray(want.indices),
                                  np.asarray(got.indices), err_msg=str(ctx))


def assert_topk_close(want, got, *, max_ulp: float = F32_MAX_ULP, ctx=""):
    """The f32 rule: scores within ``max_ulp`` f32 ULPs at the row
    scale, ids equal outside near-tie groups (``want`` is the oracle)."""
    bad = topk_close_violations(want, got, max_ulp=max_ulp)
    assert not bad, f"{'; '.join(bad[:3])} {ctx}"


def assert_scores_close(want, got, *, max_ulp: float = F32_MAX_ULP, ctx=""):
    """The f32 rule on full score matrices [B, N]: every score within
    ``max_ulp`` f32 ULPs at its row's scale."""
    from repro.core.precision import f32_ulp_at

    ws = np.asarray(want, np.float64)
    gs = np.asarray(got, np.float64)
    assert ws.shape == gs.shape, f"shape {gs.shape} != {ws.shape} {ctx}"
    scale = np.max(np.abs(ws), axis=-1, keepdims=True)
    err = np.abs(gs - ws) / f32_ulp_at(scale)
    worst = float(err.max()) if err.size else 0.0
    assert worst <= max_ulp, \
        f"score error {worst:.1f} f32 ULP exceeds {max_ulp} {ctx}"


def recall_at_k(oracle_indices, got_indices) -> float:
    """Mean fraction of the oracle's top-k ids present in ``got`` (order
    within the list is allowed to differ — bf16 may legitimately swap
    near-ties *inside* the result set).  Delegates to the ONE canonical
    implementation (``repro.core.fusion.topk_recall``) that the benches
    and the serving example also use, so every gate enforces the same
    metric."""
    from repro.core.fusion import topk_recall

    return topk_recall(oracle_indices, got_indices)


def assert_bf16_oracle_contract(oracle, got, *, max_ulp: float = BF16_MAX_ULP,
                                ctx=""):
    """The cross-tier rule: recall@k == 1.0 against the f32 oracle on
    the ORIGINAL corpus, per-rank score error within ``max_ulp`` bf16
    ULPs at the oracle's row scale."""
    bad = bf16_contract_violations(oracle, got, max_ulp=max_ulp)
    assert not bad, f"{'; '.join(bad)} {ctx}"


def planted_margin_corpus(n: int, d: int, b: int, k: int, *, seed: int = 0):
    """(queries, corpus, planted_ids) where the true top-k is separated
    from the background by a *guaranteed* score margin, for both ip and
    l2 — so recall@k == 1.0 vs the f32 oracle is an invariant of the
    construction, not a seed lottery.  Delegates to the ONE canonical
    construction (``benchmarks/common.py: planted_margin_dense`` — the
    geometry, its margin proof, and the numpy-generator stability note
    live there), which the benches' margin-guarded recall gates use
    too, so the contract the tests reason about and the data the gates
    run on can never drift apart."""
    from benchmarks.common import planted_margin_dense

    return planted_margin_dense(n, d, b, k, seed=seed)


def require_margin(oracle_scores, *, min_gap: float):
    """Test-validity guard for randomly generated (sparse/fused) data.
    Pass f32-oracle scores for k+1 ranks; asserts every query's
    rank-k → rank-k+1 gap exceeds ``min_gap``.  If a data tweak ever
    erodes the margin below the bf16 perturbation scale, this fails
    loudly instead of letting the recall assertion turn into a coin
    flip."""
    s = np.asarray(oracle_scores, np.float64)
    assert s.shape[1] >= 2
    gap = s[:, -2] - s[:, -1]
    assert float(gap.min()) > min_gap, \
        f"test data margin {gap.min():.4f} below {min_gap} — regenerate"
