"""The precision contract: how two execution paths that compute the same
scores are compared.

**The rule.**  *Same ids under a planted margin, scores within a stated
bound* — not bit for bit.  A compiled MXU kernel, XLA's dot and the
Pallas interpreter each choose their own summation order, and a
compiler upgrade may change it, so bitwise equality across them is not
a property anyone can keep.  What every path does keep:

  * **f32 tier.**  Scores rank-to-rank within :data:`F32_MAX_ULP` f32
    ULPs at the row's score scale (max |oracle score| of the row), ids
    equal at every rank whose oracle score is separated from its
    neighbours in the list by more than twice that bound (inside a
    near-tie group the ids may permute; the group's id set is equal) —
    :func:`topk_close_violations`.  Where a check needs exact id
    equality at every rank, its data plants the margin
    (``benchmarks/common.py``).  Every f32 contraction runs at
    ``Precision.HIGHEST`` (``core.spaces.HIGHEST``), so on a TPU the
    oracle is an f32 answer and not one bf16 pass.

  * **bf16 tier.**  A corpus resident in bf16 is compared with the f32
    oracle on the ORIGINAL corpus: recall@k == 1.0 and score error
    within :data:`BF16_MAX_ULP` bf16 ULPs at the row's score scale
    (:func:`bf16_contract_violations`); paths within the bf16 tier are
    compared with each other under the f32 rule.

Bitwise equality remains only where one compiled program is compared
with itself: a cached result vs its producer, a served row vs the
offline batched run of the same pipeline.

The f32 bound: an f32 dot of D terms summed in another order moves by a
few ULPs of the running sum for random data; 64 ULPs (a relative
``2^-17`` of the row scale) leaves headroom for D = 768 and for a TPU's
multi-pass f32 matmul, and stays a thousand times below a bf16-sized
error.

The bf16 bound: bf16 round-to-nearest moves an element by at most half
a ULP, and the bf16 ULP is up to ``2^-7`` relative (7 explicit mantissa
bits), so each element moves by at most ``2^-8`` relative and a D-term
f32 dot over rounded operands by at most ``2^-8 * sum|q_i c_i|``.  For
unit-scale data that lands well inside a couple of bf16 ULPs at the
score scale; 4 leaves deterministic headroom without ever excusing an
f32-sized error.

The checks return lists of violations (empty = the contract holds), so
tests assert on them and the serving example and ``chip_smoke.py`` raise
with them.
"""

from __future__ import annotations

from typing import List

import numpy as np

__all__ = ["F32_MAX_ULP", "BF16_MAX_ULP", "f32_ulp_at", "bf16_ulp_at",
           "topk_close_violations", "bf16_contract_violations"]

# Score-error budget for the f32 tier, in f32 ULPs at each row's score
# scale.
F32_MAX_ULP = 64.0

# Score-error budget for the bf16 tier, in bf16 ULPs at each row's score
# scale (max |oracle score| of the row).
BF16_MAX_ULP = 4.0


def _ulp_at(scale, mantissa_bits: int) -> np.ndarray:
    scale = np.maximum(np.abs(np.asarray(scale, np.float64)),
                       np.finfo(np.float32).tiny)
    return 2.0 ** (np.floor(np.log2(scale)) - mantissa_bits)


def f32_ulp_at(scale) -> np.ndarray:
    """One f32 ULP at magnitude ``scale`` (elementwise; 23 explicit
    mantissa bits)."""
    return _ulp_at(scale, 23)


def bf16_ulp_at(scale) -> np.ndarray:
    """One bf16 ULP at magnitude ``scale`` (elementwise; 7 explicit
    mantissa bits)."""
    return _ulp_at(scale, 7)


def _rows(topk):
    return (np.atleast_2d(np.asarray(topk.scores, np.float64)),
            np.atleast_2d(np.asarray(topk.indices)))


def topk_close_violations(want, got, *,
                          max_ulp: float = F32_MAX_ULP) -> List[str]:
    """The f32-tier rule.  ``want``/``got`` carry ``scores`` and
    ``indices`` [B, K] (or one served row, [K]); ``want`` is the
    oracle."""
    ws, wi = _rows(want)
    gs, gi = _rows(got)
    if ws.shape != gs.shape or wi.shape != gi.shape:
        return [f"shape {gs.shape} != oracle {ws.shape}"]
    finite = np.isfinite(ws)
    if not np.array_equal(finite, np.isfinite(gs)):
        return ["-inf tails do not align"]
    out = []
    scale = np.max(np.where(finite, np.abs(ws), 0.0), axis=1, keepdims=True)
    tol = max_ulp * f32_ulp_at(scale)                         # [B, 1]
    err = np.abs(np.where(finite, gs, 0.0) - np.where(finite, ws, 0.0))
    if (err > tol).any():
        r, c = np.unravel_index(np.argmax(err / tol), err.shape)
        out.append(f"score error {err[r, c] / f32_ulp_at(scale[r, 0]):.1f} "
                   f"f32 ULP at row {r} rank {c} exceeds {max_ulp}")
    for r in range(ws.shape[0]):
        # near-tie groups: maximal runs of ranks whose oracle scores lie
        # within 2*tol of their neighbour (ties on -inf tails included)
        gap = np.abs(np.diff(np.where(finite[r], ws[r], -1e300)))
        cut = np.concatenate([[True], gap > 2 * tol[r, 0], [True]])
        starts = np.flatnonzero(cut[:-1])
        ends = np.flatnonzero(cut[1:]) + 1
        for a, b in zip(starts, ends):
            if sorted(wi[r, a:b]) != sorted(gi[r, a:b]):
                out.append(f"ids differ at row {r} ranks {a}..{b - 1}: "
                           f"{gi[r, a:b].tolist()} != {wi[r, a:b].tolist()}")
                break
    return out


def bf16_contract_violations(oracle, got, *,
                             max_ulp: float = BF16_MAX_ULP) -> List[str]:
    """The cross-tier rule: a bf16-tier result vs the f32 oracle on the
    original corpus has recall@k == 1.0 and per-rank score error within
    ``max_ulp`` bf16 ULPs at the oracle's row scale.  Scores compare
    rank to rank: with the id sets equal, the j-th largest scores differ
    by at most the largest single-document perturbation, even when
    near-ties swap ranks inside the set."""
    from repro.core.fusion import topk_recall

    os_, oi = _rows(oracle)
    gs, gi = _rows(got)
    out = []
    rec = float(topk_recall(oi, gi))
    if rec != 1.0:
        out.append(f"recall@k vs f32 oracle = {rec} != 1.0")
    finite = np.isfinite(os_)
    if not np.array_equal(finite, np.isfinite(gs)):
        return out + ["-inf tails do not align"]
    scale = np.max(np.where(finite, np.abs(os_), 0.0), axis=1, keepdims=True)
    err = (np.abs(np.where(finite, gs, 0.0) - np.where(finite, os_, 0.0))
           / bf16_ulp_at(scale))
    worst = float(err.max()) if err.size else 0.0
    if worst > max_ulp:
        out.append(f"bf16 score error {worst:.2f} ULP exceeds bound "
                   f"{max_ulp}")
    return out
