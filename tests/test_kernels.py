"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret mode) + hypothesis property tests."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # bare install: seeded parametrized fallback
    from _proptest import given, settings, st

from repro.core.sparse import from_dense, densify
from repro.core.brute_force import TopK
from repro.kernels import ops, ref
from repro.kernels.mips_topk import mips_topk_pallas
from _precision import assert_topk_close


@pytest.mark.parametrize("b,n,d,k,tile", [
    (8, 512, 64, 10, 128),
    (16, 1024, 128, 16, 256),
    (4, 300, 32, 5, 64),      # non-multiple N -> padding path
    (1, 256, 256, 32, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mips_topk_vs_oracle(b, n, d, k, tile, dtype):
    """The f32 rule of ``tests/_precision.py`` for BOTH dtypes: the
    library oracle upcasts to f32 before the first multiply like the
    kernel's per-tile upcast, so a bf16 corpus needs no wider band —
    only the summation order differs."""
    q = jax.random.normal(jax.random.PRNGKey(0), (b, d), dtype)
    c = jax.random.normal(jax.random.PRNGKey(1), (n, d), dtype)
    got = ops.mips_topk(q, c, k, tile_n=tile)
    want = TopK(*ref.mips_topk_ref(q, c, k))
    assert str(got.scores.dtype) == str(want.scores.dtype) == "float32"
    assert_topk_close(want, got)


@pytest.mark.parametrize("space", ["ip", "l2"])
def test_mips_topk_spaces(space):
    q = jax.random.normal(jax.random.PRNGKey(2), (4, 64))
    c = jax.random.normal(jax.random.PRNGKey(3), (512, 64))
    got = ops.mips_topk(q, c, 8, tile_n=128, space=space)
    want_s, want_i = ref.mips_topk_ref(q, c, 8, space=space)
    np.testing.assert_allclose(np.asarray(got.scores), np.asarray(want_s),
                               rtol=1e-4, atol=1e-4)
    assert np.array_equal(np.asarray(got.indices), np.asarray(want_i))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_mips_topk_permutation_invariance(seed):
    """Top-k scores are invariant to corpus row permutation (ids map)."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(2, 16)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(128, 16)), jnp.float32)
    perm = rng.permutation(128)
    a = ops.mips_topk(q, c, 5, tile_n=64)
    b = ops.mips_topk(q, c[perm], 5, tile_n=64)
    np.testing.assert_allclose(np.asarray(a.scores), np.asarray(b.scores),
                               rtol=1e-5)
    assert np.array_equal(perm[np.asarray(b.indices)], np.asarray(a.indices))


def _fold_case(case: str, b: int):
    """Integer rows and queries (every score exact in f32, whatever the
    summation order) -> (queries, corpus, k, tile_n, n_valid, rounds):
    ``rounds`` is the fold's insertion-round count where the case fixes
    it, else None."""
    n, d, tile, k = 512, 8, 128, 10
    rng = np.random.default_rng(_FOLD_CASES.index(case))
    q = rng.integers(-3, 4, (b, d))
    c = rng.integers(-3, 4, (n, d))
    n_valid, rounds = n, None
    if case in ("ascending", "descending"):
        # score of row i is +-(query index + 1) * i
        c = np.zeros((n, d), np.int64)
        c[:, 0], c[:, 1] = np.arange(n) >> 8, np.arange(n) & 255
        sign = 1 if case == "ascending" else -1
        q = np.zeros((b, d), np.int64)
        q[:, 0] = sign * 256 * np.arange(1, b + 1)
        q[:, 1] = sign * np.arange(1, b + 1)
        # every tile beats the running K-th score / none after the first
        rounds = k * (n // tile) if case == "ascending" else k
    elif case == "all_equal":
        c = np.ones((n, d), np.int64)
        q = np.ones((b, d), np.int64)
        rounds = k
    elif case == "duplicates_across_tiles":
        q = rng.integers(1, 4, (b, d))
        c[tile - 6:tile + 6] = 20               # the best rows, tied
    elif case == "n_valid_below_n":
        q = rng.integers(1, 4, (b, d))
        n_valid = 450
        c[n_valid:] = 20                        # would win if not masked
    elif case == "k_above_tile":
        k = 200
    elif case == "k_equals_tile":
        k = tile
    return (jnp.asarray(q, jnp.float32), jnp.asarray(c, jnp.float32), k,
            tile, n_valid, rounds)


_FOLD_CASES = ("ascending", "descending", "all_equal",
               "duplicates_across_tiles", "n_valid_below_n", "k_above_tile",
               "k_equals_tile")


@pytest.mark.parametrize("b", [1, 16])
@pytest.mark.parametrize("case", _FOLD_CASES)
def test_mips_topk_fold_bitwise(case, b):
    """The threshold-gated fold selects exactly what a stable descending
    sort does (ties to the lower id), bit for bit, and runs the insertion
    rounds the case fixes: K on every tile of an ascending corpus, K on
    the first tile only of a descending or all-equal one."""
    q, c, k, tile, n_valid, rounds = _fold_case(case, b)
    s, i, r = jax.jit(functools.partial(mips_topk_pallas, k=k, tile_n=tile,
                                        n_valid=n_valid))(q, c)
    scores = (np.asarray(q, np.float64) @ np.asarray(c, np.float64).T
              )[:, :n_valid].astype(np.float32)
    want_i = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    want_s = np.take_along_axis(scores, want_i, axis=1)
    assert np.array_equal(np.asarray(i), want_i)
    assert np.asarray(s).tobytes() == want_s.tobytes()
    assert 0 < int(r[0]) <= k * (c.shape[0] // tile)
    if rounds is not None:
        assert int(r[0]) == rounds


@pytest.mark.parametrize("b,n,v,nnz,dd,tile", [
    (6, 384, 100, 8, 32, 128),
    (2, 200, 64, 16, 16, 64),   # padding path
    (8, 512, 200, 4, 64, 256),
])
def test_fused_kernel_vs_oracle(b, n, v, nnz, dd, tile):
    rng = np.random.default_rng(0)
    qd = rng.uniform(size=(b, v)) * (rng.uniform(size=(b, v)) > 0.7)
    cd = rng.uniform(size=(n, v)) * (rng.uniform(size=(n, v)) > 0.85)
    qs, cs = from_dense(jnp.asarray(qd, jnp.float32), nnz), from_dense(
        jnp.asarray(cd, jnp.float32), nnz)
    qv = jax.random.normal(jax.random.PRNGKey(4), (b, dd))
    cv = jax.random.normal(jax.random.PRNGKey(5), (n, dd))
    got = ops.fused_scores(qs, qv, cs, cv, v, 0.6, 0.4, tile_n=tile)
    qdfull = jnp.pad(densify(qs, v), ((0, 0), (0, 1)))
    want = ref.fused_score_ref(qdfull, qv, cs.indices, cs.values, cv, 0.6, 0.4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(st.floats(0.0, 2.0), st.floats(0.0, 2.0))
def test_fused_kernel_weight_linearity(wd, ws):
    """score(wd, ws) == wd*score(1,0) + ws*score(0,1) — the adjustable-
    weight property the paper's scenario-1 export relies on."""
    rng = np.random.default_rng(7)
    b, n, v, nnz, dd = 3, 128, 50, 6, 16
    qd = rng.uniform(size=(b, v)) * (rng.uniform(size=(b, v)) > 0.7)
    cd = rng.uniform(size=(n, v)) * (rng.uniform(size=(n, v)) > 0.8)
    qs, cs = from_dense(jnp.asarray(qd, jnp.float32), nnz), from_dense(
        jnp.asarray(cd, jnp.float32), nnz)
    qv = jax.random.normal(jax.random.PRNGKey(8), (b, dd))
    cv = jax.random.normal(jax.random.PRNGKey(9), (n, dd))
    s_d = ops.fused_scores(qs, qv, cs, cv, v, 1.0, 0.0, tile_n=64)
    s_s = ops.fused_scores(qs, qv, cs, cv, v, 0.0, 1.0, tile_n=64)
    s_m = ops.fused_scores(qs, qv, cs, cv, v, float(wd), float(ws), tile_n=64)
    np.testing.assert_allclose(np.asarray(s_m),
                               wd * np.asarray(s_d) + ws * np.asarray(s_s),
                               rtol=1e-4, atol=1e-5)


def test_kernel_drop_in_for_pipeline():
    """The kernel path and the library path agree inside the system."""
    from repro.core.brute_force import exact_topk
    from repro.core.spaces import DenseSpace

    q = jax.random.normal(jax.random.PRNGKey(10), (4, 32))
    c = jax.random.normal(jax.random.PRNGKey(11), (256, 32))
    lib = exact_topk(DenseSpace("ip"), q, c, 10)
    ker = ops.mips_topk(q, c, 10, tile_n=64)
    assert np.array_equal(np.asarray(lib.indices), np.asarray(ker.indices))
