"""Dense linalg tests (≙ tests/matrix_test.c)."""

import jax.numpy as jnp
import numpy as np
import pytest

from splatt_tpu.ops.linalg import (form_normal_lhs, gram, normalize_columns,
                                   solve_normals)


def _rand(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).random(shape))


def test_gram():
    U = _rand((20, 6))
    np.testing.assert_allclose(np.asarray(gram(U)),
                               np.asarray(U).T @ np.asarray(U), atol=1e-12)


def test_form_normal_lhs():
    grams = [gram(_rand((10, 4), seed=s)) for s in range(3)]
    lhs = form_normal_lhs(grams, mode=1, regularization=0.5)
    want = np.asarray(grams[0]) * np.asarray(grams[2]) + 0.5 * np.eye(4)
    np.testing.assert_allclose(np.asarray(lhs), want, atol=1e-12)


def test_solve_normals_spd():
    rng = np.random.default_rng(3)
    A = rng.random((5, 5))
    lhs = jnp.asarray(A @ A.T + 5 * np.eye(5))  # SPD
    rhs = _rand((12, 5), seed=4)
    X = solve_normals(lhs, rhs)
    # X · lhs = rhs
    np.testing.assert_allclose(np.asarray(X @ lhs), np.asarray(rhs), atol=1e-8)


def test_solve_normals_singular_fallback():
    """Rank-deficient lhs exercises the pseudoinverse path
    (≙ the gelss fallback, src/matrix.c:554-603)."""
    v = np.array([1.0, 2.0, 3.0])
    lhs = jnp.asarray(np.outer(v, v))  # rank 1, not SPD
    rhs = _rand((4, 3), seed=5)
    X = solve_normals(lhs, rhs)
    assert np.all(np.isfinite(np.asarray(X)))
    # least-squares optimality: residual orthogonal to range(lhs)
    resid = np.asarray(X @ lhs) - np.asarray(rhs)
    np.testing.assert_allclose(resid @ np.asarray(lhs).T, 0.0, atol=1e-8)


def test_normalize_2norm():
    U = _rand((30, 5), seed=6)
    out, lam = normalize_columns(U, "2")
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(out, axis=0)),
                               1.0, atol=1e-12)
    np.testing.assert_allclose(np.asarray(out * lam), np.asarray(U), atol=1e-12)


def test_normalize_maxnorm_floor():
    """Max-norm is the *signed* max clamped at 1 (≙ p_mat_maxnorm,
    src/matrix.c:164-194 — SS_MAX over raw vals, no fabs)."""
    U = jnp.asarray(np.array([[0.5, 3.0], [0.25, -6.0]]))
    out, lam = normalize_columns(U, "max")
    np.testing.assert_allclose(np.asarray(lam), [1.0, 3.0])
    np.testing.assert_allclose(np.asarray(out),
                               [[0.5, 1.0], [0.25, -2.0]])
    # all-negative column: signed max < 1 -> λ clamps to 1, no scaling
    V = jnp.asarray(np.array([[-2.0], [-3.0]]))
    outv, lamv = normalize_columns(V, "max")
    np.testing.assert_allclose(np.asarray(lamv), [1.0])
    np.testing.assert_allclose(np.asarray(outv), np.asarray(V))


def test_normalize_zero_column_safe():
    U = jnp.asarray(np.array([[0.0, 1.0], [0.0, 1.0]]))
    out, lam = normalize_columns(U, "2")
    assert np.all(np.isfinite(np.asarray(out)))
    assert float(lam[0]) == 0.0


def _spd_with_cond(n, cond, seed):
    """A seeded n×n SPD matrix with eigenvalues log-spaced from 1 down
    to 1/cond under a random rotation, in float64."""
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    return (q * np.logspace(0, -np.log10(cond), n)) @ q.T


@pytest.mark.parametrize("cond", [1e3, 1.3e4, 7.6e4, 1e6])
def test_solve_normals_ill_conditioned_stays_least_squares(cond):
    """An f32 SPD normal matrix at the condition numbers ALS meets on
    skewed tensors (1.3e4-7.6e4 from the first max-norm iteration) is
    solved to f32 accuracy: the per-row residual of ``M = X·lhs``
    (float64), against the row's largest entry, the measure of
    benchmark/reference.py."""
    lhs = _spd_with_cond(50, cond, seed=11)
    M = np.random.default_rng(12).random((64, 50)) @ lhs
    X = solve_normals(jnp.asarray(lhs, jnp.float32),
                      jnp.asarray(M, jnp.float32))
    R = M - np.asarray(X, np.float64) @ lhs
    resid = np.max(np.abs(R), axis=1) / np.max(np.abs(M), axis=1)
    assert resid.max() <= 1e-5


@pytest.mark.parametrize("cond", [1e3, 1e6])
def test_solve_normals_report_cholesky_and_pivot_ratio(cond):
    """The report of an SPD solve: Cholesky ran (pinv 0.0), and the
    estimate is the pivot ratio, a lower bound on κ₂."""
    from splatt_tpu.ops.linalg import solve_normals_report

    lhs = _spd_with_cond(50, cond, seed=11).astype(np.float32)
    _, report = solve_normals_report(jnp.asarray(lhs),
                                     jnp.asarray(np.eye(50, dtype=np.float32)))
    pinv, est, margin = np.asarray(report, np.float64)
    assert pinv == 0.0
    L = np.linalg.cholesky(lhs.astype(np.float64))
    want = (np.diag(L).max() / np.diag(L).min()) ** 2
    assert est == pytest.approx(want, rel=1e-3)
    assert 1.0 <= est <= np.linalg.cond(lhs.astype(np.float64))
    # the margin of the factorization test, min_i L_ii² / (n·eps·lhs_ii),
    # off the f32 factor, whose smallest pivot rounding moves most
    floor = 50 * np.finfo(np.float32).eps * np.diag(lhs).astype(np.float64)
    assert margin == pytest.approx((np.diag(L) ** 2 / floor).min(),
                                   rel=1e-2)
    assert margin > 1.0


def test_solve_normals_singular_with_positive_pivots_takes_pinv():
    """A rank-deficient f32 normal matrix (the Hadamard product of two
    Grams of rank 7 and 3, at rank 23) whose Cholesky factor rounding
    leaves with every pivot positive still takes the pseudoinverse:
    such a pivot is below the rounding floor of its diagonal entry.
    Across the seeds some factors keep every pivot positive, so the
    floor, not a NaN, is what catches them."""
    from splatt_tpu.ops.linalg import solve_normals_report

    positive = 0
    for seed in range(12):
        rng = np.random.default_rng(seed)
        U1 = rng.random((7, 23)).astype(np.float32)
        U2 = rng.random((3, 23)).astype(np.float32)
        lhs = jnp.asarray((U1.T @ U1) * (U2.T @ U2))
        d = np.diag(np.asarray(jnp.linalg.cholesky(lhs)))
        positive += bool(np.all(np.isfinite(d)) and np.all(d > 0))
        rhs = jnp.asarray(rng.random((5, 23)).astype(np.float32)) @ lhs
        X, report = solve_normals_report(lhs, rhs)
        assert float(report[0]) == 1.0
        assert 0.0 <= float(report[2]) <= 1.0
        assert np.all(np.isfinite(np.asarray(X)))
        # the minimum-norm least-squares answer: consistent rhs is met
        np.testing.assert_allclose(np.asarray(X @ lhs), np.asarray(rhs),
                                   rtol=0, atol=1e-3 * float(
                                       np.abs(np.asarray(rhs)).max()))
    assert positive >= 1


@pytest.mark.parametrize("seed", [21, 22, 24, 25, 26])
def test_solve_normals_badly_scaled_singular_keeps_real_components(seed):
    """A singular f32 normal matrix whose diagonal spans twelve orders
    of magnitude, ``D·A·D`` with ``A`` of unit diagonal and rank n−1:
    the factorization test refuses it, and the pseudoinverse path still
    meets a consistent rhs in every column, each against its own
    largest entry.  A cut relative to the largest eigenvalue alone drops
    the directions of the small-diagonal columns; the Jacobi-scaled cut
    drops only the null direction."""
    from splatt_tpu.ops.linalg import solve_normals_report

    n = 12
    rng = np.random.default_rng(seed)
    B = rng.standard_normal((n, n - 1))
    A = B @ B.T
    A /= np.sqrt(np.outer(np.diag(A), np.diag(A)))
    D = np.logspace(-3, 3, n)
    lhs = (A * np.outer(D, D)).astype(np.float32)
    M = rng.standard_normal((32, n)) @ lhs.astype(np.float64)
    X, report = solve_normals_report(jnp.asarray(lhs),
                                     jnp.asarray(M, jnp.float32))
    assert float(report[0]) == 1.0
    R = M - np.asarray(X, np.float64) @ lhs.astype(np.float64)
    resid = np.max(np.abs(R), axis=0) / np.max(np.abs(M), axis=0)
    assert resid.max() <= 1e-4
