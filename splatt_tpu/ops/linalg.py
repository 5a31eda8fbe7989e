"""Dense linear algebra for CPD-ALS (≙ src/matrix.c, src/splatt_lapack.h).

All rank×rank / dim×rank dense math lowers to XLA (MXU):
- :func:`gram`             ≙ mat_aTa          (src/matrix.c:414-455, BLAS syrk)
- :func:`form_normal_lhs`  ≙ p_form_gram      (src/matrix.c:29-83)
- :func:`solve_normals`    ≙ mat_solve_normals (src/matrix.c:529-606,
                             LAPACK potrf/potrs with gelss SVD fallback)
- :func:`normalize_columns` ≙ p_mat_2norm/p_mat_maxnorm (src/matrix.c:87-205)

The solve keeps the reference's order: Cholesky (potrf/potrs) whenever
the factorization succeeds, and a pseudoinverse (the gelss fallback)
only when it fails, that is when the normal matrix is numerically
singular.  Cholesky in f32 is backward stable far past the condition
numbers ALS meets (κ₂ past 1e6 on a NELL-2-sized power-law tensor at
rank 50, from the first max-norm iteration), so ill-conditioning alone
never switches the path.  The switch is a ``lax.cond`` on the device:
the eigendecompositions run only on the rare singular path, and no host
sync is involved.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp


def gram(U: jax.Array) -> jax.Array:
    """UᵀU (rank×rank Gram matrix; ≙ mat_aTa).

    The reference only fills the upper triangle then mirrors; XLA emits a
    full syrk-like matmul on the MXU either way.  Low-precision factors
    (bf16/f16) accumulate in f32 — Gram matrices feed the normal
    equations and cannot afford bf16 accumulation error.
    """
    from splatt_tpu.config import acc_dtype
    from splatt_tpu.ops.mttkrp import mxu_precision

    return jnp.matmul(U.T, U, preferred_element_type=acc_dtype(U.dtype),
                      precision=mxu_precision(U.dtype))


def form_normal_lhs(grams: Sequence[jax.Array], mode: int,
                    regularization: float = 0.0) -> jax.Array:
    """Hadamard product of all Grams except `mode`, + λI (≙ p_form_gram)."""
    rank = grams[0].shape[0]
    out = jnp.ones((rank, rank), dtype=grams[0].dtype)
    for m, g in enumerate(grams):
        if m != mode:
            out = out * g
    if regularization != 0.0:
        out = out + regularization * jnp.eye(rank, dtype=out.dtype)
    return out


#: the entries of one mode's solve report (:func:`solve_normals_report`)
SOLVE_REPORT = ("pinv", "cond", "margin")


def _eig_pinv(a: jax.Array):
    """``pinv(a) = V diag(1/w) Vᵀ`` for a symmetric ``a`` through its
    eigendecomposition, as its two factors ``V diag(1/w)`` and ``Vᵀ``;
    κ₂ = max|w| / min|w| (inf when an eigenvalue is zero); and the
    number of eigenvalues kept.

    Eigenvalues with |w| ≤ n·eps·max|w| are dropped: gelss's
    machine-precision ``rcond`` scaled by the rank, the size of the
    rounding error an eigensolver leaves in every eigenvalue of an n×n
    matrix.  Below it a direction carries only that noise; above it
    each is a real component of the system and is kept."""
    w, v = jnp.linalg.eigh(a)
    top = jnp.max(jnp.abs(w))
    keep = jnp.abs(w) > a.shape[0] * jnp.finfo(a.dtype).eps * top
    w_inv = jnp.where(keep, 1.0 / jnp.where(keep, w, 1.0), 0.0)
    return (v * w_inv, v.T), top / jnp.min(jnp.abs(w)), jnp.sum(keep)


def _pinv_solve(lhs: jax.Array, rhs: jax.Array):
    """Least-squares ``X · lhs = rhs`` for a numerically singular lhs
    (≙ the gelss fallback, src/matrix.c:554-603), and κ₂ of lhs.

    Two truncations, and the one that keeps more directions.  The
    minimum-norm answer, ``rhs · pinv(lhs)``, is gelss's; its cut is
    relative to lhs's largest eigenvalue, so on a badly scaled lhs it
    also drops directions the data determine.  The Jacobi-scaled
    answer, ``rhs · D·pinv(D·lhs·D)·D`` with ``D = diag(lhs)^-½``, cuts
    on the unit-diagonal scale the factorization test of
    :func:`solve_normals_report` uses.  That test refuses a factor only
    when a scaled pivot is at most n·eps; the smallest scaled
    eigenvalue is then too, and the scaled cut (n·eps times the largest
    scaled eigenvalue, which is at least 1) drops it, while it keeps
    every direction above the eigensolver's noise on that scale.  When
    both keep as many directions, the minimum-norm answer stands, so a
    well-scaled singular system gets gelss's answer; on a consistent
    rhs (ALS's always is: its MTTKRP rows lie in the normal matrix's
    range) either meets it.  The choice is made on the rank×rank
    factors, with D folded into them, so rhs goes through one solve."""
    from splatt_tpu.config import acc_dtype
    from splatt_tpu.ops.mttkrp import mxu_precision

    (left, right), cond, kept = _eig_pinv(lhs)
    diag = jnp.diagonal(lhs)
    d = jnp.where(diag > 0, jax.lax.rsqrt(jnp.where(diag > 0, diag, 1.0)),
                  1.0)
    (s_left, s_right), _, s_kept = _eig_pinv(lhs * d[:, None] * d[None, :])
    scaled = s_kept > kept
    left = jnp.where(scaled, d[:, None] * s_left, left)
    right = jnp.where(scaled, s_right * d[None, :], right)
    prec = mxu_precision(lhs.dtype)
    acc = acc_dtype(rhs.dtype)
    x = jnp.matmul(jnp.matmul(rhs, left, precision=prec,
                              preferred_element_type=acc), right,
                   precision=prec, preferred_element_type=acc)
    return x, cond


def solve_normals_report(lhs: jax.Array, rhs: jax.Array):
    """:func:`solve_normals` and its report: ``(X, [pinv, cond,
    margin])``, a vector in lhs's dtype holding 1.0 when the
    pseudoinverse ran (else 0.0), the condition estimate of lhs, and
    the factorization test's margin.

    The estimate's kind follows the path.  Cholesky: the pivot ratio
    ``(max_i L_ii / min_i L_ii)²``, a lower bound on κ₂ (each squared
    pivot lies between lhs's smallest and largest eigenvalue), read off
    the factor for free.  Pseudoinverse: κ₂ from the eigenvalues it
    computed.

    The factorization has failed, and lhs is numerically singular,
    when a pivot is not finite or when a squared pivot ``L_ii²`` is no
    larger than ``n·eps·lhs_ii``; the test reads the factor alone, so
    the Cholesky solve runs only in its branch.  The first is LAPACK
    potrf's own failure (info > 0), which jax returns as NaN.  The
    second is the scale-free form of the pseudoinverse's cutoff
    (gelss's machine-precision ``rcond`` scaled by the rank):
    ``L_ii² / lhs_ii`` is the pivot of lhs with its diagonal scaled to
    1, and a pivot that small is rounding noise,
    since the computed factor is exact only for ``lhs + ΔA`` with
    ``|ΔA_ii| ≤ (n+1)·(eps/2)·lhs_ii`` (Higham, Accuracy and Stability
    of Numerical Algorithms, Thm 10.3), before the rounding that formed
    lhs.  Such a factor solves a nearby singular matrix: finite
    garbage, which potrf would have returned.  Every squared pivot is
    at least lhs's smallest eigenvalue and every diagonal entry at most
    its largest, so an SPD lhs is refused only when κ₂ ≥ 1/(n·eps),
    1.7e5 at n = 50 in f32; the test is on the factor, not on κ₂, and
    seeded random rotations keep Cholesky to κ₂ = 1e7.  The margin,
    ``min_i L_ii² / (n·eps·lhs_ii)``, says how far the factor is from
    that test: above 1 Cholesky ran, and 0 stands for a pivot that is
    not finite.
    """
    with jax.named_scope("als.solve"):
        chol = jax.scipy.linalg.cho_factor(lhs, lower=True)
        d = jnp.diagonal(chol[0])
        floor = (lhs.shape[0] * jnp.finfo(lhs.dtype).eps
                 * jnp.diagonal(lhs))
        ok = jnp.all(jnp.isfinite(d)) & jnp.all(d * d > floor)
        margin = jnp.min(d * d / floor)
        margin = jnp.where(jnp.isnan(margin), 0.0, margin)

        def cholesky():
            x = jax.scipy.linalg.cho_solve(chol, rhs.T).T
            return x, (jnp.max(d) / jnp.min(d)) ** 2

        def pinv():
            x, cond = _pinv_solve(lhs, rhs)
            return x.astype(jnp.result_type(lhs, rhs)), cond.astype(d.dtype)

        x, cond = jax.lax.cond(ok, cholesky, pinv)
        return x, jnp.stack([jnp.where(ok, 0.0, 1.0).astype(d.dtype),
                             cond, margin.astype(d.dtype)])


def solve_normals(lhs: jax.Array, rhs: jax.Array) -> jax.Array:
    """Solve ``X · lhs = rhs`` for X (rows = factor rows; ≙ mat_solve_normals).

    lhs is the rank×rank normal-equations matrix (symmetric PSD), rhs the
    (dim, rank) MTTKRP result.  Cholesky whenever the factorization
    succeeds, whatever the condition number; the minimum-norm
    least-squares solve through the eigendecomposition pseudoinverse
    only when it fails (≙ potrf, then gelss when potrf fails,
    src/matrix.c:529-606).  :func:`solve_normals_report` states the
    failure test and the cutoff, and also returns which path ran.
    """
    return solve_normals_report(lhs, rhs)[0]


@partial(jax.jit, static_argnames=("which",))
def normalize_columns(U: jax.Array, which: str = "2") -> tuple[jax.Array, jax.Array]:
    """Normalize columns, returning (normalized U, λ).

    which="2": 2-norm (used on ALS iteration 0); which="max": max-norm with
    a floor of 1 so λ never shrinks columns (≙ p_mat_2norm / p_mat_maxnorm,
    src/matrix.c:87-205).  The max-norm is the *signed* max like the
    reference (p_mat_maxnorm accumulates SS_MAX over raw vals from 0,
    then clamps to >= 1, src/matrix.c:164-194) — a column whose entries
    are all negative gets λ=1, keeping iteration trajectories comparable
    bit-for-bit with reference runs.
    """
    from splatt_tpu.config import acc_dtype

    if which == "2":
        # upcast-before-reduce: a bf16 column's squared norm loses
        # mass accumulated at 8 mantissa bits — one pinned contraction
        # accumulates wide without materializing U*U (SPL024)
        lam = jnp.sqrt(jnp.einsum("dr,dr->r", U, U,
                                  preferred_element_type=acc_dtype(U.dtype)))
    elif which == "max":
        lam = jnp.maximum(jnp.max(U, axis=0), 1.0)
    else:
        raise ValueError(f"unknown norm {which!r}")
    safe = jnp.where(lam > 0, lam, 1.0)
    return U / safe.astype(U.dtype), lam
