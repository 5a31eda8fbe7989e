"""The ALS update stays a least-squares update on skewed tensors.

On power-law slices the columns of a max-normed factor are nearly
collinear, so from the second (first max-norm) iteration on the normal
matrices reach condition numbers of 1e4 and more.  Cholesky in f32
solves them to f32 accuracy; a solve that drops their small
eigenvalues is no least-squares update at all.  Checked as
benchmark/reference.py states it: ``M_m ≈ U_m diag(s) H_m`` per row,
with ``M_m`` the float64 MTTKRP, ``H_m`` the Hadamard product of the
other modes' Grams, and the column scales s fitted.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np

from splatt_tpu.blocked import BlockedSparse
from splatt_tpu.config import Options, Verbosity
from splatt_tpu.coo import SparseTensor
from splatt_tpu.cpd import _make_sweep, cpd_als
from splatt_tpu.ops.linalg import gram

DIMS = (40, 30, 8000)
NNZ = 120_000
RANK = 25


def zipf_tensor(dims, nnz, seed):
    """Each coordinate drawn from Zipf(1.0) over its mode's slices,
    rankings permuted, duplicates dropped; values uniform in (0, 1]."""
    rng = np.random.default_rng(seed)
    cols = []
    for d in dims:
        p = 1.0 / np.arange(1, d + 1)
        cols.append(rng.permutation(d)[rng.choice(d, size=2 * nnz,
                                                  p=p / p.sum())])
    inds = np.unique(np.stack(cols), axis=1)
    inds = inds[:, rng.permutation(inds.shape[1])[:nnz]]
    vals = (1.0 - rng.random(inds.shape[1])).astype(np.float32)
    return inds, vals


def mttkrp64(inds, vals, factors, m):
    w = np.repeat(vals.astype(np.float64)[:, None], RANK, axis=1)
    for k, U in enumerate(factors):
        if k != m:
            w *= np.asarray(U, np.float64)[inds[k]]
    out = np.zeros((DIMS[m], RANK))
    np.add.at(out, inds[m], w)
    return out


def hadamard_grams(factors, skip):
    H = np.ones((RANK, RANK))
    for k, U in enumerate(factors):
        if k != skip:
            U = np.asarray(U, np.float64)
            H *= U.T @ U
    return H


def update_residual(M, U, H):
    """max over rows of max_c |(M − U diag(s) H)_ic| / max_c |M_ic|,
    s the least-squares column scales (benchmark/reference.py)."""
    U = np.asarray(U, np.float64)
    s = np.linalg.lstsq((U.T @ U) * (H @ H),
                        np.einsum("ir,ir->r", U, M @ H), rcond=None)[0]
    top = np.max(np.abs(M), axis=1)
    top = np.maximum(top, 1e-6 * top.max())
    return float(np.max(np.max(np.abs(M - (U * s) @ H), axis=1) / top))


def test_als_update_is_least_squares_on_zipf_slices():
    inds, vals = zipf_tensor(DIMS, NNZ, seed=0)
    opts = Options(random_seed=0, verbosity=Verbosity.NONE, tolerance=0.0,
                   use_pallas=False, autotune=False, val_dtype=np.float32)
    bs = BlockedSparse.from_coo(SparseTensor(inds, vals, DIMS), opts)
    rng = np.random.default_rng(100)
    init = [rng.random((d, RANK), dtype=np.float32) for d in DIMS]

    # the normal matrices the second sweep solves, from the driver's
    # own internal (max-normed) factors
    f = [jnp.asarray(U) for U in init]
    g = [gram(U) for U in f]
    sweep = _make_sweep(bs, 3, 0.0)
    states = [init]
    for first in (True, False):
        f, g, *_ = sweep(list(f), list(g), first)
        states.append([np.asarray(U) for U in f])
    conds = [np.linalg.cond(hadamard_grams(
        [states[2][k] if k < m else states[1][k] for k in range(3)], m))
        for m in range(3)]
    assert max(conds) > 2900, conds

    runs = [init] + [[np.asarray(U) for U in cpd_als(
        bs, RANK, dataclasses.replace(opts, max_iterations=i),
        init=init).factors] for i in (1, 2)]
    for i in (1, 2):
        for m in range(3):
            ins = [runs[i][k] if k < m else runs[i - 1][k]
                   for k in range(3)]
            r = update_residual(mttkrp64(inds, vals, ins, m), runs[i][m],
                                hadamard_grams(ins, m))
            assert r <= 1e-4, (i, m, r)
