"""CPD-ALS of a 4-mode tensor with one long mode against a plain float64
reference, at rank 25.

The tensor is a small stand-in for FROSTT Enron (sender x receiver x
word x date): Zipf(1.0) slices in every mode, one mode of 2000 rows
whose lightest slices hold a handful of nonzeros, so its blocks span
many output rows.  The reference is numpy ALS in float64 with no
kernels, layouts or blocking: each mode's MTTKRP by ``np.add.at``, the
normal equations by ``np.linalg.solve``, the first iteration's columns
2-normed and the later ones max-normed (floor 1), regularization 0, the
result's columns folded into λ.  The program runs ``cpd_als`` on an
allmode ``BlockedSparse`` from the same init, under the Mosaic one-hot
engine in interpret mode (three gathered row arrays per kernel step)
and under the XLA scan engine.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from splatt_tpu import trace
from splatt_tpu.blocked import BlockedSparse
from splatt_tpu.config import BlockAlloc, Options, Verbosity
from splatt_tpu.coo import SparseTensor
from splatt_tpu.cpd import cpd_als
from tests.test_als_least_squares import zipf_tensor

DIMS = (40, 30, 2000, 12)
NNZ = 30_000
RANK = 25
BLOCK = 512


def mttkrp64(inds, vals, factors, m):
    w = np.repeat(vals.astype(np.float64)[:, None], RANK, axis=1)
    for k, U in enumerate(factors):
        if k != m:
            w *= U[inds[k]]
    out = np.zeros((DIMS[m], RANK))
    np.add.at(out, inds[m], w)
    return out


def als64(inds, vals, init, iters):
    """float64 CPD-ALS from `init`: (factors with unit 2-norm columns,
    λ) after each of `iters` iterations."""
    U = [np.asarray(u, np.float64) for u in init]
    out = []
    for it in range(iters):
        for m in range(len(DIMS)):
            H = np.ones((RANK, RANK))
            for k, V in enumerate(U):
                if k != m:
                    H *= V.T @ V
            Um = np.linalg.solve(H, mttkrp64(inds, vals, U, m).T).T
            lam = (np.linalg.norm(Um, axis=0) if it == 0
                   else np.maximum(Um.max(axis=0), 1.0))
            U[m] = Um / np.where(lam > 0, lam, 1.0)
        norms = [np.linalg.norm(V, axis=0) for V in U]
        out.append(([V / n for V, n in zip(U, norms)],
                    lam * np.prod(norms, axis=0)))
    return out


@pytest.fixture(scope="module")
def case():
    inds, vals = zipf_tensor(DIMS, NNZ, seed=4)
    rng = np.random.default_rng(29)
    init = [rng.random((d, RANK), dtype=np.float32) for d in DIMS]
    return inds, vals, init, als64(inds, vals, init, 2)


#: engine the dispatch span names under each setting
ENGINES = {True: "unfused_pallas", False: "xla"}

#: largest entry error of any factor (columns at unit 2-norm) against
#: the reference's.  Sound runs read 5.8e-5-1.3e-4 (CPU, both engines,
#: iterations 1-2): f32 sums of up to ~10^3 products per output row,
#: then solves whose normal matrices (Hadamard products of four Grams)
#: amplify that rounding.  Values stored in bfloat16 (2^-9 relative
#: rounding each) read 3.0e-2-4.5e-2.
FACTOR_TOL = 1e-3
#: largest relative error of λ: sound 2.2e-5-6.0e-5, bfloat16 values
#: 1.5e-2-2.0e-2 (CPU); λ is the column norms' product, so it averages
#: the factors' rounding over each column.
LAM_TOL = 5e-4


def run(inds, vals, init, iters, use_pallas, **over):
    """The program's factors and λ after `iters` iterations from
    `init`, and the engines its MTTKRP dispatch chose."""
    opts = Options(random_seed=0, verbosity=Verbosity.NONE, tolerance=0.0,
                   use_pallas=use_pallas, autotune=False,
                   block_alloc=BlockAlloc.ALLMODE, nnz_block=BLOCK,
                   val_dtype=np.float32, **over)
    bs = BlockedSparse.from_coo(SparseTensor(inds, vals, DIMS), opts)
    # the long mode's blocks span many rows (72 at this size)
    assert bs.layout_for(2).seg_width > 16
    trace.reset()
    out = cpd_als(bs, RANK, dataclasses.replace(opts, max_iterations=iters,
                                                trace=True), init=init)
    engines = {s["args"]["engine"] for s in trace.spans("mttkrp.dispatch")}
    trace.reset()
    return [np.asarray(U) for U in out.factors], np.asarray(out.lam), engines


def errors(fs, lam, want):
    """(largest factor entry error, largest relative λ error) against
    the reference's (factors, λ)."""
    rfs, rlam = want
    fac = max(float(np.max(np.abs(f - r))) for f, r in zip(fs, rfs))
    return fac, float(np.max(np.abs(lam - rlam) / np.abs(rlam)))


@pytest.mark.parametrize("it", [1, 2])
@pytest.mark.parametrize("use_pallas", [True, False],
                         ids=["unfused_pallas", "xla"])
def test_matches_float64_als(case, use_pallas, it):
    """Iteration 1 (2-normed columns) and 2 (max-normed) agree with the
    float64 reference within each tolerance."""
    inds, vals, init, ref = case
    fs, lam, engines = run(inds, vals, init, it, use_pallas)
    assert engines == {ENGINES[use_pallas]}
    fac, lam_err = errors(fs, lam, ref[it - 1])
    assert fac <= FACTOR_TOL and lam_err <= LAM_TOL, (fac, lam_err)


@pytest.mark.parametrize("use_pallas", [True, False],
                         ids=["unfused_pallas", "xla"])
def test_bf16_values_fail_the_tolerances(case, use_pallas):
    """The control: the program's bfloat16 value storage, one precision
    below the f32 the comparison states, fails at least one tolerance
    at iteration 2."""
    inds, vals, init, ref = case
    fs, lam, engines = run(inds, vals, init, 2, use_pallas,
                           val_storage="bf16")
    assert engines == {ENGINES[use_pallas]}
    fac, lam_err = errors(fs, lam, ref[1])
    assert fac > FACTOR_TOL or lam_err > LAM_TOL, (fac, lam_err)
