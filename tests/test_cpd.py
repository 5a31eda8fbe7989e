"""CPD-ALS end-to-end tests (≙ the cpd CLI path + fit semantics).

The reference has no direct cpd unit test; correctness is anchored by the
MTTKRP oracle plus the fit formula.  Here we verify stronger properties:
exact recovery of a synthetic low-rank tensor, fit monotonic-ish
improvement, determinism under a fixed seed, and stream-vs-blocked
agreement on the final fit.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from splatt_tpu.blocked import BlockedSparse
from splatt_tpu.config import BlockAlloc, Options, Verbosity
from splatt_tpu.coo import SparseTensor
from splatt_tpu.cpd import cpd_als, init_factors
from tests import gen


def lowrank_tensor(dims, rank, seed=11, keep=1.0):
    """Sparse sample of an exactly rank-`rank` tensor.

    Note: with keep < 1 the *sparse* tensor (missing entries = zeros) is
    no longer low-rank — only keep=1.0 admits exact recovery.
    """
    rng = np.random.default_rng(seed)
    factors = [rng.random((d, rank)) + 0.1 for d in dims]
    dense = np.einsum("ir,jr,kr->ijk", *factors)
    mask = rng.random(dims) < keep
    idx = np.argwhere(mask)
    vals = dense[mask]
    return SparseTensor(idx.T, vals, dims)


def _opts(**kw):
    kw.setdefault("random_seed", 42)
    kw.setdefault("verbosity", Verbosity.NONE)
    kw.setdefault("val_dtype", np.float64)
    return Options(**kw)


def test_exact_recovery_stream():
    tt = lowrank_tensor((15, 12, 10), rank=3)
    out = cpd_als(tt, rank=5, opts=_opts(max_iterations=100, tolerance=1e-10))
    assert float(out.fit) > 0.999


def test_exact_recovery_blocked():
    tt = lowrank_tensor((15, 12, 10), rank=3, seed=12)
    bs = BlockedSparse.from_coo(tt, _opts(nnz_block=128))
    out = cpd_als(bs, rank=5, opts=_opts(max_iterations=100, tolerance=1e-10))
    assert float(out.fit) > 0.999


def test_reconstruction_matches_fit():
    tt = lowrank_tensor((8, 7, 6), rank=2, seed=13, keep=1.0)
    out = cpd_als(tt, rank=4, opts=_opts(max_iterations=100, tolerance=1e-12))
    dense = tt.to_dense()
    recon = out.to_dense()
    rel = np.linalg.norm(dense - recon) / np.linalg.norm(dense)
    assert rel == pytest.approx(1.0 - float(out.fit), abs=1e-6)
    assert rel < 1e-3


def test_deterministic_with_seed():
    tt = gen.fixture_tensor("med")
    a = cpd_als(tt, rank=4, opts=_opts(max_iterations=5))
    b = cpd_als(tt, rank=4, opts=_opts(max_iterations=5))
    np.testing.assert_allclose(float(a.fit), float(b.fit), atol=0)
    for fa, fb in zip(a.factors, b.factors):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))


def test_stream_blocked_fit_agreement():
    """Blocked CPD must track the stream CPD bit-for-bit-ish: same init,
    same math, different MTTKRP path."""
    tt = gen.fixture_tensor("med")
    opts = _opts(max_iterations=10, block_alloc=BlockAlloc.ALLMODE,
                 nnz_block=256)
    init = init_factors(tt.dims, 8, opts.seed(), dtype=jnp.float64)
    a = cpd_als(tt, rank=8, opts=opts, init=init)
    bs = BlockedSparse.from_coo(tt, opts)
    b = cpd_als(bs, rank=8, opts=opts, init=init)
    assert float(a.fit) == pytest.approx(float(b.fit), abs=1e-7)


def test_fit_in_range_and_lambda_positive():
    out = cpd_als(gen.fixture_tensor("med4"), rank=4,
                  opts=_opts(max_iterations=8))
    assert 0.0 <= float(out.fit) <= 1.0
    assert np.all(np.asarray(out.lam) >= 0)
    # post-processing leaves unit-norm columns (cpd_post_process)
    for U in out.factors:
        norms = np.linalg.norm(np.asarray(U), axis=0)
        np.testing.assert_allclose(norms[norms > 1e-12], 1.0, atol=1e-8)


def test_convergence_tolerance_stops_early():
    tt = lowrank_tensor((10, 9, 8), rank=2, seed=14, keep=0.5)
    loose = cpd_als(tt, rank=3, opts=_opts(max_iterations=50, tolerance=1e-2))
    assert 0.0 < float(loose.fit) <= 1.0


def test_regularization_runs():
    tt = gen.fixture_tensor("small")
    out = cpd_als(tt, rank=3, opts=_opts(max_iterations=5, regularization=1e-3))
    assert np.isfinite(float(out.fit))


def test_4mode_and_5mode():
    for name in ("med4", "med5"):
        tt = gen.fixture_tensor(name)
        bs = BlockedSparse.from_coo(tt, _opts(nnz_block=256))
        out = cpd_als(bs, rank=4, opts=_opts(max_iterations=5))
        assert np.isfinite(float(out.fit))
        assert out.nmodes == tt.nmodes


def test_checkpoint_resume_past_max_iterations(tmp_path):
    """Resuming a finished run must return the checkpointed model, not
    a zero-fit shell."""
    tt = gen.fixture_tensor("med")
    ck = str(tmp_path / "ck.npz")
    opts = _opts(max_iterations=6)
    a = cpd_als(tt, rank=3, opts=opts, checkpoint_path=ck,
                checkpoint_every=2)
    b = cpd_als(tt, rank=3, opts=opts, checkpoint_path=ck,
                checkpoint_every=2)  # start_it == max_iterations
    assert float(b.fit) == pytest.approx(float(a.fit), abs=1e-8)
    np.testing.assert_allclose(b.to_dense(), a.to_dense(), atol=1e-8)


def test_checkpoint_mismatch_rejected(tmp_path):
    tt = gen.fixture_tensor("med")
    ck = str(tmp_path / "ck.npz")
    cpd_als(tt, rank=3, opts=_opts(max_iterations=4),
            checkpoint_path=ck, checkpoint_every=2)
    with pytest.raises(ValueError, match="checkpoint"):
        cpd_als(tt, rank=8, opts=_opts(max_iterations=4),
                checkpoint_path=ck, checkpoint_every=2)
    # resume=False overwrites instead
    out = cpd_als(tt, rank=8, opts=_opts(max_iterations=4),
                  checkpoint_path=ck, checkpoint_every=2, resume=False)
    assert out.rank == 8


def test_fit_check_every_same_result():
    """k>1 batches host syncs between convergence checks; with
    convergence disabled (tol=0) the math is identical to k=1."""
    import numpy as np

    from splatt_tpu import BlockedSparse, cpd_als, default_opts
    from tests.gen import fixture_tensor

    tt = fixture_tensor("small")
    res = {}
    for k in (1, 4):
        opts = default_opts()
        opts.random_seed = 5
        opts.max_iterations = 8
        opts.tolerance = 0.0
        opts.fit_check_every = k
        res[k] = cpd_als(BlockedSparse.from_coo(tt, opts), rank=3, opts=opts)
    assert abs(float(res[1].fit) - float(res[4].fit)) < 1e-6
    for a, b in zip(res[1].factors, res[4].factors):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_fit_check_every_validation():
    import pytest

    from splatt_tpu import default_opts

    opts = default_opts()
    opts.fit_check_every = 0
    with pytest.raises(ValueError, match="fit_check_every"):
        opts.validate()


def test_phased_sweep_matches_fused():
    """The per-phase jitted sweep (the TPU default) is bit-identical to the fused
    sweep — same phase order, same accumulations."""
    from splatt_tpu.cpd import _make_phased_sweep, _make_sweep
    from splatt_tpu.ops.linalg import gram

    rng = np.random.default_rng(5)
    dims = (14, 11, 9)
    ind = np.stack([rng.integers(0, d, size=300) for d in dims])
    tt = SparseTensor(ind, rng.random(300), dims)
    # pin the XLA engine: the un-jitted phased sweep would otherwise
    # dispatch to the native C++ engine, whose summation order differs
    bs = BlockedSparse.from_coo(tt, _opts(nnz_block=128,
                                          block_alloc=BlockAlloc.ALLMODE,
                                          use_pallas=False))
    outs = []
    for builder in (_make_sweep, _make_phased_sweep):
        factors = init_factors(tt.dims, 6, 3, dtype=jnp.float64)
        grams = [gram(U) for U in factors]
        sweep = builder(bs, tt.nmodes, 0.0)
        f, g, lam, zz, inner = sweep(factors, grams, True)
        for _ in range(3):
            f, g, lam, zz, inner = sweep(f, g, False)
        outs.append((f, lam, float(zz), float(inner)))
    (f_a, lam_a, zz_a, in_a), (f_b, lam_b, zz_b, in_b) = outs
    assert zz_a == zz_b and in_a == in_b
    np.testing.assert_array_equal(np.asarray(lam_a), np.asarray(lam_b))
    for ua, ub in zip(f_a, f_b):
        np.testing.assert_array_equal(np.asarray(ua), np.asarray(ub))


def test_phased_sweep_donation_bit_identical():
    """Regression for the SPL008-driven restructure of the phased
    sweep (the last mode's update + fit moved OUTSIDE the donating
    loop so the donated M is never live at the fit read): mid-phase M
    donation stays a pure buffer-aliasing optimization — bit-identical
    to the non-donating phased sweep, callers' factors untouched."""
    from splatt_tpu.cpd import _make_phased_sweep
    from splatt_tpu.ops.linalg import gram

    rng = np.random.default_rng(7)
    dims = (14, 11, 9)
    ind = np.stack([rng.integers(0, d, size=300) for d in dims])
    tt = SparseTensor(ind, rng.random(300), dims)
    bs = BlockedSparse.from_coo(tt, _opts(nnz_block=128,
                                          block_alloc=BlockAlloc.ALLMODE,
                                          use_pallas=False))
    outs = []
    for donate in (False, True):
        factors = init_factors(tt.dims, 6, 3, dtype=jnp.float64)
        grams = [gram(U) for U in factors]
        sweep = _make_phased_sweep(bs, tt.nmodes, 0.0, donate=donate)
        f, g, lam, zz, inner = sweep(factors, grams, True)
        for _ in range(2):
            f, g, lam, zz, inner = sweep(f, g, False)
        # the fit phase read M AFTER the last (non-donating) update —
        # with donation on, a mid-phase M re-read would have raised
        outs.append((f, lam, float(zz), float(inner)))
        assert not any(u.is_deleted() for u in factors)
    (f_a, lam_a, zz_a, in_a), (f_b, lam_b, zz_b, in_b) = outs
    assert zz_a == zz_b and in_a == in_b
    np.testing.assert_array_equal(np.asarray(lam_a), np.asarray(lam_b))
    for ua, ub in zip(f_a, f_b):
        np.testing.assert_array_equal(np.asarray(ua), np.asarray(ub))


def test_stop_hook_checkpoints_and_returns_early(tmp_path):
    """The cooperative `stop` hook (the serve daemon's drain,
    docs/serve.md): polled at fit-check iterations; returning True
    checkpoints the just-committed state and returns early, and a
    later resume continues the same optimization to the un-stopped
    result."""
    from splatt_tpu.cpd import load_checkpoint

    tt = lowrank_tensor((15, 12, 10), rank=3)
    ck = str(tmp_path / "stop.npz")
    opts = _opts(max_iterations=20, tolerance=0.0)
    calls = []

    def stop():
        calls.append(1)
        return len(calls) >= 3

    partial = cpd_als(tt, rank=3, opts=opts, checkpoint_path=ck,
                      checkpoint_every=100, stop=stop)
    _, _, it, fit = load_checkpoint(ck)
    assert it == 3 and len(calls) == 3      # stopped at the 3rd check
    assert fit == pytest.approx(float(partial.fit))
    # resume without the hook: finishes the remaining iterations and
    # matches an uninterrupted run of the same config
    resumed = cpd_als(tt, rank=3, opts=opts, checkpoint_path=ck,
                      checkpoint_every=100)
    straight = cpd_als(tt, rank=3, opts=opts)
    assert float(resumed.fit) == pytest.approx(float(straight.fit),
                                               abs=1e-6)


def test_stop_hook_never_true_changes_nothing(tmp_path):
    tt = lowrank_tensor((15, 12, 10), rank=3)
    opts = _opts(max_iterations=10, tolerance=0.0)
    a = cpd_als(tt, rank=3, opts=opts)
    b = cpd_als(tt, rank=3, opts=opts, stop=lambda: False)
    assert float(a.fit) == pytest.approx(float(b.fit), abs=0.0)


def test_health_guard_disabled_skips_snapshot_refresh(monkeypatch):
    """Satellite: with SPLATT_HEALTH_RETRIES=0 the sentinel's host-
    snapshot refresh is skipped entirely (guards must be free when
    disabled) — only the single initial rescue snapshot is taken for
    the donated fused sweep, and none at all for non-donating sweeps.
    """
    import splatt_tpu.cpd as cpd_mod

    monkeypatch.setenv("SPLATT_HEALTH_RETRIES", "0")
    tt = lowrank_tensor((15, 12, 10), rank=3)
    opts = _opts(max_iterations=6, tolerance=0.0)
    bs = BlockedSparse.from_coo(tt, opts)

    copies = []
    real = np.asarray

    def counting_asarray(a, *k, **kw):
        copies.append(1)
        return real(a, *k, **kw)

    monkeypatch.setattr(cpd_mod.np, "asarray", counting_asarray)
    out = cpd_als(bs, rank=3, opts=opts)
    disabled_copies = len(copies)
    assert np.isfinite(float(out.fit))

    # with the sentinel ON, the snapshot refreshes at every check
    # iteration — strictly more host copies than the disabled run
    monkeypatch.setenv("SPLATT_HEALTH_RETRIES", "3")
    copies.clear()
    cpd_als(bs, rank=3, opts=opts)
    assert len(copies) > disabled_copies
