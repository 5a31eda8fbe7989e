"""Pallas kernel differential tests (interpret mode on CPU).

≙ the reference's practice of running the real optimized kernels in
tests (tests/mttkrp_test.c) — interpret mode executes the exact kernel
semantics that Mosaic compiles on TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from splatt_tpu.blocked import BlockedSparse
from splatt_tpu.config import BlockAlloc, Options
from splatt_tpu.ops.mttkrp import mttkrp, mttkrp_blocked
from splatt_tpu.ops.pallas_kernels import (onehot_reduce_full,
                                           onehot_reduce_sorted)
from tests import gen
from tests.test_mttkrp import make_factors, np_mttkrp

TOL = 1e-10


def _np_onehot_sorted(local, prod, S):
    nb, B = local.shape
    out = np.zeros((nb, S, prod.shape[-1]), dtype=np.float64)
    for b in range(nb):
        for j in range(B):
            s = local[b, j]
            if 0 <= s < S:
                out[b, s] += prod[b, j]
    return out


def test_onehot_reduce_sorted_kernel():
    rng = np.random.default_rng(0)
    nb, B, S, R = 5, 128, 16, 8
    local = rng.integers(-1, S + 3, size=(nb, B)).astype(np.int32)
    prod = rng.random((nb, B, R))
    got = onehot_reduce_sorted(jnp.asarray(local), jnp.asarray(prod), S,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               _np_onehot_sorted(local, prod, S), atol=TOL)


def test_onehot_reduce_full_kernel():
    rng = np.random.default_rng(1)
    nb, B, W, R = 9, 128, 24, 8  # nb not divisible by the chunk size
    local = rng.integers(0, W, size=(nb, B)).astype(np.int32)
    prod = rng.random((nb, B, R))
    got = onehot_reduce_full(jnp.asarray(local), jnp.asarray(prod), W,
                             interpret=True)
    want = _np_onehot_sorted(local, prod, W).sum(axis=0)
    np.testing.assert_allclose(np.asarray(got), want, atol=TOL)


@pytest.mark.parametrize("name", ["med", "med4"])
def test_pallas_mttkrp_matches_oracle(name):
    """Full blocked MTTKRP with the Pallas engine (interpret) on every
    mode/path where a one-hot reduction runs."""
    tt = gen.fixture_tensor(name)
    opts = Options(block_alloc=BlockAlloc.ALLMODE, nnz_block=128,
                   val_dtype=np.float64)
    bs = BlockedSparse.from_coo(tt, opts)
    factors = make_factors(tt.dims)
    for mode in range(tt.nmodes):
        want = np_mttkrp(tt, factors, mode)
        got = mttkrp_blocked(bs.layout_for(mode), factors, mode,
                             path="sorted_onehot", impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(got), want, atol=TOL,
                                   err_msg=f"sorted_onehot mode={mode}")
        other = bs.layout_for((mode + 1) % tt.nmodes)
        if other.mode != mode:
            got = mttkrp_blocked(other, factors, mode,
                                 path="privatized", impl="pallas_interpret")
            np.testing.assert_allclose(np.asarray(got), want, atol=TOL,
                                       err_msg=f"privatized mode={mode}")


def test_public_mttkrp_forced_pallas():
    tt = gen.fixture_tensor("med")
    opts = Options(val_dtype=np.float64, use_pallas=True, nnz_block=256)
    bs = BlockedSparse.from_coo(tt, opts)
    factors = make_factors(tt.dims)
    got = mttkrp(bs, factors, bs.layouts[0].mode)
    want = np_mttkrp(tt, factors, bs.layouts[0].mode)
    np.testing.assert_allclose(np.asarray(got), want, atol=TOL)


def test_unfused_pallas_engine_direct():
    """The chip's main-path engine (scan over block chunks: XLA gather
    + Hadamard, Mosaic one-hot reduce) — sorted partials + privatized
    totals vs the numpy brute force."""
    from splatt_tpu.blocked import build_layout
    from splatt_tpu.ops.mttkrp import _scan_fused

    tt = gen.fixture_tensor("med")
    factors = make_factors(tt.dims)
    for mode in range(tt.nmodes):
        lay = build_layout(tt, mode, block=128, val_dtype=np.float64)
        want = np_mttkrp(tt, factors, mode)
        S = lay.seg_width
        # a small step target forces several scan steps and a padded tail
        parts = _scan_fused(lay, factors, mode, S, accumulate=False,
                            target_elems=3 * 128 * 8, pallas=True,
                            interpret=True)
        idx = (np.asarray(lay.row_start)[:, None] + np.arange(S)).reshape(-1)
        out = np.zeros((tt.dims[mode] + S + 1, factors[0].shape[1]))
        np.add.at(out, idx, np.asarray(parts).reshape(-1, factors[0].shape[1]))
        np.testing.assert_allclose(out[:tt.dims[mode]], want, atol=TOL,
                                   err_msg=f"unfused sorted mode={mode}")
        W = -(-(tt.dims[mode] + 1) // 8) * 8
        tot = _scan_fused(lay, factors, mode, W, accumulate=True,
                          target_elems=3 * 128 * 8, pallas=True,
                          interpret=True)
        np.testing.assert_allclose(np.asarray(tot)[:tt.dims[mode]], want,
                                   atol=TOL,
                                   err_msg=f"unfused privatized mode={mode}")


def test_fused_tg_kernel_direct():
    """Sublane-tiled fused kernel (grid over rank tiles × blocks) vs the
    numpy brute force — covering multi-chunk lane gathers (block larger
    than a padded mode dim), multiple rank tiles, and both output
    contracts."""
    from splatt_tpu.blocked import build_layout
    from splatt_tpu.ops.pallas_kernels import fused_mttkrp_tg

    for name, block, rank in (("med", 128, 8),      # single tile
                              ("med", 512, 20),     # ck>1, 3 rank tiles
                              ("med4", 256, 12)):   # 4-mode
        tt = gen.fixture_tensor(name)
        factors = make_factors(tt.dims, rank=rank)
        for mode in range(tt.nmodes):
            lay = build_layout(tt, mode, block=block, val_dtype=np.float64)
            want = np_mttkrp(tt, factors, mode)
            S = lay.seg_width
            parts = fused_mttkrp_tg(lay, factors, mode, S, accumulate=False,
                                    interpret=True)
            idx = (np.asarray(lay.row_start)[:, None]
                   + np.arange(S)).reshape(-1)
            out = np.zeros((tt.dims[mode] + S + 1, rank))
            np.add.at(out, idx, np.asarray(parts).reshape(-1, rank))
            np.testing.assert_allclose(
                out[:tt.dims[mode]], want, atol=TOL,
                err_msg=f"fused_tg sorted {name} block={block} mode={mode}")
            W = -(-(tt.dims[mode] + 1) // 8) * 8
            tot = fused_mttkrp_tg(lay, factors, mode, W, accumulate=True,
                                  interpret=True)
            np.testing.assert_allclose(
                np.asarray(tot)[:tt.dims[mode]], want, atol=TOL,
                err_msg=f"fused_tg priv {name} block={block} mode={mode}")


def test_fused_tg_dispatch_when_tables_too_big(monkeypatch):
    """When whole-table residency (fused_t) is gated out, dispatch picks
    the sublane-tiled kernel — whose VMEM plan is rank/dim independent —
    and the answer still matches."""
    import splatt_tpu.ops.pallas_kernels as pk
    from splatt_tpu.ops.mttkrp import engine_plan

    tt = gen.fixture_tensor("med")
    opts = Options(block_alloc=BlockAlloc.ALLMODE, nnz_block=128,
                   val_dtype=np.float64)
    bs = BlockedSparse.from_coo(tt, opts)
    factors = make_factors(tt.dims)
    monkeypatch.setattr(pk, "fused_t_vmem_ok", lambda *a, **k: False)
    mttkrp_blocked.clear_cache()
    for mode in range(tt.nmodes):
        lay = bs.layout_for(mode)
        assert engine_plan(lay, factors, mode, "sorted_onehot",
                           "pallas_interpret") == "fused_tg"
        want = np_mttkrp(tt, factors, mode)
        got = mttkrp_blocked(lay, factors, mode,
                             path="sorted_onehot", impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(got), want, atol=TOL,
                                   err_msg=f"fused_tg dispatch mode={mode}")


def test_fused_tg_bf16_accumulates_f32():
    from splatt_tpu.blocked import build_layout
    from splatt_tpu.ops.pallas_kernels import fused_mttkrp_tg

    tt = gen.fixture_tensor("med")
    factors = [jnp.asarray(np.asarray(f), dtype=jnp.bfloat16)
               for f in make_factors(tt.dims)]
    lay = build_layout(tt, 0, block=128, val_dtype=jnp.bfloat16)
    W = -(-(tt.dims[0] + 1) // 8) * 8
    tot = fused_mttkrp_tg(lay, factors, 0, W, accumulate=True,
                          interpret=True)
    assert tot.dtype == jnp.float32
    want = np_mttkrp(tt, [np.asarray(f, np.float64) for f in factors], 0)
    np.testing.assert_allclose(np.asarray(tot)[:tt.dims[0]], want, atol=0.6,
                               rtol=0.1)


def test_fused_vmem_gate():
    from splatt_tpu.ops.pallas_kernels import fused_t_vmem_ok

    small = [jnp.zeros((64, 16)) for _ in range(3)]
    assert fused_t_vmem_ok(small, 0, 64, 128)
    huge = [jax.ShapeDtypeStruct((4_000_000, 64), jnp.float32)
            for _ in range(3)]
    assert not fused_t_vmem_ok(huge, 0, 64, 4096)


def test_pallas_unfused_fallback_matches(monkeypatch):
    """When factors exceed the fused VMEM budget the Pallas engine falls
    back to the unfused (prod-precomputed) kernels — same answer."""
    import splatt_tpu.ops.pallas_kernels as pk

    tt = gen.fixture_tensor("med")
    opts = Options(block_alloc=BlockAlloc.ALLMODE, nnz_block=128,
                   val_dtype=np.float64)
    bs = BlockedSparse.from_coo(tt, opts)
    factors = make_factors(tt.dims)
    monkeypatch.setattr(pk, "fused_t_vmem_ok", lambda *a, **k: False)
    monkeypatch.setattr(pk, "fused_tg_vmem_ok", lambda *a, **k: False)
    # identical statics/avals were traced earlier in this file with the
    # fused branch; drop the cache so the monkeypatch is consulted
    mttkrp_blocked.clear_cache()
    from splatt_tpu.ops.mttkrp import engine_plan

    for mode in range(tt.nmodes):
        lay = bs.layout_for(mode)
        assert engine_plan(lay, factors, mode, "sorted_onehot",
                           "pallas_interpret") == "unfused_pallas"
        want = np_mttkrp(tt, factors, mode)
        got = mttkrp_blocked(lay, factors, mode,
                             path="sorted_onehot", impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(got), want, atol=TOL,
                                   err_msg=f"unfused fallback mode={mode}")


def test_unfused_pallas_bf16_accumulates_f32():
    from splatt_tpu.blocked import build_layout
    from splatt_tpu.ops.mttkrp import _scan_fused

    tt = gen.fixture_tensor("med")
    factors = [jnp.asarray(np.asarray(f), dtype=jnp.bfloat16)
               for f in make_factors(tt.dims)]
    lay = build_layout(tt, 0, block=128, val_dtype=jnp.bfloat16)
    W = -(-(tt.dims[0] + 1) // 8) * 8
    tot = _scan_fused(lay, factors, 0, W, accumulate=True, pallas=True,
                      interpret=True)
    assert tot.dtype == jnp.float32
    want = np_mttkrp(tt, [np.asarray(f, np.float64) for f in factors], 0)
    np.testing.assert_allclose(np.asarray(tot)[:tt.dims[0]], want, atol=0.6,
                               rtol=0.1)


def test_vmem_chunk_bounds():
    from splatt_tpu.ops.pallas_kernels import vmem_chunk

    assert vmem_chunk(64, 512, 128) >= 1          # typical config fits
    assert vmem_chunk(4096, 4096, 128) == 0       # pathological: fall back
    assert 1 <= vmem_chunk(8, 128, 8) <= 8


def test_pallas_bf16_accumulates_f32():
    """bf16 inputs through the Pallas kernels produce f32 outputs that
    match the f64 brute force at bf16 tolerance."""
    rng = np.random.default_rng(3)
    nb, B, S, R = 4, 128, 16, 8
    local = rng.integers(-1, S + 2, size=(nb, B)).astype(np.int32)
    prod16 = jnp.asarray(rng.random((nb, B, R)), dtype=jnp.bfloat16)
    got = onehot_reduce_sorted(jnp.asarray(local), prod16, S, interpret=True)
    assert got.dtype == jnp.float32
    want = _np_onehot_sorted(local, np.asarray(prod16, dtype=np.float64), S)
    np.testing.assert_allclose(np.asarray(got, dtype=np.float64), want,
                               atol=3e-2)
    got2 = onehot_reduce_full(jnp.asarray(local), prod16, S + 8,
                              interpret=True)
    assert got2.dtype == jnp.float32
    want2 = _np_onehot_sorted(local, np.asarray(prod16, dtype=np.float64),
                              S + 8).sum(axis=0)
    np.testing.assert_allclose(np.asarray(got2, dtype=np.float64), want2,
                               atol=3e-2)


def test_fused_tg_production_dims_interpret():
    """fused_tg index math at real NELL-2 production dims — block 4096,
    28928-lane padded gathers, rank 50 (the shapes whose Mosaic
    compiles crash for fused_t) — stays exact in interpret mode."""
    from splatt_tpu.blocked import build_layout
    from splatt_tpu.ops.mttkrp import mttkrp_stream
    from splatt_tpu.ops.pallas_kernels import fused_mttkrp_tg

    rng = np.random.default_rng(0)
    dims = (12092, 9184, 28818)
    nnz, rank = 4096, 50
    inds = np.stack([rng.integers(0, d, nnz) for d in dims]).astype(np.int64)
    from splatt_tpu.coo import SparseTensor

    tt = SparseTensor(inds=inds, vals=rng.standard_normal(nnz), dims=dims)
    fac = [jnp.asarray(rng.standard_normal((d, rank)).astype(np.float32))
           for d in dims]
    lay = build_layout(tt, 0, block=4096, val_dtype=np.float32)
    S = lay.seg_width
    parts = fused_mttkrp_tg(lay, fac, 0, S, accumulate=False,
                            interpret=True)
    idx = (np.asarray(lay.row_start)[:, None] + np.arange(S)).reshape(-1)
    out = np.zeros((dims[0] + S + 1, rank), np.float32)
    np.add.at(out, idx, np.asarray(parts).reshape(-1, rank))
    gold = np.asarray(mttkrp_stream(jnp.asarray(tt.inds),
                                    jnp.asarray(tt.vals), fac, 0, dims[0]))
    err = (np.abs(out[:dims[0]] - gold).max()
           / max(np.abs(gold).max(), 1e-9))
    assert err < 5e-5, err


def test_scan_target_knob_changes_chunking_not_results():
    """scan_target tunes the XLA engine's scan granularity (the
    hardware sweep knob; default from SPLATT_SCAN_TARGET_ELEMS) as a
    static jit argument — distinct values re-trace — without changing
    the computed MTTKRP."""
    from splatt_tpu.blocked import build_layout

    tt = gen.fixture_tensor("med")
    factors = make_factors(tt.dims)
    lay = build_layout(tt, 0, block=128, val_dtype=np.float64)
    want = np_mttkrp(tt, factors, 0)
    for target in (1 << 10, 1 << 16, 1 << 24):
        got = mttkrp_blocked(lay, factors, 0, path="sorted_onehot",
                             impl="xla", scan_target=target)
        np.testing.assert_allclose(np.asarray(got), want, atol=TOL,
                                   err_msg=str(target))
